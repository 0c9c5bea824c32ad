"""Gated DeltaNet ("gdn": a gated delta rule with ONE decay a head; "Gated
Delta Networks", arXiv:2412.06464, as Qwen3-Next runs it, `model_type:
qwen3_next`), x [B, S, d], u = ln1(x), no bias and no rotary embedding,
Hk = `gdn_key_heads` heads of q and k of size Dk = `gdn_key_dim`,
Hv = `gdn_value_heads` heads of v of size Dv = `gdn_value_dim`, Hv a multiple
of Hk:

`[q | k | v | z] = u W_qkvz` (d -> 2 Hk Dk + 2 Hv Dv, one array, in this
column order), `[b | a] = u W_ba` (d -> 2 Hv);
`[q | k | v] <- silu(causal_depthwise_conv1d([q | k | v]))`, width `gdn_conv`,
one call over the 2 Hk Dk + Hv Dv channels; value head j reads key head
`j // (Hv / Hk)`; per head `q <- q / |q|_2 * Dk^-0.5`,
`k <- k / |k|_2`; `beta = sigmoid(b)` and the log decay
`g = -exp(A_log[h]) * softplus(a + dt_bias[h])`, one number a value head,
float32; the recurrence of `ops/kda.py` (state [Dk, Dv] per value head,
float32) in its chunked form with ONE decay a head, `ops/gdn.py`'s
`gdn_chunked`: q and k go in unrepeated and g as [B, S, Hv], and for TPU the
scalar decay is one [C, C] mask on a plain `k k^T`, as `ssd_chunked` has
Mamba-2's (everywhere else it is the per-channel rule with equal channels,
which is the same numbers);
`o <- RMSNorm_head(o) * w * silu(z)` (norm over each head's Dv with one learned
scale [Dv], stored and started at 1 whatever `norm_zero_centred` says of the
stream's norms; the gate full-rank); `W_o: Hv Dv -> d`.

As Mamba-2 and KDA: the heads of a recurrence are replicated under `tp`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.mixers.base import (
    Leaf, Mixer, batch_sharded, constrainer, inv_softplus, joined, log_of_uniform, log_uniform, normal, ones, out_scale,
    proj_scale, rms_norm, stream_norm,
)
from ray_tpu.ops.delta_conv import delta_conv
from ray_tpu.ops.gdn import PAIR, gdn_chunked
from ray_tpu.util import tracing

# The fused q|k|v|z projection before its convolution, beta's and the decay's
# logits (d -> 2 Hv, one array), and the residual stream after the mixer.
GDN_QKVZ = "gdn_qkvz"
GDN_BA = "gdn_ba"
GDN_MIXED = "gdn_mixed"


def _widths(config):
    """(the q|k part, the v part): channels of the fused projection, of which q|k|v are convolved."""
    return 2 * config.gdn_key_heads * config.gdn_key_dim, config.gdn_value_heads * config.gdn_value_dim


def leaves(config):
    """The published kernels' layer (`fla.layers.gated_deltanet`) draws A
    uniform in (0, 16) and dt = softplus(dt_bias) log-uniform in [1e-3, 1e-1],
    one of each a value head; A here from [1, 16], as `mixers/kda.py` draws
    it (log A is a stored leaf).  The convolutions as Mamba-2's here."""
    c, (qk, v), heads = config, _widths(config), config.gdn_value_heads
    into = normal(proj_scale(c))
    return {
        "wqkvz": Leaf((c.d_model, qk + 2 * v), ("embed", None), into),
        "wba": Leaf((c.d_model, 2 * heads), ("embed", None), into),
        "conv_w": Leaf((qk + v, c.gdn_conv), (None, None), normal(c.gdn_conv ** -0.5)),
        "A_log": Leaf((heads,), (None,), log_of_uniform(1.0, 16.0)),
        "dt_bias": Leaf((heads,), (None,), inv_softplus(log_uniform(1e-3, 1e-1))),
        "norm": ones((c.gdn_value_dim,)),
        "wo": Leaf((v, c.d_model), (None, "embed"), normal(out_scale(c))),
    }


def validate(config) -> None:
    c = config
    if not (c.gdn_key_heads > 0 and c.gdn_key_dim > 0 and c.gdn_value_dim > 0 and c.gdn_value_heads > 0
            and c.gdn_value_heads % c.gdn_key_heads == 0):
        raise ValueError("a gdn layer needs gdn_key_heads, gdn_key_dim, gdn_value_dim and gdn_value_heads, "
                         "the value heads a multiple of the key heads")


def mix(x, layer_params, positions, config, rules, mesh=None, *, window=None, data=None, shared=None, emit=False):
    """The gdn half of a layer.  Its regions sit inside the two mixer scopes
    every layer has, as a KDA layer's do: `gdn/proj` (ln1, the fused q|k|v|z
    projection, beta's and the decay's logits, `wo`, the residual add),
    `gdn/conv` (`ops/delta_conv.py` `delta_conv`: the convolutions + SiLU and
    the L2 norms of q and k in one call, positions-major, read from the
    q | k | v columns of `gdn_qkvz` where they lie, on TPU the kernels
    `delta_conv_fwd` / `delta_conv_bwd`; the decay's activation, the gated
    per-head RMSNorm),
    `gdn/scan` (the chunked recurrence, `ops/gdn.py`).

    With the three `saved` residuals kept the backward runs none of the
    d-wide projections again (the convolution, the recurrence and the gated
    norm run again).  The recurrence names its own residuals
    (`KernelPair.residual_names`, `recurrence` below), and `kda` lists its
    op's in `saved`; this kind does NOT: with them kept (277 MB a layer at
    8,192 positions and 32 value heads) `qwen3-next-ep16-1chip.seq8k`'s step
    compiles to 15.659 GB, over the 15.6 GB ISSUE 64 set, and libtpu's
    rematerialization pass duplicates two matmuls (PERF.md section 7)."""
    del positions, window, data, shared, emit  # the decay carries position
    c, dt, p = config, config.dtype, layer_params["gdn"]
    f32 = jnp.float32
    constrain, sharded = constrainer(rules, mesh), batch_sharded(rules, mesh)
    (qk, v_width), heads = _widths(c), c.gdn_value_heads
    with tracing.scope("layer/attn_proj"):
        with tracing.scope("gdn/proj"):
            h = stream_norm(c, x, layer_params, "ln1")
            qkvz = checkpoint_name(jnp.einsum("bse,ef->bsf", h, p["wqkvz"].astype(dt)), GDN_QKVZ)
            ba = checkpoint_name(jnp.einsum("bse,ef->bsf", h, p["wba"].astype(dt)), GDN_BA)
        with tracing.scope("gdn/conv"):
            wq, wk, wv = jnp.split(p["conv_w"], (qk // 2, qk), axis=0)  # q's and k's go by head, [Hk, Dk, K]
            by_head = (c.gdn_key_heads, c.gdn_key_dim, -1)
            q, k, v = delta_conv(qkvz, wq.reshape(by_head), wk.reshape(by_head), wv, **sharded)  # z's columns stay behind
            v = v.reshape(*v.shape[:2], heads, c.gdn_value_dim)  # value head j reads key head j // (Hv / Hk)
            beta = jax.nn.sigmoid(ba[..., :heads].astype(f32))
            g = jax.nn.softplus(ba[..., heads:].astype(f32) + p["dt_bias"].astype(f32)) * -jnp.exp(p["A_log"].astype(f32))
    with tracing.scope("layer/attn_core"):
        o = gdn_chunked(q, k, v, g, beta, **sharded)
    with tracing.scope("layer/attn_proj"):
        with tracing.scope("gdn/conv"):
            z = qkvz[..., qk + v_width:].astype(f32).reshape(o.shape)
            o = (rms_norm(o, p["norm"], c.norm_eps) * jax.nn.silu(z)).astype(dt)  # over each head's own channels
        with tracing.scope("gdn/proj"):
            out = jnp.einsum("bsf,fe->bse", o.reshape(*o.shape[:2], v_width), p["wo"].astype(dt))
            return checkpoint_name(joined(c, x, out, constrain), GDN_MIXED), {}


MIXER = Mixer("gdn", "gdn_layers", "gdn", leaves, validate, mix, saved=(GDN_QKVZ, GDN_BA, GDN_MIXED),
              recurrence=PAIR.residual_names)
