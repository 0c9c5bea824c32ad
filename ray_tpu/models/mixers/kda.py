"""Kimi Delta Attention ("kda": a gated delta rule with a decay per channel;
Kimi Linear, arXiv:2510.26692, `model_type: kimi_linear`), x [B, S, d],
u = ln1(x), no bias and no rotary embedding, H = `kda_heads` heads of size
D = `kda_head_dim`:

`[q | k | v] = silu(causal_depthwise_conv1d(u W_qkv))`, width `kda_conv`,
three convolutions over H*D channels each (one call over the 3*H*D); per
head `q <- q / |q|_2 * D^-0.5`, `k <- k / |k|_2`; the log decay
`g = -exp(A_log[h]) * softplus((u W_f_down) W_f_up + dt_bias)` per channel,
float32; `beta = sigmoid(u W_beta)` per head; the recurrence of
`ops/kda.py` (state [D, D] per head, float32) in its chunked form;
`o <- RMSNorm_head(o) * sigmoid((u W_g_down) W_g_up)` (norm over each
head's D with one learned scale [D]; both gates low-rank, d -> D -> H*D);
`W_o: H*D -> d`.

As Mamba-2: the heads of a recurrence are replicated under `tp`.
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
from ray_tpu.ops.kda import PAIR, kda_chunked
from ray_tpu.util import tracing

# The fused q|k|v projection before its convolution, the two low-rank gates'
# narrow halves with beta's logits (d -> 2 * D + H, one array), and the
# residual stream after the mixer.
KDA_QKV = "kda_qkv"
KDA_LOW = "kda_low"
KDA_MIXED = "kda_mixed"


def leaves(config):
    """The published kernels' initial values (`fla.layers.kda`): A drawn
    uniform in [1, 16] per head, dt = softplus(dt_bias) log-uniform in
    [1e-3, 1e-1] per channel; the convolutions as Mamba-2's here."""
    c, heads, dim = config, config.kda_heads, config.kda_head_dim
    inner, into, up = heads * dim, normal(proj_scale(c)), normal(dim ** -0.5)
    return {
        "wqkv": Leaf((c.d_model, 3 * inner), ("embed", None), into),
        "conv_w": Leaf((3 * inner, c.kda_conv), (None, None), normal(c.kda_conv ** -0.5)),
        "f_down": Leaf((c.d_model, dim), ("embed", None), into),
        "f_up": Leaf((dim, inner), (None, None), up),
        "g_down": Leaf((c.d_model, dim), ("embed", None), into),
        "g_up": Leaf((dim, inner), (None, None), up),
        "w_beta": Leaf((c.d_model, heads), ("embed", None), into),
        "A_log": Leaf((heads,), (None,), log_of_uniform(1.0, 16.0)),
        "dt_bias": Leaf((inner,), (None,), inv_softplus(log_uniform(1e-3, 1e-1))),
        "norm": ones((dim,)),
        "wo": Leaf((inner, c.d_model), (None, "embed"), normal(out_scale(c))),
    }


def validate(config) -> None:
    if not (config.kda_heads > 0 and config.kda_head_dim > 0):
        raise ValueError("a kda layer needs kda_heads and kda_head_dim")


def mix(x, layer_params, positions, config, rules, mesh=None, *, window=None, data=None, shared=None, emit=False):
    """The KDA half of a layer.  Its regions sit inside the two mixer scopes
    every layer has, as a Mamba-2 layer's do: `kda/proj` (ln1, the fused
    q|k|v projection, both low-rank gates, beta, `wo`, the residual add),
    `kda/conv` (`ops/delta_conv.py` `delta_conv`: the convolutions + SiLU and
    the L2 norms of q and k in one call, positions-major, on TPU the kernels
    `delta_conv_fwd` / `delta_conv_bwd`; the decay's activation, the gated
    per-head RMSNorm),
    `kda/scan` (the chunked recurrence, named in `ops/kda.py`).

    With the `saved` residuals kept the backward runs none of the d-wide
    projections again, and not the recurrence's forward kernel: the op names
    its output and the states its backward starts from
    (`KernelPair.residual_names`; o float32 and the state that enters each
    pair of chunks, 553 MB a layer at 16,384 positions and 32 heads), so the
    gated norm's recompute reads the o the first call wrote (the gates'
    narrow-to-wide halves, the convolution and the gated norm run again)."""
    del positions, window, data, shared, emit  # the decay carries position
    c, dt, p = config, config.dtype, layer_params["kda"]
    f32 = jnp.float32
    constrain, sharded = constrainer(rules, mesh), batch_sharded(rules, mesh)
    heads, dim = c.kda_heads, c.kda_head_dim
    inner = heads * dim
    with tracing.scope("layer/attn_proj"):
        with tracing.scope("kda/proj"):
            h = stream_norm(c, x, layer_params, "ln1")
            qkv = checkpoint_name(jnp.einsum("bse,ef->bsf", h, p["wqkv"].astype(dt)), KDA_QKV)
            narrow = jnp.concatenate([p["f_down"], p["g_down"], p["w_beta"]], axis=-1).astype(dt)
            low = checkpoint_name(jnp.einsum("bse,ef->bsf", h, narrow), KDA_LOW)
            decay_in = jnp.einsum("bsr,rf->bsf", low[..., :dim], p["f_up"].astype(dt))
            gate_in = jnp.einsum("bsr,rf->bsf", low[..., dim: 2 * dim], p["g_up"].astype(dt))
        with tracing.scope("kda/conv"):
            wq, wk, wv = jnp.split(p["conv_w"], 3, axis=0)  # each [H * D, K]: q's and k's go by head
            q, k, v = delta_conv(qkv, wq.reshape(heads, dim, -1), wk.reshape(heads, dim, -1), wv, **sharded)
            v = v.reshape(*v.shape[:2], heads, dim)
            step = jax.nn.softplus(decay_in.astype(f32) + p["dt_bias"].astype(f32))
            g = step.reshape(*step.shape[:2], heads, dim) * -jnp.exp(p["A_log"].astype(f32))[:, None]
            beta = jax.nn.sigmoid(low[..., 2 * dim:].astype(f32))
    with tracing.scope("layer/attn_core"):
        o = kda_chunked(q, k, v, g, beta, **sharded)
    with tracing.scope("layer/attn_proj"):
        with tracing.scope("kda/conv"):
            gate = jax.nn.sigmoid(gate_in.astype(f32)).reshape(o.shape)
            o = (rms_norm(o, p["norm"], c.norm_eps) * gate).astype(dt)  # over each head's own channels
        with tracing.scope("kda/proj"):
            out = jnp.einsum("bsf,fe->bse", o.reshape(*o.shape[:2], inner), p["wo"].astype(dt))
            return checkpoint_name(joined(c, x, out, constrain), KDA_MIXED), {}


MIXER = Mixer("kda", "kda_layers", "kda", leaves, validate, mix, saved=(KDA_QKV, KDA_LOW, KDA_MIXED, *PAIR.residual_names),
              recurrence=PAIR.residual_names)
