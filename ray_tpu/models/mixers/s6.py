"""A Mamba-1 selective scan layer ("s6"; SambaY / Phi-4-mini-flash-reasoning,
`model_type: phi4flash`, arXiv:2507.06607; Mamba, arXiv:2312.00752),
x [B, S, d], u = ln1(x), `s6_inner` channels, state N = `s6_state`, dt's
projection of rank `s6_dt_rank` (0 = ceil(d / 16)):

`[x | z] = u W_in` (no bias); `x = silu(conv1d_causal_depthwise(x, width
s6_conv, with bias))`; `[dt_low | B | C] = x W_x` (dt_rank | N | N);
`dt = softplus(dt_low W_dt + b_dt)` [S, inner]; `A = -exp(A_log)` [inner, N];
`h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]`;
`y_t[c] = sum_n C_t[n] h_t[c, n] + D[c] x_t[c]` (`ops/selective_scan.py`,
in its chunked form); `out = (y * silu(z)) W_out`.  State, `dt * A`, its
exponentials and the softplus in float32.  At the layer `s6_memory_layer`,
`M = y` (with the D skip, before the gate) is handed on as `MEMORY`, which
every later "gmu" layer reads.

As Mamba-2: fsdp shards the projections over `embed`, tp replicates the
inner width.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.mixers.base import (
    Leaf, Mixer, batch_sharded, constrainer, inv_softplus, joined, log_arange, log_uniform, normal, ones,
    out_scale, proj_scale, stream_norm, zeros,
)
from ray_tpu.ops.selective_scan import PAIR, selective_scan
from ray_tpu.ops.ssm import causal_conv1d_silu
from ray_tpu.util import tracing

# What the memory layer hands on: its scan's output.
MEMORY = "memory"
# `W_in`'s output before its split into x and z, and the stream after `W_out`.
S6_IN_PROJ = "s6_in_proj"
S6_MIXED = "s6_mixed"


def dt_rank(config) -> int:
    return config.s6_dt_rank or -(-config.d_model // 16)


def leaves(config):
    """Mamba's own initial values (arXiv:2312.00752; `mamba_ssm`): A =
    -(1..N) in every channel, D = 1, a step dt = softplus(dt_bias) drawn
    log-uniform in [1e-3, 1e-1]; the convolution as Mamba-2's here."""
    c, inner, rank, state = config, config.s6_inner, dt_rank(config), config.s6_state
    return {
        "in_proj": Leaf((c.d_model, 2 * inner), ("embed", None), normal(proj_scale(c))),
        "conv_w": Leaf((inner, c.s6_conv), (None, None), normal(c.s6_conv ** -0.5)),
        "conv_b": zeros((inner,)),
        "x_proj": Leaf((inner, rank + 2 * state), (None, None), normal(inner ** -0.5)),
        "dt_proj": Leaf((rank, inner), (None, None), normal(rank ** -0.5)),
        "dt_bias": Leaf((inner,), (None,), inv_softplus(log_uniform(1e-3, 1e-1))),
        "A_log": Leaf((inner, state), (None, None), log_arange, draws=False),
        "D": ones((inner,)),
        "out_proj": Leaf((inner, c.d_model), (None, "embed"), normal(out_scale(c))),
    }


def validate(config) -> None:
    if not (config.s6_inner > 0 and config.s6_state > 0):
        raise ValueError("an s6 or gmu layer needs s6_inner and s6_state")


def mix(x, layer_params, positions, config, rules, mesh=None, *, window=None, data=None, shared=None, emit=False):
    """The Mamba-1 half of a layer.  Its regions sit inside the two mixer
    scopes every layer has: `s6/proj` (ln1, `W_in`, `W_x`, `W_dt` with the
    softplus, `W_out`, the residual add), `s6/conv` (convolution + SiLU, on TPU
    Mamba-2's kernels; the gate `y * silu(z)`), `s6/scan` (named in
    `ops/selective_scan.py`).

    With both `saved` residuals kept no d-wide projection runs again (`W_x`,
    `W_dt`, the convolution, the scan and the gate do)."""
    del positions, window, data, shared  # a recurrence needs none of them
    c, dt, p = config, config.dtype, layer_params["s6"]
    f32 = jnp.float32
    constrain, sharded = constrainer(rules, mesh), batch_sharded(rules, mesh)
    rank, n = dt_rank(c), c.s6_state
    with tracing.scope("layer/attn_proj"):
        with tracing.scope("s6/proj"):
            h = stream_norm(c, x, layer_params, "ln1")
            xz = checkpoint_name(jnp.einsum("bse,ef->bsf", h, p["in_proj"].astype(dt)), S6_IN_PROJ)
            xs, z = jnp.split(xz, 2, axis=-1)
        with tracing.scope("s6/conv"):
            xs = causal_conv1d_silu(xs, p["conv_w"], p["conv_b"], **sharded)
        with tracing.scope("s6/proj"):
            low = jnp.einsum("bsf,fr->bsr", xs, p["x_proj"].astype(dt))
            step = jnp.einsum("bsr,rf->bsf", low[..., :rank], p["dt_proj"].astype(dt), preferred_element_type=f32)
            step = jax.nn.softplus(step + p["dt_bias"].astype(f32))
    with tracing.scope("layer/attn_core"):
        y = selective_scan(xs, step, -jnp.exp(p["A_log"].astype(f32)), low[..., rank: rank + n],
                           low[..., rank + n:], p["D"], **sharded)
    with tracing.scope("layer/attn_proj"):
        with tracing.scope("s6/conv"):
            gated = (y.astype(f32) * jax.nn.silu(z.astype(f32))).astype(dt)
        with tracing.scope("s6/proj"):
            out = jnp.einsum("bsf,fe->bse", gated, p["out_proj"].astype(dt))
            return checkpoint_name(joined(c, x, out, constrain), S6_MIXED), ({MEMORY: y} if emit else {})


MIXER = Mixer("s6", "s6_layers", "s6", leaves, validate, mix, saved=(S6_IN_PROJ, S6_MIXED),
              recurrence=PAIR.residual_names, hands=(MEMORY,), source="s6_memory_layer")
