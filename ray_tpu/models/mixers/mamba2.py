"""A Mamba-2 selective state-space layer ("mamba"; Granite 4.0-H,
`modeling_granitemoehybrid.py`; Nemotron-H, `modeling_nemotron_h.py`; Mamba-2
/ SSD, arXiv:2405.21060), x [B, S, d], u = ln1(x), no bias anywhere but the
convolution's:

`d_inner = ssm_heads * ssm_head_dim` (NOT an `expand` times d: Nemotron-3-Nano
has 64 x 64 = 4096 on a 2688-wide stream), state N = `ssm_state`, G =
`ssm_groups` groups of B and C (1: Granite; 8: Nemotron-3-Nano):
`in_proj: d -> [z: d_inner | xBC: d_inner + 2GN | dt: ssm_heads]`;
`xBC = silu(causal_depthwise_conv1d(xBC, width ssm_conv, with bias))`,
split into x [S, heads, head_dim], B [S, G, N], C [S, G, N];
`dt = softplus(dt + dt_bias)` per head; `A = -exp(A_log)` per head (a
scalar).  Per head, which reads group `head // (heads / G)`, with state H_t in
R^{head_dim x N}:
`H_t = exp(dt_t A) H_{t-1} + dt_t x_t (outer) B_t`, `y_t = H_t C_t + D x_t`
(`ops/ssm.py`, in its chunked form).  Then
`y = RMSNorm(y * silu(z))` per group of d_inner / G channels (one group: over
all of d_inner) with one learned scale [d_inner], and
`out_proj: d_inner -> d`.

The mixer's inner width carries no logical axis: `fsdp` shards the two
projections over `embed`, and under `tp` the scan's heads are REPLICATED over
`tensor` (its FFN still shards), not refused.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.mixers.base import (
    Leaf, Mixer, batch_sharded, constrainer, inv_softplus, joined, log_arange, log_uniform, normal, ones,
    out_scale, proj_scale, rms_norm, stream_norm, zeros,
)
from ray_tpu.ops.ssm import SCAN, causal_conv1d_silu, ssd_chunked
from ray_tpu.util import tracing

# `in_proj`'s output before its split into z, x|B|C and dt, and the residual
# stream after the mixer, as it enters the FFN half.
SSM_IN_PROJ = "ssm_in_proj"
SSM_MIXED = "ssm_mixed"


def leaves(config):
    """Mamba-2's own initial values (arXiv:2405.21060; `mamba_ssm`): A =
    -(1..heads), D = 1, and a step dt = softplus(dt_bias) drawn log-uniform
    in [1e-3, 1e-1] (dt_bias is its inverse softplus)."""
    c, heads, inner = config, config.ssm_heads, config.ssm_heads * config.ssm_head_dim
    conv = inner + 2 * c.ssm_groups * c.ssm_state  # x | B | C, the channels the convolution runs over
    return {
        "in_proj": Leaf((c.d_model, inner + conv + heads), ("embed", None), normal(proj_scale(c))),
        "conv_w": Leaf((conv, c.ssm_conv), (None, None), normal(c.ssm_conv ** -0.5)),
        "conv_b": zeros((conv,)),
        "dt_bias": Leaf((heads,), (None,), inv_softplus(log_uniform(1e-3, 1e-1))),
        "A_log": Leaf((heads,), (None,), log_arange, draws=False),
        "D": ones((heads,)),
        "norm": ones((inner,)),
        "out_proj": Leaf((inner, c.d_model), (None, "embed"), normal(out_scale(c))),
    }


def validate(config) -> None:
    if not (config.ssm_heads > 0 and config.ssm_head_dim > 0 and config.ssm_state > 0):
        raise ValueError("a mamba layer needs ssm_heads, ssm_head_dim and ssm_state")
    if config.ssm_groups < 1 or config.ssm_heads % config.ssm_groups:
        raise ValueError(f"ssm_groups={config.ssm_groups} does not divide ssm_heads={config.ssm_heads}")


def mix(x, layer_params, positions, config, rules, mesh=None, *, window=None, data=None, shared=None, emit=False):
    """The Mamba-2 half of a layer.  Its regions sit INSIDE the two mixer
    scopes every layer has, so `layer/attn_proj` stays "the mixer's
    projections" and `layer/attn_core` "the mixer's core": `ssm/proj` (ln1,
    in_proj, out_proj, the residual add), `ssm/conv` (convolution + SiLU, one
    unit with its own backward: on TPU the kernels `ssm_conv_fwd` /
    `ssm_conv_bwd`; softplus; the gated RMSNorm, float32 over the scan's bf16
    output), `ssm/scan` (the SSD, named in `ops/ssm.py`).

    With both `saved` residuals kept the backward runs neither projection's
    forward again: `in_proj`'s consumers start from the saved array, and
    `out_proj`'s forward fed only the FFN half, which starts from the saved
    stream (its backward needs `y`, so convolution, scan and gated norm still
    run again; `ln1` too, for `in_proj`'s weight gradient)."""
    del positions, window, data, shared, emit  # a recurrence needs no positions
    c, dt, ssm = config, config.dtype, layer_params["ssm"]
    constrain, sharded = constrainer(rules, mesh), batch_sharded(rules, mesh)
    heads, inner, n, groups = c.ssm_heads, c.ssm_heads * c.ssm_head_dim, c.ssm_state, c.ssm_groups
    with tracing.scope("layer/attn_proj"):
        with tracing.scope("ssm/proj"):
            h = stream_norm(c, x, layer_params, "ln1")
            zxbcdt = jnp.einsum("bse,ef->bsf", h, ssm["in_proj"].astype(dt))
            zxbcdt = checkpoint_name(zxbcdt, SSM_IN_PROJ)
            z, xbc, step = jnp.split(zxbcdt, [inner, 2 * inner + 2 * groups * n], axis=-1)
        with tracing.scope("ssm/conv"):
            xbc = causal_conv1d_silu(xbc, ssm["conv_w"], ssm["conv_b"], **sharded)
            step = jax.nn.softplus(step.astype(jnp.float32) + ssm["dt_bias"].astype(jnp.float32))
            xs, b_in, c_out = jnp.split(xbc, [inner, inner + groups * n], axis=-1)
            if groups > 1:  # [B, S, G, N]: heads g * heads / G .. read group g
                b_in, c_out = (a.reshape(*a.shape[:2], groups, n) for a in (b_in, c_out))
    with tracing.scope("layer/attn_core"):
        y = ssd_chunked(
            xs.reshape(*xs.shape[:2], heads, c.ssm_head_dim), step,
            -jnp.exp(ssm["A_log"].astype(jnp.float32)), b_in, c_out, ssm["D"], **sharded,
        )
    with tracing.scope("layer/attn_proj"):
        with tracing.scope("ssm/conv"):
            y = y.reshape(*y.shape[:2], inner)
            gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
            if groups > 1:  # each group of inner / G channels has its own statistics, the one scale is [inner]
                by_group = gated.reshape(*gated.shape[:2], groups, inner // groups)
                by_group = by_group * jax.lax.rsqrt(jnp.mean(jnp.square(by_group), axis=-1, keepdims=True) + c.norm_eps)
                y = (by_group.reshape(gated.shape) * ssm["norm"].astype(jnp.float32)).astype(dt)
            else:
                y = rms_norm(gated, ssm["norm"], c.norm_eps).astype(dt)  # one group: over all of d_inner
        with tracing.scope("ssm/proj"):
            out = jnp.einsum("bsf,fe->bse", y, ssm["out_proj"].astype(dt))
            return checkpoint_name(joined(c, x, out, constrain), SSM_MIXED), {}


MIXER = Mixer("mamba", "mamba_layers", "ssm", leaves, validate, mix, saved=(SSM_IN_PROJ, SSM_MIXED),
              recurrence=SCAN.residual_names)
