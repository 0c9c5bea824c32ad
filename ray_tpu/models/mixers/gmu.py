"""A Gated Memory Unit ("gmu"; SambaY, arXiv:2507.06607), x [B, S, d],
u = ln1(x): the memory M an s6 layer handed on (`s6.MEMORY`), gated by this
layer's own projection of the stream:

`out = (M * silu(u W_1)) W_2`, `W_1: d -> s6_inner`, `W_2: s6_inner -> d`,
no bias.

It replicates its inner width under `tp`, as s6 does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.mixers import s6
from ray_tpu.models.mixers.base import Leaf, Mixer, constrainer, joined, normal, out_scale, proj_scale, stream_norm
from ray_tpu.util import tracing

# `W_1`'s output, and the stream after `W_2`.
GMU_GATE = "gmu_gate"
GMU_MIXED = "gmu_mixed"


def leaves(config):
    c = config
    return {
        "w1": Leaf((c.d_model, c.s6_inner), ("embed", None), normal(proj_scale(c))),
        "w2": Leaf((c.s6_inner, c.d_model), (None, "embed"), normal(out_scale(c))),
    }


def mix(x, layer_params, positions, config, rules, mesh=None, *, window=None, data=None, shared=None, emit=False):
    """The GMU half of a layer.  All of it is `gmu` inside `layer/attn_proj`
    (the layer has no core)."""
    del positions, window, data, emit
    c, dt, p = config, config.dtype, layer_params["gmu"]
    f32 = jnp.float32
    with tracing.scope("layer/attn_proj"), tracing.scope("gmu"):
        h = stream_norm(c, x, layer_params, "ln1")
        gate = checkpoint_name(jnp.einsum("bse,ef->bsf", h, p["w1"].astype(dt)), GMU_GATE)
        gated = (shared[s6.MEMORY].astype(f32) * jax.nn.silu(gate.astype(f32))).astype(dt)
        out = jnp.einsum("bsf,fe->bse", gated, p["w2"].astype(dt))
        return checkpoint_name(joined(c, x, out, constrainer(rules, mesh)), GMU_MIXED), {}


MIXER = Mixer("gmu", "gmu_layers", "gmu", leaves, s6.validate, mix, saved=(GMU_GATE, GMU_MIXED), reads=(s6.MEMORY,))
