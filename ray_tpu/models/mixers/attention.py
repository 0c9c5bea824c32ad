"""Causal softmax attention ("attention"), x [B, S, d], u = ln1(x):

q/k/v projections (GQA: `n_heads` q heads, `n_kv_heads` k and v heads of
`head_dim`), no bias; optionally (`qk_norm`) an RMSNorm with a learned scale
over the WHOLE projected q and k (True: OLMoE, OLMo 2; scales [heads,
head_dim]) or over each HEAD of them ("per_head": the Qwen3 family's; one
scale [head_dim] for q's heads, one for k's; `1 + w` under `norm_zero_centred`);
the rotary embedding of the layer (`layer_ropes`: ops/rotary.py `Rope`) or else
the model's (`rope_theta`, on the first `rotary_dim` dims of each head where
the model states a rotated PART, the rest of the head passing as it is; none
when `rope_theta` is None); causal softmax of `q k^T * attention_scale`
(`head_dim ** -0.5` when None), with a window w (`layer_windows`) query i
seeing keys i - w + 1 .. i; with `attn_output_gate` (Qwen3-Next) q's
projection is twice as wide, each head's columns its q and then its gate, and
the core's output is multiplied by `sigmoid(gate)` (the gate is a named
residual, `attn_gate`, so `qkv_attn` runs `wq` once); output projection (in
such a model the gate, `wo` and the residual add lie under one more scope,
`attn/gate`).  In a model that has
`layer_windows` the core lies under one more scope, `attn/window` or
`attn/full`, which tells a trace the two kinds of layer apart.

In a block-diffusion model (`diffusion_block` B: generation by masked
diffusion inside blocks of B tokens, left to right between blocks) the mask
is not causal: the core takes `ops.attention.BlockDiffusion(B, noisy_rows)`
under one more scope, `attn/block_diffusion`.  `noisy_rows` 0 is the plain
forward, block-causal over one copy of the sequence (what a sampler's pass
over the finished blocks and the current one is); `noisy_rows` S is the
training step's `[x_t ‖ x_0]`, 2S rows whose `positions` are `[0..S-1,
0..S-1]`: a noisy token rotates as the clean token it stands for.

The core dispatches to the pallas flash kernel when lowered for TPU (under
shard_map when there is a mesh), the XLA forms otherwise
(ray_tpu.ops.attention), or ring attention when the mesh has a nontrivial
`seq` axis.  The ring takes no `window`, no rope of a layer's own, no
`attention_scale`, no per-head norm and no block-diffusion mask: `placement`
refuses the pairing by name when configuration, rules and mesh first meet.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.mixers.base import (
    Leaf, Mixer, constrainer, fitting_axis, joined, may_ring, norm_scale, normal, out_scale, proj_scale,
    refuse_attn_bias, ring_axis, rms_norm, stream_norm,
)
from ray_tpu.ops.attention import BlockDiffusion, dot_product_attention
from ray_tpu.ops.rotary import Rope, apply_rope
from ray_tpu.util import tracing

ATTN_GATE = "attn_gate"  # the output gate's logits, the second half of each head's columns of `wq`


def leaves(config):
    c, hd = config, config.head_dim
    q, kv = ("embed", "heads", "head_dim"), ("embed", "kv_heads", "head_dim")
    out = {
        "wq": Leaf((c.d_model, c.n_heads, 2 * hd if c.attn_output_gate else hd), q, normal(proj_scale(c))),
        "wk": Leaf((c.d_model, c.n_kv_heads, hd), kv, normal(proj_scale(c))),
        "wv": Leaf((c.d_model, c.n_kv_heads, hd), kv, normal(proj_scale(c))),
        "wo": Leaf((c.n_heads, hd, c.d_model), ("heads", "head_dim", "embed"), normal(out_scale(c))),
    }
    if c.qk_norm == "per_head":
        out["q_norm"] = norm_scale(c, (hd,), ("head_dim",))
        out["k_norm"] = norm_scale(c, (hd,), ("head_dim",))
    elif c.qk_norm:
        out["q_norm"] = norm_scale(c, (c.n_heads, hd), ("heads", "head_dim"))
        out["k_norm"] = norm_scale(c, (c.n_kv_heads, hd), ("kv_heads", "head_dim"))
    return out


def placement(config, rules, mesh) -> None:
    """What the sequence-parallel ring does not take (ops/ring_attention.py:
    full causal blocks at `head_dim ** -0.5`, one rope for the model, heads
    it may split)."""
    c = config
    if not may_ring(rules, mesh):
        return
    has = [name for name, there in (
        ("window (layer_windows)", c.layer_windows is not None and any(w is not None for w in c.layer_windows)),
        ("rope of a layer's own (layer_ropes)", c.layer_ropes is not None),
        ("attention_scale", c.attention_scale is not None),
        ("per-head QK-norm (qk_norm 'per_head')", c.qk_norm == "per_head"),
        ("block-diffusion mask (diffusion_block)", c.diffusion_block is not None),
    ) if there]
    if has:
        raise ValueError("ring attention takes no " + ", no ".join(has)
                         + f": these rules shard act_seq over {rules.get('act_seq')!r}")


def mix(x, layer_params, positions, config, rules, mesh=None, *, window=None, data=None, shared=None, emit=False,
        rope=None, noisy_rows=None):
    """The attention half of a layer; `rope` is the layer's own rotary
    embedding (None: the model's `rope_theta`); `noisy_rows` (a
    block-diffusion model's alone) how many of x's first rows are the noisy
    copy: 0 in the plain forward, half of them in a training step."""
    del data, shared, emit
    c, dt, p = config, config.dtype, layer_params["attn"]
    constrain = constrainer(rules, mesh)

    # The scopes name each region in the compiled step's op metadata, which
    # is what a device trace can tell fusions apart by (PERF.md section 3).
    with tracing.scope("layer/attn_proj"):
        h = stream_norm(c, x, layer_params, "ln1")
        q = jnp.einsum("bse,ehd->bshd", h, p["wq"].astype(dt))
        if c.attn_output_gate:
            q, gate = q[..., :c.head_dim], checkpoint_name(q[..., c.head_dim:], ATTN_GATE)
        kk = jnp.einsum("bse,ehd->bshd", h, p["wk"].astype(dt))
        vv = jnp.einsum("bse,ehd->bshd", h, p["wv"].astype(dt))
        q = constrain(q, ("act_batch", "act_seq", "act_heads", "act_head_dim"))
        kk = constrain(kk, ("act_batch", "act_seq", "act_kv_heads", "act_head_dim"))
        if c.qk_norm == "per_head":
            q = rms_norm(q, p["q_norm"], c.norm_eps, zero_centred=c.norm_zero_centred)
            kk = rms_norm(kk, p["k_norm"], c.norm_eps, zero_centred=c.norm_zero_centred)
        elif c.qk_norm:
            # over the WHOLE projection: heads * head_dim is one vector per position
            q = rms_norm(q, p["q_norm"], c.norm_eps, axis=(-2, -1), zero_centred=c.norm_zero_centred)
            kk = rms_norm(kk, p["k_norm"], c.norm_eps, axis=(-2, -1), zero_centred=c.norm_zero_centred)
        if rope is None and c.rope_theta is not None:
            rope = Rope(c.rope_theta, rotary_dim=c.rotary_dim)
        if rope is not None:
            q = apply_rope(q, positions, rope)
            kk = apply_rope(kk, positions, rope)
        q = checkpoint_name(q, "q")
        kk = checkpoint_name(kk, "k")
        vv = checkpoint_name(vv, "v")
    batch_axes = head_ax = None
    if rules is not None:
        batch_axes = rules.get("act_batch")
        head_ax = fitting_axis(rules.get("act_heads"), mesh, q.shape[2])
        if head_ax is not None and kk.shape[2] % mesh.shape[head_ax] != 0:
            head_ax = None  # GQA kv heads don't divide: replicate heads
    seq_axis = ring_axis(rules, mesh, q)
    # the layer's kind, named only in a model that has two
    kind = (contextlib.nullcontext() if c.layer_windows is None
            else tracing.scope("attn/full" if window is None else "attn/window"))
    masked = {} if window is None else {"window": window}
    if c.diffusion_block is not None:  # the mask that takes the causal one's place, and its name in a trace
        kind = tracing.scope("attn/block_diffusion")
        masked = {"block_diffusion": BlockDiffusion(c.diffusion_block, noisy_rows or 0)}
    with tracing.scope("layer/attn_core"), kind:
        if seq_axis is not None:
            # Sequence parallelism: activations are seq-sharded, so full
            # attention would force XLA to all-gather the sequence.  Ring
            # attention keeps KV rotating over ICI instead
            # (ops/ring_attention.py; SURVEY.md §5.7 — novel, no reference
            # counterpart).
            from ray_tpu.ops.ring_attention import ring_attention_sharded

            attn = ring_attention_sharded(
                q, kk, vv, mesh,
                seq_axis=seq_axis,
                batch_axes=batch_axes,
                head_axis=head_ax,
                causal=True,
            )
        else:
            attn = dot_product_attention(
                q, kk, vv, causal=True, scale=c.attention_scale, impl=c.attention_impl,
                mesh=mesh if rules is not None else None,
                batch_axes=batch_axes, head_axis=head_ax, **masked,
            )
    # `attn/gate` holds the gate AND `wo`: XLA fuses the gate's pass into the projection's operand, and a fusion
    # carries one name (its root's), so a scope around the gate alone would reach no op of a trace
    with tracing.scope("layer/attn_proj"), (tracing.scope("attn/gate") if c.attn_output_gate else contextlib.nullcontext()):
        if c.attn_output_gate:
            attn = (attn * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dt)
        attn_out = jnp.einsum("bshd,hde->bse", attn, p["wo"].astype(dt))
        return joined(c, x, attn_out, constrain), {}


MIXER = Mixer("attention", "layers", "attn", leaves, refuse_attn_bias, mix, saved=(ATTN_GATE,), rotates=True,
              placement=placement, flash_heads=lambda c: (c.head_dim, c.head_dim))
