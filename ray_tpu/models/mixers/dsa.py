"""Latent attention's two further kinds, as dots3-note-prev stacks them one
full layer to three sliding ones; x [B, S, d], u = ln1(x), no bias but the
indexer's LayerNorm.  Both are mixers/mla.py's layer (its `project`) with

- the RESCALE of the two normed latents, `c_q = (d / q_rank)^0.5 RMSNorm(u
  W_qa)` and `c = (d / kv_rank)^0.5 RMSNorm(c)` (LongCat-Flash's
  `mla_scale_q_lora` / `mla_scale_kv_lora`),
- the layer's own rotary embedding (`layer_ropes`) on the rope parts, and
- a HEAD-WISE output gate (Gated Attention, arXiv:2505.06708): `g = sigmoid(u
  W_g)`, one number a head and position (`W_g [d, H]`, a leaf of its own), and
  `out = sum_h g[t, h] * o[t, h] W_o[h]` (gate, `W_o` and the residual add
  under `attn/gate`, as mixers/attention.py's element-wise gate has them).

"mla_window" reads a SECOND geometry (`TransformerConfig.window_latent`, an
`mla.Latent`: its own heads, ranks and head sizes) and runs the causal core
under the layer's window (`layer_windows`: query t sees keys t - w + 1 .. t)
through `dot_product_attention`, the flash kernels' windowed walk in a step
lowered for TPU, under the scope `mla/window`.

"mla_sparse" reads "mla"'s own fields and attends a LEARNED selection
(DeepSeek-V3.2-Exp's DSA; ops/sparse_attention.py): an indexer of
`index_heads` heads of `index_head_dim`, `q^I = sg(c_q) W^I_q`, ONE key a
position `k^I = LayerNorm(sg(u) W^I_k)` (scale and bias), the first
`qk_rope_head_dim` dims of both rotated by the layer's rope, `w = sg(u) W^I_w
* index_heads^-0.5 * index_head_dim^-0.5` in float32; `I[t, s] = sum_j w[t, j]
relu(q^I[t, j] . k^I[s])`; `S_t` the `min(t + 1, index_topk)` causal keys of
largest `I[t, .]`; the core `o[t, h] = softmax_{s in S_t}(q[t, h] . k[s, h] *
(nope + rope)^-0.5) v[s, h]` over exactly that set; and the indexer's loss
`mean_t KL(p_t || softmax_{S_t}(I[t, .]))`, `p_t` the head-summed attention
probabilities over `S_t`, L1-normalised, a constant.  sg = `stop_gradient`:
the indexer's five leaves get gradient from that term alone and every other
leaf none from it (models/lm.py adds the layers' terms to the objective).
Scopes inside `layer/attn_core`: `dsa/index` (the indexer's projections and
scores), `dsa/topk`, `dsa/attn` (the core, both directions), `dsa/kl` (the
target's `q k^T` and the loss).  The layer REPORTS two numbers (`Mixer.
reports`): its loss term and the pairs it selected.

A model that holds a SHARE of its heads (`TransformerConfig.head_share` =
(index, of): one rank's share of a tensor-parallel attention) holds, of each
kind's H heads, `H / of` from `index * H / of` on: `W_qb`, `W_kvb`, `W_g` and
`W_o` are that range of the whole model's draw, the layer computes its own
heads' part of `W_o`'s sum (and the target from its own heads), and `W_qa`,
`W_kva`, the norms and the indexer are whole, so every share selects the same
keys.  A share runs on one device.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.mixers import mla
from ray_tpu.models.mixers.base import Leaf, Mixer, constrainer, joined, layer_norm, normal, ones, proj_scale, stream_norm, zeros
from ray_tpu.ops import sparse_attention as sa
from ray_tpu.ops.attention import dot_product_attention
from ray_tpu.ops.rotary import apply_rope
from ray_tpu.util import tracing

HEAD_GATE = "attn_head_gate"  # the gate's logits [B, S, H]
INDEX_KL = "dsa_index_kl"  # what a sparse layer reports: its term of the objective, nats
SELECTED_PAIRS = "dsa_selected_pairs"  # and the (query, key) pairs it selected, a sequence


def _held(config, g: mla.Latent) -> mla.Latent:
    return g if config.head_share is None else g.held(*config.head_share)


def sparse_latent(config) -> mla.Latent:
    return _held(config, mla.latent_of(config))


def window_latent(config) -> mla.Latent:
    return _held(config, config.window_latent)


def _gated_leaves(config, g: mla.Latent):
    return {**mla.latent_leaves(config, g, rescale=True),
            "w_gate": Leaf((config.d_model, g.heads), ("embed", "heads"), g.of_heads(proj_scale(config), -1))}


def sparse_leaves(config):
    c, g = config, sparse_latent(config)
    return {
        **_gated_leaves(c, g),
        "wi_q": Leaf((g.q_rank, c.index_heads, c.index_head_dim), (None, None, None), normal(proj_scale(c))),  # of the rescaled q latent
        "wi_k": Leaf((c.d_model, c.index_head_dim), ("embed", None), normal(proj_scale(c))),
        "ki_norm": ones((c.index_head_dim,)),
        "ki_norm_b": zeros((c.index_head_dim,)),
        "wi_w": Leaf((c.d_model, c.index_heads), ("embed", None), normal(proj_scale(c))),
    }


def window_leaves(config):
    return _gated_leaves(config, window_latent(config))


def _validate(config, g, kind: str) -> None:
    c = config
    if g is None or g.q_rank is None:
        raise ValueError(f"an {kind} layer needs its latent geometry, q low-rank (q_lora_rank) as the rescale and the indexer read it")
    mla.validate_latent(g, None)
    if g.rope <= 0 or g.rope % 2:
        raise ValueError(f"an {kind} layer rotates an even qk_rope_head_dim by the layer's rope (layer_ropes), got {g.rope}")
    if c.head_share is not None:
        index, of = c.head_share
        if not (0 <= index < of and g.heads % of == 0):
            raise ValueError(f"head_share={c.head_share} is no share (index, of) of an {kind} layer's {g.heads} heads")
    if c.attn_bias:
        raise ValueError(f"attn_bias is the differential kinds' alone: an {kind} layer has no bias")


def validate_sparse(config) -> None:
    c = config
    _validate(c, mla.latent_of(c), "mla_sparse")
    if not (c.index_heads > 0 and c.index_head_dim >= c.qk_rope_head_dim and c.index_topk > 0):
        raise ValueError(f"an mla_sparse layer needs index_heads, index_topk and an index_head_dim no narrower than the rope "
                         f"part, got {c.index_heads}, {c.index_topk}, {c.index_head_dim}")


def validate_window(config) -> None:
    _validate(config, config.window_latent, "mla_window")


def placement(config, rules, mesh) -> None:
    if config.head_share is not None and rules is not None and mesh is not None and mesh.size > 1:
        raise ValueError("head_share is one rank's share of the heads: it runs on one device, not beside a mesh")


def _gated_out(c, x, attn, gate, p, constrain):
    """The gate, `wo` and the residual add (one name: XLA fuses the gate's pass into the projection's operand)."""
    with tracing.scope("layer/attn_proj"), tracing.scope("attn/gate"):
        attn = (attn * jax.nn.sigmoid(gate.astype(jnp.float32))[..., None]).astype(c.dtype)
        out = jnp.einsum("bshd,hde->bse", attn, p["wo"].astype(c.dtype))
        return checkpoint_name(joined(c, x, out, constrain), mla.MLA_MIXED)


def _projected(c, g, p, x, layer_params, positions, rope, constrain):
    with tracing.scope("layer/attn_proj"), tracing.scope("mla/proj"):
        h = stream_norm(c, x, layer_params, "ln1")
        q, kk, vv, c_q = mla.project(c, g, p, h, positions, rope, constrain, rescale=True)
        gate = checkpoint_name(jnp.einsum("bse,eh->bsh", h, p["w_gate"].astype(c.dtype)), HEAD_GATE)
    return h, q, kk, vv, c_q, gate


def mix_window(x, layer_params, positions, config, rules, mesh=None, *, window=None, data=None, shared=None, emit=False,
               rope=None):
    """The sliding kind's half of a layer: the second geometry, the layer's window and rope."""
    del data, shared, emit
    c, p, g = config, layer_params["mla_window"], window_latent(config)
    constrain = constrainer(rules, mesh)
    _, q, kk, vv, _, gate = _projected(c, g, p, x, layer_params, positions, rope, constrain)
    batch_axes, head_ax = mla.local_heads(rules, mesh, q)
    with tracing.scope("layer/attn_core"), tracing.scope("mla/window"):
        attn = dot_product_attention(
            q, kk, vv, causal=True, scale=g.qk ** -0.5, impl=c.attention_impl, mesh=mesh if rules is not None else None,
            batch_axes=batch_axes, head_axis=head_ax, **({} if window is None else {"window": window}))
    return _gated_out(c, x, attn, gate, p, constrain), {}


def mix_sparse(x, layer_params, positions, config, rules, mesh=None, *, window=None, data=None, shared=None, emit=False,
               rope=None):
    """The full kind's half of a layer: the indexer, the selection, the core
    over it, the indexer's loss (module docstring)."""
    del data, shared, emit
    if window is not None:
        raise ValueError("an mla_sparse layer selects its keys: it takes no window (layer_windows)")
    c, dt, p, g = config, config.dtype, layer_params["mla_sparse"], sparse_latent(config)
    constrain = constrainer(rules, mesh)
    h, q, kk, vv, c_q, gate = _projected(c, g, p, x, layer_params, positions, rope, constrain)
    batch_axes, _ = mla.local_heads(rules, mesh, q)
    sg = jax.lax.stop_gradient
    with tracing.scope("layer/attn_core"):
        with tracing.scope("dsa/index"):
            # q^I, k^I and w are made and rotated in float32 and rounded ONCE, as the scores' bf16 operands: every rounding
            # before the selection moves keys across its threshold (PERF.md section 6, PR 66)
            f32 = dict(preferred_element_type=jnp.float32)
            part = None if rope is None else dataclasses.replace(rope, rotary_dim=g.rope)
            qi = jnp.einsum("bsr,rjd->bsjd", sg(c_q), p["wi_q"].astype(dt), **f32)
            ki = layer_norm(jnp.einsum("bse,ed->bsd", sg(h), p["wi_k"].astype(dt), **f32), p["ki_norm"], p["ki_norm_b"], c.norm_eps)[:, :, None]
            if part is not None:
                qi, ki = apply_rope(qi, positions, part), apply_rope(ki, positions, part)
            qi, ki = qi.astype(dt), ki.astype(dt)
            w = jnp.einsum("bse,ej->bsj", sg(h), p["wi_w"].astype(dt), **f32) * (c.index_heads * c.index_head_dim) ** -0.5
            scores = sa.index_scores(qi, ki[:, :, 0], w, mesh=mesh if rules is not None else None, batch_axes=batch_axes)
        with tracing.scope("dsa/topk"):
            mask = checkpoint_name(sa.select_topk(scores, c.index_topk), sa.MASK)
        with tracing.scope("dsa/attn"):
            scaled = q * jnp.asarray(g.qk ** -0.5, dt)
            attn, lse = sa.selected_attention(scaled, kk, vv, mask, mesh=mesh if rules is not None else None, batch_axes=batch_axes)
        with tracing.scope("dsa/kl"):
            kl = sa.index_kl(scores, mask, sa.head_mean_probs(scaled, kk, lse, mask))
            pairs = jnp.sum(mask.astype(jnp.float32)) / mask.shape[0]
    return _gated_out(c, x, attn, gate, p, constrain), {INDEX_KL: kl, SELECTED_PAIRS: pairs}


_SAVED = (mla.MLA_MIXED, HEAD_GATE)

SPARSE = Mixer("mla_sparse", "mla_sparse_layers", "mla_sparse", sparse_leaves, validate_sparse, mix_sparse,
               saved=(*_SAVED, sa.MASK, sa.KL_GRAD, *sa.PAIR.residual_names), rotates=True, placement=placement,
               reports=(INDEX_KL, SELECTED_PAIRS), holds_heads=True)
WINDOW = Mixer("mla_window", "mla_window_layers", "mla_window", window_leaves, validate_window, mix_window, saved=_SAVED,
               rotates=True, placement=placement, holds_heads=True,
               flash_heads=lambda c: (c.window_latent.qk, c.window_latent.v))
