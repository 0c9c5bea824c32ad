"""Latent attention ("mla": DeepSeek-V2/V3's, arXiv:2412.19437 section 2.1;
Kimi Linear's full layers, arXiv:2510.26692; GLM-4.7-Flash's every layer),
x [B, S, d], u = ln1(x), `n_heads` heads, no bias:

`q = u W_q -> [H, nope + rope]`, or with `q_lora_rank` low-rank,
`c_q = RMSNorm(u W_qa)`, `q = c_q W_qb`; `[c | k_pe] = u W_kva ->
[kv_lora_rank | rope]`; `c <- RMSNorm(c)`; `[k_nope | v] = c W_kvb -> [H,
nope | v_head_dim]`; with `mla_rope` the `rope`-wide part of every q head and
the ONE `k_pe` are rotated by their positions (adjacent pairs,
`ops/rotary.py`), the `nope`-wide parts are not; without one nothing is
rotated (Kimi Linear's NoPE); `k = [k_nope | k_pe]`, the one `k_pe` shared
by the heads; causal softmax of `q k^T * (nope + rope)^-0.5`; `W_o: H *
v_head_dim -> d`.

Keys and values are expanded from one low-rank latent (the form training
runs; the absorbed form, which attends in the latent, is a decode path's),
and q/k heads may be wider than v heads (the flash kernels take the two
sizes).  It runs local attention only: no sequence-parallel ring.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.mixers.base import (
    Leaf, Mixer, constrainer, fitting_axis, joined, normal, ones, out_scale, proj_scale, refuse_attn_bias,
    ring_axis, rms_norm, stream_norm,
)
from ray_tpu.ops.attention import dot_product_attention
from ray_tpu.ops.rotary import Rope, apply_rope
from ray_tpu.util import tracing

# The residual stream after `wo` (q, k, v carry attention's own names).
MLA_MIXED = "mla_mixed"


@dataclasses.dataclass(frozen=True)
class Latent:
    """The sizes of ONE latent geometry: what "mla" reads from the
    configuration's own fields (`latent_of`) and what a model with a second
    geometry states for its other kind (`TransformerConfig.window_latent`;
    mixers/dsa.py).  `heads` of the model's `all_heads` (None: all of them)
    are held, from `first_head` on: the leaves are `heads` wide and drawn as
    that range of the whole model's."""
    heads: int
    q_rank: Optional[int]  # None: q is one projection
    kv_rank: int
    nope: int
    rope: int
    v: int
    all_heads: Optional[int] = None
    first_head: int = 0

    @property
    def qk(self) -> int:
        return self.nope + self.rope

    def held(self, index: int, of: int) -> "Latent":
        """Share `index` of `of` of the heads."""
        if self.heads % of:
            raise ValueError(f"{self.heads} heads are no {of} equal shares")
        return dataclasses.replace(self, heads=self.heads // of, all_heads=self.heads, first_head=index * (self.heads // of))

    def of_heads(self, scale: float, axis: int):
        """`normal(scale)` for a leaf whose `axis` (counted from the end) is
        the heads': the held range of the draw for all the model's heads."""
        if self.all_heads in (None, self.heads):
            return normal(scale)

        def init(key, shape):
            whole = list(shape)
            whole[axis] = self.all_heads
            return jax.lax.slice_in_dim(normal(scale)(key, tuple(whole)), self.first_head, self.first_head + self.heads,
                                        axis=len(shape) + axis)
        return init


def latent_of(config) -> Latent:
    c = config
    return Latent(c.n_heads, c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim)


def latent_leaves(config, g: Latent, rescale: bool = False):
    """`rescale`: the layer multiplies each normed latent by `(d_model / its
    rank) ** 0.5` (`project`), so an up-projection's input has mean square
    `d_model / rank` and its fan-in scale is `d_model ** -0.5`, a d-wide
    projection's: q, k_nope and v start at the variance of k_pe, as they do
    without the rescale."""
    c, rank = config, g.kv_rank
    heads = ("heads", "head_dim")
    up = (lambda r: proj_scale(c)) if rescale else (lambda r: r ** -0.5)
    if g.q_rank is None:
        q = {"wq": Leaf((c.d_model, g.heads, g.qk), ("embed", *heads), g.of_heads(proj_scale(c), -2))}
    else:
        q = {"w_qa": Leaf((c.d_model, g.q_rank), ("embed", None), normal(proj_scale(c))),
             "q_norm": ones((g.q_rank,)),
             "w_qb": Leaf((g.q_rank, g.heads, g.qk), (None, *heads), g.of_heads(up(g.q_rank), -2))}
    return {
        **q,
        "w_kva": Leaf((c.d_model, rank + g.rope), ("embed", None), normal(proj_scale(c))),
        "kv_norm": ones((rank,)),
        "w_kvb": Leaf((rank, g.heads, g.nope + g.v), (None, *heads), g.of_heads(up(rank), -2)),
        "wo": Leaf((g.heads, g.v, c.d_model), (*heads, "embed"), g.of_heads(out_scale(c), -3)),
    }


def leaves(config):
    return latent_leaves(config, latent_of(config))


def validate_latent(g: Latent, rope, field: str = "mla_rope") -> None:
    if not (g.kv_rank > 0 and g.nope > 0 and g.v > 0):
        raise ValueError("a latent-attention layer needs kv_lora_rank, qk_nope_head_dim and v_head_dim")
    if g.q_rank is not None and g.q_rank <= 0:
        raise ValueError(f"q_lora_rank is None (q is one projection) or the rank of its two, got {g.q_rank}")
    if rope is not None and not (isinstance(rope, Rope) and g.rope > 0 and g.rope % 2 == 0):
        raise ValueError(f"{field} is an ops.rotary.Rope over an even qk_rope_head_dim, or None; got {rope!r} "
                         f"over {g.rope}")


def validate(config) -> None:
    validate_latent(latent_of(config), config.mla_rope)
    refuse_attn_bias(config)


def project(config, g: Latent, p, h, positions, rope: Optional[Rope], constrain, rescale: bool = False):
    """q [B, S, H, nope + rope], k alike, v [B, S, H, v] of the normed stream
    `h`, q, k and v under attention's names, and the q latent (None where q
    is one projection).  `rescale`: each normed latent times `(d_model /
    its rank) ** 0.5` (LongCat-Flash's `mla_scale_q_lora` / `mla_scale_kv_lora`)."""
    c, dt, rank, nope = config, config.dtype, g.kv_rank, g.nope
    c_q = None
    if g.q_rank is None:
        q = jnp.einsum("bse,ehd->bshd", h, p["wq"].astype(dt))
    else:
        c_q = rms_norm(jnp.einsum("bse,er->bsr", h, p["w_qa"].astype(dt)), p["q_norm"], c.norm_eps)
        if rescale:
            c_q = c_q * jnp.asarray((c.d_model / g.q_rank) ** 0.5, dt)
        q = jnp.einsum("bsr,rhd->bshd", c_q, p["w_qb"].astype(dt))
    latent = jnp.einsum("bse,ef->bsf", h, p["w_kva"].astype(dt))
    c_kv = rms_norm(latent[..., :rank], p["kv_norm"], c.norm_eps)
    if rescale:
        c_kv = c_kv * jnp.asarray((c.d_model / rank) ** 0.5, dt)
    kv = jnp.einsum("bsr,rhd->bshd", c_kv, p["w_kvb"].astype(dt))
    k_pe = latent[..., None, rank:]  # [B, S, 1, rope]: one for all heads
    if rope is not None:
        q = jnp.concatenate([q[..., :nope], apply_rope(q[..., nope:], positions, rope)], axis=-1)
        k_pe = apply_rope(k_pe, positions, rope)
    k_pe = jnp.broadcast_to(k_pe, (*kv.shape[:3], g.rope))
    kk = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
    q = constrain(q, ("act_batch", "act_seq", "act_heads", "act_head_dim"))
    kk = constrain(kk, ("act_batch", "act_seq", "act_heads", "act_head_dim"))
    return checkpoint_name(q, "q"), checkpoint_name(kk, "k"), checkpoint_name(kv[..., nope:], "v"), c_q


def local_heads(rules, mesh, q):
    """(batch axes, the mesh axis the heads are split over) of a local attention call; a ring is refused."""
    batch_axes = head_ax = None
    if rules is not None:
        batch_axes = rules.get("act_batch")
        head_ax = fitting_axis(rules.get("act_heads"), mesh, q.shape[2])
    if ring_axis(rules, mesh, q) is not None:
        raise ValueError("a latent-attention layer runs local attention only (no sequence-parallel ring)")
    return batch_axes, head_ax


def mix(x, layer_params, positions, config, rules, mesh=None, *, window=None, data=None, shared=None, emit=False):
    """The latent-attention half of a layer.  `mla/proj` names its
    projections (and the rotation of the two rope parts) inside
    `layer/attn_proj`; the core is `dot_product_attention` with q/k heads of
    `nope + rope` and v heads of `v_head_dim`."""
    del window, data, shared, emit
    c, dt, p = config, config.dtype, layer_params["mla"]
    constrain = constrainer(rules, mesh)
    with tracing.scope("layer/attn_proj"), tracing.scope("mla/proj"):
        h = stream_norm(c, x, layer_params, "ln1")
        q, kk, vv, _ = project(c, latent_of(c), p, h, positions, c.mla_rope, constrain)
    batch_axes, head_ax = local_heads(rules, mesh, q)
    with tracing.scope("layer/attn_core"):
        attn = dot_product_attention(
            q, kk, vv, causal=True, scale=q.shape[-1] ** -0.5, impl=c.attention_impl,
            mesh=mesh if rules is not None else None, batch_axes=batch_axes, head_axis=head_ax,
        )
    with tracing.scope("layer/attn_proj"), tracing.scope("mla/proj"):
        out = jnp.einsum("bshd,hde->bse", attn, p["wo"].astype(dt))
        return checkpoint_name(joined(c, x, out, constrain), MLA_MIXED), {}


MIXER = Mixer("mla", "mla_layers", "mla", leaves, validate, mix, saved=(MLA_MIXED,),
              flash_heads=lambda c: (c.qk_nope_head_dim + c.qk_rope_head_dim, c.v_head_dim))
