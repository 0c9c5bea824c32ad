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


def leaves(config):
    c, rank = config, config.kv_lora_rank
    heads = ("heads", "head_dim")
    qk = c.qk_nope_head_dim + c.qk_rope_head_dim
    if c.q_lora_rank is None:
        q = {"wq": Leaf((c.d_model, c.n_heads, qk), ("embed", *heads), normal(proj_scale(c)))}
    else:
        q = {"w_qa": Leaf((c.d_model, c.q_lora_rank), ("embed", None), normal(proj_scale(c))),
             "q_norm": ones((c.q_lora_rank,)),
             "w_qb": Leaf((c.q_lora_rank, c.n_heads, qk), (None, *heads), normal(c.q_lora_rank ** -0.5))}
    return {
        **q,
        "w_kva": Leaf((c.d_model, rank + c.qk_rope_head_dim), ("embed", None), normal(proj_scale(c))),
        "kv_norm": ones((rank,)),
        "w_kvb": Leaf((rank, c.n_heads, c.qk_nope_head_dim + c.v_head_dim), (None, *heads), normal(rank ** -0.5)),
        "wo": Leaf((c.n_heads, c.v_head_dim, c.d_model), (*heads, "embed"), normal(out_scale(c))),
    }


def validate(config) -> None:
    if not (config.kv_lora_rank > 0 and config.qk_nope_head_dim > 0 and config.v_head_dim > 0):
        raise ValueError("an mla layer needs kv_lora_rank, qk_nope_head_dim and v_head_dim")
    if config.q_lora_rank is not None and config.q_lora_rank <= 0:
        raise ValueError(f"q_lora_rank is None (q is one projection) or the rank of its two, got {config.q_lora_rank}")
    if config.mla_rope is not None and not (isinstance(config.mla_rope, Rope) and config.qk_rope_head_dim > 0
                                            and config.qk_rope_head_dim % 2 == 0):
        raise ValueError(f"mla_rope is an ops.rotary.Rope over an even qk_rope_head_dim, or None; got {config.mla_rope!r} "
                         f"over {config.qk_rope_head_dim}")
    refuse_attn_bias(config)


def mix(x, layer_params, positions, config, rules, mesh=None, *, window=None, data=None, shared=None, emit=False):
    """The latent-attention half of a layer.  `mla/proj` names its
    projections (and the rotation of the two rope parts) inside
    `layer/attn_proj`; the core is `dot_product_attention` with q/k heads of
    `nope + rope` and v heads of `v_head_dim`."""
    del window, data, shared, emit
    c, dt, p = config, config.dtype, layer_params["mla"]
    constrain = constrainer(rules, mesh)
    rank, nope = c.kv_lora_rank, c.qk_nope_head_dim
    with tracing.scope("layer/attn_proj"), tracing.scope("mla/proj"):
        h = stream_norm(c, x, layer_params, "ln1")
        if c.q_lora_rank is None:
            q = jnp.einsum("bse,ehd->bshd", h, p["wq"].astype(dt))
        else:
            c_q = rms_norm(jnp.einsum("bse,er->bsr", h, p["w_qa"].astype(dt)), p["q_norm"], c.norm_eps)
            q = jnp.einsum("bsr,rhd->bshd", c_q, p["w_qb"].astype(dt))
        latent = jnp.einsum("bse,ef->bsf", h, p["w_kva"].astype(dt))
        kv = jnp.einsum("bsr,rhd->bshd", rms_norm(latent[..., :rank], p["kv_norm"], c.norm_eps),
                        p["w_kvb"].astype(dt))
        k_pe = latent[..., None, rank:]  # [B, S, 1, rope]: one for all heads
        if c.mla_rope is not None:
            q = jnp.concatenate([q[..., :nope], apply_rope(q[..., nope:], positions, c.mla_rope)], axis=-1)
            k_pe = apply_rope(k_pe, positions, c.mla_rope)
        k_pe = jnp.broadcast_to(k_pe, (*kv.shape[:3], c.qk_rope_head_dim))
        kk = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
        q = constrain(q, ("act_batch", "act_seq", "act_heads", "act_head_dim"))
        kk = constrain(kk, ("act_batch", "act_seq", "act_heads", "act_head_dim"))
        q = checkpoint_name(q, "q")
        kk = checkpoint_name(kk, "k")
        vv = checkpoint_name(kv[..., nope:], "v")
    batch_axes = head_ax = None
    if rules is not None:
        batch_axes = rules.get("act_batch")
        head_ax = fitting_axis(rules.get("act_heads"), mesh, q.shape[2])
    if ring_axis(rules, mesh, q) is not None:
        raise ValueError("an mla layer runs local attention only (no sequence-parallel ring)")
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
