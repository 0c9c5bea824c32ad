"""Differential attention ("diff_attention") and differential
cross-attention ("diff_cross") (SambaY / Phi-4-mini-flash-reasoning,
arXiv:2507.06607; differential attention, arXiv:2410.05258), x [B, S, d],
u = ln1(x), no positional encoding, `n_heads` / `n_kv_heads` heads of
`head_dim`, both even; with `attn_bias` the projections carry biases:

- "diff_attention": `[q | k | v] = u W_qkv + b_qkv`; heads pair up
  adjacently: q pair p = q heads (2p, 2p+1) = (q1, q2); with
  G = n_heads / n_kv_heads, kv pair j = p // G: k heads (2j, 2j+1) = (k1, k2),
  `v = [v_2j | v_2j+1]` of width 2 * head_dim.
  `a1 = softmax(q1 k1^T / sqrt(head_dim) + mask) v`, `a2` likewise from
  (q2, k2); `lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init`, four
  learned vectors of head_dim a layer, `lambda_init = 0.8 - 0.6 exp(-0.3 l)`
  with l the layer's PUBLISHED index (`layer_ids`);
  `o = RMSNorm(a1 - lambda a2) * (1 - lambda_init)` over 2 * head_dim (one
  learned scale a layer), reshaped to two heads; `out = o W_o + b_o`.  The
  mask is causal, and with a window w (`layer_windows`) query i sees keys
  i - w + 1 .. i.  At the layer `kv_source_layer`, k and v (after bias) are
  handed on as `SHARED_K` and `SHARED_V`.
- "diff_cross": its own `q = u W_q + b_q`, lambda, norm and `W_o`; k and v
  are the `kv_source_layer`'s; full causal.

The heads carry no logical axis: `tp` and the sequence-parallel ring are
refused by name (`_core`).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.mixers.base import (
    Leaf, Mixer, constrainer, fitting_axis, joined, normal, ones, out_scale, proj_scale, ring_axis,
    stream_norm, zeros,
)
from ray_tpu.ops.attention import dot_product_attention
from ray_tpu.util import tracing

# What the K/V layer hands on: its keys and values.
SHARED_K, SHARED_V = "shared_k", "shared_v"
# The stream after `W_o` (q, k, v, the output and the log-sum-exp carry
# attention's own names: both maps are one call).
DIFF_MIXED = "diff_mixed"
# The four learned vectors of `head_dim`.
_LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")


def _leaves(config, cross: bool):
    """The four lambda vectors normal * 0.1 (arXiv:2410.05258), the norm's scale 1, biases 0."""
    c, hd = config, config.head_dim
    q_wide = c.n_heads * hd
    first, wide = ("q", q_wide) if cross else ("qkv", q_wide + 2 * c.n_kv_heads * hd)
    out = {
        "w" + first: Leaf((c.d_model, wide), ("embed", None), normal(proj_scale(c))),
        "wo": Leaf((q_wide, c.d_model), (None, "embed"), normal(out_scale(c))),
        **{name: Leaf((hd,), (None,), normal(0.1)) for name in _LAMBDAS},
        "subln": ones((2 * hd,)),
    }
    if c.attn_bias:
        out["b" + first] = zeros((wide,))
        out["bo"] = zeros((c.d_model,))
    return out


def validate(config) -> None:
    if config.n_heads % 2 or config.n_kv_heads % 2 or config.n_heads % config.n_kv_heads:
        raise ValueError("differential attention pairs adjacent heads: n_heads and n_kv_heads "
                         "must be even, n_heads a multiple of n_kv_heads")


def diff_head_maps(n_heads: int, n_kv_heads: int) -> Tuple[np.ndarray, np.ndarray]:
    """The pairing as two gathers, one entry per q head i: the k head its map
    scores against, `2 * (i // 2 // G) + i % 2`, and the PAIR of v heads (one
    value of twice the width) it averages, `i // 2 // G`, with
    G = n_heads / n_kv_heads (module docstring)."""
    i = np.arange(n_heads)
    kv_pair = i // 2 // (n_heads // n_kv_heads)
    return 2 * kv_pair + i % 2, kv_pair


def _core(q, k, v, p, lambda_init, config, rules, mesh, window):
    """Both softmax maps of every head pair and their combination: q
    [B, S, H, D], k and v [B, S, Hkv, D] -> [B, S, H * D] in the model's dtype.
    One attention call over H maps with q/k heads of D and values of 2 * D
    (the flash kernels' two head sizes), the heads gathered to their pairing
    around it, under `diff/window` or `diff/full`; then `diff/combine`, in
    float32 from the call's output: `a1 - lambda a2`, the RMSNorm over 2 * D,
    the scale `1 - lambda_init`."""
    c, f32 = config, jnp.float32
    b, s, heads, hd = q.shape
    if rules is not None and (fitting_axis(rules.get("act_heads"), mesh, heads) is not None
                              or ring_axis(rules, mesh, q) is not None):
        raise ValueError(
            "differential attention ('diff_attention', 'diff_cross') runs with its heads and its "
            "sequence whole: strategy 'tp' and the sequence-parallel ring do not take its pairing")
    k_of, v_of = diff_head_maps(heads, k.shape[2])
    with tracing.scope("layer/attn_core"):
        with tracing.scope("diff/full" if window is None else "diff/window"):
            keys = jnp.take(k, k_of, axis=2)
            values = jnp.take(v.reshape(b, s, v.shape[2] // 2, 2 * hd), v_of, axis=2)
            maps = dot_product_attention(
                q, keys, values, causal=True, scale=hd ** -0.5, impl=c.attention_impl,
                mesh=mesh if rules is not None else None,
                batch_axes=None if rules is None else rules.get("act_batch"), head_axis=None,
                **({} if window is None else {"window": window}),
            )
        with tracing.scope("diff/combine"):
            maps = maps.astype(f32).reshape(b, s, heads // 2, 2, 2 * hd)
            lam = (jnp.exp(jnp.sum(p["lambda_q1"].astype(f32) * p["lambda_k1"].astype(f32)))
                   - jnp.exp(jnp.sum(p["lambda_q2"].astype(f32) * p["lambda_k2"].astype(f32))) + lambda_init)
            o = maps[..., 0, :] - lam * maps[..., 1, :]
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + c.norm_eps)
            o = o * (p["subln"].astype(f32) * (1.0 - lambda_init))
            return o.astype(c.dtype).reshape(b, s, heads * hd)


def mix(x, layer_params, positions, config, rules, mesh=None, *, window=None, data=None, shared=None, emit=False):
    """The differential half of a layer: a cross layer when it is handed keys
    and values (`shared`), a self layer otherwise.  `diff/proj` names its
    projections inside `layer/attn_proj`; the core is `_core`."""
    del positions  # no positional encoding
    c, dt, p = config, config.dtype, layer_params["diff"]
    constrain = constrainer(rules, mesh)
    hd, q_wide = c.head_dim, c.n_heads * c.head_dim
    cross, handed = bool(shared), {}
    with tracing.scope("layer/attn_proj"), tracing.scope("diff/proj"):
        h = stream_norm(c, x, layer_params, "ln1")
        first = "q" if cross else "qkv"
        proj = jnp.einsum("bse,ef->bsf", h, p["w" + first].astype(dt))
        if c.attn_bias:
            proj = proj + p["b" + first].astype(dt)
        heads_of = lambda a: a.reshape(*a.shape[:2], a.shape[-1] // hd, hd)  # noqa: E731
        q = checkpoint_name(heads_of(proj[..., :q_wide]), "q")
        if cross:
            kk, vv = shared[SHARED_K], shared[SHARED_V]
        else:
            kk, vv = (heads_of(a) for a in jnp.split(proj[..., q_wide:], 2, axis=-1))
            kk, vv = checkpoint_name(kk, "k"), checkpoint_name(vv, "v")
            if emit:
                handed = {SHARED_K: kk, SHARED_V: vv}
    o = _core(q, kk, vv, p, data["lambda_init"], c, rules, mesh, window)
    with tracing.scope("layer/attn_proj"), tracing.scope("diff/proj"):
        out = jnp.einsum("bsf,fe->bse", o, p["wo"].astype(dt))
        if c.attn_bias:
            out = out + p["bo"].astype(dt)
        return checkpoint_name(joined(c, x, out, constrain), DIFF_MIXED), handed


def _data(config):
    return {"lambda_init": config.lambda_inits()}


def _flash_heads(config):
    return config.head_dim, 2 * config.head_dim  # one call over both maps of a pair (`_core`)


MIXER = Mixer("diff_attention", "diff_layers", "diff", lambda c: _leaves(c, cross=False), validate, mix,
              saved=(DIFF_MIXED,), hands=(SHARED_K, SHARED_V), source="kv_source_layer", data=_data, flash_heads=_flash_heads)
CROSS = Mixer("diff_cross", "cross_layers", "diff", lambda c: _leaves(c, cross=True), validate, mix,
              saved=(DIFF_MIXED,), reads=(SHARED_K, SHARED_V), data=_data, flash_heads=_flash_heads)
