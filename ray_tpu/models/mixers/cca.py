"""Compressed Convolutional Attention ("cca"; arXiv:2510.04476, as ZAYA1 runs it, arXiv:2511.17127): attention
whose q, k and v live in latents narrower than the stream and are MIXED ALONG THE SEQUENCE before the softmax.
x [B, S, d], u = ln1(x), H = `n_heads` query heads and G = `n_kv_heads` key heads of `head_dim` D, g = H / G:

- `cca/proj`: the latents `qt = u W_Q` [S, H, D] and `kt = u W_K` [S, G, D] (at ZAYA1-8B's widths 2x and 8x
  narrower than d), and the value `v = u W_V` [S, G, D] whose SECOND half of key heads is shifted one position
  back (`v[t, j] = (u W_V)[t - 1, j]` for j >= G / 2, zero at t = 0: with two key heads, head 0 reads t and head 1
  reads t - 1); no bias.
- `cca/mix` (`qk_mixing`): two causal convolutions over the concatenated latent [S, H + G, D], zero history, each
  with a bias, nothing between them: a depthwise one of `cca_taps[0]` taps a channel, then one of `cca_taps[1]`
  taps in H + G groups, a [D, D] map a head and tap (tap i of a T-tap convolution multiplies position t - (T - 1 -
  i)); to its result c the PRE-convolution mean is added: `q_h = c^q_h + (qt_h + kt_{h // g}) / 2`, `k_j = c^k_j +
  (mean_{h in group j} qt_h + kt_j) / 2`; each head of q and k is brought to the norm sqrt(D) in float32 (`y *
  rsqrt(sum y^2 + 1e-6) * sqrt(D)`) and k's head j multiplied by the learned temperature `tau[j]` (1 at the seed);
  the rope of the layer (`layer_ropes`) or the model's (`rope_theta` on the first `rotary_dim` dims), float32; one
  rounding to the model's dtype.
- `layer/attn_core`: causal softmax of `q k^T * attention_scale` (`D ** -0.5` when None), 4 query heads a key head
  at ZAYA1-8B's 8 / 2 heads of 128: `ops.attention`'s call and flash kernels as an "attention" layer's.
- `cca/proj`: `o W_O` and the join, through the layer's learned residual scaling where the model has one
  (`residual_scaling`: `base.joined`).

Under `remat_policy="qkv_attn"` the backward recomputes no d-wide projection and no convolution: beside q, k, v,
the core's output and log-sum-exp it keeps the latent (`cca_latent`: the depthwise convolution's input), the
depthwise convolution's output (`cca_conv1`: the grouped one's input) and the sum the norm reads (`cca_mixed`),
each [S, H + G, D] in the model's dtype.

No window (ZAYA1-8B's `sliding_window` is null; a windowed kind with a rope of its own waits), no bias, no QK-norm
of the learned-scale kind, no output gate; heads and sequence whole: `tp` and the sequence-parallel ring are
refused when configuration, rules and mesh first meet.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.mixers.base import (
    Leaf, Mixer, constrainer, fitting_axis, joined, may_ring, normal, ones, out_scale, proj_scale, refuse_attn_bias, stream_norm,
    zeros,
)
from ray_tpu.ops.attention import dot_product_attention
from ray_tpu.ops.delta_conv import _l2_normed  # y / sqrt(|y|^2 + 1e-6) * scale over a head, float32: a delta rule's q and k too
from ray_tpu.ops.rotary import Rope, apply_rope
from ray_tpu.util import tracing

LATENT, CONV1, MIXED = "cca_latent", "cca_conv1", "cca_mixed"  # what `qkv_attn` keeps of the mixing (module docstring)


def leaves(config):
    c, hd, heads = config, config.head_dim, config.n_heads + config.n_kv_heads
    taps0, taps1 = c.cca_taps
    q, kv = ("embed", "heads", "head_dim"), ("embed", "kv_heads", "head_dim")
    return {
        "wq": Leaf((c.d_model, c.n_heads, hd), q, normal(proj_scale(c))),
        "wk": Leaf((c.d_model, c.n_kv_heads, hd), kv, normal(proj_scale(c))),
        "wv": Leaf((c.d_model, c.n_kv_heads, hd), kv, normal(proj_scale(c))),
        "conv1_w": Leaf((taps0, heads, hd), (None, None, None), normal(taps0 ** -0.5)),
        "conv1_b": zeros((heads, hd)),
        "conv2_w": Leaf((taps1, heads, hd, hd), (None, None, None, None), normal((taps1 * hd) ** -0.5)),
        "conv2_b": zeros((heads, hd)),
        "tau": ones((c.n_kv_heads,)),
        "wo": Leaf((c.n_heads, hd, c.d_model), ("heads", "head_dim", "embed"), normal(out_scale(c))),
    }


def validate(config) -> None:
    c = config
    refuse_attn_bias(c)
    has = [name for name, there in (
        ("qk_norm", bool(c.qk_norm)), ("attn_output_gate", c.attn_output_gate), ("diffusion_block", c.diffusion_block is not None),
        ("layer_windows at a cca layer", any(w is not None and kind == "cca" for w, kind in zip(
            c.layer_windows or (), c.layer_types or ()))),
    ) if there]
    if has or c.n_heads % c.n_kv_heads or c.n_kv_heads % 2 or len(c.cca_taps) != 2 or min(c.cca_taps) < 1:
        raise ValueError(
            f"a cca layer has n_heads={c.n_heads} a multiple of n_kv_heads={c.n_kv_heads}, an even number of key heads (the "
            f"second half reads the value one position back) and cca_taps={c.cca_taps} two counts >= 1; it takes no "
            + ", no ".join(has or ["learned QK-norm, gate, window or block-diffusion mask"]))


def placement(config, rules, mesh) -> None:
    if rules is not None and (may_ring(rules, mesh) or fitting_axis(rules.get("act_heads"), mesh, config.n_heads) is not None):
        raise ValueError("a cca layer runs with its heads and its sequence whole (its convolutions mix the q|k latent along the "
                         "sequence and its q-k mean crosses a group's heads): strategy 'tp' and the sequence-parallel ring do not take it")


def _back(x: jax.Array, n: int) -> jax.Array:
    """x [B, S, ...] read n positions back: y[t] = x[t - n], zero before the sequence's start."""
    if n == 0:
        return x
    # (not `jnp.pad`: a jitted function of jax's own, whose output a checkpoint keeps whatever its policy names)
    return jnp.concatenate([jnp.zeros_like(x[:, :n]), x[:, :-n]], axis=1)


def qk_mixing(latent: jax.Array, p, positions: jax.Array, rope, n_heads: int):
    """`cca/mix` of the module docstring: the latent [B, S, H + G, D] (q's heads first) and the layer's `conv1_w`,
    `conv1_b`, `conv2_w`, `conv2_b`, `tau` -> (q [B, S, H, D], k [B, S, G, D]) in the latent's dtype, normed, tempered
    and rotated (`rope` None: not rotated)."""
    f32, dt = jnp.float32, latent.dtype
    b, s, heads, hd = latent.shape
    kv_heads = heads - n_heads
    x = latent.astype(f32)
    # the grouped convolution's operands are the model's dtype's values WIDENED (exact), its products float32: at the default
    # precision a TPU multiplies them as they were, and the CPU has no bfloat16 x bfloat16 -> float32 product with a batch axis
    w1, w2 = p["conv1_w"].astype(f32), p["conv2_w"].astype(dt).astype(f32)
    taps0, taps1 = w1.shape[0], w2.shape[0]
    c1 = sum(w1[i] * _back(x, taps0 - 1 - i) for i in range(taps0)) + p["conv1_b"].astype(f32)
    c1 = checkpoint_name(c1.astype(dt), CONV1).astype(f32)
    c2 = sum(jnp.einsum("bsgd,gde->bsge", _back(c1, taps1 - 1 - i), w2[i]) for i in range(taps1)) + p["conv2_b"].astype(f32)
    qt, kt = x[:, :, :n_heads], x[:, :, n_heads:]
    group = n_heads // kv_heads
    q_mean = (qt + jnp.repeat(kt, group, axis=2)) * 0.5
    k_mean = (jnp.mean(qt.reshape(b, s, kv_heads, group, hd), axis=3) + kt) * 0.5
    normed = _l2_normed(checkpoint_name((c2 + jnp.concatenate([q_mean, k_mean], axis=2)).astype(dt), MIXED), hd ** 0.5)
    q, k = normed[:, :, :n_heads], normed[:, :, n_heads:] * p["tau"].astype(f32)[None, None, :, None]
    if rope is not None:
        q, k = apply_rope(q, positions, rope), apply_rope(k, positions, rope)
    return q.astype(dt), k.astype(dt)


def mix(x, layer_params, positions, config, rules, mesh=None, *, window=None, data=None, shared=None, emit=False, rope=None):
    """The CCA half of a layer; `rope` is the layer's own rotary embedding (None: the model's `rope_theta`)."""
    del data, shared, emit
    if window is not None:
        raise ValueError("a cca layer takes no window (layer_windows)")
    c, dt, p = config, config.dtype, layer_params["cca"]
    constrain = constrainer(rules, mesh)
    if rope is None and c.rope_theta is not None:
        rope = Rope(c.rope_theta, rotary_dim=c.rotary_dim)
    with tracing.scope("layer/attn_proj"):
        with tracing.scope("cca/proj"):
            u = stream_norm(c, x, layer_params, "ln1")
            latent = jnp.concatenate([jnp.einsum("bse,ehd->bshd", u, p["wq"].astype(dt)),
                                      jnp.einsum("bse,ehd->bshd", u, p["wk"].astype(dt))], axis=2)
            latent = checkpoint_name(latent, LATENT)
            vv = jnp.einsum("bse,ehd->bshd", u, p["wv"].astype(dt))
            now = c.n_kv_heads // 2  # the key heads that read the value of their own position
            vv = checkpoint_name(jnp.concatenate([vv[:, :, :now], _back(vv[:, :, now:], 1)], axis=2), "v")
        with tracing.scope("cca/mix"):
            q, kk = qk_mixing(latent, p, positions, rope, c.n_heads)
            q = checkpoint_name(constrain(q, ("act_batch", "act_seq", "act_heads", "act_head_dim")), "q")
            kk = checkpoint_name(constrain(kk, ("act_batch", "act_seq", "act_kv_heads", "act_head_dim")), "k")
    with tracing.scope("layer/attn_core"):
        attn = dot_product_attention(
            q, kk, vv, causal=True, scale=c.attention_scale, impl=c.attention_impl,
            mesh=mesh if rules is not None else None,
            batch_axes=None if rules is None else rules.get("act_batch"), head_axis=None,
        )
    with tracing.scope("layer/attn_proj"), tracing.scope("cca/proj"):
        out = jnp.einsum("bshd,hde->bse", attn, p["wo"].astype(dt))
        return joined(c, x, out, constrain, layer_params.get("res1")), {}


MIXER = Mixer("cca", "cca_layers", "cca", leaves, validate, mix, saved=(LATENT, CONV1, MIXED), rotates=True, placement=placement,
              flash_heads=lambda c: (c.head_dim, c.head_dim), scales_residual=True)
