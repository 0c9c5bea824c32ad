"""Plain reference for kind "dense_decoder": the decoder in straightforward
float32 `jax.numpy`, written from the published descriptions (Mistral 7B,
arXiv:2310.06825; InternLM2, arXiv:2403.17297): token embedding, L pre-norm
blocks of RMSNorm -> grouped-query causal softmax attention with rotary
position embeddings -> residual, RMSNorm -> SwiGLU -> residual, a final
RMSNorm and an untied (or tied) output head.  No kernel, cache, sharding or
remat, and no import from `ray_tpu.models` or `ray_tpu.ops`: it shares with
the program only the layout of the parameter tree it is handed.

Departures from the papers, all noted: rotary pairs are adjacent dims
(2i, 2i+1) as in RoFormer and mistral-inference (Hugging Face's rotate_half
is the same function under a fixed permutation of each head's columns);
InternLM2's fused wqkv arrives as wq/wk/wv.

On a TPU a float32 matmul runs in bf16 passes unless told otherwise, so
everything here runs under `jax.default_matmul_precision("highest")`.
Weights are upcast ONE LAYER AT A TIME and one sequence is processed at a
time, queries in blocks, so the reference fits beside the training state;
it runs on one device whatever mesh the parameters live on.

TOLERANCE.  The program computes in bf16 (8 bits of significand; every
activation is rounded after every op, the logits too) from the same bf16
weights, the reference in float32.  The roundings accumulate like a random
walk, so the logits' relative RMS error grows like sqrt(L).  Measured on the
v5e (my chip runs, PR 22): 1.2% at 5 Mistral layers, 2.2% at 16 InternLM2
layers, 2.6% at 24 Mistral layers; on the CPU at a toy width 1.1% at 3 layers
and 1.7% at 8.  `tolerance(L)` allows 0.012 * sqrt(L) (2.7%, 4.8%, 5.9% for
the three configurations), about 2.2x what bf16 costs.  At the toy width,
int8 weights alone err 4-5x what bf16 does (5.0% at 3 layers, 7.1% at 8) and
fp8 weights 11x, so either lands over the tolerance; a dropped causal mask
changes the logits by 70% and more, and a dropped rope, a wrong head grouping
or a missing residual by tens of percent.  fp16 is finer than the bf16 the
configurations state, so it neither can nor needs to fail.
`benchmarks/tests/test_reference.py` holds the tolerance to that.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512  # scores held at once: H * 512 * S float32 (1 GB at 32 heads, 16k)


def tolerance(num_layers: int) -> float:
    """Largest allowed rms(program - reference) / rms(reference) over the
    compared logits; see TOLERANCE above."""
    return 0.012 * math.sqrt(num_layers)


def rel_rms_error(got, want) -> float:
    """rms(got - want) / rms(want); `got` may be sharded over the mesh, the
    reference lives on the first device."""
    want = jnp.asarray(want, jnp.float32)
    got = jax.device_put(jnp.asarray(got, jnp.float32), list(want.devices())[0])
    return float(jnp.sqrt(jnp.mean(jnp.square(got - want)) / jnp.mean(jnp.square(want))))


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rope(x, theta):
    """x [S, heads, D]: rotate each adjacent pair (2i, 2i+1) of position p by
    the angle p * theta^(-2i/D)."""
    s, _, d = x.shape
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("theta", "eps", "causal"))
def _block(x, w, *, theta: float, eps: float, causal: bool = True):
    """One decoder block on one sequence.  x [S, d] float32; w: this layer's
    weights, float32, in the program's layout (wq [d, H, D], wk/wv
    [d, Hkv, D], wo [H, D, d], w_gate/w_up [d, F], w_down [F, d])."""
    s = x.shape[0]
    h = _rms_norm(x, w["ln1"], eps)
    q = _rope(jnp.einsum("se,ehd->shd", h, w["attn"]["wq"]), theta)
    k = _rope(jnp.einsum("se,ehd->shd", h, w["attn"]["wk"]), theta)
    v = jnp.einsum("se,ehd->shd", h, w["attn"]["wv"])
    n_heads, head_dim = q.shape[1], q.shape[2]
    group = n_heads // k.shape[1]
    # query head i reads key/value head i // group
    qg = q.reshape(s, k.shape[1], group, head_dim)
    block = min(QUERY_BLOCK, s)
    assert s % block == 0, (s, block)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(qg, start, block, axis=0)
        scores = jnp.einsum("qkgd,tkd->kgqt", qb, k) / math.sqrt(head_dim)
        if causal:
            qpos = start + jnp.arange(block)[:, None]
            scores = jnp.where(jnp.arange(s)[None, :] <= qpos, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("kgqt,tkd->qkgd", probs, v).reshape(block, n_heads, head_dim)
        xb = jax.lax.dynamic_slice_in_dim(x, start, block, axis=0)
        xb = xb + jnp.einsum("qhd,hde->qe", ctx, w["attn"]["wo"])
        hb = _rms_norm(xb, w["ln2"], eps)
        ff = jax.nn.silu(hb @ w["mlp"]["w_gate"]) * (hb @ w["mlp"]["w_up"])
        return xb + ff @ w["mlp"]["w_down"]

    out = jax.lax.map(one_block, jnp.arange(0, s, block))
    return out.reshape(s, x.shape[1])


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, *, eps: float):
    return _rms_norm(x, final_norm, eps) @ head


def _local(tree):
    """The tree's arrays as float32 on ONE device.  A tree sharded over a
    mesh is first replicated by one jitted identity (an all-gather over the
    chips' interconnect, not a trip through the host) and the first device's
    copy is read."""
    leaves = jax.tree_util.tree_leaves(tree)
    shardings = [getattr(a, "sharding", None) for a in leaves]
    if any(isinstance(s, jax.sharding.NamedSharding) and s.mesh.size > 1 for s in shardings):
        mesh = next(s.mesh for s in shardings if isinstance(s, jax.sharding.NamedSharding))
        replicated = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        tree = jax.jit(lambda t: t, out_shardings=replicated)(tree)
        tree = jax.tree_util.tree_map(lambda a: a.addressable_shards[0].data, tree)
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a).astype(jnp.float32), tree)


@jax.jit
def _take_layer(layers, index):
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False), layers)


def logits(config: Dict[str, Any], params, tokens, *, last: int, causal: bool = True):
    """Reference logits [N, last, V] (float32) for the LAST `last` positions
    of each sequence of `tokens` [N, S], attending the whole context.
    `params` is the program's parameter tree (any dtype, any sharding);
    `causal=False` exists for the test that shows the tolerance catches a
    dropped mask.  Layers outside, sequences inside: each layer's weights are
    fetched and upcast once."""
    theta, eps = float(config["rope_theta"]), float(config["rms_norm_eps"])
    tokens = jnp.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        embed = _local(params["embed"]["tokens"][tokens])
        xs = [embed[i] for i in range(tokens.shape[0])]
        for layer in range(config["num_hidden_layers"]):
            w = _local(_take_layer(params["layers"], layer))
            xs = [_block(x, w, theta=theta, eps=eps, causal=causal) for x in xs]
        head = params["embed"]["tokens"].T if config.get("tie_word_embeddings") else params["lm_head"]
        head, final_norm = _local(head), _local(params["final_norm"])
        return jnp.stack([_head(x[-last:], final_norm, head, eps=eps) for x in xs])
