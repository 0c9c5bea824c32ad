"""Plain reference for kind "hybrid_decoder": Granite 4.0-H (Hugging Face
`modeling_granitemoehybrid.py`, `model_type: granitemoehybrid`) with its
Mamba-2 mixer (SSD, arXiv:2405.21060) in straightforward float32 `jax.numpy`,
one sequence at a time.  x is [S, d]; every RMSNorm has a learned scale and
`rms_norm_eps`; no bias anywhere but the convolution's.

- model: `h0 = embed[tokens] * embedding_multiplier`; the layers, each of the
  kind `layer_types[i]`; `logits = (RMSNorm(h) @ embed^T) / logits_scaling`
  (tied embeddings; an untied `lm_head` is read if the tree has one).
- every layer: `h = h + residual_multiplier * mixer(RMSNorm_1(h))`, then
  `h = h + residual_multiplier * SwiGLU(RMSNorm_2(h))`, the SwiGLU of width
  `shared_intermediate_size` (the model has no experts: `num_local_experts` 0).
- "attention": q/k/v projections, NO rotary embedding when
  `position_embedding_type` is "nope" (rotary with `rope_theta` when it is
  "rope"), causal softmax of `q k^T * attention_multiplier` in query blocks,
  grouped-query heads, output projection.
- "mamba": `in_proj: d -> [z: d_inner | xBC: d_inner + 2N | dt: heads]`;
  `xBC = silu(conv(xBC))`, the causal depthwise convolution of width
  `mamba_d_conv` written as that many SHIFTED ADDS
  (`y_t = b + sum_k w[:, k] * x_{t-(K-1)+k}`, zeros before the start); split
  into x [S, heads, P], B [S, N], C [S, N] (one group);
  `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`, and the recurrence TOKEN
  BY TOKEN, a `lax.scan` over S with the [heads, P, N] state:
  `H_t = exp(dt_t A) H_{t-1} + dt_t x_t (outer) B_t`,
  `y_t = H_t C_t + D x_t`; then `RMSNorm(y * silu(z))` over all d_inner
  channels and `out_proj`.

No chunking, no kernel, no cache, no sharding, and no import from `ray_tpu`:
it shares with the program only the layout of the parameter tree it is handed
(`layers` the attention layers' stack, `mamba_layers` the Mamba-2 ones', each
in the order the layers of its kind appear), so a wrong chunk boundary, decay
or mask in the program cannot be wrong twice.  The sizes (heads, P, N, K) are
read off the leaves' shapes.

Everything runs under `jax.default_matmul_precision("highest")`.  On the chip
`logits` streams one layer's weights at a time, upcast as they are used, and
every position of every layer is computed (the recurrence needs them all); the
head runs on the last `last` positions.  `jax.grad` of `objective` is the
reference gradient.  `tolerance(L)` is the dense reference's, unchanged.

Departures, all noted: the whole batch is packed sequences with no padding
mask and no reset of the state at a document boundary (what the program does
too; `assumed` in the configuration file); rotary pairs, where a
configuration has rotary at all, are adjacent dims as in the dense reference.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmarks.lib.reference import (QUERY_BLOCK, _head, _local, _rms_norm, _rope, _take_layer,
                                      rel_rms_error, tolerance)

__all__ = ["logits", "objective", "rel_rms_error", "tolerance"]

SUBTREE = {"attention": "layers", "mamba": "mamba_layers"}
FFN_BLOCK = 2048  # rows of the SwiGLU held at once: 2048 x 8192 float32 = 67 MB a matrix


def _ffn(x, w, *, eps: float, residual: float):
    """x + residual * SwiGLU(RMSNorm_2(x)) on one sequence, in row blocks."""
    s = x.shape[0]
    block = min(FFN_BLOCK, s)
    assert s % block == 0, (s, block)

    def one_block(xb):
        hb = _rms_norm(xb, w["ln2"], eps)
        ff = jax.nn.silu(hb @ w["mlp"]["w_gate"]) * (hb @ w["mlp"]["w_up"])
        return xb + residual * (ff @ w["mlp"]["w_down"])

    return jax.lax.map(one_block, x.reshape(s // block, block, -1)).reshape(x.shape)


def _attention(x, w, *, eps: float, residual: float, scale: float, theta: Optional[float],
               causal: bool = True):
    """x + residual * attention(RMSNorm_1(x)) on one sequence.  w: this
    layer's `attn` (wq [d, H, D], wk/wv [d, Hkv, D], wo [H, D, d]) and `ln1`."""
    s = x.shape[0]
    h = _rms_norm(x, w["ln1"], eps)
    a = w["attn"]
    q = jnp.einsum("se,ehd->shd", h, a["wq"])
    k = jnp.einsum("se,ehd->shd", h, a["wk"])
    v = jnp.einsum("se,ehd->shd", h, a["wv"])
    if theta is not None:
        q, k = _rope(q, theta), _rope(k, theta)
    n_heads, head_dim = q.shape[1], q.shape[2]
    group = n_heads // k.shape[1]
    qg = q.reshape(s, k.shape[1], group, head_dim)  # query head i reads key/value head i // group
    block = min(QUERY_BLOCK, s)
    assert s % block == 0, (s, block)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(qg, start, block, axis=0)
        scores = jnp.einsum("qkgd,tkd->kgqt", qb, k) * scale
        if causal:
            qpos = start + jnp.arange(block)[:, None]
            scores = jnp.where(jnp.arange(s)[None, :] <= qpos, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("kgqt,tkd->qkgd", probs, v).reshape(block, n_heads, head_dim)
        return jnp.einsum("qhd,hde->qe", ctx, a["wo"])

    out = jax.lax.map(one_block, jnp.arange(0, s, block)).reshape(s, x.shape[1])
    return x + residual * out


def _conv(x, w, b):
    """Causal depthwise convolution as K shifted adds: x [S, C], w [C, K]."""
    s, k = x.shape[0], w.shape[1]
    out = jnp.broadcast_to(b, x.shape)
    for i in range(k):
        shift = k - 1 - i  # w[:, i] multiplies x_{t - shift}
        shifted = jnp.concatenate([jnp.zeros((shift, x.shape[1]), x.dtype), x[: s - shift]], axis=0)
        out = out + shifted * w[:, i]
    return out


def _recurrence(x, dt, A, B, C, D):
    """Token by token.  x [S, H, P], dt [S, H], A [H], B/C [S, N], D [H] ->
    y [S, H, P]; the state [H, P, N] starts at zero."""

    def step(state, inp):
        xt, dtt, bt, ct = inp
        state = jnp.exp(dtt * A)[:, None, None] * state + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :]
        return state, jnp.sum(state * ct[None, None, :], axis=-1) + D[:, None] * xt

    n_heads, p = x.shape[1], x.shape[2]
    _, y = jax.lax.scan(step, jnp.zeros((n_heads, p, B.shape[1]), x.dtype), (x, dt, B, C))
    return y


def _mamba(x, w, *, eps: float, residual: float):
    """x + residual * mamba2(RMSNorm_1(x)) on one sequence.  w: this layer's
    `ssm` leaves and `ln1`; the sizes come from their shapes."""
    m = w["ssm"]
    s = x.shape[0]
    heads, inner = m["A_log"].shape[0], m["norm"].shape[0]
    n = (m["conv_w"].shape[0] - inner) // 2
    zxbcdt = _rms_norm(x, w["ln1"], eps) @ m["in_proj"]
    z, xbc, dt = zxbcdt[:, :inner], zxbcdt[:, inner: 2 * inner + 2 * n], zxbcdt[:, 2 * inner + 2 * n:]
    xbc = jax.nn.silu(_conv(xbc, m["conv_w"], m["conv_b"]))
    xs, b, c = xbc[:, :inner], xbc[:, inner: inner + n], xbc[:, inner + n:]
    dt = jax.nn.softplus(dt + m["dt_bias"])
    y = _recurrence(xs.reshape(s, heads, inner // heads), dt, -jnp.exp(m["A_log"]), b, c, m["D"])
    y = _rms_norm(y.reshape(s, inner) * jax.nn.silu(z), m["norm"], eps)
    return x + residual * (y @ m["out_proj"])


def _facts(config: Dict[str, Any]):
    """(kinds of the layers, keyword arguments of the two mixers and the FFN)."""
    kinds = list(config["layer_types"])[: config["num_hidden_layers"]]
    eps, residual = float(config["rms_norm_eps"]), float(config["residual_multiplier"])
    theta = None if config["position_embedding_type"] == "nope" else float(config["rope_theta"])
    attention = dict(eps=eps, residual=residual, scale=float(config["attention_multiplier"]), theta=theta)
    return kinds, attention, dict(eps=eps, residual=residual)


def _output_head(config, params):
    tied = config.get("tie_word_embeddings") or "lm_head" not in params
    return params["embed"]["tokens"].T if tied else params["lm_head"]


# -- the forward on the chip: layers streamed ---------------------------------------

_attention_jit = jax.jit(_attention, static_argnames=("eps", "residual", "scale", "theta", "causal"))
_mamba_jit = jax.jit(_mamba, static_argnames=("eps", "residual"))
_ffn_jit = jax.jit(_ffn, static_argnames=("eps", "residual"))


@functools.partial(jax.jit, static_argnames=("divisor",))
def _divide(x, *, divisor: float):
    return x / divisor


def logits(config: Dict[str, Any], params, tokens, *, last: int, causal: bool = True):
    """Reference logits [N, last, V] (float32) for the LAST `last` positions
    of each sequence of `tokens` [N, S], every position of every layer
    computed.  `params` is the program's parameter tree (any dtype, any
    sharding).  Layers outside, sequences inside: each layer's weights are
    fetched and upcast once."""
    kinds, attention, common = _facts(config)
    tokens = jnp.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        embed = _local(params["embed"]["tokens"][tokens]) * float(config["embedding_multiplier"])
        xs = [embed[i] for i in range(tokens.shape[0])]
        seen = dict.fromkeys(SUBTREE, 0)
        for kind in kinds:
            w = _local(_take_layer(params[SUBTREE[kind]], seen[kind]))
            seen[kind] += 1
            if kind == "attention":
                xs = [_attention_jit(x, w, causal=causal, **attention) for x in xs]
            else:
                xs = [_mamba_jit(x, w, **common) for x in xs]
            xs = [_ffn_jit(x, w, **common) for x in xs]
        head, final_norm = _local(_output_head(config, params)), _local(params["final_norm"])
        out = jnp.stack([_head(x[-last:], final_norm, head, eps=common["eps"]) for x in xs])
        return _divide(out, divisor=float(config["logits_scaling"]))


# -- the training objective: one pure function, for jax.grad ------------------------


def objective(config: Dict[str, Any], params, tokens, targets):
    """Mean next-token cross entropy on tokens/targets [N, S], float32
    throughout, nothing streamed.  `params` must be float32."""
    kinds, attention, common = _facts(config)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens] * float(config["embedding_multiplier"])  # [N, S, d]
        seen = dict.fromkeys(SUBTREE, 0)
        for kind in kinds:
            w = jax.tree_util.tree_map(lambda a, i=seen[kind]: a[i], params[SUBTREE[kind]])
            seen[kind] += 1
            if kind == "attention":
                x = jax.vmap(lambda xi: _attention(xi, w, **attention))(x)
            else:
                x = jax.vmap(lambda xi: _mamba(xi, w, **common))(x)
            x = jax.vmap(lambda xi: _ffn(xi, w, **common))(x)
        out = _rms_norm(x, params["final_norm"], common["eps"]) @ _output_head(config, params)
        out = out / float(config["logits_scaling"])
        logp = jax.nn.log_softmax(out, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
