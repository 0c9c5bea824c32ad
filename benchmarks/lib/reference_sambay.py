"""Plain reference for kind "sambay_decoder": Phi-4-mini-flash-reasoning
(`model_type: phi4flash`; the SambaY decoder-hybrid-decoder, arXiv:2507.06607;
differential attention, arXiv:2410.05258; Mamba, arXiv:2312.00752) in
straightforward float32 `jax.numpy`, one sequence at a time.  x is [S, d]; no
positional encoding anywhere; every norm of the stream is a LayerNorm (mean
and variance, learned scale and bias, `layer_norm_eps`).

- model: `h0 = embed[tokens]`; the layers; `logits = LN_f(h) @ embed.T` (tied).
- every layer: `h = x + Mixer(LN1(x))`, then
  `y = h + W_down(silu(W_gate u) * (W_up u))` with `u = LN2(h)`, no bias.
- the mixer follows from the layer's PUBLISHED index l (`layer_indices`):
  even l <= 16 Mamba-1; odd l < 16 differential attention with the window
  `sliding_window`; 17 differential attention, full causal; even l > 17 a Gated
  Memory Unit; odd l > 17 differential cross-attention.
- Mamba-1, u = LN1(x): `[x | z] = u W_in`; `x = silu(conv(x))`, a causal
  depthwise convolution with bias written as SHIFTED ADDS, zeros before the
  start; `[dt_low | B | C] = x W_x`; `dt = softplus(dt_low W_dt + b_dt)`;
  `A = -exp(A_log)`; the recurrence TOKEN BY TOKEN, a `lax.scan` over S with
  the state [channels, N] from zero:
  `h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t`, `y_t = h_t C_t + D x_t`;
  `out = (y * silu(z)) W_out`.  At index 16, `M = y` is kept for the GMUs.
- GMU: `out = (M * silu(u W_1)) W_2`.
- differential attention: `[q | k | v] = u W_qkv + b_qkv` in heads of 64
  (`HEAD_DIM`); q heads (2p, 2p+1) are (q1, q2) of pair p; with G = q pairs /
  kv pairs, kv pair j = p // G has k heads (2j, 2j+1) = (k1, k2) and the value
  `[v_2j | v_2j+1]` of width 128.  `a1 = softmax(q1 k1^T / 8 + mask) v`, `a2`
  from (q2, k2), dense masks in query blocks; `lambda = exp(lq1 . lk1) -
  exp(lq2 . lk2) + lambda_init`, `lambda_init = 0.8 - 0.6 exp(-0.3 l)`;
  `o = RMSNorm_128(a1 - lambda a2) * (1 - lambda_init)` (one learned scale a
  layer, eps `layer_norm_eps`), two heads of 64 again; `out = o W_o + b_o`.
  With a window w query i sees keys i - w + 1 .. i.  At index 17 k and v
  (after the bias) are kept for the cross layers, which have `W_q`, `b_q`,
  `W_o`, `b_o`, lambda and norm of their own.

No chunking, no kernel, no cache, no sharding, and no import from `ray_tpu`:
it shares with the program only the layout of the parameter tree it is handed
(`s6_layers`, `diff_layers`, `gmu_layers`, `cross_layers`: one stack per kind
of mixer, each in the order its layers appear), so a wrong chunk boundary,
decay, tile, window, pairing or sum of cotangents in the program cannot be
wrong twice.

Everything runs under `jax.default_matmul_precision("highest")`.  On the chip
`logits` streams one layer's weights at a time, upcast as they are used, and
every position of every layer is computed (the recurrence needs them all); the
head runs on the last `last` positions.  `jax.grad` of `objective` is the
reference gradient.  `tolerance(L)` is the dense reference's, unchanged.

Departures, all noted: the whole batch is packed sequences with no padding
mask and no reset of the scan's state, the convolution or the memory at a
document boundary (what the program does too; `assumed` in the configuration
file).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from benchmarks.lib.reference import QUERY_BLOCK, _local, _take_layer, rel_rms_error, tolerance

__all__ = ["logits", "objective", "layer_kinds", "heads", "lambda_init", "rel_rms_error", "tolerance"]

HEAD_DIM = 64  # not a key of the source: hidden_size / num_attention_heads at the published sizes
MEMORY_LAYER, KV_LAYER = 16, 17  # published indices: the Mamba-1 layer the GMUs read, the attention layer the cross layers read
STACKS = {"s6": "s6_layers", "diff_attention": "diff_layers", "gmu": "gmu_layers", "diff_cross": "cross_layers"}


def layer_kinds(config: Dict[str, Any]) -> List[Tuple[str, int]]:
    """(kind, published index) of the layers that run: the first
    `num_hidden_layers` entries of `layer_indices`."""
    indices = list(config["layer_indices"])[: config["num_hidden_layers"]]
    if len(indices) != config["num_hidden_layers"]:
        raise ValueError("layer_indices is shorter than num_hidden_layers")
    kinds = []
    for l in indices:
        if l % 2 == 0:
            kinds.append(("s6" if l <= MEMORY_LAYER else "gmu", l))
        else:
            kinds.append(("diff_attention" if l <= KV_LAYER else "diff_cross", l))
    return kinds


def heads(config: Dict[str, Any]) -> Tuple[int, int]:
    """(q heads, kv heads) at the published head size 64 and the published
    ratio: 40 and 20 at the published width; what follows the width in a
    rehearsal, whose 2 / 1 heads could not pair."""
    q = config["hidden_size"] // HEAD_DIM
    return q, q // (config["num_attention_heads"] // config["num_key_value_heads"])


def lambda_init(index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def _layer_norm(x, weight, bias, eps):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(jnp.mean(centred * centred, axis=-1, keepdims=True) + eps) * weight + bias


def _ffn(x, w, eps):
    u = _layer_norm(x, w["ln2"], w["ln2_b"], eps)
    return x + (jax.nn.silu(u @ w["mlp"]["w_gate"]) * (u @ w["mlp"]["w_up"])) @ w["mlp"]["w_down"]


def _conv_silu(x, weight, bias):
    """x [S, C]; weight [C, K]; `weight[:, K-1]` multiplies `x_t`."""
    s, width = x.shape[0], weight.shape[1]
    padded = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), x.dtype), x], axis=0)
    return jax.nn.silu(bias + sum(padded[i: i + s] * weight[:, i] for i in range(width)))


def _s6(x, w, *, eps):
    """One Mamba-1 layer's mixer half: (x + out, the scan's output y [S, channels])."""
    m = w["s6"]
    u = _layer_norm(x, w["ln1"], w["ln1_b"], eps)
    xs, z = jnp.split(u @ m["in_proj"], 2, axis=-1)
    xs = _conv_silu(xs, m["conv_w"], m["conv_b"])
    rank, n = m["dt_proj"].shape[0], m["A_log"].shape[1]
    low = xs @ m["x_proj"]
    dt = jax.nn.softplus(low[:, :rank] @ m["dt_proj"] + m["dt_bias"])
    A = -jnp.exp(m["A_log"])  # [channels, N]

    def step(h, inp):
        x_t, dt_t, B_t, C_t = inp
        h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * x_t)[:, None] * B_t[None, :]
        return h, h @ C_t + m["D"] * x_t

    _, y = jax.lax.scan(step, jnp.zeros_like(A), (xs, dt, low[:, rank: rank + n], low[:, rank + n:]))
    return x + (y * jax.nn.silu(z)) @ m["out_proj"], y


def _gmu(x, w, memory, *, eps):
    u = _layer_norm(x, w["ln1"], w["ln1_b"], eps)
    return x + (memory * jax.nn.silu(u @ w["gmu"]["w1"])) @ w["gmu"]["w2"]


def _diff(x, w, kv, *, eps, index: int, window: Optional[int], q_heads: int, kv_heads: int, causal: bool = True):
    """One differential (cross-)attention layer's mixer half: (x + out, k, v).
    `kv` None: the layer's own keys and values; else the (k, v) it reads."""
    m = w["diff"]
    s = x.shape[0]
    u = _layer_norm(x, w["ln1"], w["ln1_b"], eps)
    if kv is None:
        proj = u @ m["wqkv"] + m["bqkv"]
        q, k, v = jnp.split(proj, [q_heads * HEAD_DIM, (q_heads + kv_heads) * HEAD_DIM], axis=-1)
        k, v = k.reshape(s, kv_heads, HEAD_DIM), v.reshape(s, kv_heads, HEAD_DIM)
    else:
        q, (k, v) = u @ m["wq"] + m["bq"], kv
    pairs, kv_pairs = q_heads // 2, kv_heads // 2
    group = pairs // kv_pairs
    qp = q.reshape(s, kv_pairs, group, 2, HEAD_DIM)  # [.., kv pair j, pair within it, which map, 64]
    kp = k.reshape(s, kv_pairs, 2, HEAD_DIM)  # [.., kv pair j, which map, 64]
    vp = v.reshape(s, kv_pairs, 2 * HEAD_DIM)
    init = lambda_init(index)
    lam = jnp.exp(jnp.sum(m["lambda_q1"] * m["lambda_k1"])) - jnp.exp(jnp.sum(m["lambda_q2"] * m["lambda_k2"])) + init
    block = min(QUERY_BLOCK, s)
    assert s % block == 0, (s, block)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(qp, start, block, axis=0)
        scores = jnp.einsum("qjgwd,tjwd->jgwqt", qb, kp) / math.sqrt(HEAD_DIM)
        if causal:
            ahead = (start + jnp.arange(block))[:, None] - jnp.arange(s)[None, :]  # query - key
            seen = ahead >= 0
            if window is not None:
                seen = seen & (ahead < window)
            scores = jnp.where(seen, scores, -jnp.inf)
        a = jnp.einsum("jgwqt,tjc->qjgwc", jax.nn.softmax(scores, axis=-1), vp)
        o = a[..., 0, :] - lam * a[..., 1, :]  # [q, j, g, 128]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * m["subln"] * (1.0 - init)
        return o.reshape(block, q_heads * HEAD_DIM) @ m["wo"] + m["bo"]

    out = jax.lax.map(one_block, jnp.arange(0, s, block)).reshape(s, x.shape[1])
    return x + out, k, v


def _facts(config: Dict[str, Any]):
    q_heads, kv_heads = heads(config)
    return layer_kinds(config), dict(q_heads=q_heads, kv_heads=kv_heads), float(config["layer_norm_eps"])


def _mixer(kind: str, index: int, x, w, carried: Dict[str, Any], *, eps, window, attn, causal=True, fns=None):
    """x after layer `index`'s mixer; `carried` gains what the layer hands on.
    `fns`: the three mixers to call (`logits` passes their jitted forms)."""
    s6, gmu, diff = fns or (_s6, _gmu, _diff)
    if kind == "s6":
        x, y = s6(x, w, eps=eps)
        if index == MEMORY_LAYER:
            carried["memory"] = y
    elif kind == "gmu":
        x = gmu(x, w, carried["memory"], eps=eps)
    else:
        x, k, v = diff(x, w, carried["kv"] if kind == "diff_cross" else None, eps=eps, index=index, causal=causal,
                       window=window if index < MEMORY_LAYER else None, **attn)
        if index == KV_LAYER:
            carried["kv"] = (k, v)
    return x


# -- the forward on the chip: layers streamed ---------------------------------------

_JITTED = (jax.jit(_s6, static_argnames=("eps",)), jax.jit(_gmu, static_argnames=("eps",)),
           jax.jit(_diff, static_argnames=("eps", "index", "window", "q_heads", "kv_heads", "causal")))
_ffn_jit = jax.jit(_ffn, static_argnames=("eps",))


@jax.jit
def _head(x, weight, bias, table, eps):
    return _layer_norm(x, weight, bias, eps) @ table.T


def logits(config: Dict[str, Any], params, tokens, *, last: int, causal: bool = True):
    """Reference logits [N, last, V] (float32) for the LAST `last` positions
    of each sequence of `tokens` [N, S], every position of every layer
    computed.  `params` is the program's parameter tree (any dtype, any
    sharding).  Layers outside, sequences inside: each layer's weights are
    fetched and upcast once; what a layer hands on is kept per sequence."""
    kinds, attn, eps = _facts(config)
    window = config["sliding_window"]
    tokens = jnp.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        embed = _local(params["embed"]["tokens"][tokens])
        xs = [embed[i] for i in range(tokens.shape[0])]
        carried: List[Dict[str, Any]] = [{} for _ in xs]
        seen: Dict[str, int] = {}
        for kind, index in kinds:
            w = _local(_take_layer(params[STACKS[kind]], seen.get(kind, 0)))
            seen[kind] = seen.get(kind, 0) + 1
            for i, x in enumerate(xs):
                x = _mixer(kind, index, x, w, carried[i], eps=eps, window=window, attn=attn, causal=causal, fns=_JITTED)
                xs[i] = _ffn_jit(x, w, eps=eps)
        table = _local(params["embed"]["tokens"])
        norm, bias = _local(params["final_norm"]), _local(params["final_norm_b"])
        return jnp.stack([_head(x[-last:], norm, bias, table, eps) for x in xs])


# -- the training objective: one pure function, for jax.grad ------------------------


def objective(config: Dict[str, Any], params, tokens, targets):
    """Mean next-token cross entropy on tokens/targets [N, S], float32
    throughout, nothing streamed.  `params` must be float32."""
    kinds, attn, eps = _facts(config)
    window = config["sliding_window"]

    def one_sequence(x):
        carried: Dict[str, Any] = {}
        seen: Dict[str, int] = {}
        for kind, index in kinds:
            w = jax.tree_util.tree_map(lambda a, i=seen.get(kind, 0): a[i], params[STACKS[kind]])
            seen[kind] = seen.get(kind, 0) + 1
            x = _ffn(_mixer(kind, index, x, w, carried, eps=eps, window=window, attn=attn), w, eps)
        return _layer_norm(x, params["final_norm"], params["final_norm_b"], eps) @ params["embed"]["tokens"].T

    with jax.default_matmul_precision("highest"):
        out = jax.vmap(one_sequence)(params["embed"]["tokens"][tokens])
        logp = jax.nn.log_softmax(out, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
