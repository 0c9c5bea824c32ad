"""Plain reference for kind "kimi_linear_decoder": Kimi Linear ("Kimi Linear",
arXiv:2510.26692; Hugging Face `model_type: kimi_linear`) in straightforward
float32 `jax.numpy`, one sequence at a time.  x is [S, d]; every RMSNorm has a
learned scale and `rms_norm_eps`; no bias and NO rotary embedding anywhere
(KDA carries position in its decay, `mla_use_nope` is true).

- model: `h0 = embed[tokens]`; the layers; `logits = RMSNorm(h) @ lm_head`.
- every layer: `h = h + mixer(RMSNorm_1(h))`, then `h = h + FFN(RMSNorm_2(h))`.
  Layer i (1-based, as the published lists count) has the mixer "kda" if i is
  in `linear_attn_config.kda_layers`, "mla" if in `full_attn_layers`; its FFN is
  a dense SwiGLU of `intermediate_size` for i <= `first_k_dense_replace`,
  the expert layer after that.
- "kda" (H heads of D = `linear_attn_config.head_dim` for q, k and v):
  `[q | k | v] = silu(conv(x W_qkv))`, three causal depthwise convolutions of
  width `short_conv_kernel_size` written as that many SHIFTED ADDS, zeros
  before the start; per head `q <- q / |q|_2 * D^-0.5`, `k <- k / |k|_2`
  (`x / sqrt(sum x^2 + 1e-6)`); the log decay
  `g = -exp(A_log[h]) * softplus((x W_f_down) W_f_up + dt_bias)` per channel;
  `beta = sigmoid(x W_beta)` per head; the recurrence TOKEN BY TOKEN, a
  `lax.scan` over S with the state [H, D, D] (keys x values) from zero:
  `S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T`,
  `o_t = S_t^T q_t`; `o <- RMSNorm_head(o) * sigmoid((x W_g_down) W_g_up)`
  (norm over each head's D, one learned scale [D]); `W_o`.
- "mla": `q = x W_q -> [H, nope + rope]`; `[c | k_pe] = x W_kva`;
  `c <- RMSNorm(c)`; `[k_nope | v] = c W_kvb -> [H, nope | v]`;
  `k = [k_nope | k_pe]` with the one `k_pe` shared by the heads, nothing
  rotated; causal softmax of `q k^T * (nope + rope)^-0.5` in query blocks;
  `W_o`.
- expert layer: `s = sigmoid(x W_r)` over all `share.num_experts_total` experts;
  the choice is the top `num_experts_per_token` of `s + b` (b the stored
  `e_score_correction_bias`; one group, so no group step); the gate values are
  the chosen s, renormalised to sum to one (`moe_renormalize`), times
  `routed_scaling_factor`; `y = sum_i w_i SwiGLU_{choice_i}(x) +
  SwiGLU_shared(x)`.  The tree holds the experts `first .. first + held` only
  (one rank's share of an expert-parallel deployment): the sum runs over the
  chosen experts that are HELD, and what the absent ones would have added is
  left out, here as in the program.  `first` is `share.first_expert_held`,
  `held` is read off the leaves' shapes.

No chunking, no kernel, no cache, no sharding, and no import from `ray_tpu`:
it shares with the program only the layout of the parameter tree it is handed
(`kda_layers_dense`, `kda_layers_experts`, `mla_layers`: one stack per pair
of mixer and FFN, each in the order its layers appear; a mixer paired with one
kind of FFN only has the stack `kda_layers` / `mla_layers`), so a wrong chunk
boundary, decay, solve or mask in the program cannot be wrong twice.

Everything runs under `jax.default_matmul_precision("highest")`.  On the chip
`logits` streams one layer's weights at a time, upcast as they are used, and
every position of every layer is computed (the recurrence needs them all); the
head runs on the last `last` positions.  `jax.grad` of `objective` is the
reference gradient.  `tolerance(L)` is the dense reference's, unchanged.

Departures, all noted: the whole batch is packed sequences with no padding
mask and no reset of the KDA state or the convolutions at a document boundary
(what the program does too; `assumed` in the configuration file).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from benchmarks.lib.reference import QUERY_BLOCK, _head, _local, _rms_norm, _take_layer, rel_rms_error, tolerance

__all__ = ["logits", "objective", "layer_pairs", "rel_rms_error", "tolerance"]

ROW_BLOCK = 2048  # rows of a SwiGLU held at once
L2_EPS = 1e-6


def layer_pairs(config: Dict[str, Any]) -> List[Tuple[str, str]]:
    """(mixer, FFN) of the layers that run: the first `num_hidden_layers` of
    the published lists, which count layers from 1."""
    linear = config["linear_attn_config"]
    pairs = []
    for i in range(1, config["num_hidden_layers"] + 1):
        if i in linear["kda_layers"]:
            mixer = "kda"
        elif i in linear["full_attn_layers"]:
            mixer = "mla"
        else:
            raise ValueError(f"layer {i} is in neither kda_layers nor full_attn_layers")
        pairs.append((mixer, "dense" if i <= config["first_k_dense_replace"] else "experts"))
    return pairs


def stack_name(pairs: List[Tuple[str, str]], mixer: str, ffn: str) -> str:
    """The program's layout: `<mixer>_layers`, with `_<ffn>` when the model
    pairs that mixer with both kinds of FFN."""
    both = len({f for m, f in pairs if m == mixer}) > 1
    return f"{mixer}_layers_{ffn}" if both else f"{mixer}_layers"


def _swiglu(h, w):
    return (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def _in_row_blocks(fn, x):
    s = x.shape[0]
    block = min(ROW_BLOCK, s)
    assert s % block == 0, (s, block)
    return jax.lax.map(fn, x.reshape(s // block, block, -1)).reshape(s, -1)


def _dense_ffn(x, w, *, eps: float):
    """x + SwiGLU(RMSNorm_2(x)) on one sequence, in row blocks."""
    return _in_row_blocks(lambda xb: xb + _swiglu(_rms_norm(xb, w["ln2"], eps), w["mlp"]), x)


def _route(h, router, bias, *, top_k: int, renormalize: bool, scaling: float):
    """h [T, d] -> the gate values as a dense [T, E] weight, 0 where not chosen."""
    scores = jax.nn.sigmoid(h @ router)
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    if renormalize:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(chosen, router.shape[1], dtype=h.dtype)
    return jnp.sum(onehot * (gates * scaling)[..., None], axis=1)


def _expert_ffn(x, w, *, eps: float, first: int, top_k: int, renormalize: bool, scaling: float):
    """x + (the held experts' part of the routed sum + the shared expert) of
    RMSNorm_2(x), in row blocks: every held expert on every row, masked by who
    chose it."""
    mlp = w["mlp"]
    held = mlp["w_gate"].shape[0]

    def one_block(xb):
        h = _rms_norm(xb, w["ln2"], eps)
        weight = _route(h, mlp["router"], mlp["router_bias"], top_k=top_k, renormalize=renormalize,
                        scaling=scaling)[:, first: first + held]
        inner = jax.nn.silu(jnp.einsum("td,ndf->ntf", h, mlp["w_gate"])) * jnp.einsum("td,ndf->ntf", h, mlp["w_up"])
        routed = jnp.einsum("ntd,tn->td", jnp.einsum("ntf,nfd->ntd", inner, mlp["w_down"]), weight)
        return xb + routed + _swiglu(h, mlp["shared"])

    return _in_row_blocks(one_block, x)


def _conv(x, w):
    """Causal depthwise convolution as K shifted adds, no bias: x [S, C], w [C, K]."""
    s, k = x.shape[0], w.shape[1]
    out = jnp.zeros_like(x)
    for i in range(k):
        shift = k - 1 - i  # w[:, i] multiplies x_{t - shift}
        shifted = jnp.concatenate([jnp.zeros((shift, x.shape[1]), x.dtype), x[: s - shift]], axis=0)
        out = out + shifted * w[:, i]
    return out


def _l2_normed(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _delta_rule(q, k, v, g, beta):
    """Token by token.  q, k, g [S, H, K], v [S, H, V], beta [S, H] ->
    o [S, H, V]; the state [H, K, V] starts at zero."""

    def step(state, inp):
        qt, kt, vt, gt, bt = inp
        state = jnp.exp(gt)[:, :, None] * state  # Diag(alpha_t) S_{t-1}
        read = jnp.einsum("hk,hkv->hv", kt, state)
        state = state + bt[:, None, None] * kt[:, :, None] * (vt - read)[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", qt, state)

    heads, dk = k.shape[1], k.shape[2]
    _, o = jax.lax.scan(step, jnp.zeros((heads, dk, v.shape[2]), q.dtype), (q, k, v, g, beta))
    return o


def _kda(x, w, *, eps: float):
    """x + kda(RMSNorm_1(x)) on one sequence.  w: this layer's `kda` leaves and
    `ln1`; the sizes come from their shapes."""
    m = w["kda"]
    s = x.shape[0]
    heads, dim = m["A_log"].shape[0], m["norm"].shape[0]
    h = _rms_norm(x, w["ln1"], eps)
    q, k, v = (a.reshape(s, heads, dim) for a in jnp.split(jax.nn.silu(_conv(h @ m["wqkv"], m["conv_w"])), 3, axis=-1))
    q, k = _l2_normed(q) * dim ** -0.5, _l2_normed(k)
    g = -jnp.exp(m["A_log"])[:, None] * jax.nn.softplus((h @ m["f_down"]) @ m["f_up"] + m["dt_bias"]).reshape(s, heads, dim)
    beta = jax.nn.sigmoid(h @ m["w_beta"])
    o = _delta_rule(q, k, v, g, beta)
    gate = jax.nn.sigmoid((h @ m["g_down"]) @ m["g_up"]).reshape(s, heads, dim)
    o = _rms_norm(o, m["norm"], eps) * gate
    return x + o.reshape(s, heads * dim) @ m["wo"]


def _mla(x, w, *, eps: float, causal: bool = True):
    """x + mla(RMSNorm_1(x)) on one sequence, queries in blocks.  w: this
    layer's `mla` leaves (wq [d, H, nope + rope], w_kva [d, rank + rope],
    kv_norm [rank], w_kvb [rank, H, nope + v], wo [H, v, d]) and `ln1`."""
    m = w["mla"]
    s = x.shape[0]
    rank = m["kv_norm"].shape[0]
    h = _rms_norm(x, w["ln1"], eps)
    q = jnp.einsum("se,ehd->shd", h, m["wq"])
    latent = h @ m["w_kva"]
    k_pe = latent[:, rank:]
    nope = q.shape[2] - k_pe.shape[1]
    kv = jnp.einsum("sr,rhd->shd", _rms_norm(latent[:, :rank], m["kv_norm"], eps), m["w_kvb"])
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe[:, None, :], (s, q.shape[1], k_pe.shape[1]))], axis=-1)
    v = kv[..., nope:]
    scale = q.shape[2] ** -0.5
    block = min(QUERY_BLOCK, s)
    assert s % block == 0, (s, block)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        scores = jnp.einsum("qhd,thd->hqt", qb, k) * scale
        if causal:
            qpos = start + jnp.arange(block)[:, None]
            scores = jnp.where(jnp.arange(s)[None, :] <= qpos, scores, -jnp.inf)
        ctx = jnp.einsum("hqt,thd->qhd", jax.nn.softmax(scores, axis=-1), v)
        return jnp.einsum("qhd,hde->qe", ctx, m["wo"])

    return x + jax.lax.map(one_block, jnp.arange(0, s, block)).reshape(s, x.shape[1])


def _facts(config: Dict[str, Any]):
    """(pairs of the layers, the expert layer's keyword arguments, eps)."""
    experts = dict(first=int(config["share"]["first_expert_held"]), top_k=config["num_experts_per_token"],
                   renormalize=bool(config["moe_renormalize"]), scaling=float(config["routed_scaling_factor"]))
    if config["moe_router_activation_func"] != "sigmoid" or config["num_expert_group"] != 1:
        raise ValueError("the reference routes by sigmoid scores in one group")
    return layer_pairs(config), experts, float(config["rms_norm_eps"])


# -- the forward on the chip: layers streamed ---------------------------------------

_kda_jit = jax.jit(_kda, static_argnames=("eps",))
_mla_jit = jax.jit(_mla, static_argnames=("eps", "causal"))
_dense_ffn_jit = jax.jit(_dense_ffn, static_argnames=("eps",))
_expert_ffn_jit = jax.jit(_expert_ffn, static_argnames=("eps", "first", "top_k", "renormalize", "scaling"))


def logits(config: Dict[str, Any], params, tokens, *, last: int, causal: bool = True):
    """Reference logits [N, last, V] (float32) for the LAST `last` positions
    of each sequence of `tokens` [N, S], every position of every layer
    computed.  `params` is the program's parameter tree (any dtype, any
    sharding).  Layers outside, sequences inside: each layer's weights are
    fetched and upcast once."""
    pairs, experts, eps = _facts(config)
    tokens = jnp.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        embed = _local(params["embed"]["tokens"][tokens])
        xs = [embed[i] for i in range(tokens.shape[0])]
        seen: Dict[Tuple[str, str], int] = {}
        for mixer, ffn in pairs:
            w = _local(_take_layer(params[stack_name(pairs, mixer, ffn)], seen.get((mixer, ffn), 0)))
            seen[mixer, ffn] = seen.get((mixer, ffn), 0) + 1
            if mixer == "kda":
                xs = [_kda_jit(x, w, eps=eps) for x in xs]
            else:
                xs = [_mla_jit(x, w, eps=eps, causal=causal) for x in xs]
            if ffn == "dense":
                xs = [_dense_ffn_jit(x, w, eps=eps) for x in xs]
            else:
                xs = [_expert_ffn_jit(x, w, eps=eps, **experts) for x in xs]
        head, final_norm = _local(params["lm_head"]), _local(params["final_norm"])
        return jnp.stack([_head(x[-last:], final_norm, head, eps=eps) for x in xs])


# -- the training objective: one pure function, for jax.grad ------------------------


def objective(config: Dict[str, Any], params, tokens, targets):
    """Mean next-token cross entropy on tokens/targets [N, S] (the model has
    no auxiliary loss: both coefficients are 0), float32 throughout, nothing
    streamed.  `params` must be float32."""
    pairs, experts, eps = _facts(config)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens]  # [N, S, d]
        seen: Dict[Tuple[str, str], int] = {}
        for mixer, ffn in pairs:
            index = seen.get((mixer, ffn), 0)
            seen[mixer, ffn] = index + 1
            w = jax.tree_util.tree_map(lambda a, i=index: a[i], params[stack_name(pairs, mixer, ffn)])
            mix = functools.partial(_kda if mixer == "kda" else _mla, eps=eps)
            feed = functools.partial(_dense_ffn, eps=eps) if ffn == "dense" else functools.partial(
                _expert_ffn, eps=eps, **experts)
            x = jax.vmap(lambda xi: feed(mix(xi, w), w))(x)
        out = _rms_norm(x, params["final_norm"], eps) @ params["lm_head"]
        logp = jax.nn.log_softmax(out, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
