"""Plain reference for kind "sparse_mla_moe_decoder" (dots3-note-prev), in
straightforward float32 `jax.numpy`, written from the config's keys and the
three published descriptions they point at (DeepSeek-V3 / V3.2-Exp for latent
attention, the indexer and its KL term; LongCat-Flash for the latents' rescale;
Gated Attention, arXiv:2505.06708, for the head-wise gate).  One sequence x
[S, d], u = RMSNorm_1(x), per layer:

  c_q = s_q RMSNorm(u W_qa), q = c_q W_qb -> H x (nope | rope);
  [c | k_pe] = u W_kva, c <- s_kv RMSNorm(c), [k_nope | v] = c W_kvb;
  s_q = (d / q_rank)^0.5, s_kv = (d / kv_rank)^0.5; the rope parts rotated by
  the kind's theta, adjacent pairs; scale (nope + rope)^-0.5;
  g = sigmoid(u W_g) [S, H]; x + sum_h g[t, h] o[t, h] W_o[h].

A SLIDING layer (`mla_window` leaves): o over the keys 0 <= t - s < window.
A FULL layer (`mla_sparse` leaves): q^I = sg(c_q) W^I_q -> J x D_I, k^I =
LayerNorm(sg(u) W^I_k), the first `rope` dims of both rotated, w = sg(u) W^I_w
(J D_I)^-0.5; I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s]); S_t the causal
keys that score no less than the min(t + 1, topk)-th largest causal score of
the row (found with a SORT: the program's radix select is not used here); o
over exactly S_t; and the layer's term of the objective, mean_t KL(p_t ||
softmax_{S_t}(I[t, .])) with p_t = sg(mean over the heads of the attention
probabilities).  Then the FFN half, which is GLM-4.7-Flash's to the letter
(`reference_glm_moe_lite._ffn`: a dense SwiGLU, or the held experts' part of a
sigmoid top-k routed sum plus the shared expert).

No kernel, cache, sharding or remat, and no import from `ray_tpu`: it shares
with the program only the layout of the parameter tree (`mla_sparse_layers_dense`,
`mla_sparse_layers_experts`, `mla_window_layers`; a stack is `<kind>_layers`
where the kind has one FFN).  Everything runs under
`jax.default_matmul_precision("highest")`; on the chip `logits` streams one
layer's weights at a time, queries in blocks.

THE CONTROLS (`wrong=`, one name): what a program that got one mechanism wrong
would compute, each of which the comparison must see (tests/test_dots3_note_
model.py on the CPU, `scripts/dsa_control.py` on the chip):
`dense_causal` (every causal key in place of S_t), `no_index_weights` (w = 1),
`no_relu`, `no_gate` (g = 1), `window_short` (one key less: 512 for 513),
`no_rescale` (s_q = s_kv = 1), `sliding_full_ranks` (a sliding layer's s_kv from
the FULL kind's kv rank).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from benchmarks.lib.reference import QUERY_BLOCK, _local, _rms_norm, _take_layer, rel_rms_error, tolerance  # noqa: F401 (the last two for callers)
from benchmarks.lib.reference_glm_moe_lite import _ffn, _rotate

__all__ = ["logits", "loss", "layer_stacks", "rel_rms_error", "tolerance", "WRONG"]

WRONG = ("dense_causal", "no_index_weights", "no_relu", "no_gate", "window_short", "no_rescale", "sliding_full_ranks")
FULL, SLIDING = "full_attention", "sliding_attention"
SUBTREE = {FULL: "mla_sparse", SLIDING: "mla_window"}
INDEX_BLOCK = 256  # queries whose [J, block, S] float32 products are held at once
sg = jax.lax.stop_gradient


def layer_stacks(config: Dict[str, Any]) -> List[str]:
    """The program's stack of each layer that runs: `<kind>_layers`, with `_<ffn>` where the kind meets both kinds of FFN."""
    n = config["num_hidden_layers"]
    kinds = config["layer_types"][:n]
    ffns = ["dense" if i < config["first_k_dense_replace"] else "experts" for i in range(n)]
    both = {k: len({f for kk, f in zip(kinds, ffns) if kk == k}) > 1 for k in set(kinds)}
    return [f"{SUBTREE[k]}_layers" + (f"_{f}" if both[k] else "") for k, f in zip(kinds, ffns)]


def _blocked(fn, s: int, block: int):
    block = min(block, s)
    assert s % block == 0, (s, block)
    out = jax.lax.map(fn, jnp.arange(0, s, block))
    return jax.tree_util.tree_map(lambda a: a.reshape(s, *a.shape[2:]), out)


def _layer_norm(x, weight, bias, eps):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(jnp.mean(centred * centred, axis=-1, keepdims=True) + eps) * weight + bias


def _selection(u, c_q, m, *, theta: float, rope: int, topk: int, eps: float, wrong: Optional[str]):
    """(I [S, S], the mask of S_t [S, S] bool) of a full layer."""
    s = u.shape[0]
    qi = jnp.einsum("sr,rjd->sjd", sg(c_q), m["wi_q"])
    ki = _layer_norm(sg(u) @ m["wi_k"], m["ki_norm"], m["ki_norm_b"], eps)[:, None]
    qi = jnp.concatenate([_rotate(qi[..., :rope], theta), qi[..., rope:]], axis=-1)
    ki = jnp.concatenate([_rotate(ki[..., :rope], theta), ki[..., rope:]], axis=-1)[:, 0]
    w = (sg(u) @ m["wi_w"]) * (qi.shape[1] * qi.shape[2]) ** -0.5
    if wrong == "no_index_weights":
        w = jnp.ones_like(w)

    def scores_of(start):
        z = jnp.einsum("qjd,sd->qjs", jax.lax.dynamic_slice_in_dim(qi, start, min(INDEX_BLOCK, s)), ki)
        z = z if wrong == "no_relu" else jax.nn.relu(z)
        return jnp.sum(z * jax.lax.dynamic_slice_in_dim(w, start, min(INDEX_BLOCK, s))[..., None], axis=1)

    scores = _blocked(scores_of, s, INDEX_BLOCK)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    if wrong == "dense_causal":
        return scores, causal
    ranked = -jnp.sort(-jnp.where(causal, sg(scores), -jnp.inf), axis=-1)  # each row's causal scores, largest first
    kth = jnp.take_along_axis(ranked, (jnp.minimum(jnp.arange(s) + 1, topk) - 1)[:, None], axis=-1)
    return scores, causal & (scores >= kth)


def _attention(x, w, *, kind: str, eps: float, theta: float, window: Optional[int], topk: int, full_kv_rank: int,
               wrong: Optional[str] = None):
    """(x + attention(RMSNorm_1(x)), the layer's KL term: 0 for a sliding layer) on one sequence."""
    m = w[SUBTREE[kind]]
    s, d = x.shape
    u = _rms_norm(x, w["ln1"], eps)
    q_rank, kv_rank = m["q_norm"].shape[0], m["kv_norm"].shape[0]
    s_q, s_kv = (d / q_rank) ** 0.5, (d / kv_rank) ** 0.5
    if wrong == "sliding_full_ranks" and kind == SLIDING:
        s_kv = (d / full_kv_rank) ** 0.5
    if wrong == "no_rescale":
        s_q = s_kv = 1.0
    c_q = s_q * _rms_norm(u @ m["w_qa"], m["q_norm"], eps)
    q = jnp.einsum("sr,rhd->shd", c_q, m["w_qb"])
    latent = u @ m["w_kva"]
    kv = jnp.einsum("sr,rhd->shd", s_kv * _rms_norm(latent[:, :kv_rank], m["kv_norm"], eps), m["w_kvb"])
    k_pe = _rotate(latent[:, None, kv_rank:], theta)
    rope = k_pe.shape[-1]
    nope = q.shape[-1] - rope
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], theta)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (s, q.shape[1], rope))], axis=-1)
    v = kv[..., nope:]
    gate = jnp.ones((s, q.shape[1])) if wrong == "no_gate" else jax.nn.sigmoid(u @ m["w_gate"])
    distance = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    if kind == FULL:
        scores, seen = _selection(u, c_q, m, theta=theta, rope=rope, topk=topk, eps=eps, wrong=wrong)
    else:
        scores, seen = None, (distance >= 0) & (distance < (window - 1 if wrong == "window_short" else window))

    def one_block(start):
        block = min(QUERY_BLOCK, s)
        rows = lambda a: jax.lax.dynamic_slice_in_dim(a, start, block)
        logits = jnp.where(rows(seen)[None], jnp.einsum("qhd,thd->hqt", rows(q), k) * q.shape[-1] ** -0.5, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("qhd,hde->qe", jnp.einsum("hqt,thd->qhd", probs, v) * rows(gate)[..., None], m["wo"])
        if scores is None:
            return out, jnp.zeros((block,))
        p = sg(jnp.mean(probs, axis=0))
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        log_q = jax.nn.log_softmax(jnp.where(rows(seen), rows(scores), -jnp.inf), axis=-1)
        return out, jnp.sum(jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0)) - jnp.where(p > 0, log_q, 0.0)), 0.0), axis=-1)

    out, kl = _blocked(one_block, s, QUERY_BLOCK)
    return x + out, jnp.mean(kl)


def _facts(config: Dict[str, Any]):
    """(the kind and the stack of each layer, the keyword arguments of the two halves by kind)."""
    if config["hidden_act"] != "silu" or config.get("rope_scaling") is not None or not config["apply_mla_qkv_lora_rescale"]:
        raise ValueError("the reference runs SwiGLU, the default rope and rescaled latents")
    eps = float(config["rms_norm_eps"])
    common = dict(eps=eps, topk=int(config["index_topk"]), full_kv_rank=int(config["kv_lora_rank"]))
    attn = {FULL: dict(common, kind=FULL, theta=float(config["rope_theta"]), window=None),
            SLIDING: dict(common, kind=SLIDING, theta=float(config["swa_rope_theta"]), window=int(config["sliding_window_size"]))}
    ffn = dict(eps=eps, first=int(config["share"]["first_expert_held"]), top_k=int(config["num_experts_per_tok"]),
               renormalize=bool(config["norm_topk_prob"]), scaling=float(config["routed_scaling_factor"]))
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    return list(zip(kinds, layer_stacks(config))), attn, ffn


_attention_jit = jax.jit(_attention, static_argnames=("kind", "eps", "theta", "window", "topk", "full_kv_rank", "wrong"))
_ffn_jit = jax.jit(_ffn, static_argnames=("eps", "first", "top_k", "renormalize", "scaling", "lowered"))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, *, eps: float):
    return _rms_norm(x, final_norm, eps) @ head


def logits(config: Dict[str, Any], params, tokens, *, last: int, wrong: Optional[str] = None):
    """Reference logits [N, last, V] (float32) for the LAST `last` positions
    of each sequence of `tokens` [N, S], every position of every layer
    computed.  `params` is the program's parameter tree (any dtype, any
    sharding).  Layers outside, sequences inside: each layer's weights are
    fetched and upcast once.  `wrong` is one of `WRONG` (module docstring)."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"wrong={wrong!r} is none of {WRONG}")
    layers, attn, ffn = _facts(config)
    tokens = jnp.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        embed = _local(params["embed"]["tokens"][tokens])
        xs = [embed[i] for i in range(tokens.shape[0])]
        seen: Dict[str, int] = {}
        for kind, stack in layers:
            w = _local(_take_layer(params[stack], seen.get(stack, 0)))
            seen[stack] = seen.get(stack, 0) + 1
            xs = [_ffn_jit(_attention_jit(x, w, wrong=wrong, **attn[kind])[0], w, **ffn) for x in xs]
        head, final_norm = _local(params["lm_head"]), _local(params["final_norm"])
        return jnp.stack([_head(x[-last:], final_norm, head, eps=attn[FULL]["eps"]) for x in xs])


def loss(config: Dict[str, Any], params, tokens, targets, *, terms=("ce", "kl")):
    """(the objective, its terms) on tokens/targets [N, S], float32
    throughout, nothing streamed: the mean cross entropy of each position's
    logits against `targets` + the SUM over the full layers of the indexer's
    KL term (the mean over the sequences of each layer's).  `terms` leaves one
    of the two out of the objective (the test of the gradient's separation).
    `params` must be float32."""
    layers, attn, ffn = _facts(config)

    def block(x, w, kind):
        x, kl = jax.vmap(lambda xi: _attention(xi, w, **attn[kind]))(x)
        return jax.vmap(lambda xi: _ffn(xi, w, **ffn))(x), jnp.mean(kl)

    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens]
        seen: Dict[str, int] = {}
        kl = jnp.zeros(())
        for kind, stack in layers:
            index = seen.get(stack, 0)
            seen[stack] = index + 1
            x, term = block(x, jax.tree_util.tree_map(lambda a, i=index: a[i], params[stack]), kind)
            kl = kl + term
        out = _rms_norm(x, params["final_norm"], attn[FULL]["eps"]) @ params["lm_head"]
        ce = -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(out, axis=-1), targets[..., None], axis=-1))
        return ("ce" in terms) * ce + ("kl" in terms) * kl, {"ce_loss": ce, "dsa_index_kl": kl}
