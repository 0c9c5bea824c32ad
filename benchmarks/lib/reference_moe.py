"""Plain reference for kind "moe_decoder": OLMoE (arXiv:2409.02060; Hugging
Face `modeling_olmoe.py`) in straightforward float32 `jax.numpy`: token
embedding, L pre-norm blocks of RMSNorm -> q/k/v projections -> RMSNorm with
a learned scale over the WHOLE projected q and the whole projected k
(QK-norm) -> rotary position embeddings -> causal softmax attention ->
residual, RMSNorm -> sparse experts -> residual, a final RMSNorm and an
untied output head.  The expert block: router logits `h @ router`, softmax
over all E experts, the K largest probabilities and their experts, the gate
values NOT renormalised unless `norm_topk_prob`; then for EVERY expert a mask
of the tokens that chose it, three dense matmuls on all tokens
(`silu(h @ w_gate) * (h @ w_up)) @ w_down`) and the masked, gate-weighted
sum.  No sort, no grouping, no ragged matmul, no kernel, no capacity, and no
import from `ray_tpu`: it routes for itself and shares with the program only
the layout of the parameter tree it is handed.

The training objective (`objective`) is cross entropy plus the two router
losses, each as published:

- load balancing, as `load_balancing_loss_func` of the Hugging Face
  implementation computes it: concatenate the router logits of ALL layers
  ([L*T, E]), softmax, top-K; `f[k, e]` = mean over those rows of
  `one_hot(choice k) == e`, `P[e]` = mean probability;
  `E * sum_k sum_e f[k, e] * P[e]`, times `router_aux_loss_coef` (0.01).
- router z-loss (the paper's eq. 3): mean over the same rows of
  `logsumexp(logits)**2`, times 0.001 (the paper's coefficient; the Hugging
  Face implementation leaves this term out).

`jax.grad` of `objective` is the reference gradient.

Departures, all noted: rotary pairs are adjacent dims (2i, 2i+1) as in the
dense reference (Hugging Face's rotate_half is the same function under a
fixed permutation of each head's columns); the whole batch is one set of
tokens with no padding mask (the stream is packed); on the chip `logits`
streams one layer's weights and `EXPERT_CHUNK` experts at a time, upcast as
they are used, so it fits beside the training state.

Routing is discrete: a bf16 program and this float32 reference pick a
different K-th expert for a few percent of the tokens of each layer (near
ties of the K-th and K+1-th probability), which costs about as much error as
bf16 itself (sized on a toy, ISSUE 26; measured on the chip, PERF.md section
6 PR 26).  `tolerance(L)` is the dense reference's, unchanged.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from benchmarks.lib.reference import (QUERY_BLOCK, _head, _local, _rms_norm, _rope, _take_layer,
                                      rel_rms_error, tolerance)

__all__ = ["logits", "objective", "rel_rms_error", "tolerance"]

EXPERT_CHUNK = 8  # experts upcast and multiplied at once: 8 x 6.3M weights = 201 MB in float32


def _attention(x, w, *, theta: float, eps: float, causal: bool = True):
    """The attention half of a block on one sequence: x [S, d] -> x + attn.
    w: this layer's `attn` weights (wq [d, H, D], wk/wv [d, Hkv, D], wo
    [H, D, d], q_norm [H, D], k_norm [Hkv, D]) and `ln1`, float32."""
    s = x.shape[0]
    h = _rms_norm(x, w["ln1"], eps)
    a = w["attn"]
    q = jnp.einsum("se,ehd->shd", h, a["wq"])
    k = jnp.einsum("se,ehd->shd", h, a["wk"])
    v = jnp.einsum("se,ehd->shd", h, a["wv"])
    n_heads, head_dim = q.shape[1], q.shape[2]
    # QK-norm: one RMSNorm over all H*D (Hkv*D) values of a position
    q = _rms_norm(q.reshape(s, -1), a["q_norm"].reshape(-1), eps).reshape(q.shape)
    k = _rms_norm(k.reshape(s, -1), a["k_norm"].reshape(-1), eps).reshape(k.shape)
    q, k = _rope(q, theta), _rope(k, theta)
    group = n_heads // k.shape[1]
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
        return jnp.einsum("qhd,hde->qe", ctx, a["wo"])

    out = jax.lax.map(one_block, jnp.arange(0, s, block))
    return x + out.reshape(s, x.shape[1])


def _route(h, router, *, top_k: int, norm_topk_prob: bool):
    """h [T, d] -> (router logits [T, E], chosen experts [T, K], gate values
    [T, K], the latter two as a dense [T, E] weight: 0 where not chosen)."""
    router_logits = h @ router
    probs = jax.nn.softmax(router_logits, axis=-1)
    gates, chosen = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    n_experts = router.shape[1]
    weight = jnp.sum(jax.nn.one_hot(chosen, n_experts, dtype=h.dtype) * gates[..., None], axis=1)
    return router_logits, chosen, weight


def _expert_sum(h, weight, w_gate, w_up, w_down):
    """sum over the given experts e of weight[:, e] * SwiGLU_e(h): every
    expert on every token, masked by who chose it.  h [T, d], weight [T, n],
    w_gate/w_up [n, d, F], w_down [n, F, d] (any float dtype, upcast here)."""
    w_gate, w_up, w_down = (w.astype(jnp.float32) for w in (w_gate, w_up, w_down))
    inner = jax.nn.silu(jnp.einsum("td,ndf->ntf", h, w_gate)) * jnp.einsum("td,ndf->ntf", h, w_up)
    out = jnp.einsum("ntf,nfd->ntd", inner, w_down)
    return jnp.einsum("ntd,tn->td", out, weight)


# -- the forward on the chip: layers and experts streamed ---------------------------

_attention_jit = jax.jit(_attention, static_argnames=("theta", "eps", "causal"))
_route_jit = jax.jit(_route, static_argnames=("top_k", "norm_topk_prob"))
_expert_sum_jit = jax.jit(_expert_sum)
_rms_norm_jit = jax.jit(_rms_norm, static_argnums=2)


@functools.partial(jax.jit, static_argnames=("size",))
def _take_experts(mlp, start, *, size: int):
    return {k: jax.lax.dynamic_slice_in_dim(mlp[k], start, size, axis=0)
            for k in ("w_gate", "w_up", "w_down")}


def logits(config: Dict[str, Any], params, tokens, *, last: int, causal: bool = True,
           record: Optional[List] = None):
    """Reference logits [N, last, V] (float32) for the LAST `last` positions
    of each sequence of `tokens` [N, S], attending the whole context.
    `params` is the program's parameter tree (any dtype, any sharding).
    `record`, if a list, receives each layer's chosen experts ([N, S, K]) for
    the tool that counts routing flips.  Layers outside, sequences inside."""
    theta, eps = float(config["rope_theta"]), float(config["rms_norm_eps"])
    n_experts, top_k = config["num_experts"], config["num_experts_per_tok"]
    norm_topk = bool(config.get("norm_topk_prob", False))
    chunk = math.gcd(n_experts, EXPERT_CHUNK)
    tokens = jnp.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        embed = _local(params["embed"]["tokens"][tokens])
        xs = [embed[i] for i in range(tokens.shape[0])]
        for layer in range(config["num_hidden_layers"]):
            lw = _take_layer(params["layers"], layer)
            w = _local({"attn": lw["attn"], "ln1": lw["ln1"], "ln2": lw["ln2"],
                        "router": lw["mlp"]["router"]})
            xs = [_attention_jit(x, w, theta=theta, eps=eps, causal=causal) for x in xs]
            hs = [_rms_norm_jit(x, w["ln2"], eps) for x in xs]
            routed = [_route_jit(h, w["router"], top_k=top_k, norm_topk_prob=norm_topk) for h in hs]
            if record is not None:
                record.append(jnp.stack([r[1] for r in routed]))
            for start in range(0, n_experts, chunk):
                we = _local(_take_experts(lw["mlp"], start, size=chunk))
                xs = [x + _expert_sum_jit(h, r[2][:, start:start + chunk],
                                          we["w_gate"], we["w_up"], we["w_down"])
                      for x, h, r in zip(xs, hs, routed)]
        head, final_norm = _local(params["lm_head"]), _local(params["final_norm"])
        return jnp.stack([_head(x[-last:], final_norm, head, eps=eps) for x in xs])


# -- the training objective: one pure function, for jax.grad ------------------------


def objective(config: Dict[str, Any], params, tokens, targets):
    """(objective, its terms) on tokens/targets [N, S], float32 throughout,
    nothing streamed: cross entropy + `router_aux_loss_coef` * load balancing
    + `router_z_loss_coef` * z-loss, the router terms over the rows of all
    layers concatenated (module docstring).  `params` must be float32."""
    theta, eps = float(config["rope_theta"]), float(config["rms_norm_eps"])
    n_experts, top_k = config["num_experts"], config["num_experts_per_tok"]
    norm_topk = bool(config.get("norm_topk_prob", False))
    n, s = tokens.shape
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens]  # [N, S, d]
        all_logits = []
        for layer in range(config["num_hidden_layers"]):
            w = jax.tree_util.tree_map(lambda a: a[layer], params["layers"])
            x = jax.vmap(lambda xi: _attention(xi, w, theta=theta, eps=eps))(x)
            h = _rms_norm(x, w["ln2"], eps).reshape(n * s, -1)
            router_logits, _, weight = _route(h, w["mlp"]["router"], top_k=top_k,
                                              norm_topk_prob=norm_topk)
            all_logits.append(router_logits)
            y = _expert_sum(h, weight, w["mlp"]["w_gate"], w["mlp"]["w_up"], w["mlp"]["w_down"])
            x = x + y.reshape(x.shape)
        out = _rms_norm(x, params["final_norm"], eps) @ params["lm_head"]
        logp = jax.nn.log_softmax(out, axis=-1)
        ce = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))

        rows = jnp.concatenate(all_logits, axis=0)  # [L*T, E], as Hugging Face concatenates
        probs = jax.nn.softmax(rows, axis=-1)
        _, chosen = jax.lax.top_k(probs, top_k)
        share = jnp.mean(jax.nn.one_hot(chosen, n_experts, dtype=jnp.float32), axis=0)  # f [K, E]
        lb = n_experts * jnp.sum(share * jnp.mean(probs, axis=0)[None, :])
        z = jnp.mean(jnp.square(jax.nn.logsumexp(rows, axis=-1)))
    terms = {"ce_loss": ce, "moe_lb_loss": lb, "moe_z_loss": z}
    total = ce + config["router_aux_loss_coef"] * lb + config["router_z_loss_coef"] * z
    return total, terms
