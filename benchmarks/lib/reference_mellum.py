"""Plain reference for kind "swa_moe_decoder": Mellum 2 (`model_type: mellum`,
JetBrains/Mellum2-12B-A2.5B-Instruct) in straightforward float32 `jax.numpy`,
one sequence at a time, layer by layer in a Python loop.  x is [S, d]; every
norm is an RMSNorm with a learned scale and `rms_norm_eps`; no bias anywhere.

- model: `h = embed[tokens]`; `num_hidden_layers` pre-norm layers,
  `h = h + Attn_l(norm_1(h))`, `h = h + MoE(norm_2(h))`;
  `logits = norm_f(h) @ lm_head` (untied).
- `Attn_l`: `q: d -> heads x head_dim`, `k, v: d -> kv_heads x head_dim` (the
  head size is the configuration's own, 128 on a 2304-wide stream); with
  `qk_norm: "per_head"` an RMSNorm with a learned scale over each head of q and
  of k (the Qwen3 family's `q_norm` / `k_norm`: one scale [head_dim] each);
  the layer's rope; softmax of `q k^T * head_dim^-0.5` over the keys the
  layer's MASK admits, built from positions: `layer_types[l]` "full_attention"
  admits keys `j <= i`, "sliding_attention" keys `i - sliding_window < j <= i`;
  each K/V head serves heads / kv_heads query heads; `o`.
- the layer's rope, `rope_parameters[layer_types[l]]`, rotating adjacent pairs
  (2i, 2i+1) of position p by `p * inv_freq[i]`: "default"
  `inv_freq = theta^(-2i/D)`; "yarn" as Hugging Face's
  `_compute_yarn_parameters` constructs it (`inv_freq_of`): the correction
  dims of `beta_fast` and `beta_slow` rotations over
  `original_max_position_embeddings`, floor and ceiling, a linear ramp between
  them, `inv_freq = interpolation * ramp + extrapolation * (1 - ramp)`, and
  `cos` and `sin` both times `attention_factor`.
- `MoE`: `p = softmax(u W_r)` over all `share.num_experts_total` experts in
  float32, the `num_experts_per_tok` largest, their values divided by their sum
  (`norm_topk_prob`: over ALL the chosen, held here or not);
  `out = sum_e gate_e W_down,e (silu(W_gate,e u) * W_up,e u)`.  The tree holds
  the experts `first .. first + held` only (one rank's share of an
  expert-parallel deployment): the sum runs over the chosen experts that are
  HELD, every held expert on every row, masked by who chose it, and what the
  absent ones would have added is left out, here as in the program.

The training objective (`objective`) is cross entropy plus
`router_aux_loss_coef` times the load-balancing loss as Hugging Face's
`load_balancing_loss_func` computes it over the router logits of ALL layers
concatenated (`reference_moe.objective` has the formula); no z-loss.
`jax.grad` of it is the reference gradient.

No kernel, no scan over layers, no sort or grouping, no cache, no sharding and
no import from `ray_tpu`: it shares with the program only the layout of the
parameter tree it is handed (`params["layers"]`: `attn` wq [d, H, D], wk / wv
[d, Hkv, D], wo [H, D, d], q_norm / k_norm [D]; `mlp` router [d, E], w_gate /
w_up [held, d, F], w_down [held, F, d]; `ln1`, `ln2`).  Everything runs under
`jax.default_matmul_precision("highest")`.  On the chip `logits` streams one
layer's weights at a time, upcast as they are used, queries in blocks of
`QUERY_BLOCK` and expert rows in blocks of `ROW_BLOCK`, so that 16,384 tokens
fit beside the training state.  `tolerance(L)` is the dense reference's.

THE CONTROL (`logits(..., lowered=...)`): the configuration states float32 for
the router, for every norm's statistics and for the rope.  `lowered` names
which of `STATED` ("router", "norms", "rope") the reference computes in
bfloat16 instead, the nearest precision below: what a program that forgot an
upcast would produce.  `scripts/precision_control.py` reads, on the chip at
the cell's sizes, how far that moves the logits (PERF.md section 6, PR 50).

Departures, all noted: rotary pairs are adjacent dims as in the dense
reference (Hugging Face's rotate_half is the same function under a fixed
permutation of each head's columns); the batch is packed sequences with no
padding mask and attention crosses document boundaries (what the program does
too; `assumed` in the configuration file); no MTP head (`config.json` has no
key for one).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib.reference import QUERY_BLOCK, _local, _rms_norm, _take_layer, rel_rms_error, tolerance

__all__ = ["logits", "objective", "inv_freq_of", "seen", "rel_rms_error", "tolerance", "STATED"]

STATED = ("router", "norms", "rope")  # what the configuration states float32 for; `lowered` is a subset of it
LOW = jnp.bfloat16
ROW_BLOCK = 2048  # rows of an expert block held at once: 16 experts x 2048 x 896 float32 = 117 MB


# -- the rope of a layer ------------------------------------------------------------


def inv_freq_of(rope: Dict[str, Any], head_dim: int) -> Tuple[np.ndarray, float]:
    """(inverse frequency of each rotated pair [head_dim / 2], what multiplies
    cos and sin) of one entry of `rope_parameters`, by the published
    construction (Hugging Face `modeling_rope_utils`)."""
    base, dim = float(rope["rope_theta"]), head_dim
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope["rope_type"] == "default":
        return (1.0 / pos_freqs).astype(np.float32), 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"the reference knows the default rope and YaRN, got {rope['rope_type']!r}")
    factor, original = float(rope["factor"]), rope["original_max_position_embeddings"]
    attention_factor = rope.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0  # get_mscale(factor)

    def find_correction_dim(num_rotations):
        return dim * math.log(original / (num_rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(find_correction_dim(rope.get("beta_fast", 32))), 0)
    high = min(math.ceil(find_correction_dim(rope.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001  # as published: no division by zero
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    inv_freq_extrapolation = 1.0 / pos_freqs
    inv_freq_interpolation = 1.0 / (factor * pos_freqs)
    inv_freq = inv_freq_interpolation * ramp + inv_freq_extrapolation * (1.0 - ramp)
    return inv_freq.astype(np.float32), float(attention_factor)


def _rotate(x, inv_freq, factor: float, low: bool = False):
    """x [S, heads, D]: each adjacent pair (2i, 2i+1) of position p by the angle p * inv_freq[i];
    `low`: positions, angles, cos and sin in bfloat16."""
    dtype = LOW if low else jnp.float32
    ang = jnp.arange(x.shape[0], dtype=dtype)[:, None] * inv_freq.astype(dtype)[None, :]
    cos, sin = ((jnp.cos(ang) * factor)[:, None, :].astype(x.dtype), (jnp.sin(ang) * factor)[:, None, :].astype(x.dtype))
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(x.shape)


# -- the two halves of a layer, each `x + half(norm(x))` on one sequence ---------------


def _norm(x, weight, eps: float, low: bool = False):
    """RMSNorm over the last axis; `low`: the statistic (mean square, rsqrt) in bfloat16."""
    if not low:
        return _rms_norm(x, weight, eps)
    xl = x.astype(LOW)
    return x * jax.lax.rsqrt(jnp.mean(xl * xl, axis=-1, keepdims=True, dtype=LOW) + LOW(eps)).astype(x.dtype) * weight


def seen(qpos, kpos, window):
    """bool [q, k]: the keys a query's mask admits; `window` None = every key up to its own."""
    mask = kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask = mask & (kpos[None, :] > qpos[:, None] - window)
    return mask


def _attention(x, w, inv_freq, *, eps: float, factor: float, window, per_head_norm: bool, masked: bool = True,
               lowered: Tuple[str, ...] = ()):
    a = w["attn"]
    s = x.shape[0]
    h = _norm(x, w["ln1"], eps, "norms" in lowered)
    q = jnp.einsum("se,ehd->shd", h, a["wq"])
    k = jnp.einsum("se,ehd->shd", h, a["wk"])
    v = jnp.einsum("se,ehd->shd", h, a["wv"])
    if per_head_norm:
        q, k = _norm(q, a["q_norm"], eps, "norms" in lowered), _norm(k, a["k_norm"], eps, "norms" in lowered)
    q, k = _rotate(q, inv_freq, factor, "rope" in lowered), _rotate(k, inv_freq, factor, "rope" in lowered)
    n_heads, head_dim = q.shape[1], q.shape[2]
    qg = q.reshape(s, k.shape[1], n_heads // k.shape[1], head_dim)  # query head i reads key/value head i // group
    block = min(QUERY_BLOCK, s)
    assert s % block == 0, (s, block)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(qg, start, block, axis=0)
        scores = jnp.einsum("qkgd,tkd->kgqt", qb, k) * head_dim ** -0.5
        if masked:
            scores = jnp.where(seen(start + jnp.arange(block), jnp.arange(s), window), scores, -jnp.inf)
        ctx = jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(scores, axis=-1), v).reshape(block, n_heads, head_dim)
        return jnp.einsum("qhd,hde->qe", ctx, a["wo"])

    return x + jax.lax.map(one_block, jnp.arange(0, s, block)).reshape(s, x.shape[1])


def route(h, router, *, top_k: int, renormalize: bool, low: bool = False):
    """h [T, d] -> (router logits [T, E]; the gate values as a dense [T, E] weight, 0 where not chosen);
    `low`: logits, softmax, the choice and the gate values in bfloat16."""
    router_logits = h.astype(LOW) @ router.astype(LOW) if low else h @ router
    gates, chosen = jax.lax.top_k(jax.nn.softmax(router_logits, axis=-1), top_k)
    if renormalize:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(chosen, router.shape[1], dtype=h.dtype)
    return router_logits.astype(h.dtype), jnp.sum(onehot * gates.astype(h.dtype)[..., None], axis=1)


def expert_part(h, mlp, *, first: int, top_k: int, renormalize: bool, low: bool = False):
    """(router logits; the held experts' part of the routed sum) of normed
    rows h [T, d]: every held expert on every row, masked by who chose it."""
    held = mlp["w_up"].shape[0]
    router_logits, weight = route(h, mlp["router"], top_k=top_k, renormalize=renormalize, low=low)
    inner = jax.nn.silu(jnp.einsum("td,ndf->ntf", h, mlp["w_gate"])) * jnp.einsum("td,ndf->ntf", h, mlp["w_up"])
    out = jnp.einsum("ntf,nfd->ntd", inner, mlp["w_down"])
    return router_logits, jnp.einsum("ntd,tn->td", out, weight[:, first: first + held])


def _experts(x, w, *, eps: float, lowered: Tuple[str, ...] = (), **routing):
    s = x.shape[0]
    block = min(ROW_BLOCK, s)
    assert s % block == 0, (s, block)

    def one_block(xb):
        router_logits, routed = expert_part(_norm(xb, w["ln2"], eps, "norms" in lowered), w["mlp"],
                                            low="router" in lowered, **routing)
        return xb + routed, router_logits

    out, router_logits = jax.lax.map(one_block, x.reshape(s // block, block, -1))
    return out.reshape(s, -1), router_logits.reshape(s, -1)


# -- the configuration as the reference reads it -------------------------------------


def _facts(config: Dict[str, Any]):
    """(per layer: window, inverse frequencies, factor on cos / sin; keyword arguments of the two halves)."""
    kinds = config["layer_types"][: config["num_hidden_layers"]]
    if len(kinds) != config["num_hidden_layers"] or set(kinds) - {"full_attention", "sliding_attention"}:
        raise ValueError(f"layer_types gives {kinds!r} for {config['num_hidden_layers']} layers")
    if set(config["mlp_layer_types"]) != {"sparse"} or config["hidden_act"] != "silu":
        raise ValueError("the reference runs SwiGLU experts in every layer")
    if config["qk_norm"] not in ("per_head", None):
        raise ValueError(f"qk_norm is 'per_head' or null, got {config['qk_norm']!r}")
    ropes = {kind: inv_freq_of(rope, config["head_dim"]) for kind, rope in config["rope_parameters"].items()}
    layers = [(config["sliding_window"] if kind == "sliding_attention" else None, *ropes[kind]) for kind in kinds]
    eps = float(config["rms_norm_eps"])
    attn = dict(eps=eps, per_head_norm=config["qk_norm"] == "per_head")
    experts = dict(eps=eps, first=int(config["share"]["first_expert_held"]), top_k=int(config["num_experts_per_tok"]),
                   renormalize=bool(config["norm_topk_prob"]))
    return layers, attn, experts


_attention_jit = jax.jit(_attention, static_argnames=("eps", "factor", "window", "per_head_norm", "masked", "lowered"))
_experts_jit = jax.jit(_experts, static_argnames=("eps", "first", "top_k", "renormalize", "lowered"))


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _head(x, final_norm, head, *, eps: float, low: bool):
    return _norm(x, final_norm, eps, low) @ head


def logits(config: Dict[str, Any], params, tokens, *, last: int, causal: bool = True, lowered: Tuple[str, ...] = ()):
    """Reference logits [N, last, V] (float32) for the LAST `last` positions
    of each sequence of `tokens` [N, S], every position of every layer
    computed.  `params` is the program's parameter tree (any dtype, any
    sharding).  Layers outside, sequences inside: each layer's weights are
    fetched and upcast once.  `causal=False` exists for the test that shows
    the tolerance catches a dropped mask: every layer then sees every key;
    `lowered` is the control of the module docstring."""
    if set(lowered) - set(STATED):
        raise ValueError(f"lowered names {sorted(set(lowered) - set(STATED))}, not of {STATED}")
    lowered = tuple(sorted(lowered))
    layers, attn, experts = _facts(config)
    tokens = jnp.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        embed = _local(params["embed"]["tokens"][tokens])
        xs = [embed[i] for i in range(tokens.shape[0])]
        for index, (window, inv_freq, factor) in enumerate(layers):
            w = _local(_take_layer(params["layers"], index))
            xs = [_attention_jit(x, w, jnp.asarray(inv_freq), factor=factor, window=window, masked=causal,
                                 lowered=lowered, **attn) for x in xs]
            xs = [_experts_jit(x, w, lowered=lowered, **experts)[0] for x in xs]
        head, final_norm = _local(params["lm_head"]), _local(params["final_norm"])
        return jnp.stack([_head(x[-last:], final_norm, head, eps=attn["eps"], low="norms" in lowered) for x in xs])


def objective(config: Dict[str, Any], params, tokens, targets):
    """(objective, its terms) on tokens/targets [N, S], float32 throughout,
    nothing streamed: cross entropy + `router_aux_loss_coef` * load balancing
    over the rows of all layers concatenated.  `params` must be float32."""
    layers, attn, experts = _facts(config)
    n_experts, top_k = config["share"]["num_experts_total"], config["num_experts_per_tok"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens]  # [N, S, d]
        all_logits = []
        for index, (window, inv_freq, factor) in enumerate(layers):
            w = jax.tree_util.tree_map(lambda a, i=index: a[i], params["layers"])
            x = jax.vmap(functools.partial(_attention, w=w, inv_freq=jnp.asarray(inv_freq), factor=factor,
                                           window=window, **attn))(x)
            x, router_logits = jax.vmap(functools.partial(_experts, w=w, **experts))(x)
            all_logits.append(router_logits.reshape(-1, n_experts))
        out = _rms_norm(x, params["final_norm"], attn["eps"]) @ params["lm_head"]
        logp = jax.nn.log_softmax(out, axis=-1)
        ce = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
        rows = jnp.concatenate(all_logits, axis=0)  # [L*T, E], as Hugging Face concatenates
        probs = jax.nn.softmax(rows, axis=-1)
        _, chosen = jax.lax.top_k(probs, top_k)
        share = jnp.mean(jax.nn.one_hot(chosen, n_experts, dtype=jnp.float32), axis=0)  # f [K, E]
        lb = n_experts * jnp.sum(share * jnp.mean(probs, axis=0)[None, :])
    return ce + config["router_aux_loss_coef"] * lb, {"ce_loss": ce, "moe_lb_loss": lb}
