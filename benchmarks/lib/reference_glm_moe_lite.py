"""Plain reference for kind "mla_moe_decoder": GLM-4.7-Flash (Hugging Face
`model_type: glm4_moe_lite`), which is DeepSeek-V3's block (arXiv:2412.19437
sections 2.1-2.2) at its own sizes, in straightforward float32 `jax.numpy`,
one sequence at a time.  x is [S, d]; every RMSNorm has a learned scale and
`rms_norm_eps`; no bias anywhere.

- model: `h0 = embed[tokens]`; the layers; `logits = RMSNorm(h) @ lm_head`.
- every layer: `h = h + mla(RMSNorm_1(h))`, then `h = h + FFN(RMSNorm_2(h))`;
  the FFN of layer i (from 0) is a dense SwiGLU of `intermediate_size` for
  i < `first_k_dense_replace`, the expert layer after that.
- "mla" (H heads): `c_q = RMSNorm(u W_qa)`, `q = c_q W_qb -> [H, nope + rope]`
  (a tree with `wq` in place of the three has the one projection `q = u W_q`);
  `[c | k_pe] = u W_kva -> [kv_lora_rank | rope]`; `c <- RMSNorm(c)`;
  `[k_nope | v] = c W_kvb -> [H, nope | v]`; the `rope`-wide part of every q
  head and the one `k_pe` are rotated by the position (`rope_theta`, no
  scaling; `rope_theta` None rotates nothing), the `nope`-wide parts are not;
  `k = [k_nope | k_pe]`, the one `k_pe` shared by the heads; causal softmax of
  `q k^T * (nope + rope)^-0.5` in query blocks; `W_o: H * v -> d`.
- expert layer: `s = sigmoid(x W_r)` over all `share.num_experts_total`
  experts; the choice is the top `num_experts_per_tok` of `s + b` (b the stored
  `e_score_correction_bias`; `noaux_tc` with ONE group, so no group step); the
  gate values are the chosen s, divided by their sum (`norm_topk_prob`), times
  `routed_scaling_factor`; `y = sum_i w_i SwiGLU_{choice_i}(x) +
  SwiGLU_shared(x)`.  The tree holds the experts `first .. first + held` only
  (one rank's share of an expert-parallel deployment): the sum runs over the
  chosen experts that are HELD, the gate values are renormalised over ALL the
  chosen, and what the absent experts would have added is left out, here as in
  the program.  `first` is `share.first_expert_held`, `held` is read off the
  leaves' shapes.
- the multi-token-prediction module (`num_nextn_predict_layers` 1), with h the
  main model's output behind its final norm and t the tokens:
  `h'_i = [RMSNorm_e(embed[t_{i+1}]) ; RMSNorm_h(h_i)] W_eh` (`[2d, d]`),
  `h1 = Block(h')`, one more layer of the last layer's kind with the module's
  own weights at positions 0..S-1, `logits1_i = RMSNorm_s(h1_i) @ lm_head`
  (the main model's table and head): the prediction of t_{i+2}.
- `loss`: `CE(logits, t_{i+1}) + mtp_loss_weight * CE(logits1, t_{i+2})`, each
  a mean over the positions that have a target (the last has no t_{i+2}).

No kernel, no scan over layers, no sort or grouping, no cache, no sharding and
no import from `ray_tpu`: it shares with the program only the layout of the
parameter tree it is handed (`mla_layers_dense`, `mla_layers_experts`: one
stack per pair of mixer and FFN, `mla_layers` where the model has one kind of
FFN; `mtp`: `enorm`, `hnorm`, `eh_proj`, `norm`, `block`).  Everything runs
under `jax.default_matmul_precision("highest")`.  On the chip `both_logits`
streams one layer's weights at a time, upcast as they are used, queries in
blocks of `QUERY_BLOCK` and rows in blocks of `ROW_BLOCK`, so that 8,192
positions fit beside the training state.  `tolerance(L)` is the dense
reference's.

THE CONTROL (`lowered=`): the configuration states float32 for the router, for
every norm's statistics (the module's three among them) and for the rope, and
bfloat16 for the weights.  `lowered` names which of `STATED` the reference
computes in bfloat16 instead, the nearest precision below: what a program that
forgot an upcast would produce; and `WEIGHTS` rounds every weight it is handed
to float8 (e4m3) before it is used, the nearest precision below THEIRS.
`scripts/precision_control.py` reads, on the chip at the cell's sizes, how far
each moves the logits (PERF.md section 6, PR 54).

Departures from the published description, all noted:
- rotary pairs are adjacent dims (2i, 2i+1) as in the dense reference; the
  released code deinterleaves the rope part first (a fixed permutation of its
  64 columns, the same for q and k_pe, which seeded weights do not see).
- the order of the concatenation is the released code's (embedding first);
  DeepSeek-V3's paper writes the hidden state first (with seeded weights a
  permutation of `W_eh`'s rows).
- h is taken BEHIND the main model's final norm, as the released inference code
  hands it on; the paper does not say.
- the router's bias is a stored leaf that takes part in the choice alone; its
  update outside the gradient is a training recipe, not a key of `config.json`.
- the batch is packed sequences with no padding mask and attention crosses
  document boundaries (what the program does too).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from benchmarks.lib.reference import QUERY_BLOCK, _rms_norm, _take_layer, rel_rms_error, tolerance
from benchmarks.lib.reference import _local as _float32_local

__all__ = ["logits", "mtp_logits", "both_logits", "loss", "ffn_kinds", "stack_name", "rel_rms_error", "tolerance", "STATED",
           "WEIGHTS"]

STATED = ("router", "norms", "rope")  # what the configuration states float32 for
WEIGHTS = "weights"  # the file's bfloat16 weights; `lowered` is a subset of STATED + (WEIGHTS,)
LOW = jnp.bfloat16
ROW_BLOCK = 2048  # rows of an FFN held at once: 16 experts x 2048 x 1536 float32 = 200 MB


def _float8_local(tree):
    """`_local`, every array rounded to float8 (e4m3) on the way: the control's weights."""
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32), _float32_local(tree))


def ffn_kinds(config: Dict[str, Any]) -> List[str]:
    """The FFN of each layer that runs."""
    return ["dense" if i < config["first_k_dense_replace"] else "experts" for i in range(config["num_hidden_layers"])]


def stack_name(kinds: List[str], ffn: str) -> str:
    """The program's layout: `mla_layers`, with `_<ffn>` when the model has both kinds of FFN."""
    return f"mla_layers_{ffn}" if len(set(kinds)) > 1 else "mla_layers"


def _norm(x, weight, eps: float, low: bool = False):
    """RMSNorm over the last axis; `low`: the statistic (mean square, rsqrt) in bfloat16."""
    if not low:
        return _rms_norm(x, weight, eps)
    xl = x.astype(LOW)
    return x * jax.lax.rsqrt(jnp.mean(xl * xl, axis=-1, keepdims=True, dtype=LOW) + LOW(eps)).astype(x.dtype) * weight


def _rotate(x, theta: float, low: bool = False):
    """x [S, heads, D]: each adjacent pair (2i, 2i+1) of position p by the angle
    p * theta^(-2i / D); `low`: positions, angles, cos and sin in bfloat16."""
    dtype = LOW if low else jnp.float32
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=dtype)[:, None] * inv_freq.astype(dtype)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :].astype(x.dtype), jnp.sin(ang)[:, None, :].astype(x.dtype)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(x.shape)


def _mla(x, w, *, eps: float, theta: Optional[float], causal: bool = True, lowered: Tuple[str, ...] = ()):
    """x + mla(RMSNorm_1(x)) on one sequence, queries in blocks.  w: this
    layer's `mla` leaves (w_qa [d, q_rank], q_norm, w_qb [q_rank, H, nope +
    rope], or wq [d, H, nope + rope]; w_kva [d, rank + rope], kv_norm [rank],
    w_kvb [rank, H, nope + v], wo [H, v, d]) and `ln1`."""
    m, low = w["mla"], "norms" in lowered
    s = x.shape[0]
    rank = m["kv_norm"].shape[0]
    h = _norm(x, w["ln1"], eps, low)
    if "wq" in m:
        q = jnp.einsum("se,ehd->shd", h, m["wq"])
    else:
        q = jnp.einsum("sr,rhd->shd", _norm(h @ m["w_qa"], m["q_norm"], eps, low), m["w_qb"])
    latent = h @ m["w_kva"]
    k_pe = latent[:, None, rank:]  # [S, 1, rope]
    nope = q.shape[2] - k_pe.shape[2]
    kv = jnp.einsum("sr,rhd->shd", _norm(latent[:, :rank], m["kv_norm"], eps, low), m["w_kvb"])
    if theta is not None:
        q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], theta, "rope" in lowered)], axis=-1)
        k_pe = _rotate(k_pe, theta, "rope" in lowered)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (s, q.shape[1], k_pe.shape[2]))], axis=-1)
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


def _swiglu(h, w):
    return (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def _in_row_blocks(fn, x):
    s = x.shape[0]
    block = min(ROW_BLOCK, s)
    assert s % block == 0, (s, block)
    return jax.lax.map(fn, x.reshape(s // block, block, -1)).reshape(s, -1)


def route(h, router, bias, *, top_k: int, renormalize: bool, scaling: float, low: bool = False):
    """h [T, d] -> the gate values as a dense [T, E] weight, 0 where not
    chosen; `low`: logits, scores, the choice and the gate values in bfloat16."""
    scores = jax.nn.sigmoid(h.astype(LOW) @ router.astype(LOW) if low else h @ router)
    _, chosen = jax.lax.top_k(scores + bias.astype(scores.dtype), top_k)
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    if renormalize:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(chosen, router.shape[1], dtype=h.dtype)
    return jnp.sum(onehot * (gates * scaling).astype(h.dtype)[..., None], axis=1)


def routed_part(h, mlp, *, first: int, low: bool = False, **routing):
    """The held experts' part of the routed sum of normed rows h [T, d]: every
    held expert on every row, masked by who chose it."""
    held = mlp["w_gate"].shape[0]
    weight = route(h, mlp["router"], mlp["router_bias"], low=low, **routing)[:, first: first + held]
    inner = jax.nn.silu(jnp.einsum("td,ndf->ntf", h, mlp["w_gate"])) * jnp.einsum("td,ndf->ntf", h, mlp["w_up"])
    return jnp.einsum("ntd,tn->td", jnp.einsum("ntf,nfd->ntd", inner, mlp["w_down"]), weight)


def _ffn(x, w, *, eps: float, lowered: Tuple[str, ...] = (), **routing):
    """x + FFN(RMSNorm_2(x)) on one sequence, in row blocks: the dense SwiGLU
    where the layer's `mlp` has no router, else the held experts' part of the
    routed sum plus the shared expert."""
    mlp = w["mlp"]

    def one_block(xb):
        h = _norm(xb, w["ln2"], eps, "norms" in lowered)
        if "router" not in mlp:
            return xb + _swiglu(h, mlp)
        return xb + routed_part(h, mlp, low="router" in lowered, **routing) + _swiglu(h, mlp["shared"])

    return _in_row_blocks(one_block, x)


def _mtp_input(h, e, w, *, eps: float, lowered: Tuple[str, ...] = ()):
    """[RMSNorm_e(e) ; RMSNorm_h(h)] W_eh: the embedding's half first."""
    low = "norms" in lowered
    return jnp.concatenate([_norm(e, w["enorm"], eps, low), _norm(h, w["hnorm"], eps, low)], axis=-1) @ w["eh_proj"]


def _facts(config: Dict[str, Any]):
    """(the FFN of each layer, the keyword arguments of the two halves)."""
    if (config["hidden_act"] != "silu" or config["n_group"] != 1 or config["topk_group"] != 1
            or config.get("rope_scaling") is not None):
        raise ValueError("the reference runs SwiGLU, one router group and the default rope")
    eps = float(config["rms_norm_eps"])
    theta = config["rope_theta"]
    attn = dict(eps=eps, theta=None if theta is None else float(theta))
    ffn = dict(eps=eps, first=int(config["share"]["first_expert_held"]), top_k=int(config["num_experts_per_tok"]),
               renormalize=bool(config["norm_topk_prob"]), scaling=float(config["routed_scaling_factor"]))
    return ffn_kinds(config), attn, ffn


_mla_jit = jax.jit(_mla, static_argnames=("eps", "theta", "causal", "lowered"))
_ffn_jit = jax.jit(_ffn, static_argnames=("eps", "first", "top_k", "renormalize", "scaling", "lowered"))
_mtp_input_jit = jax.jit(_mtp_input, static_argnames=("eps", "lowered"))


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _normed(x, weight, *, eps: float, low: bool):
    return _norm(x, weight, eps, low)


@jax.jit
def _head(x, head):
    return x @ head


# -- the forward on the chip: layers streamed ---------------------------------------


def both_logits(config: Dict[str, Any], params, tokens, next_tokens=None, *, last: int, causal: bool = True,
                lowered: Tuple[str, ...] = ()):
    """(reference logits, the module's logits or None), [N, last, V] float32
    each, for the LAST `last` positions of each sequence of `tokens` [N, S],
    every position of every layer computed.  `next_tokens` [N, S] is the token
    after each position (None: no module is run).  `params` is the program's
    parameter tree (any dtype, any sharding).  Layers outside, sequences
    inside: each layer's weights are fetched and upcast once.  `causal=False`
    exists for the test that shows the tolerance catches a dropped mask;
    `lowered` is the control of the module docstring."""
    if set(lowered) - {*STATED, WEIGHTS}:
        raise ValueError(f"lowered names {sorted(set(lowered) - {*STATED, WEIGHTS})}, not of {STATED + (WEIGHTS,)}")
    lowered = tuple(sorted(lowered))
    kinds, attn, ffn = _facts(config)
    eps, low = attn["eps"], "norms" in lowered
    tokens = jnp.asarray(tokens)
    _local = _float8_local if WEIGHTS in lowered else _float32_local
    lowered = tuple(part for part in lowered if part != WEIGHTS)

    def block(xs, w):
        xs = [_mla_jit(x, w, causal=causal, lowered=lowered, **attn) for x in xs]
        return [_ffn_jit(x, w, lowered=lowered, **ffn) for x in xs]

    with jax.default_matmul_precision("highest"):
        embed = _local(params["embed"]["tokens"][tokens])
        xs = [embed[i] for i in range(tokens.shape[0])]
        seen: Dict[str, int] = {}
        for kind in kinds:
            xs = block(xs, _local(_take_layer(params[stack_name(kinds, kind)], seen.get(kind, 0))))
            seen[kind] = seen.get(kind, 0) + 1
        head, final_norm = _local(params["lm_head"]), _local(params["final_norm"])
        hs = [_normed(x, final_norm, eps=eps, low=low) for x in xs]
        main = jnp.stack([_head(h[-last:], head) for h in hs])
        if next_tokens is None:
            return main, None
        mtp = _local(params["mtp"])
        after = _local(params["embed"]["tokens"][jnp.asarray(next_tokens)])
        xs = [_mtp_input_jit(h, after[i], mtp, eps=eps, lowered=lowered) for i, h in enumerate(hs)]
        xs = block(xs, mtp["block"])
        return main, jnp.stack([_head(_normed(x[-last:], mtp["norm"], eps=eps, low=low), head) for x in xs])


def logits(config: Dict[str, Any], params, tokens, *, last: int, causal: bool = True, lowered: Tuple[str, ...] = ()):
    """The main model's reference logits [N, last, V] (`both_logits`)."""
    return both_logits(config, params, tokens, last=last, causal=causal, lowered=lowered)[0]


def mtp_logits(config: Dict[str, Any], params, tokens, next_tokens, *, last: int, lowered: Tuple[str, ...] = ()):
    """The module's reference logits [N, last, V] for the token after the next (`both_logits`)."""
    return both_logits(config, params, tokens, next_tokens, last=last, lowered=lowered)[1]


# -- the training objective: one pure function, for jax.grad ------------------------


def loss(config: Dict[str, Any], params, tokens, targets):
    """(the objective, its two terms) on tokens/targets [N, S], float32
    throughout, nothing streamed.  `targets` is the token after each position;
    the module's targets are `targets` shifted once more, the last position of
    a sequence left out.  The model has no auxiliary router loss.  `params`
    must be float32."""
    kinds, attn, ffn = _facts(config)
    eps = attn["eps"]

    def block(x, w):
        return jax.vmap(lambda xi: _ffn(_mla(xi, w, **attn), w, **ffn))(x)

    def cross_entropy(out, wanted):
        return -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(out, axis=-1), wanted[..., None], axis=-1))

    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens]  # [N, S, d]
        seen: Dict[str, int] = {}
        for kind in kinds:
            index = seen.get(kind, 0)
            seen[kind] = index + 1
            x = block(x, jax.tree_util.tree_map(lambda a, i=index: a[i], params[stack_name(kinds, kind)]))
        h = _rms_norm(x, params["final_norm"], eps)
        ce = cross_entropy(h @ params["lm_head"], targets)
        if not config["num_nextn_predict_layers"]:
            return ce, {"ce_loss": ce}
        mtp = params["mtp"]
        x = block(_mtp_input(h, params["embed"]["tokens"][targets], mtp, eps=eps), mtp["block"])
        out = _rms_norm(x, mtp["norm"], eps) @ params["lm_head"]
        mtp_ce = cross_entropy(out[:, :-1], targets[:, 1:])  # position i predicts t_{i+2} = targets[i + 1]
        return ce + config["train"]["mtp_loss_weight"] * mtp_ce, {"ce_loss": ce, "mtp_loss": mtp_ce}
