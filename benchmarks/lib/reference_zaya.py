"""Plain reference for kind "cca_moe_decoder" (ZAYA1-8B), in straightforward
float32 `jax.numpy`, written from the config's keys and the two published
descriptions (Compressed Convolutional Attention, arXiv:2510.04476; the ZAYA1
technical report, arXiv:2511.17127) as ISSUE 68 states the layers; every item
that no key of `config.json` fixes is listed, with its equation, under
`assumed` in `benchmarks/configs/zaya1-8b-vp8-1chip.json`.  One sequence x
[T, d], H query heads and G key heads of D, g = H / G, per layer l:

  x <- join_1(x, CCA(rms(x; ln1)));  x, r_l <- join_2(x, MoE(rms(x; ln2), r_{l-1}))
  join(x, y) = (a_res * x + b_res) + (a_out * y + b_out)

  CCA(u): qt = u W_Q [T, H, D], kt = u W_K [T, G, D];
    v = u W_V [T, G, D], its key heads j >= G / 2 read one position back
    (v[t, j] <- v[t - 1, j], zero at t = 0);
    c = conv2(conv1([qt | kt])) over the H + G heads, both causal with zero
    history and a bias, nothing between: conv1 depthwise (tap i of T0
    multiplies position t - (T0 - 1 - i)), conv2 a [D, D] map a head and tap;
    q_h = c^q_h + (qt_h + kt_{h // g}) / 2;  k_j = c^k_j + (mean_{h in group j} qt_h + kt_j) / 2;
    q <- sqrt(D) q / sqrt(|q|^2 + 1e-6), k <- tau_j sqrt(D) k / sqrt(|k|^2 + 1e-6), a head each;
    the first `partial_rotary_factor * D` dims of q and k rotated (adjacent
    pairs, theta of `rope_parameters.hybrid`);
    o = causal softmax(q k^T / sqrt(D)) v, query head h reading key head h // g;  CCA = o W_O.

  MoE(h, r_prev): r = h W_d + b_d + gamma * r_prev (r_prev = 0 before layer 0), handed on;
    z = W_3 gelu(W_2 gelu(W_1 rms(r; norm) + b_1) + b_2) (exact GeLU), p = softmax(z),
    e = argmax(p + bias), MoE = p_e * W_down_e(silu(h W_gate_e) * (h W_up_e)):
    every expert on every token, masked by who chose it.

  logits = rms(x; final) E^T over the rows of the embedding table it is handed
  (`tie_word_embeddings`): a vocabulary slice's table gives the slice's logits.

No kernel, cache, sharding or remat, the convolutions as explicit shifts,
attention a full masked softmax in blocks of queries, and no import from
`ray_tpu`: it shares with the program only the layout of the parameter tree
(`cca_layers`: `cca`, `ln1`, `ln2`, `res1`, `res2`, `mlp` with `router` and
`router_bias`).  Everything runs under `jax.default_matmul_precision("highest")`;
on the chip `logits` streams one layer's weights and `EXPERT_CHUNK` experts at a
time.

THE CONTROLS (`wrong=`, one name): what a program that got one mechanism wrong
would compute, each of which the comparison must see (tests/test_zaya_model.py):
`no_value_shift` (every key head reads its own position), `no_qk_mean` (q, k =
c alone), `no_tau` (tau taken as 1), `no_router_state` (r_prev dropped), `gate_one`
(the gate taken as 1).

THE CONTROL OF PRECISION (`logits(lowered=True)`): the two parts the configuration
states in float32 computed in bfloat16, the nearest precision below: the WHOLE
router (h, its weights, the carried state, every product; the softmax alone in
float32 from the bfloat16 logits) and the mixing between the latent and q, k
(both convolutions, the q-k mean, the unit norm, tau; the rope's angles stay
float32).  Everything else is the reference's.  What it reads against the
float32 reference at the timed size is in PERF.md beside the program's largest
reading (`scripts/routing_flips.py --lowered`).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from benchmarks.lib.reference import QUERY_BLOCK, _head, _local, _rms_norm, _rope, _take_layer, rel_rms_error, tolerance  # noqa: F401
from benchmarks.lib.reference_moe import _expert_sum, _take_experts  # every expert on every token, masked by who chose it

__all__ = ["logits", "loss", "qk_mixing", "expert_layer", "rel_rms_error", "tolerance", "WRONG", "STACK"]

WRONG = ("no_value_shift", "no_qk_mean", "no_tau", "no_router_state", "gate_one")
STACK = "cca_layers"
EXPERT_CHUNK = 4  # experts upcast and multiplied at once: 4 x 12.6M weights = 201 MB in float32, 4 x [T, 2048] products
EPS_UNIT = 1e-6  # under the unit norm's root


def _back(x, n: int):
    """x [T, ...] read n positions back, zero before the start."""
    return x if n == 0 else jnp.concatenate([jnp.zeros_like(x[:n]), x[:-n]], axis=0)


def _rotated(x, theta: float, part: int):
    """The first `part` dims of each head of x [T, heads, D] rotated as a head of that size, the rest passed."""
    return jnp.concatenate([_rope(x[..., :part], theta), x[..., part:]], axis=-1)


def qk_mixing(latent, a, *, n_heads: int, theta: Optional[float], rotary: int, wrong: Optional[str] = None, lowered: bool = False):
    """The q|k latent [T, H + G, D] (q's heads first) and the layer's `cca` leaves -> (q [T, H, D], k [T, G, D]):
    both convolutions, the q-k mean, the unit norm, tau and the rope (`theta` None: none).  `lowered`: all of it up to
    the rope in bfloat16 (module docstring)."""
    if lowered:
        latent, a = jax.tree_util.tree_map(lambda leaf: leaf.astype(jnp.bfloat16), (latent, a))
    t, heads, d = latent.shape
    kv_heads = heads - n_heads
    taps0, taps1 = a["conv1_w"].shape[0], a["conv2_w"].shape[0]
    c = sum(a["conv1_w"][i] * _back(latent, taps0 - 1 - i) for i in range(taps0)) + a["conv1_b"]
    c = sum(jnp.einsum("tgd,gde->tge", _back(c, taps1 - 1 - i), a["conv2_w"][i]) for i in range(taps1)) + a["conv2_b"]
    qt, kt = latent[:, :n_heads], latent[:, n_heads:]
    group = n_heads // kv_heads
    if wrong != "no_qk_mean":
        q_mean = (qt + jnp.repeat(kt, group, axis=1)) / 2
        k_mean = (jnp.mean(qt.reshape(t, kv_heads, group, d), axis=2) + kt) / 2
        c = c + jnp.concatenate([q_mean, k_mean], axis=1)
    c = c * jax.lax.rsqrt(jnp.sum(c * c, axis=-1, keepdims=True) + EPS_UNIT) * math.sqrt(d)
    q, k = c[:, :n_heads], c[:, n_heads:]
    if wrong != "no_tau":
        k = k * a["tau"][None, :, None]
    q, k = q.astype(jnp.float32), k.astype(jnp.float32)
    if theta is not None:
        q, k = _rotated(q, theta, rotary), _rotated(k, theta, rotary)
    return q, k


def _join(x, y, res):
    return (res["a_res"] * x + res["b_res"]) + (res["a_out"] * y + res["b_out"])


def _cca(x, w, *, theta: float, rotary: int, eps: float, wrong: Optional[str] = None, lowered: bool = False):
    """The attention half of a layer on one sequence: x [T, d] -> join_1(x, CCA(rms(x; ln1)))."""
    t = x.shape[0]
    a = w["cca"]
    u = _rms_norm(x, w["ln1"], eps)
    qt = jnp.einsum("te,ehd->thd", u, a["wq"])
    kt = jnp.einsum("te,ehd->thd", u, a["wk"])
    v = jnp.einsum("te,ehd->thd", u, a["wv"])
    n_heads, kv_heads, d = qt.shape[1], kt.shape[1], qt.shape[2]
    if wrong != "no_value_shift":
        v = jnp.concatenate([v[:, :kv_heads // 2], _back(v[:, kv_heads // 2:], 1)], axis=1)
    q, k = qk_mixing(jnp.concatenate([qt, kt], axis=1), a, n_heads=n_heads, theta=theta, rotary=rotary, wrong=wrong, lowered=lowered)
    qg = q.reshape(t, kv_heads, n_heads // kv_heads, d)  # query head h reads key head h // g
    block = min(QUERY_BLOCK, t)
    assert t % block == 0, (t, block)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(qg, start, block, axis=0)
        scores = jnp.einsum("qkgd,tkd->kgqt", qb, k) / math.sqrt(d)
        qpos = start + jnp.arange(block)[:, None]
        scores = jnp.where(jnp.arange(t)[None, :] <= qpos, scores, -jnp.inf)
        ctx = jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(scores, axis=-1), v).reshape(block, n_heads, d)
        return jnp.einsum("qhd,hde->qe", ctx, a["wo"])

    out = jax.lax.map(one_block, jnp.arange(0, t, block)).reshape(t, x.shape[1])
    return _join(x, out, w["res1"])


def _route(h, r_prev, router, bias, *, eps: float, wrong: Optional[str] = None, lowered: bool = False):
    """h [T, d] (the normed stream) and the state of the layer before -> (this layer's state r [T, R], the chosen
    expert [T], its gate value p_e [T], the scores p [T, E]).  `lowered`: the router in bfloat16, its state too, the
    softmax in float32 from the bfloat16 logits (module docstring)."""
    if lowered:
        h, r_prev, router = jax.tree_util.tree_map(lambda leaf: jnp.asarray(leaf, jnp.bfloat16), (h, r_prev, router))
    r = h @ router["down"] + router["down_b"]
    if wrong != "no_router_state":
        r = r + router["gamma"] * r_prev
    z = _rms_norm(r, router["norm"], eps)
    z = jax.nn.gelu(z @ router["w1"] + router["b1"], approximate=False)
    z = jax.nn.gelu(z @ router["w2"] + router["b2"], approximate=False)
    p = jax.nn.softmax((z @ router["w3"]).astype(jnp.float32), axis=-1)
    chosen = jnp.argmax(p + jax.lax.stop_gradient(bias), axis=-1)
    gate = jnp.take_along_axis(p, chosen[:, None], axis=-1)[:, 0]
    return r, chosen, gate, p


def _weight(chosen, gate, n_experts: int, wrong: Optional[str] = None):
    """[T, E]: the gate value at the chosen expert, 0 elsewhere."""
    gate = jnp.ones_like(gate) if wrong == "gate_one" else gate
    return jax.nn.one_hot(chosen, n_experts, dtype=gate.dtype) * gate[:, None]


def expert_layer(h, r_prev, mlp, *, eps: float, routing=None):
    """The expert layer alone on normed rows h [T, d]: (its output [T, d], its state r, the chosen expert, the gate).
    `routing` = (chosen [T], gate [T]) takes the place of the layer's own: the experts' arithmetic on a routing GIVEN."""
    r, chosen, gate, _ = _route(h, r_prev, mlp["router"], mlp["router_bias"], eps=eps)
    if routing is not None:
        chosen, gate = routing
    weight = _weight(chosen, gate, mlp["w_gate"].shape[0])
    return _expert_sum(h, weight, mlp["w_gate"], mlp["w_up"], mlp["w_down"]), r, chosen, gate


def _sizes(config: Dict[str, Any]):
    hybrid = config["rope_parameters"]["hybrid"]
    return dict(theta=float(hybrid["rope_theta"]), rotary=int(config["head_dim"] * hybrid["partial_rotary_factor"]),
                eps=float(config["rms_norm_eps"]))


# -- the forward on the chip: layers and experts streamed ---------------------------

_cca_jit = jax.jit(_cca, static_argnames=("theta", "rotary", "eps", "wrong", "lowered"))
_route_jit = jax.jit(_route, static_argnames=("eps", "wrong", "lowered"))
_expert_sum_jit = jax.jit(_expert_sum)
_rms_norm_jit = jax.jit(_rms_norm, static_argnums=2)
_weight_jit = jax.jit(_weight, static_argnames=("n_experts", "wrong"))
_join_jit = jax.jit(_join)


def logits(config: Dict[str, Any], params, tokens, *, last: int, wrong: Optional[str] = None,
           record: Optional[List] = None, routing: Optional[List] = None, lowered: bool = False):
    """Reference logits [N, last, V] (float32) for the LAST `last` positions of each sequence of `tokens` [N, T],
    attending the whole context, V the rows of the table handed over.  `params` is the program's parameter tree (any
    dtype, any sharding).  `record`, if a list, receives each layer's chosen experts ([N, T]) for the tool that counts
    routing flips; `routing`, a DIAGNOSTIC's alone (`scripts/routing_flips.py`; never the comparison behind
    `correct`), gives each layer's chosen experts ([N, T]) in the place of the reference's own, the gate value still the
    reference's score of that expert: what is left of an error once the flipped choices are taken out of it.
    `lowered` is the control of precision (module docstring).  Layers outside, sequences inside; the router's state goes from layer to layer with its sequence."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"unknown control {wrong!r}; the controls are {WRONG}")
    sizes, n_experts = _sizes(config), config["num_experts"]
    eps = sizes["eps"]
    chunk = math.gcd(n_experts, EXPERT_CHUNK)
    tokens = jnp.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        embed = _local(params["embed"]["tokens"][tokens])
        xs = [embed[i] for i in range(tokens.shape[0])]
        rs = [0.0] * len(xs)
        for layer in range(config["num_hidden_layers"]):
            lw = _take_layer(params[STACK], layer)
            w = _local({k: v for k, v in lw.items() if k != "mlp"})
            w["router"], w["router_bias"] = _local(lw["mlp"]["router"]), _local(lw["mlp"]["router_bias"])
            xs = [_cca_jit(x, w, wrong=wrong, lowered=lowered, **sizes) for x in xs]
            hs = [_rms_norm_jit(x, w["ln2"], eps) for x in xs]
            routed = [_route_jit(h, r, w["router"], w["router_bias"], eps=eps, wrong=wrong, lowered=lowered) for h, r in zip(hs, rs)]
            rs = [r[0] for r in routed]
            if routing is not None:
                given = jnp.asarray(routing[layer])
                routed = [(r[0], given[i], jnp.take_along_axis(r[3], given[i][:, None], axis=-1)[:, 0], r[3]) for i, r in enumerate(routed)]
            if record is not None:
                record.append(jnp.stack([r[1] for r in routed]))
            weights = [_weight_jit(r[1], r[2], n_experts=n_experts, wrong=wrong) for r in routed]
            ys = [jnp.zeros_like(x) for x in xs]
            for start in range(0, n_experts, chunk):
                we = _take_experts(lw["mlp"], start, size=chunk)
                ys = [y + _expert_sum_jit(h, wt[:, start:start + chunk], we["w_gate"], we["w_up"], we["w_down"])
                      for y, h, wt in zip(ys, hs, weights)]
            xs = [_join_jit(x, y, w["res2"]) for x, y in zip(xs, ys)]
        head, final_norm = _local(params["embed"]["tokens"]).T, _local(params["final_norm"])  # tied
        return jnp.stack([_head(x[-last:], final_norm, head, eps=eps) for x in xs])


# -- the training objective: one pure function, for jax.grad ------------------------


def forward(config: Dict[str, Any], params, tokens, *, wrong: Optional[str] = None, head=None):
    """Logits [N, T, V] for tokens [N, T], float32 throughout, nothing streamed; `params` must be float32.  `head`
    [V, d]: the table the logits are taken over where it is not the embedding's own (the tests' alone: a
    vocabulary slice's rows under the whole table's stream; the tied table's two uses apart)."""
    sizes, n_experts = _sizes(config), config["num_experts"]
    n, t = tokens.shape
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens]  # [N, T, d]
        r = jnp.zeros((n * t, 1), jnp.float32)
        for layer in range(config["num_hidden_layers"]):
            w = jax.tree_util.tree_map(lambda a: a[layer], params[STACK])
            x = jax.vmap(lambda xi: _cca(xi, w, wrong=wrong, **sizes))(x)
            h = _rms_norm(x, w["ln2"], sizes["eps"]).reshape(n * t, -1)
            mlp = w["mlp"]
            r, chosen, gate, _ = _route(h, r, mlp["router"], mlp["router_bias"], eps=sizes["eps"], wrong=wrong)
            y = _expert_sum(h, _weight(chosen, gate, n_experts, wrong), mlp["w_gate"], mlp["w_up"], mlp["w_down"])
            x = _join(x, y.reshape(x.shape), w["res2"])
        return _rms_norm(x, params["final_norm"], sizes["eps"]) @ (params["embed"]["tokens"] if head is None else head).T


def loss(config: Dict[str, Any], params, tokens, targets, *, wrong: Optional[str] = None, head=None):
    """The mean next-token cross entropy over the table's rows: the whole objective (no auxiliary loss)."""
    logp = jax.nn.log_softmax(forward(config, params, tokens, wrong=wrong, head=head), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
