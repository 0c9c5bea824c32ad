"""Mixture-of-experts FFN for the transformer layer: dropless, sort-and-group.

No counterpart in the reference (SURVEY §2.4: EP absent).  A layer whose FFN
kind is "experts" gets this block where a dense one has its SwiGLU
(`transformer._ffn_half`, scope `layer/mlp`); `TransformerConfig` is the one
description of the model, this module reads `d_model`, `expert_width` (ONE
expert's width), `n_experts`, `experts_per_token`, `norm_topk_prob`,
`router_activation`, `routed_scaling_factor`, `n_shared_experts`,
`shared_expert_width`, `shared_expert_gate`, `expert_kind`, `n_experts_held` /
`first_expert_held`, `dtype`.

An expert, routed or shared, is one of two forms (`expert_kind`): "swiglu",
`W_down(silu(W_gate u) * (W_up u))`, three matrices (OLMoE, Kimi Linear), or
"relu2", `W_down relu(W_up u)^2`, two matrices and no gate (Nemotron-H's
`mlp_hidden_act: relu2`; the leaves are `w_up`, `w_down` alone).  Below,
"the activation" is `silu(gate) * up` or `relu(up)^2`.

The layer, on T tokens with K choices each out of E experts:

- `moe/router`: logits `h @ router` in float32 (precision HIGHEST: on a TPU a
  float32 matmul is otherwise bf16 passes, and routing is discrete), the
  scores, `lax.top_k`, and the statistics the router losses are made of.  Two
  routers.  "softmax" (OLMoE): the scores are the softmax over all E, the top
  K of them are the gate values, renormalised to sum to one under
  `norm_topk_prob`.  "sigmoid" (DeepSeek-V3's, Kimi Linear's): the scores are
  `sigmoid(logits)`, each expert's own; the CHOICE is the top K of
  `score + router_bias` (the stored `e_score_correction_bias`, which takes
  part in the choice alone: it reaches no gate value and gets no gradient),
  the gate values are the chosen SCORES, renormalised under `norm_topk_prob`,
  times `routed_scaling_factor`.  The logits are one linear map of the token
  (`router_kind` "linear") or ZAYA1's network with a state that runs from
  layer to layer ("mlp", arXiv:2511.17127; all float32, matmuls at HIGHEST):
  `r = h W_d + b_d` [T, `router_hidden`], `r <- r + gamma * r_prev` with
  `r_prev` the state the layer before handed on (zeros before the first, whose
  `gamma` so multiplies nothing) and `gamma` a learned [router_hidden] vector,
  1 at the seed; r is handed on (`router_state`); the logits are `W_3
  gelu(W_2 gelu(W_1 RMSNorm(r) + b_1) + b_2)` (exact GeLU, a learned norm
  scale); such a router has a stored `router_bias` that takes part in the
  choice alone, whatever its activation, and reports its mean gate value
  (`gate_mean`).  K = 1 is a top-k like any other: the gate is the chosen
  expert's unnormalised score (`norm_topk_prob` would make it 1 and cut the
  router's gradient).
- `moe/dispatch`: a stable sort of the T*K assignments by expert, the E group
  sizes, a gather of the token rows into expert order, and the T*K gate values
  into the same order (by a sort: `_permuted`).
- `moe/experts`: gate and up (relu2: up alone) as grouped matmuls over the E
  ragged groups, the activation times the row's gate value, down as another
  (`ops/grouped_matmul.py`: Pallas kernels when lowered for TPU, an XLA form
  of the same schedule elsewhere).
- `moe/combine`: rows back into token order, summed over each token's K rows.
- `moe/shared` (with `n_shared_experts`): one more expert of the same form,
  `n_shared_experts` experts wide or as wide as the model states
  (`shared_expert_d_ff`: Nemotron-3-Nano's 3712 beside routed 1856), that
  every token goes through, added to the routed result; with
  `shared_expert_gate` (Qwen3-Next) times `sigmoid(u w_sg)`, a scalar a token
  from a stored `w_sg` [d, 1] (`shared.gate`).

Held experts (`n_experts_held`): the layer is TOLD which experts it holds,
`first_expert_held .. + n_experts_held` of the E the router scores, as one
rank of an expert-parallel deployment is.  The router keeps its width E and
its K choices; the weights are `[n_experts_held, ...]`; the layer computes
the held experts' part for the tokens routed to them, plus the shared
expert, and what the absent experts would have added is left out.  That is
`_experts(first_expert=...)`, the form a mesh's `expert` axis uses, without
its `psum`: nothing stands in for the absent ranks.  The rows each held
expert got go out with the statistics (`held_rows`).  With
`router_share_init` the E / held blocks of the router start equal, so a
token's K choices start as the K * held / E best columns of a block, once in
every share: each share starts with its even part of the rows whatever the
seed (independent columns put a seed's winners where the draw has them, and
a share's rows, with them the step, follow the draw; PERF.md section 6,
PR 50).  Training moves the blocks apart as it moves any weights.

A share moves the rows it holds, not all T*K (PR 48).  The sort is stable
with this share's experts first, so the held rows are the first
`sum(group_sizes)` positions of `order`, and everything behind the sorts (the
gather of token rows, the gate values, the grouped matmuls, the activation,
the way back to token order, and the backward of each) works on buffers of a
static size R: `_rungs` is a short ladder of sizes up to T*K, from twice a
uniform router's share, or from 1.25 times it where the share gets at least
one assignment a token (PR 53), and `lax.switch` takes the smallest that
holds the count (`_sized_experts`).  The last rung is the
all-experts code, a permutation of all T*K rows, so no assignment is ever
dropped and the layer is the same function at every count; the rung follows
from the count alone.
`rows_moved_share` (the rung over T*K) goes out with the statistics.

The gate value of an assignment scales its row where the row is `d_ff` wide,
BEFORE the down projection, and not the `d_model`-wide row that comes out of
it as the published order has it: a linear map commutes with a scalar per
row, so the layer is the same function (in bf16 one rounding moves from
after `w_down` to before it).  What differs is what the backward has to be
given.  The gradient of a gate value is then a row sum over `d_ff` inside the
`silu` backward pass, of operands the `w_down` backward needs anyway; applied
after the un-permute it is `<dy, out>`, which needs the down projection's
output in token order, and a layer checkpointed without its FFN (`qkv_attn`)
would run the third grouped matmul and the un-permute gather again for a
[T, K] gradient.  As it is the recompute ends at `silu(gate) * up`, and
combine is linear with dispatch as its transpose: its backward is dispatch's
gather, `dy[order // K]` (`tests/test_moe_model.py` counts the recompute;
PERF.md section 6, PR 29).

No capacity: every assignment is computed whatever the imbalance.  Both row
movements are gathers in both directions (the backward of a permutation is
the inverse permutation, which XLA cannot know of a plain gather and would
scatter-add).

Across chips (`rules` + a `mesh` of more than one device) dispatch, experts
and combine run under `shard_map`: tokens stay on their batch shard and are
replicated over the `expert` axis, each rank of that axis holds E/ranks
experts and computes their groups only, and a `psum` over the axis adds the
partial results.  The simplest correct form; an all-to-all that moves only
the routed rows waits for a cell on four chips (PERF.md section 7).

Router losses, as published for OLMoE (arXiv:2409.02060; the first also as
Hugging Face's `load_balancing_loss_func` computes it), from per-layer
statistics that the layer scan emits:

- load balancing = `E * sum_k sum_e f[k, e] * P[e]`, with `f[k, e]` the share
  of tokens whose k-th choice is expert e and `P[e]` the mean router
  probability of e, both over the tokens of ALL layers taken together (every
  layer has the same tokens, so the mean over layers of the per-layer
  means).  `f` carries no gradient, `P` does.  K at perfect balance.
- z-loss = mean over tokens and layers of `logsumexp(router logits)**2`.

The training objective adds them times `router_aux_loss_coef` and
`router_z_loss_coef` (`models/lm.py`).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.grouped_matmul import grouped_matmul
from ray_tpu.parallel.sharding import Rules, _fit_spec, logical_to_spec
from ray_tpu.util import tracing


def moe_param_axes(config: Any) -> Dict:
    """Logical axes of ONE layer's expert leaves (the stack adds `layers`)."""
    one = {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")}
    names = expert_leaves(config)
    axes = {"router": ("embed", "expert"), **{n: ("expert",) + one[n] for n in names}}
    if config.router_kind == "mlp":
        axes["router"] = {"down": ("embed", None), "w1": (None, None), "w2": (None, None), "w3": (None, "expert"),
                          **{n: (None,) for n in ("down_b", "gamma", "norm", "b1", "b2")}}
    if _has_router_bias(config):
        axes["router_bias"] = (None,)
    if config.shared_expert_width:
        axes["shared"] = {n: one[n] for n in names}
        if config.shared_expert_gate:
            axes["shared"]["gate"] = ("embed", None)
    return axes


def _has_router_bias(config: Any) -> bool:
    """Whether the layer stores a bias that takes part in the router's choice alone."""
    return config.router_activation == "sigmoid" or config.router_kind == "mlp"


def expert_leaves(config: Any) -> Tuple[str, ...]:
    """The matrices of one expert, routed or shared, in the order they multiply."""
    return ("w_up", "w_down") if config.expert_kind == "relu2" else ("w_gate", "w_up", "w_down")


def init_moe_params(config: Any, key: jax.Array, leading: Tuple[int, ...] = (),
                    out_scale: Optional[float] = None) -> Dict:
    """Seeded normal expert weights in `config.param_dtype`; `leading` is the
    layer stack's shape, `out_scale` the stack's depth-scaled down projection."""
    c = config
    k1, k2, k3, k4 = jax.random.split(key, 4)
    scale = c.d_model ** -0.5
    down_scale = scale if out_scale is None else out_scale
    E, D, F = c.n_experts, c.d_model, c.expert_width
    held = E if c.n_experts_held is None else c.n_experts_held  # the router scores all E

    def init(k, shape, s):
        return (jax.random.normal(k, leading + shape, jnp.float32) * s).astype(c.param_dtype)

    def matrices(keys, width, down, experts=()):
        """One form's matrices, `experts` of them stacked; each leaf keeps its
        key whatever the form (a two-matrix expert draws none for `w_gate`)."""
        keys = dict(zip(("w_gate", "w_up", "w_down"), keys))
        return {name: init(keys[name], experts + (width, D), down) if name == "w_down"
                else init(keys[name], experts + (D, width), scale) for name in expert_leaves(c)}

    # `routed_branch_init`: a token's K routed outputs are ONE residual branch of the depth-scaled variance, 1 / K each
    routed_down = down_scale * c.experts_per_token ** -0.5 if c.routed_branch_init else down_scale
    if c.router_kind == "mlp":
        R = c.router_hidden
        kd, kw1, kw2, kw3 = jax.random.split(k1, 4)
        vector = lambda value: jnp.full(leading + (R,), value, c.param_dtype)  # noqa: E731
        router = {"down": init(kd, (D, R), scale), "down_b": vector(0.0), "gamma": vector(1.0), "norm": vector(1.0),
                  "w1": init(kw1, (R, R), R ** -0.5), "b1": vector(0.0), "w2": init(kw2, (R, R), R ** -0.5), "b2": vector(0.0),
                  "w3": init(kw3, (R, E), R ** -0.5)}
    else:
        router = init(k1, (D, E), scale)
    if c.router_share_init:  # every share's block starts as the first: a token's K choices start K * held / E on each share
        router = jnp.tile(router[..., :held], E // held)
    params = {"router": router, **matrices((k2, k3, k4), F, routed_down, (held,))}
    if _has_router_bias(c):
        # zero at the seed; a job with `router_bias_update_rate` moves it after every step by the experts' load,
        # outside the gradient (`load_following_bias`), and any other leaves it so.  An "mlp" router's is float32 as
        # the rest of that router is: steps of 1e-3 are under bf16's spacing from 0.125 on
        params["router_bias"] = jnp.zeros(leading + (E,), jnp.float32 if c.router_kind == "mlp" else c.param_dtype)
    if c.shared_expert_width:
        params["shared"] = matrices(jax.random.split(jax.random.fold_in(key, 1), 3), c.shared_expert_width, down_scale)
        if c.shared_expert_gate:
            params["shared"]["gate"] = init(jax.random.fold_in(key, 2), (D, 1), scale)
    return params


# -- rows into expert order and back: gathers in both directions -------------------
#
# `order` lists the T*K assignments (indices into the flattened [T, K]) by
# expert, `inverse` is its inverse permutation.  The two row movements are
# each other's transpose, and say so: autodiff's own transpose of a gather is
# a scatter-add, and of the sum over K a [T*K, D] broadcast written out
# before it is gathered.


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _to_expert_order(tokens, order, inverse, k):
    """tokens [T, D] -> rows [T*k, D]: row i is the token of the i-th
    assignment in expert order."""
    return tokens[order // k]


def _to_expert_order_fwd(tokens, order, inverse, k):
    return _to_expert_order(tokens, order, inverse, k), (order, inverse)


def _to_expert_order_bwd(k, res, g):
    return _to_token_order(g, *res, k), None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _to_token_order(rows, order, inverse, k):
    """rows [T*k, D] in expert order -> [T, D]: each token's k rows, added."""
    return rows[inverse].reshape(-1, k, rows.shape[-1]).sum(axis=1)


def _to_token_order_fwd(rows, order, inverse, k):
    return _to_token_order(rows, order, inverse, k), (order, inverse)


def _to_token_order_bwd(k, res, g):
    return _to_expert_order(g, *res, k), None, None


_to_expert_order.defvjp(_to_expert_order_fwd, _to_expert_order_bwd)
_to_token_order.defvjp(_to_token_order_fwd, _to_token_order_bwd)


@jax.custom_vjp
def _permuted(x, dest, source):
    """x [n] -> y [n] with y[dest[j]] = x[j], which is x[source] (`dest` and
    `source` are inverse permutations), as a sort of x by the key `dest`: on
    the v5e a sort of 65,536 pairs takes 0.07 ms where a gather of single
    elements takes 0.48 and a scatter 0.31 (same section).  Its transpose is
    the same sort by `source`."""
    return jax.lax.sort((dest, x), num_keys=1)[1]


def _permuted_fwd(x, dest, source):
    return _permuted(x, dest, source), (dest, source)


def _permuted_bwd(res, g):
    dest, source = res
    return _permuted(g, source, dest), None, None


_permuted.defvjp(_permuted_fwd, _permuted_bwd)


# -- a share's movements: R rows, of which the first `held` are held ----------------
#
# The same pair for a buffer of R <= T*K rows in expert order.  Into expert
# order is a gather of R rows.  Back is NOT the gather by `inverse` (T*K rows
# whatever is held): the R rows are gathered into TOKEN order (a sort of R
# assignments), a token's at most k rows, now neighbours, are added, and each token
# fetches its sum from its first row: two gathers of R and T rows and one
# pass, no scatter (scripts/moe_dispatch_check.py times the forms; PERF.md
# section 6, PR 48).  Rows behind `held` read as zero on both ways, whatever
# they hold (a grouped matmul writes nothing defined there).


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _rows_of_tokens(tokens, order, inverse, held, k, r):
    """tokens [T, D] -> rows [r, D]: row i < held is the token of the i-th
    assignment in expert order, the rows behind are zero."""
    i = jnp.arange(r, dtype=jnp.int32)
    token = jnp.where(i < held, order[:r] // k, tokens.shape[0])
    return tokens.at[token].get(mode="fill", fill_value=0)


def _rows_of_tokens_fwd(tokens, order, inverse, held, k, r):
    return _rows_of_tokens(tokens, order, inverse, held, k, r), (order, inverse, held)


def _rows_of_tokens_bwd(k, r, res, g):
    return _tokens_of_rows(g, *res, k, r), None, None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _tokens_of_rows(rows, order, inverse, held, k, r):
    """rows [r, D] in expert order -> [T, D]: the rows below `held` of each
    token, added in float32."""
    n_tokens = order.shape[0] // k
    i = jnp.arange(r, dtype=jnp.int32)
    # by assignment, t * k + j: token order, a token's rows in the order of its choices (the order the sum
    # over K adds them in); the rows behind the count sort last
    assignment, perm = jax.lax.sort((jnp.where(i < held, order[:r], order.shape[0]), i), num_keys=1)
    ids = assignment // k
    # k - 1 rows more, so that every row has k - 1 neighbours behind it
    ids_behind = jnp.concatenate([ids, jnp.full((k - 1,), -1, jnp.int32)])
    in_token_order = rows[jnp.concatenate([perm, jnp.zeros((k - 1,), jnp.int32)])]
    total = sum(jnp.where((ids_behind[j:j + r] == ids)[:, None], in_token_order[j:j + r], 0).astype(jnp.float32)
                for j in range(k)).astype(rows.dtype)  # at a token's FIRST row: the sum of its rows
    n_rows = jnp.sum(inverse.reshape(n_tokens, k) < held, axis=1, dtype=jnp.int32)  # [T]: rows held of each token
    first = jnp.cumsum(n_rows) - n_rows
    return total.at[jnp.where(n_rows > 0, first, r)].get(mode="fill", fill_value=0)


def _tokens_of_rows_fwd(rows, order, inverse, held, k, r):
    return _tokens_of_rows(rows, order, inverse, held, k, r), (order, inverse, held)


def _tokens_of_rows_bwd(k, r, res, g):
    return _rows_of_tokens(g, *res, k, r), None, None, None


_rows_of_tokens.defvjp(_rows_of_tokens_fwd, _rows_of_tokens_bwd)
_tokens_of_rows.defvjp(_tokens_of_rows_fwd, _tokens_of_rows_bwd)


# -- the layer ---------------------------------------------------------------------


def _dot32(a: jax.Array, b: jax.Array) -> jax.Array:
    """A float32 product at precision HIGHEST: on a TPU a float32 matmul is otherwise bf16 passes, and routing is discrete."""
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)


def router_state(params: Dict, x: jax.Array, config: Any, prev: jax.Array) -> jax.Array:
    """The "mlp" router's state of one layer, float32 [B, S, router_hidden]: the down-projection of the normed hidden
    state x [B, S, D] plus `gamma` times the state `prev` the layer before handed on (module docstring).  It is what
    the layer hands to the next one and what its own `moe_ffn(..., router_state=...)` routes by."""
    p = params["router"]
    with tracing.scope("moe/router"):
        r = _dot32(x.reshape(-1, x.shape[-1]), p["down"]) + p["down_b"].astype(jnp.float32)
        return r.reshape(*x.shape[:-1], -1) + p["gamma"].astype(jnp.float32) * prev


def _mlp_logits(p: Dict, state: jax.Array, eps: float) -> jax.Array:
    """The "mlp" router's logits [T, E] from its state [T, router_hidden] (module docstring), float32."""
    f32 = jnp.float32
    r = state * jax.lax.rsqrt(jnp.mean(jnp.square(state), axis=-1, keepdims=True) + eps) * p["norm"].astype(f32)
    r = jax.nn.gelu(_dot32(r, p["w1"]) + p["b1"].astype(f32), approximate=False)
    r = jax.nn.gelu(_dot32(r, p["w2"]) + p["b2"].astype(f32), approximate=False)
    return _dot32(r, p["w3"])


def _route(params: Dict, tokens: jax.Array, config: Any, state: Optional[jax.Array] = None):
    """Router of one layer on tokens [T, D]: (expert_idx [T, K] int32, gates
    [T, K] float32, statistics for the router losses).  An "mlp" router
    routes by its `state` [T, router_hidden] (`router_state`) and not by the
    tokens themselves."""
    c = config
    E, K = c.n_experts, c.experts_per_token
    if c.router_kind == "mlp":
        logits = _mlp_logits(params["router"], state, c.norm_eps)
    else:
        logits = _dot32(tokens, params["router"])  # [T, E]
    probs = jax.nn.sigmoid(logits) if c.router_activation == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    if _has_router_bias(c):  # sigmoid: each expert's own score
        bias = jax.lax.stop_gradient(params["router_bias"].astype(jnp.float32))
        _, expert_idx = jax.lax.top_k(probs + bias, K)  # the bias takes part in the choice alone
        gates = jnp.take_along_axis(probs, expert_idx, axis=-1)
    else:
        gates, expert_idx = jax.lax.top_k(probs, K)
    if c.norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    if c.routed_scaling_factor != 1.0:
        gates = gates * c.routed_scaling_factor
    stats = {
        # f[k, e]: share of tokens whose k-th choice is e (no gradient)
        "choice_share": jnp.mean(jax.nn.one_hot(expert_idx, E, dtype=jnp.float32), axis=0),
        "mean_prob": jnp.mean(probs, axis=0),  # P[e]
        "z": jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
    }
    if c.router_kind == "mlp":
        stats["gate_mean"] = jnp.mean(gates)  # the mean gate value of the layer's assignments
    return expert_idx, gates, stats


def _activation(h, into, matmul):
    """An expert's hidden row before `w_down`, from the matrices `into` it:
    `silu(gate) * up` of (w_gate, w_up), `relu(up)^2` of (w_up,).  `matmul(h,
    w)` is the form's product."""
    if len(into) == 1:
        return jnp.square(jax.nn.relu(matmul(h, into[0])))
    return jax.nn.silu(matmul(h, into[0])) * matmul(h, into[1])


def _by_expert(flat, n_local):
    """The assignments `flat` [T*K] (expert of each, this share's first) in
    expert order: (`order`, its inverse permutation, the rows of each of the
    first `n_local` experts)."""
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    # (a sort too: `.at[order].set(iota)` is a scatter, 0.3 ms a call on the v5e against 0.07)
    inverse = jnp.argsort(order, stable=False).astype(jnp.int32)
    # (a one-hot sum: `bincount` is a scatter-add, 0.6 ms a call on the v5e)
    group_sizes = jnp.sum(jax.nn.one_hot(flat, n_local, dtype=jnp.int32), axis=0)
    return order, inverse, group_sizes


def _experts(tokens, expert_idx, gates, weights, n_experts, first_expert=None):
    """Dispatch, grouped matmuls and combine for the experts `first_expert ..
    first_expert + weights[0].shape[0]` of `n_experts` (None: all of them, on
    one device).  tokens [T, D], expert_idx / gates [T, K], `weights` the
    experts' two or three matrices (`expert_leaves`); returns (those
    experts' part of the output, [T, D]; the rows each of them got, int32;
    the share of the T*K assignments whose rows were moved, 1.0 with all
    experts).  The gate values go to their rows in
    expert order and multiply them in the pass that makes the activation,
    so nothing behind `w_down` is a residual of the backward (module
    docstring).  A share of the experts goes by `_sized_experts`."""
    k = expert_idx.shape[1]
    n_local = weights[0].shape[0]
    if first_expert is not None:
        with tracing.scope("moe/dispatch"):
            flat = (expert_idx.reshape(-1) - first_expert) % n_experts  # this rank's experts first
            order, inverse, group_sizes = _by_expert(flat, n_local)
            g_sorted = _permuted(gates.reshape(-1), inverse, order)
        rungs = _rungs(flat.shape[0], n_local, n_experts, k)
        # the smallest rung that holds this share's rows
        rung = jnp.sum(jnp.sum(group_sizes) > jnp.asarray(rungs[:-1], jnp.int32), dtype=jnp.int32)
        out = _sized_experts(tokens, g_sorted, tuple(weights), order, inverse, group_sizes, rung, k, rungs)
        return out, group_sizes, jnp.asarray(rungs, jnp.float32)[rung] / flat.shape[0]
    with tracing.scope("moe/dispatch"):
        order, inverse, group_sizes = _by_expert(expert_idx.reshape(-1), n_local)
        rows = _to_expert_order(tokens, order, inverse, k)
        g_row = _permuted(gates.reshape(-1), inverse, order).astype(rows.dtype)[:, None]
    with tracing.scope("moe/experts"):
        hidden = _activation(rows, weights[:-1], lambda h, w: grouped_matmul(h, w, group_sizes))
        out = grouped_matmul(hidden * g_row, weights[-1], group_sizes)
    with tracing.scope("moe/combine"):
        return _to_token_order(out, order, inverse, k), group_sizes, 1.0


# -- a share of the experts: buffers sized by the rows held ------------------------

_ROW_TILE = 512  # the grouped-matmul kernels' row tile (`ops/pallas/grouped_matmul.py` `_TM`): a rung is whole tiles
_RUNGS = 4  # at most: every rung is traced, lowered and compiled (PERF.md section 6, PR 48: what a rung costs `setup_s`)


# A first rung AT the uniform share would be a coin toss between two step times (`mellum2` holds 1.006 of it); a
# quarter over it is the capacity factor expert-parallel training gives ONE expert (Switch Transformer, arXiv:2101.03961,
# section 2.2).  `mellum2`'s rank stays under it for 67 steps and leaves it in 17% of 346 steps' layers (PERF.md section 6, PR 53)
_BALANCED_RUNG = (5, 4)  # 1.25 as a ratio: the rungs are whole numbers
# The uniform share, in assignments a token (K * held / experts), from which a share's first rung is that one.  What a
# lower rung saves grows with the share: from here on the rows moved outnumber the tokens and are a large part of the layer
_ROWS_A_TOKEN = 1


def _rungs(assignments: int, n_local: int, n_experts: int, k: int) -> Tuple[int, ...]:
    """The static row counts a share's buffers may have, ascending, in whole
    row tiles.  Twice a uniform router's share of the T*K `assignments` (so a
    balanced router, and one out of balance by up to 2, stays in it), then
    each twice the last; the last is T*K itself.  Of a share that gets at
    least `_ROWS_A_TOKEN` of a token's `k` choices the FIRST rung is
    `_BALANCED_RUNG` times the uniform share instead (so a four-way share
    has that rung and T*K: what is over 1.25x its uniform share moves all
    rows)."""
    rung = -(-2 * assignments * n_local // n_experts // _ROW_TILE) * _ROW_TILE
    rungs = []
    while rung < assignments and len(rungs) < _RUNGS - 1:
        rungs.append(rung)
        rung *= 2
    if k * n_local >= _ROWS_A_TOKEN * n_experts:
        times, over = _BALANCED_RUNG
        balanced = -(-times * assignments * n_local // (over * n_experts) // _ROW_TILE) * _ROW_TILE
        if balanced < assignments:
            rungs[:1] = [balanced]  # in the 2x rung's place, or alone where 2x is T*K already
    return (*rungs, assignments)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _rung_forward(r, k, tokens, g_sorted, weights, order, inverse, group_sizes):
    """The share's part of the output, [T, D], on buffers of `r` rows (at
    least the rows `group_sizes` hold).  `g_sorted` [T*K] float32: the gate
    values in expert order.  Assignments to other experts sort behind the
    last group, where a grouped matmul writes nothing defined: those rows and
    their gate values are zeroed going in (which zeroes their gradients
    coming back), the rows also coming out.  `r` = T*K is the all-experts
    movement, a permutation."""
    held = jnp.sum(group_sizes)
    whole = r == order.shape[0]
    with tracing.scope("moe/dispatch"):
        mine = (jnp.arange(r, dtype=jnp.int32) < held)[:, None]
        if whole:
            rows = jnp.where(mine, _to_expert_order(tokens, order, inverse, k), 0)
        else:
            rows = _rows_of_tokens(tokens, order, inverse, held, k, r)
        g_row = jnp.where(mine, g_sorted[:r].astype(rows.dtype)[:, None], 0)
    with tracing.scope("moe/experts"):
        hidden = _activation(rows, weights[:-1], lambda h, w: grouped_matmul(h, w, group_sizes))
        out = grouped_matmul(hidden * g_row, weights[-1], group_sizes)
    with tracing.scope("moe/combine"):
        if whole:
            return _to_token_order(jnp.where(mine, out, 0), order, inverse, k)
        return _tokens_of_rows(out, order, inverse, held, k, r)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _sized_experts(tokens, g_sorted, weights, order, inverse, group_sizes, rung, k, rungs):
    """`_rung_forward` at `rungs[rung]` rows, the smallest of `rungs` that
    holds the rows of `group_sizes`.  One `custom_vjp` around the switch:
    differentiated by jax, a switch keeps every branch's residuals, those of
    the rungs not taken as zeros of their full size; here the backward is a
    switch of its own on the same rung, each branch the vjp of its forward
    from the layer's inputs (the activation runs again in it, as it does
    under `qkv_attn` whatever the form)."""
    branches = [functools.partial(_rung_forward, r, k) for r in rungs]
    return jax.lax.switch(rung, branches, tokens, g_sorted, weights, order, inverse, group_sizes)


def _sized_experts_fwd(tokens, g_sorted, weights, order, inverse, group_sizes, rung, k, rungs):
    args = (tokens, g_sorted, weights, order, inverse, group_sizes)
    return _sized_experts(*args, rung, k, rungs), (args, rung)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _rung_backward(r, k, tokens, g_sorted, weights, order, inverse, group_sizes, g):
    """The cotangents of tokens, gate values and weights for `g`, that of `_rung_forward`'s output."""
    forward = functools.partial(_rung_forward, r, k, order=order, inverse=inverse, group_sizes=group_sizes)
    return jax.vjp(forward, tokens, g_sorted, weights)[1](g)


def _sized_experts_bwd(k, rungs, res, g):
    args, rung = res
    branches = [functools.partial(_rung_backward, r, k) for r in rungs]
    return (*jax.lax.switch(rung, branches, *args, g), None, None, None, None)


_sized_experts.defvjp(_sized_experts_fwd, _sized_experts_bwd)


def moe_ffn(
    params: Dict,
    x: jax.Array,
    config: Any,
    *,
    rules: Optional[Rules] = None,
    mesh=None,
    router_state: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x [B, S, D] (the normed hidden state) -> (y [B, S, D], this layer's
    router statistics: `choice_share` [K, E], `mean_prob` [E], `z` [], and
    with held experts `held_rows` [n_experts_held] and `rows_moved_share` [],
    with an "mlp" router `gate_mean` []).  `_ffn_half` calls it
    inside its `layer/mlp` scope (PERF.md section 3), an "mlp" router's layer
    with this layer's `router_state` [B, S, router_hidden] (`router_state()`)."""
    B, S, D = x.shape
    held = config.n_experts_held is not None
    with tracing.scope("moe/router"):
        state = None if router_state is None else router_state.reshape(B * S, -1)
        expert_idx, gates, stats = _route(params, x.reshape(B * S, D), config, state)
    expert_idx = expert_idx.reshape(B, S, -1)
    gates = gates.reshape(B, S, -1)
    weights = [params[k].astype(x.dtype) for k in expert_leaves(config)]

    across_devices = rules is not None and mesh is not None and mesh.size > 1
    expert_ax = None  # the mesh axis the experts are split over, if it is a real split
    if across_devices:
        ax = rules.get("expert")
        if ax in mesh.axis_names and mesh.shape[ax] > 1 and config.n_experts % mesh.shape[ax] == 0:
            expert_ax = ax
    if held and across_devices:
        raise ValueError("n_experts_held is one rank's share of the experts: it runs on one "
                         "device, not beside a mesh that shards tokens or experts")

    def body(xb, idx, g, *weights):
        b = xb.shape[0]
        first = config.first_expert_held if held else None
        if expert_ax is not None:
            first = jax.lax.axis_index(expert_ax) * weights[0].shape[0]
        y, rows, moved = _experts(xb.reshape(b * S, D), idx.reshape(b * S, -1), g.reshape(b * S, -1),
                                  weights, config.n_experts, first)
        if expert_ax is not None:
            y = jax.lax.psum(y, expert_ax)
        return y.reshape(b, S, D), rows, moved

    def with_shared(y):
        if not config.shared_expert_width:
            return y
        with tracing.scope("moe/shared"):
            w = [params["shared"][k].astype(x.dtype) for k in expert_leaves(config)]
            hidden = _activation(x, w[:-1], lambda h, m: jnp.einsum("bse,ef->bsf", h, m))
            out = jnp.einsum("bsf,fe->bse", hidden, w[-1])
            if config.shared_expert_gate:
                logit = jnp.einsum("bse,ef->bsf", x, params["shared"]["gate"].astype(x.dtype))
                out = out * jax.nn.sigmoid(logit.astype(jnp.float32)).astype(x.dtype)
            return y + out

    if not across_devices:
        y, rows, moved = body(x, expert_idx, gates, *weights)
        if held:
            stats["held_rows"] = rows.astype(jnp.float32)
            stats["rows_moved_share"] = moved
        return with_shared(y), stats
    tok_spec = _fit_spec(x.shape, logical_to_spec(("act_batch", None, None), rules), mesh)
    w_spec = P(expert_ax, None, None)
    y = jax.shard_map(
        lambda *a: body(*a)[0], mesh=mesh,
        in_specs=(tok_spec, tok_spec, tok_spec, *[w_spec] * len(weights)),
        out_specs=tok_spec, check_vma=False,
    )(x, expert_idx, gates, *weights)
    return with_shared(y), stats


def load_following_bias(bias: jax.Array, choice_share: jax.Array, rate: float) -> jax.Array:
    """The stored `router_bias` [..., E] after one step of the load-following rule (auxiliary-loss-free balancing,
    arXiv:2408.15664, as DeepSeek-V3 trains with it): `bias_e + rate * sign(mean load - load_e)`, the load of an
    expert the share of the step's assignments it took (`choice_share` [..., K, E], this step's, summed over the K
    choices).  An expert over the mean loses `rate` of its part in the choice, one under it gains as much; the bias
    reaches no gate value and no gradient reaches it (`_route`).  The step is taken in float32."""
    load = jnp.sum(choice_share, axis=-2)  # [..., E], K in all
    step = jnp.sign(jnp.mean(load, axis=-1, keepdims=True) - load)
    return (bias.astype(jnp.float32) + rate * step).astype(bias.dtype)


def router_losses(stats: Dict[str, jax.Array], config: Any) -> Dict[str, jax.Array]:
    """The router losses and the load figure of a step, from the statistics
    the layer scan stacked ([L, ...] each); formulas in the module docstring.
    Unweighted: `lm.py` applies the two coefficients."""
    share = jnp.mean(stats["choice_share"], axis=0)  # f over all layers' tokens, [K, E]
    prob = jnp.mean(stats["mean_prob"], axis=0)  # P, [E]
    load = jnp.sum(stats["choice_share"], axis=1)  # [L, E], sums to K per layer
    out = {
        "moe_lb_loss": config.n_experts * jnp.sum(share * prob[None, :]),
        "moe_z_loss": jnp.mean(stats["z"]),
        # tokens at the busiest expert over the mean, in the worst layer
        "moe_load_max_over_mean": jnp.max(jnp.max(load, axis=1) / jnp.mean(load, axis=1)),
    }
    if "held_rows" in stats:
        # rows a held expert multiplied this step: the mean over held experts
        # and layers, and the busiest held expert of any layer
        out["moe_held_rows_mean"] = jnp.mean(stats["held_rows"])
        out["moe_held_rows_max"] = jnp.max(stats["held_rows"])
        # rows of the rung the share's buffers took over the T*K assignments, mean over layers (1.0: all were moved)
        out["moe_rows_moved_share"] = jnp.mean(stats["rows_moved_share"])
    if config.router_bias_update_rate:
        # what the load-following bias is there to keep up: the experts of a layer that got a row this step, mean over the layers
        out["moe_experts_in_use"] = jnp.mean(jnp.sum((load > 0).astype(jnp.float32), axis=1))
    if "gate_mean" in stats:
        out["moe_gate_mean"] = jnp.mean(stats["gate_mean"])  # an "mlp" router's mean gate value, over the layers
    return out
