"""Mixture-of-experts FFN for the transformer layer: dropless, sort-and-group.

No counterpart in the reference (SURVEY §2.4: EP absent).  A layer whose FFN
kind is "experts" gets this block where a dense one has its SwiGLU
(`transformer._ffn_half`, scope `layer/mlp`); `TransformerConfig` is the one
description of the model, this module reads `d_model`, `expert_width` (ONE
expert's width), `n_experts`, `experts_per_token`, `norm_topk_prob`,
`router_activation`, `routed_scaling_factor`, `n_shared_experts`,
`shared_expert_width`, `expert_kind`, `n_experts_held` / `first_expert_held`,
`dtype`.

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
  times `routed_scaling_factor`.
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
  every token goes through, added to the routed result.

Held experts (`n_experts_held`): the layer is TOLD which experts it holds,
`first_expert_held .. + n_experts_held` of the E the router scores, as one
rank of an expert-parallel deployment is.  The router keeps its width E and
its K choices; the weights are `[n_experts_held, ...]`; the layer computes
the held experts' part for the tokens routed to them, plus the shared
expert, and what the absent experts would have added is left out.  That is
`_experts(first_expert=...)`, the form a mesh's `expert` axis uses, without
its `psum`: nothing stands in for the absent ranks.  `_experts` still sorts
and gathers all T*K assignments (the rows of absent experts sort behind the
last held group, where the grouped matmuls visit no tile); a dispatch sized
by the held rows waits for a `perf_opt` (PERF.md section 7).  The rows each
held expert got go out with the statistics (`held_rows`).

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


def moe_param_axes(config: Any) -> Dict:
    """Logical axes of ONE layer's expert leaves (the stack adds `layers`)."""
    one = {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")}
    names = expert_leaves(config)
    axes = {"router": ("embed", "expert"), **{n: ("expert",) + one[n] for n in names}}
    if config.router_activation == "sigmoid":
        axes["router_bias"] = (None,)
    if config.shared_expert_width:
        axes["shared"] = {n: one[n] for n in names}
    return axes


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
    params = {"router": init(k1, (D, E), scale), **matrices((k2, k3, k4), F, routed_down, (held,))}
    if c.router_activation == "sigmoid":
        # zero, and the job leaves it so: its published update follows the
        # experts' load, outside the gradient (a recipe, not a key of a config)
        params["router_bias"] = jnp.zeros(leading + (E,), c.param_dtype)
    if c.shared_expert_width:
        params["shared"] = matrices(jax.random.split(jax.random.fold_in(key, 1), 3), c.shared_expert_width, down_scale)
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


# -- the layer ---------------------------------------------------------------------


def _route(params: Dict, tokens: jax.Array, config: Any):
    """Router of one layer on tokens [T, D]: (expert_idx [T, K] int32, gates
    [T, K] float32, statistics for the router losses)."""
    c = config
    E, K = c.n_experts, c.experts_per_token
    logits = jnp.dot(tokens.astype(jnp.float32), params["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)  # [T, E]
    if c.router_activation == "sigmoid":
        probs = jax.nn.sigmoid(logits)  # each expert's own score
        bias = jax.lax.stop_gradient(params["router_bias"].astype(jnp.float32))
        _, expert_idx = jax.lax.top_k(probs + bias, K)  # the bias takes part in the choice alone
        gates = jnp.take_along_axis(probs, expert_idx, axis=-1)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
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
    return expert_idx, gates, stats


def _activation(h, into, matmul):
    """An expert's hidden row before `w_down`, from the matrices `into` it:
    `silu(gate) * up` of (w_gate, w_up), `relu(up)^2` of (w_up,).  `matmul(h,
    w)` is the form's product."""
    if len(into) == 1:
        return jnp.square(jax.nn.relu(matmul(h, into[0])))
    return jax.nn.silu(matmul(h, into[0])) * matmul(h, into[1])


def _experts(tokens, expert_idx, gates, weights, n_experts, first_expert=None):
    """Dispatch, grouped matmuls and combine for the experts `first_expert ..
    first_expert + weights[0].shape[0]` of `n_experts` (None: all of them, on
    one device).  tokens [T, D], expert_idx / gates [T, K], `weights` the
    experts' two or three matrices (`expert_leaves`); returns (those
    experts' part of the output, [T, D]; the rows each of them got, int32).  The gate values go to their rows in
    expert order and multiply them in the pass that makes the activation,
    so nothing behind `w_down` is a residual of the backward (module
    docstring).  Assignments to other experts sort behind the last group,
    where a grouped matmul writes nothing defined: those rows and their gate
    values are zeroed going in (which zeroes their gradients coming back),
    the rows also coming out."""
    k = expert_idx.shape[1]
    n_local = weights[0].shape[0]
    with jax.named_scope("moe/dispatch"):
        flat = expert_idx.reshape(-1)
        if first_expert is not None:
            flat = (flat - first_expert) % n_experts  # this rank's experts first
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        # (a sort too: `.at[order].set(iota)` is a scatter, 0.3 ms a call on the v5e against 0.07)
        inverse = jnp.argsort(order, stable=False).astype(jnp.int32)
        # (a one-hot sum: `bincount` is a scatter-add, 0.6 ms a call on the v5e)
        group_sizes = jnp.sum(jax.nn.one_hot(flat, n_local, dtype=jnp.int32), axis=0)
        rows = _to_expert_order(tokens, order, inverse, k)
        g_row = _permuted(gates.reshape(-1), inverse, order).astype(rows.dtype)[:, None]
        if first_expert is not None:
            mine = (jnp.arange(rows.shape[0], dtype=jnp.int32) < jnp.sum(group_sizes))[:, None]
            rows, g_row = jnp.where(mine, rows, 0), jnp.where(mine, g_row, 0)
    with jax.named_scope("moe/experts"):
        hidden = _activation(rows, weights[:-1], lambda h, w: grouped_matmul(h, w, group_sizes))
        out = grouped_matmul(hidden * g_row, weights[-1], group_sizes)
    with jax.named_scope("moe/combine"):
        if first_expert is not None:
            out = jnp.where(mine, out, 0)
        return _to_token_order(out, order, inverse, k), group_sizes


def moe_ffn(
    params: Dict,
    x: jax.Array,
    config: Any,
    *,
    rules: Optional[Rules] = None,
    mesh=None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x [B, S, D] (the normed hidden state) -> (y [B, S, D], this layer's
    router statistics: `choice_share` [K, E], `mean_prob` [E], `z` [], and
    with held experts `held_rows` [n_experts_held]).  `_ffn_half` calls it
    inside its `layer/mlp` scope (PERF.md section 3)."""
    B, S, D = x.shape
    held = config.n_experts_held is not None
    with jax.named_scope("moe/router"):
        expert_idx, gates, stats = _route(params, x.reshape(B * S, D), config)
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
        y, rows = _experts(xb.reshape(b * S, D), idx.reshape(b * S, -1), g.reshape(b * S, -1),
                           weights, config.n_experts, first)
        if expert_ax is not None:
            y = jax.lax.psum(y, expert_ax)
        return y.reshape(b, S, D), rows

    def with_shared(y):
        if not config.shared_expert_width:
            return y
        with jax.named_scope("moe/shared"):
            w = [params["shared"][k].astype(x.dtype) for k in expert_leaves(config)]
            hidden = _activation(x, w[:-1], lambda h, m: jnp.einsum("bse,ef->bsf", h, m))
            return y + jnp.einsum("bsf,fe->bse", hidden, w[-1])

    if not across_devices:
        y, rows = body(x, expert_idx, gates, *weights)
        if held:
            stats["held_rows"] = rows.astype(jnp.float32)
        return with_shared(y), stats
    tok_spec = _fit_spec(x.shape, logical_to_spec(("act_batch", None, None), rules), mesh)
    w_spec = P(expert_ax, None, None)
    y = jax.shard_map(
        lambda *a: body(*a)[0], mesh=mesh,
        in_specs=(tok_spec, tok_spec, tok_spec, *[w_spec] * len(weights)),
        out_specs=tok_spec, check_vma=False,
    )(x, expert_idx, gates, *weights)
    return with_shared(y), stats


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
    return out
