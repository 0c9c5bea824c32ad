"""What a kind of mixer declares (`Mixer`, `Leaf`, the initializers of its
leaves) and what every kind's code shares: the stream's norms, the
placement of activations, the mesh axes attention may run over."""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.parallel.sharding import Rules, with_logical_constraint


@dataclasses.dataclass(frozen=True)
class Leaf:
    """One parameter leaf of ONE layer: `init_params` stacks it on a leading
    layers axis, `param_axes` puts "layers" before `axes`, `num_params` takes
    the product of `shape`."""
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]  # logical axes (parallel/sharding.py), one per entry of `shape`
    init: Callable[[Optional[jax.Array], Tuple[int, ...]], jax.Array]  # (key, stacked shape) -> float32
    draws: bool = True  # False: a constant; it takes no key of the stack's sequence


@dataclasses.dataclass(frozen=True)
class Mixer:
    """One kind of mixer, everything `transformer.py` knows of it.

    `mix(x, layer_params, positions, config, rules, mesh=None, *, window=None,
    data=None, shared=None, emit=False) -> (x + mixer(ln1(x)), handed)`: the
    first half of a layer (the FFN half is every kind's, `transformer.layer`).
    `window` is the layer's causal window, `data` this layer's row of the
    kind's `data`, `shared` what earlier layers handed on under the names in
    `reads`; with `emit` the layer returns `hands` by name, and {} otherwise.
    A kind that `rotates` also takes `rope=`, the layer's own rotary embedding
    (`TransformerConfig.layer_ropes`), given only to a layer that has one."""
    name: str  # the kind, as `TransformerConfig.layer_types` spells it
    stack: str  # the subtree of the parameters that stacks its layers (`TransformerConfig.stack_name`)
    subtree: str  # the subtree of one layer that holds the mixer's own leaves
    leaves: Callable[[Any], Mapping[str, Leaf]]  # config -> its leaves, in the order their keys are drawn
    validate: Callable[[Any], None]  # config -> None; raises ValueError on what the kind cannot run
    mix: Callable
    # The `checkpoint_name`s of the residuals with which its backward runs
    # no d-wide projection again, beside attention's own q, k, v, output and
    # log-sum-exp: what `remat_policy="qkv_attn"` keeps.
    saved: Tuple[str, ...] = ()
    # `KernelPair.residual_names` of the recurrence its core runs, () for a kind without one.  A kind that also lists
    # them in `saved` runs the recurrence's forward kernel once a layer under "qkv_attn" (its backward reads what the
    # first call wrote); one that does not runs it again in the layer's recompute.  Read by the step counter
    # `lm._rerun_counters` alone.
    recurrence: Tuple[str, ...] = ()
    # What crosses layers.  A value is RETURNED by the layer that makes it
    # (the layer whose index the config field `source` holds), carried by
    # `trunk` beside the stream and given to the later layers that read it as
    # an argument: under `jax.checkpoint` an input of the reading layer, not
    # recomputed by it, and its cotangents sum over the readers.
    hands: Tuple[str, ...] = ()
    source: Optional[str] = None
    reads: Tuple[str, ...] = ()
    # config -> {name: one float per layer OF THE MODEL}; a layer gets its own as `data[name]`
    data: Callable[[Any], Dict[str, Tuple[float, ...]]] = lambda config: {}
    # config -> (q/k head size, v head size) of the causal attention call its core makes (the flash kernels' two
    # head sizes, from which their tiles follow); None for a kind whose core is no attention call.  Read by the step
    # counters `lm._causal_counters` and `lm._unmasked_counters` alone; held to the real call by tests/test_mellum_model.py
    flash_heads: Callable[[Any], Optional[Tuple[int, int]]] = lambda config: None
    rotates: bool = False  # its `mix` takes `rope=` (a layer of another kind may have no entry in `layer_ropes`)
    # Per-layer float32 scalars its `mix` returns beside what it hands on, under these names: `transformer.trunk_reports`
    # stacks them over the kind's layers, [layers of the kind] each, for the objective and the step counters (models/lm.py)
    reports: Tuple[str, ...] = ()
    holds_heads: bool = False  # it reads `TransformerConfig.head_share` (a model with one has no layer of a kind that does not)
    # its `mix` joins the stream through the layer's learned scaling (`joined(..., scaling=layer_params.get("res1"))`): a model with
    # `TransformerConfig.residual_scaling` has no layer of a kind that does not
    scales_residual: bool = False
    # (config, rules, mesh) -> None; raises ValueError on what the kind cannot run UNDER THESE RULES ON THIS MESH,
    # when the three first meet (`transformer.check_placement`), not when a step is traced
    placement: Callable[[Any, Optional[Rules], Any], None] = lambda config, rules, mesh: None


# -- initializers: (key, stacked shape) -> float32, rounded to `param_dtype` by `init_params` ----


def normal(scale: float):
    return lambda key, shape: jax.random.normal(key, shape, jnp.float32) * scale


def log_uniform(low: float, high: float):
    span, floor = math.log(high) - math.log(low), math.log(low)
    return lambda key, shape: jnp.exp(jax.random.uniform(key, shape, jnp.float32) * span + floor)


def log_of_uniform(low: float, high: float):
    """log A with A uniform in [low, high]: a delta rule's stored `A_log`."""
    return lambda key, shape: jnp.log(jax.random.uniform(key, shape, jnp.float32, low, high))


def inv_softplus(draw):
    """The bias b with softplus(b) = what `draw` gives (a step dt)."""
    def init(key, shape):
        dt = draw(key, shape)
        return dt + jnp.log(-jnp.expm1(-dt))
    return init


def log_arange(key, shape):
    """log(1..n) along the last axis, the same in every row: A = -(1..n)."""
    del key
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)), shape)


def ones(shape, axes=None) -> Leaf:
    """A norm's scale, a skip's weight: starts at 1."""
    return Leaf(shape, axes or (None,) * len(shape), lambda key, full: jnp.ones(full, jnp.float32), draws=False)


def zeros(shape, axes=None) -> Leaf:
    """A bias, a zero-centred norm's stored scale: starts at 0."""
    return Leaf(shape, axes or (None,) * len(shape), lambda key, full: jnp.zeros(full, jnp.float32), draws=False)


def norm_scale(config, shape, axes=None) -> Leaf:
    """The stored scale of a norm of the stream or of q and k: 1, or with
    `norm_zero_centred` the w of `1 + w`, 0."""
    return (zeros if config.norm_zero_centred else ones)(shape, axes)


def proj_scale(config) -> float:
    return config.d_model ** -0.5


def out_scale(config) -> float:
    """Of every projection back into the stream: GPT-2-style depth scaling."""
    return (2 * config.n_layers * config.d_model) ** -0.5


# -- the stream ------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float, axis=-1, zero_centred: bool = False) -> jax.Array:
    """`zero_centred`: the scale is `1 + weight` (the Qwen3-Next family stores
    w and starts it at 0, so weight decay pulls the scale to 1), applied in
    float32 before the one rounding to x's dtype."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=axis, keepdims=True)
    if zero_centred:
        return (xf * jax.lax.rsqrt(var + eps) * (1.0 + weight.astype(jnp.float32))).astype(x.dtype)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight.astype(x.dtype)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array, eps: float) -> jax.Array:
    """LayerNorm over the last axis: statistics in float32, the scale and the
    bias applied in x's dtype, as `rms_norm` applies its scale."""
    xf = x.astype(jnp.float32)
    centred = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(centred), axis=-1, keepdims=True)
    return (centred * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight.astype(x.dtype) + bias.astype(x.dtype)


def stream_norm(config, x: jax.Array, params: Dict, name: str) -> jax.Array:
    """The stream's norm called `name` in `params`, of the configured kind."""
    if config.norm_kind == "layer":
        return layer_norm(x, params[name], params[name + "_b"], config.norm_eps)
    return rms_norm(x, params[name], config.norm_eps, zero_centred=config.norm_zero_centred)


def constrainer(rules: Optional[Rules], mesh):
    """`(activation, logical axes) -> activation`, placed as the rules say
    (the identity without rules)."""
    if rules is None:
        return lambda h, axes: h
    return lambda h, axes: with_logical_constraint(h, axes, rules, mesh)


def residual_scaling_leaves(config) -> Dict[str, Leaf]:
    """One sub-block's learned residual scaling (`TransformerConfig.residual_scaling`): four [d] vectors, the
    identity at the seed."""
    d = (config.d_model,)
    return {"a_res": ones(d), "b_res": zeros(d), "a_out": ones(d), "b_out": zeros(d)}


def joined(config, x: jax.Array, block_out: jax.Array, constrain, scaling: Optional[Dict] = None) -> jax.Array:
    """The residual stream after a block's output joined it: `x + block_out`, or with the sub-block's learned
    `scaling` (ZAYA1's: `residual_scaling_leaves`) `(a_res * x + b_res) + (a_out * block_out + b_out)`, in
    float32 with one rounding to the stream's dtype."""
    block_out = constrain(block_out, ("act_batch", "act_seq", "act_embed"))
    if config.residual_multiplier != 1.0:
        block_out = block_out * jnp.asarray(config.residual_multiplier, block_out.dtype)
    if scaling is None:
        return x + block_out
    a_res, b_res, a_out, b_out = (scaling[name].astype(jnp.float32) for name in ("a_res", "b_res", "a_out", "b_out"))
    return ((a_res * x.astype(jnp.float32) + b_res) + (a_out * block_out.astype(jnp.float32) + b_out)).astype(x.dtype)


def batch_sharded(rules: Optional[Rules], mesh) -> Dict:
    """What the ops that run under a `shard_map` take of a mesh (none without rules)."""
    return {} if rules is None else dict(mesh=mesh, batch_axes=rules.get("act_batch"))


def fitting_axis(axis, mesh, dim: int) -> Optional[str]:
    """Resolve a rules entry to a single mesh axis name that divides dim."""
    if axis is None or mesh is None:
        return None
    if isinstance(axis, tuple):
        axis = axis[0] if axis else None
    if axis not in mesh.axis_names:
        return None
    return axis if dim % mesh.shape[axis] == 0 and mesh.shape[axis] > 1 else None


def ring_axis(rules: Optional[Rules], mesh, q: jax.Array) -> Optional[str]:
    """The mesh axis to run ring attention over, or None for local attention.

    Non-None iff the strategy shards act_seq onto a real (>1) mesh axis that
    divides the sequence length: exactly the case where plain attention
    would silently all-gather the sequence."""
    if rules is None:
        return None
    return fitting_axis(rules.get("act_seq"), mesh, q.shape[1])


def may_ring(rules: Optional[Rules], mesh) -> bool:
    """Whether these rules on this mesh run the sequence-parallel ring at
    SOME length: `ring_axis` before a sequence is known."""
    return rules is not None and fitting_axis(rules.get("act_seq"), mesh, 0) is not None


def refuse_attn_bias(config) -> None:
    if config.attn_bias:
        raise ValueError("attn_bias is the differential kinds' alone: 'attention' and 'mla' layers have no bias")
