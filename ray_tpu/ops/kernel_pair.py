"""The choice between a Mosaic kernel and its plain form, made in ONE place.

An op of `ray_tpu/ops/` that has Pallas kernels beside a plain `jax.numpy`
(or XLA) form runs the kernels in a step lowered for TPU at the shapes they
take and the plain form everywhere else.  `dispatch` is that choice, and the
only caller of `jax.lax.platform_dependent(.., tpu=.., default=..)` under
`ray_tpu/ops/` (`ops/pallas/flash_attention.py`'s `tpu=compiled,
cpu=interpreted` is a kernel's own interpret mode, another decision).

An op with a kernel for EACH direction (the recurrences, the convolutions
that feed them) also declares a `KernelPair` beside its mathematics, and `run`
is the scaffold every such op shares: cut the chunk to the sequence, one
`jax.custom_vjp` a record whose two directions go through `dispatch`, the
record's scope around it, and with a mesh, at shapes the kernels take, a
`jax.shard_map` over the batch axes (GSPMD partitions a plain form by itself;
a Mosaic call it cannot).  A new op is its mathematics, its kernels and one
record.

Nothing here is an option: no argument, field or variable selects a form.  A
test that wants a step "as lowered for TPU" on the CPU replaces `dispatch`
(`tests/conftest.py`).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel.sharding import _fit_spec
from ray_tpu.util import tracing


def dispatch(takes: bool, kernel: Callable, plain: Callable, *inputs):
    """`kernel(*inputs)` in a step lowered for TPU when the kernels take
    these shapes, `plain(*inputs)` everywhere else.  The form follows the
    platform a step is LOWERED for, not the process's backend (a CPU process
    that lowers for TPU gets the kernel); both forms are traced, so both
    return the same shapes and dtypes.  At shapes the kernels refuse no choice
    is traced at all."""
    if takes:
        return jax.lax.platform_dependent(*inputs, tpu=kernel, default=plain)
    return plain(*inputs)


class Call(NamedTuple):
    """What `run` hands a record's direction: the kernels' module, whether
    they take this call's shapes, the chunk as cut, whether a forward runs
    for a backward (and may write more states), and the choice itself."""

    kernels: Any
    takes: bool
    chunk: Optional[int]
    residuals: bool

    def __call__(self, kernel: Callable, plain: Callable, *inputs):
        return dispatch(self.takes, kernel, plain, *inputs)


@dataclasses.dataclass(frozen=True)
class KernelPair:
    """What the scaffold cannot know of one op.

    `forward(call, *args) -> (out, *states)` and `backward(call, *args,
    *states, d_out) -> the cotangents`, each in its argument's shape and
    dtype, hold the op's own layout work around ONE `call(kernel, plain,
    *inputs)`: the kernel form, the plain form and what both read.  `out` is
    one array or a tuple of them, each [batch, sequence, ...] (`delta_conv`'s
    q, k and v), and `d_out` then a tuple alike.  A state
    the plain form has no use for is zeros of the kernel's shape, or None
    where `call.takes` is false."""

    name: str  # the public function's, for its errors
    scope: Optional[str]  # the name the benchmark reads the op by (PERF.md section 3); None: its caller names it
    kernels: str  # the module under `ops/pallas/` that holds the kernels
    takes: Callable[..., bool]  # (that module, *args, chunk) -> whether the kernels take these shapes
    forward: Callable
    backward: Callable
    replicated: Tuple[int, ...] = ()  # the arguments every device holds whole under `shard_map`
    power_of_two_chunk: bool = False
    # at shapes the kernels refuse, this (*args, chunk) -> out IN PLACE of the `custom_vjp`, differentiated by JAX whole
    refused: Optional[Callable] = None

    @property
    def residual_names(self) -> Tuple[str, str]:
        """The `checkpoint_name`s `vjp(self).fwd` puts on `out` and on the
        states: a checkpoint policy that lists both keeps what the backward
        reads, and the layer's recompute holds no forward kernel of this op;
        one that lists neither (a name no policy lists lowers to nothing)
        runs the forward again."""
        return f"{self.name}/out", f"{self.name}/states"

    def module(self):
        """Imported at first use: a dense model's process loads no Pallas for this op."""
        return importlib.import_module(f"ray_tpu.ops.pallas.{self.kernels}")

    def call(self, args, chunk: Optional[int], residuals: bool = False) -> Call:
        kernels = self.module()
        return Call(kernels, bool(self.takes(kernels, *args, chunk)), chunk, residuals)


@functools.cache
def vjp(pair: KernelPair):
    """The record's `jax.custom_vjp`, built once: (chunk, *args) -> out."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def primal(chunk, *args):
        return pair.forward(pair.call(args, chunk), *args)[0]

    def fwd(chunk, *args):
        out, *states = pair.forward(pair.call(args, chunk, residuals=True), *args)
        of_out, of_states = pair.residual_names
        named = lambda tree, name: jax.tree_util.tree_map(lambda a: checkpoint_name(a, name), tree)  # a None stays None
        return named(out, of_out), (args, named(tuple(states), of_states))

    def bwd(chunk, res, d_out):
        args, states = res
        return tuple(pair.backward(pair.call(args, chunk), *args, *states, d_out))

    primal.defvjp(fwd, bwd)
    return primal


def cut(pair: KernelPair, s: int, chunk: int) -> int:
    """The chunk the op runs with: the one asked for, cut to a shorter sequence."""
    chunk = min(chunk, s)
    if pair.power_of_two_chunk and (s % chunk or chunk & (chunk - 1)):
        raise ValueError(f"{pair.name}: sequence length {s} needs a power-of-two chunk that divides it, got {chunk}")
    if s % chunk:
        raise ValueError(f"{pair.name}: sequence length {s} is not a multiple of the chunk {chunk}")
    return chunk


def run(pair: KernelPair, *args, chunk: Optional[int] = None, mesh=None, batch_axes=None):
    """The op of `pair` on `args`, each [batch, sequence, ...] but the
    replicated ones, in chunks of `chunk` positions (None: the op has no
    chunks).  mesh / batch_axes say how they are sharded: with a mesh, at
    shapes the kernels take, each device runs its own rows with the whole
    sequence under `shard_map`."""
    if chunk is not None:
        chunk = cut(pair, args[0].shape[1], chunk)
    takes = pair.call(args, chunk).takes
    if takes or pair.refused is None:
        body = functools.partial(vjp(pair), chunk)
    else:
        body = lambda *args: pair.refused(*args, chunk)

    def scoped(*args):  # the scope INSIDE what shard_map wraps: its body starts a name stack of its own
        with tracing.scope(pair.scope) if pair.scope else contextlib.nullcontext():
            return body(*args)

    if mesh is None or not takes:
        return scoped(*args)
    # An op its caller names takes that name inside with it: on more than one device the body is lowered as a function
    # of its own, which starts a new name stack (on one device jax lowers it in line, under the caller's).
    caller = None if pair.scope or mesh.size == 1 else tracing.open_scope()

    def sharded(*args):  # `jax.named_scope` alone: the caller's own `tracing.scope` keeps the host's account
        with jax.named_scope(caller) if caller else contextlib.nullcontext():
            return scoped(*args)

    rows = _fit_spec(args[0].shape, P(batch_axes, *[None] * (args[0].ndim - 1)), mesh)
    specs = tuple(P() if i in pair.replicated else P(*rows[: a.ndim]) for i, a in enumerate(args))
    return jax.shard_map(sharded, mesh=mesh, in_specs=specs, out_specs=rows, check_vma=False)(*args)
