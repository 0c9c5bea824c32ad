"""The selective scan's forward kernel (`ops/pallas/selective_scan.py`) in
interpret mode on the CPU: against the plain chunked form it replaces on TPU
(`ops/selective_scan.py:_plain_forward`) and the recurrence itself; the states
it writes, which the backward starts from; the `custom_vjp` around both, whose
backward is JAX's differentiation of `_chunk_body` on every platform.

Blocks of 128 channels by 64 positions here (the chip's are `_BLOCK_C` by
`_BLOCK_S`): 256 channels are two programs, 128 positions two blocks of two
chunks each."""

import functools
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import selective_scan as op
from ray_tpu.ops.pallas import selective_scan as kernels
from ray_tpu.parallel import MeshSpec, build_mesh

SCAN_TOL = 1e-5  # the chunked form's own against the recurrence (test_sambay_model.py)
CHUNK = op.CHUNK


def inputs(seed, step, b=1, s=128, c=256, n=16, dtype=jnp.float32):
    """A = -(1..N) as Mamba starts it; dt around `step`."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, s, c)).astype(dtype)
    dt = step * jax.nn.softplus(jax.random.normal(ks[1], (b, s, c)))
    A = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (c, n))
    B, C = (jax.random.normal(k, (b, s, n)).astype(dtype) for k in ks[2:4])
    return x, dt, A, B, C, 1.0 + 0.1 * jax.random.normal(ks[4], (c,))


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / (np.mean(b ** 2) + 1e-300)))


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(kernels, "_BLOCK_C", 128)
    monkeypatch.setattr(kernels, "_BLOCK_S", 64)


@pytest.fixture
def kernel_on_the_cpu(monkeypatch):
    """`selective_scan` as a step lowered for TPU has it, the kernel
    interpreted: the dispatch takes its `tpu` branch."""
    monkeypatch.setattr(kernels, "s6_scan_fwd", functools.partial(kernels.s6_scan_fwd, interpret=True))
    monkeypatch.setattr(op.jax.lax, "platform_dependent", lambda *args, tpu, default: tpu(*args))


@pytest.fixture
def no_kernel(monkeypatch):
    def refuse(*args, **kwargs):
        raise AssertionError("the kernel was called")

    monkeypatch.setattr(kernels, "s6_scan_fwd", refuse)
    monkeypatch.setattr(op.jax.lax, "platform_dependent", lambda *args, tpu, default: tpu(*args))


def seed_form(x, dt, A, B, C, D, chunk=CHUNK):
    """`selective_scan` as it was before the `custom_vjp` (PR 40), for JAX to
    differentiate whole: the oracle for 'the plain side is today's'."""
    b, s, c = x.shape
    A_t = A.astype(jnp.float32).T
    body = jax.checkpoint(lambda carry, inp: op._chunk_body(A_t, D, carry, inp))
    chunks = functools.partial(op._chunks, chunk=chunk)
    _, y = jax.lax.scan(body, jnp.zeros((b, A.shape[1], c), jnp.float32),
                        (chunks(x), chunks(dt.astype(jnp.float32)), chunks(B), chunks(C)))
    return op._positions(y).astype(x.dtype)


def gradients(f, args, seed=9):
    probe = jax.random.normal(jax.random.PRNGKey(seed), args[0].shape)
    return jax.jit(jax.grad(lambda *a: jnp.sum(f(*a).astype(jnp.float32) * probe), argnums=range(6)))(*args)


DECAYS = pytest.mark.parametrize("step", [1e-3, 0.1, 4.0], ids=["slow", "mixed", "fast"])
NAMES = ("x", "dt", "A", "B", "C", "D")


@DECAYS
@pytest.mark.parametrize("b", [1, 2], ids=["batch1", "batch2"])
def test_kernel_forward_is_the_plain_form_and_the_recurrence(kernel_on_the_cpu, step, b):
    """`fast`: `dt * A` is about -50 a position at n = 16, so the running sum
    passes -88 (where `exp` is 0 in float32) within two positions of every
    block."""
    args = inputs(b, step, b=b)
    x, dt, A = args[:3]
    exponent = dt[..., None] * A
    assert float(jnp.max(exponent)) <= 0.0  # dt > 0, A < 0: no exponential of the scan can exceed 1
    assert step < 4 or float(jnp.max(jnp.sum(exponent[:, :64], axis=1)[..., -1])) < -88
    got = op.selective_scan(*args)
    assert got.dtype == x.dtype and bool(jnp.all(jnp.isfinite(got)))
    assert rel(got, seed_form(*args)) <= 1e-6
    assert rel(got, op.selective_scan_recurrent(*args)[0]) <= SCAN_TOL


def test_a_decay_that_float32_calls_zero_forgets_exactly():
    """dt = 7 everywhere: `exp(dt * A)` at n = 16 is exp(-112), 0 in float32 (a
    quotient of cumulative exponentials would be 0 / 0 there).  That state's
    row is then the last position's input alone, to the bit, and the rows whose
    decay float32 can hold agree with the recurrence."""
    x, _, A, B, C, D = inputs(4, 1.0, b=2)
    dt = jnp.full(x.shape, 7.0)
    assert float(jnp.exp(7.0 * A[0, -1])) == 0.0
    y, entering = kernels.s6_scan_fwd(x, dt, A.T, B, C, D, interpret=True)
    assert bool(jnp.all(jnp.isfinite(y))) and bool(jnp.all(jnp.isfinite(entering)))
    for i in range(1, 4):
        last = i * CHUNK - 1
        assert bool(jnp.all(entering[i, :, -1, :] == (dt * x)[:, last] * B[:, last, -1:]))
    assert rel(y, op.selective_scan_recurrent(x, dt, A, B, C, D)[0]) <= SCAN_TOL


def test_kernel_writes_the_state_that_enters_each_chunk():
    """Every 32nd position's state, across the blocks of the sequence (64
    positions) and the programs of the channels (128) and the batch."""
    args = inputs(3, 0.05, b=2)
    x, dt, A, B, C, D = args
    y, entering = kernels.s6_scan_fwd(x, dt, A.T, B, C, D, interpret=True)
    assert entering.shape == (4, 2, 16, 256) and entering.dtype == jnp.float32
    assert not entering[0].any()  # a sequence starts from nothing
    for i in range(1, 4):
        want = op.selective_scan_recurrent(*(t[:, : i * CHUNK] for t in (x, dt)), A,
                                           *(t[:, : i * CHUNK] for t in (B, C)), D)[1]
        assert rel(entering[i], want.swapaxes(1, 2)) <= 1e-6, i
    assert rel(entering, op._plain_forward(x, dt, A.T, B, C, D, CHUNK)[1]) <= 1e-6


@DECAYS
def test_gradients_through_the_kernel_are_the_plain_forms_and_the_recurrences(kernel_on_the_cpu, step):
    """All six inputs: the backward starts each chunk from the state the
    KERNEL wrote."""
    args = inputs(5, step, b=2)
    got = gradients(op.selective_scan, args)
    plain = gradients(seed_form, args)
    want = gradients(lambda *a: op.selective_scan_recurrent(*a)[0], args)
    for name, g, p, w in zip(NAMES, got, plain, want):
        assert g.shape == w.shape and g.dtype == p.dtype, name
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert rel(g, p) <= 1e-5 and rel(g, w) <= 10 * SCAN_TOL, (name, rel(g, p), rel(g, w))


def test_off_tpu_the_custom_vjp_is_the_plain_form():
    """No monkeypatch: the CPU's lowering of the dispatch.  y and all six
    gradients are the seed's, bit for bit: the same `_chunk_body` a chunk at a
    time, forward and, from the same entering states, backward."""
    args = inputs(7, 0.1, b=2)
    assert kernels.supported(256, 16, 128, CHUNK)
    assert "pallas_call" in str(jax.make_jaxpr(op.selective_scan)(*args))  # one branch of the dispatch, unused here
    assert bool(jnp.all(op.selective_scan(*args) == seed_form(*args)))
    for name, g, w in zip(NAMES, gradients(op.selective_scan, args), gradients(seed_form, args)):
        assert g.dtype == w.dtype and rel(g, w) <= 1e-6, (name, rel(g, w))


@pytest.mark.parametrize("shape,chunk", [(dict(c=80), CHUNK), (dict(n=4), CHUNK), (dict(s=96), CHUNK), (dict(), 8)],
                         ids=["channels-80", "states-4", "sequence-96", "chunk-8"])
def test_shapes_the_kernel_refuses_run_the_plain_form_in_both_directions(no_kernel, shape, chunk):
    """Not whole lane tiles, not whole sublane tiles, not whole blocks, chunks
    of half a packed tile: no dispatch at all, whatever the platform."""
    args = inputs(11, 0.1, **shape)
    assert not kernels.supported(args[0].shape[2], args[3].shape[2], args[0].shape[1], chunk)
    scan = functools.partial(op.selective_scan, chunk=chunk)
    assert "pallas_call" not in str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(scan(*a)), argnums=range(6)))(*args))
    assert bool(jnp.all(scan(*args) == seed_form(*args, chunk=chunk)))
    for name, g, w in zip(NAMES, gradients(scan, args), gradients(lambda *a: op.selective_scan_recurrent(*a)[0], args)):
        assert rel(g, w) <= 10 * SCAN_TOL, name


def test_bfloat16_inputs_give_y_in_bfloat16_rounded_once(kernel_on_the_cpu):
    """x, B, C in bf16 as the layer has them: the kernel's y is its float32 y
    (the same values typed float32) rounded at the store and nowhere before,
    and the cotangents leave in their arguments' dtypes."""
    x, dt, A, B, C, D = inputs(13, 0.1, dtype=jnp.bfloat16)
    half = op.selective_scan(x, dt, A, B, C, D)
    full = op.selective_scan(*(t.astype(jnp.float32) for t in (x, dt, A, B, C, D)))
    assert half.dtype == jnp.bfloat16 and full.dtype == jnp.float32
    # the float32 y rounded ONCE: half an ulp of bf16 (2^-8 of the value at most) from it everywhere, and the same bf16 number in all
    # but the few elements where the two compiles of the interpreted body contract a product and a sum differently
    assert float(jnp.max(jnp.abs(half.astype(jnp.float32) - full) / jnp.abs(full))) <= 2.0 ** -8 * 1.001
    assert float(jnp.mean(half != full.astype(jnp.bfloat16))) < 1e-3
    for g, t in zip(gradients(op.selective_scan, (x, dt, A, B, C, D)), (x, dt, A, B, C, D)):
        assert g.dtype == t.dtype and g.shape == t.shape


def test_a_chunk_of_16_and_blocks_of_their_own(kernel_on_the_cpu):
    """`test_sambay_model.py`'s chunk; blocks given by hand tile the same result."""
    args = inputs(15, 0.1, b=2)
    x, dt, A, B, C, D = args
    assert rel(op.selective_scan(*args, chunk=16), op.selective_scan_recurrent(*args)[0]) <= SCAN_TOL
    y, entering = kernels.s6_scan_fwd(x, dt, A.T, B, C, D, chunk=16, interpret=True)
    wide, states = kernels.s6_scan_fwd(x, dt, A.T, B, C, D, chunk=16, block_c=256, block_s=128, interpret=True)
    assert entering.shape == (8, 2, 16, 256) and bool(jnp.all(y == wide)) and bool(jnp.all(entering == states))


def test_on_a_mesh_the_kernel_runs_under_shard_map_over_the_batch(kernel_on_the_cpu):
    """GSPMD cannot partition a Mosaic call: each device runs the kernel on its
    own rows, A's and D's cotangents summed over them."""
    mesh = build_mesh(MeshSpec(data=2), devices=jax.devices()[:2])
    args = inputs(17, 0.1, b=2)
    sharded = functools.partial(op.selective_scan, mesh=mesh, batch_axes=("data",))
    assert "shard_map" in str(jax.make_jaxpr(sharded)(*args))
    assert "shard_map" not in str(jax.make_jaxpr(functools.partial(sharded, chunk=8))(*args))  # the plain form is GSPMD's
    assert rel(sharded(*args), op.selective_scan(*args)) <= 1e-7
    for name, g, w in zip(NAMES, gradients(sharded, args), gradients(op.selective_scan, args)):
        assert rel(g, w) <= 1e-6, name


def _kernel_equations():
    x, dt, A, B, C, D = inputs(0, 0.1, dtype=jnp.bfloat16)
    outer = jax.make_jaxpr(functools.partial(kernels.s6_scan_fwd, interpret=False))(x, dt, A.T, B, C, D)
    call = next(e for e in outer.jaxpr.eqns if e.primitive.name == "pallas_call")
    assert call.params["name"] == "s6_scan_fwd"  # what a profile and the benchmark's readers find it by

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)

    return list(walk(call.params["jaxpr"]))


def test_no_exponential_in_the_kernel_can_take_a_positive_argument_and_nothing_is_a_default_precision_dot():
    """Every `exp` of the kernel's jaxpr takes the product of a row of dt and
    A, float32, and nothing else is exponentiated or divided; the kernel has no
    dot at all (a float32 dot at default precision is one bf16 pass in Mosaic
    and exact in interpret mode: PR 39's finding), and the file's text says the
    same: one `jnp.exp(`, of `dt[...] * A`."""
    eqns = _kernel_equations()
    made_by = {str(v): e for e in eqns for v in e.outvars}
    exps = [e for e in eqns if e.primitive.name == "exp"]
    assert len(exps) == CHUNK  # one a position of the unrolled chunk
    for e in exps:
        (arg,) = e.invars
        assert arg.aval.dtype == jnp.float32 and made_by[str(arg)].primitive.name == "mul"
    names = {e.primitive.name for e in eqns}
    assert not names & {"dot_general", "div", "exp2", "log", "pow", "cumsum", "cumlogsumexp"}, names
    assert all(v.aval.dtype != jnp.bfloat16 for e in eqns if e.primitive.name in ("mul", "add") for v in e.invars)
    text = inspect.getsource(kernels)
    assert text.count("jnp.exp(") == 1 and "jnp.exp(dt[k: k + 1] * A)" in text
    assert "jnp.dot" not in text and "einsum" not in text and "dot_general" not in text
