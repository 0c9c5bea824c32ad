"""The selective scan's two kernels (`ops/pallas/selective_scan.py`) in
interpret mode on the CPU: against the plain chunked form they replace on TPU
(`ops/selective_scan.py:_plain_forward`, `_plain_backward`) and the recurrence
itself with JAX's gradient of it; the states the forward writes, which either
backward starts from; the `custom_vjp` around both, whose backward off TPU and
at refused shapes is JAX's differentiation of `_chunk_body`.

Blocks of 128 channels by 64 positions here (the chip's are `_BLOCK_C` by
`_BLOCK_S`): 256 channels are two programs, 128 positions two blocks of two
chunks each."""

import functools
import inspect
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import as_lowered_for_tpu

from ray_tpu.models.transformer import _remat_policy
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


def _as_lowered_for_tpu(patch):
    """`selective_scan` as a step lowered for TPU has it, the kernels
    interpreted: both directions' choices take the kernel."""
    for name in ("s6_scan_fwd", "s6_scan_bwd"):
        patch.setattr(kernels, name, functools.partial(getattr(kernels, name), interpret=True))
    as_lowered_for_tpu(patch)


@pytest.fixture
def kernel_on_the_cpu(monkeypatch):
    _as_lowered_for_tpu(monkeypatch)


@pytest.fixture
def no_kernel(no_kernel_runs):
    """As a step lowered for TPU has it, and a choice that takes a kernel fails (conftest.py)."""


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


COTANGENTS = ("dx", "ddt", "dA", "dB", "dC", "dD")
# name: (inputs' keywords, chunk, (channels, positions) a program).  128 positions are two blocks of 64; 256 channels two
# blocks of 128, whose partial sums for B and C XLA adds, or one of 256.
CASES = {
    "f32-one_row-one_block": (dict(s=64), 32, (128, 64)),
    "f32-two_rows-two_blocks": (dict(b=2, s=128), 32, (128, 64)),
    "bf16-one_row-four_blocks": (dict(s=256, dtype=jnp.bfloat16), 32, (128, 64)),
    "bf16-two_rows-three_blocks-chunk_16": (dict(b=2, s=192, dtype=jnp.bfloat16), 16, (128, 64)),
    "f32-one_row-two_blocks-one_channel_block": (dict(s=128), 32, (256, 64)),
    "f32-one_row-two_blocks-a_running_sum_past_minus_88": (dict(s=128, step=4.0), 32, (128, 64)),
}


def both(f, args, seed=9):
    """(y, its six cotangents under a random probe) of f."""
    y, pull = jax.vjp(f, *args)
    return (y, *pull(jax.random.normal(jax.random.PRNGKey(seed), y.shape).astype(y.dtype)))


@functools.lru_cache(maxsize=None)
def _case(name):
    """(the kernels', JAX's of the plain forward, JAX's of the recurrence) y and cotangents: computed once a case, read by a
    test a cotangent."""
    kw, chunk, (block_c, block_s) = CASES[name]
    args = inputs(21, **{"step": 0.1, **kw})
    plain = both(jax.jit(functools.partial(seed_form, chunk=chunk)), args)
    recurrence = both(jax.jit(lambda *a: op.selective_scan_recurrent(*a)[0].astype(a[0].dtype)), args)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(kernels, "_BLOCK_C", block_c)
        patch.setattr(kernels, "_BLOCK_S", block_s)
        _as_lowered_for_tpu(patch)
        got = both(jax.jit(functools.partial(op.selective_scan, chunk=chunk)), args)
    return got, plain, recurrence


@pytest.mark.parametrize("quantity", COTANGENTS)
@pytest.mark.parametrize("name", CASES)
def test_the_backward_kernel_gives_the_plain_forms_cotangents_and_the_recurrences(name, quantity):
    """`s6_scan_bwd` from the states `s6_scan_fwd` wrote, against JAX's own
    differentiation of the plain forward and of the token-by-token recurrence.
    float32 cotangents agree to rounding; one typed bf16 (x's, B's, C's with
    bf16 inputs) is the same float32 value rounded once, so the few elements
    that sit on a rounding boundary differ by one bf16 step."""
    got, plain, recurrence = _case(name)
    i = 1 + COTANGENTS.index(quantity)
    assert got[i].shape == plain[i].shape and got[i].dtype == plain[i].dtype == recurrence[i].dtype
    assert bool(jnp.all(jnp.isfinite(got[i].astype(jnp.float32))))
    rounded = got[i].dtype == jnp.bfloat16
    assert rel(got[i], plain[i]) <= (1e-3 if rounded else 1e-5), rel(got[i], plain[i])
    assert rel(got[i], recurrence[i]) <= (1e-3 if rounded else 10 * SCAN_TOL), rel(got[i], recurrence[i])


def test_the_running_sum_of_that_case_passes_minus_88_inside_a_chunk():
    kw, chunk, _ = CASES["f32-one_row-two_blocks-a_running_sum_past_minus_88"]
    _, dt, A, *_ = inputs(21, **kw)
    exponent = (dt[..., None] * A)[..., -1].reshape(1, -1, chunk, dt.shape[-1])
    assert float(jnp.max(jnp.sum(exponent, axis=2))) < -88  # every chunk of every channel: exp of it is 0 in float32


def test_the_backward_kernel_alone_from_given_states_and_blocks_of_its_own():
    """The kernel by itself against `_plain_backward` from the same entering
    states; programs of 128 or 256 channels and 64 or 128 positions tile the
    same result (B's and C's partial sums are added in another order)."""
    x, dt, A, B, C, D = inputs(23, 0.1, b=2)
    dy = jax.random.normal(jax.random.PRNGKey(1), x.shape)
    _, entering = kernels.s6_scan_fwd(x, dt, A.T, B, C, D, interpret=True)
    want = op._plain_backward(x, dt, A.T, B, C, D, entering, dy, CHUNK)
    got = kernels.s6_scan_bwd(x, dt, A.T, B, C, D, entering, dy, interpret=True)
    wide = kernels.s6_scan_bwd(x, dt, A.T, B, C, D, entering, dy, block_c=256, block_s=128, interpret=True)
    for name, g, w, v in zip(COTANGENTS, got, wide, want):
        assert g.shape == v.shape and g.dtype == v.dtype, name
        assert rel(g, v) <= 1e-5 and rel(g, w) <= 1e-6, (name, rel(g, v), rel(g, w))


def test_under_a_checkpoint_with_qkv_attns_policy_the_gradients_are_the_same(kernel_on_the_cpu):
    """Nothing of the scan carries a saved name: the layer's checkpoint runs
    `s6_scan_fwd` again in the backward, and `s6_scan_bwd` once."""
    args = inputs(25, 0.1)
    policy = _remat_policy(types.SimpleNamespace(remat_policy="qkv_attn"))
    loss = lambda f: (lambda *a: jnp.sum(f(*a) ** 2))
    want = jax.jit(jax.grad(loss(op.selective_scan), argnums=range(6)))(*args)
    got = jax.jit(jax.grad(loss(jax.checkpoint(op.selective_scan, policy=policy)), argnums=range(6)))(*args)
    for name, g, w in zip(NAMES, got, want):
        assert rel(g, w) <= 1e-6, name
    text = str(jax.make_jaxpr(jax.grad(loss(jax.checkpoint(op.selective_scan, policy=policy))))(*args))
    assert text.count("name=s6_scan_fwd") == 2 and text.count("name=s6_scan_bwd") == 1


def test_a_step_lowered_for_tpu_holds_one_kernel_a_direction_off_the_cpu_none():
    """The form follows the platform of the LOWERING (asked for by hand, from
    a CPU process): `s6_scan_fwd` and `s6_scan_bwd` once each for TPU and no
    reverse loop over `_chunk_body`'s associative scan; for the CPU the plain
    form in both directions."""
    args = inputs(27, 0.1, s=64, dtype=jnp.bfloat16)
    traced = jax.jit(jax.grad(lambda *a: jnp.sum(op.selective_scan(*a).astype(jnp.float32)), argnums=range(6))).trace(*args)
    for_tpu = traced.lower(lowering_platforms=("tpu",)).as_text()
    for kernel in ("s6_scan_fwd", "s6_scan_bwd"):
        assert for_tpu.count(f'kernel_name = "{kernel}"') == 1
    assert "stablehlo.while" not in for_tpu
    for_cpu = traced.lower(lowering_platforms=("cpu",)).as_text()
    assert "tpu_custom_call" not in for_cpu and for_cpu.count("stablehlo.while") >= 2


def test_off_tpu_the_backward_is_the_plain_one_bit_for_bit():
    """No monkeypatch: through the dispatch as the CPU lowers it, the six
    cotangents are `_plain_backward`'s (the parent's `_scan_bwd`, JAX's
    differentiation of `_chunk_body` a chunk at a time) from the plain
    forward's entering states, to the bit."""
    x, dt, A, B, C, D = args = inputs(29, 0.1, b=2, dtype=jnp.bfloat16)
    assert kernels.supported(x.shape[2], B.shape[2], x.shape[1], CHUNK)
    y, pull = jax.vjp(op.selective_scan, *args)
    dy = jax.random.normal(jax.random.PRNGKey(2), y.shape).astype(y.dtype)
    A_t, D32 = A.astype(jnp.float32).T, D.astype(jnp.float32)
    entering = op._plain_forward(x, dt, A_t, B, C, D, CHUNK)[1]
    dx, d_dt, d_A, dB, dC, d_D = op._plain_backward(x, dt, A_t, B, C, D32, entering, dy, CHUNK)
    for name, g, w in zip(NAMES, pull(dy), (dx, d_dt, d_A.T, dB, dC, d_D)):
        assert g.dtype == w.dtype and bool(jnp.all(g == w)), name


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


def _kernel_equations(name):
    x, dt, A, B, C, D = inputs(0, 0.1, dtype=jnp.bfloat16)
    more = () if name == "s6_scan_fwd" else (jnp.zeros((x.shape[1] // CHUNK, 1, 16, x.shape[2])), x)
    outer = jax.make_jaxpr(functools.partial(getattr(kernels, name), interpret=False))(x, dt, A.T, B, C, D, *more)
    call = next(e for e in outer.jaxpr.eqns if e.primitive.name == "pallas_call")
    assert call.params["name"] == name  # what a profile and the benchmark's readers find it by

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)

    return list(walk(call.params["jaxpr"]))


@pytest.mark.parametrize("name,exps_a_position", [("s6_scan_fwd", 1), ("s6_scan_bwd", 2)])
def test_no_exponential_in_the_kernel_can_take_a_positive_argument_and_nothing_is_a_default_precision_dot(name, exps_a_position):
    """Every `exp` of a kernel's jaxpr takes the product of a row of dt and
    A, float32, and nothing else is exponentiated or divided; the kernels have
    no dot at all (a float32 dot at default precision is one bf16 pass in Mosaic
    and exact in interpret mode: PR 39's finding), and the file's text says the
    same: one `jnp.exp(`, of `dt[...] * A`, for both."""
    eqns = _kernel_equations(name)
    made_by = {str(v): e for e in eqns for v in e.outvars}
    exps = [e for e in eqns if e.primitive.name == "exp"]
    assert len(exps) == exps_a_position * CHUNK  # of the unrolled chunk: the recurrence, and in the backward a_t once more
    for e in exps:
        (arg,) = e.invars
        assert arg.aval.dtype == jnp.float32 and made_by[str(arg)].primitive.name == "mul"
    names = {e.primitive.name for e in eqns}
    assert not names & {"dot_general", "div", "exp2", "log", "pow", "cumsum", "cumlogsumexp"}, names
    assert all(v.aval.dtype != jnp.bfloat16 for e in eqns if e.primitive.name in ("mul", "add") for v in e.invars)
    text = inspect.getsource(kernels)
    assert text.count("jnp.exp(") == 1 and "jnp.exp(dt[k: k + 1] * A)" in text
    assert "jnp.dot" not in text and "einsum" not in text and "dot_general" not in text
