"""The KDA kernels (`ops/pallas/kda.py`) in interpret mode on the CPU: the
forward against the plain form it replaces on TPU (`ops/kda.py:plain_forward`)
and the recurrence itself; the backward against JAX's own differentiation of
the plain segment, which stays the backward off TPU and at refused shapes; the
`custom_vjp` around both.

Interpret mode runs the kernel's own arithmetic: its products are bf16 halves
multiplied in three passes (`_split`, `_dot`), here as on the chip, so the
distance to the plain form on the CPU (whose float32 products are exact there)
is three passes' own rounding, not zero."""

import functools
import hashlib

import jax
import jax.extend
import jax.numpy as jnp
import pytest

from ray_tpu.ops import kda
from ray_tpu.ops.pallas import kda as kernels

KDA_TOL = 1e-5  # the chunked form's own against the recurrence (test_kimi_linear_model.py)


def inputs(seed, s, decay, b, h=2, d=128):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, s, h, d))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (b, s, h, d)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    return q, k, v, g, beta


def rel(a, b):
    return float(jnp.sqrt(jnp.mean(jnp.square(a - b)) / jnp.mean(jnp.square(b))))


@pytest.fixture(autouse=True)
def segments_of_two_chunks(monkeypatch):
    """128 positions a segment: 512 positions are four of them."""
    monkeypatch.setattr(kda, "SEGMENT", 2)


@pytest.fixture
def kernel_on_the_cpu(monkeypatch, lowered_for_tpu_on_the_cpu):
    """`kda_chunked` as a step lowered for TPU has it (conftest.py), the kernel interpreted."""
    monkeypatch.setattr(kernels, "kda_fwd", functools.partial(kernels.kda_fwd, interpret=True))
    monkeypatch.setattr(kernels, "kda_bwd", functools.partial(kernels.kda_bwd, interpret=True))


def plain(q, k, v, g, beta):
    """The plain form alone, for JAX to differentiate: the oracle."""
    segments = functools.partial(kda.segments, chunk=kda.CHUNK, per_segment=kda.per_segment(k.shape[1], kda.CHUNK))
    return kda.positions(kda.plain_forward(*map(segments, (q, k, v, g, beta[..., None])))[0])


DECAYS = pytest.mark.parametrize("decay", [1e-3, 1.0, 40.0], ids=["slow", "mixed", "fast"])


@DECAYS
@pytest.mark.parametrize("s", [128, 512], ids=["one-segment", "four-segments"])
@pytest.mark.parametrize("b", [1, 2], ids=["batch1", "batch2"])
def test_kernel_forward_is_the_plain_form_and_the_recurrence(kernel_on_the_cpu, decay, s, b):
    """`fast`: g is about -32 a token, so a channel's running sum passes -88
    (where `exp` is 0 in float32) within three positions of every chunk."""
    args = inputs(b, s, decay, b)
    assert decay < 40 or float(jnp.max(jnp.sum(args[3][:, :64], axis=1))) < -88
    got = kda.kda_chunked(*args)
    assert got.dtype == jnp.float32 and bool(jnp.all(jnp.isfinite(got)))
    assert rel(got, plain(*args)) <= 1e-5
    assert rel(got, kda.kda_recurrent(*args)) <= KDA_TOL


def test_kernel_writes_the_state_that_enters_each_segment(kernel_on_the_cpu):
    q, k, v, g, beta = inputs(3, 512, 0.05, 2)
    segments = functools.partial(kda.segments, chunk=64, per_segment=2)
    o, entering = kernels.kda_fwd(*map(segments, (q, k, v, g)), beta)
    want_o, want = kda.plain_forward(*map(segments, (q, k, v, g, beta[..., None])))
    assert entering.shape == want.shape == (4, 2, 2, 128, 128) and o.shape == want_o.shape
    assert not entering[0].any()  # a sequence starts from nothing
    assert rel(entering[1:], want[1:]) <= 1e-5


def grad_tol(name, decay):
    """Three passes' rounding, 5e-6 to 9e-6 in every cotangent; but dg under the
    `fast` decay is made of decays alone, and a decay is the exponential of a
    difference of running sums near -2,000, which float32 holds to 1.2e-4:
    there the plain form itself is 2.7e-5 from the recurrence's gradient."""
    return 1e-4 if (name, decay) == ("g", 40.0) else 1e-5


@functools.cache
def gradients_through_the_dispatch():
    """Jitted once, and traced under `kernel_on_the_cpu`: the cases of one shape share a compile."""
    return jax.jit(jax.grad(lambda q, k, v, g, beta, probe: jnp.sum(kda.kda_chunked(q, k, v, g, beta) * probe),
                            argnums=range(5)))


@DECAYS
@pytest.mark.parametrize("s", [128, 512], ids=["one-segment", "four-segments"])
def test_gradients_through_the_kernel_are_the_plain_forms(kernel_on_the_cpu, decay, s):
    """Both directions are kernels under the dispatch's `tpu` branch; the
    oracle is JAX's differentiation of the plain form, leaf by leaf."""
    args = inputs(5, s, decay, 2)
    probe = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    got = gradients_through_the_dispatch()(*args, probe)
    want = backward_kernel_and_oracle()[1](*args, probe)
    for name, a, w in zip("q k v g beta".split(), got, want):
        assert a.shape == w.shape and a.dtype == w.dtype, name
        assert bool(jnp.all(jnp.isfinite(a))) and rel(a, w) <= grad_tol(name, decay), (name, rel(a, w))


@functools.cache
def backward_kernel_and_oracle():
    """Jitted once: the cases of one shape share a compile."""
    segments = lambda x: kda.segments(x, kda.CHUNK, kda.per_segment(x.shape[1], kda.CHUNK))

    def kernel(q, k, v, g, beta, probe):
        blocks = tuple(map(segments, (q, k, v, g)))
        _, entering, pairs = kernels.kda_fwd(*blocks, beta, pair_states=True, interpret=True)
        *d, dbeta = kernels.kda_bwd(*blocks, beta, pairs, segments(probe), interpret=True)
        return (*map(kda.positions, d), dbeta), entering, pairs

    oracle = jax.grad(lambda q, k, v, g, beta, probe: jnp.sum(plain(q, k, v, g, beta) * probe), argnums=range(5))
    return jax.jit(kernel), jax.jit(oracle)


@DECAYS
@pytest.mark.parametrize("s", [128, 512], ids=["one-segment", "four-segments"])
@pytest.mark.parametrize("b", [1, 2], ids=["batch1", "batch2"])
def test_backward_kernel_gives_the_plain_forms_cotangents(decay, s, b):
    """`kda_bwd` alone, from the states `kda_fwd` wrote, against `jax.grad` of
    the plain form; v in bf16 as the layer has it, so dv is.  `fast`: a
    channel's running sum passes -88 inside every chunk, and dg is finite."""
    q, k, v, g, beta = inputs(17 + b, s, decay, b)
    v = v.astype(jnp.bfloat16)
    assert decay < 40 or float(jnp.max(jnp.sum(g[:, :64], axis=1))) < -88
    probe = jax.random.normal(jax.random.PRNGKey(9), v.shape)
    kernel, oracle = backward_kernel_and_oracle()
    got, entering, pairs = kernel(q, k, v, g, beta, probe)
    want = oracle(q, k, v, g, beta, probe)
    assert bool(jnp.all(pairs[:, :, 0] == entering))  # per_segment is 2 here: every pair opens a segment
    for name, a, w in zip("q k v g beta".split(), got, want):
        assert a.shape == w.shape and a.dtype == w.dtype, name
        a, w = a.astype(jnp.float32), w.astype(jnp.float32)
        tol = 4e-3 if name == "v" else grad_tol(name, decay)  # bf16: each side rounds its own float32 once more
        assert bool(jnp.all(jnp.isfinite(a))) and rel(a, w) <= tol, (name, rel(a, w))


def test_the_states_cotangent_crosses_segments_and_programs(monkeypatch):
    """A probe on the LAST 128 positions alone reaches the first: through the
    VMEM scratch from program to program (segments of 4 chunks, 2 a program)
    and from segment to segment."""
    monkeypatch.setattr(kda, "SEGMENT", 4)
    monkeypatch.setattr(kernels, "_ROWS", 128)
    q, k, v, g, beta = inputs(23, 512, 0.01, 1)
    probe = jax.random.normal(jax.random.PRNGKey(9), v.shape).at[:, :-128].set(0.0)
    kernel, oracle = backward_kernel_and_oracle()
    got, entering, pairs = jax.jit(kernel.__wrapped__)(q, k, v, g, beta, probe)  # traced anew: other constants
    assert entering.shape[0] == 2 and pairs.shape[:3] == (2, 1, 2)
    for name, a, w in zip("q k v g beta".split(), got, oracle(q, k, v, g, beta, probe)):
        if name == "q":  # a query has a part in its own position's output alone
            assert not a[:, :-128].any() and not w[:, :-128].any()
        else:
            assert float(jnp.max(jnp.abs(a[:, :128]))) > 0, name
            assert rel(a[:, :128], w[:, :128]) <= 2e-5, (name, rel(a[:, :128], w[:, :128]))
        assert rel(a, w) <= 1e-5, (name, rel(a, w))


# `str(jax.make_jaxpr(_pair))` at PR 39 (commit 80d96e1), 988 lines: the forward's arithmetic
PAIR_AT_PR_39 = "c443b6c8b33195f750c5e2d8be8a674048b3d5652fb612511b0a78c2624afb06"


def test_the_forward_kernels_arithmetic_is_pr_39s_text_for_text():
    tile = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    text = str(jax.make_jaxpr(kernels._pair)(tile, tile, tile, tile, jax.ShapeDtypeStruct((128, 1), jnp.float32), tile))
    assert hashlib.sha256(text.encode()).hexdigest() == PAIR_AT_PR_39


def test_pair_states_are_a_third_output_and_change_neither_of_the_two():
    q, k, v, g, beta = inputs(3, 512, 0.05, 2)
    blocks = [kda.segments(x, 64, 4) for x in (q, k, v, g)]  # two pairs a segment, two segments
    o, entering = kernels.kda_fwd(*blocks, beta, interpret=True)
    o_too, entering_too, pairs = kernels.kda_fwd(*blocks, beta, pair_states=True, interpret=True)
    assert bool(jnp.all(o == o_too)) and bool(jnp.all(entering == entering_too))  # bit for bit
    assert pairs.shape == (2, 2, 2, 2, 128, 128) and bool(jnp.all(pairs[:, :, 0] == entering))
    want = kda.plain_forward(*[kda.segments(x, 64, 2) for x in (q, k, v, g, beta[..., None])])[1]  # a pair a segment
    assert rel(jnp.moveaxis(pairs, 2, 1).reshape(want.shape)[1:], want[1:]) <= 1e-5


def test_off_tpu_the_custom_vjp_is_the_plain_form():
    """No kernel here (the dispatch's default branch): the forward is the
    plain form to the bit, and all five gradients are JAX's own differentiation
    of it, in each argument's dtype, to the order in which XLA sums (the
    backward walks the segments as its own scan)."""
    q, k, v, g, beta = inputs(7, 512, 1.0, 1)
    args = (q, k, v.astype(jnp.bfloat16), g, beta)
    probe = jax.random.normal(jax.random.PRNGKey(9), v.shape)
    assert bool(jnp.all(kda.kda_chunked(*args) == plain(*args)))
    got = jax.grad(lambda *a: jnp.sum(kda.kda_chunked(*a) * probe), argnums=range(5))(*args)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * probe), argnums=range(5))(*args)
    for name, a, w in zip("q k v g beta".split(), got, want):
        assert a.dtype == w.dtype and rel(a.astype(jnp.float32), w.astype(jnp.float32)) <= 1e-6, name


@pytest.mark.parametrize(
    "s,d,chunk,why",
    [(256, 64, None, "a head of half a lane tile"), (256, 128, 32, "another chunk"), (64, 128, None, "one chunk")],
    ids=["head64", "chunk32", "one-chunk"],
)
def test_shapes_the_kernel_refuses_run_the_plain_form(kernel_on_the_cpu, s, d, chunk, why):
    args = inputs(11, s, 1.0, 1, d=d)
    per_segment = kda.per_segment(s, chunk or kda.CHUNK)
    assert not kernels.supported(d, d, chunk or kda.CHUNK, per_segment), why
    segments = functools.partial(kda.segments, chunk=chunk or kda.CHUNK, per_segment=per_segment)
    with pytest.raises(ValueError, match="kda_fwd: unsupported"):
        kernels.kda_fwd(*map(segments, args[:4]), args[4])
    assert rel(kda.kda_chunked(*args, chunk=chunk), kda.kda_recurrent(*args)) <= KDA_TOL


@pytest.mark.parametrize("s,d,chunk", [(256, 64, None), (256, 128, 32), (64, 128, None)],
                         ids=["head64", "chunk32", "one-chunk"])
def test_at_shapes_the_kernel_refuses_the_backward_is_jaxs_own_of_the_segment(kernel_on_the_cpu, s, d, chunk):
    """No dispatch is traced at all: the gradient's jaxpr holds no kernel and no
    choice by platform, the residual beside the arguments is the segments'
    entering states alone, and the cotangents are the recurrence's."""
    args = inputs(11, s, 1.0, 1, d=d)
    chunk_ = chunk or kda.CHUNK
    segments = functools.partial(kda.segments, chunk=chunk_, per_segment=kda.per_segment(s, chunk_))
    with pytest.raises(ValueError, match="kda_bwd: unsupported"):
        kernels.kda_bwd(*map(segments, args[:4]), args[4], None, segments(args[2]))
    assert kda.PAIR.forward(kda.PAIR.call(args, chunk_, residuals=True), *args)[-1] is None
    loss = lambda f: lambda *a: jnp.sum(jnp.square(f(*a)))
    grad = jax.grad(loss(functools.partial(kda.kda_chunked, chunk=chunk)), argnums=range(5))
    text = str(jax.make_jaxpr(grad)(*args))
    assert "pallas_call" not in text and "platform_index" not in text
    want = jax.grad(loss(kda.kda_recurrent), argnums=range(5))(*args)
    for name, a, w in zip("q k v g beta".split(), grad(*args), want):
        assert rel(a, w) <= KDA_TOL, (name, rel(a, w))


def test_supported_is_what_the_kernel_takes():
    assert kernels.supported(128, 128, 64, 32) and kernels.chunks_per_program(32) == 8
    assert kernels.chunks_per_program(6) == 6 and kernels.chunks_per_program(2) == 2
    assert not kernels.supported(128, 128, 64, 1) and not kernels.supported(128, 256, 64, 32)
    assert not kernels.supported(256, 128, 64, 32) and not kernels.supported(128, 128, 128, 32)


def kernel_jaxprs():
    """The three KDA kernels, and the three of the scalar-decay pair (`ops/pallas/gdn.py`, PR 58), which
    hold the same contract: one value head, its decay one number a position."""
    from ray_tpu.ops.pallas import gdn

    q, k, v, g, beta = inputs(13, 128, 1.0, 1, h=1)
    blocks = [kda.segments(x, 64, 2) for x in (q, k, v, g)]
    pairs = jnp.zeros((1, 1, 1, 1, 128, 128))
    scalar = (q, k, v, g[..., 0], beta)
    return {
        "forward": jax.make_jaxpr(kernels.kda_fwd)(*blocks, beta),
        "forward-with-pair-states": jax.make_jaxpr(functools.partial(kernels.kda_fwd, pair_states=True))(*blocks, beta),
        "backward": jax.make_jaxpr(kernels.kda_bwd)(*blocks, beta, pairs, blocks[2]),
        "gdn-forward": jax.make_jaxpr(functools.partial(gdn.gdn_fwd, per_segment=2))(*scalar),
        "gdn-forward-with-pair-states": jax.make_jaxpr(functools.partial(gdn.gdn_fwd, per_segment=2, pair_states=True))(*scalar),
        "gdn-backward": jax.make_jaxpr(functools.partial(gdn.gdn_bwd, per_segment=2))(*scalar, pairs[0], v),
    }


def equations(jaxpr):
    """(equation, the jaxpr it sits in), nested jaxprs too."""
    for eqn in jaxpr.eqns:
        yield eqn, jaxpr
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(sub)


# (the kernel, its dots at least, its exponentials at least: KDA's six levels, from the start, to the end, the chunk's
# whole; the scalar decay's one [C, C] mask, from the start, to the end, each chunk's whole)
KERNELS = pytest.mark.parametrize(
    "which,least,decays",
    [("forward", 80, 9), ("forward-with-pair-states", 80, 9), ("backward", 200, 9),
     ("gdn-forward", 50, 5), ("gdn-forward-with-pair-states", 50, 5), ("gdn-backward", 90, 5)])


@KERNELS
def test_no_kernel_dot_takes_float32_operands(which, least, decays):
    """The trap: a float32 dot without a precision is ONE bf16 pass in Mosaic
    and exact in interpret mode, so no test on the CPU would see it.  Every
    dot of the kernels' jaxprs has bf16 operands and a float32 result."""
    found = [eqn for eqn, _ in equations(kernel_jaxprs()[which].jaxpr) if eqn.primitive.name == "dot_general"]
    assert len(found) > least
    for eqn in found:
        assert [v.aval.dtype for v in eqn.invars] == [jnp.bfloat16] * 2 and eqn.outvars[0].aval.dtype == jnp.float32, eqn


@KERNELS
def test_every_exponent_in_a_kernel_is_clamped_at_zero(which, least, decays):
    """A decay is `exp(min(.., 0))`: the rounding of a running sum may not turn
    one over 1, in the backward's recomputation as in the forward."""
    found = [(eqn, jaxpr) for eqn, jaxpr in equations(kernel_jaxprs()[which].jaxpr) if eqn.primitive.name == "exp"]
    assert len(found) >= decays
    for eqn, jaxpr in found:
        made_by = {id(out): e for e in jaxpr.eqns for out in e.outvars}[id(eqn.invars[0])]
        assert made_by.primitive.name == "min", made_by
        assert any(isinstance(v, jax.extend.core.Literal) and float(v.val) == 0.0 for v in made_by.invars), made_by
