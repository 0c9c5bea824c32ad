"""The KDA forward kernel (`ops/pallas/kda.py`) in interpret mode on the CPU,
against the plain form it replaces on TPU (`ops/kda.py:_plain_forward`) and
the recurrence itself; the `custom_vjp` around both, whose backward is JAX's
own differentiation of the plain segment.

Interpret mode runs the kernel's own arithmetic: its products are bf16 halves
multiplied in three passes (`_split`, `_dot`), here as on the chip, so the
distance to the plain form on the CPU (whose float32 products are exact there)
is three passes' own rounding, not zero."""

import functools

import jax
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
def kernel_on_the_cpu(monkeypatch):
    """`kda_chunked` as a step lowered for TPU has it, the kernel interpreted:
    the dispatch takes its `tpu` branch."""
    monkeypatch.setattr(kernels, "kda_fwd", functools.partial(kernels.kda_fwd, interpret=True))
    monkeypatch.setattr(kda.jax.lax, "platform_dependent", lambda *args, tpu, default: tpu(*args))


def plain(q, k, v, g, beta):
    """The plain form alone, for JAX to differentiate: the oracle."""
    segments = functools.partial(kda._segments, chunk=kda.CHUNK, per_segment=kda._per_segment(k.shape[1], kda.CHUNK))
    return kda._positions(kda._plain_forward(*map(segments, (q, k, v, g, beta[..., None])))[0])


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
    segments = functools.partial(kda._segments, chunk=64, per_segment=2)
    o, entering = kernels.kda_fwd(*map(segments, (q, k, v, g)), beta)
    want_o, want = kda._plain_forward(*map(segments, (q, k, v, g, beta[..., None])))
    assert entering.shape == want.shape == (4, 2, 2, 128, 128) and o.shape == want_o.shape
    assert not entering[0].any()  # a sequence starts from nothing
    assert rel(entering[1:], want[1:]) <= 1e-5


@DECAYS
@pytest.mark.parametrize("s,tol", [(128, 1e-6), (512, 1e-5)], ids=["one-segment", "four-segments"])
def test_gradients_through_the_kernel_are_the_plain_forms(kernel_on_the_cpu, decay, s, tol):
    """The backward is `jax.vjp` of the plain segment, the same mathematics
    leaf by leaf: over one segment the kernel has no part in it; over several
    its part is the state that enters each (three passes' rounding, which a
    slow decay carries furthest)."""
    args = inputs(5, s, decay, 2)
    probe = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    got = jax.grad(lambda *a: jnp.sum(kda.kda_chunked(*a) * probe), argnums=range(5))(*args)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * probe), argnums=range(5))(*args)
    for name, a, w in zip("q k v g beta".split(), got, want):
        assert a.shape == w.shape and a.dtype == w.dtype, name
        assert bool(jnp.all(jnp.isfinite(a))) and rel(a, w) <= tol, (name, rel(a, w))


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
    per_segment = kda._per_segment(s, chunk or kda.CHUNK)
    assert not kernels.supported(d, d, chunk or kda.CHUNK, per_segment), why
    segments = functools.partial(kda._segments, chunk=chunk or kda.CHUNK, per_segment=per_segment)
    with pytest.raises(ValueError, match="kda_fwd: unsupported"):
        kernels.kda_fwd(*map(segments, args[:4]), args[4])
    assert rel(kda.kda_chunked(*args, chunk=chunk), kda.kda_recurrent(*args)) <= KDA_TOL


def test_supported_is_what_the_kernel_takes():
    assert kernels.supported(128, 128, 64, 32) and kernels.chunks_per_program(32) == 8
    assert kernels.chunks_per_program(6) == 6 and kernels.chunks_per_program(2) == 2
    assert not kernels.supported(128, 128, 64, 1) and not kernels.supported(128, 256, 64, 32)
    assert not kernels.supported(256, 128, 64, 32) and not kernels.supported(128, 128, 128, 32)


def test_no_kernel_dot_takes_float32_operands():
    """The trap: a float32 dot without a precision is ONE bf16 pass in Mosaic
    and exact in interpret mode, so no test on the CPU would see it.  Every
    dot of the kernel's jaxpr has bf16 operands and a float32 result."""
    q, k, v, g, beta = inputs(13, 128, 1.0, 1, h=1)
    segments = functools.partial(kda._segments, chunk=64, per_segment=2)
    closed = jax.make_jaxpr(kernels.kda_fwd)(*map(segments, (q, k, v, g)), beta)

    def dots(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from dots(sub)

    found = list(dots(closed.jaxpr))
    assert len(found) > 80
    for eqn in found:
        assert [v.aval.dtype for v in eqn.invars] == [jnp.bfloat16] * 2 and eqn.outvars[0].aval.dtype == jnp.float32, eqn
