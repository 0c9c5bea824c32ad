"""The scalar-decay kernels (`ops/pallas/gdn.py`) in interpret mode on the
CPU: the forward against the plain form it replaces on TPU (`ops/kda.py`'s, on
q and k repeated and the head's decay broadcast: `kda_chunked` as the layer
called it before) and the recurrence itself; the backward against JAX's own
differentiation of the plain segment, which stays the backward off TPU and at
refused shapes; the `custom_vjp` around both (`ops/gdn.py`).

As in `test_kda_kernel.py`, interpret mode runs the kernels' own arithmetic
(three bf16 passes a product), so the distance to the plain form on the CPU is
three passes' rounding, not zero."""

import contextlib
import functools

import jax
import jax.numpy as jnp
import pytest
from conftest import as_lowered_for_tpu

from ray_tpu.ops import gdn, kda
from ray_tpu.ops.pallas import gdn as kernels

TOL = 1e-5  # the chunked form's own against the recurrence (test_kimi_linear_model.py)
SHAPES = pytest.mark.parametrize("s,hk,hv", [(256, 1, 2), (512, 2, 4)], ids=["two-segments-one-key-head", "four-segments-two-key-heads"])
# `fast`: A scaled by 30, as `test_qwen3_next_model.py` does: a head keeps e^-20 a token at its fastest
DECAYS = pytest.mark.parametrize("decay", [1e-3, 1.0, 30.0], ids=["slow", "mixed", "fast"])


def inputs(seed, s, decay, hk, hv, b=1, d=128):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, s, hk, d))
    k = jax.random.normal(ks[1], (b, s, hk, d))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, s, hv, d))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (b, s, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, hv)))
    return q, k, v, g, beta


def rel(a, b):
    return float(jnp.sqrt(jnp.mean(jnp.square(a - b)) / jnp.mean(jnp.square(b))))


def per_channel(q, k, v, g, beta):
    """The arguments as the layer handed them to `kda_chunked` before."""
    group = v.shape[2] // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    return jnp.repeat(q, group, axis=2), k, v, jnp.broadcast_to(g[..., None], k.shape), beta


@pytest.fixture(autouse=True)
def segments_of_two_chunks(monkeypatch):
    """128 positions a segment: 512 positions are four of them."""
    monkeypatch.setattr(kda, "SEGMENT", 2)


@contextlib.contextmanager
def kernels_on_the_cpu():
    """`gdn_chunked` as a step lowered for TPU has it, the kernels interpreted:
    the dispatch takes its `tpu` branch.  Around the call alone: `kda_chunked`,
    which the same dispatch would hand ITS kernels, stays the plain form
    outside."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(kernels, "gdn_fwd", functools.partial(kernels.gdn_fwd, interpret=True))
        patch.setattr(kernels, "gdn_bwd", functools.partial(kernels.gdn_bwd, interpret=True))
        as_lowered_for_tpu(patch)
        yield


@functools.cache
def forward_through_the_dispatch():
    """Jitted once, and traced under `kernels_on_the_cpu`: the cases of one shape share a compile."""
    return jax.jit(gdn.gdn_chunked)


@DECAYS
@SHAPES
def test_kernel_forward_is_kda_chunked_with_the_decay_broadcast_and_the_recurrence(decay, s, hk, hv):
    """`fast`: g is about -24 a token, so a head's running sum passes -88
    (where `exp` is 0 in float32) within four positions of every chunk."""
    args = inputs(hv, s, decay, hk, hv)
    assert decay < 30 or float(jnp.max(jnp.sum(args[3][:, :64], axis=1))) < -88
    with kernels_on_the_cpu():
        got = forward_through_the_dispatch()(*args)
    assert got.shape == args[2].shape and got.dtype == jnp.float32 and bool(jnp.all(jnp.isfinite(got)))
    assert rel(got, kda.kda_chunked(*per_channel(*args))) <= TOL
    assert rel(got, kda.kda_recurrent(*per_channel(*args))) <= TOL


def test_kernel_writes_the_state_that_enters_each_segment_and_each_pair():
    q, k, v, g, beta = inputs(3, 512, 0.05, 2, 4)
    o, entering = kernels.gdn_fwd(q, k, v, g, beta, per_segment=4, interpret=True)  # two pairs a segment, two segments
    o_too, entering_too, pairs = kernels.gdn_fwd(q, k, v, g, beta, per_segment=4, pair_states=True, interpret=True)
    assert bool(jnp.all(o == o_too)) and bool(jnp.all(entering == entering_too))  # a third output changes neither
    assert entering.shape == (2, 1, 4, 128, 128) and pairs.shape == (1, 4, 4, 128, 128)
    assert not entering[0].any() and bool(jnp.all(pairs[:, ::2] == jnp.moveaxis(entering, 0, 1)))
    want = gdn._plain_forward(q, k, v, g, beta, 64)[1]  # `SEGMENT` is 2 here: a pair a segment
    assert rel(jnp.moveaxis(pairs, 1, 0)[1:], want[1:]) <= TOL


def grad_tol(name, decay):
    """Three passes' rounding, 5e-6 to 9e-6 in every cotangent; dg under the
    `fast` decay is made of decays alone, the exponentials of differences of
    running sums near -1,500, which float32 holds to 1e-4
    (`test_kda_kernel.py`)."""
    return 1e-4 if (name, decay) == ("g", 30.0) else 1e-5


@functools.cache
def gradients_through_the_dispatch():
    return jax.jit(jax.grad(lambda q, k, v, g, beta, probe: jnp.sum(gdn.gdn_chunked(q, k, v, g, beta) * probe),
                            argnums=range(5)))


@functools.cache
def oracle():
    """JAX's own differentiation of the plain form on the repeated and broadcast arguments: `jnp.repeat`'s and
    `broadcast_to`'s transposes sum dq and dk over a group and dg over the channels."""
    def plain(q, k, v, g, beta):
        segments = functools.partial(kda.segments, chunk=kda.CHUNK, per_segment=kda.per_segment(k.shape[1], kda.CHUNK))
        q, k, v, g, beta = per_channel(q, k, v, g, beta)
        return kda.positions(kda.plain_forward(*map(segments, (q, k, v, g, beta[..., None])))[0])

    return jax.jit(jax.grad(lambda q, k, v, g, beta, probe: jnp.sum(plain(q, k, v, g, beta) * probe), argnums=range(5)))


@DECAYS
@SHAPES
def test_the_five_cotangents_through_the_kernels_are_the_plain_forms(decay, s, hk, hv):
    """Both directions are kernels under the dispatch's `tpu` branch; dq and dk
    come back summed over a key head's value heads, dg as [b, S, Hv]; v in
    bf16 as the layer has it, so dv is."""
    q, k, v, g, beta = inputs(5 + hv, s, decay, hk, hv)
    v = v.astype(jnp.bfloat16)
    probe = jax.random.normal(jax.random.PRNGKey(9), v.shape)
    with kernels_on_the_cpu():
        got = gradients_through_the_dispatch()(q, k, v, g, beta, probe)
    want = oracle()(q, k, v, g, beta, probe)
    for name, a, w in zip("q k v g beta".split(), got, want):
        assert a.shape == w.shape and a.dtype == w.dtype, name
        a, w = a.astype(jnp.float32), w.astype(jnp.float32)
        tol = 4e-3 if name == "v" else grad_tol(name, decay)  # bf16: each side rounds its own float32 once more
        assert bool(jnp.all(jnp.isfinite(a))) and rel(a, w) <= tol, (name, rel(a, w))


def test_the_states_cotangent_crosses_segments_and_programs(monkeypatch):
    """A probe on the LAST 128 positions alone reaches the first: through the
    VMEM scratch from program to program (segments of 4 chunks; a program is
    2 chunks of both value heads of its key head) and from segment to
    segment."""
    monkeypatch.setattr(kda, "SEGMENT", 4)
    monkeypatch.setattr(kernels, "_UNITS", 2)
    assert kernels._chunks_per_program(4, 2) == 2
    q, k, v, g, beta = inputs(23, 512, 0.01, 1, 2)
    probe = jax.random.normal(jax.random.PRNGKey(9), v.shape).at[:, :-128].set(0.0)
    _, entering, pairs = kernels.gdn_fwd(q, k, v, g, beta, per_segment=4, pair_states=True, interpret=True)
    assert entering.shape[0] == 2 and pairs.shape[1] == 4
    got = kernels.gdn_bwd(q, k, v, g, beta, pairs, probe, per_segment=4, interpret=True)
    for name, a, w in zip("q k v g beta".split(), got, oracle()(q, k, v, g, beta, probe)):
        if name == "q":  # a query has a part in its own position's output alone
            assert not a[:, :-128].any() and not w[:, :-128].any()
        else:
            assert float(jnp.max(jnp.abs(a[:, :128]))) > 0, name
            assert rel(a[:, :128], w[:, :128]) <= 2e-5, (name, rel(a[:, :128], w[:, :128]))
        assert rel(a, w) <= 1e-5, (name, rel(a, w))


def test_off_tpu_the_custom_vjp_is_the_plain_form():
    """No kernel here (the dispatch's default branch): the forward is
    `kda_chunked` on the repeated and broadcast arguments to the bit, and all
    five gradients are JAX's own differentiation of it, in each argument's
    shape and dtype, to the order in which XLA sums."""
    q, k, v, g, beta = inputs(7, 512, 1.0, 2, 4)
    args = (q, k, v.astype(jnp.bfloat16), g, beta)
    probe = jax.random.normal(jax.random.PRNGKey(9), v.shape)
    assert bool(jnp.all(gdn.gdn_chunked(*args) == kda.kda_chunked(*per_channel(*args))))
    got = jax.grad(lambda *a: jnp.sum(gdn.gdn_chunked(*a) * probe), argnums=range(5))(*args)
    for name, a, w in zip("q k v g beta".split(), got, oracle()(*args, probe)):
        assert a.shape == w.shape and a.dtype == w.dtype, name
        assert rel(a.astype(jnp.float32), w.astype(jnp.float32)) <= 1e-6, name


REFUSED = pytest.mark.parametrize("s,d,chunk", [(128, 64, None), (128, 128, 32), (64, 128, None)],
                                  ids=["head64", "chunk32", "one-chunk"])


@REFUSED
def test_shapes_the_kernels_refuse_run_the_plain_form(s, d, chunk):
    """No dispatch is traced at all: the gradient's jaxpr holds no kernel and
    no choice by platform, the residual beside the arguments is the segments'
    entering states alone, and o and the cotangents are the recurrence's."""
    args = inputs(11, s, 1.0, 1, 2, d=d)
    chunk_ = chunk or kda.CHUNK
    per_segment = kda.per_segment(s, chunk_)
    assert not kernels.supported(d, d, chunk_, per_segment, 2, 1)
    if chunk is None:  # the kernels' own chunk: they say so themselves
        with pytest.raises(ValueError, match="gdn_fwd: unsupported"):
            kernels.gdn_fwd(*args, per_segment=per_segment)
        with pytest.raises(ValueError, match="gdn_bwd: unsupported"):
            kernels.gdn_bwd(*args, None, args[2], per_segment=per_segment)
    loss = lambda f: lambda *a: jnp.sum(jnp.square(f(*a)))
    grad = jax.grad(loss(functools.partial(gdn.gdn_chunked, chunk=chunk)), argnums=range(5))
    with kernels_on_the_cpu():
        assert gdn.PAIR.forward(gdn.PAIR.call(args, chunk_, residuals=True), *args)[-1] is None
        text = str(jax.make_jaxpr(grad)(*args))
        assert "pallas_call" not in text and "platform_index" not in text
        got_o, got = gdn.gdn_chunked(*args, chunk=chunk), grad(*args)
    assert rel(got_o, kda.kda_recurrent(*per_channel(*args))) <= TOL
    want = jax.grad(loss(lambda *a: kda.kda_recurrent(*per_channel(*a))), argnums=range(5))(*args)
    for name, a, w in zip("q k v g beta".split(), got, want):
        assert a.shape == w.shape and rel(a, w) <= TOL, (name, rel(a, w))


def test_supported_is_what_the_kernels_take():
    # a program: `_UNITS` pairs of chunks over the group's value heads, whole pairs that divide the segment's chunks
    assert [kernels._chunks_per_program(32, group) for group in (1, 2, 4, 8)] == [8, 4, 2, 2]
    assert kernels._chunks_per_program(6, 1) == 6 and kernels._chunks_per_program(6, 2) == 2
    assert kernels.supported(128, 128, 64, 32, 32, 16) and kernels.supported(128, 128, 64, 2, 2, 2)
    assert not kernels.supported(128, 128, 64, 32, 32, 12) and not kernels.supported(128, 128, 64, 32, 3, 2)
    assert not kernels.supported(128, 128, 64, 1, 2, 1) and not kernels.supported(128, 256, 64, 32, 2, 1)
    assert not kernels.supported(256, 128, 64, 32, 2, 1) and not kernels.supported(128, 128, 128, 32, 2, 1)
    with pytest.raises(ValueError, match="whole groups"):
        gdn.gdn_chunked(*inputs(1, 128, 1.0, 2, 3))
    with pytest.raises(ValueError, match="power-of-two chunk"):
        gdn.gdn_chunked(*inputs(1, 96, 1.0, 1, 2), chunk=64)
