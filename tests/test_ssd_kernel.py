"""Mamba-2's scan kernels (`ops/pallas/ssd.py`) in interpret mode on the CPU:
`ssd_chunked` as a step lowered for TPU has it (the `custom_vjp` whose two
directions are the kernels) against the plain chunked form it replaces there
(`ops/ssm.py:_plain_forward`) and JAX's own gradient of that, for x, dt, A, B,
C and D; the shapes `supported` refuses, which run the plain form; the dispatch
off TPU.

Chunks of 128 (one block of the [chunk, chunk] matrices) and of 256 (the
program's own: a diagonal and an off-diagonal block) here; sequences of one to
four chunks."""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import as_lowered_for_tpu

from ray_tpu.models.transformer import _remat_policy
from ray_tpu.ops import ssm as op
from ray_tpu.ops.pallas import ssd as kernels
from ray_tpu.parallel import MeshSpec, build_mesh

NAMES = ("y", "dx", "ddt", "dA", "dB", "dC", "dD")
# name: (inputs' keywords, chunk, tolerance of y, of the cotangents).  float32 inputs make every
# product exact in interpret mode, so both forms agree to rounding (dA to 3e-5: two sums of W
# that cancel, taken in another order); bf16 ones round a cotangent at different products
# (`ops/pallas/ssd.py`: PRECISION).
CASES = {
    "one_group-two_chunks-f32": (dict(s=256, h=4), 128, 1e-6, 1e-4),
    "eight_groups-one_chunk-f32": (dict(s=128, h=16, groups=8), 128, 1e-6, 1e-4),
    "a_group_every_two_heads-two_chunks-f32": (dict(s=256, h=4, groups=2), 128, 1e-6, 1e-4),
    "four_heads_of_32_a_tile-one_chunk-f32": (dict(s=128, h=4, p=32), 128, 1e-6, 1e-4),
    "one_group-two_chunks_of_256-f32": (dict(s=512, h=2), 256, 1e-6, 1e-4),
    "two_groups-two_rows-one_chunk_of_256-bf16": (dict(b=2, s=256, h=4, groups=2, dtype=jnp.bfloat16), 256, 2e-3, 1e-2),
    "one_group-four_chunks-bf16": (dict(s=512, h=2, dtype=jnp.bfloat16), 128, 2e-3, 1e-2),
}


def inputs(seed=0, b=1, s=256, h=4, p=64, n=128, groups=None, dtype=jnp.float32, step=0.05):
    """A = -(1..H) as Mamba-2 starts it; dt around `step`."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, s, h, p)).astype(dtype)
    dt = step * jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.arange(1, h + 1, dtype=jnp.float32)
    shape = (b, s, n) if groups is None else (b, s, groups, n)
    B, C = (jax.random.normal(k, shape).astype(dtype) * n ** -0.25 for k in ks[2:4])
    return x, dt, A, B, C, 1.0 + 0.1 * jax.random.normal(ks[4], (h,))


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / (np.mean(b ** 2) + 1e-300)))


def _as_lowered_for_tpu(patch):
    """The kernels interpreted and the dispatch taking its `tpu` branch; the
    jits around the kernels forget what they traced before and after."""
    for name in ("ssd_fwd", "ssd_bwd"):
        patch.setattr(kernels, name, functools.partial(getattr(kernels, name), interpret=True))
    as_lowered_for_tpu(patch)
    op._kernel_forward.clear_cache()
    op._kernel_backward.clear_cache()


@pytest.fixture
def kernel_on_the_cpu(monkeypatch):
    _as_lowered_for_tpu(monkeypatch)
    yield
    op._kernel_forward.clear_cache()
    op._kernel_backward.clear_cache()


@pytest.fixture
def no_kernel(monkeypatch):
    def refuse(*args, **kwargs):
        raise AssertionError("a kernel was called")

    monkeypatch.setattr(kernels, "ssd_fwd", refuse)
    monkeypatch.setattr(kernels, "ssd_bwd", refuse)
    op._kernel_forward.clear_cache()
    op._kernel_backward.clear_cache()


def both(f, args, seed=9):
    """(y, its six gradients under a random probe) of f."""
    probe = jax.random.normal(jax.random.PRNGKey(seed), args[0].shape)
    y, pull = jax.vjp(f, *args)
    return (y, *pull(probe.astype(y.dtype)))


@functools.lru_cache(maxsize=None)
def _case(name):
    """(the kernels', the plain form's) y and gradients: computed once a case, read by a test a quantity."""
    kw, chunk, _, _ = CASES[name]
    args = inputs(**kw)
    want = both(functools.partial(op._plain_forward, chunk=chunk), args)
    with pytest.MonkeyPatch.context() as patch:
        _as_lowered_for_tpu(patch)
        got = both(functools.partial(op.ssd_chunked, chunk=chunk), args)
    op._kernel_forward.clear_cache()
    op._kernel_backward.clear_cache()
    return got, want


@pytest.mark.parametrize("quantity", NAMES)
@pytest.mark.parametrize("name", CASES)
def test_the_kernels_equal_the_plain_form_and_its_gradient(name, quantity):
    got, want = _case(name)
    i = NAMES.index(quantity)
    assert got[i].shape == want[i].shape and got[i].dtype == want[i].dtype
    assert rel(got[i], want[i]) < CASES[name][2 if i == 0 else 3]


def test_the_kernel_writes_the_state_that_enters_each_chunk():
    x, dt, A, B, C, D = inputs(s=512, h=2)
    dtc, cum = op._running_sums(dt, A, 128)
    _, entering = kernels.ssd_fwd(x, dtc.reshape(dt.shape), cum.reshape(dt.shape), B, C, D, chunk=128, interpret=True)
    assert entering.shape == (1, 4, 128, 2 * 64)
    # the recurrence itself, a position at a time: H_t = exp(dt A) H + dt x (outer) B
    def step(H, inp):
        x_t, dt_t, B_t = inp  # [h, p], [h], [n]
        H = jnp.exp(dt_t * A)[:, None, None] * H + (dt_t[:, None] * x_t)[:, :, None] * B_t
        return H, H
    _, states = jax.lax.scan(step, jnp.zeros((2, 64, 128)), (x[0], dt[0], B[0]))
    for c in range(1, 4):  # [h, p, n] after position 128 c - 1 -> [n, h p]
        want = states[128 * c - 1].transpose(2, 0, 1).reshape(128, 128)
        assert rel(entering[0, c], want) < 1e-5
    assert not entering[0, 0].any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_a_running_sum_past_minus_88_inside_a_chunk_gives_no_nan_and_no_inf(kernel_on_the_cpu, dtype):
    """dt = 1 and A = -(1..4): head 4's running sum is -4 a position, past -88
    (where float32's exp is 0 and a quotient of two would be 0/0) from position 22 of a chunk."""
    x, _, A, B, C, D = inputs(s=256, h=4, dtype=dtype)
    dt = jnp.ones((1, 256, 4))
    assert float(jnp.min(op._running_sums(dt, A, 128)[1])) < -500
    got = both(functools.partial(op.ssd_chunked, chunk=128), (x, dt, A, B, C, D))
    want = both(functools.partial(op._plain_forward, chunk=128), (x, dt, A, B, C, D))
    for g, w in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
        assert rel(g, w) < (1e-4 if dtype == jnp.float32 else 2e-2)


def test_under_a_checkpoint_with_qkv_attns_policy_the_gradients_are_the_same(kernel_on_the_cpu):
    """Nothing of the scan carries a saved name: the checkpoint runs `ssd_fwd` again in the backward."""
    args = inputs(s=256, h=2)
    policy = _remat_policy(types.SimpleNamespace(remat_policy="qkv_attn"))
    scan = functools.partial(op.ssd_chunked, chunk=128)
    loss = lambda f: (lambda *a: jnp.sum(f(*a) ** 2))
    want = jax.grad(loss(scan), argnums=range(6))(*args)
    got = jax.jit(jax.grad(loss(jax.checkpoint(scan, policy=policy)), argnums=range(6)))(*args)
    for g, w in zip(got, want):
        assert rel(g, w) < 1e-6
    text = str(jax.make_jaxpr(jax.grad(loss(jax.checkpoint(scan, policy=policy))))(*args))
    assert text.count("name=ssd_fwd") == 2 and text.count("name=ssd_bwd") == 1


def test_on_a_mesh_the_kernels_run_under_shard_map_over_the_batch(kernel_on_the_cpu):
    args = inputs(b=2, s=128, h=4, groups=2)
    mesh = build_mesh(MeshSpec(data=2), devices=jax.devices()[:2])
    scan = functools.partial(op.ssd_chunked, chunk=128)
    want = both(scan, args)
    got = both(functools.partial(scan, mesh=mesh, batch_axes="data"), args)
    for g, w in zip(got, want):
        assert rel(g, w) < 1e-6
    assert "shard_map" in str(jax.make_jaxpr(functools.partial(scan, mesh=mesh, batch_axes="data"))(*args))


REFUSED = {
    "a_sequence_of_no_whole_chunks": dict(h=4, p=64, n=128, groups=1, s=384, chunk=256),
    "a_chunk_of_no_whole_blocks": dict(h=4, p=64, n=128, groups=1, s=192, chunk=64),
    "an_odd_head_size": dict(h=4, p=48, n=128, groups=1, s=256, chunk=128),
    "a_head_of_a_whole_lane_tile": dict(h=4, p=128, n=128, groups=1, s=256, chunk=128),
    "a_state_of_half_a_lane_tile": dict(h=4, p=64, n=64, groups=1, s=256, chunk=128),
    "groups_that_do_not_divide_the_heads": dict(h=6, p=64, n=128, groups=4, s=256, chunk=128),
    "one_head_of_64_a_group": dict(h=4, p=64, n=128, groups=4, s=256, chunk=128),
}


@pytest.mark.parametrize("name", REFUSED)
def test_supported_refuses(name):
    assert not kernels.supported(**REFUSED[name])
    assert kernels.supported(**{**REFUSED[name], **dict(h=4, p=64, n=128, groups=1, s=256, chunk=128)})


@pytest.mark.parametrize("kw,chunk", [(dict(s=256, h=4, p=48), 128), (dict(s=256, h=4, n=64), 128),
                                      (dict(s=192, h=4), 64), (dict(s=256, h=4, groups=4), 128)],
                         ids=["odd_head_size", "small_state", "short_chunk", "one_head_a_group"])
def test_refused_shapes_run_the_plain_form_differentiated_by_jax(no_kernel, lowered_for_tpu_on_the_cpu, kw, chunk):
    args = inputs(**kw)
    got = both(functools.partial(op.ssd_chunked, chunk=chunk), args)
    want = both(functools.partial(op._plain_forward, chunk=chunk), args)
    for g, w in zip(got, want):
        assert bool(jnp.all(g == w))
    assert "custom_vjp" not in str(jax.make_jaxpr(functools.partial(op.ssd_chunked, chunk=chunk))(*args))


@pytest.mark.parametrize("kw", [dict(s=384, h=4), dict(s=256, h=6, groups=4)], ids=["sequence", "groups"])
def test_what_ssd_chunked_refused_before_it_still_refuses(kw):
    with pytest.raises(ValueError):
        op.ssd_chunked(*inputs(**kw), chunk=256)


def test_off_tpu_supported_shapes_take_the_plain_form_inside_the_custom_vjp(no_kernel):
    """The dispatch follows the platform the step is lowered for: here the CPU,
    so the plain form, bit for bit, and JAX's gradient of it from the arguments."""
    args = inputs(s=256, h=4)
    got = both(functools.partial(op.ssd_chunked, chunk=128), args)
    want = both(functools.partial(op._plain_forward, chunk=128), args)
    for g, w in zip(got, want):
        assert bool(jnp.all(g == w))


def test_a_step_lowered_for_tpu_holds_one_kernel_a_direction_off_the_cpu_none():
    """The form follows the platform of the LOWERING (here asked for by hand,
    from a CPU process): `ssd_fwd` and `ssd_bwd` once each for TPU, the plain
    form's einsums for the CPU.  (That the compiled ops sit under `ssm/scan`:
    `test_tpu_compiled_step.py`.)"""
    args = inputs(s=128, h=2, dtype=jnp.bfloat16)
    traced = jax.jit(jax.grad(lambda *a: jnp.sum(op.ssd_chunked(*a, chunk=128).astype(jnp.float32)))).trace(*args)
    for_tpu = traced.lower(lowering_platforms=("tpu",)).as_text()
    for kernel in ("ssd_fwd", "ssd_bwd"):
        assert for_tpu.count(f'kernel_name = "{kernel}"') == 1
    assert "tpu_custom_call" not in traced.lower(lowering_platforms=("cpu",)).as_text()
