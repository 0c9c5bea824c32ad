"""The positions-major convolution kernels of a delta layer
(`ops/pallas/delta_conv.py`) in interpret mode on the CPU, at Kimi Linear's and
Qwen3-Next's head layouts and K = 4, over two blocks of positions so that the
halo is crossed: forward and every cotangent (dx, dw, and through the norm)
against the plain form (`ops/delta_conv.py`), which is what the layers ran
until PR 60 (`causal_conv1d_silu` + `jnp.split` + the L2 norm: held equal to
that composition here, bit for bit); the `custom_vjp` around both; the block
indices that keep an array still while another's columns run."""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import as_lowered_for_tpu

from ray_tpu.ops import delta_conv as op
from ray_tpu.ops import ssm
from ray_tpu.ops.pallas import delta_conv as kernels

f32, bf16 = jnp.float32, jnp.bfloat16
S, ROWS, K = 64, 32, 4  # two blocks of 32 positions
# q heads, k heads, v channels, the columns of x: Kimi's 32 + 32 + 32 heads are the whole array, Qwen3-Next's 16 + 16 key
# heads and 32 value heads the first 8,192 columns of `gdn_qkvz`'s 12,288
LAYOUTS = {"kimi": (32, 32, 4096, 12288), "qwen3_next": (16, 16, 4096, 12288)}
LAYOUT = pytest.mark.parametrize("layout", LAYOUTS)
DTYPE = pytest.mark.parametrize("dtype", [f32, bf16], ids=["float32", "bfloat16"])
OUTPUTS = ("q", "k", "v")
COTANGENTS = ("dx", "dw")


def rel(a, b):
    a, b = a.astype(f32), b.astype(f32)
    return float(jnp.sqrt(jnp.mean(jnp.square(a - b)) / jnp.mean(jnp.square(b))))


def on_the_cpu(wq, wk, rows=ROWS):
    """The two kernels interpreted in blocks of `rows` positions, at these weights' head counts: (x, w [C, K], ..)."""
    size = dict(q_heads=wq.shape[0], k_heads=wk.shape[0], rows=rows, interpret=True)
    return functools.partial(kernels.conv_fwd, **size), functools.partial(kernels.conv_bwd, **size)


@functools.cache
def case(layout, dtype, b=1):
    """(x, wq, wk, wv), a probe of the three outputs, both forms' outputs and
    cotangents: once a layout and dtype, shared by the cases below."""
    hq, hk, cv, cx = LAYOUTS[layout]
    ks = jax.random.split(jax.random.PRNGKey(sum(map(ord, layout))), 7)
    x = jax.random.normal(ks[0], (b, S, cx)).astype(dtype)
    wq, wk = (jax.random.normal(k, (h, 128, K)) * K ** -0.5 for k, h in zip(ks[1:3], (hq, hk)))
    wv = jax.random.normal(ks[3], (cv, K)) * K ** -0.5
    args = (x, wq, wk, wv)
    widths = (hq * 128, hk * 128, cv)
    probe = tuple(jax.random.normal(k, (b, S, w)).astype(t) for k, w, t in zip(ks[4:], widths, (f32, f32, dtype)))
    plain = jax.jit(op._plain_forward)(*args), jax.jit(op._plain_backward)(*args, *probe)
    fwd, bwd = map(jax.jit, on_the_cpu(wq, wk))
    kernel = fwd(x, op._weights(wq, wk, wv)), bwd(x, op._weights(wq, wk, wv), *probe)
    return args, probe, plain, kernel


@LAYOUT
@DTYPE
@pytest.mark.parametrize("name", OUTPUTS)
def test_kernel_forward_is_the_plain_form(layout, dtype, name):
    """q and k differ by the order of a 128-term float32 sum, v (and in bf16
    the y the norm reads) by an odd last place of the sigmoid's."""
    _, _, (plain, _), (kernel, _) = case(layout, dtype)
    i = OUTPUTS.index(name)
    assert kernel[i].shape == plain[i].shape and kernel[i].dtype == plain[i].dtype == (dtype if name == "v" else f32)
    assert rel(kernel[i], plain[i]) < (2e-4 if dtype == bf16 else 1e-6)


@LAYOUT
@pytest.mark.parametrize("name", ("q", "k"))
def test_q_and_k_leave_the_kernel_normalised_per_head(layout, name):
    """|k| = 1 and |q| = 128^-0.5 over each head's 128 lanes, whatever the convolution gave."""
    _, _, _, (kernel, _) = case(layout, bf16)
    heads = kernel[OUTPUTS.index(name)].reshape(1, S, -1, 128)
    np.testing.assert_allclose(jnp.linalg.norm(heads, axis=-1), 1.0 if name == "k" else 128 ** -0.5, rtol=1e-4)


@LAYOUT
@pytest.mark.parametrize("name", COTANGENTS)
def test_kernel_backward_is_the_plain_form_in_float32(layout, name):
    """In float32 nothing is rounded on the way: dx (through the norm for q's
    and k's columns, the anti-causal taps across the halo) and dw (the sum
    over both blocks of positions) are the plain form's."""
    _, _, (_, plain), (_, kernel) = case(layout, f32)
    i = COTANGENTS.index(name)
    assert kernel[i].shape == plain[i].shape and kernel[i].dtype == plain[i].dtype
    assert rel(kernel[i], plain[i]) < 1e-5


@LAYOUT
@pytest.mark.parametrize("name", COTANGENTS)
def test_kernel_backward_in_bfloat16_is_nearer_the_float32_cotangent_than_the_plain_form(layout, name):
    """The plain form rounds the norm's cotangent to bf16 on its way into the
    convolution's backward (y is bf16 there); the kernel keeps it in VMEM in
    float32.  Held against the plain form with that one rounding left out."""
    (x, wq, wk, wv), probe, (_, plain), (_, kernel) = case(layout, bf16)
    conv = op._convolution(x, wq, wk, wv)
    _, through_norm = jax.vjp(lambda y: op._normed(y, wq, wk), ssm._conv_silu_plain(*conv).astype(f32))
    dy, = through_norm(tuple(p.astype(f32) for p in probe))
    exact = ssm._conv_silu_bwd_plain(*conv, dy)[:2]
    i = COTANGENTS.index(name)
    assert rel(kernel[i], exact[i]) <= rel(plain[i], exact[i]) + 1e-6
    assert rel(kernel[i], plain[i]) < 5e-3


@LAYOUT
def test_the_halo_is_crossed_and_the_start_sees_zeros(layout):
    """The first K - 1 positions of the second block read the first block's
    last ones (one block of 64 positions gives the same numbers), and the
    sequence's first positions read zeros, not the halo's clamped block."""
    (x, *w), probe, _, (fwd, bwd) = case(layout, f32)
    one_fwd, one_bwd = on_the_cpu(*w[:2], rows=S)
    whole = one_fwd(x, op._weights(*w)), one_bwd(x, op._weights(*w), *probe)
    for blocked, one in zip((*fwd, *bwd), (*whole[0], *whole[1])):
        np.testing.assert_allclose(blocked, one, rtol=1e-5, atol=1e-5)


def test_a_batch_row_starts_its_own_sequence():
    """Two rows in one call: the second row's first positions read zeros, and
    its blocks of q, k and v are its own (the phases' frozen indices carry the row)."""
    (x, *w), probe, _, _ = case("qwen3_next", f32)
    x2, probe2 = jnp.concatenate([x, x[:, ::-1]]), tuple(jnp.concatenate([p, 2 * p]) for p in probe)
    two_fwd, two_bwd = on_the_cpu(*w[:2])
    fwd = two_fwd(x2, op._weights(*w))
    dx, dw = two_bwd(x2, op._weights(*w), *probe2)
    plain_dx, plain_dw = op._plain_backward(x2, *w, *probe2)
    for got, want in zip((*fwd, dx, dw), (*op._plain_forward(x2, *w), plain_dx, plain_dw)):
        assert rel(got, want) < 1e-5


# -- the op around the kernels -------------------------------------------------------------


def until_pr_60(x, wq, wk, wv):
    """What `mixers/kda.py` and `mixers/gdn.py` ran in place of `delta_conv`."""
    w = jnp.concatenate([wq.reshape(-1, K), wk.reshape(-1, K), wv])
    conv = ssm.causal_conv1d_silu(x[..., : w.shape[0]], w, jnp.zeros((w.shape[0],), w.dtype))
    q, k, v = jnp.split(conv, (wq.shape[0] * wq.shape[1], w.shape[0] - wv.shape[0]), axis=-1)

    def l2_normed(a, heads, scale=1.0):
        af = a.reshape(*a.shape[:2], heads, -1).astype(f32)
        return af * (jax.lax.rsqrt(jnp.sum(jnp.square(af), axis=-1, keepdims=True) + 1e-6) * scale)

    return l2_normed(q, wq.shape[0], wq.shape[1] ** -0.5), l2_normed(k, wk.shape[0]), v


def small(d=128, dtype=bf16):
    """Two q heads, one k head, 256 channels of v, of 1,024 columns; `d` = 64 is a shape the kernels refuse."""
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    return (jax.random.normal(ks[0], (2, S, 1024)).astype(dtype), jax.random.normal(ks[1], (2, d, K)) * 0.5,
            jax.random.normal(ks[2], (1, d, K)) * 0.5, jax.random.normal(ks[3], (256, K)) * 0.5)


def loss(f, *args):
    q, k, v = f(*args)
    return jnp.sum(jnp.sin(q)) + jnp.sum(k * k[:, ::-1]) + jnp.sum(jnp.cos(v.astype(f32)))


@pytest.mark.parametrize("d", [128, 64], ids=["taken", "refused"])
def test_off_tpu_the_op_is_bit_for_bit_what_the_layers_ran_until_pr_60(d):
    """The plain form IS the old composition: outputs and all four gradients equal, at a shape the kernels take and at one they refuse."""
    args = small(d)
    assert all(bool(jnp.all(a == b)) for a, b in zip(op.delta_conv(*args), until_pr_60(*args)))
    new, old = (jax.grad(functools.partial(loss, f), argnums=(0, 1, 2, 3))(*args) for f in (op.delta_conv, until_pr_60))
    assert all(n.shape == o.shape and n.dtype == o.dtype and bool(jnp.all(n == o)) for n, o in zip(new, old))


@contextlib.contextmanager
def kernels_on_the_cpu():
    """`delta_conv` as a step lowered for TPU has it, the kernels interpreted in blocks of 32 positions."""
    with pytest.MonkeyPatch.context() as patch:
        for name in ("conv_fwd", "conv_bwd"):
            patch.setattr(kernels, name, functools.partial(getattr(kernels, name), rows=ROWS, interpret=True))
        as_lowered_for_tpu(patch)
        yield


@DTYPE
def test_the_custom_vjp_hands_every_argument_its_cotangent_through_the_kernels(dtype):
    """x's cotangent in x's whole shape (zeros in the columns the convolution
    does not read), the three weights' in theirs, through `jax.grad` of the op."""
    args = small(dtype=dtype)
    plain = jax.grad(functools.partial(loss, op.delta_conv), argnums=(0, 1, 2, 3))(*args)
    with kernels_on_the_cpu():
        out = op.delta_conv(*args)
        through = jax.grad(functools.partial(loss, op.delta_conv), argnums=(0, 1, 2, 3))(*args)
    assert [o.shape for o in out] == [(2, S, 2, 128), (2, S, 1, 128), (2, S, 256)]
    assert [(t.shape, t.dtype) for t in through] == [(a.shape, a.dtype) for a in args]
    assert not bool(jnp.any(through[0][..., 640:]))
    for t, p in zip(through, plain):
        assert rel(t, p) < (5e-3 if dtype == bf16 else 1e-5)


def test_on_a_mesh_each_device_runs_its_own_rows_through_the_kernels():
    """Under `shard_map` over the batch axis (the weights replicated) the three outputs keep the rows' sharding."""
    from jax.sharding import Mesh

    args = small(dtype=f32)
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    with kernels_on_the_cpu():
        sharded = jax.jit(functools.partial(op.delta_conv, mesh=mesh, batch_axes="data"))(*args)
    for got, want in zip(sharded, op.delta_conv(*args)):
        assert got.shape == want.shape and rel(got, want) < 1e-6


@pytest.mark.parametrize("bad", ["weights", "columns"])
def test_the_op_refuses_weights_that_are_no_layout_of_x(bad):
    x, wq, wk, wv = small()
    with pytest.raises(ValueError, match="delta_conv"):
        op.delta_conv(x[..., :512], wq, wk, wv) if bad == "columns" else op.delta_conv(x, wq.reshape(-1, K), wk, wv)


# -- the blocks ------------------------------------------------------------------------------


@pytest.mark.parametrize("s,cx,hq,hk,d,cv,k,takes", [
    (16384, 12288, 32, 32, 128, 4096, 4, True), (8192, 12288, 16, 16, 128, 4096, 4, True),
    (8192, 8192, 16, 16, 64, 4096, 4, False), (8200, 12288, 16, 16, 128, 4096, 4, False), (64, 512, 1, 1, 128, 192, 4, False),
    (64, 512, 2, 2, 128, 256, 4, False), (64, 512, 1, 1, 128, 256, 9, False), (64, 512, 1, 1, 128, 256, 1, False),
], ids=["kimi", "qwen3-next", "heads-of-64", "positions-off-a-tile", "v-off-a-lane-tile", "wider-than-x", "nine-taps", "one-tap"])
def test_supported_takes_heads_of_128_and_whole_tiles(s, cx, hq, hk, d, cv, k, takes):
    assert kernels.supported(s, cx, hq, hk, d, cv, k) is takes


@LAYOUT
def test_blocks_are_whole_heads_and_divide_each_of_the_three_arrays(layout):
    hq, hk, cv, _ = LAYOUTS[layout]
    g = kernels._Grid(16384, hq, hk, cv, None, None)
    assert (g.rows, g.lanes) == (kernels._ROWS, kernels._LANES) and g.lanes % 128 == 0
    assert (g.nq, g.nk, g.nv) == (hq * 128 // g.lanes, hk * 128 // g.lanes, cv // g.lanes)
    assert kernels._Grid(48, 1, 3, 640, None, None).lanes == 128 and kernels._Grid(48, 1, 3, 640, None, None).rows == 48


def test_an_array_outside_its_phase_keeps_the_block_it_will_first_have_or_last_had():
    """k's columns are the grid's blocks 2 and 3 of 6: before them its block
    is (row 0, column 0), which the phase's first step then writes; after them
    the last it wrote; in between the grid's own.  So no step of another phase
    moves it, and nothing is copied for it there."""
    g = kernels._Grid(128, 2, 2, 256, 32, 128)
    assert g.phases == ((0, 2), (2, 2), (4, 2)) and g.grid(3) == (3, 6, 4)
    index = lambda j, i: tuple(int(v) for v in g.of_phase(2, 2).index_map(1, j, i))
    assert [index(j, i) for j in (0, 1) for i in (0, 3)] == [(1, 0, 0)] * 4
    assert [index(2, 0), index(2, 3), index(3, 1)] == [(1, 0, 0), (1, 3, 0), (1, 1, 1)]
    assert [index(j, i) for j in (4, 5) for i in (0, 3)] == [(1, 3, 1)] * 4
    after = lambda j, i: tuple(int(v) for v in g.of_phase(2, 2, which="after").index_map(0, j, i))
    assert after(2, 0) == (0, 2, 0) and after(2, 3) == (0, 7, 0)  # the 16 positions after a block of 32; clamped at the end
    before = lambda i: tuple(int(v) for v in g.of_x("before").index_map(0, 5, i))
    assert before(0) == (0, 0, 5) and before(2) == (0, 3, 5)
