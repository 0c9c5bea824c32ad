"""Attention kernel correctness: blockwise and pallas (interpret) and ring
attention must all match the O(S^2) reference implementation.

Mirrors the reference's approach of unit-testing each numeric component in
isolation (SURVEY.md §4), adapted: our kernels are JAX/pallas, tested on the
8-device virtual CPU mesh from conftest.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import (
    blockwise_attention,
    reference_attention,
)


def _qkv(key, b=2, s=256, h=4, kv=None, d=32):
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, kv or h, d), jnp.float32)
    v = jax.random.normal(kv_, (b, s, kv or h, d), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_blockwise_matches_reference(causal):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    ref = reference_attention(q, k, v, causal=causal)
    blk = blockwise_attention(q, k, v, causal=causal, block_size=64)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(blk), atol=2e-5, rtol=2e-5)


def test_blockwise_gqa():
    q, k, v = _qkv(jax.random.PRNGKey(1), h=8, kv=2)
    ref = reference_attention(q, k, v, causal=True)
    blk = blockwise_attention(q, k, v, causal=True, block_size=64)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(blk), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_pallas_flash_matches_reference(causal):
    from ray_tpu.ops.pallas.flash_attention import flash_attention

    q, k, v = _qkv(jax.random.PRNGKey(2), s=256, d=64)
    ref = reference_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5, rtol=2e-5)


def test_pallas_flash_grad():
    from ray_tpu.ops.pallas.flash_attention import flash_attention

    q, k, v = _qkv(jax.random.PRNGKey(3), b=1, s=128, h=2, d=32)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64, block_k=64).sum()

    def loss_ref(q, k, v):
        return reference_attention(q, k, v, causal=True).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


def test_pallas_flash_at_head_size_64_with_a_callers_scale():
    """Granite 4.0-H's attention: heads of 64, GQA 4:1, and the published
    softmax scale 1/64 (not 64 ** -0.5): forward and all three gradients of
    the kernels in interpret mode against `reference_attention`."""
    from ray_tpu.ops.pallas.flash_attention import flash_attention

    q, k, v = _qkv(jax.random.PRNGKey(7), b=1, s=256, h=4, kv=1, d=64)
    q, k = 4.0 * q, 4.0 * k  # logits of order one at this scale, so the softmax is not flat
    scale = 1 / 64

    def run(fn):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v))), argnums=(0, 1, 2))(q, k, v)

    flash = lambda q, k, v: flash_attention(q, k, v, causal=True, scale=scale, block_q=128, block_k=64,  # noqa: E731
                                            bwd_block_q=64, bwd_block_k=128)
    ref = lambda q, k, v: reference_attention(q, k, v, causal=True, scale=scale)  # noqa: E731
    np.testing.assert_allclose(np.asarray(flash(q, k, v)), np.asarray(ref(q, k, v)), atol=2e-5, rtol=2e-5)
    (_, g_flash), (_, g_ref) = run(flash), run(ref)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)
    # the scale is the caller's: the default one gives another function
    other = reference_attention(q, k, v, causal=True)
    assert np.abs(np.asarray(other) - np.asarray(ref(q, k, v))).max() > 1e-2


def test_auto_dispatch_gives_head_size_64_to_the_kernel_when_lowered_for_tpu():
    """ONE rule for every model: sequence lengths a multiple of 128, head size
    a multiple of 64.  Head size 32 stays with the XLA forms."""
    from ray_tpu.ops.attention import dot_product_attention

    def lowered(d):
        q = jax.ShapeDtypeStruct((1, 256, 2, d), jnp.bfloat16)
        fn = jax.jit(lambda q, k, v: dot_product_attention(q, k, v, scale=1 / 64))
        return fn.trace(q, q, q).lower(lowering_platforms=("tpu",)).as_text()

    assert 'kernel_name = "flash_fwd"' in lowered(64) and 'kernel_name = "flash_fwd"' in lowered(128)
    assert "tpu_custom_call" not in lowered(32)


@pytest.mark.parametrize("causal", [True, False])
def test_pallas_flash_grad_noncausal_and_mixed_blocks(causal):
    """Backward kernels with bwd tile sizes differing from fwd tiles."""
    from ray_tpu.ops.pallas.flash_attention import flash_attention

    q, k, v = _qkv(jax.random.PRNGKey(5), b=1, s=256, h=2, d=32)
    g = jax.random.normal(jax.random.PRNGKey(6), q.shape, jnp.float32)

    def loss_flash(q, k, v):
        out = flash_attention(
            q, k, v, causal=causal,
            block_q=128, block_k=128, bwd_block_q=64, bwd_block_k=128,
        )
        return (out * g).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=causal) * g).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


def test_pallas_flash_grad_gqa():
    from ray_tpu.ops.pallas.flash_attention import flash_attention

    q, k, v = _qkv(jax.random.PRNGKey(7), b=1, s=128, h=4, kv=2, d=32)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64, block_k=64).sum()

    def loss_ref(q, k, v):
        return reference_attention(q, k, v, causal=True).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(causal):
    from ray_tpu.ops.ring_attention import ring_attention_sharded
    from ray_tpu.parallel import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(data=2, seq=4, tensor=1))
    q, k, v = _qkv(jax.random.PRNGKey(4), b=2, s=64, h=4, d=16)
    ref = reference_attention(q, k, v, causal=causal)
    out = ring_attention_sharded(q, k, v, mesh, causal=causal, head_axis=None)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5, rtol=2e-5)


# -- the table of visible tile pairs (PR 70; in the place of PR 55's index maps) -------------------


def _mask_of_a_tile(qrows, krows, window, bd, causal=True):
    """The mask over one tile pair by pair, in numpy, from the rules
    themselves (the causal one and its window; the three of block diffusion)."""
    q, k = qrows[:, None], krows[None, :]
    if bd is None:
        seen = (q >= k) if causal else np.ones((len(qrows), len(krows)), bool)
        return seen if window is None else seen & (q - k < window)
    q_noisy, k_noisy = q < bd.noisy, k < bd.noisy
    qb, kb = np.where(q_noisy, q, q - bd.noisy) // bd.block, np.where(k_noisy, k, k - bd.noisy) // bd.block
    return np.where(k_noisy, q_noisy & (kb == qb), np.where(q_noisy, kb < qb, kb <= qb))


def _tiles_of_the_mask(sq, sk, bq, bk, causal, window, bd):
    """(holds a pair, every pair) of the dense mask by (query tile, key tile), brute force."""
    some, whole = np.zeros((sq // bq, sk // bk), bool), np.zeros((sq // bq, sk // bk), bool)
    for qi in range(sq // bq):
        for ki in range(sk // bk):
            mask = _mask_of_a_tile(np.arange(qi * bq, (qi + 1) * bq), np.arange(ki * bk, (ki + 1) * bk), window, bd, causal)
            some[qi, ki], whole[qi, ki] = mask.any(), mask.all()
    return some, whole


def _the_table_is_the_masks(sq, sk, bq, bk, causal, window, bd):
    """`tile_pairs` against the dense mask reduced to tiles, both outer axes:
    the same pairs, ascending within an own tile, every own tile present (one
    that sees nothing with ONE pair, masked), the first / last flags on the
    right pairs, the masked word iff the mask over the tile is not all true.
    Returns the forward-oriented (query tile, key tile) pairs."""
    from ray_tpu.ops.pallas import flash_attention as fa

    some, whole = _tiles_of_the_mask(sq, sk, bq, bk, causal, window, bd)
    for keys in (True, False):
        some_, whole_ = (some, whole) if keys else (some.T, whole.T)
        want = []
        for own in range(some_.shape[0]):
            others = np.flatnonzero(some_[own]) if some_[own].any() else [some_.shape[1] - 1]
            want += [(own, int(j), n == 0, n == len(others) - 1, not whole_[own, j]) for n, j in enumerate(others)]
        table = fa.tile_pairs(sq, sk, bq, bk, causal, window, bd, keys)
        assert {a.dtype for a in table} == {np.dtype(np.int32)} and len({len(a) for a in table}) == 1
        got = [(int(i), int(j), bool(w & fa._FIRST), bool(w & fa._LAST), bool(w & fa._MASKED)) for i, j, w in zip(*table)]
        assert got == want, keys
    return [tuple(int(x) for x in p) for p in np.argwhere(some)]


def _pairs(table, *, keys=True):
    """A table's (own tile, other tile) pairs in the grid's order; without
    `keys` (a dkv table: key tiles outside) turned to (query tile, key tile)."""
    return [(int(i), int(j)) if keys else (int(j), int(i)) for i, j in zip(table.own, table.other)]


def _rectangle_every_pair_masked(monkeypatch):
    """`tile_pairs` answering with every tile pair of the rectangle, each
    under the mask: the grid that leaves no pair out and trusts the mask alone."""
    from ray_tpu.ops.pallas import flash_attention as fa

    real = fa.tile_pairs

    def rectangle(sq, sk, bq, bk, causal, window, bd, keys):
        table = real(sq, sk, bq, bk, False, None, None, keys)
        return table._replace(kind=table.kind | fa._MASKED)

    monkeypatch.setattr(fa, "tile_pairs", rectangle)


@pytest.mark.parametrize("seq, tiles, d, dv", [(512, (128, 128), 64, 64), (1024, (256, 128), 128, 128),
                                               (512, (128, 128), 192, 128)])
def test_leaving_the_invisible_pairs_out_of_a_causal_calls_grid_changes_no_bit_of_out_lse_dq_dk_dv(monkeypatch, seq, tiles, d, dv):
    """A pair no query of which sees a key adds exact zeros wherever it stands
    in its row's walk; the visible ones are walked in the rectangle's order."""
    from ray_tpu.ops.pallas import flash_attention as fa

    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(seq + d), 4)
    q = jax.random.normal(kq, (1, seq, 2, d), jnp.float32)
    k = jax.random.normal(kk, (1, seq, 2, d), jnp.float32)
    v = jax.random.normal(kv, (1, seq, 2, dv), jnp.float32)
    g = jax.random.normal(kg, (1, seq, 2, dv), jnp.float32)
    bq, bk = tiles
    blocks = dict(causal=True, scale=d ** -0.5, block_q=bq, block_k=bk)

    def everything():
        out, lse = fa._flash_fwd(q, k, v, **blocks)
        return (out, lse) + fa._flash_bwd(q, k, v, out, lse, g, **blocks)

    changed = everything()
    _rectangle_every_pair_masked(monkeypatch)
    assert len(fa.tile_pairs(seq, seq, bq, bk, True, None, None, True).own) == (seq // bq) * (seq // bk)
    rectangles = everything()
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), changed, rectangles):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(changed[0]), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("seq, bq, bk", [(16384, 1024, 1024), (16384, 1024, 512), (4096, 1024, 512), (1024, 256, 128),
                                         (1024, 128, 256), (1024, 1024, 512)])
def test_a_causal_calls_table_holds_the_tiles_on_and_under_the_diagonal_in_the_rectangles_order(seq, bq, bk):
    from ray_tpu.ops.pallas import flash_attention as fa

    n_q, n_k = seq // bq, seq // bk
    runs = lambda qi, ki: ki * bk <= qi * bq + bq - 1  # noqa: E731  (what the parent's kernels predicated on, all three)
    visible = [(qi, ki) for qi in range(n_q) for ki in range(n_k) if runs(qi, ki)]
    assert _the_table_is_the_masks(seq, seq, bq, bk, True, None, None) == visible
    # forward and dq: a query tile's key tiles ascending; dkv: a key tile's query tiles ascending
    assert _pairs(fa.tile_pairs(seq, seq, bq, bk, True, None, None, True)) == visible
    assert _pairs(fa.tile_pairs(seq, seq, bq, bk, True, None, None, False), keys=False) == sorted(visible, key=lambda p: p[::-1])
    # the counter: the table's pairs over the rectangle they replace, either axis outside
    for keys in (True, False):
        assert fa.causal_steps_copying_pct(seq, bq, bk, keys=keys) == pytest.approx(100 * len(visible) / (n_q * n_k))
    # a call without a mask: the full rectangle from the same builder, no pair masked
    for keys in (True, False):
        whole = fa.tile_pairs(seq, seq, bq, bk, False, None, None, keys)
        n_own, n_other = (n_q, n_k) if keys else (n_k, n_q)
        assert _pairs(whole) == [(i, j) for i in range(n_own) for j in range(n_other)] and not (whole.kind & fa._MASKED).any()


def test_causal_steps_copying_counts_the_visible_pairs_at_the_tiles_in_use():
    from ray_tpu.ops.pallas import flash_attention as fa

    assert fa.causal_steps_copying_pct(16384, 1024, 1024, keys=True) == pytest.approx(100 * 136 / 256)
    assert fa.causal_steps_copying_pct(16384, 1024, 512, keys=True) == pytest.approx(100 * 272 / 512)  # dq
    assert fa.causal_steps_copying_pct(16384, 1024, 512, keys=False) == pytest.approx(100 * 272 / 512)  # dkv
    assert fa.causal_steps_copying_pct(1024, 1024, 1024, keys=True) == 100  # one q tile: the table is the rectangle
    assert fa.causal_steps_copying_pct(1024, 1024, 512, keys=False) == 100
    assert fa.causal_forward_tiles(16384, 128, 128) == (1024, 1024) and fa.causal_forward_tiles(8192, 256, 256) == (1024, 512)
    assert fa.causal_forward_tiles(4224, 128, 128) == (384, 384) and fa.causal_forward_tiles(1100, 128, 128) is None


# (what, queries, keys, window, mask, forward tiles, backward tiles, pairs a head: forward, dq, dkv)
_TABLE_CASES = [
    ("causal-16384", 16384, 16384, None, None, (1024, 1024), (1024, 512), (136, 272, 272)),  # `mistral7b-1chip.seq16k`
    ("diffusion-2x8192-b4", 16384, 16384, None, (4, 8192), (1024, 1024), (1024, 512), (80, 160, 160)),  # `sdar`
    ("window-513-of-2048", 2048, 2048, 513, None, (512, 512), (512, 512), (7, 7, 7)),  # `dots3-note`'s tiles: [1, 2, 2, 2]
    ("window-1024-of-16384", 16384, 16384, 1024, None, (1024, 1024), (1024, 512), (31, 62, 62)),  # `mellum2`
    ("no-mask", 1024, 512, None, None, (256, 128), (128, 256), (16, 16, 16)),
]


@pytest.mark.parametrize("what, sq, sk, window, diffusion, fwd, bwd, counts", _TABLE_CASES, ids=[c[0] for c in _TABLE_CASES])
def test_the_table_is_the_dense_mask_reduced_to_tiles_at_the_cells_shapes(what, sq, sk, window, diffusion, fwd, bwd, counts):
    from ray_tpu.ops.attention import BlockDiffusion
    from ray_tpu.ops.pallas import flash_attention as fa

    bd = None if diffusion is None else BlockDiffusion(*diffusion)
    causal = what != "no-mask" and bd is None
    _the_table_is_the_masks(sq, sk, *fwd, causal, window, bd)
    _the_table_is_the_masks(sq, sk, *bwd, causal, window, bd)
    tables = [fa.tile_pairs(sq, sk, *tiles, causal, window, bd, keys) for tiles, keys in ((fwd, True), (bwd, True), (bwd, False))]
    assert tuple(len(t.own) for t in tables) == counts
    if window == 513:
        assert np.bincount(tables[0].own).tolist() == [1, 2, 2, 2]
    assert fa.tile_pairs(sq, sk, *fwd, causal, window, bd, True) is tables[0]  # built once a (shape, mask): every layer asks for the same one


def test_a_causal_call_with_more_keys_than_queries_gives_every_key_tile_a_pair_and_matches_the_reference():
    """`sq != sk`: a key tile past the last query sees nothing and keeps ONE
    pair, masked everywhere, so that its dk and dv blocks are written (zeros)."""
    from ray_tpu.ops.pallas import flash_attention as fa

    _the_table_is_the_masks(256, 512, 128, 128, True, None, None)
    _the_table_is_the_masks(512, 256, 128, 128, True, None, None)
    dkv = fa.tile_pairs(256, 512, 128, 128, True, None, None, False)  # 4 key tiles, 2 query tiles
    assert _pairs(dkv) == [(0, 0), (0, 1), (1, 1), (2, 1), (3, 1)] and (dkv.kind[-2:] == fa._FIRST | fa._LAST | fa._MASKED).all()
    assert _pairs(fa.tile_pairs(256, 512, 128, 128, True, None, None, True)) == [(0, 0), (1, 0), (1, 1)]
    q, k, v = _qkv(jax.random.PRNGKey(11), b=1, s=512, h=2, d=64)
    q = q[:, :256]
    flash = lambda q, k, v: fa.flash_attention(  # noqa: E731
        q, k, v, causal=True, block_q=128, block_k=128, bwd_block_q=128, bwd_block_k=128)
    np.testing.assert_allclose(np.asarray(flash(q, k, v)), np.asarray(reference_attention(q, k, v, causal=True)),
                               atol=2e-5, rtol=2e-5)
    grads = jax.grad(lambda *a: flash(*a).sum(), argnums=(0, 1, 2))(q, k, v)
    wanted = jax.grad(lambda *a: reference_attention(*a, causal=True).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads, wanted):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)
    assert not np.asarray(grads[1][:, 256:]).any() and not np.asarray(grads[2][:, 256:]).any()


# -- the block-diffusion mask (PR 62) ------------------------------------------------------


def _explicit_block_diffusion(q, k, v, block, noisy):
    """Softmax attention under the mask built pair by pair from the three
    rules, in numpy: the oracle of the three forms."""
    rows, heads = q.shape[1], q.shape[2]
    is_noisy = np.arange(rows) < noisy
    blk = np.where(is_noisy, np.arange(rows), np.arange(rows) - noisy) // block
    mask = np.zeros((rows, rows), bool)
    for i in range(rows):
        for j in range(rows):
            if is_noisy[i] and is_noisy[j]:
                mask[i, j] = blk[j] == blk[i]
            elif is_noisy[i]:
                mask[i, j] = blk[j] < blk[i]
            elif not is_noisy[j]:
                mask[i, j] = blk[j] <= blk[i]
    kk, vv = (jnp.repeat(a, heads // a.shape[2], axis=2) for a in (k, v))
    logits = jnp.where(mask, jnp.einsum("bqhd,bkhd->bhqk", q, kk) * q.shape[-1] ** -0.5, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, axis=-1), vv), mask


# (rows, noisy rows, pallas tiles): tiles smaller than, equal to and larger than a copy (one tile holds the call),
# a query tile that is the whole call over key tiles that are not, and the plain forward's one copy
_DIFFUSION_LAYOUTS = [(256, 128, (64, 64, 64, 32)), (256, 128, (128, 128, 128, 64)), (256, 128, (1024, 1024, 1024, 512)),
                      (256, 128, (256, 128, 256, 64)), (256, 0, (64, 64, 64, 32)), (256, 0, (128, 64, 64, 128))]


@pytest.mark.parametrize("block", [4, 32])
@pytest.mark.parametrize("rows, noisy, tiles", _DIFFUSION_LAYOUTS)
@pytest.mark.parametrize("form", ["reference", "blockwise", "pallas"])
def test_the_three_forms_under_the_block_diffusion_mask_match_an_explicit_mask(form, rows, noisy, tiles, block):
    """Forward and the three gradients of each form of the core (the Pallas
    kernels in interpret mode) against the mask written out pair by pair."""
    from ray_tpu.ops.attention import BlockDiffusion
    from ray_tpu.ops.pallas.flash_attention import flash_attention

    bd = BlockDiffusion(block, noisy)
    q, k, v = _qkv(jax.random.PRNGKey(7), b=1, s=rows, h=4, kv=2, d=64)
    weight = jax.random.normal(jax.random.PRNGKey(8), q.shape)
    core = {
        "reference": lambda q, k, v: reference_attention(q, k, v, block_diffusion=bd),
        "blockwise": lambda q, k, v: blockwise_attention(q, k, v, block_size=64, block_diffusion=bd),
        "pallas": lambda q, k, v: flash_attention(q, k, v, block_diffusion=bd, block_q=tiles[0], block_k=tiles[1],
                                                  bwd_block_q=tiles[2], bwd_block_k=tiles[3]),
    }[form]
    want, want_grads = jax.value_and_grad(
        lambda q, k, v: (_explicit_block_diffusion(q, k, v, block, noisy)[0] * weight).sum(), (0, 1, 2))(q, k, v)
    got, got_grads = jax.jit(jax.value_and_grad(lambda q, k, v: (core(q, k, v) * weight).sum(), (0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5, atol=1e-3)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("seq, block", [(8192, 4), (1024, 32), (256, 4)])
def test_the_mask_holds_s_squared_plus_s_b_pairs_and_the_forward_visits_the_tiles_that_hold_them(seq, block):
    """The pair count the needed FLOPs rest on, against a brute-force count at
    a small size; every visited tile pair holds a pair and no other does
    (80 of 256 at 2 x 8,192 rows and 1024-tiles); the counter is their ratio."""
    from ray_tpu.ops.attention import BlockDiffusion, _seen_block_diffusion
    from ray_tpu.ops.pallas import flash_attention as fa

    bd = BlockDiffusion(block, seq)
    bq, bk = fa._diffusion_blocks(2 * seq, bd, fa.DEFAULT_BLOCKS[:2])
    visited = set(_pairs(fa.tile_pairs(2 * seq, 2 * seq, bq, bk, False, None, bd, True)))
    if seq == 8192:
        assert len(visited) == 80 and (2 * seq // bq) * (2 * seq // bk) == 256
    else:
        mask = np.asarray(_seen_block_diffusion(jnp.arange(2 * seq), jnp.arange(2 * seq), bd))
        assert mask.sum() == seq * seq + seq * block
        holding = {(i, j) for i in range(2 * seq // bq) for j in range(2 * seq // bk)
                   if mask[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk].any()}
        assert visited == holding
    fill = fa.diffusion_mask_fill_pct(seq, block, 128, 128)
    assert fill == pytest.approx(100.0 * (seq * seq + seq * block) / (len(visited) * bq * bk))
    assert seq != 8192 or fill == pytest.approx(80.04, abs=0.01)


@pytest.mark.parametrize("keys", [True, False])
def test_a_block_diffusion_table_walks_an_own_tiles_noisy_run_and_then_its_clean_run(keys):
    """The table at the cell's shapes (forward / dq and dkv): 160 pairs where
    the parent's grids had 16 x 18 and 32 x 16 steps, each own tile's pairs
    the noisy tiles that see it (or that it sees), then the clean ones, each
    run ascending and without a gap, the flags on the first and the last."""
    from ray_tpu.ops.attention import BlockDiffusion
    from ray_tpu.ops.pallas import flash_attention as fa

    bd, rows = BlockDiffusion(4, 8192), 16384
    own, other = (1024, 512) if keys else (512, 1024)
    table = fa.tile_pairs(rows, rows, 1024, 512, False, None, bd, keys)
    assert len(table.own) == 160 and np.array_equal(np.unique(table.own), np.arange(rows // own))
    for i in range(rows // own):
        mine = table.other[table.own == i]
        noisy, clean = mine[mine < 8192 // other], mine[mine >= 8192 // other]
        assert np.array_equal(mine, np.r_[noisy, clean])
        assert all(len(run) == 0 or np.array_equal(run, np.arange(run[0], run[-1] + 1)) for run in (noisy, clean))
        kinds = table.kind[table.own == i]
        assert kinds[0] & fa._FIRST and kinds[-1] & fa._LAST and not (kinds[1:] & fa._FIRST).any() and not (kinds[:-1] & fa._LAST).any()
        if keys:  # a noisy query tile: the two key tiles of its own blocks (a clean one: no noisy key); the clean tiles up to its last block
            assert (len(noisy), len(clean)) == (2 if i < 8 else 0, 2 * (i % 8) + 2)


def test_block_diffusion_refuses_a_window_unequal_lengths_and_a_block_that_does_not_divide():
    from ray_tpu.ops.attention import BlockDiffusion, dot_product_attention
    from ray_tpu.ops.pallas.flash_attention import flash_attention

    q, k, v = _qkv(jax.random.PRNGKey(9), b=1, s=128, h=2, d=64)
    with pytest.raises(ValueError, match="no block_diffusion"):
        dot_product_attention(q, k, v, window=16, block_diffusion=BlockDiffusion(4))
    with pytest.raises(ValueError, match="has no window"):
        flash_attention(q, k, v, window=16, block_diffusion=BlockDiffusion(4))
    with pytest.raises(ValueError, match="equal sequence lengths"):
        reference_attention(q, k[:, :64], v[:, :64], block_diffusion=BlockDiffusion(4))
    with pytest.raises(ValueError, match="divides a copy"):
        blockwise_attention(q, k, v, block_diffusion=BlockDiffusion(48))
    with pytest.raises(ValueError, match="first\\s+half"):
        reference_attention(q, k, v, block_diffusion=BlockDiffusion(4, 32))


# -- only the tiles the mask's edge crosses are masked (PR 63) -----------------------------------


def _run_steps(rows, bq, bk, window, bd, *, keys):
    """The (query tile, key tile) pairs of a head's grid steps, in the grid's
    order, as the kernels are handed them.  `keys`: forward and dq (query
    tiles outside); without: dkv."""
    from ray_tpu.ops.pallas import flash_attention as fa

    return _pairs(fa.tile_pairs(rows, rows, bq, bk, bd is None, window, bd, keys), keys=keys)


def _taken_whole(rows, bq, bk, window, bd, *, keys):
    """The (query tile, key tile) pairs whose kind word sends them to the body without the mask."""
    from ray_tpu.ops.pallas import flash_attention as fa

    table = fa.tile_pairs(rows, rows, bq, bk, bd is None, window, bd, keys)
    return {pair for pair, kind in zip(_pairs(table, keys=keys), table.kind) if not kind & fa._MASKED}


# (what, rows, noisy rows, window, forward tiles, backward tiles, (wholly visible, crossed) forward, the same backward)
_EDGE_CASES = [
    ("causal", 16384, 0, None, (1024, 1024), (1024, 512), (120, 16), (240, 32)),
    ("causal", 8192, 0, None, (1024, 1024), (1024, 512), (28, 8), (56, 16)),
    ("causal", 4096, 0, None, (1024, 1024), (1024, 512), (6, 4), (12, 8)),
    ("causal", 1024, 0, None, (1024, 1024), (1024, 512), (0, 1), (0, 2)),
    ("window", 8192, 0, 512, (512, 512), (512, 512), (0, 31), (0, 31)),  # `phi4`'s layers: both visited tiles are boundary tiles
    ("window", 16384, 0, 1024, (1024, 1024), (1024, 512), (0, 31), (0, 62)),  # `mellum2`'s
    ("window", 8192, 0, 3072, (1024, 1024), (1024, 512), (13, 13), (26, 26)),  # three tiles wide: the middle ones are whole
    ("diffusion", 16384, 8192, None, (1024, 1024), (1024, 512), (56, 24), (112, 48)),  # `sdar`'s step
    ("diffusion", 8192, 0, None, (1024, 1024), (1024, 512), (28, 8), (56, 16)),  # one copy alone: block-causal
]


@pytest.mark.parametrize("kernel", ["forward", "dq", "dkv"])
@pytest.mark.parametrize("what, rows, noisy, window, fwd, bwd, fwd_counts, bwd_counts", _EDGE_CASES,
                         ids=[f"{c[0]}-{c[1]}" + (f"-w{c[3]}" if c[3] else "") for c in _EDGE_CASES])
def test_a_run_step_is_taken_as_wholly_visible_iff_the_mask_over_its_tile_is_all_true(
        kernel, what, rows, noisy, window, fwd, bwd, fwd_counts, bwd_counts):
    """The table's kind word against the mask itself at the cells' shapes, in
    the three kernels' tiles and orientations; the tiles that hold a pair are
    the ones visited, as before."""
    from ray_tpu.ops.attention import BlockDiffusion
    from ray_tpu.ops.pallas import flash_attention as fa

    bd = BlockDiffusion(4, noisy) if what == "diffusion" else None
    (bq, bk), counts = (fwd, fwd_counts) if kernel == "forward" else (bwd, bwd_counts)
    steps = _run_steps(rows, bq, bk, window, bd, keys=kernel != "dkv")
    assert len(set(steps)) == len(steps)
    holding, whole = set(), set()
    for qi in range(rows // bq):
        for ki in range(rows // bk):
            mask = _mask_of_a_tile(np.arange(qi * bq, (qi + 1) * bq), np.arange(ki * bk, (ki + 1) * bk), window, bd)
            if mask.any():
                holding.add((qi, ki))
            if mask.all():
                whole.add((qi, ki))
    assert set(steps) == holding
    taken = _taken_whole(rows, bq, bk, window, bd, keys=kernel != "dkv")
    assert taken == whole
    assert (len(taken), len(steps) - len(taken)) == counts
    assert fa.run_steps_unmasked(rows, bq, bk, window, bd) == (len(taken), len(steps))  # what the counter counts, either axis outside


def test_tiles_unmasked_counts_the_forwards_run_steps_at_the_tiles_in_use():
    from ray_tpu.ops.pallas import flash_attention as fa

    assert fa.tiles_unmasked_pct(8192, 128, 128, diffusion_block=4) == pytest.approx(70.0)  # `sdar`: 56 of 80
    assert fa.tiles_unmasked_pct(16384, 128, 128) == pytest.approx(100 * 120 / 136)  # 88.2
    assert fa.tiles_unmasked_pct(16384, 192, 128) == pytest.approx(100 * 120 / 136)  # Kimi's latent layer
    assert fa.tiles_unmasked_pct(8192, 128, 128) == pytest.approx(100 * 28 / 36)  # 77.8
    assert fa.tiles_unmasked_pct(8192, 256, 256) == pytest.approx(100 * 56 / 72)  # on a key tile of 512: 77.8 too
    assert fa.tiles_unmasked_pct(4096, 128, 128) == pytest.approx(60.0)
    assert fa.tiles_unmasked_pct(1024, 128, 128) == 0.0  # one tile a head, the diagonal's
    assert fa.tiles_unmasked_pct(8192, 64, 128, window=512) == 0.0 == fa.tiles_unmasked_pct(16384, 128, 128, window=1024)
    assert fa.tiles_unmasked_pct(8192, 128, 128, window=3072) == pytest.approx(100 * 13 / 26)
    assert fa.tiles_unmasked_pct(1100, 128, 128) is None  # no tile divides it: the kernels do not run


def _every_tile_crossed(monkeypatch):
    """`tile_pairs` with the masked bit on every pair: the kernels of PR 62, every grid step under the mask."""
    from ray_tpu.ops.pallas import flash_attention as fa

    real = fa.tile_pairs
    monkeypatch.setattr(fa, "tile_pairs", lambda *call: (table := real(*call))._replace(kind=table.kind | fa._MASKED))


# (rows, noisy rows, window, block of the block-diffusion mask or None for a causal call)
_BIT_CASES = {"causal": (512, 0, None, None), "window": (768, 0, 400, None), "diffusion": (1024, 512, None, 4),
              "diffusion-blocks-of-32": (1024, 512, None, 32), "diffusion-one-copy": (512, 0, None, 4)}


@pytest.mark.parametrize("case", list(_BIT_CASES))
def test_leaving_the_mask_off_the_wholly_visible_tiles_changes_no_bit_of_out_lse_dq_dk_dv(monkeypatch, case):
    """Several tiles a side, some of them wholly visible in all three kernels:
    the call is bit for bit the call whose every grid step is masked, and
    agrees with `reference_attention` as it did."""
    from ray_tpu.ops.attention import BlockDiffusion
    from ray_tpu.ops.pallas import flash_attention as fa

    rows, noisy, window, block = _BIT_CASES[case]
    bd = None if block is None else BlockDiffusion(block, noisy)
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(rows + (window or 0)), 4)
    q = jax.random.normal(kq, (1, rows, 2, 64), jnp.float32)
    k = jax.random.normal(kk, (1, rows, 2, 64), jnp.float32)
    v = jax.random.normal(kv, (1, rows, 2, 128), jnp.float32)
    g = jax.random.normal(kg, (1, rows, 2, 128), jnp.float32)
    mask = dict(causal=bd is None, scale=0.125, window=window, diffusion=bd)
    for (bq, bk), keys in (((128, 128), True), ((128, 64), True), ((128, 64), False)):  # forward, dq, dkv
        assert 0 < len(_taken_whole(rows, bq, bk, window, bd, keys=keys)) < len(_run_steps(rows, bq, bk, window, bd, keys=keys))

    def everything():
        out, lse = fa._flash_fwd(q, k, v, block_q=128, block_k=128, **mask)
        return (out, lse) + fa._flash_bwd(q, k, v, out, lse, g, block_q=128, block_k=64, **mask)

    changed = everything()
    _every_tile_crossed(monkeypatch)
    parents = everything()
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), changed, parents):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    plain = lambda q, k, v: reference_attention(q, k, v, causal=True, scale=0.125, window=window, block_diffusion=bd)  # noqa: E731
    ref, vjp = jax.vjp(plain, q, k, v)
    np.testing.assert_allclose(np.asarray(changed[0]), np.asarray(ref), atol=2e-5, rtol=2e-5)
    for a, b in zip(changed[2:], vjp(g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


# sha256[:16] of `str(jax.make_jaxpr(grad(flash_attention(..).sum())))`, `0x..` addresses and `.py:<line>` blanked.  Taken
# first at the PARENT of PR 62 (to show that PR left a call without `block_diffusion` alone, kernel bodies included),
# re-taken at PR 63, which changed the bodies on purpose (two of them a kernel), and RE-TAKEN at PR 70 for both windows,
# which changes every call's grid on purpose (one inner axis over a scalar-prefetched table of the visible tile pairs; the
# bodies read the table where they read `program_id`s, and what all its words agree on is decided when the call is traced:
# under this window every pair is a boundary tile, so the masked body alone is traced): a later PR that means to leave a causal or a windowed call alone
# holds these.
_PINNED_JAXPRS = {None: "16586049dd519e00", 96: "0417be8334e85cdd"}


@pytest.mark.parametrize("window", [None, 96])
def test_a_call_without_block_diffusion_traces_the_pinned_program(window):
    import hashlib
    import re

    from ray_tpu.ops.pallas.flash_attention import flash_attention

    q = jnp.zeros((1, 256, 4, 64), jnp.float32)
    k = jnp.zeros((1, 256, 2, 64), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window, block_q=128, block_k=128, bwd_block_q=128,
                               bwd_block_k=64).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, k))
    text = re.sub(r"\.py:\d+", ".py", re.sub(r"0x[0-9a-f]+", "0x", text))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == _PINNED_JAXPRS[window]
