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


# -- the index maps of a causal call (PR 55) -------------------------------------------------------


def _maps_of_the_parent(monkeypatch):
    """`_inner_tile` with the causal call's map put back to `j -> j`."""
    from ray_tpu.ops.pallas import flash_attention as fa

    real = fa._inner_tile

    def parents(*args, **masks):
        n, tile = real(*args, **masks)
        return (n, lambda i, j: j) if args[4] is None else (n, tile)

    monkeypatch.setattr(fa, "_inner_tile", parents)


@pytest.mark.parametrize("seq, tiles, d, dv", [(512, (128, 128), 64, 64), (1024, (256, 128), 128, 128),
                                               (512, (128, 128), 192, 128)])
def test_a_causal_calls_clamped_maps_change_no_bit_of_out_lse_dq_dk_dv(monkeypatch, seq, tiles, d, dv):
    """The maps name other blocks on the steps that compute nothing alone."""
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
    _maps_of_the_parent(monkeypatch)
    parents = everything()
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), changed, parents):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(changed[0]), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("seq, bq, bk", [(16384, 1024, 1024), (16384, 1024, 512), (4096, 1024, 512), (1024, 256, 128),
                                         (1024, 128, 256), (1024, 1024, 512)])
def test_a_causal_map_gives_a_run_step_its_own_tile_and_an_off_step_its_neighbours(monkeypatch, seq, bq, bk):
    from ray_tpu.ops.pallas import flash_attention as fa

    n_q, n_k = seq // bq, seq // bk
    runs = lambda qi, ki: ki * bk <= qi * bq + bq - 1  # noqa: E731  (the kernels' `run`, all three)
    pairs = sum(runs(qi, ki) for qi in range(n_q) for ki in range(n_k))
    # forward and dq: key tiles inside, the off steps LAST
    n, tile = fa._inner_tile(n_q, n_k, bq, bk, None, keys=True, causal=True)
    assert n == n_k
    for qi in range(n_q):
        last = max(ki for ki in range(n_k) if runs(qi, ki))
        assert [tile(qi, ki) for ki in range(n_k)] == [ki if runs(qi, ki) else last for ki in range(n_k)]
    # dkv: query tiles inside, the off steps FIRST
    n, tile = fa._inner_tile(n_k, n_q, bk, bq, None, keys=False, causal=True)
    assert n == n_q
    for ki in range(n_k):
        first = min(qi for qi in range(n_q) if runs(qi, ki))
        assert [tile(ki, qi) for qi in range(n_q)] == [qi if runs(qi, ki) else first for qi in range(n_q)]
    # the counter: the visible pairs, from the same maps; a map `j -> j` copies on every step
    for keys, steps in ((True, n_q * n_k), (False, n_k * n_q)):
        assert fa.causal_steps_copying_pct(seq, bq, bk, keys=keys) == pytest.approx(100 * pairs / steps)
    no_mask = fa._inner_tile(n_q, n_k, bq, bk, None, keys=True, causal=False)[1]
    assert [no_mask(3, j) for j in range(n_k)] == list(range(n_k))
    _maps_of_the_parent(monkeypatch)
    assert fa.causal_steps_copying_pct(seq, bq, bk, keys=True) == fa.causal_steps_copying_pct(seq, bq, bk, keys=False) == 100


def test_causal_steps_copying_counts_the_visible_pairs_at_the_tiles_in_use():
    from ray_tpu.ops.pallas import flash_attention as fa

    assert fa.causal_steps_copying_pct(16384, 1024, 1024, keys=True) == pytest.approx(100 * 136 / 256)
    assert fa.causal_steps_copying_pct(16384, 1024, 512, keys=True) == pytest.approx(100 * 272 / 512)  # dq
    assert fa.causal_steps_copying_pct(16384, 1024, 512, keys=False) == pytest.approx(100 * 272 / 512)  # dkv
    assert fa.causal_steps_copying_pct(1024, 1024, 1024, keys=True) == 100  # one q tile: every step runs
    assert fa.causal_steps_copying_pct(1024, 1024, 512, keys=False) == 100
    assert fa.causal_forward_tiles(16384, 128, 128) == (1024, 1024) and fa.causal_forward_tiles(8192, 256, 256) == (1024, 512)
    assert fa.causal_forward_tiles(4224, 128, 128) == (384, 384) and fa.causal_forward_tiles(1100, 128, 128) is None


def test_a_causal_call_with_more_keys_than_queries_keeps_its_maps_inside_the_sequences():
    """`sq != sk`: the bounds are the predicates' own, held inside the grid."""
    from ray_tpu.ops.pallas import flash_attention as fa

    n, tile = fa._inner_tile(4, 2, 128, 128, None, keys=False, causal=True)  # 4 key tiles, 2 query tiles
    assert [[tile(ki, qi) for qi in range(n)] for ki in range(4)] == [[0, 1], [1, 1], [1, 1], [1, 1]]
    n, tile = fa._inner_tile(2, 4, 128, 128, None, keys=True, causal=True)
    assert [[tile(qi, ki) for ki in range(n)] for qi in range(2)] == [[0, 0, 0, 0], [0, 1, 1, 1]]
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
    visited = set()
    for i in range(2 * seq // bq):
        a1, n1, a2, n2 = fa._diffusion_ranges(i, bq, bk, 2 * seq, bd, keys=True)
        visited |= {(i, j) for j in (*range(a1, a1 + n1), *range(a2, a2 + n2))}
    if seq == 8192:
        assert len(visited) == 80 and (2 * seq // bq) * (2 * seq // bk) == 256
        assert fa._inner_tile(16, 16, bq, bk, None, keys=True, causal=False, diffusion=bd)[0] == 9
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
def test_a_block_diffusion_map_walks_the_visible_tiles_and_holds_the_last_through_the_off_steps(keys):
    """The index map of the inner axis at the cell's shapes (forward / dq and
    dkv): a step that runs names a tile some query of the pair sees, each once,
    and a step that is off names the tile the step before named, so the
    pipeline copies nothing for it."""
    from ray_tpu.ops.attention import BlockDiffusion
    from ray_tpu.ops.pallas import flash_attention as fa

    bd, rows = BlockDiffusion(4, 8192), 16384
    own, other = (1024, 512) if keys else (512, 1024)
    n_inner, tile = fa._inner_tile(rows // own, rows // other, own, other, None, keys=keys, causal=False, diffusion=bd)
    counts = fa._diffusion_visible(rows // own, own, other, rows, bd, keys=keys)
    assert n_inner == max(counts) and sum(counts) == 160
    for i, count in enumerate(counts):
        named = [int(tile(i, j)) for j in range(n_inner)]
        assert len(set(named[:count])) == count and set(named[count:]) <= {named[count - 1]}
        assert all(bool(fa._diffusion_step(i, j, own, other, rows, bd, keys=keys)[1]) == (j < count) for j in range(n_inner))


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


def _mask_of_a_tile(qrows, krows, window, bd):
    """The mask over one tile pair by pair, in numpy, from the rules
    themselves (the causal one and its window; the three of block diffusion)."""
    q, k = qrows[:, None], krows[None, :]
    if bd is None:
        return (q >= k) if window is None else (q >= k) & (q - k < window)
    q_noisy, k_noisy = q < bd.noisy, k < bd.noisy
    qb, kb = np.where(q_noisy, q, q - bd.noisy) // bd.block, np.where(k_noisy, k, k - bd.noisy) // bd.block
    return np.where(k_noisy, q_noisy & (kb == qb), np.where(q_noisy, kb < qb, kb <= qb))


def _run_steps(rows, bq, bk, window, bd, *, keys):
    """The (query tile, key tile) pairs of a head's run steps, in the grid's
    order, as the kernels find them: the outer axis's tile, the inner axis's
    from the kernel's own arithmetic, the kernel's `run`.  `keys`: forward and
    dq (query tiles outside); without: dkv."""
    from ray_tpu.ops.pallas import flash_attention as fa

    n_q, n_k = rows // bq, rows // bk
    own, other, n_own, n_other = (bq, bk, n_q, n_k) if keys else (bk, bq, n_k, n_q)
    n_inner, _ = fa._inner_tile(n_own, n_other, own, other, window, keys=keys, causal=bd is None, diffusion=bd)
    steps = []
    for i in range(n_own):
        for j in range(n_inner):
            if bd is not None:
                tile, run = fa._diffusion_step(i, j, own, other, rows, bd, keys=keys)
            else:
                tile = j if window is None else fa._first_visible(i, own, other, window, keys=keys) + j
                q_start, k_start = (i * bq, tile * bk) if keys else (tile * bq, i * bk)
                run = k_start <= q_start + bq - 1
                if window is not None and not keys:
                    run = run and q_start <= k_start + bk - 1 + window - 1 and q_start < n_q * bq
            if run:
                steps.append((i, int(tile)) if keys else (int(tile), i))
    return steps


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
    """The predicate against the mask itself at the cells' shapes, in the three
    kernels' tiles and orientations; the tiles that hold a pair are the ones
    visited, as before."""
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
    taken = {(qi, ki) for qi, ki in steps if fa._wholly_visible(qi * bq, ki * bk, bq, bk, window, bd)}
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
    """`_wholly_visible` answering no on every tile: the kernels of the parent,
    every run step masked (a traced False, as the grid's values are)."""
    from ray_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "_wholly_visible", lambda q_start, k_start, *tile: q_start < 0)


# (rows, noisy rows, window, block of the block-diffusion mask or None for a causal call)
_BIT_CASES = {"causal": (512, 0, None, None), "window": (768, 0, 400, None), "diffusion": (1024, 512, None, 4),
              "diffusion-blocks-of-32": (1024, 512, None, 32), "diffusion-one-copy": (512, 0, None, 4)}


@pytest.mark.parametrize("case", list(_BIT_CASES))
def test_leaving_the_mask_off_the_wholly_visible_tiles_changes_no_bit_of_out_lse_dq_dk_dv(monkeypatch, case):
    """Several tiles a side, some of them wholly visible in all three kernels:
    the call is bit for bit the call whose every run step is masked, and
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
        steps = _run_steps(rows, bq, bk, window, bd, keys=keys)
        whole = sum(bool(fa._wholly_visible(qi * bq, ki * bk, bq, bk, window, bd)) for qi, ki in steps)
        assert 0 < whole < len(steps)

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
# first at the PARENT of PR 62 (to show that PR left a call without `block_diffusion` alone, kernel bodies included) and
# RE-TAKEN at PR 63, which changes the bodies on purpose (two of them a kernel: a run step's tile is wholly visible or
# crossed by the mask's edge): a later PR that means to leave a causal or a windowed call alone holds these.
_PINNED_JAXPRS = {None: "412f8a8cc2ff613e", 96: "d9ec86f84133d5de"}


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
