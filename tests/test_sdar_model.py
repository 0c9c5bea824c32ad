"""SDAR through the program (PERF.md section 4, PR 62): Qwen3-MoE's block (GQA
attention with an RMSNorm per head of q and k, softmax-routed SwiGLU experts
with a renormalised top-k, a share of them held) as a BLOCK-DIFFUSION model:
attention causal between blocks of B tokens and two-sided inside one, a
training step that sends a noisy copy of each sequence through the stack
beside the clean one under the three-part mask, and the 1/t-weighted masked
loss on the noisy half, row i predicting token i.  Held to
`benchmarks/lib/reference_sdar.py` (explicit boolean masks from the three
rules, its own copy of the noise, its own routing) at tiny widths that keep
the published ratios (8:1 GQA, a head size that is not d / heads, top-8 of 16
with 2 held as 8 of 128 with 16), on the CPU, seeded weights; on the chip the
same two comparisons decide the cell's `correct` at the published widths."""

import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.builders import block_diffusion_moe_decoder as builder  # noqa: E402
from benchmarks.lib import reference_mellum, reference_sdar as ref  # noqa: E402
from ray_tpu.models import LMTrainContext, TransformerConfig, lm, moe, transformer  # noqa: E402
from ray_tpu.models.lm import DIFFUSION_FILL, MASKED_SHARE  # noqa: E402
from ray_tpu.ops.pallas import flash_attention as fa  # noqa: E402
from ray_tpu.parallel import MeshSpec, build_mesh  # noqa: E402
from ray_tpu.train import run_record  # noqa: E402

SEQ = 64
with open(os.path.join(ROOT, "benchmarks", "configs", "sdar-30b-a3b-chat-ep8-1chip.json")) as f:
    PUBLISHED = json.load(f)
# The configuration file's keys at a tiny size: three layers, 2 of 16 experts held from expert 2, top-8.
CONFIG = dict(
    PUBLISHED, hidden_size=64, num_attention_heads=8, num_key_value_heads=1, head_dim=16, vocab_size=128,
    moe_intermediate_size=24, num_experts=2, num_experts_per_tok=8, num_hidden_layers=3, rope_theta=100,
    share=dict(PUBLISHED["share"], num_experts_total=16, first_expert_held=2),
)
RTOL = 2e-4  # float32 against float32 under precision "highest": what the orders of summation cost


def published(block=4, **kw):
    assumed = dict(CONFIG["assumed"], block_length=dict(CONFIG["assumed"]["block_length"], value=block))
    return dict(CONFIG, assumed=assumed, **kw)


def config_of(block=4, **kw):
    base = builder.model_kwargs(published(block), SEQ)
    base.update(dtype=jnp.float32, param_dtype=jnp.float32, remat=False, remat_policy=None)
    base.update(kw)
    return TransformerConfig(**base)


def redrawn(params, seed=1):
    """Every leaf that starts at a constant (the norms' scales, q_norm and
    k_norm among them) drawn anew, so that a test cannot pass by ignoring it."""
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    out = [1.0 + 0.2 * jax.random.normal(key, leaf.shape, leaf.dtype)
           if path[-1].key in ("ln1", "ln2", "final_norm", "q_norm", "k_norm") else leaf
           for (path, leaf), key in zip(flat, keys)]
    return jax.tree_util.tree_unflatten(tree, out)


def one_device_ctx(cfg, **kw):
    return LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), strategy="dp", **kw)


def noise_of(cfg, tokens, key=5):
    key = jax.random.PRNGKey(key) if isinstance(key, int) else key
    return lm.diffusion_noise(key, tokens, block=cfg.diffusion_block, mask_id=cfg.mask_id,
                              eps=cfg.diffusion_eps)


@pytest.fixture(scope="module", params=[4, 32], ids=["B4", "B32"])
def tiny(request):
    cfg = config_of(request.param)
    params = redrawn(transformer.init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, SEQ), 0, cfg.vocab_size - 1)
    return dict(cfg=cfg, published=published(request.param), params=params, tokens=tokens, noisy=noise_of(cfg, tokens)[0])


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / (np.mean(b ** 2) + 1e-300)))


# -- the two forwards ------------------------------------------------------------------------


def test_the_plain_forward_agrees_with_the_reference(tiny):
    got = transformer.forward(tiny["params"], tiny["tokens"], tiny["cfg"])
    assert rel(got, ref.logits(tiny["published"], tiny["params"], tiny["tokens"], last=SEQ)) < RTOL


def test_the_training_forward_agrees_with_the_reference(tiny):
    got = one_device_ctx(tiny["cfg"]).apply_diffusion(tiny["params"], tiny["noisy"], tiny["tokens"])  # the cell's comparison's entry
    assert got.shape == (2, SEQ, tiny["cfg"].vocab_size)
    want = ref.training_logits(tiny["published"], tiny["params"], tiny["noisy"], tiny["tokens"], last=SEQ)
    assert rel(got, want) < RTOL
    last = ref.training_logits(tiny["published"], tiny["params"], tiny["noisy"], tiny["tokens"], last=16)
    assert rel(last, want[:, -16:]) < 1e-5  # the last layer and the head for the asked rows alone: the same rows


def test_the_comparison_notices_a_dropped_mask_and_a_causal_one(tiny):
    """The tolerance is tight enough: without the mask, and under a causal mask
    over the 2S rows in its place, the logits land far over RTOL."""
    want = ref.training_logits(tiny["published"], tiny["params"], tiny["noisy"], tiny["tokens"], last=SEQ)
    dropped = ref.training_logits(tiny["published"], tiny["params"], tiny["noisy"], tiny["tokens"], last=SEQ, masked=False)
    assert rel(dropped, want) > 100 * RTOL
    causal = dataclasses.replace(tiny["cfg"], diffusion_block=None)
    rows = jnp.concatenate([tiny["noisy"], tiny["tokens"]], axis=1)
    assert rel(transformer.forward(tiny["params"], rows, causal)[:, :SEQ], want) > 100 * RTOL


def test_a_training_passs_block_is_the_plain_forwards_with_that_block_swapped_for_its_noisy_tokens(tiny):
    """THE identity that ties the two masks: the training pass's logits on the
    noisy rows of block k equal the plain forward's at block k when its input
    is x_0 with block k replaced by x_t's; every k."""
    cfg, block = tiny["cfg"], tiny["cfg"].diffusion_block
    training = transformer.diffusion_forward(tiny["params"], tiny["noisy"], tiny["tokens"], cfg)
    plain = jax.jit(functools.partial(transformer.forward, config=cfg))
    for k in range(SEQ // block):
        rows = slice(k * block, (k + 1) * block)
        swapped = tiny["tokens"].at[:, rows].set(tiny["noisy"][:, rows])
        np.testing.assert_allclose(np.asarray(plain(tiny["params"], swapped)[:, rows]), np.asarray(training[:, rows]),
                                   atol=2e-5, rtol=2e-5)


# -- the objective ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def loss_and_grads(tiny):
    cfg = dataclasses.replace(tiny["cfg"], remat=True, remat_policy="qkv_attn")
    key = jax.random.PRNGKey(3)
    batch = {"tokens": tiny["tokens"], "targets": jnp.roll(tiny["tokens"], -1, axis=1)}
    (loss, terms), grads = jax.value_and_grad(one_device_ctx(cfg)._loss, has_aux=True)(tiny["params"], batch, key)
    (want_loss, want_terms), want_grads = jax.value_and_grad(functools.partial(ref.objective, tiny["published"]), has_aux=True)(
        tiny["params"], tiny["tokens"], key)
    return dict(loss=loss, terms=terms, grads=grads, want_loss=want_loss, want_terms=want_terms, want_grads=want_grads)


def test_loss_agrees_with_the_reference(loss_and_grads):
    got, want = loss_and_grads["terms"], loss_and_grads["want_terms"]
    assert float(loss_and_grads["loss"]) == pytest.approx(float(loss_and_grads["want_loss"]), rel=2e-5)
    assert float(got["ce_loss"]) == pytest.approx(float(want["ce_loss"]), rel=2e-5)
    assert float(got["moe_lb_loss"]) == pytest.approx(float(want["moe_lb_loss"]), rel=2e-5)
    assert float(loss_and_grads["loss"]) == pytest.approx(float(got["ce_loss"]) + 0.001 * float(got["moe_lb_loss"]))
    assert 0.2 < float(got[MASKED_SHARE]) < 0.8


def test_gradients_agree_with_the_reference_leaf_by_leaf(loss_and_grads):
    got = dict(jax.tree_util.tree_flatten_with_path(loss_and_grads["grads"])[0])
    want = dict(jax.tree_util.tree_flatten_with_path(loss_and_grads["want_grads"])[0])
    assert got.keys() == want.keys()
    worst = {jax.tree_util.keystr(p): rel(got[p], want[p]) for p in got}
    assert max(worst.values()) < 2e-3, sorted(worst.items(), key=lambda kv: -kv[1])[:5]
    assert all(float(jnp.abs(want[p]).max()) > 0 for p in want)  # every leaf has a gradient, q_norm and k_norm too


def test_the_weighted_cross_entropy_is_the_plain_one_with_its_weights_given():
    """`head_weighted_cross_entropy` with the weights `mask / sum(mask)` is
    `head_cross_entropy` with that mask, value and both gradients; with general
    weights it is the weighted sum of the rows' negative log-likelihoods."""
    keys = jax.random.split(jax.random.PRNGKey(4), 5)
    x, head = jax.random.normal(keys[0], (2, 16, 32)), jax.random.normal(keys[1], (32, 48))
    targets = jax.random.randint(keys[2], (2, 16), 0, 48)
    mask = jax.random.bernoulli(keys[3], 0.5, (2, 16))
    constrain = lambda a, axes: a  # noqa: E731
    plain = jax.value_and_grad(lambda x, h: lm.head_cross_entropy(constrain, x, h, targets, mask), (0, 1))(x, head)
    given = jax.value_and_grad(
        lambda x, h: lm.head_weighted_cross_entropy(constrain, x, h, targets, mask / jnp.sum(mask)), (0, 1))(x, head)
    for a, b in zip(jax.tree_util.tree_leaves(plain), jax.tree_util.tree_leaves(given)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7)
    weights = jax.random.uniform(keys[4], (2, 16)) * 3
    nll = -jnp.take_along_axis(jax.nn.log_softmax(x @ head), targets[..., None], axis=-1)[..., 0]
    want = jax.value_and_grad(lambda x, h: jnp.sum(
        weights * -jnp.take_along_axis(jax.nn.log_softmax(x @ h), targets[..., None], axis=-1)[..., 0]), (0, 1))(x, head)
    got = jax.value_and_grad(lambda x, h: lm.head_weighted_cross_entropy(constrain, x, h, targets, weights), (0, 1))(x, head)
    assert float(got[0]) == pytest.approx(float(jnp.sum(weights * nll)), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)


# -- the noise ---------------------------------------------------------------------------------


def test_the_programs_draw_is_the_references_copy_and_the_same_key_gives_the_same_mask():
    tokens = jax.random.randint(jax.random.PRNGKey(1), (3, 256), 0, 500)
    kw = dict(block=4, mask_id=511, eps=1e-3)
    got, want = lm.diffusion_noise(jax.random.PRNGKey(9), tokens, **kw), ref.noise(jax.random.PRNGKey(9), tokens, **kw)
    again = lm.diffusion_noise(jax.random.PRNGKey(9), tokens, **kw)
    other = lm.diffusion_noise(jax.random.PRNGKey(10), tokens, **kw)
    for g, w, a in zip(got, want, again):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        np.testing.assert_array_equal(np.asarray(g), np.asarray(a))
    assert not np.array_equal(np.asarray(got[1]), np.asarray(other[1]))
    noisy, masked, _ = got
    np.testing.assert_array_equal(np.asarray(noisy), np.where(np.asarray(masked), 511, np.asarray(tokens)))


def test_the_rates_are_stratified_over_eps_to_one_and_a_blocks_masked_share_is_its_rate():
    """One rate in every 1/n of [eps, 1] a sequence (the low-discrepancy draw),
    dealt to the blocks in a shuffled order, every token of a block under its
    block's rate; over many sequences a block's masked share is its rate."""
    n_seqs, seq, block, eps = 512, 256, 4, 1e-3
    tokens = jnp.zeros((n_seqs, seq), jnp.int32)
    _, masked, t = lm.diffusion_noise(jax.random.PRNGKey(3), tokens, block=block, mask_id=7, eps=eps)
    t, masked = np.asarray(t), np.asarray(masked)
    blocks = seq // block
    rates = t.reshape(n_seqs, blocks, block)
    assert (rates == rates[..., :1]).all()  # a rate a BLOCK
    rates = rates[..., 0]
    assert rates.min() >= eps and rates.max() <= 1.0
    strata = np.sort((rates - eps) / (1 - eps), axis=-1)  # one in every 1/n, whatever the sequence's offset
    assert (np.floor(strata * blocks).astype(int) == np.arange(blocks)).mean() > 0.99  # float32 at the strata's edges
    np.testing.assert_allclose(np.diff(strata, axis=-1), 1.0 / blocks, atol=1e-5)
    assert not (np.argsort(rates, axis=-1) == np.arange(blocks)).all(axis=-1).any()  # dealt, not in order
    assert abs(masked.mean() - (eps + (1 - eps) * 0.5)) < 0.01
    order = np.argsort(rates.ravel())
    per_block = masked.reshape(n_seqs * blocks, block).mean(axis=-1)[order]
    for lo in range(0, order.size, order.size // 8):  # eight bands of rates: the masked share follows the rate
        band = slice(lo, lo + order.size // 8)
        assert abs(per_block[band].mean() - rates.ravel()[order][band].mean()) < 0.02


# -- the share ---------------------------------------------------------------------------------


def test_the_eight_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The guide's share test at this model's split: 16 experts in 8 shares of
    2, as the deployment's eight chips hold 128 in shares of 16, top-8; the
    shares' routed parts sum to the uncut reference's layer (no shared expert
    to count once), the gate values renormalised over all eight chosen, held
    or not.  Program and reference both."""
    cfg = config_of(n_experts_held=None, router_share_init=False)
    key = jax.random.PRNGKey(11)
    whole = moe.init_moe_params(cfg, key)
    x = jax.random.normal(jax.random.fold_in(key, 3), (2, SEQ, cfg.d_model))
    flat = x.reshape(-1, cfg.d_model)
    routing = dict(top_k=8, renormalize=True)
    experts_of = lambda first: {k: (v if k == "router" else v[first: first + 2]) for k, v in whole.items()}  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = reference_mellum.expert_part(flat, whole, first=0, **routing)[1]
        routed_ref, routed_prog, rows = jnp.zeros_like(flat), jnp.zeros_like(flat), 0.0
        for first in range(0, 16, 2):
            part = experts_of(first)
            routed_ref += reference_mellum.expert_part(flat, part, first=first, **routing)[1]
            y, stats = moe.moe_ffn(part, x, dataclasses.replace(cfg, n_experts_held=2, first_expert_held=first))
            rows += float(jnp.sum(stats["held_rows"]))
            routed_prog += y.reshape(flat.shape)
    assert rows == flat.shape[0] * 8  # every assignment is held by exactly one share
    assert float(jnp.abs(want).max()) > 0.01
    assert rel(routed_ref, want) < 1e-5
    assert rel(routed_prog, want) < 1e-5


# -- names, counters, refusals -----------------------------------------------------------------


def test_a_step_names_its_core_and_its_noise_and_its_counters_reach_the_run_record():
    """`attn/block_diffusion` inside `layer/attn_core`, `diffusion/noise`, and
    the two counters among the step's metrics and in the run's record; the
    causal kernels' counter is not this model's."""
    cfg = config_of(max_seq_len=256)
    ctx = one_device_ctx(cfg)
    run_record.drain_step_counters(), run_record.drain_step_series()
    state = ctx.init_state(seed=0)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (1, 256), 0, cfg.vocab_size))
    text = ctx._train_step.lower(state, ctx.make_batch({"tokens": tokens, "targets": tokens})).as_text(debug_info=True)
    assert "layer/attn_core/attn/block_diffusion" in text and "diffusion/noise" in text
    state, metrics = ctx.train_step(state, {"tokens": tokens, "targets": tokens})
    first = float(metrics[MASKED_SHARE])
    state, metrics = ctx.train_step(state, {"tokens": tokens, "targets": tokens})
    assert float(metrics[MASKED_SHARE]) != first  # a key a step
    newest = run_record.drain_step_counters()
    assert newest[DIFFUSION_FILL] == pytest.approx(fa.diffusion_mask_fill_pct(256, 4, 16, 16))
    assert 0.3 < newest[MASKED_SHARE] < 0.7
    assert lm.CAUSAL_STEPS not in newest and "moe_held_rows_mean" in newest
    # 256 rows a copy hold one tile: the step's one tile holds both copies and the edge crosses it; the cell's 2 x 8,192: 56 of 80
    assert newest[lm.TILES_UNMASKED] == 0.0 == fa.tiles_unmasked_pct(256, 16, 16, diffusion_block=4)
    assert lm._unmasked_counters(config_of(max_seq_len=8192), 8192) == {lm.TILES_UNMASKED: pytest.approx(70.0)}
    assert {MASKED_SHARE, DIFFUSION_FILL, lm.TILES_UNMASKED} <= set(lm.STEP_COUNTERS)


def test_one_seed_governs_a_run_the_states_key_and_the_steps_count_draw_the_noise():
    """`init_state(seed)` puts the noise's key into the state beside the
    weights it draws; the step folds its count into it and hands it on."""
    cfg = config_of()
    ctx = one_device_ctx(cfg)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (1, SEQ), 0, cfg.vocab_size - 1))
    batch = {"tokens": tokens, "targets": tokens}
    shares = {}
    for seed in (0, 1):
        state = ctx.init_state(seed=seed)
        key = np.asarray(state["noise_key"])
        for step in range(2):
            want = noise_of(cfg, jnp.asarray(tokens), key=jax.random.fold_in(jnp.asarray(key), step))[1]
            state, metrics = ctx.train_step(state, batch)
            assert float(metrics[MASKED_SHARE]) == pytest.approx(float(np.mean(np.asarray(want))))
            shares[seed, step] = np.asarray(want)
        np.testing.assert_array_equal(np.asarray(state["noise_key"]), key)
    assert not np.array_equal(shares[0, 0], shares[1, 0]) and not np.array_equal(shares[0, 0], shares[0, 1])
    assert "noise_key" not in one_device_ctx(TransformerConfig.tiny()).init_state(seed=0)  # a next-token model's state is what it was


def test_a_mesh_of_data_and_fsdp_runs_the_step_and_agrees_with_one_device():
    """`dp` x `fsdp` on four virtual devices: the XLA forms of the core are
    GSPMD's to partition, the noise is drawn from the same key."""
    cfg = config_of(n_experts=None, n_experts_held=None, router_share_init=False, routed_branch_init=False)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (4, SEQ), 0, cfg.vocab_size - 1))
    batch = {"tokens": tokens, "targets": tokens}
    losses = []
    for ctx in (one_device_ctx(cfg), LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=2, fsdp=2), devices=jax.devices()[:4]),
                                                    strategy="fsdp")):
        _, metrics = ctx.train_step(ctx.init_state(seed=0), batch)
        losses.append(float(metrics["loss"]))
    assert losses[0] == pytest.approx(losses[1], rel=1e-4)


@pytest.mark.parametrize("kw, match", [
    (dict(layer_types=("attention", "mamba", "attention"), ssm_heads=4, ssm_head_dim=16, ssm_state=16), "layer_types"),
    (dict(layer_windows=(8, None, None)), "layer_windows"),
    (dict(mtp_depth=1, mtp_loss_weight=0.1), "mtp_depth"),
    (dict(diffusion_block=0), "diffusion_block=0"),
    (dict(diffusion_eps=0.0), "diffusion_eps"),
])
def test_a_block_diffusion_model_refuses_by_name_what_its_mask_does_not_take(kw, match):
    with pytest.raises(ValueError, match=match):
        config_of(**kw)


def test_the_ring_the_pipeline_and_an_odd_length_are_refused_by_name():
    dense = config_of(n_experts=None, n_experts_held=None, router_share_init=False, routed_branch_init=False, n_layers=4)
    with pytest.raises(ValueError, match="block-diffusion mask"):
        LMTrainContext(dense, mesh=build_mesh(MeshSpec(data=2, seq=2), devices=jax.devices()[:4]), strategy="sp")
    with pytest.raises(ValueError, match="next-token models only"):
        LMTrainContext(dataclasses.replace(dense, qk_norm=False),
                       mesh=build_mesh(MeshSpec(pipeline=2), devices=jax.devices()[:2]), strategy="pp")
    cfg = config_of()
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="does not divide"):
        transformer.forward(params, jnp.zeros((1, 62), jnp.int32), cfg)
    with pytest.raises(ValueError, match="block-diffusion model's"):
        transformer.trunk(params, jnp.zeros((1, SEQ), jnp.int32), dataclasses.replace(cfg, diffusion_block=None),
                          noisy=jnp.zeros((1, SEQ), jnp.int32))
    with pytest.raises(ValueError, match="apply_diffusion needs"):
        one_device_ctx(TransformerConfig.tiny()).apply_diffusion(params, None, None)
