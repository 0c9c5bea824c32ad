"""dots3-note-prev through the program (PERF.md section 4, PR 66): latent
attention of two geometries in one stack (a full kind that attends the keys
its indexer selects, a sliding kind under a window), both with rescaled
latents, a rope of the layer's kind and a head-wise gate, the indexer trained
by a KL term of the objective, and a model that is TOLD which heads, experts
and vocabulary rows it holds.  Held to `benchmarks/lib/reference_dots3_note.py`
(its own rope, its own selection by a sort, its own KL) at tiny widths on the
CPU, seeded weights; on the chip the same comparison decides the cell's
`correct` at the published widths."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.builders import sparse_mla_moe_decoder as builder  # noqa: E402
from benchmarks.lib import reference_dots3_note as ref  # noqa: E402
from benchmarks.lib.reference_glm_moe_lite import _swiglu  # noqa: E402
from ray_tpu.models import LMTrainContext, transformer  # noqa: E402
from ray_tpu.models.mixers import MIXERS, dsa  # noqa: E402
from ray_tpu.ops import sparse_attention as sa  # noqa: E402
from ray_tpu.ops.attention import reference_attention  # noqa: E402
from ray_tpu.ops.pallas import flash_attention as fa  # noqa: E402
from ray_tpu.parallel import MeshSpec, build_mesh  # noqa: E402

SEQ = 64
# The configuration file's keys at a tiny size: layer 0 full + dense, layer 1 full + experts, layers 2-4 sliding + experts;
# the SECOND of two head shares (4 of 8 full heads, 2 of 4 sliding ones), experts 2-3 of 8, top-16 of up to 64 keys, a window of 9.
CONFIG = {
    "attention_bias": False, "hidden_act": "silu", "tie_word_embeddings": False, "topk_method": "noaux_tc", "scoring_func": "sigmoid",
    "rope_scaling": None, "apply_mla_qkv_lora_rescale": True, "attention_gate_type": "headwise", "swa_attention_gate_type": "headwise",
    "moe_layer_freq": 1, "hidden_size": 64, "intermediate_size": 96, "vocab_size": 128, "num_hidden_layers": 5,
    "layer_types": ["full_attention", "full_attention", "sliding_attention", "sliding_attention", "sliding_attention", "full_attention"],
    "num_attention_heads": 4, "num_key_value_heads": 4, "rms_norm_eps": 1e-5, "rope_theta": 80000000, "first_k_dense_replace": 1,
    "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "swa_num_attention_heads": 2, "swa_num_key_value_heads": 2, "swa_q_lora_rank": 32, "swa_kv_lora_rank": 32, "swa_qk_nope_head_dim": 24,
    "swa_qk_rope_head_dim": 8, "swa_v_head_dim": 16, "swa_rope_theta": 50000, "sliding_window_size": 9,
    "index_n_heads": 8, "index_head_dim": 16, "index_topk": 16,
    "n_routed_experts": 2, "num_experts_per_tok": 2, "moe_intermediate_size": 32, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1,
    "share": {"num_experts_total": 8, "first_expert_held": 2, "head_parallel": 2, "head_share_index": 1, "num_attention_heads_total": 8,
              "swa_num_attention_heads_total": 4, "vocab_size_total": 256, "num_hidden_layers_total": 6},
    "train": {"chips": 1, "mesh": {"data": 1}, "strategy": "dp", "param_dtype": "float32", "compute_dtype": "float32",
              "optimizer": "default_optimizer", "lr_warmup_steps": 100, "remat_policy": None},
}
RTOL = 2e-4  # float32 against float32 under precision "highest": what the orders of summation cost
INDEXER = ("wi_q", "wi_k", "ki_norm", "ki_norm_b", "wi_w")


def config_of(published=CONFIG, **kw):
    return dataclasses.replace(builder._transformer_config(published, SEQ), remat=False, **kw)


def redrawn(params, seed=1):
    """Every leaf that starts at a constant (norm scales, biases) drawn anew, so that a test cannot pass by ignoring it."""
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    out = []
    for (path, leaf), key in zip(flat, keys):
        name = path[-1].key
        if name in ("ln1", "ln2", "final_norm", "kv_norm", "q_norm", "ki_norm"):
            leaf = 1.0 + 0.2 * jax.random.normal(key, leaf.shape, leaf.dtype)
        elif name in ("router_bias", "ki_norm_b"):
            leaf = 0.3 * jax.random.normal(key, leaf.shape, leaf.dtype)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(tree, out)


def one_device_ctx(cfg):
    return LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), strategy="dp")


@pytest.fixture(scope="module")
def tiny():
    cfg = config_of()
    params = redrawn(transformer.init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, SEQ), 0, CONFIG["vocab_size"])
    return cfg, params, tokens


def rel(a, b):
    return float(jnp.sqrt(jnp.mean(jnp.square(a - b)) / jnp.mean(jnp.square(b))))


def test_the_stack_is_two_full_runs_and_a_sliding_run_each_with_its_window_and_rope(tiny):
    cfg, params, _ = tiny
    assert cfg.layer_runs() == (("mla_sparse", "dense", 0, 1), ("mla_sparse", "experts", 0, 1), ("mla_window", "experts", 0, 3))
    assert list(cfg.stacks()) == ref.layer_stacks(CONFIG)[:3:1][:1] + ["mla_sparse_layers_experts", "mla_window_layers"]
    assert [cfg.layer_variant(i)[0] for i in range(5)] == [None, None, 9, 9, 9]
    assert [cfg.layer_variant(i)[1].theta for i in range(5)] == [8e7, 8e7, 5e4, 5e4, 5e4]
    assert list(MIXERS)[9:11] == ["mla_sparse", "mla_window"]  # appended behind the nine before them (PR 68 appended "cca" behind these): no other model's weights move
    assert cfg.num_params() == sum(a.size for a in jax.tree_util.tree_leaves(params))
    axes = transformer.param_axes(cfg)
    assert jax.tree_util.tree_structure(jax.tree_util.tree_map(lambda a: 0, params)) == jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda a: 0, axes, is_leaf=lambda t: isinstance(t, tuple)))


def test_the_builders_totals_are_the_published_ones_and_the_files():
    import json

    with open(os.path.join(ROOT, "benchmarks/configs/dots3-note-prev-ep32-1chip.json")) as f:
        config = json.load(f)
    assert round(builder.total_params(config, uncut=True) / 1e9, 2) == 279.55
    assert round(builder.total_params(config, uncut=True, active=True) / 1e9, 2) == 16.25
    assert round(builder.total_params(dict(config, num_attention_heads=32, swa_num_attention_heads=16)) / 1e6, 1) == 1452.5  # ISSUE 66's 4-way
    assert builder.total_params(config) == builder._transformer_config(config, 8192).num_params() == 1_390_831_104
    assert round(builder.needed_flops_per_token(config, 8192) / 3e6) == 1304
    assert round(builder.distortion(config, 8192)["selected_pairs_pct_of_causal"], 1) == 43.7


def test_logits_agree_with_the_reference(tiny):
    cfg, params, tokens = tiny
    got = transformer.forward(params, tokens, cfg)
    assert rel(got, ref.logits(CONFIG, params, tokens, last=SEQ)) < RTOL


@pytest.mark.parametrize("policy", ["attn", "qkv_attn"])
def test_logits_and_loss_agree_through_the_remat_policies(tiny, policy):
    cfg, params, tokens = tiny
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}
    want, _ = one_device_ctx(cfg)._loss(params, batch)
    again = dataclasses.replace(cfg, remat=True, remat_policy=policy)
    got, grads = jax.value_and_grad(lambda p: one_device_ctx(again)._loss(p, batch)[0])(params)
    assert abs(float(got) - float(want)) < 1e-5 and all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree_util.tree_leaves(grads))


@pytest.mark.parametrize("wrong, least", [
    ("dense_causal", 0.05), ("no_index_weights", 0.02), ("no_relu", 0.02), ("no_gate", 0.1), ("window_short", 0.01),
    ("no_rescale", 0.05), ("sliding_full_ranks", 0.02)])
def test_the_comparison_notices_each_mechanism_got_wrong(tiny, wrong, least):
    """Each control must FAIL the comparison: its logits lie further from the
    program's than the harness's limit at this depth, by the margin given."""
    cfg, params, tokens = tiny
    got = transformer.forward(params, tokens, cfg)
    error = rel(got, ref.logits(CONFIG, params, tokens, last=SEQ, wrong=wrong))
    assert error > max(least, ref.tolerance(CONFIG["num_hidden_layers"])), (wrong, error)


@pytest.fixture(scope="module")
def loss_and_grads(tiny):
    cfg, params, tokens = tiny
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}
    ctx = one_device_ctx(cfg)
    (got, terms), grads = jax.value_and_grad(ctx._loss, has_aux=True)(params, batch)
    (want, want_terms), want_grads = jax.value_and_grad(
        lambda p: ref.loss(CONFIG, p, batch["tokens"], batch["targets"]), has_aux=True)(params)
    return ctx, batch, params, (got, terms, grads), (want, want_terms, want_grads)


def test_loss_and_both_terms_agree_with_the_reference(loss_and_grads):
    _, _, _, (got, terms, _), (want, want_terms, _) = loss_and_grads
    assert abs(float(got) - float(want)) < 1e-4 * abs(float(want))
    for name in ("ce_loss", "dsa_index_kl"):
        assert abs(float(terms[name]) - float(want_terms[name])) < 1e-4 * abs(float(want_terms[name])), name
    assert float(terms["dsa_index_kl"]) > 0.01  # two layers' terms, nats: part of the objective, not noise
    assert float(terms["dsa_causal_pairs"]) == SEQ * (SEQ + 1) / 2
    assert float(terms["dsa_index_tiles_visited_pct"]) == 100.0  # 8 heads of 16 at 64 positions: the plain form
    wanted = sum(min(t + 1, 16) for t in range(SEQ))  # and the keys that tie with a query's 16th (eight heads' ReLUs can all be 0)
    assert wanted <= float(terms["dsa_selected_pairs"]) < wanted + 4


def test_gradients_agree_with_the_reference_leaf_by_leaf(loss_and_grads):
    _, _, _, (_, _, grads), (_, _, want) = loss_and_grads
    flat, want_flat = (dict(jax.tree_util.tree_flatten_with_path(g)[0]) for g in (grads, want))
    assert flat.keys() == want_flat.keys()
    for path, g in flat.items():
        if path[-1].key == "router_bias":
            assert float(jnp.max(jnp.abs(g))) == 0.0
            continue
        assert float(jnp.max(jnp.abs(want_flat[path]))) > 0, path
        assert rel(g, want_flat[path]) < 2e-3, (jax.tree_util.keystr(path), rel(g, want_flat[path]))


def test_the_indexer_learns_from_the_kl_term_alone_and_nothing_else_from_it(loss_and_grads):
    """The gradient's separation, in the program (the reference's two terms are held leaf by leaf above, as their sum)."""
    ctx, batch, params, _, _ = loss_and_grads
    for term, own in (("ce_loss", False), ("dsa_index_kl", True)):
        grads = jax.grad(lambda p: ctx._loss(p, batch)[1][term])(params)
        for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
            learns = (path[-1].key in INDEXER) == own and path[-1].key != "router_bias"  # the indexer's five from KL, the rest from CE
            assert (float(jnp.max(jnp.abs(g))) > 0) == learns, (term, path)


def _uncut():
    """The tiny model with every head, expert and row, and its reference configuration."""
    share = CONFIG["share"]
    published = dict(CONFIG, num_attention_heads=8, num_key_value_heads=8, swa_num_attention_heads=4, swa_num_key_value_heads=4,
                     n_routed_experts=8, vocab_size=256,
                     share=dict(share, head_parallel=1, head_share_index=0, first_expert_held=0))
    return published, config_of(published)


def test_a_share_of_the_heads_is_that_range_of_the_whole_models_draw():
    _, whole_cfg = _uncut()
    whole = transformer.init_params(whole_cfg, jax.random.PRNGKey(0))
    for index in (0, 1):
        part = transformer.init_params(dataclasses.replace(whole_cfg, head_share=(index, 2)), jax.random.PRNGKey(0))
        for stack, subtree, heads in (("mla_sparse_layers_dense", "mla_sparse", 4), ("mla_window_layers", "mla_window", 2)):
            a, b = whole[stack][subtree], part[stack][subtree]
            held = slice(index * heads, (index + 1) * heads)
            assert bool(jnp.all(a["w_qb"][:, :, held] == b["w_qb"])) and bool(jnp.all(a["w_kvb"][:, :, held] == b["w_kvb"]))
            assert bool(jnp.all(a["wo"][:, held] == b["wo"])) and bool(jnp.all(a["w_gate"][..., held] == b["w_gate"]))
            assert all(bool(jnp.all(a[name] == b[name])) for name in a if name not in ("w_qb", "w_kvb", "wo", "w_gate"))  # the indexer too


def test_the_shares_of_heads_experts_and_rows_add_up_to_the_uncut_layer():
    """What every chip computes alike (the latents, the indexer and its
    selection, the router, the shared expert) counted once, the four parts a
    chip computes of its own (its heads' part of W_o's sum, its experts' part of
    the routed sum, its rows of the head) summed: the uncut reference's layer."""
    published, whole_cfg = _uncut()
    whole = redrawn(transformer.init_params(whole_cfg, jax.random.PRNGKey(0)))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, SEQ, 64))
    positions = jnp.arange(SEQ)
    layers, attn, ffn = ref._facts(published)
    for (kind, stack), rope, window in ((layers[1], whole_cfg.layer_ropes[1], None), (layers[2], whole_cfg.layer_ropes[2], 9)):
        w = jax.tree_util.tree_map(lambda a: a[0], whole[stack])
        mixer, sub = MIXERS[ref.SUBTREE[kind]], ref.SUBTREE[kind]
        total = jnp.zeros_like(x)
        for index in (0, 1):
            heads = w[sub]["wo"].shape[0] // 2
            held = slice(index * heads, (index + 1) * heads)
            part = dict(w, **{sub: dict(w[sub], w_qb=w[sub]["w_qb"][:, held], w_kvb=w[sub]["w_kvb"][:, held], wo=w[sub]["wo"][held],
                                        w_gate=w[sub]["w_gate"][:, held])})
            cfg = dataclasses.replace(whole_cfg, head_share=(index, 2))
            total += mixer.mix(x, part, positions, cfg, None, window=window, rope=rope)[0] - x
        with jax.default_matmul_precision("highest"):
            mixed = ref._attention(x[0], w, **attn[kind])[0]
            assert rel(x[0] + total[0], mixed) < RTOL, kind
            routed = jnp.zeros_like(x)
            shared = None
            for first in (0, 4):
                mlp = dict(w["mlp"], **{name: w["mlp"][name][first: first + 4] for name in ("w_gate", "w_up", "w_down")})
                cfg = dataclasses.replace(whole_cfg, n_experts_held=4, first_expert_held=first)
                out = transformer._ffn_half(mixed[None], dict(w, mlp=mlp), cfg, lambda h, axes: h, None, None, "experts")[0] - mixed[None]
                alone = _swiglu(ref._rms_norm(mixed, w["ln2"], 1e-5), w["mlp"]["shared"])
                shared = alone
                routed += out - alone[None]
            assert rel(mixed + routed[0] + shared, ref._ffn(mixed, w, **dict(ffn, first=0))) < RTOL, kind
    logits = transformer.forward(whole, jnp.zeros((1, SEQ), jnp.int32), whole_cfg)
    rows = dict(whole, lm_head=whole["lm_head"][:, 128:])
    assert rel(transformer.forward(rows, jnp.zeros((1, SEQ), jnp.int32), whole_cfg), logits[..., 128:]) < 1e-6


def test_select_topk_keeps_each_querys_best_causal_keys_as_a_sort_would():
    scores = 3.0 * jax.random.normal(jax.random.PRNGKey(1), (2, 128, 128))
    mask = np.asarray(sa.select_topk(scores, 32))
    causal = np.tril(np.ones((128, 128), bool))
    for b in range(2):
        ranked = np.where(causal, np.asarray(scores[b]), -np.inf)
        want = np.zeros((128, 128), np.int8)
        for t in range(128):
            want[t, np.argsort(-ranked[t], kind="stable")[:min(t + 1, 32)]] = 1
        assert (mask[b] == want).all()
    tied = np.asarray(sa.select_topk(jnp.zeros((1, 128, 128)), 32))[0]  # every key ties with the k-th: all causal keys kept
    assert (tied == causal).all()


def _core_inputs(s=256, h=2, d=64, dv=64):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (1, s, h, d)) * d ** -0.5
    k, v = jax.random.normal(ks[1], (1, s, h, d)), jax.random.normal(ks[2], (1, s, h, dv))
    mask = sa.select_topk(jax.random.normal(ks[3], (1, s, s)), 64)
    return q, k, v, mask, jax.random.normal(ks[4], (1, s, h, dv))


def _dense_core(q, k, v, mask):
    logits = jnp.where(mask[:, None] != 0, jnp.einsum("bqhd,bshd->bhqs", q, k), -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqs,bshd->bqhd", probs, v), jnp.moveaxis(jax.nn.logsumexp(logits, axis=-1), 1, 2), probs


@pytest.mark.parametrize("form", ["plain", "kernels"])
def test_the_core_attends_exactly_the_selected_keys_in_both_forms_and_directions(form, monkeypatch):
    """The plain form and the Pallas kernels (interpret mode, 256-tiles: a
    tile pair above the diagonal is never run) against the explicit softmax
    over the mask: output, log-sum-exp, the three gradients and the target."""
    if form == "kernels":
        from tests.conftest import as_lowered_for_tpu

        as_lowered_for_tpu(monkeypatch)
    q, k, v, mask, do = _core_inputs()
    want_out, want_lse, probs = _dense_core(q, k, v, mask)
    out, lse = sa.selected_attention(q, k, v, mask)
    assert rel(out, want_out) < 1e-5 and float(jnp.max(jnp.abs(lse - want_lse))) < 1e-4
    grads = jax.vjp(lambda *a: sa.selected_attention(*a, mask)[0], q, k, v)[1](do)
    want = jax.vjp(lambda *a: _dense_core(*a, mask)[0], q, k, v)[1](do)
    assert all(rel(a, b) < 1e-4 for a, b in zip(grads, want))
    target = sa.head_mean_probs(q, k, lse, mask)
    assert float(jnp.max(jnp.abs(target - jnp.mean(probs, axis=1)))) < 1e-5
    assert "pallas_call" in str(jax.make_jaxpr(lambda *a: sa.selected_attention(*a, mask))(q, k, v))  # both forms are traced


def _index_inputs(dtype, s=384, j=8, d=128):
    """384 positions are three 128-tiles a side: three tile pairs lie wholly above the diagonal and are never run."""
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    qi, ki = jax.random.normal(ks[0], (1, s, j, d)).astype(dtype), jax.random.normal(ks[1], (1, s, d)).astype(dtype)
    w = jax.random.normal(ks[2], (1, s, j)) * (j * d) ** -0.5
    selected = sa.select_topk(jax.random.normal(ks[3], (1, s, s)), 64) != 0
    return qi, ki, w, jnp.where(selected, jax.random.normal(ks[4], (1, s, s)), 0.0)


def _dense_scores(qi, ki, w):
    z = jnp.einsum("bqjd,bsd->bqjs", qi.astype(jnp.float32), ki.astype(jnp.float32), precision="highest")
    return jnp.tril(jnp.sum(jax.nn.relu(z) * w[..., None], axis=2))


@pytest.mark.parametrize("form", ["plain", "kernels"])
def test_the_indexers_scores_agree_in_both_forms_and_directions(form, monkeypatch):
    """PR 67.  The plain form and the Pallas kernels (interpret mode,
    128-tiles over 384 positions: a tile pair above the diagonal is never run)
    against the explicit sum over the heads: every causal pair to float32
    rounding, 0.0 on every pair above the diagonal; `dq`, `dk`, `dw` against
    `jax.vjp` of the plain form for a cotangent that is zero off a random
    selection, in float32 and with bf16 operands (there both forms round the
    backward's `dz` and two of the sums to bf16)."""
    if form == "kernels":
        from tests.conftest import as_lowered_for_tpu

        as_lowered_for_tpu(monkeypatch)
    for dtype, tolerance in ((jnp.float32, 1e-5), (jnp.bfloat16, 8e-3)):
        qi, ki, w, d_scores = _index_inputs(dtype)
        scores, pull = jax.vjp(sa.index_scores, qi, ki, w)
        assert scores.dtype == jnp.float32 and rel(scores, _dense_scores(qi, ki, w)) < 1e-6
        assert float(jnp.max(jnp.abs(jnp.triu(scores[0], 1)))) == 0.0
        want = jax.vjp(lambda qi, ki, w: sa._plain_scores(ki, qi, w), qi, ki, w)[1](d_scores)
        got = pull(d_scores)
        assert [(g.shape, g.dtype) for g in got] == [(a.shape, a.dtype) for a in (qi, ki, w)]
        assert all(rel(a.astype(jnp.float32), b.astype(jnp.float32)) < tolerance for a, b in zip(got, want))
    text = str(jax.make_jaxpr(lambda *a: jax.vjp(sa.index_scores, *a)[1](d_scores))(qi, ki, w))
    assert all(name in text for name in ("dsa_index_fwd", "dsa_index_bwd_dq", "dsa_index_bwd_dk"))  # both forms are traced


def test_the_two_forms_of_the_scores_select_the_same_keys(monkeypatch):
    """PR 67.  `select_topk` over the kernels' scores and over the plain form's
    on a seeded input with bf16 operands: the masks are equal but for keys whose
    score lies within float32 rounding of the query's threshold (the forms sum
    the heads in different orders); none is expected."""
    from tests.conftest import as_lowered_for_tpu

    qi, ki, w, _ = _index_inputs(jnp.bfloat16)
    plain = sa._plain_scores(ki, qi, w)
    as_lowered_for_tpu(monkeypatch)
    kernels = sa.index_scores(qi, ki, w)
    mask, other = sa.select_topk(plain, 64), sa.select_topk(kernels, 64)
    threshold = jnp.min(jnp.where(mask != 0, plain, jnp.inf), axis=-1, keepdims=True)
    flipped = mask != other
    assert int(jnp.sum(flipped & (jnp.abs(plain - threshold) > 1e-5 * jnp.abs(threshold)))) == 0
    assert int(jnp.sum(flipped)) <= 2, int(jnp.sum(flipped))
    assert int(jnp.sum(mask)) == sum(min(t + 1, 64) for t in range(384))


@pytest.mark.parametrize("heads, dim, seq, want", [(64, 128, 8192, 53.125), (8, 128, 384, 100 * 6 / 9), (8, 16, 64, 100.0)])
def test_the_step_counts_the_tile_pairs_the_index_kernel_runs(heads, dim, seq, want):
    """PR 67: `dsa_index_tiles_visited_pct`, a constant of the traced step: the
    pairs on or under the diagonal at the tiles in use (136 of 256 at 8,192
    under 512-tiles of keys), 100 at shapes the kernels refuse (the plain form
    scores every pair), nothing for a model without a learned-sparse layer."""
    from ray_tpu.models import lm

    cfg = config_of(index_heads=heads, index_head_dim=dim)
    assert lm._index_counters(cfg, seq) == {lm.INDEX_TILES: pytest.approx(want)}
    assert lm.INDEX_TILES == "dsa_index_tiles_visited_pct" and lm.INDEX_TILES in lm.STEP_COUNTERS
    sliding = dataclasses.replace(cfg, layer_types=tuple("mla_window" for _ in cfg.layer_types))
    assert lm._index_counters(sliding, seq) == {}


def test_the_kl_term_and_its_hand_written_gradient():
    q, k, v, mask, _ = _core_inputs(s=128)
    scores = jax.random.normal(jax.random.PRNGKey(7), (1, 128, 128))
    target = jnp.mean(_dense_core(q, k, v, mask)[2], axis=1)

    def plain(scores):
        log_q = jax.nn.log_softmax(jnp.where(mask != 0, scores, -jnp.inf), axis=-1)
        return jnp.sum(jnp.where(target > 0, target * (jnp.log(jnp.where(target > 0, target, 1.0)) - jnp.where(mask != 0, log_q, 0.0)), 0.0)) / 128

    assert abs(float(sa.index_kl(scores, mask, target)) - float(plain(scores))) < 1e-5
    assert rel(jax.grad(lambda s: sa.index_kl(s, mask, target))(scores), jax.grad(plain)(scores)) < 1e-5


def test_the_windowed_flash_kernels_take_a_window_that_is_a_multiple_of_no_tile():
    """513 = 512 + the query's own key: tiles of 512, two key tiles a query tile, the walk starting at the tile that holds key t - 512."""
    assert fa._window_blocks(513, fa.DEFAULT_BLOCKS) == (512, 512, 512, 512)
    assert np.bincount(fa.tile_pairs(2048, 2048, 512, 512, True, 513, None, True).own).tolist() == [1, 2, 2, 2]
    s, window = 384, 129  # the same at 128-tiles: interpret mode at 2,048 positions would take minutes
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = (jax.random.normal(key, (1, s, 2, 64)) for key in ks[:2])
    v, do = (jax.random.normal(key, (1, s, 2, 64)) for key in ks[2:])
    flash = lambda q, k, v: fa.flash_attention(q, k, v, causal=True, window=window)
    plain = lambda q, k, v: reference_attention(q, k, v, causal=True, window=window)
    assert fa._window_blocks(window, fa.DEFAULT_BLOCKS)[0] == 128
    assert rel(flash(q, k, v), plain(q, k, v)) < 1e-5
    assert all(rel(a, b) < 1e-4 for a, b in zip(jax.vjp(flash, q, k, v)[1](do), jax.vjp(plain, q, k, v)[1](do)))


def test_the_layers_are_named_for_the_trace(tiny):
    cfg, params, tokens = tiny
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}
    text = jax.jit(lambda p: jax.grad(lambda p: one_device_ctx(cfg)._loss(p, batch)[0])(p)).lower(params).as_text(debug_info=True)
    for name in ("dsa/index", "dsa/topk", "dsa/attn", "dsa/kl", "attn/gate", "mla/proj", "mla/window"):
        assert name in text, name


@pytest.mark.parametrize("kw, match", [
    (dict(head_share=(2, 2)), "head_share"), (dict(head_share=(0, 3)), "head_share"),
    (dict(index_topk=0), "index_heads, index_topk"), (dict(q_lora_rank=None), "q low-rank"), (dict(window_latent=None), "latent geometry"),
    (dict(mtp_depth=1, mtp_loss_weight=0.1, layer_types=("mla_sparse",) * 5, layer_windows=None), "report"),
    (dict(layer_types=("mla",) * 5, layer_ropes=None, mla_rope=None), "every layer is of a kind that reads it"),
])
def test_the_configuration_refuses_what_the_two_kinds_cannot_run(kw, match):
    with pytest.raises(ValueError, match=match):
        config_of(**kw)


def test_a_share_of_the_heads_runs_on_one_device(tiny):
    cfg, _, _ = tiny
    with pytest.raises(ValueError, match="head_share is one rank's share"):
        LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=2), devices=jax.devices()[:2]), strategy="dp")
    with pytest.raises(ValueError, match="takes no window"):
        dsa.mix_sparse(jnp.zeros((1, SEQ, 64)), {}, jnp.arange(SEQ), cfg, None, window=9)
