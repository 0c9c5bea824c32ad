"""Qwen3-Next through the program (PERF.md section 4, PR 57): "gdn" layers (a
gated delta rule with ONE decay a head, value heads in groups over key heads,
a full-rank `silu(z)` gate in the per-head norm) three to one with softmax
attention whose heads rotate a PART of themselves and whose output passes a
sigmoid gate, zero-centred norms, and softmax-routed experts with a gated
shared expert, a share of them held.  Held to
`benchmarks/lib/reference_qwen3_next.py` (the recurrence token by token, its
own routing) at tiny widths that keep the published ratios (2 value heads a
key head, GQA 4:1 at a head size that is not d / heads, a quarter of each head
rotated, top-5 of 32 with 2 held as top-10 of 512 with 32, delta delta delta
attention twice), on the CPU, seeded weights; on the chip the same comparison
decides the cell's `correct` at the published widths."""

import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import only_the_delta_convolution_runs_its_kernels, without_file_locations

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.builders import qwen3_next_decoder as builder  # noqa: E402
from benchmarks.lib import reference  # noqa: E402
from benchmarks.lib import reference_qwen3_next as ref  # noqa: E402
from ray_tpu.models import LMTrainContext, TransformerConfig, default_optimizer, moe  # noqa: E402
from ray_tpu.models import transformer  # noqa: E402
from ray_tpu.models.mixers import MIXERS, gdn  # noqa: E402
from ray_tpu.models.mixers.base import rms_norm  # noqa: E402
from ray_tpu.ops.rotary import Rope, apply_rope  # noqa: E402
from ray_tpu.parallel import MeshSpec, build_mesh  # noqa: E402

SEQ = 64
with open(os.path.join(ROOT, "benchmarks", "configs", "qwen3-next-80b-a3b-ep16-1chip.json")) as f:
    PUBLISHED = json.load(f)
# The configuration file's keys at a tiny size: two periods, 2 key heads and 4 value heads of 16, 4 q heads and 1 k/v
# head of 32 (8 of them rotated), 2 of 32 experts held from expert 4, five choices a token.
CONFIG = dict(
    PUBLISHED, hidden_size=64, num_attention_heads=4, num_key_value_heads=1, head_dim=32, vocab_size=128,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16, linear_value_head_dim=16,
    moe_intermediate_size=24, shared_expert_intermediate_size=24, num_experts=2, num_experts_per_tok=5, rope_theta=100,
    share=dict(PUBLISHED["share"], num_experts_total=32, first_expert_held=4),
)
RTOL = 2e-4  # float32 against float32 under precision "highest": what the orders of summation cost
NORMS = ("ln1", "ln2", "final_norm", "q_norm", "k_norm")
TOY_BF16 = 3 * reference.tolerance(8)  # what bf16 may cost at THESE widths (`test_logits_in_bfloat16_...`)


def config_of(published=CONFIG, **kw):
    base = builder.model_kwargs(published, SEQ)
    base.update(dtype=jnp.float32, param_dtype=jnp.float32, remat=False, remat_policy=None)
    base.update(kw)
    return TransformerConfig(**base)


def redrawn(params, seed=1):
    """Every leaf that starts at a constant (the zero-centred norms' w, the
    gated norm's scale) drawn anew, so that a test cannot pass by ignoring it."""
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    out = []
    for (path, leaf), key in zip(flat, keys):
        name = path[-1].key
        if name in NORMS:
            leaf = 0.3 * jax.random.normal(key, leaf.shape, leaf.dtype)
        elif name == "norm":
            leaf = 1.0 + 0.2 * jax.random.normal(key, leaf.shape, leaf.dtype)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(tree, out)


def one_device_ctx(cfg, **kw):
    return LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), strategy="dp", **kw)


@pytest.fixture(scope="module", autouse=True)
def chunk_of_16():
    """The program's chunk for this module: S = 64 crosses three boundaries."""
    from ray_tpu.ops import kda

    saved, kda.CHUNK = kda.CHUNK, 16
    yield
    kda.CHUNK = saved


@pytest.fixture(scope="module")
def tiny():
    cfg = config_of()
    params = redrawn(transformer.init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, SEQ), 0, cfg.vocab_size)
    return dict(cfg=cfg, params=params, tokens=tokens, targets=jnp.roll(tokens, -1, axis=1))


@pytest.fixture(scope="module")
def want(tiny):
    return ref.logits(CONFIG, tiny["params"], tiny["tokens"], last=SEQ)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / (np.mean(b ** 2) + 1e-300)))


# -- the stack and what it holds ---------------------------------------------------------------


def test_two_periods_are_four_runs_over_two_stacks(tiny):
    cfg = tiny["cfg"]
    assert cfg.layer_types == ("gdn", "gdn", "gdn", "attention") * 2
    assert cfg.layer_runs() == (("gdn", "experts", 0, 3), ("attention", "experts", 0, 1),
                                ("gdn", "experts", 3, 3), ("attention", "experts", 1, 1))
    assert {k: v[2] for k, v in cfg.stacks().items()} == {"layers": 2, "gdn_layers": 6}
    assert list(MIXERS)[8] == "gdn"  # appended behind the eight before it (PR 66's two behind it): the order fixes the key sequence of every other model's weights
    attn, delta = tiny["params"]["layers"]["attn"], tiny["params"]["gdn_layers"]["gdn"]
    assert attn["wq"].shape == (2, 64, 4, 64) and attn["q_norm"].shape == (2, 32)  # q | gate a head; one scale for all heads
    assert sorted(delta) == ["A_log", "conv_w", "dt_bias", "norm", "wba", "wo", "wqkvz"]
    assert delta["wqkvz"].shape == (6, 64, 2 * 32 + 2 * 64) and delta["A_log"].shape == (6, 4)
    assert tiny["params"]["gdn_layers"]["mlp"]["shared"]["gate"].shape == (6, 64, 1)
    fresh = transformer.init_params(cfg, jax.random.PRNGKey(0))
    for name in ("ln1", "ln2"):  # a zero-centred norm stores w, started at 0; the gated norm's scale starts at 1
        assert not fresh["gdn_layers"][name].any() and not fresh["layers"][name].any()
    assert not fresh["final_norm"].any() and not fresh["layers"]["attn"]["q_norm"].any()
    assert bool((fresh["gdn_layers"]["gdn"]["norm"] == 1).all())


def test_the_program_holds_what_the_builder_counts_and_the_file_the_published_totals():
    kw = builder.model_kwargs(PUBLISHED, 8192)
    cfg = TransformerConfig(**{**kw, "dtype": jnp.bfloat16, "param_dtype": jnp.bfloat16})
    assert cfg.num_params() == builder.total_params(PUBLISHED) == PUBLISHED["share"]["params_here"] == 1_173_540_992
    assert builder.total_params(PUBLISHED, uncut=True) == PUBLISHED["share"]["params_total"] == 79_674_391_296  # "80B"
    assert cfg.head_dim == 256 != cfg.d_model // cfg.n_heads and cfg.rotary_dim == 64
    assert moe._rungs(8192 * 10, 32, 512, 10) == (10240, 20480, 40960, 81920)  # 0.625 a token: twice a uniform share first
    assert len(cfg.layer_runs()) == 4 and sorted(PUBLISHED["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    with pytest.raises(ValueError, match="router_share_init needs"):  # 10 * 32 / 512 is no whole number of choices
        dataclasses.replace(cfg, router_share_init=True)


@pytest.mark.parametrize("cfg", [config_of(), config_of(n_experts_held=None)], ids=["share", "whole"])
def test_num_params_counts_every_leaf(cfg):
    params = jax.eval_shape(lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params)) == cfg.num_params()


@pytest.mark.parametrize("kw, match", [
    (dict(norm_kind="layer"), "norm_zero_centred is the RMSNorms'"),
    (dict(n_shared_experts=0), "shared_expert_gate needs a shared expert"),
    (dict(gdn_value_heads=3), "the value heads a multiple of the key heads"),
    (dict(layer_types=("gdn",) * 8, layer_ropes=(Rope(100.0),) + (None,) * 7), "at a gdn layer"),
], ids=["layer-norm", "gate-without-expert", "ungrouped-heads", "rope-at-a-delta-layer"])
def test_what_the_new_fields_cannot_express_is_refused_when_the_configuration_is_built(kw, match):
    with pytest.raises(ValueError, match=match):
        config_of(**kw)


# -- the model against the reference -----------------------------------------------------------


def test_logits_agree_with_the_reference(tiny, want):
    assert rel(transformer.forward(tiny["params"], tiny["tokens"], tiny["cfg"]), want) < RTOL


def test_logits_in_bfloat16_stay_far_under_every_wrong_mechanism(tiny):
    """The cell's comparison at the tiny size: bf16 weights, the program in
    bf16, the reference in float32 from the same weights.  At the published
    widths on the chip it reads 0.021-0.022 of the harness's 0.0339 (PERF.md
    section 6, PR 57); at these widths (heads of 16 and 32, d 64) a rounding is
    averaged over an eighth of the terms and the delta layers carry ~0.75% a
    layer (0.04-0.10 over seeds and lengths), so the toy is held to `TOY_BF16`,
    three times the harness's limit, and every wrong mechanism below to more."""
    cfg = dataclasses.replace(tiny["cfg"], dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), tiny["params"])
    got = transformer.forward(params, tiny["tokens"], cfg)
    error = rel(got, ref.logits(CONFIG, params, tiny["tokens"], last=SEQ))
    assert 10 * RTOL < error < TOY_BF16


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_the_tolerance_catches_each_mechanism_gone_wrong(tiny, want, wrong):
    """One negative control each, in the reference: the float32 program is
    further from a reference with ONE mechanism wrong than a bf16 program is
    from the right one, at the harness's limit and at the toy's."""
    got = transformer.forward(tiny["params"], tiny["tokens"], tiny["cfg"])
    other = ref.logits(CONFIG, tiny["params"], tiny["tokens"], last=SEQ, wrong=(wrong,))
    assert rel(got, other) > TOY_BF16 > reference.tolerance(CONFIG["num_hidden_layers"])
    assert rel(other, want) > TOY_BF16


@pytest.mark.parametrize("change", [dict(rotary_dim=None), dict(norm_zero_centred=False), dict(shared_expert_gate=False),
                                    dict(norm_topk_prob=False)],
                         ids=["rope-over-the-whole-head", "scale-w-not-1-plus-w", "shared-expert-ungated", "gates-not-renormalised"])
def test_the_tolerance_catches_each_field_left_at_its_default(tiny, want, change):
    """The same from the program's side: one field of `TransformerConfig` left at what the other ten have."""
    params = tiny["params"]
    if "shared_expert_gate" in change:
        drop = lambda stack: {**stack, "mlp": {**stack["mlp"], "shared": {  # noqa: E731
            k: v for k, v in stack["mlp"]["shared"].items() if k != "gate"}}}
        params = {**params, "layers": drop(params["layers"]), "gdn_layers": drop(params["gdn_layers"])}
    got = transformer.forward(params, tiny["tokens"], dataclasses.replace(tiny["cfg"], **change))
    assert rel(got, want) > reference.tolerance(CONFIG["num_hidden_layers"])


def test_a_dropped_mask_and_a_wrong_name_are_noticed(tiny, want):
    assert rel(ref.logits(CONFIG, tiny["params"], tiny["tokens"], last=SEQ, causal=False), want) > 0.1
    with pytest.raises(ValueError, match="not of"):
        ref.logits(CONFIG, tiny["params"], tiny["tokens"], last=SEQ, wrong=("no_rope",))


def test_logits_agree_through_the_remat_policies(tiny):
    want = transformer.forward(tiny["params"], tiny["tokens"], tiny["cfg"])
    for policy in (None, "attn", "qkv_attn"):
        cfg = dataclasses.replace(tiny["cfg"], remat=True, remat_policy=policy)
        np.testing.assert_allclose(transformer.forward(tiny["params"], tiny["tokens"], cfg), want, rtol=1e-5, atol=1e-5)


@pytest.fixture(scope="module")
def loss_and_grads(tiny):
    cfg = dataclasses.replace(tiny["cfg"], remat=True, remat_policy="qkv_attn")
    batch = {"tokens": tiny["tokens"], "targets": tiny["targets"]}
    (loss, terms), grads = jax.jit(jax.value_and_grad(one_device_ctx(cfg)._loss, has_aux=True))(tiny["params"], batch)
    want_loss, want_grads = jax.jit(jax.value_and_grad(functools.partial(ref.objective, CONFIG)))(
        tiny["params"], tiny["tokens"], tiny["targets"])
    return dict(loss=loss, terms=terms, grads=grads, want_loss=want_loss, want_grads=want_grads)


def test_loss_agrees_with_the_reference_and_the_step_counters_ride_beside_it(loss_and_grads, tiny):
    terms = loss_and_grads["terms"]
    assert abs(float(loss_and_grads["loss"]) - float(loss_and_grads["want_loss"])) < 1e-5
    assert float(loss_and_grads["loss"]) == float(terms["ce_loss"])  # no auxiliary loss: both coefficients are 0
    tokens, k, total = tiny["tokens"].size, 5, 32
    assert 0 < float(terms["moe_held_rows_mean"]) <= float(terms["moe_held_rows_max"]) <= tokens
    assert float(terms["moe_held_rows_mean"]) == pytest.approx(tokens * k / total, rel=0.6)  # K*T/E rows an expert
    assert 0 < float(terms["moe_rows_moved_share"]) <= 1.0 <= float(terms["moe_load_max_over_mean"])


def test_gradients_agree_with_the_reference_leaf_by_leaf(loss_and_grads):
    got = dict(jax.tree_util.tree_flatten_with_path(loss_and_grads["grads"])[0])
    want = dict(jax.tree_util.tree_flatten_with_path(loss_and_grads["want_grads"])[0])
    assert got.keys() == want.keys()
    worst = {jax.tree_util.keystr(p): rel(got[p], want[p]) for p in got}
    assert max(worst.values()) < 2e-3, sorted(worst.items(), key=lambda kv: -kv[1])[:5]
    assert all(float(jnp.abs(want[p]).max()) > 0 for p in want)  # every leaf has a gradient: both gates, A_log, dt_bias


# -- the delta layer's convolution (PR 60) -------------------------------------------------------


def test_at_heads_of_128_the_layers_convolution_runs_its_kernels_and_no_mamba_2_kernel(monkeypatch):
    """PR 60: at the published head sizes a delta layer's q, k and v come from
    `delta_conv`'s kernels (two blocks of positions here), read from the
    q | k | v columns of the fused projection with z's left behind, with the
    same loss and gradients as the plain form gives, `conv_w`'s and `wqkvz`'s
    among them.  And the step lowered for TPU holds those kernels under
    `gdn/conv` and none of `ssm_conv_*`."""
    cfg = config_of(dict(CONFIG, linear_key_head_dim=128, linear_value_head_dim=128), remat=True, remat_policy="qkv_attn")
    params = redrawn(transformer.init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, SEQ), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}
    objective = lambda p: one_device_ctx(cfg)._loss(p, batch)[0]  # noqa: E731
    want_loss, want = jax.jit(jax.value_and_grad(objective))(params)
    lowered = jax.jit(jax.grad(objective)).trace(params).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "ssm_conv" not in without_file_locations(lowered)
    assert "gdn/conv/cond/branch_0_fun/delta_conv_fwd" in lowered and "delta_conv_bwd" in lowered
    only_the_delta_convolution_runs_its_kernels(monkeypatch)
    loss, got = jax.jit(jax.value_and_grad(objective))(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    worst = {jax.tree_util.keystr(path): rel(g, w) for (path, g), w in
             zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree_util.tree_leaves(want))}
    assert max(worst.values()) < 1e-4, sorted(worst.items(), key=lambda kv: -kv[1])[:5]
    assert any("conv_w" in path for path in worst) and any("wqkvz" in path for path in worst)


# -- the delta layer against the token-by-token recurrence ---------------------------------------


@pytest.mark.parametrize("decay", [1.0, 30.0], ids=["decay-mid", "decay-near-0"])
def test_gdn_through_gdn_chunked_is_the_recurrence_forward_and_gradient(tiny, decay):
    """One delta layer, 2 value heads a key head: the program's half of a layer
    (`gdn_chunked`: off TPU and at these heads of 16 the per-channel plain form
    with the head's decay broadcast over the key's channels, one chunk of 64
    positions) against the reference's token-by-token scan,
    the output and the gradient of every leaf and of the stream.  `decay`
    scales A: at 30 a head keeps e^-20 a token at its fastest."""
    cfg = tiny["cfg"]
    layer = jax.tree_util.tree_map(lambda a: a[1], tiny["params"]["gdn_layers"])
    layer = {"ln1": layer["ln1"], "gdn": {**layer["gdn"], "A_log": layer["gdn"]["A_log"] + jnp.log(decay)}}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, cfg.d_model))
    probe = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    facts = dict(eps=cfg.norm_eps, key_heads=2, key_dim=16)

    def program(layer, x):
        return gdn.mix(x, layer, None, cfg, None)[0]

    def recurrence(layer, x):
        with jax.default_matmul_precision("highest"):
            return jax.vmap(lambda xi: ref._gdn(xi, layer, **facts))(x)

    assert rel(program(layer, x) - x, recurrence(layer, x) - x) < RTOL
    got = jax.grad(lambda *a: jnp.sum(program(*a) * probe), argnums=(0, 1))(layer, x)
    wanted = jax.grad(lambda *a: jnp.sum(recurrence(*a) * probe), argnums=(0, 1))(layer, x)
    flat_got, flat_want = (dict(jax.tree_util.tree_flatten_with_path(t)[0]) for t in (got, wanted))
    worst = {jax.tree_util.keystr(p): rel(flat_got[p], flat_want[p]) for p in flat_got}
    assert max(worst.values()) < 1e-3, sorted(worst.items(), key=lambda kv: -kv[1])[:5]


def test_one_decay_a_head_is_what_the_scan_is_given(tiny):
    """The reference's rule with a decay per CHANNEL equals its rule with one a
    head when the channels are equal, and not when they are drawn apart."""
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    q, k = (ref._l2_normed(jax.random.normal(key, (SEQ, 4, 16))) for key in ks[:2])
    v, beta = jax.random.normal(ks[2], (SEQ, 4, 8)), jax.nn.sigmoid(jax.random.normal(ks[3], (SEQ, 4)))
    g = -jax.nn.softplus(jax.random.normal(ks[4], (SEQ, 4)))
    with jax.default_matmul_precision("highest"):
        a_head = ref.delta_rule(q, k, v, g, beta)
        np.testing.assert_allclose(ref.delta_rule(q, k, v, jnp.broadcast_to(g[..., None], k.shape), beta), a_head, atol=1e-6)
        assert rel(ref.delta_rule(q, k, v, g[..., None] * jnp.linspace(0.5, 1.5, 16), beta), a_head) > 0.05


def test_qkv_attn_saves_each_kinds_named_residuals_and_reruns_no_wide_projection(tiny):
    """Under the cell's policy a delta layer keeps, beside its arguments, the
    fused q|k|v|z and the b|a logits; an attention layer q, k, v, the gate and
    the core's output (on the chip the kernel's log-sum-exp too): no d-wide
    projection runs again in either backward."""
    from jax._src.ad_checkpoint import saved_residuals  # the list `jax.ad_checkpoint.print_saved_residuals` prints

    cfg = dataclasses.replace(tiny["cfg"], dtype=jnp.bfloat16, remat=True, remat_policy="qkv_attn")
    x = jnp.zeros((2, SEQ, cfg.d_model), jnp.bfloat16)

    def saved(kind, stack):
        layer = jax.tree_util.tree_map(lambda a: a[0], {k: v for k, v in tiny["params"][stack].items() if k != "mlp"})
        run = jax.checkpoint(lambda p, x: transformer.layer(MIXERS[kind], x, p, jnp.arange(SEQ), cfg, None, ffn="none")[0],
                             policy=transformer._remat_policy(cfg))
        return sorted((aval.shape, str(aval.dtype)) for aval, why in saved_residuals(run, layer, x)
                      if "from the argument" not in why and "from a constant" not in why)

    # (the stream after `wo` is the layer's output, an argument of the next: kept, and no residual of this one)
    assert saved("gdn", "gdn_layers") == [((2, SEQ, 8), "bfloat16"), ((2, SEQ, 192), "bfloat16")]
    assert saved("attention", "layers") == [((2, SEQ, 1, 32), "bfloat16")] * 2 + [((2, SEQ, 4, 32), "bfloat16")] * 3  # k, v; q, gate, output


# -- the rope, the norm, the names ---------------------------------------------------------------


def test_a_partly_rotated_head_is_a_rope_on_a_slice_and_the_reference_rotates_alike():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 48, 3, 32))
    positions = jnp.arange(48)
    part = apply_rope(x, positions, Rope(100.0, rotary_dim=8))
    np.testing.assert_array_equal(part[..., 8:], x[..., 8:])  # 24 of 32 pass
    np.testing.assert_array_equal(part[..., :8], apply_rope(x[..., :8], positions, Rope(100.0)))  # a head of 8, its own frequencies
    assert rel(part, jax.vmap(lambda a: ref._rotate(a, 100.0, 8))(x)) < 1e-6
    assert rel(part, apply_rope(x, positions, Rope(100.0))) > 0.1
    np.testing.assert_array_equal(apply_rope(x, positions, Rope(100.0, rotary_dim=32)), apply_rope(x, positions, Rope(100.0)))
    for bad in (7, 0):
        with pytest.raises(ValueError, match="even and positive"):
            Rope(100.0, rotary_dim=bad)
    with pytest.raises(ValueError, match="wider than the head"):
        apply_rope(x, positions, Rope(100.0, rotary_dim=64))


@pytest.mark.parametrize("w", [0.4, -0.4])
def test_weight_decay_pulls_a_zero_centred_scale_to_one(w):
    """A step of the job's AdamW on a ZERO gradient moves the stored w toward
    0, so the scale `1 + w` toward 1 (a plain norm's stored scale would go
    toward 0, and the layer with it)."""
    params = {"ln1": jnp.full((8,), w)}
    opt = default_optimizer()
    updates, _ = opt.update(jax.tree_util.tree_map(jnp.zeros_like, params), opt.init(params), params)
    after = optax.apply_updates(params, updates)["ln1"]
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8))
    scale = lambda leaf: rms_norm(x, leaf, 1e-6, zero_centred=True) / rms_norm(x, jnp.ones((8,)), 1e-6)  # noqa: E731
    assert bool(jnp.all(jnp.abs(after) < abs(w)))
    np.testing.assert_allclose(scale(params["ln1"]), 1 + w, rtol=1e-5)
    assert bool(jnp.all(jnp.abs(scale(after) - 1) < abs(w)))


def test_every_region_the_readers_name_is_in_the_lowered_step(tiny):
    cfg = dataclasses.replace(tiny["cfg"], remat=True, remat_policy="qkv_attn")
    ctx = one_device_ctx(cfg)
    batch = {"tokens": tiny["tokens"], "targets": tiny["targets"]}
    text = jax.jit(lambda p, b: jax.grad(lambda p: ctx._loss(p, b)[0])(p)).lower(tiny["params"], batch).as_text(debug_info=True)
    for name in ("gdn/proj", "gdn/conv", "gdn/scan", "attn/gate", "moe/router", "moe/shared", "moe/dispatch", "moe/experts",
                 "moe/combine", "layer/attn_proj", "layer/attn_core", "layer/mlp"):
        assert name in text, name
    assert "kda/scan" not in text  # the recurrence's region carries THIS layer's name


# -- the share ------------------------------------------------------------------------------------


def test_the_sixteen_shares_of_the_experts_add_up_to_the_uncut_layer(tiny):
    """The guide's share test: 32 experts in 16 shares of 2, as the
    deployment's sixteen chips hold 512 in shares of 32; the shares' routed
    parts plus the gated shared expert COUNTED ONCE equal the uncut
    reference's layer.  The renormalisation is over all the chosen, held or
    not: a share's gate values are the whole layer's.  Program and reference
    both."""
    cfg = dataclasses.replace(tiny["cfg"], n_experts_held=None)
    key = jax.random.PRNGKey(11)
    whole = moe.init_moe_params(cfg, key)
    x = jax.random.normal(jax.random.fold_in(key, 3), (2, SEQ, cfg.d_model))
    flat = x.reshape(-1, cfg.d_model)
    routing = dict(top_k=5, renormalize=True)
    experts_of = lambda first: {k: (v[first: first + 2] if k in ("w_gate", "w_up", "w_down") else v)  # noqa: E731
                                for k, v in whole.items()}
    with jax.default_matmul_precision("highest"):
        routed_want, shared_want = ref.expert_block(flat, whole, first=0, **routing)
        want = routed_want + shared_want
        routed_ref, summed_prog, rows = jnp.zeros_like(flat), jnp.zeros_like(flat), 0.0
        for first in range(0, 32, 2):
            part = experts_of(first)
            routed_ref += ref.expert_block(flat, part, first=first, **routing)[0]
            y, stats = moe.moe_ffn(part, x, dataclasses.replace(cfg, n_experts_held=2, first_expert_held=first))
            assert stats["held_rows"].shape == (2,)
            rows += float(jnp.sum(stats["held_rows"]))
            summed_prog += y.reshape(flat.shape)  # every share adds the shared expert: sixteen times
        whole_prog, _ = moe.moe_ffn(whole, x, cfg)
    assert rows == flat.shape[0] * 5  # every assignment is held by exactly one share
    assert float(jnp.abs(routed_want).max()) > 0.01 and float(jnp.abs(shared_want).max()) > 0.01
    assert rel(routed_ref + shared_want, want) < 1e-5
    assert rel(summed_prog - 15 * shared_want, want) < 1e-5
    assert rel(whole_prog.reshape(flat.shape), want) < 1e-5
