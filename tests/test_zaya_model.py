"""ZAYA1-8B through the program (PERF.md section 4, PR 68): "cca" layers
(attention in a compressed latent mixed along the sequence: two causal
convolutions over q|k, the q-k mean, a value shift, unit-norm q and k with a
learned temperature, a rope on half of each head), top-1 experts behind a
router that is a network whose state runs from layer to layer, a learned scale
and bias on both sides of every join, a tied head over a vocabulary slice.
Held to `benchmarks/lib/reference_zaya.py` (its own shifts, its own masked
softmax, its own routing, every expert on every token) at tiny widths on the
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

from benchmarks.builders import cca_moe_decoder as builder  # noqa: E402
from benchmarks.lib import reference, reference_zaya as ref  # noqa: E402
from ray_tpu.models import LMTrainContext, lm, moe, transformer  # noqa: E402
from ray_tpu.models.mixers import MIXERS, cca  # noqa: E402
from ray_tpu.parallel import MeshSpec, build_mesh  # noqa: E402

SEQ, LAYERS, SLICES = 64, 4, 8
# The configuration file's keys at a tiny size: four layers (the chain's length), 4 query / 2 key heads of 16 (8 rotated),
# 4 experts top-1 behind a router of width 16, the UNCUT vocabulary of 128 rows (8 slices of 16).
CONFIG = {
    "attention_bias": False, "hidden_act": "silu", "tie_word_embeddings": True, "lm_head_bias": False, "sliding_window": None,
    "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2, "cca_time0": 2, "cca_time1": 2,
    "layer_types": ["hybrid"] * LAYERS, "num_hidden_layers": LAYERS, "num_experts": 4, "num_experts_per_tok": 1,
    "moe_intermediate_size": 32, "router_hidden_size": 16, "rms_norm_eps": 1e-5, "vocab_size": 128, "partial_rotary_factor": 0.5,
    "rope_parameters": {"hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000, "rope_type": "default"}},
    "share": {"num_hidden_layers_total": 40, "vocab_size_total": 1024},
    "train": {"chips": 1, "mesh": {"data": 1}, "strategy": "dp", "param_dtype": "float32", "compute_dtype": "float32",
              "optimizer": "default_optimizer", "remat_policy": None},
}
RTOL = 2e-4  # float32 against float32 under precision "highest": what the orders of summation cost
TOLERANCE = reference.tolerance(LAYERS)  # the harness's own limit at this depth, 0.024: what every control must fail
CONSTANTS = {  # every leaf that starts at a constant, and the spread it is redrawn with around that constant
    "ln1": 0.2, "ln2": 0.2, "final_norm": 0.2, "norm": 0.2, "a_res": 0.2, "a_out": 0.2, "gamma": 0.3, "tau": 0.4,
    "b_res": 0.1, "b_out": 0.1, "conv1_b": 0.3, "conv2_b": 0.3, "down_b": 0.3, "b1": 0.3, "b2": 0.3, "router_bias": 0.02,
}


def config_of(published=CONFIG, **kw):
    return dataclasses.replace(builder._transformer_config(published, SEQ), remat=False, **kw)


def redrawn(params, seed=1):
    """Every leaf that starts at a constant drawn anew around it, so that a test cannot pass by ignoring it."""
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    out = []
    for (path, leaf), key in zip(flat, keys):
        spread = CONSTANTS.get(path[-1].key)
        out.append(leaf if spread is None else leaf + spread * jax.random.normal(key, leaf.shape, leaf.dtype))
    return jax.tree_util.tree_unflatten(tree, out)


def one_device_ctx(cfg):
    return LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), strategy="dp")


def rel(a, b):
    return float(jnp.sqrt(jnp.mean(jnp.square(a - b)) / jnp.mean(jnp.square(b))))


@pytest.fixture(scope="module")
def tiny():
    cfg = config_of()
    params = redrawn(transformer.init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, SEQ + 1), 0, CONFIG["vocab_size"])
    return cfg, params, tokens[:, :-1], tokens[:, 1:]


@pytest.fixture(scope="module")
def program_logits(tiny):
    cfg, params, tokens, _ = tiny
    return jax.jit(lambda p, t: transformer.forward(p, t, cfg))(params, tokens)


def test_the_kind_is_appended_and_the_stack_is_one_run_of_one_parameter_stack(tiny):
    cfg, params, _, _ = tiny
    assert list(MIXERS)[-1] == "cca"  # appended: no other model's weights move
    assert cfg.layer_runs() == (("cca", "experts", 0, LAYERS),) and list(cfg.stacks()) == [ref.STACK]
    assert cfg.carries_router_state and cfg.residual_scaling and cfg.experts_per_token == 1 and not cfg.norm_topk_prob
    assert cfg.num_params() == sum(a.size for a in jax.tree_util.tree_leaves(params)) == builder.total_params(CONFIG)
    axes = transformer.param_axes(cfg)
    assert jax.tree_util.tree_structure(jax.tree_util.tree_map(lambda a: 0, params)) == jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda a: 0, axes, is_leaf=lambda t: isinstance(t, tuple)))
    layer = params[ref.STACK]
    assert set(layer) == {"cca", "ln1", "ln2", "mlp", "res1", "res2"} and set(layer["res1"]) == {"a_res", "b_res", "a_out", "b_out"}
    assert layer["cca"]["conv2_w"].shape == (LAYERS, 2, 6, 16, 16) and layer["cca"]["tau"].shape == (LAYERS, 2)
    assert MIXERS["cca"].flash_heads(cfg) == (16, 16) and MIXERS["cca"].rotates and MIXERS["cca"].scales_residual


def test_at_the_seed_every_join_is_the_plain_sum_and_tau_and_gamma_are_one():
    params = transformer.init_params(config_of(), jax.random.PRNGKey(3))[ref.STACK]
    for res in (params["res1"], params["res2"]):
        assert all(float(jnp.min(res[n])) == float(jnp.max(res[n])) == v for n, v in (("a_res", 1), ("a_out", 1), ("b_res", 0), ("b_out", 0)))
    assert float(jnp.min(params["cca"]["tau"])) == 1.0 == float(jnp.min(params["mlp"]["router"]["gamma"]))
    assert float(jnp.max(jnp.abs(params["mlp"]["router_bias"]))) == 0.0


def test_the_published_files_totals_are_the_issues_and_the_programs():
    import json

    with open(os.path.join(ROOT, "benchmarks/configs/zaya1-8b-vp8-1chip.json")) as f:
        config = json.load(f)
    assert builder.total_params(config) == builder._transformer_config(config, 16384).num_params() == 1_105_061_210
    assert abs(builder.total_params(config, uncut=True) / (40 * 207.6e6 + 537.1e6) - 1) < 0.01  # ~8.84B, the table counted once
    assert round(builder.total_params(config, uncut=True, active=True) / 1e9, 2) == 1.29  # 0.76B + the 0.54B table
    assert round(builder.needed_flops_per_token(config, 16384) / 3e6, 1) == 490.2
    d = builder.distortion(config, 16384)
    assert [round(d[k], 1) for k in ("causal_core_pct", "experts_pct", "head_pct", "head_pct_uncut")] == [34.2, 25.7, 27.4, 27.4]
    assert builder.mix_bytes_per_layer(config) == 4 * 1280 * 2 and d["rows_per_expert_uniform"] == 1024


def test_logits_agree_with_the_reference(tiny, program_logits):
    cfg, params, tokens, _ = tiny
    want = ref.forward(CONFIG, params, tokens)
    assert rel(program_logits, want) < RTOL
    streamed = ref.logits(CONFIG, params, tokens, last=16)  # the form the chip's comparison takes: layers and experts streamed
    assert rel(streamed, want[:, -16:]) < 1e-5


def test_the_control_of_precision_lowers_the_router_and_the_mixing_and_nothing_else(tiny):
    """`logits(lowered=True)`: the reference's float32 router and q|k mixing in bfloat16.  It reads bfloat16's error, far over
    float32's agreement; whether that is over the harness's tolerance is read at the timed size on the chip (PERF.md section 6)."""
    cfg, params, tokens, _ = tiny
    plain, theirs, lows = ref.logits(CONFIG, params, tokens, last=16), [], []
    assert rel(ref.logits(CONFIG, params, tokens, last=16, record=theirs), plain) == 0.0
    error = rel(ref.logits(CONFIG, params, tokens, last=16, lowered=True, record=lows), plain)
    assert 5 * RTOL < error < 0.5, error
    assert len(lows) == LAYERS and all(a.shape == b.shape == tokens.shape for a, b in zip(lows, theirs))
    latent = jax.random.normal(jax.random.PRNGKey(5), (SEQ, 6, 16))
    a = jax.tree_util.tree_map(lambda leaf: leaf[0], params[ref.STACK]["cca"])
    q, k = ref.qk_mixing(latent, a, n_heads=4, theta=5e6, rotary=8)
    ql, kl = ref.qk_mixing(latent, a, n_heads=4, theta=5e6, rotary=8, lowered=True)
    assert ql.dtype == kl.dtype == jnp.float32 and 1e-3 < rel(ql, q) < 3e-2 and 1e-3 < rel(kl, k) < 3e-2


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_each_control_fails_the_harnesss_tolerance(tiny, program_logits, wrong):
    cfg, params, tokens, _ = tiny
    assert rel(program_logits, ref.forward(CONFIG, params, tokens, wrong=wrong)) > TOLERANCE
    with pytest.raises(ValueError, match="unknown control"):
        ref.logits(CONFIG, params, tokens, last=16, wrong="no_such_mechanism")


@pytest.fixture(scope="module")
def gradients(tiny):
    cfg, params, tokens, targets = tiny
    (loss, terms), got = jax.jit(jax.value_and_grad(one_device_ctx(cfg)._loss, has_aux=True))(params, {"tokens": tokens, "targets": targets})
    want_loss, want = jax.jit(jax.value_and_grad(lambda p: ref.loss(CONFIG, p, tokens, targets)))(params)
    return loss, terms, got, want_loss, want


def test_the_loss_is_the_references_and_the_router_adds_no_term(gradients):
    loss, terms, _, want_loss, _ = gradients
    assert abs(float(loss) - float(want_loss)) < RTOL * float(want_loss) and float(terms["ce_loss"]) == float(loss)
    assert 0.25 < float(terms["moe_gate_mean"]) < 1.0 and 1.0 <= float(terms["moe_load_max_over_mean"]) <= 4.0  # top-1 of 4


LEAF_KINDS = {
    "tau": ("cca", "tau"), "gamma": ("mlp", "router", "gamma"), "a_res": ("res1", "a_res"), "b_res": ("res2", "b_res"),
    "a_out": ("res2", "a_out"), "b_out": ("res1", "b_out"), "conv1_w": ("cca", "conv1_w"), "conv1_b": ("cca", "conv1_b"),
    "conv2_w": ("cca", "conv2_w"), "conv2_b": ("cca", "conv2_b"), "router_down": ("mlp", "router", "down"),
    "router_w2": ("mlp", "router", "w2"), "router_b1": ("mlp", "router", "b1"), "router_norm": ("mlp", "router", "norm"),
    "wq": ("cca", "wq"), "wv": ("cca", "wv"), "wo": ("cca", "wo"), "w_gate": ("mlp", "w_gate"), "w_down": ("mlp", "w_down"), "ln2": ("ln2",),
}


@pytest.mark.parametrize("kind", LEAF_KINDS)
def test_the_gradient_of_every_kind_of_leaf_is_the_references(gradients, kind):
    _, _, got, _, want = gradients
    g, w = got[ref.STACK], want[ref.STACK]
    for name in LEAF_KINDS[kind]:
        g, w = g[name], w[name]
    assert float(jnp.max(jnp.abs(w))) > 0 and rel(g, w) < 5 * RTOL


def test_every_other_leafs_gradient_too_and_the_stored_choice_bias_gets_none(gradients):
    _, _, got, _, want = gradients
    flat_got, flat_want = (jax.tree_util.tree_flatten_with_path(t)[0] for t in (got, want))
    for (path, g), (_, w) in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            assert float(jnp.max(jnp.abs(g))) == 0.0 == float(jnp.max(jnp.abs(w)))
        else:
            assert rel(g, w) < 5 * RTOL, name
    # layer 0's gamma multiplies the zeros before the first layer: a stored leaf no gradient reaches
    assert float(jnp.max(jnp.abs(got[ref.STACK]["mlp"]["router"]["gamma"][0]))) == 0.0


def test_the_tied_table_receives_the_embeddings_and_the_heads_gradient_once_each(tiny, gradients):
    cfg, params, tokens, targets = tiny
    _, _, got, _, _ = gradients
    table = params["embed"]["tokens"]
    frozen = jax.lax.stop_gradient

    def as_head(t):  # the table as the head alone: the lookups read a copy no gradient reaches
        return ref.loss(CONFIG, dict(params, embed={"tokens": frozen(table)}), tokens, targets, head=t)

    def as_embedding(t):
        return ref.loss(CONFIG, dict(params, embed={"tokens": t}), tokens, targets, head=frozen(table))

    head, rows = jax.jit(jax.grad(as_head))(table), jax.jit(jax.grad(as_embedding))(table)
    assert rel(got["embed"]["tokens"], head + rows) < 5 * RTOL
    assert rel(got["embed"]["tokens"], head) > 0.05 and rel(got["embed"]["tokens"], rows) > 0.05  # neither alone


def test_the_state_runs_from_layer_to_layer_and_gamma_carries_layer_0s_gradient(tiny):
    """The gradient of layer 0's W_d THROUGH THE LATER LAYERS' GATE VALUES: with the later layers' choices held (a
    choice has no gradient) and the stream cut behind layer 0, what is left reaches `down[0]` through gamma alone."""
    cfg, params, tokens, _ = tiny
    x = jax.random.normal(jax.random.PRNGKey(5), (1, SEQ, cfg.d_model))
    layers = [jax.tree_util.tree_map(lambda a: a[i], params[ref.STACK]) for i in range(LAYERS)]

    def last_gate_mean(down0, gammas):
        state = {"router_state": jnp.zeros((1, SEQ, cfg.router_hidden))}
        for i, lp in enumerate(layers):
            router = dict(lp["mlp"]["router"], gamma=gammas[i], **({"down": down0} if i == 0 else {}))
            lp = dict(lp, mlp=dict(lp["mlp"], router=router))
            _, stats, state = transformer._ffn_half(x, lp, cfg, lambda h, axes: h, None, None, "experts", state)
        return stats["gate_mean"]  # of layer 3: a function of layer 0's W_d through r_0 -> r_1 -> r_2 -> r_3 alone

    down0 = layers[0]["mlp"]["router"]["down"]
    gammas = [lp["mlp"]["router"]["gamma"] for lp in layers]
    carried = jax.grad(last_gate_mean)(down0, gammas)
    cut = jax.grad(last_gate_mean)(down0, [gammas[0], gammas[1], jnp.zeros_like(gammas[2]), gammas[3]])
    assert float(jnp.max(jnp.abs(carried))) > 0 and float(jnp.max(jnp.abs(cut))) == 0.0

    def reference_gate_mean(down0):
        r = 0.0
        for i, lp in enumerate(layers):
            router = dict(lp["mlp"]["router"], **({"down": down0} if i == 0 else {}))
            r, _, gate, _ = ref._route(ref._rms_norm(x[0], lp["ln2"], 1e-5), r, router, lp["mlp"]["router_bias"], eps=1e-5)
        return jnp.mean(gate)

    with jax.default_matmul_precision("highest"):
        assert rel(carried, jax.grad(reference_gate_mean)(down0)) < 5 * RTOL


def test_the_mixing_alone_agrees_with_the_references_shifts_and_every_part_of_it_matters(tiny):
    cfg, params, _, _ = tiny
    a = jax.tree_util.tree_map(lambda v: v[1], params[ref.STACK]["cca"])
    latent = jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, 6, 16))
    from ray_tpu.ops.rotary import Rope

    q, k = cca.qk_mixing(latent, a, jnp.arange(SEQ), Rope(5e6, rotary_dim=8), 4)
    with jax.default_matmul_precision("highest"):
        want = [ref.qk_mixing(latent[i], a, n_heads=4, theta=5e6, rotary=8) for i in range(2)]
        assert rel(q, jnp.stack([w[0] for w in want])) < RTOL and rel(k, jnp.stack([w[1] for w in want])) < RTOL
        np.testing.assert_allclose(jnp.linalg.norm(q, axis=-1), 4.0, rtol=1e-4)  # sqrt(16): the rope keeps the norm
        np.testing.assert_allclose(jnp.linalg.norm(k, axis=-1), jnp.broadcast_to(jnp.abs(a["tau"]) * 4.0, k.shape[:3]), rtol=1e-4)
        # causal, zero history: position 0 reads itself alone, and nothing reads ahead
        later = latent.at[:, 5:].set(0.0)
        q5, k5 = cca.qk_mixing(later, a, jnp.arange(SEQ), None, 4)
        q0, k0 = cca.qk_mixing(latent, a, jnp.arange(SEQ), None, 4)
        np.testing.assert_allclose(q5[:, :5], q0[:, :5], rtol=1e-5, atol=1e-6)
        for wrong in ("no_qk_mean", "no_tau"):
            bad = ref.qk_mixing(latent[0], a, n_heads=4, theta=5e6, rotary=8, wrong=wrong)
            assert rel(k[0], bad[1]) > 0.05, wrong


def test_the_value_of_the_second_half_of_the_key_heads_is_read_one_position_back(tiny):
    cfg, params, _, _ = tiny
    lp = jax.tree_util.tree_map(lambda v: v[0], params[ref.STACK])
    x = jax.random.normal(jax.random.PRNGKey(8), (1, SEQ, cfg.d_model))
    seen = {}
    real = cca.dot_product_attention

    def spy(q, k, v, **kw):
        seen["v"] = v
        return real(q, k, v, **kw)

    cca.dot_product_attention = spy
    try:
        cca.mix(x, lp, jnp.arange(SEQ), cfg, None)
    finally:
        cca.dot_product_attention = real
    u = transformer.rms_norm(x, lp["ln1"], cfg.norm_eps)
    plain = jnp.einsum("bse,ehd->bshd", u, lp["cca"]["wv"])
    np.testing.assert_allclose(seen["v"][:, :, 0], plain[:, :, 0], rtol=1e-6)  # key head 0 reads t
    np.testing.assert_allclose(seen["v"][:, 1:, 1], plain[:, :-1, 1], rtol=1e-6)  # key head 1 reads t - 1
    assert float(jnp.max(jnp.abs(seen["v"][:, 0, 1]))) == 0.0  # and nothing before the start


# -- K = 1 through dispatch, the rungs and combine -----------------------------------------------------------------


ROUTINGS = {
    "an_expert_empty": lambda t: jnp.asarray([0, 1, 3] * (t // 3 + 1))[:t],  # expert 2 gets no row
    "one_expert_takes_all": lambda t: jnp.full((t,), 2),
}


@pytest.mark.parametrize("held", [False, True], ids=["all_experts", "as_a_share_over_the_rungs"])
@pytest.mark.parametrize("routing", ROUTINGS)
def test_top_1_goes_through_dispatch_experts_and_combine(tiny, monkeypatch, routing, held):
    cfg, params, _, _ = tiny
    monkeypatch.setattr(moe, "_ROW_TILE", 8)  # so that a share of these few rows has a ladder
    mlp = jax.tree_util.tree_map(lambda v: v[0], params[ref.STACK]["mlp"])
    tokens = jax.random.normal(jax.random.PRNGKey(9), (96, cfg.d_model))
    chosen = ROUTINGS[routing](96)
    gates = jax.random.uniform(jax.random.PRNGKey(10), (96,), minval=0.1, maxval=1.0)
    weights = [mlp[n] for n in moe.expert_leaves(cfg)]
    first = 0
    if held:  # expert 2 of the four, as one rank of four holds it: rungs of 48 and of all 96 rows
        first, weights = 2, [w[2:3] for w in weights]

    def run(tokens, gates, *weights):
        return moe._experts(tokens, chosen[:, None].astype(jnp.int32), gates[:, None], list(weights), 4, first if held else None)

    got, rows, moved = run(tokens, gates, *weights)
    onehot = jax.nn.one_hot(chosen, 4) * gates[:, None]
    with jax.default_matmul_precision("highest"):
        want = ref._expert_sum(tokens, onehot[:, first:first + len(weights[0])], *weights)
        assert rel(got, want) < RTOL if float(jnp.max(jnp.abs(want))) > 0 else float(jnp.max(jnp.abs(got))) == 0.0
        assert rows.tolist() == [int(jnp.sum(chosen == e)) for e in range(first, first + len(weights[0]))]
        # and its gradients, the gate's among them: the router learns through p_e alone
        g_got = jax.grad(lambda t, g, *w: jnp.sum(run(t, g, *w)[0] ** 2), argnums=(0, 1, 2))(tokens, gates, *weights)
        g_want = jax.grad(lambda t, g, *w: jnp.sum(ref._expert_sum(t, (jax.nn.one_hot(chosen, 4) * g[:, None])[:, first:first + len(w[0])], *w) ** 2),
                          argnums=(0, 1, 2))(tokens, gates, *weights)
    for a, b in zip(g_got, g_want):
        if float(jnp.max(jnp.abs(b))) > 0:
            assert rel(a, b) < 5 * RTOL
        else:
            assert float(jnp.max(jnp.abs(a))) == 0.0
    assert (float(moved) < 1.0) == (held and routing == "an_expert_empty")  # a share under its first rung moves its own rows alone


def test_the_experts_arithmetic_in_bf16_on_the_references_routing(tiny):
    """The routing TAKEN FROM THE REFERENCE: the experts' products in the model's bf16 against the float32 ones, where a
    flipped argmax can neither hide an error of theirs nor drown the comparison."""
    cfg, params, _, _ = tiny
    mlp = jax.tree_util.tree_map(lambda v: v[2], params[ref.STACK]["mlp"])
    h = jax.random.normal(jax.random.PRNGKey(11), (SEQ, cfg.d_model))
    prev = 0.3 * jax.random.normal(jax.random.PRNGKey(12), (SEQ, cfg.router_hidden))
    with jax.default_matmul_precision("highest"):
        want, state, chosen, gate = ref.expert_layer(h, prev, mlp, eps=1e-5)
        again, *_ = ref.expert_layer(h, prev, mlp, eps=1e-5, routing=(chosen, gate))
    assert rel(again, want) == 0.0
    bf16 = jnp.bfloat16
    got, rows, _ = moe._experts(h.astype(bf16), chosen[:, None].astype(jnp.int32), gate[:, None],
                                [mlp[n].astype(bf16) for n in moe.expert_leaves(cfg)], 4)
    assert got.dtype == bf16 and 0.001 < rel(got.astype(jnp.float32), want) < 0.012  # bf16's own rounding, no more
    # and the program's own router on the same rows makes the reference's state, choices and gate values
    own_state = moe.router_state(mlp, h[None], cfg, prev[None])
    idx, gates, stats = moe._route(mlp, h, cfg, own_state[0])
    assert rel(own_state[0], state) < RTOL and idx[:, 0].tolist() == chosen.tolist() and rel(gates[:, 0], gate) < RTOL
    assert float(stats["gate_mean"]) == pytest.approx(float(jnp.mean(gate)), rel=1e-4)


# -- the share tied to the model: eight slices of the tied table ------------------------------------------------------


@pytest.fixture(scope="module")
def sliced(tiny):
    """The uncut model (128 rows) and what chip k of eight holds of it: the same layers, rows 16k .. 16k + 15 of the table."""
    cfg, params, _, _ = tiny
    rows = CONFIG["vocab_size"] // SLICES
    slice_cfg = dataclasses.replace(cfg, vocab_size=rows)
    ctx = one_device_ctx(slice_cfg)
    trunk = jax.jit(lambda p, t: transformer.trunk(p, t, slice_cfg)[:2])
    loss = jax.jit(lambda p, b: ctx._loss(p, b)[0])
    uncut = jax.jit(lambda t: ref.forward(CONFIG, params, t))
    return rows, trunk, loss, uncut


@pytest.mark.parametrize("k", range(SLICES))
def test_a_vocabulary_slices_logits_and_loss_are_the_uncut_models_over_its_rows(tiny, sliced, k):
    cfg, params, _, _ = tiny
    rows, trunk, loss, uncut = sliced
    table = params["embed"]["tokens"]
    held = dict(params, embed={"tokens": table[k * rows:(k + 1) * rows]})
    local = jax.random.randint(jax.random.PRNGKey(20 + k), (2, SEQ + 1), 0, rows)  # ids over the slice, as the cell's traffic draws them
    tokens, targets = local[:, :-1], local[:, 1:]
    x, head = trunk(held, tokens)
    want = uncut(tokens + k * rows)  # the same sequences by their ids in the whole vocabulary
    # the eight slices' logits side by side, every chip's head on the stream all chips share, are the uncut model's
    side_by_side = jnp.concatenate([x @ table[j * rows:(j + 1) * rows].T for j in range(SLICES)], axis=-1)
    assert rel(side_by_side, want) < RTOL and rel(x @ head, want[..., k * rows:(k + 1) * rows]) < RTOL
    # and this chip's loss is the cross entropy over ITS rows: a sliced vocabulary is a smaller vocabulary
    logp = jax.nn.log_softmax(want[..., k * rows:(k + 1) * rows], axis=-1)
    over_the_slice = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
    assert abs(float(loss(held, {"tokens": tokens, "targets": targets})) - float(over_the_slice)) < RTOL * float(over_the_slice)


# -- remat, names, counters -----------------------------------------------------------------------------------------------


def test_qkv_attn_keeps_the_mixings_three_arrays_and_reruns_no_projection_and_no_convolution(tiny):
    from jax._src.ad_checkpoint import saved_residuals

    cfg = dataclasses.replace(tiny[0], dtype=jnp.bfloat16, remat=True, remat_policy="qkv_attn")
    x = jnp.zeros((2, SEQ, cfg.d_model), jnp.bfloat16)
    layer = jax.tree_util.tree_map(lambda a: a[0], {k: v for k, v in tiny[1][ref.STACK].items() if k != "mlp"})
    run = jax.checkpoint(lambda p, x: transformer.layer(MIXERS["cca"], x, p, jnp.arange(SEQ), cfg, None, ffn="none")[0],
                         policy=transformer._remat_policy(cfg))
    saved = sorted((aval.shape, str(aval.dtype)) for aval, why in saved_residuals(run, layer, x)
                   if "from the argument" not in why and "from a constant" not in why)
    # k, v [2 heads]; q and the core's output [4 heads]; the latent, the depthwise convolution's output and the normed sum [6 heads]
    assert saved == [((2, SEQ, 2, 16), "bfloat16")] * 2 + [((2, SEQ, 4, 16), "bfloat16")] * 2 + [((2, SEQ, 6, 16), "bfloat16")] * 3
    assert set(MIXERS["cca"].saved) == {cca.LATENT, cca.CONV1, cca.MIXED} <= set(transformer.saved_names(cfg))
    # and the backward runs no product of the projections or the convolutions again: as many as with every residual kept (no
    # checkpoint at all) but the core's own two, q k^T and p v, which off the chip every attention layer's recompute runs (the
    # XLA form keeps no probabilities; the flash kernels read the kept log-sum-exp)
    plain = lambda p, x: transformer.layer(MIXERS["cca"], x, p, jnp.arange(SEQ), cfg, None, ffn="none")[0]  # noqa: E731
    dots = lambda f: str(jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(f(p, x).astype(jnp.float32)), argnums=(0, 1)))(layer, x)).count(  # noqa: E731
        "dot_general")
    assert dots(run) - 2 == dots(plain) > 3 * 6  # three products (forward, two cotangents) for each of the six weights at least
    everything = jax.checkpoint(plain)  # no policy: the whole layer again, its projections and convolutions among it
    assert dots(everything) > dots(run)


def test_the_step_names_cca_proj_cca_mix_and_the_whole_router_and_counts_the_mean_gate(tiny):
    from conftest import without_file_locations

    cfg, params, tokens, targets = tiny
    ctx = one_device_ctx(cfg)
    state = jax.eval_shape(ctx._init, jax.random.PRNGKey(0))
    text = without_file_locations(ctx._train_step.lower(state, {"tokens": tokens, "targets": targets}).as_text(debug_info=True))
    for name in ("layer/attn_proj/cca/proj", "layer/attn_proj/cca/mix", "layer/attn_core", "layer/mlp/moe/router", "moe/experts"):
        assert name in text, name
    assert lm.GATE_MEAN in lm.STEP_COUNTERS and lm.GATE_MEAN == "moe_gate_mean"
    state, metrics = ctx.train_step(ctx.init_state(0), {"tokens": tokens, "targets": targets})
    assert 0.25 < float(metrics["moe_gate_mean"]) < 1.0 and "attn_causal_steps_copying_pct" in metrics


# -- the stored bias follows the load (`router_bias_update_rate`) -----------------------------------------------------


def test_the_rule_takes_the_rate_from_an_expert_over_the_mean_load_and_gives_it_to_one_under():
    share = jnp.asarray([[[0.5, 0.125, 0.25, 0.125]], [[0.25, 0.25, 0.25, 0.25]]])  # [layers, K = 1, E]; the second layer is even
    bias = jnp.asarray([[0.0, 0.5, -0.5, 0.25], [1.0, 2.0, 3.0, 4.0]], jnp.float32)
    got = moe.load_following_bias(bias, share, 0.125)
    np.testing.assert_array_equal(got, bias + 0.125 * jnp.asarray([[-1.0, 1.0, 0.0, 1.0], [0.0] * 4]))
    two = jnp.stack([share[0, 0], jnp.asarray([0.3, 0.3, 0.0, 0.4])])[None]  # K = 2: the load is the sum over the choices
    np.testing.assert_array_equal(moe.load_following_bias(bias[:1], two, 1.0) - bias[:1], [[-1.0, 1.0, 1.0, -1.0]])
    assert moe.load_following_bias(bias.astype(jnp.bfloat16), share, 0.125).dtype == jnp.bfloat16


def test_each_run_of_expert_layers_takes_its_rows_of_the_statistic_to_its_own_stack():
    """Two stacks, one of them in three runs around a dense layer: the rows of `choice_share` go where the layers are."""
    cfg = transformer.TransformerConfig.tiny(
        n_layers=5, n_experts=4, experts_per_token=2, moe_d_ff=32, router_activation="sigmoid", router_bias_update_rate=0.5,
        layer_types=("attention", "diff_attention", "attention", "attention", "attention"),
        ffn_types=("experts", "experts", "experts", "dense", "experts"))
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    busy = jnp.eye(4)[:, None, :] * jnp.ones((4, 2, 1)) / 2  # expert layer l sends every choice to expert l
    got = transformer.biases_following_load(params, params, busy, cfg)
    step = lambda l: np.where(np.arange(4) == l, -0.5, 0.5)  # noqa: E731
    np.testing.assert_array_equal(got["layers_experts"]["mlp"]["router_bias"], [step(0), step(2), step(3)])
    np.testing.assert_array_equal(got["diff_layers"]["mlp"]["router_bias"], [step(1)])
    rest = jax.tree_util.tree_map(lambda a, b: bool(jnp.array_equal(a, b)), got, params)
    rest["layers_experts"]["mlp"].pop("router_bias"), rest["diff_layers"]["mlp"].pop("router_bias")
    assert all(jax.tree_util.tree_leaves(rest))


@pytest.fixture(scope="module")
def following(tiny):
    """The tiny model's job with the rule on, every token of its seed on expert 0: (context, state, batch)."""
    cfg, params, tokens, targets = tiny
    ctx = one_device_ctx(dataclasses.replace(cfg, router_bias_update_rate=0.03125))
    state = ctx.init_state(0)
    stack = state["params"][ref.STACK]
    forced = jnp.zeros_like(stack["mlp"]["router_bias"]).at[:, 0].set(0.5)  # over the spread of the four scores: the choice is the bias's
    state["params"][ref.STACK] = {**stack, "mlp": {**stack["mlp"], "router_bias": forced}}
    return ctx, state, {"tokens": tokens, "targets": targets}


def test_a_step_moves_the_stored_bias_by_its_load_and_the_optimizer_does_not_touch_it(tiny, following):
    ctx, state, batch = following
    before = np.asarray(state["params"][ref.STACK]["mlp"]["router_bias"])
    assert before.dtype == np.float32  # an "mlp" router's, whatever the parameters' dtype
    state, metrics = ctx.train_step(jax.tree_util.tree_map(jnp.copy, state), batch)
    assert float(metrics["moe_load_max_over_mean"]) == 4.0 and float(metrics["moe_experts_in_use"]) < 2.0 and "moe_choice_share" not in metrics
    after = np.asarray(state["params"][ref.STACK]["mlp"]["router_bias"])
    np.testing.assert_array_equal(after - before, np.tile([-0.03125, 0.03125, 0.03125, 0.03125], (LAYERS, 1)))  # and no decay of the 0.5
    plain = one_device_ctx(tiny[0])  # the same job without the rule: the bias is a leaf that nothing moves
    state, metrics = plain.train_step(plain.init_state(0), batch)
    assert float(jnp.max(jnp.abs(state["params"][ref.STACK]["mlp"]["router_bias"]))) == 0.0 and "moe_experts_in_use" not in metrics


def test_the_rule_spreads_a_collapsed_router_over_the_experts(following):
    ctx, state, batch = following
    state, loads = jax.tree_util.tree_map(jnp.copy, state), []
    for _ in range(24):
        state, metrics = ctx.train_step(state, batch)
        loads.append(float(metrics["moe_load_max_over_mean"]))
    assert float(metrics["moe_experts_in_use"]) == 4.0 and "moe_experts_in_use" in lm.STEP_COUNTERS
    # the fullest expert of the worst of four layers, 128 tokens each: 4.0 is one expert with every token, 1.0 an even load
    assert loads[0] == 4.0 and max(loads[-4:]) < 2.5, loads


REFUSED = {
    "a_window": (dict(layer_windows=(None, 8, None, None)), "layer_windows at a cca layer"),
    "an_odd_number_of_key_heads": (dict(n_heads=3, n_kv_heads=1), "an even number of key heads"),
    "a_learned_qk_norm": (dict(qk_norm=True), "no qk_norm"),
    "an_mlp_router_without_a_width": (dict(router_hidden=0), "router_hidden >= 1"),
    "an_unknown_router": (dict(router_kind="tree"), "router_kind is 'linear' or 'mlp'"),
    "residual_scaling_beside_another_kind": (dict(layer_types=("cca", "attention", "cca", "cca")), "residual_scaling is every layer's"),
    "one_tap_count": (dict(cca_taps=(2,)), "two counts"),
    "a_bias_rule_without_a_stored_bias": (dict(router_kind="linear", router_hidden=0, router_bias_update_rate=0.001), "moves a STORED router_bias"),
    "a_negative_bias_rate": (dict(router_bias_update_rate=-0.001), "by a rate >= 0"),
}


@pytest.mark.parametrize("case", REFUSED)
def test_the_configuration_refuses(tiny, case):
    change, match = REFUSED[case]
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(tiny[0], **change)
