"""Phi-4-mini-flash-reasoning (SambaY) through the program (PERF.md section 4,
PR 40): Mamba-1 layers, differential attention with a window, one full layer
whose K and V the cross layers read, Gated Memory Units reading one Mamba-1
layer's scan output; LayerNorm with bias.  Held to
`benchmarks/lib/reference_sambay.py` (token-by-token recurrence, dense masks,
its own pairing) at small widths on the CPU, seeded weights; on the chip the
same comparison decides the cell's `correct` at the published widths."""

import dataclasses
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

from benchmarks.builders import sambay_decoder as builder  # noqa: E402
from benchmarks.lib import reference_sambay as ref  # noqa: E402
from ray_tpu.models import LMTrainContext, TransformerConfig  # noqa: E402
from ray_tpu.models import transformer  # noqa: E402
from ray_tpu.models.mixers import s6  # noqa: E402
from ray_tpu.ops import attention as attn_ops  # noqa: E402
from ray_tpu.ops.pallas import flash_attention as fa  # noqa: E402
from ray_tpu.ops.selective_scan import selective_scan, selective_scan_recurrent  # noqa: E402
from ray_tpu.parallel import MeshSpec, build_mesh  # noqa: E402

SEQ = 64
WINDOW = 16
CUT = [0, 1, 2, 3, 16, 17, 18, 19, 20, 21]  # the published indices of the benchmark's ten layers
# The configuration file's keys at a small size: heads of 64 (the reference's constant), 4 q / 2 kv heads at d 256.
CONFIG = {
    "hidden_size": 256, "intermediate_size": 128, "vocab_size": 128, "num_hidden_layers": len(CUT),
    "num_attention_heads": 40, "num_key_value_heads": 20, "layer_norm_eps": 1e-5, "sliding_window": WINDOW,
    "hidden_act": "silu", "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False, "mb_per_layer": 2,
    "embd_pdrop": 0, "resid_pdrop": 0, "layer_indices": CUT,
    "train": {"compute_dtype": "float32", "param_dtype": "float32", "remat_policy": None},
}
# float32 against float32 under precision "highest": what the orders of summation cost (the scan's
# associative levels against the token-by-token chain, a blocked softmax against a dense one).
RTOL = 2e-4


def published(indices=CUT, **kw):
    return dict(CONFIG, layer_indices=list(indices), num_hidden_layers=len(indices), **kw)


def config_of(config=CONFIG, **kw):
    base = builder.model_kwargs(config, SEQ)
    base.update(dtype=jnp.float32, param_dtype=jnp.float32, remat=False)
    base.update(kw)
    return TransformerConfig(**base)


def redrawn(params, seed=7):
    """Every leaf that the initial values leave at 0 or 1 (biases, norm
    scales, D) moved off it, so that a comparison sees them."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out = []
    for (path, leaf), key in zip(leaves, keys):
        name = str(path[-1].key)
        if name in ("A_log", "dt_bias") or leaf.ndim >= 3 or name == "tokens":
            out.append(leaf)
        else:
            out.append(leaf + 0.1 * jax.random.normal(key, leaf.shape, leaf.dtype))
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(params), out)


forward = jax.jit(transformer.forward, static_argnums=2)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / (np.mean(b ** 2) + 1e-300)))


def model(indices=CUT, **kw):
    config = published(indices)
    cfg = config_of(config, **kw)
    params = jax.jit(lambda key: redrawn(transformer.init_params(cfg, key)))(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, SEQ), 0, cfg.vocab_size)
    return dict(config=config, cfg=cfg, params=params, tokens=tokens, targets=jnp.roll(tokens, -1, axis=1))


@pytest.fixture(scope="module", autouse=True)
def chunk_of_16():
    """The program's chunk for this module: S = 64 crosses three boundaries."""
    from ray_tpu.ops import selective_scan as op

    saved, op.CHUNK = op.CHUNK, 16
    yield
    op.CHUNK = saved


@pytest.fixture(scope="module")
def cut():
    return model()


@pytest.fixture(scope="module")
def middle():
    """The memory layer, the K/V layer and one reader of each."""
    return model([16, 17, 18, 19])


# -- the stack ------------------------------------------------------------------------------


def test_the_stack_is_ten_runs_over_four_parameter_stacks(cut):
    cfg = cut["cfg"]
    assert cfg.layer_types == ("s6", "diff_attention", "s6", "diff_attention", "s6", "diff_attention",
                               "gmu", "diff_cross", "gmu", "diff_cross")
    assert cfg.layer_windows == (None, WINDOW, None, WINDOW) + (None,) * 6
    assert (cfg.s6_memory_layer, cfg.kv_source_layer) == (4, 5)
    assert len(cfg.layer_runs()) == 10 and cfg.run_starts() == tuple(range(10))
    assert {k: v[2] for k, v in cfg.stacks().items()} == {
        "s6_layers": 3, "diff_layers": 3, "gmu_layers": 2, "cross_layers": 2}
    assert cfg.num_params() == builder.total_params(cut["config"])


def test_runs_of_one_variant_share_a_body():
    """Layers that differ in nothing static run as ONE scan: the window and
    what a layer hands on split a run, the published index does not."""
    cfg = config_of(published([1, 3, 5, 17, 19, 21]))
    assert cfg.layer_runs() == (("diff_attention", "dense", 0, 3), ("diff_attention", "dense", 3, 1),
                                ("diff_cross", "dense", 0, 2))


def test_the_published_model_counts_3_852_billion_parameters():
    with open(os.path.join(ROOT, "benchmarks/configs/phi-4-mini-flash-vp4-1chip.json")) as f:
        config = json.load(f)
    assert builder.total_params(config, uncut=True) == pytest.approx(3.852e9, rel=0.005)
    assert builder.total_params(config) == pytest.approx(1.1759e9, rel=0.001)
    kw = builder.model_kwargs(config, 8192)
    kw.update(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    assert TransformerConfig(**kw).num_params() == builder.total_params(config)


def test_lambda_init_follows_the_published_index(cut):
    want = [0.8 - 0.6 * np.exp(-0.3 * l) for l in CUT]
    assert cut["cfg"].lambda_inits() == pytest.approx(want)
    assert [ref.lambda_init(l) for l in CUT] == pytest.approx(want)
    assert config_of(published([1, 17])).lambda_inits() != config_of(published([1, 3])).lambda_inits()


# -- the model against the reference --------------------------------------------------------


@pytest.mark.parametrize("indices", [[17], [1], [16, 18], [17, 19], CUT],
                         ids=["pairing-full", "pairing-window", "gmu", "cross", "cut-model"])
def test_logits_agree_with_the_reference(indices):
    m = model(indices)
    got = forward(m["params"], m["tokens"], m["cfg"])
    want = ref.logits(m["config"], m["params"], m["tokens"], last=SEQ)
    assert rel(got, want) < RTOL


def test_gradients_agree_through_the_remat_policies(middle):
    def grads_of(cfg):
        return jax.jit(jax.grad(lambda p: jnp.mean(jnp.square(transformer.forward(p, middle["tokens"], cfg)))))(
            middle["params"])

    want = jax.tree_util.tree_leaves(grads_of(middle["cfg"]))
    for policy in (None, "qkv_attn"):
        got = grads_of(dataclasses.replace(middle["cfg"], remat=True, remat_policy=policy))
        assert max(rel(g, w) for g, w in zip(jax.tree_util.tree_leaves(got), want)) < 1e-5
    with pytest.raises(ValueError, match="s6_in_proj.*gmu_gate.*diff_mixed"):
        transformer._remat_policy(dataclasses.replace(middle["cfg"], remat_policy="all"))


@pytest.fixture(scope="module")
def loss_and_grads(cut):
    ctx = LMTrainContext(dataclasses.replace(cut["cfg"], remat=True, remat_policy="qkv_attn"),
                         mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), strategy="dp")
    batch = {"tokens": cut["tokens"], "targets": cut["targets"]}
    with ctx.mesh:
        (loss, _), grads = jax.jit(jax.value_and_grad(ctx._loss, has_aux=True))(cut["params"], batch)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.objective(cut["config"], p, cut["tokens"], cut["targets"])))(cut["params"])
    return loss, grads, want, want_grads


def test_loss_agrees_with_the_reference(loss_and_grads):
    loss, _, want, _ = loss_and_grads
    assert float(loss) == pytest.approx(float(want), rel=1e-5)


def test_gradients_agree_with_the_reference_leaf_by_leaf(loss_and_grads):
    """Every leaf, through the program's remat policy.  The memory layer's
    (index 16: the last of `s6_layers`) and the K/V layer's (17: the last of
    `diff_layers`) gradients are sums over their readers, two GMUs and two
    cross layers beside their own layer: a cotangent dropped on the way back
    shows in these leaves."""
    _, grads, _, want = loss_and_grads
    flat, flat_want = (dict(jax.tree_util.tree_flatten_with_path(t)[0]) for t in (grads, want))
    assert flat.keys() == flat_want.keys()
    worst = max(flat, key=lambda path: rel(flat[path], flat_want[path]))
    assert rel(flat[worst], flat_want[worst]) < 10 * RTOL, jax.tree_util.keystr(worst)
    for stack, mixer, leaf in (("s6_layers", "s6", "in_proj"), ("diff_layers", "diff", "wqkv")):
        assert rel(grads[stack][mixer][leaf][-1], want[stack][mixer][leaf][-1]) < 10 * RTOL


def test_the_cut_model_with_the_scan_kernel_interpreted_is_the_plain_run(cut, loss_and_grads, monkeypatch, lowered_for_tpu_on_the_cpu):
    """The ten layers as a step lowered for TPU has their scans (PRs 42, 51):
    both kernels interpreted, under shard_map on the context's mesh, the
    forward's in the forward and in the layer's recompute; the backward's
    starts each chunk from the states the forward's wrote.  Loss and every
    leaf's gradient are the plain run's.  (No other dispatch of the model takes
    its kernel at 64 positions.)"""
    from ray_tpu.ops.pallas import selective_scan as kernels

    calls, backward_calls, real, real_backward = [], [], kernels.s6_scan_fwd, kernels.s6_scan_bwd
    monkeypatch.setattr(kernels, "_BLOCK_S", SEQ)
    monkeypatch.setattr(kernels, "s6_scan_fwd", lambda *a, **kw: calls.append(kw) or real(*a, interpret=True, **kw))
    monkeypatch.setattr(kernels, "s6_scan_bwd",
                        lambda *a, **kw: backward_calls.append(kw) or real_backward(*a, interpret=True, **kw))
    ctx = LMTrainContext(dataclasses.replace(cut["cfg"], remat=True, remat_policy="qkv_attn"),
                         mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), strategy="dp")
    with ctx.mesh:
        (loss, _), grads = jax.jit(jax.value_and_grad(ctx._loss, has_aux=True))(
            cut["params"], {"tokens": cut["tokens"], "targets": cut["targets"]})
    assert len(calls) >= 6 and all(kw == {"chunk": 16} for kw in calls)  # three layers, forward and recompute
    assert len(backward_calls) >= 3 and all(kw == {"chunk": 16} for kw in backward_calls)
    plain_loss, plain = loss_and_grads[:2]
    assert float(loss) == pytest.approx(float(plain_loss), rel=1e-6)
    flat, flat_plain = (dict(jax.tree_util.tree_flatten_with_path(t)[0]) for t in (grads, plain))
    worst = max(flat, key=lambda path: rel(flat[path], flat_plain[path]))
    assert rel(flat[worst], flat_plain[worst]) < 1e-5, jax.tree_util.keystr(worst)


def test_the_readers_cotangents_reach_the_layers_that_hand_on(middle):
    cut = middle
    """With the GMUs' and the cross layers' output weights at zero the
    memory's and the K/V's readers send nothing back: the gradient of the
    weights that make only M (`x_proj` of index 16) and only K/V changes."""
    def grads_of(params):
        return jax.jit(jax.grad(lambda p: jnp.mean(jnp.square(transformer.forward(p, cut["tokens"], cut["cfg"])))))(params)

    whole = grads_of(cut["params"])
    cutoff = jax.tree_util.tree_map(lambda a: a, cut["params"])
    cutoff["gmu_layers"]["gmu"]["w2"] = jnp.zeros_like(cutoff["gmu_layers"]["gmu"]["w2"])
    cutoff["cross_layers"]["diff"]["wo"] = jnp.zeros_like(cutoff["cross_layers"]["diff"]["wo"])
    alone = grads_of(cutoff)
    assert rel(whole["s6_layers"]["s6"]["x_proj"][-1], alone["s6_layers"]["s6"]["x_proj"][-1]) > 1e-2
    assert rel(whole["diff_layers"]["diff"]["bqkv"][-1], alone["diff_layers"]["diff"]["bqkv"][-1]) > 1e-2


def test_the_model_comparison_notices_the_scan_in_bfloat16(middle, monkeypatch):
    cut = middle
    """`dt * A`, its exponentials and the states are stated float32: rounding
    the scan's inputs to bf16 moves the logits past RTOL."""
    real = s6.selective_scan
    monkeypatch.setattr(s6, "selective_scan", lambda x, dt, A, *rest: real(
        x, dt.astype(jnp.bfloat16).astype(jnp.float32), A.astype(jnp.bfloat16).astype(jnp.float32), *rest))
    got = transformer.forward(cut["params"], cut["tokens"], cut["cfg"])
    want = ref.logits(cut["config"], cut["params"], cut["tokens"], last=SEQ)
    assert rel(got, want) > RTOL  # 3.8e-4, where the program as it is reads under 2e-5
    monkeypatch.undo()
    assert rel(transformer.forward(cut["params"], cut["tokens"], cut["cfg"]), want) < RTOL / 10


def test_a_train_step_learns_and_notes_the_window_counter():
    from ray_tpu.train import run_record

    cut = model([1, 16, 17, 18, 19])
    cfg = dataclasses.replace(cut["cfg"], remat=True, remat_policy="qkv_attn")
    ctx = LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), strategy="dp")
    state = ctx.init_state(seed=0)
    batch = {"tokens": np.asarray(cut["tokens"]), "targets": np.asarray(cut["targets"])}
    losses = []
    for _ in range(4):
        state, metrics = ctx.train_step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert run_record.drain_step_counters()["attn_window_tiles_visited_pct"] == pytest.approx(
        fa.window_tiles_visited_pct(SEQ, WINDOW))


# -- what the configuration refuses -----------------------------------------------------------


@pytest.mark.parametrize("kw, match", [
    (dict(layer_types=("gmu", "s6"), s6_memory_layer=1), "a gmu layer needs s6_memory_layer"),
    (dict(layer_types=("s6", "gmu"), s6_memory_layer=None), "a gmu layer needs s6_memory_layer"),
    (dict(layer_types=("diff_attention", "diff_cross"), kv_source_layer=1), "is no diff_attention layer"),
    (dict(layer_types=("s6", "s6"), s6_inner=0), "needs s6_inner"),
    (dict(layer_types=("diff_attention",) * 2, n_heads=3, n_kv_heads=3), "pairs adjacent heads"),
    (dict(layer_types=("attention",) * 2, attn_bias=True), "attn_bias is the differential kinds'"),
    (dict(norm_kind="batch"), "unknown norm_kind"),
    (dict(layer_windows=(8,)), "layer_windows needs n_layers"),
], ids=["gmu-before-memory", "gmu-without-memory", "cross-source-not-attention", "s6-sizes", "odd-heads",
        "bias-on-plain-attention", "norm-kind", "windows-length"])
def test_the_configuration_refuses(kw, match):
    base = dict(n_layers=2, s6_inner=32, n_heads=4, n_kv_heads=2)
    base.update(kw)
    with pytest.raises(ValueError, match=match):
        TransformerConfig.tiny(**base)


@pytest.mark.parametrize("strategy, match", [("tp", "strategy 'tp'"), ("pp", "strategy 'pp'")])
def test_tensor_and_pipeline_parallelism_refuse_the_differential_kinds_by_name(cut, strategy, match):
    spec = MeshSpec(tensor=2) if strategy == "tp" else MeshSpec(pipeline=2)
    with pytest.raises(ValueError, match=match):  # 'pp' as the context is built, 'tp' where the heads are placed
        ctx = LMTrainContext(cut["cfg"], mesh=build_mesh(spec, devices=jax.devices()[:2]), strategy=strategy)
        jax.eval_shape(ctx._loss, cut["params"], {"tokens": cut["tokens"], "targets": cut["targets"]})


def test_a_windowed_attention_layer_runs_and_the_ring_refuses_it():
    """`layer_windows` reaches the plain attention kind too."""
    cfg = TransformerConfig.tiny(n_layers=2, layer_windows=(8, None))
    assert len(cfg.layer_runs()) == 2
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
    windowed = transformer.forward(params, tokens, cfg)
    full = transformer.forward(params, tokens, dataclasses.replace(cfg, layer_windows=None))
    assert rel(windowed[:, :8], full[:, :8]) < 1e-5 and rel(windowed[:, 8:], full[:, 8:]) > 1e-3
    with pytest.raises(ValueError, match="ring attention takes no window"):  # when the context is built, not at trace time
        LMTrainContext(cfg, mesh=build_mesh(MeshSpec(seq=2), devices=jax.devices()[:2]), strategy="sp")


# -- the chunked selective scan against the token-by-token one --------------------------------


def scan_inputs(seed, step, b=2, s=192, c=24, n=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, s, c))
    dt = step * jax.nn.softplus(jax.random.normal(ks[1], (b, s, c)))
    A = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (c, n))
    return x, dt, A, jax.random.normal(ks[2], (b, s, n)), jax.random.normal(ks[3], (b, s, n)), jnp.ones((c,))


SCAN_TOL = 1e-5  # float32 both sides: log2(chunk) levels of products against a chain of them


@pytest.mark.parametrize("step", [1e-3, 0.1, 4.0], ids=["decay-near-1", "decay-mid", "decay-overflows-a-quotient"])
def test_chunked_scan_is_the_recurrence_forward_and_gradient(step):
    """At step 4.0 `dt * |A|` reaches ~80 a position: the running sum passes
    float32's -88 inside two positions, where a quotient of cumulative
    exponentials is 0 / 0; products of factors in (0, 1] are exact zeros."""
    args = scan_inputs(0, step)
    assert rel(selective_scan(*args, chunk=64), selective_scan_recurrent(*args)[0]) < SCAN_TOL
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    got = jax.grad(lambda *a: jnp.sum(selective_scan(*a, chunk=64) * weight), argnums=range(6))(*args)
    want = jax.grad(lambda *a: jnp.sum(selective_scan_recurrent(*a)[0] * weight), argnums=range(6))(*args)
    for g, w in zip(got, want):
        assert np.all(np.isfinite(np.asarray(g))) and rel(g, w) < 10 * SCAN_TOL


def test_chunked_scan_admits_only_whole_chunks_and_rounds_once():
    args = scan_inputs(1, 0.1)
    with pytest.raises(ValueError, match="not a multiple of the chunk"):
        selective_scan(*args, chunk=80)
    half = selective_scan(args[0].astype(jnp.bfloat16), *args[1:], chunk=64)
    assert half.dtype == jnp.bfloat16


def test_the_scan_comparison_notices_decays_in_bfloat16():
    x, dt, A, B, C, D = scan_inputs(2, 0.1)
    rounded = selective_scan(x, dt.astype(jnp.bfloat16).astype(jnp.float32), A, B, C, D, chunk=64)
    assert rel(rounded, selective_scan_recurrent(x, dt, A, B, C, D)[0]) > 10 * SCAN_TOL


# -- windowed attention against a dense masked softmax ---------------------------------------


def dense_windowed(q, k, v, window):
    """softmax(q k^T / sqrt(d) + mask) v with the mask written out: key t is
    seen by query i iff i - window < t <= i."""
    s = q.shape[1]
    i, t = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = (t <= i) if window is None else (t <= i) & (t > i - window)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attention_inputs(s=512, h=2, d=64, dv=128):
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    return (jax.random.normal(ks[0], (1, s, h, d)), jax.random.normal(ks[1], (1, s, h, d)),
            jax.random.normal(ks[2], (1, s, h, dv)), jax.random.normal(ks[3], (1, s, h, dv)))


FORMS = {
    "reference": lambda q, k, v, w: attn_ops.reference_attention(q, k, v, window=w),
    "blockwise": lambda q, k, v, w: attn_ops.blockwise_attention(q, k, v, window=w, block_size=128),
    "pallas": lambda q, k, v, w: fa.flash_attention(q, k, v, window=w),
    "dispatch": lambda q, k, v, w: attn_ops.dot_product_attention(q, k, v, window=w, impl="pallas"),
}


@pytest.mark.parametrize("window", [128, 200, 64], ids=["window-128", "window-200", "window-64"])
@pytest.mark.parametrize("form", list(FORMS))
def test_windowed_attention_is_the_dense_masked_softmax(form, window):
    """At S = 512 a window of 128 gives the kernels tiles of 128: a query tile
    sees two of the four key tiles, the others are never visited; 200 is no
    multiple of a tile, 64 is half of one.  Values and, for the kernels,
    gradients (interpret mode: the kernel bodies a TPU lowering compiles)."""
    q, k, v, do = attention_inputs()
    assert rel(FORMS[form](q, k, v, window), dense_windowed(q, k, v, window)) < 1e-5
    if form == "pallas":
        got = jax.grad(lambda *a: jnp.sum(FORMS[form](*a, window) * do), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: jnp.sum(dense_windowed(*a, window) * do), argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            assert rel(g, w) < 1e-5


def _pallas_grids(jaxpr):
    """name -> grid of every `pallas_call` in a jaxpr, sub-jaxprs included."""
    found = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = tuple(eqn.params["grid_mapping"].grid)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.update(_pallas_grids(sub))
    return found


def test_a_windowed_call_leaves_tiles_out_of_its_grids_and_no_window_is_todays_call():
    q, k, v, do = attention_inputs(s=1024)

    def grids(**kw):
        grad = jax.grad(lambda *a: jnp.sum(fa.flash_attention(*a, **kw) * do), argnums=(0, 1, 2))
        return _pallas_grids(jax.make_jaxpr(grad)(q, k, v).jaxpr)

    # tiles of 128 at a window of 128: 8 x 8 tile pairs, of which a query tile sees 2 (the first 1), a key tile 2 (the last 1):
    # since PR 70 the innermost axis walks those 15 pairs and no other step (it was 8 x 2, one step of it predicated off)
    assert grids(window=128) == {"flash_fwd": (1, 2, 15), "flash_bwd_dq": (1, 2, 15), "flash_bwd_dkv": (1, 2, 15)}
    assert grids() == {"flash_fwd": (1, 2, 1), "flash_bwd_dq": (1, 2, 2), "flash_bwd_dkv": (1, 2, 2)}
    # `window=None` traces nothing new: the jaxpr of the call, text for text
    text = lambda **kw: str(jax.make_jaxpr(lambda *a: fa.flash_attention(*a, **kw))(q, k, v))  # noqa: E731
    assert text() == text(window=None)
    # and a window that spans the sequence is the causal call's result
    assert rel(fa.flash_attention(q, k, v, window=1024), fa.flash_attention(q, k, v)) < 1e-6


def test_window_tiles_visited_counts_tile_areas():
    # 16 query tiles of 512 see 1 + 15 * 2 key tiles of 512; the causal call sees 36 tile pairs of 1024
    assert fa.window_tiles_visited_pct(8192, 512) == pytest.approx(100 * 31 * 512 ** 2 / (36 * 1024 ** 2))
    assert fa.window_tiles_visited_pct(1100, 512) is None  # no tile divides it: the kernels do not run
    with pytest.raises(ValueError, match="a window needs causal"):
        fa.flash_attention(*attention_inputs()[:3], window=128, causal=False)
