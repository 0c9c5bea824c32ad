"""The names the train step gives its own work (PERF.md section 3): model
scopes and kernel names in the lowered step, the program's host spans on the
profiler's clock, and a compile-cache key that tells two builds apart when
only those names differ."""

import ast
import collections
import dataclasses
import functools
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import LMTrainContext, TransformerConfig
from ray_tpu.parallel import MeshSpec, build_mesh
from ray_tpu.util import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPES = ("embed", "layers", "layer/attn_proj", "layer/attn_core", "layer/mlp", "final_norm", "lm_head",
          "loss", "optimizer")
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
VOCAB = 384  # unlike every other width below, so a `[batch, 128, VOCAB]` type is the logits' or their cotangent's
CFG = TransformerConfig.tiny(
    n_heads=2, n_kv_heads=1, d_model=256, d_ff=256, max_seq_len=128,
    remat=True, remat_policy="qkv_attn", vocab_size=VOCAB,
)


@pytest.fixture(scope="module", params=[(1, MeshSpec(data=1), "dp"), (4, MeshSpec(data=1, fsdp=4), "fsdp")],
                ids=["dp1", "fsdp4"])
def lowered_for_tpu(request):
    """The step cross-lowered for TPU from the virtual CPU devices, with its
    locations printed: the op names a device trace will carry."""
    n_devices, spec, strategy = request.param
    mesh = build_mesh(spec, devices=jax.devices()[:n_devices])
    ctx = LMTrainContext(CFG, mesh=mesh, strategy=strategy)
    state = jax.eval_shape(ctx._init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((8, 128), jnp.int32)
    traced = ctx._train_step.trace(state, {"tokens": toks, "targets": toks})
    return traced.lower(lowering_platforms=("tpu",)).as_text(debug_info=True)


@pytest.mark.parametrize("scope", SCOPES)
def test_lowered_step_names_every_model_scope(lowered_for_tpu, scope):
    assert f"/{scope}/" in lowered_for_tpu or f"({scope})" in lowered_for_tpu


@pytest.mark.parametrize("kernel", KERNELS)
def test_lowered_step_names_the_three_kernels(lowered_for_tpu, kernel):
    # (under shard_map the body is a function of its own, and MLIR locations nest per function:
    # `layer/attn_core` is then on the caller's line, not the kernel's)
    assert any(f"/{kernel}/" in line and "pallas_call" in line for line in lowered_for_tpu.splitlines())
    assert "layer/attn_core/" in lowered_for_tpu


def test_recomputed_ops_carry_the_scope_under_the_remat_marker(lowered_for_tpu):
    """MLIR locations nest per function, so the whole path (`transpose(jvp())/while/...`)
    is on no one line here; the recorded trace in benchmarks/tests holds those."""
    assert '"checkpoint/rematted_computation/layer/mlp/' in lowered_for_tpu
    assert '"layer/mlp/' in lowered_for_tpu and "transpose(" in lowered_for_tpu


# -- the expert layer's names (PERF.md section 3, PR 26) -------------------------------------

MOE_SCOPES = ("moe/router", "moe/dispatch", "moe/experts", "moe/combine")
MOE_KERNELS = ("moe_gmm", "moe_tgmm")
MOE_CFG = TransformerConfig.tiny(
    n_heads=2, n_kv_heads=2, d_model=256, d_ff=128, max_seq_len=128, remat=True, remat_policy="qkv_attn",
    n_experts=8, experts_per_token=2, qk_norm=True, router_aux_loss_coef=0.01, router_z_loss_coef=0.001,
    vocab_size=VOCAB,
)


@pytest.fixture(scope="module", params=[(1, MeshSpec(data=1), "dp"), (4, MeshSpec(data=1, expert=4), "ep")],
                ids=["dp1", "ep4"])
def moe_lowered_for_tpu(request):
    """The expert model's step cross-lowered for TPU: 2,048 token-expert rows
    of width 256 -> 128, shapes the grouped-matmul kernels take."""
    n_devices, spec, strategy = request.param
    mesh = build_mesh(spec, devices=jax.devices()[:n_devices])
    ctx = LMTrainContext(MOE_CFG, mesh=mesh, strategy=strategy)
    state = jax.eval_shape(ctx._init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((8, 128), jnp.int32)
    traced = ctx._train_step.trace(state, {"tokens": toks, "targets": toks})
    return traced.lower(lowering_platforms=("tpu",)).as_text(debug_info=True)


@pytest.mark.parametrize("scope", MOE_SCOPES)
def test_lowered_expert_step_names_the_four_regions_inside_the_mlp_scope(moe_lowered_for_tpu, scope):
    """Nested INSIDE `layer/mlp`, so `mlp_time_pct` stays the whole FFN block
    and `benchmarks/lib/trace_moe.py` splits it."""
    # (under shard_map the body is a function of its own, and MLIR locations nest per function:
    # there `layer/mlp` is on the caller's line and the region's name starts the body's)
    assert f"layer/mlp/{scope}/" in moe_lowered_for_tpu or (
        "shard_map" in moe_lowered_for_tpu and f'"{scope}/' in moe_lowered_for_tpu
        and "layer/mlp/shard_map" in moe_lowered_for_tpu)


@pytest.mark.parametrize("kernel", MOE_KERNELS)
def test_lowered_expert_step_names_the_grouped_matmul_kernels(moe_lowered_for_tpu, kernel):
    calls = [line for line in moe_lowered_for_tpu.splitlines() if "@tpu_custom_call" in line
             and f'kernel_name = "{kernel}"' in line]
    assert calls
    assert any(f"/{kernel}/" in line and "pallas_call" in line for line in moe_lowered_for_tpu.splitlines())


def test_lowered_expert_step_recomputes_two_of_the_three_grouped_matmuls(moe_lowered_for_tpu):
    """Per layer `moe_gmm` runs 3 times forward, 3 times for the rows'
    gradients and, under `qkv_attn`, twice in the recompute: gate and up.
    The gate value scales the rows that enter `w_down` (`models/moe.py`), so
    no residual needs the down projection again (9 with the gate multiply
    behind it).  With 3 `moe_tgmm` and the 3 flash kernels: the 14
    `tpu_custom_calls` of `olmoe-1chip.seq4k`'s HLO facts (PERF.md section 6, PR 29)."""
    kernels = [line for line in moe_lowered_for_tpu.splitlines() if "@tpu_custom_call" in line]
    # a rank of the `expert` axis sizes its buffers by the rows it holds (PR 48): 2 of 8 experts, 2,048
    # assignments, rungs of 1,024 and 2,048 rows, each with the layer's eleven kernels
    rungs = 2 if "shard_map" in moe_lowered_for_tpu else 1
    assert sum('kernel_name = "moe_gmm"' in line for line in kernels) == 8 * rungs
    assert sum('kernel_name = "moe_tgmm"' in line for line in kernels) == 3 * rungs
    assert len(kernels) == 11 * rungs + 3


def test_lowered_expert_step_keeps_the_flash_kernels_and_the_loss_scope(moe_lowered_for_tpu):
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert f'kernel_name = "{name}"' in moe_lowered_for_tpu
    assert "/loss/" in moe_lowered_for_tpu or "(loss)" in moe_lowered_for_tpu


# -- the Mamba-2 mixer's names (PERF.md section 3, PR 30) -----------------------------------

SSM_SCOPES = {"ssm/proj": "layer/attn_proj", "ssm/conv": "layer/attn_proj", "ssm/scan": "layer/attn_core"}
HYBRID_CFG = TransformerConfig.tiny(
    n_layers=4, n_heads=4, n_kv_heads=1, d_model=256, d_ff=256, max_seq_len=128, remat=True, remat_policy="qkv_attn",
    tie_embeddings=True, rope_theta=None, layer_types=("mamba", "mamba", "attention", "mamba"),
    ssm_heads=8, ssm_head_dim=64, ssm_state=128, embedding_multiplier=12.0, residual_multiplier=0.22,
    logits_scaling=8.0, attention_scale=1 / 64, vocab_size=VOCAB,
)


@functools.lru_cache(maxsize=None)
def _hybrid_step_lowered_for_tpu(remat_policy):
    cfg = dataclasses.replace(HYBRID_CFG, remat_policy=remat_policy)
    ctx = LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), strategy="dp")
    state = jax.eval_shape(ctx._init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    traced = ctx._train_step.trace(state, {"tokens": toks, "targets": toks})
    return traced.lower(lowering_platforms=("tpu",)).as_text(debug_info=True)


@pytest.fixture(scope="module")
def hybrid_lowered_for_tpu():
    return _hybrid_step_lowered_for_tpu(HYBRID_CFG.remat_policy)


@pytest.mark.parametrize("scope", SSM_SCOPES)
def test_lowered_hybrid_step_names_the_mixers_regions_inside_the_two_mixer_scopes(hybrid_lowered_for_tpu, scope):
    """Forward and recompute: `layer/attn_proj` and `layer/attn_core` stay
    the mixer's projections and the mixer's core in every cell, and
    `benchmarks/lib/trace_ssm.py` splits them."""
    outer = SSM_SCOPES[scope]
    assert f'"{outer}/{scope}/' in hybrid_lowered_for_tpu
    assert f'"checkpoint/rematted_computation/{outer}/{scope}/' in hybrid_lowered_for_tpu


@pytest.mark.parametrize("scope", SCOPES)
def test_lowered_hybrid_step_keeps_every_model_scope(hybrid_lowered_for_tpu, scope):
    assert f"/{scope}/" in hybrid_lowered_for_tpu or f"({scope})" in hybrid_lowered_for_tpu


@pytest.mark.parametrize("kernel", KERNELS)
def test_lowered_hybrid_step_holds_each_flash_kernel_exactly_once(hybrid_lowered_for_tpu, kernel):
    calls = [line for line in hybrid_lowered_for_tpu.splitlines() if "@tpu_custom_call" in line
             and f'kernel_name = "{kernel}"' in line]
    assert len(calls) == 1  # one attention layer, its forward saved (`qkv_attn`)


# What PR 31 added to the Mamba-2 layer, as (the op's own name, the scope it must sit in): the
# convolution's kernels.  (The barrier that pinned the scan's output to bf16 before its relayout
# went with the plain form, PR 49: the scan's kernels sit behind a `jax.jit` of their own, whose
# ops carry the outer path only once compiled: `test_tpu_compiled_step.py` reads it there.)
SSM_OPS = {"ssm_conv_fwd/pallas_call": "layer/attn_proj/ssm/conv", "ssm_conv_bwd/pallas_call": "layer/attn_proj/ssm/conv"}
SSM_KERNELS = {"ssm_conv_fwd": 4, "ssm_conv_bwd": 2, "ssd_fwd": 4, "ssd_bwd": 2}  # calls of a compiled step


@pytest.mark.parametrize("op", SSM_OPS)
def test_lowered_hybrid_step_keeps_the_mixers_new_ops_inside_its_scopes(hybrid_lowered_for_tpu, op):
    """Every location of these ops in a layer's mixer is under the `ssm/*`
    name of `SSM_SCOPES`, forward and recompute (the backward kernel: in the
    backward), so `unscoped_time_pct` and the identity of PERF.md section 3 hold."""
    outer, _, scope = SSM_OPS[op].partition("/ssm/")
    assert SSM_SCOPES["ssm/" + scope] == outer
    paths = [path for path in re.findall(r'#loc\d+ = loc\("([^"]+)"', hybrid_lowered_for_tpu)
             if path.endswith(op) and "layer/attn_" in path]
    assert paths and all(f"{SSM_OPS[op]}/" in path for path in paths)
    directions = {path.split("layer/")[0] for path in paths}
    assert directions >= ({"checkpoint/"} if "bwd" in op else {"", "checkpoint/rematted_computation/"})


@pytest.mark.parametrize("kernel", SSM_KERNELS)
def test_lowered_hybrid_step_holds_the_mixers_kernels_twice_forward_and_once_backward_a_run(hybrid_lowered_for_tpu, kernel):
    """Two runs of Mamba-2 layers around the attention layer, as in
    `granite-h-micro-1chip.seq8k`: a run's scan body holds each forward kernel
    twice (forward, recompute) and each backward kernel once, so the step's
    `tpu_custom_calls` (the `[bench] facts` line) is 3 + 6 + 6 = 15 there, 9
    before the scan had kernels (PR 49); `nemotron3-nano-ep8-1chip.seq8k`,
    whose four Mamba-2 blocks lie in three runs, went from 75 to 84."""
    calls = [line for line in hybrid_lowered_for_tpu.splitlines() if "@tpu_custom_call" in line]
    assert 3 + sum(SSM_KERNELS.values()) == 15
    if kernel == "ssd_bwd":  # behind a `jax.jit` of its own: lowered once, called from each run's backward
        assert sum(f'kernel_name = "{kernel}"' in line for line in calls) == 1
        assert hybrid_lowered_for_tpu.count("call @_kernel_backward(") == SSM_KERNELS[kernel]
    else:
        assert sum(f'kernel_name = "{kernel}"' in line for line in calls) == SSM_KERNELS[kernel]


# -- Kimi Linear's names (PERF.md section 3, PR 37) ------------------------------------------

KIMI_SCOPES = {"kda/proj": "layer/attn_proj", "kda/conv": "layer/attn_proj", "mla/proj": "layer/attn_proj",
               "kda/scan": "layer/attn_core", "moe/shared": "layer/mlp", "moe/router": "layer/mlp",
               "moe/dispatch": "layer/mlp", "moe/experts": "layer/mlp", "moe/combine": "layer/mlp"}
KIMI_CFG = TransformerConfig.tiny(
    n_layers=5, n_heads=2, n_kv_heads=2, d_model=256, d_ff=256, max_seq_len=128, remat=True, remat_policy="qkv_attn",
    rope_theta=None, layer_types=("kda", "kda", "kda", "mla", "kda"), ffn_types=("dense",) + ("experts",) * 4,
    kda_heads=2, kda_head_dim=128, kv_lora_rank=128, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    n_experts=16, n_experts_held=4, experts_per_token=2, moe_d_ff=128, n_shared_experts=1, norm_topk_prob=True,
    router_activation="sigmoid", routed_scaling_factor=2.446, vocab_size=VOCAB,
)


@pytest.fixture(scope="module")
def kimi_lowered_for_tpu():
    ctx = LMTrainContext(KIMI_CFG, mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), strategy="dp")
    state = jax.eval_shape(ctx._init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    traced = ctx._train_step.trace(state, {"tokens": toks, "targets": toks})
    return traced.lower(lowering_platforms=("tpu",)).as_text(debug_info=True)


@pytest.mark.parametrize("scope", KIMI_SCOPES)
def test_lowered_kimi_step_names_its_regions_inside_the_three_layer_scopes(kimi_lowered_for_tpu, scope):
    """Forward and recompute: `layer/attn_proj`, `layer/attn_core` and
    `layer/mlp` stay what they are in every cell, and
    `benchmarks/lib/trace_kimi.py` splits them."""
    outer = KIMI_SCOPES[scope]
    if scope in ("moe/experts", "moe/combine"):
        # a share's rows go through `moe._sized_experts` (PR 48): the rung is a jitted function called INSIDE
        # `layer/mlp` (a function of its own, and MLIR locations nest per function: `layer/mlp` is on the caller's
        # line, the region's name starts the body's), and the activation runs again in the rung's own backward,
        # which the layer's backward calls, not in the layer's recompute
        assert f'"{outer}/jit(_rung_forward)"' in kimi_lowered_for_tpu and f'"{scope}/' in kimi_lowered_for_tpu
        assert f'"checkpoint/{outer}/jit(_rung_backward)"' in kimi_lowered_for_tpu
        assert f'"checkpoint/rematted_computation/{outer}/{scope}/' not in kimi_lowered_for_tpu
        return
    assert f'"{outer}/{scope}/' in kimi_lowered_for_tpu
    # PR 64: the recurrence's residuals are kept under `qkv_attn`, so the recompute holds nothing of `kda/scan`
    assert (f'"checkpoint/rematted_computation/{outer}/{scope}/' in kimi_lowered_for_tpu) == (scope != "kda/scan")


@pytest.mark.parametrize("scope", SCOPES)
def test_lowered_kimi_step_keeps_every_model_scope(kimi_lowered_for_tpu, scope):
    assert f"/{scope}/" in kimi_lowered_for_tpu or f"({scope})" in kimi_lowered_for_tpu


@pytest.mark.parametrize("kernel", KERNELS)
def test_lowered_kimi_step_holds_each_flash_kernel_exactly_once_at_two_head_sizes(kimi_lowered_for_tpu, kernel):
    calls = [line for line in kimi_lowered_for_tpu.splitlines() if "@tpu_custom_call" in line
             and f'kernel_name = "{kernel}"' in line]
    assert len(calls) == 1  # one MLA layer, its forward saved (`qkv_attn`)
    assert "x192x" in calls[0] and "x128x" in calls[0]  # q/k heads of 192 beside v heads of 128, no padding


# What PR 60 put under `kda/conv`: the positions-major convolution with the L2 norm inside, calls of a lowered step
# (three runs of KDA layers: one over the dense FFN, two over experts around the MLA layer; each one body: forward and
# recompute, one backward).
KIMI_CONV_KERNELS = {"delta_conv_fwd": 6, "delta_conv_bwd": 3}


@pytest.mark.parametrize("kernel", KIMI_CONV_KERNELS)
def test_lowered_kimi_step_holds_the_positions_major_convolution_under_kda_conv(kimi_lowered_for_tpu, kernel):
    """As `kimi-linear-ep16-1chip.seq16k`'s step holds them 8 + 4 times (four
    KDA layers in bodies of their own there): every call under
    `layer/attn_proj/kda/conv` and the kernel's own name, which is how
    `benchmarks/lib/trace_kimi.py` finds its time, the backward kernel in the
    layer's backward and not in its recompute."""
    calls = [line for line in kimi_lowered_for_tpu.splitlines() if "@tpu_custom_call" in line
             and f'kernel_name = "{kernel}"' in line]
    assert len(calls) == KIMI_CONV_KERNELS[kernel]
    paths = [path for path in re.findall(r'#loc\d+ = loc\("([^"]+)"', kimi_lowered_for_tpu) if path.endswith(f"{kernel}/pallas_call")]
    assert paths and all("layer/attn_proj/kda/conv/" in path for path in paths), paths
    directions = {path.split("layer/")[0] for path in paths}
    assert directions == ({"checkpoint/"} if "bwd" in kernel else {"", "checkpoint/rematted_computation/"})


def test_lowered_kimi_step_holds_none_of_mamba_2s_convolution_kernels(kimi_lowered_for_tpu):
    """The layer kind picks the op: a delta layer's convolution is not `ssm.CONV`'s, whose `[B, C, S]` kernels a
    Mamba-2 and an S6 layer keep (`SSM_KERNELS` above, `test_tpu_lowering.py`)."""
    assert "ssm_conv" not in kimi_lowered_for_tpu


def test_lowered_kimi_step_runs_no_d_wide_mixer_projection_twice(kimi_lowered_for_tpu):
    """Under `qkv_attn` a KDA layer's fused q|k|v projection and an MLA
    layer's q projection run in the forward and never again: the recompute
    starts from the saved arrays (`_remat_policy`)."""
    paths = re.findall(r'#loc\d+ = loc\("([^"]+)"', kimi_lowered_for_tpu)
    recomputed = [p for p in paths if "rematted_computation/layer/attn_proj" in p and p.endswith("dot_general")]
    assert not [p for p in recomputed if "bse,ef->bsf" in p and "kda/proj" in p and "bsr" not in p], recomputed
    assert not [p for p in recomputed if "mla/proj" in p and "bse,ehd->bshd" in p], recomputed
    assert any("kda/proj/bsr,rf->bsf" in p for p in recomputed)  # the gates' narrow-to-wide halves do


def _locations(lowered):
    """{`#locN`: the path it names} of a lowered step's text."""
    return dict(re.findall(r'^(#loc\d+) = loc\("([^"]+)"', lowered, flags=re.M))


def _recomputed_matmuls(lowered):
    """How many `dot_general`s of the lowered step sit under each region of a
    layer's recompute: {the scope path after `rematted_computation/`: count}."""
    locs = _locations(lowered)
    counts = collections.Counter()
    for ref in re.findall(r"stablehlo\.dot_general.*loc\((#loc\d+)\)\s*$", lowered, flags=re.M):
        region = re.search(r"^checkpoint/rematted_computation/(layer/\w+(?:/ssm/\w+)?)/", locs.get(ref, ""))
        if region:
            counts[region.group(1)] += 1
    return counts


@pytest.mark.parametrize("remat_policy, projections, attention", [("qkv_attn", 0, 1), ("attn", 4, 4), (None, 4, 4)])
def test_only_qkv_attn_keeps_a_mamba_layers_projections_out_of_the_recompute(remat_policy, projections, attention):
    """PR 36: a Mamba-2 layer (`mixers/mamba2.py`) names `in_proj`'s output and the residual stream
    after `out_proj`, and `qkv_attn` saves both, so neither matmul runs again
    in the backward (two runs of Mamba-2 layers here: 2 x 2 otherwise).  The
    scan's own forward and the FFN's `gate` + `up` + `down` are recomputed
    under every policy, and so is `ln1`, which `in_proj`'s weight gradient
    reads (`test_lowered_hybrid_step_names_the_mixers_regions...[ssm/proj]`).
    The one attention layer recomputes `wo` alone when q, k, v are saved.
    Since PR 49 the scan's recompute is the kernel `ssd_fwd`: none of the 8
    `dot_general`s the plain form had under `ssm/scan` there is left."""
    lowered = _hybrid_step_lowered_for_tpu(remat_policy)
    counts = _recomputed_matmuls(lowered)
    assert counts["layer/attn_proj/ssm/proj"] == projections and counts["layer/attn_proj"] == attention
    assert counts["layer/attn_core/ssm/scan"] == 0 and counts["layer/mlp"] == 6
    assert '"checkpoint/rematted_computation/layer/attn_proj/ssm/proj/' in lowered


# -- the head and the cross entropy (PERF.md section 3, PR 34) -------------------------------


def _paths_of_tokens_by_vocab_ops(text):
    """The path of every op of the step's main function that takes or gives
    a `[batch, 128, VOCAB]` tensor."""
    locs = _locations(text)
    paths, in_main = [], False
    for line in text.splitlines():
        if line.lstrip().startswith("func.func"):
            in_main = "public @main" in line
        elif in_main and re.search(rf"tensor<\d+x128x{VOCAB}x", line) and " = " in line:
            ref = re.search(r"loc\((#loc\d+)\)\s*$", line)
            paths.append(locs[ref.group(1)] if ref else line)
    return paths


def _head_and_loss_are_named_in_both_directions(lowered):
    """`head_cross_entropy`'s backward is written by hand, so its names are
    too: every op over `[tokens, vocab]` sits under `lm_head` (the three
    matmuls) or `loss` (the passes), forward and `transpose(` side, and none
    is a scatter (the plain form's `take_along_axis`, transposed)."""
    paths = _paths_of_tokens_by_vocab_ops(lowered)
    assert paths and all(re.search(r"[(/](lm_head|loss)[)/]", path) for path in paths), paths
    assert not any("scatter" in path for path in paths)
    for name, ops in {"lm_head": 1, "loss": 3}.items():
        fwd = [p for p in paths if f"jvp({name})" in p and "transpose(" not in p]
        bwd = [p for p in paths if f"transpose(jvp({name}))" in p]
        assert len(fwd) >= ops and len(bwd) >= ops
    matmuls = sorted(p.split("/")[-2] for p in paths if p.endswith("dot_general"))
    assert matmuls == ["bse,bsv->ev", "bse,ev->bsv", "bsv,ev->bse"]


def test_dense_steps_head_and_loss_ops_carry_their_names_in_both_directions(lowered_for_tpu):
    _head_and_loss_are_named_in_both_directions(lowered_for_tpu)


def test_expert_steps_head_and_loss_ops_carry_their_names_in_both_directions(moe_lowered_for_tpu):
    _head_and_loss_are_named_in_both_directions(moe_lowered_for_tpu)


def test_hybrid_steps_head_and_loss_ops_carry_their_names_in_both_directions(hybrid_lowered_for_tpu):
    _head_and_loss_are_named_in_both_directions(hybrid_lowered_for_tpu)


# -- tracing.annotate -------------------------------------------------------------------


def test_annotate_without_jax_imports_nothing_and_records_nothing():
    code = (
        "import sys\n"
        "from ray_tpu.util import tracing\n"
        "with tracing.annotate('train_step/dispatch'):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules and tracing.drain_spans() == []\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "RAY_TPU_TRACE"}
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_annotate_records_a_span_with_ray_tpu_trace_on():
    code = (
        "import sys\n"
        "from ray_tpu.util import tracing\n"
        "with tracing.span('outer') as outer:\n"
        "    with tracing.annotate('train_step/make_batch'):\n"
        "        pass\n"
        "inner, top = tracing.drain_spans()\n"
        "assert 'jax' not in sys.modules\n"
        "assert (inner['name'], top['name']) == ('train_step/make_batch', 'outer')\n"
        "assert inner['parent_span_id'] == top['span_id'] and inner['end'] >= inner['start']\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=dict(os.environ, RAY_TPU_TRACE="1"),
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_annotate_opens_a_trace_annotation_once_jax_is_imported(monkeypatch):
    opened = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(self.name)

        def __exit__(self, *exc):
            opened.append("/" + self.name)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    with tracing.annotate("init_state"):
        opened.append("body")
    assert opened == ["init_state", "body", "/init_state"]


def test_train_step_is_split_into_make_batch_and_dispatch(monkeypatch):
    import numpy as np

    names = []
    real = tracing.annotate
    monkeypatch.setattr(tracing, "annotate", lambda name, **kw: names.append(name) or real(name, **kw))
    ctx = LMTrainContext(CFG, mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), strategy="dp")
    state = ctx.init_state(seed=0)
    toks = np.zeros((2, 128), np.int32)
    state, _ = ctx.train_step(state, {"tokens": toks, "targets": toks})
    assert names == ["init_state", "train_step/make_batch", "train_step/dispatch"]
    names.clear()
    ctx.train_step(state, ctx.make_batch({"tokens": toks, "targets": toks}))  # already on the device
    assert names == ["train_step/dispatch"]


def test_backend_start_records_import_and_device_open_spans():
    """Lifecycle spans: recorded with tracing OFF, into the buffer the flush
    to the head drains and into the store a run's record is built from."""
    from ray_tpu.train.backend import _init_jax_distributed

    tracing.drain_spans()
    kept = len(tracing.lifecycle_spans())
    assert not tracing.is_enabled()
    out = _init_jax_distributed("", 1, 0, None)
    want = ["train::backend::import_jax", "train::backend::device_open"]  # no chip_wait off the TPU
    assert [s["name"] for s in tracing.drain_spans()] == want
    spans = tracing.lifecycle_spans()[kept:]
    assert [s["name"] for s in spans] == want and spans[0]["attrs"] == {"already_imported": True}
    assert out["global_devices"] == len(jax.devices())


def test_span_catalog_sees_annotate_calls():
    from ray_tpu._private.analysis import span_names

    calls = [n for n in ast.walk(ast.parse(
        "with tracing.annotate('a/b'): pass\nwith span('c'): pass\nwith annotate(name): pass\nfoo('d')\n"))
        if isinstance(n, ast.Call)]
    assert sorted(filter(None, map(span_names._span_call_name, calls))) == ["a/b", "c"]
    catalog = span_names.load_catalog(os.path.join(ROOT, "ray_tpu/_private/analysis/span_names.txt"))
    assert {"init_state", "train_step/make_batch", "train_step/dispatch", "train::fit", "jax::compile",
            "train::backend::import_jax", "train::backend::chip_wait", "train::backend::device_open",
            "worker::boot::connect", "runtime::shutdown"} <= set(catalog)


def test_span_catalog_takes_no_scope_for_a_span():
    """Scopes are regions of a traced function, shared by many sites: not spans, not in `span_names.txt`."""
    from ray_tpu._private.analysis import span_names

    calls = [n for n in ast.walk(ast.parse(
        "with tracing.scope('layers'): pass\nwith scope('autodiff', host_only=True): pass\n")) if isinstance(n, ast.Call)]
    assert list(filter(None, map(span_names._span_call_name, calls))) == []
    catalog = span_names.load_catalog(os.path.join(ROOT, "ray_tpu/_private/analysis/span_names.txt"))
    assert not {"layers", "autodiff", "optimizer", "layer/attn_proj", "kda_fwd"} & set(catalog)


# -- the compile cache's key --------------------------------------------------------------


def test_apply_default_puts_metadata_in_the_cache_key(monkeypatch, tmp_path):
    from ray_tpu._private import compile_cache

    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    # set, then deleted: `monkeypatch` records nothing for a variable that is absent, and what
    # `apply_default` writes into `os.environ` itself would outlive the test in this worker process
    monkeypatch.setenv(compile_cache.METADATA_ENV, "")
    monkeypatch.delenv(compile_cache.METADATA_ENV)
    monkeypatch.setitem(sys.modules, "jax", None)  # as in a worker at entry: jax not imported yet
    compile_cache.apply_default()
    assert os.environ[compile_cache.METADATA_ENV] == "1"
    monkeypatch.setenv(compile_cache.METADATA_ENV, "0")  # the operator's own choice stands
    compile_cache.apply_default()
    assert os.environ[compile_cache.METADATA_ENV] == "0"


@pytest.mark.parametrize("keyed, entries", [("1", 2), ("0", 1)], ids=["metadata_in_key", "jax_default"])
def test_two_builds_that_differ_only_by_a_scope_share_a_cache_entry_only_by_jax_default(tmp_path, keyed, entries):
    """The trap: `jax.named_scope` changes debug info only, and JAX's default
    key strips debug info, so build B would run build A's executable, whose
    profile has A's names.  `apply_default` keys the metadata in."""
    code = (
        "import os, sys\n"
        "from ray_tpu._private import compile_cache\n"
        "compile_cache.apply_default()\n"
        "import jax, jax.numpy as jnp, numpy as np\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)\n"
        "jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)\n"
        "def build(scope):\n"
        "    def f(x):\n"
        "        with jax.named_scope(scope):\n"
        "            return jnp.tanh(x) @ x\n"
        "    return jax.jit(f)\n"
        "x = np.ones((64, 64), np.float32)\n"
        "for scope in ('layer/mlp', 'layer/attn_proj'):\n"
        "    build(scope)(x).block_until_ready()\n"
        "print(len([n for n in os.listdir(os.environ['JAX_COMPILATION_CACHE_DIR']) if n.startswith('jit_f-') and n.endswith('-cache')]))\n"
    )
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path), JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY=keyed)
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert int(proc.stdout.strip().splitlines()[-1]) == entries


# -- tracing.scope: the device's names, and the same names on the host while jax traces -------


def test_scope_gives_the_lowered_text_named_scope_gave():
    """`tracing.scope` IS `jax.named_scope` to the ops traced inside it; its
    host-only form names nothing."""
    import contextlib

    def toy(x, named, on_the_host):
        with named("layers"), named("layer/mlp"):
            x = jnp.tanh(x) @ x
        with on_the_host("autodiff"):
            return x + 1.0

    forms = ((tracing.scope, functools.partial(tracing.scope, host_only=True)),
             (jax.named_scope, lambda name: contextlib.nullcontext()))
    ours, jaxs = [jax.jit(functools.partial(toy, named=named, on_the_host=on_the_host)).lower(jnp.ones((8, 8)))
                  .as_text(debug_info=True) for named, on_the_host in forms]
    assert "layers/layer/mlp/" in ours and "autodiff" not in ours
    assert ours == jaxs


STEPS = {"dense": (CFG, KERNELS), "experts": (MOE_CFG, KERNELS + MOE_KERNELS),
         "hybrid": (HYBRID_CFG, KERNELS + ("ssm_conv_fwd", "ssm_conv_bwd")),
         "kimi": (KIMI_CFG, KERNELS + MOE_KERNELS + ("kda_fwd", "kda_bwd", "delta_conv_fwd", "delta_conv_bwd"))}


@pytest.fixture(scope="module", params=sorted(STEPS))
def step_trace_span(request):
    """(the `jax::trace` span of a tiny step, the kernel names it must say)."""
    from ray_tpu.train import run_record

    assert run_record.install_jax_listener()
    cfg, kernels = STEPS[request.param]
    ctx = LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), strategy="dp")
    state = jax.eval_shape(ctx._init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    # As in a fresh process: a body jax has traced (a jitted rung of the experts, the rules of the convolution's
    # `custom_vjp` at the same shapes) is not entered again, and the steps above have traced these.
    jax.clear_caches()
    run_record.flush_traces()
    before = len(tracing.lifecycle_spans())
    ctx._train_step.trace(state, {"tokens": toks, "targets": toks})
    run_record.flush_traces()
    (span,) = [s for s in tracing.lifecycle_spans()[before:] if s["attrs"].get("fun_name") == "_train_step"]
    return span, kernels


def test_the_steps_trace_span_names_autodiff_optimizer_the_layers_and_each_kernel(step_trace_span):
    span, kernels = step_trace_span
    attrs = span["attrs"]
    paths = set(attrs["scopes"])
    assert {"autodiff", "optimizer"} <= paths
    assert all(p == "optimizer" or p.split("/")[0] == "autodiff" for p in paths), sorted(paths)
    assert any(p.endswith("layer/attn_proj") and "layers" in p.split("/") for p in paths), sorted(paths)
    assert set(kernels) <= set(attrs["kernels"]), attrs["kernels"]
    assert all(any(p.rsplit("/", 1)[-1] == k for p in paths) for k in attrs["kernels"])
    total = sum(row[0] for row in attrs["scopes"].values()) + attrs["unscoped_s"]
    assert attrs["unscoped_s"] >= 0 and total == pytest.approx(span["end"] - span["start"], abs=1e-3)
    assert attrs["unscoped_s"] < 0.15 * (span["end"] - span["start"])  # the step's own regions all have a name


def test_the_three_programs_carry_the_names_the_readers_rely_on():
    import numpy as np

    from ray_tpu.models.lm import PROGRAMS
    from ray_tpu.train import run_record

    assert run_record.install_jax_listener()
    ctx = LMTrainContext(CFG, mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), strategy="dp")
    assert PROGRAMS == {"_init": "init", "_forward": "apply", "_train_step": "step"}
    run_record.flush_traces()
    before = len(tracing.lifecycle_spans())
    calls = {}
    state = ctx.init_state(seed=0)
    calls["init"] = len(tracing.lifecycle_spans())
    toks = np.zeros((2, 128), np.int32)
    ctx.apply(state["params"], toks)
    calls["apply"] = len(tracing.lifecycle_spans())
    ctx.train_step(state, {"tokens": toks, "targets": toks})
    run_record.flush_traces()
    calls["step"] = len(tracing.lifecycle_spans())
    spans, lo = tracing.lifecycle_spans(), before
    for fun_name, program in PROGRAMS.items():  # in the order called
        mine = [(s["name"], s["attrs"]["fun_name"]) for s in spans[lo:calls[program]] if s["name"].startswith("jax::")]
        assert ("jax::trace", fun_name) in mine, (program, mine)
        assert ("jax::lower", f"jit({fun_name})") in mine and ("jax::compile", f"jit({fun_name})") in mine
        assert not [m for m in mine if m[1].strip("jit()") in set(PROGRAMS) - {fun_name}], (program, mine)
        lo = calls[program]
    traces = {s["attrs"]["fun_name"]: s["attrs"] for s in spans[before:] if s["name"] == "jax::trace"}
    assert "scopes" in traces["_forward"] and "scopes" in traces["_train_step"]  # the same `trunk`, the same names
    assert "autodiff" not in traces["_forward"]["scopes"]
