"""The train step lowers for TPU with the flash kernel in it, on every mesh.

No chip and no libtpu needed: `lowering_platforms=("tpu",)` cross-lowers
from the virtual CPU devices, which is as far as Mosaic kernels go before
the TPU compiler.  It is far enough to catch what CPU execution cannot: off
TPU the auto dispatch runs XLA attention, which GSPMD partitions happily,
while a Mosaic call on a mesh of more than one device refuses to lower unless
it sits inside a shard_map ("Mosaic kernels cannot be automatically
partitioned").
"""

import collections
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import LMTrainContext, TransformerConfig
from ray_tpu.ops.rotary import Rope
from ray_tpu.parallel import MeshSpec, build_mesh

# 128-aligned sequence and head_dim: the shapes the auto dispatch gives to
# the kernel.  remat on, like every cell's job (benchmarks/configs/*.json).
CFG = TransformerConfig.tiny(
    n_heads=2, n_kv_heads=2, d_model=256, d_ff=256, max_seq_len=128,
    remat=True, remat_policy="qkv_attn",
)


def _lowered_text(n_devices, spec, strategy, platforms=None, cfg=CFG, debug_info=False, seq=128):
    mesh = build_mesh(spec, devices=jax.devices()[:n_devices])
    ctx = LMTrainContext(cfg, mesh=mesh, strategy=strategy)
    state = jax.eval_shape(ctx._init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((8, seq), jnp.int32)
    traced = ctx._train_step.trace(state, {"tokens": toks, "targets": toks})
    kw = {"lowering_platforms": platforms} if platforms else {}
    return traced.lower(**kw).as_text(debug_info=debug_info)


def _mosaic_kernels(text):
    """Mosaic calls of a lowered step, counted by the kernel's name."""
    return collections.Counter(
        re.search(r'kernel_name = "(\w+)"', line).group(1)
        for line in text.splitlines() if "@tpu_custom_call" in line
    )


def _kernel_paths(text, names):
    """(kernel's name, its location's whole path) of every Mosaic call named by the regex `names`."""
    locs = dict(re.findall(r'^(#loc\d+) = loc\((.*)\)$', text, flags=re.M))
    for line in text.splitlines():
        kernel = re.search(rf'kernel_name = "({names})"', line)
        if "@tpu_custom_call" in line and kernel:
            loc = re.search(r"loc\((#loc\d+)\)\s*$", line).group(1)
            seen, path = set(), ""
            while loc and loc not in seen:  # a location names its parents by reference
                seen.add(loc)
                path += locs.get(loc, "")
                nxt = re.search(r"#loc\d+", locs.get(loc, ""))
                loc = nxt.group(0) if nxt else None
            yield kernel.group(1), path


MESHES = [
    (1, MeshSpec(data=1), "dp"),
    (4, MeshSpec(data=4), "dp"),
    (4, MeshSpec(data=1, fsdp=4), "fsdp"),
    (4, MeshSpec(data=1, fsdp=2, tensor=2), "fsdp_tp"),
    (4, MeshSpec(data=2, tensor=2), "tp"),
]
MESH_IDS = ["dp1", "dp4", "fsdp4", "fsdp_tp4", "tp4"]


@pytest.mark.parametrize("n_devices,spec,strategy", MESHES, ids=MESH_IDS)
def test_train_step_lowers_for_tpu_with_kernel(n_devices, spec, strategy):
    text = _lowered_text(n_devices, spec, strategy, platforms=("tpu",))
    # `qkv_attn` saves the residuals the kernel names, so the forward kernel
    # is in the step once: a second `flash_fwd` is the backward re-running it.
    assert _mosaic_kernels(text) == {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1}
    # The dense FFN's four cotangents reach the TPU compiler through one
    # barrier (`_dense_ffn`); the checkpoint's own barrier is far wider.
    assert len(re.findall(r"%\d+:4 = stablehlo.optimization_barrier", text)) == 1


@pytest.mark.parametrize("n_devices,spec,strategy", MESHES, ids=MESH_IDS)
def test_logits_cotangent_is_placed_like_the_logits_and_nothing_scatters_into_them(n_devices, spec, strategy):
    """`head_cross_entropy` writes its own backward, so GSPMD has no forward
    op to copy the cotangent's placement from: the same constraint is on both
    (without it `fsdp` could gather `[tokens, vocab]`).  In bf16, as the
    cells run: three matmuls over the logits' shape, and no scatter into it
    (the plain cross entropy's `take_along_axis`, transposed)."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16, vocab_size=384)
    text = _lowered_text(n_devices, spec, strategy, platforms=("tpu",), cfg=cfg)
    placed = re.findall(r"sdy.sharding_constraint %\S+ (<@mesh, \[.*?\]>) : tensor<8x128x384xbf16>", text)
    assert len(placed) == 2 and placed[0] == placed[1]
    if strategy in ("tp", "fsdp_tp"):
        assert '{"tensor"}' in placed[0]  # the vocabulary over `tensor`
    assert len([line for line in text.splitlines() if "stablehlo.dot_general" in line and "8x128x384xbf16" in line]) == 3
    assert not [line for line in text.splitlines() if "stablehlo.scatter" in line and "8x128x384x" in line]
    assert "8x128x384xf32>) -> tensor<8x128x384xbf16>" in text  # the cotangent is narrowed before the matmuls


def test_full_recompute_reruns_the_forward_kernel():
    """`remat_policy=None` saves nothing per layer, the kernel's residuals
    included: the backward's recompute holds the forward kernel again."""
    cfg = dataclasses.replace(CFG, remat_policy=None)
    text = _lowered_text(1, MeshSpec(data=1), "dp", platforms=("tpu",), cfg=cfg)
    assert _mosaic_kernels(text) == {"flash_fwd": 2, "flash_bwd_dq": 1, "flash_bwd_dkv": 1}


def test_cpu_lowering_has_no_kernel():
    """The same step lowered for the CPU it runs on holds no Mosaic call
    (and no interpret-mode kernel either: auto dispatch is XLA attention)."""
    text = _lowered_text(1, MeshSpec(data=1), "dp")
    assert "tpu_custom_call" not in text


# Granite 4.0-H's shapes in small: 5 Mamba-2 layers and ONE attention layer at
# head size 64 (d 256 / 4 heads, GQA 4:1) with the published scale and no
# rotary embedding, in three runs of one kind.
HYBRID = TransformerConfig.tiny(
    n_layers=6, n_heads=4, n_kv_heads=1, d_model=256, d_ff=256, max_seq_len=128, remat=True, remat_policy="qkv_attn",
    tie_embeddings=True, rope_theta=None, layer_types=("mamba", "mamba", "mamba", "attention", "mamba", "mamba"),
    ssm_heads=8, ssm_head_dim=64, ssm_state=128, embedding_multiplier=12.0, residual_multiplier=0.22,
    logits_scaling=8.0, attention_scale=1 / 64,
)


@pytest.mark.parametrize(
    "n_devices,spec,strategy",
    [(1, MeshSpec(data=1), "dp"), (4, MeshSpec(data=1, fsdp=4), "fsdp"), (4, MeshSpec(data=2, tensor=2), "tp")],
    ids=["dp1", "fsdp4", "tp4"],
)
def test_hybrid_step_lowers_for_tpu_with_each_flash_kernel_once(n_devices, spec, strategy):
    """One attention layer, so each flash kernel exactly once, at head size 64
    (under `tp` the scan's heads are replicated).  The Mamba-2 layers lie in
    two runs, each one scan body: per run the convolution's forward kernel
    twice (forward, and the recompute: `qkv_attn` keeps `in_proj`'s output,
    not the convolution's, which measured slower when kept; PERF.md section 6,
    PR 36) and its backward kernel once, and the same of the scan's two kernels
    (PR 49: `ssd_fwd`, `ssd_bwd`), all under shard_map on a mesh of more than
    one device like the flash kernels.  15 Mosaic calls (9 before PR 49), the
    count of `granite-h-micro-1chip.seq8k`'s step, which has the same runs."""
    text = _lowered_text(n_devices, spec, strategy, platforms=("tpu",), cfg=HYBRID)
    kernels = _mosaic_kernels(text)
    # `ssd_bwd` sits behind a `jax.jit` of its own: ONE lowered function, called from both runs' backwards
    assert kernels.pop("ssd_bwd") == 1 and text.count("call @_kernel_backward(") == 2
    assert kernels == {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1, "ssm_conv_fwd": 4, "ssm_conv_bwd": 2, "ssd_fwd": 4}
    call = next(line for line in text.splitlines() if "@tpu_custom_call" in line and 'kernel_name = "flash_fwd"' in line)
    assert re.search(r"tensor<\d+x\d+x128x64xf32>", call)  # q: [batch, heads, seq, 64] (float32 in this tiny config)


@pytest.mark.parametrize(
    "n_devices,spec,strategy", [(1, MeshSpec(data=1), "dp"), (4, MeshSpec(data=1, fsdp=4), "fsdp")], ids=["dp1", "fsdp4"])
def test_a_step_with_groups_of_b_and_c_lowers_for_tpu_with_the_same_scan_kernels(n_devices, spec, strategy):
    """Nemotron-H's Mamba-2 in small: 8 heads in 2 groups of B and C (four
    heads a program), one run of three layers: the scan's forward kernel
    twice (forward, recompute) and its backward kernel once."""
    cfg = dataclasses.replace(HYBRID, n_layers=3, layer_types=("mamba",) * 3, ssm_groups=2)
    text = _lowered_text(n_devices, spec, strategy, platforms=("tpu",), cfg=cfg, debug_info=True)
    assert _mosaic_kernels(text) == {"ssm_conv_fwd": 2, "ssm_conv_bwd": 1, "ssd_fwd": 2, "ssd_bwd": 1}
    for _, path in _kernel_paths(text, "ssm_conv_fwd|ssm_conv_bwd"):  # named by its caller, on a mesh too (PR 60)
        assert "ssm/conv/" in path and "ssm/conv/ssm/conv" not in path, path


# Kimi Linear's mixers in small, at the head size the KDA kernel takes: two KDA
# layers (one run, one scan body) and one latent-attention layer.
KIMI = TransformerConfig.tiny(
    n_layers=3, n_heads=2, n_kv_heads=2, d_model=256, d_ff=256, max_seq_len=128, remat=True, remat_policy="qkv_attn",
    rope_theta=None, layer_types=("kda", "kda", "mla"), kda_heads=2, kda_head_dim=128,
    kv_lora_rank=64, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
)


@pytest.mark.parametrize(
    "n_devices,spec,strategy",
    [(1, MeshSpec(data=1), "dp"), (4, MeshSpec(data=1, fsdp=4), "fsdp"), (4, MeshSpec(data=2, tensor=2), "tp")],
    ids=["dp1", "fsdp4", "tp4"],
)
def test_kimi_step_lowers_for_tpu_with_the_kda_kernels_inside_kda_scan(n_devices, spec, strategy):
    """The KDA run is one scan body: the recurrence's forward kernel ONCE
    (PR 64: `qkv_attn` keeps the residuals the op names, its output and the
    states its backward starts from, inside a `shard_map`'s body too, so the
    recompute holds no `kda_fwd`; twice until then) and its backward kernel
    once (PR 41; before it the backward was JAX's own, of the plain segment, on
    TPU too); under shard_map on a mesh like the others, and each under
    `kda/scan` and its own name."""
    text = _lowered_text(n_devices, spec, strategy, platforms=("tpu",), cfg=KIMI, debug_info=True)
    kernels = _mosaic_kernels(text)
    assert kernels["kda_fwd"] == 1 and kernels["kda_bwd"] == 1 and kernels["flash_fwd"] == 1, kernels
    assert not [name for name in kernels if name.startswith(("kda_", "gdn_")) and name not in ("kda_fwd", "kda_bwd")]
    for kernel, path in _kernel_paths(text, "kda_fwd|kda_bwd"):
        assert "kda/scan" in path and kernel in path, path
        assert "rematted_computation" not in path, path  # `kda_bwd` in the layer's backward (the reader's `bwd`), `kda_fwd` in its forward
    _holds_the_positions_major_convolution(text, kernels, "kda/conv/", n_devices)


def _holds_the_positions_major_convolution(text, kernels, name, n_devices):
    """PR 60: a delta layer's convolution is `ops/delta_conv.py`'s, with the L2
    norm inside: its forward kernel twice in the run's body (forward, and the
    recompute), its backward kernel once, under the layer's `*/conv` name and
    their own ONCE on every mesh (on more than one device a `shard_map`'s body
    starts a name stack of its own, and the op has no name but its caller's:
    `kernel_pair.run` takes that name inside, as the scans' own names are
    entered there; on one device the layer's whole path stands), and none of
    Mamba-2's `ssm_conv_*` (whose counts in the Mamba-2 and S6 steps of this
    file stand as they were: the layer kind picks the op)."""
    assert kernels["delta_conv_fwd"] == 2 and kernels["delta_conv_bwd"] == 1, kernels
    assert not [kernel for kernel in kernels if kernel.startswith("ssm_conv")], kernels
    for kernel, path in _kernel_paths(text, "delta_conv_fwd|delta_conv_bwd"):
        assert ("layer/attn_proj/" + name if n_devices == 1 else name) in path and 2 * name not in path, path
        assert f"/{kernel}/pallas_call" in path, path
        if kernel == "delta_conv_bwd":
            assert "rematted_computation" not in path, path


def test_kimi_step_lowered_for_the_cpu_holds_no_kernel():
    assert "tpu_custom_call" not in _lowered_text(1, MeshSpec(data=1), "dp", cfg=KIMI)


# Qwen3-Next's mixers in small, at the head sizes the scalar-decay kernels take: two delta layers (one run, one scan
# body) of one key head and two value heads of 128 / 128, and one gated-attention layer.
QWEN3_NEXT = TransformerConfig.tiny(
    n_layers=3, n_heads=2, n_kv_heads=2, d_model=256, d_ff=256, max_seq_len=128, remat=True, remat_policy="qkv_attn",
    layer_types=("gdn", "gdn", "attention"), gdn_key_heads=1, gdn_value_heads=2, gdn_key_dim=128, gdn_value_dim=128,
)


@pytest.mark.parametrize(
    "n_devices,spec,strategy",
    [(1, MeshSpec(data=1), "dp"), (4, MeshSpec(data=1, fsdp=4), "fsdp"), (4, MeshSpec(data=2, tensor=2), "tp")],
    ids=["dp1", "fsdp4", "tp4"],
)
def test_a_delta_layer_lowers_for_tpu_with_the_scalar_decay_kernels_inside_gdn_scan(n_devices, spec, strategy):
    """PR 58: a layer with ONE decay a head runs `gdn_fwd` (forward, and the
    recompute: the op names its residuals since PR 64, but `gdn` does not list
    them in `Mixer.saved`, `qwen3-next`'s step has no room for them) and
    `gdn_bwd` (once, in the layer's backward), under shard_map
    on a mesh like the others, each under `gdn/scan` and its own name; no
    per-channel kernel, which is a `kda` layer's (the Kimi step above holds no
    `gdn_*`: the layer kind picks the op)."""
    text = _lowered_text(n_devices, spec, strategy, platforms=("tpu",), cfg=QWEN3_NEXT, debug_info=True)
    kernels = _mosaic_kernels(text)
    assert kernels["gdn_fwd"] == 2 and kernels["gdn_bwd"] == 1 and kernels["flash_fwd"] == 1, kernels
    assert not [name for name in kernels if name.startswith(("kda_", "gdn_")) and name not in ("gdn_fwd", "gdn_bwd")]
    for kernel, path in _kernel_paths(text, "gdn_fwd|gdn_bwd"):
        assert "gdn/scan" in path and kernel in path, path
        if kernel == "gdn_bwd":  # in the layer's backward, not its recompute: the reader's `bwd`
            assert "rematted_computation" not in path, path
    _holds_the_positions_major_convolution(text, kernels, "gdn/conv/", n_devices)


def test_a_delta_step_lowered_for_the_cpu_holds_no_kernel():
    assert "tpu_custom_call" not in _lowered_text(1, MeshSpec(data=1), "dp", cfg=QWEN3_NEXT)


# SambaY's Mamba-1 layers in small, at widths the scan's kernel takes (512 channels, 16 states; one
# block of 128 positions): two s6 layers are one run, one scan body.
S6 = TransformerConfig.tiny(
    n_layers=2, n_heads=2, n_kv_heads=2, d_model=256, d_ff=256, max_seq_len=128, remat=True, remat_policy="qkv_attn",
    rope_theta=None, layer_types=("s6", "s6"), s6_inner=512, s6_state=16, s6_dt_rank=16,
)


@pytest.mark.parametrize(
    "n_devices,spec,strategy",
    [(1, MeshSpec(data=1), "dp"), (4, MeshSpec(data=4), "dp"), (4, MeshSpec(data=1, fsdp=4), "fsdp")],
    ids=["dp1", "dp4", "fsdp4"],
)
def test_s6_step_lowers_for_tpu_with_the_scan_kernel_inside_s6_scan(n_devices, spec, strategy, monkeypatch):
    """The forward kernel twice in the run's body (forward, and the recompute:
    `qkv_attn` keeps the projections, not the scan's output) and the backward
    kernel once (PR 51: from the entering states the forward wrote); under
    shard_map on a mesh like the convolution's, under `s6/scan` and their own
    names, which is how the benchmark's readers find their time."""
    from ray_tpu.ops.pallas import selective_scan as kernels

    monkeypatch.setattr(kernels, "_BLOCK_S", 128)
    text = _lowered_text(n_devices, spec, strategy, platforms=("tpu",), cfg=S6, debug_info=True)
    found = _mosaic_kernels(text)
    assert found["s6_scan_fwd"] == 2 and found["ssm_conv_fwd"] == 2 and found["ssm_conv_bwd"] == 1, found
    assert {name: count for name, count in found.items() if name.startswith("s6_")} == {"s6_scan_fwd": 2, "s6_scan_bwd": 1}
    for kernel, count in (("s6_scan_fwd", 2), ("s6_scan_bwd", 1)):
        paths = [path for _, path in _kernel_paths(text, kernel)]
        assert len(paths) == count and all("s6/scan" in path and kernel in path for path in paths), paths


def test_s6_step_lowered_for_the_cpu_holds_no_kernel():
    assert "tpu_custom_call" not in _lowered_text(1, MeshSpec(data=1), "dp", cfg=S6)


# PR 64: a run of one kind's layers (one scan body) and the two kernels of its recurrence.  Mamba-2's is the step of
# `test_a_step_with_groups_of_b_and_c_lowers_for_tpu_with_the_same_scan_kernels`.
SCANS = {
    "kda": (dataclasses.replace(KIMI, n_layers=2, layer_types=("kda", "kda")), "kda_fwd", "kda_bwd"),
    "gdn": (dataclasses.replace(QWEN3_NEXT, n_layers=2, layer_types=("gdn", "gdn")), "gdn_fwd", "gdn_bwd"),
    "mamba": (dataclasses.replace(HYBRID, n_layers=3, layer_types=("mamba",) * 3, ssm_groups=2), "ssd_fwd", "ssd_bwd"),
    "s6": (S6, "s6_scan_fwd", "s6_scan_bwd"),
}


def _no_mesh_text(cfg):
    """The gradient of a tiny loss over `transformer.forward` with no mesh at all, lowered for TPU."""
    from ray_tpu.models import transformer

    params = jax.eval_shape(lambda key: transformer.init_params(cfg, key), jax.random.PRNGKey(0))
    loss = lambda p, tokens: jnp.mean(jnp.square(transformer.forward(p, tokens, cfg).astype(jnp.float32)))
    traced = jax.jit(jax.grad(loss)).trace(params, jax.ShapeDtypeStruct((8, 128), jnp.int32))
    return traced.lower(lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("policy", ["qkv_attn", "attn", None], ids=str)
@pytest.mark.parametrize("kind,devices", [(kind, 1) for kind in SCANS] + [("kda", None), ("kda", 4), ("gdn", None), ("gdn", 4)],
                         ids=str)
def test_the_recurrences_forward_kernel_runs_once_a_layer_where_the_policy_keeps_what_the_op_names(kind, devices, policy, monkeypatch):
    """The boundary of PR 64, pinned.  A recurrence names the residuals of its
    `custom_vjp` (`KernelPair.residual_names`) and `kda` lists those names in
    `Mixer.saved`: under `"qkv_attn"` a `kda` layer holds ONE forward scan
    kernel, with no mesh, on a mesh of one device and inside the `shard_map`
    of a mesh of four (jax hands the policy down to its body); under `"attn"`
    and `None`, which keep none of a recurrence's names, two (the forward's,
    and the recompute's that the backward kernel waits for).  `gdn`'s
    recurrence, Mamba-2's SSD and the selective scan get names no kind lists:
    two under all three (`gdn` for `qwen3-next`'s compiled peak, PERF.md
    section 7; the others are ROADMAP Speed 3(b)'s smaller entries).  One
    backward kernel a layer everywhere; `lm._rerun_counters` says the same
    from the configuration alone."""
    from ray_tpu.models import lm
    from ray_tpu.ops.pallas import selective_scan as s6_kernels

    monkeypatch.setattr(s6_kernels, "_BLOCK_S", 128)
    cfg, forward, backward = SCANS[kind]
    cfg = dataclasses.replace(cfg, remat_policy=policy)
    if devices is None:
        text = _no_mesh_text(cfg)
    else:
        spec, strategy = (MeshSpec(data=1), "dp") if devices == 1 else (MeshSpec(data=1, fsdp=4), "fsdp")
        text = _lowered_text(devices, spec, strategy, platforms=("tpu",), cfg=cfg)
    kept = kind == "kda" and policy == "qkv_attn"
    kernels = _mosaic_kernels(text)
    assert (kernels[forward], kernels[backward]) == (1 if kept else 2, 1), kernels
    assert lm._rerun_counters(cfg) == {lm.SCAN_RERUN: 0.0 if kept else 100.0}


# A share of the experts in small, at widths the grouped-matmul kernels take: two attention + expert layers (one
# run, one scan body), 2 of 16 experts held, 2,048 assignments a step: rungs of 512, 1,024 and all 2,048 rows.
SHARE = dict(n_layers=2, n_heads=2, n_kv_heads=2, d_model=256, d_ff=256, moe_d_ff=128, max_seq_len=128, remat=True,
             remat_policy="qkv_attn", n_experts=16, experts_per_token=2, n_experts_held=2, n_shared_experts=1)


# 2 of 16 at 2 choices is a quarter of an assignment a token, the ladder from twice the uniform 256 rows; 8 of 16 is ONE
# a token (`mellum2-ep4-1chip.seq16k` has two): 1.25x the uniform 1,024 rows, then all (PR 53)
@pytest.mark.parametrize("kind,held,k,want,per_rung", [("swiglu", 2, 2, (512, 1024, 2048), {"moe_gmm": 8, "moe_tgmm": 3}),
                                                       ("relu2", 2, 2, (512, 1024, 2048), {"moe_gmm": 5, "moe_tgmm": 2}),
                                                       ("swiglu", 8, 2, (1536, 2048), {"moe_gmm": 8, "moe_tgmm": 3})],
                         ids=["swiglu", "relu2", "swiglu-one-assignment-a-token"])
def test_a_share_of_the_experts_lowers_for_tpu_with_one_switch_a_direction(kind, held, k, want, per_rung):
    """The two cells that hold a share (`kimi-linear-ep16-1chip.seq16k`: three
    matrices; `nemotron3-nano-ep8-1chip.seq8k`: two) in small.  The share's
    buffers take one of at most four static sizes (PR 48), so the scan body
    holds TWO switches, the forward's and the backward's (under `qkv_attn` the
    recompute's is dead code: its residuals are the layer's inputs), each
    rung with the kernels the whole layer had: forward 3 (2) products,
    again gate and up (up) in the backward, the 3 (2) transposed products and
    the 3 (2) weight gradients.  No shape is refused to the XLA form.  The
    same of a share that gets one assignment a token, whose ladder is 1.25x
    its uniform share and all (PR 53; the rung `mellum2-ep4-1chip.seq16k` takes)."""
    from ray_tpu.models import moe
    from ray_tpu.ops.grouped_matmul import REFUSED_SCOPE

    rungs = moe._rungs(8 * 128 * k, held, 16, k)
    assert rungs == want and len(rungs) <= 4
    cfg = TransformerConfig.tiny(**dict(SHARE, n_experts_held=held, experts_per_token=k), expert_kind=kind)
    text = _lowered_text(1, MeshSpec(data=1), "dp", platforms=("tpu",), cfg=cfg, debug_info=True)
    kernels = _mosaic_kernels(text)
    assert {name: kernels[name] for name in per_rung} == {name: n * len(rungs) for name, n in per_rung.items()}, kernels
    assert REFUSED_SCOPE not in text

    def switches(jaxpr):  # `cond`s whose every branch is a rung's jitted function (a `platform_dependent` is a `cond` too), at any depth
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "cond" and all(
                    any(str(e.params.get("name")).startswith("_rung_") for e in branch.jaxpr.eqns)
                    for branch in eqn.params["branches"]):
                yield eqn
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) else [value]:
                    if hasattr(getattr(sub, "jaxpr", sub), "eqns"):
                        yield from switches(getattr(sub, "jaxpr", sub))

    ctx = LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), strategy="dp")
    toks = jax.ShapeDtypeStruct((8, 128), jnp.int32)
    step = jax.make_jaxpr(ctx._train_step)(jax.eval_shape(ctx._init, jax.random.PRNGKey(0)), {"tokens": toks, "targets": toks})
    assert [len(eqn.params["branches"]) for eqn in switches(step.jaxpr)] == [len(rungs)] * 2


# dots3-note's learned-sparse kind in small, at shapes the indexer's and the sparse core's kernels take: two full
# layers (one run, one scan body) of two heads of 64 + 64 | 128 and an indexer of 8 heads of 128, top-32 of 256 keys.
DOTS3 = TransformerConfig.tiny(
    n_layers=2, n_heads=2, n_kv_heads=2, d_model=256, d_ff=256, max_seq_len=256, remat=True, remat_policy="qkv_attn",
    rope_theta=None, layer_types=("mla_sparse", "mla_sparse"), layer_ropes=(Rope(theta=1e4),) * 2,
    q_lora_rank=64, kv_lora_rank=64, qk_nope_head_dim=64, qk_rope_head_dim=64, v_head_dim=128,
    index_heads=8, index_head_dim=128, index_topk=32,
)
_INDEX_PRODUCTS = r"tensor<\d+x256x8x256xf32>"  # a block of queries' products with every key, a head each: the plain form's


def test_a_learned_sparse_layer_lowers_for_tpu_with_the_index_kernels_once_a_direction():
    """PR 67: the indexer's scores are `dsa_index_fwd` in the layer's forward
    and `dsa_index_bwd_dq` / `dsa_index_bwd_dk` in its backward, once each
    (the scan's one body; neither form names a residual, and the recompute
    needs the scores for nothing: the mask and the KL term's gradient are kept),
    under `dsa/index`; and no `[256, 8, 256]` float32 block of a query block's
    products is in the step.  One device: the target's kernel `dsa_target` sits
    under no `shard_map` (PR 66), so the layer lowers on no larger mesh."""
    text = _lowered_text(1, MeshSpec(data=1), "dp", platforms=("tpu",), cfg=DOTS3, debug_info=True, seq=256)
    kernels = _mosaic_kernels(text)
    assert {name: n for name, n in kernels.items() if name.startswith("dsa_index")} == {
        "dsa_index_fwd": 1, "dsa_index_bwd_dq": 1, "dsa_index_bwd_dk": 1}, kernels
    for kernel, path in _kernel_paths(text, "dsa_index_fwd|dsa_index_bwd_dq|dsa_index_bwd_dk"):
        assert "dsa/index" in path and kernel in path and "rematted_computation" not in path, path
    assert not re.search(_INDEX_PRODUCTS, text)


def test_the_index_kernels_lower_for_tpu_on_a_mesh_under_shard_map():
    """The op alone on four devices, its batch over `fsdp`: the scaffold's `shard_map` holds the three kernels."""
    from ray_tpu.ops import sparse_attention as sa

    mesh = build_mesh(MeshSpec(data=1, fsdp=4), devices=jax.devices()[:4])
    scores = lambda qi, ki, w: jnp.sum(sa.index_scores(qi, ki, w, mesh=mesh, batch_axes=("data", "fsdp")))
    shapes = (jax.ShapeDtypeStruct((8, 256, 8, 128), jnp.bfloat16), jax.ShapeDtypeStruct((8, 256, 128), jnp.bfloat16),
              jax.ShapeDtypeStruct((8, 256, 8), jnp.float32))
    text = jax.jit(jax.value_and_grad(scores, argnums=(0, 1, 2))).trace(*shapes).lower(lowering_platforms=("tpu",)).as_text()
    assert _mosaic_kernels(text) == {"dsa_index_fwd": 1, "dsa_index_bwd_dq": 1, "dsa_index_bwd_dk": 1}
    assert "tensor<2x8x256x128xbf16>" in text  # a device's two rows, heads first: inside the shard_map


def test_a_learned_sparse_step_lowered_for_the_cpu_holds_no_kernel_and_the_plain_forms_products():
    text = _lowered_text(1, MeshSpec(data=1), "dp", cfg=DOTS3, seq=256)
    assert "tpu_custom_call" not in text and re.search(_INDEX_PRODUCTS, text)
