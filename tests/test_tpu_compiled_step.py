"""The train step COMPILED for a described TPU v5e (libtpu, no chip), read as
optimized HLO: which arrays reach HBM.  The lowered StableHLO cannot say (a
float32 value inside a fusion and a float32 buffer look alike there); after
fusion, an instruction outside every fused computation is a buffer.

The topology is described inside a fixture and only here (one process may
hold libtpu; see `.claude/skills/verify/SKILL.md` item 3 for the same compile
by hand)."""

import contextlib
import os
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import LMTrainContext, TransformerConfig
from ray_tpu.parallel import MeshSpec, build_mesh

B, S, V = 8, 128, 384  # V unlike every other width of the configs below
COMMON = dict(max_seq_len=128, remat=True, remat_policy="qkv_attn", vocab_size=V, dtype=jnp.bfloat16)
CONFIGS = {
    "dense": dict(n_heads=2, n_kv_heads=1, d_model=256, d_ff=256),
    "expert": dict(n_heads=2, n_kv_heads=2, d_model=256, d_ff=128, n_experts=8, experts_per_token=2, qk_norm=True,
                   router_aux_loss_coef=0.01, router_z_loss_coef=0.001),
    "hybrid": dict(n_layers=4, n_heads=4, n_kv_heads=1, d_model=256, d_ff=256, tie_embeddings=True, rope_theta=None,
                   layer_types=("mamba", "mamba", "attention", "mamba"), ssm_heads=8, ssm_head_dim=64, ssm_state=128,
                   embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=8.0, attention_scale=1 / 64),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@contextlib.contextmanager
def _no_compile_cache():
    """The persistent compile cache off: an entry written without a chip cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module", params=list(CONFIGS))
def compiled_step(request, topo):
    """Optimized HLO of the step on one described chip.  The persistent
    compile cache is off around it: an entry written without a chip cannot be
    read back."""
    cfg = TransformerConfig.tiny(**COMMON, **CONFIGS[request.param])
    ctx = LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=1), devices=topo.devices[:1]), strategy="dp")
    state = jax.eval_shape(ctx._init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=ctx.batch_sharding)
    with _no_compile_cache(), ctx.mesh:
        return ctx._train_step.lower(state, {"tokens": toks, "targets": toks}).compile().as_text()


def _buffers(hlo):
    """(name, shape, opcode, op_name) of every instruction outside a fused
    computation and outside the scalar regions of reduces and scatters."""
    found, inside = [], None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            inside = head.group(1)
            continue
        if inside is None or inside.startswith(("fused_computation", "region_")):
            continue
        inst = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\(", line)
        if inst:
            path = re.search(r'op_name="([^"]*)"', line)
            found.append((inst.group(1), inst.group(2), inst.group(3), path.group(1) if path else ""))
    return found


TOKENS_BY_VOCAB = re.compile(rf"(\w+)\[(?:1,)?(?:{B},{S}|{B * S}),{V}\]")  # group 1: the element type


def test_the_reader_sees_buffers_and_not_fused_values(compiled_step):
    buffers = _buffers(compiled_step)
    assert len(buffers) > 100 and any(op == "fusion" for _, _, op, _ in buffers)
    assert "f32" in TOKENS_BY_VOCAB.findall(compiled_step)  # the float32 softmax exists: inside fusions


def test_no_float32_tokens_by_vocab_array_reaches_hbm(compiled_step):
    """The parent's step held three: the float32 log-softmax, the
    scatter-add's zero tensor and its reshape copy."""
    wide = [(name, shape) for name, shape, _, _ in _buffers(compiled_step) if "f32" in TOKENS_BY_VOCAB.findall(shape)]
    assert wide == []


def test_every_tokens_by_vocab_array_is_the_models_dtype_and_named_lm_head_or_loss(compiled_step):
    arrays = [(name, shape, path) for name, shape, op, path in _buffers(compiled_step)
              if TOKENS_BY_VOCAB.search(shape) and op not in ("get-tuple-element", "bitcast")]
    assert arrays
    for name, shape, path in arrays:
        assert set(TOKENS_BY_VOCAB.findall(shape)) == {"bf16"}, (name, shape)
        assert re.search(r"[(/](lm_head|loss)[)/]", path), (name, path)


def test_nothing_under_lm_head_or_loss_scatters(compiled_step):
    assert "scatter" in compiled_step  # the embedding's backward
    for line in compiled_step.splitlines():
        path = re.search(r'op_name="([^"]*)"', line)
        if path and re.search(r"[(/](lm_head|loss)[)/]", path.group(1)):
            assert "scatter" not in path.group(1) and " scatter(" not in line


def test_the_kda_forward_kernel_compiles_at_the_kimi_cells_shapes(topo):
    """Mosaic takes the kernel at one 16,384-token sequence of 32 heads of 128
    (interpret mode cannot say: tiling, fast memory and the rolls, transposes
    and bf16 products the TPU compiler has to accept), as ONE custom call."""
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops.pallas import kda as kernels

    one_chip = SingleDeviceSharding(topo.devices[0])
    blocks = jax.ShapeDtypeStruct((8, 1, 32, 32, 64, 128), jnp.float32, sharding=one_chip)
    beta = jax.ShapeDtypeStruct((1, 16384, 32), jnp.float32, sharding=one_chip)
    with _no_compile_cache():
        compiled = jax.jit(kernels.kda_fwd).lower(blocks, blocks, blocks, blocks, beta).compile()
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1 and "kda_fwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20  # its operands as they come: no relayout copy


def test_the_kda_backward_kernel_compiles_at_the_kimi_cells_shapes(topo):
    """The same of `kda_bwd` (PR 41), v and its cotangent in bf16 as the layer
    has them, from the states the forward writes with `pair_states` (which
    compiles as one call too): four pairs' levels kept in VMEM from their
    forward to their backward, nine float32 transposes a pair."""
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops.pallas import kda as kernels

    one_chip = SingleDeviceSharding(topo.devices[0])
    blocks = jax.ShapeDtypeStruct((8, 1, 32, 32, 64, 128), jnp.float32, sharding=one_chip)
    values = jax.ShapeDtypeStruct(blocks.shape, jnp.bfloat16, sharding=one_chip)
    beta = jax.ShapeDtypeStruct((1, 16384, 32), jnp.float32, sharding=one_chip)
    pairs = jax.ShapeDtypeStruct((8, 1, 16, 32, 128, 128), jnp.float32, sharding=one_chip)
    with _no_compile_cache():
        backward = jax.jit(kernels.kda_bwd).lower(blocks, blocks, values, blocks, beta, pairs, blocks).compile()
        with_pairs = jax.jit(lambda *a: kernels.kda_fwd(*a, pair_states=True))
        forward = with_pairs.lower(blocks, blocks, values, blocks, beta).compile()
    for compiled, name in ((backward, "kda_bwd"), (forward, "kda_fwd")):
        text = compiled.as_text()
        assert text.count("custom_call_target=\"tpu_custom_call\"") == 1 and name in text
        # no relayout copy of an operand (268 MB each); the backward's dbeta leaves as rows, 2 MB turned once
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 22
    got = [(s.shape, s.dtype) for s in jax.tree.leaves(backward.out_info)]
    like = lambda x: (x.shape, x.dtype)
    assert got == [like(blocks), like(blocks), like(values), like(blocks), ((1, 16384, 32), jnp.float32)]


def test_the_selective_scan_kernel_compiles_at_the_phi4_cells_shapes(topo):
    """Mosaic takes `s6_scan_fwd` (PR 42) at one 8,192-token sequence of 5,120
    channels and 16 states, x in bf16 and in float32, as ONE custom call whose
    full-size operands come as they are (the only copies are B and C turned
    into columns, 0.5 MB each)."""
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops.pallas import selective_scan as kernels

    one_chip = SingleDeviceSharding(topo.devices[0])
    like = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    assert kernels.supported(5120, 16, 8192, 32)
    for dtype in (jnp.bfloat16, jnp.float32):
        args = (like((1, 8192, 5120), dtype), like((1, 8192, 5120), jnp.float32), like((16, 5120), jnp.float32),
                like((1, 8192, 16), dtype), like((1, 8192, 16), dtype), like((5120,), jnp.float32))
        with _no_compile_cache():
            compiled = jax.jit(kernels.s6_scan_fwd).lower(*args).compile()
        text = compiled.as_text()
        assert text.count("custom_call_target=\"tpu_custom_call\"") == 1 and "s6_scan_fwd" in text
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 22
        got = [(s.shape, s.dtype) for s in jax.tree.leaves(compiled.out_info)]
        assert got == [((1, 8192, 5120), dtype), ((256, 1, 16, 5120), jnp.float32)]

def test_the_selective_scans_backward_kernel_compiles_at_the_phi4_cells_shapes(topo):
    """Mosaic takes `s6_scan_bwd` (PR 51) at the same shapes as ONE custom
    call: x, dt, y's cotangent and the entering states come as they are, the
    cotangents leave in their arguments' dtypes, and what is temporary is B's
    and C's partial sums a channel block (21 MB each) and their columns."""
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops.pallas import selective_scan as kernels

    one_chip = SingleDeviceSharding(topo.devices[0])
    like = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    for dtype in (jnp.bfloat16, jnp.float32):
        args = (like((1, 8192, 5120), dtype), like((1, 8192, 5120), jnp.float32), like((16, 5120), jnp.float32),
                like((1, 8192, 16), dtype), like((1, 8192, 16), dtype), like((5120,), jnp.float32),
                like((256, 1, 16, 5120), jnp.float32), like((1, 8192, 5120), dtype))
        with _no_compile_cache():
            compiled = jax.jit(kernels.s6_scan_bwd).lower(*args).compile()
        text = compiled.as_text()
        assert text.count("custom_call_target=\"tpu_custom_call\"") == 1 and "s6_scan_bwd" in text
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 26
        got = [(s.shape, s.dtype) for s in jax.tree.leaves(compiled.out_info)]
        assert got == [((1, 8192, 5120), dtype), ((1, 8192, 5120), jnp.float32), ((16, 5120), jnp.float32),
                       ((1, 8192, 16), dtype), ((1, 8192, 16), dtype), ((5120,), jnp.float32)]


def test_the_hybrid_steps_scan_is_two_kernels_under_ssm_scan_and_no_chunk_by_chunk_array_reaches_hbm(request, compiled_step):
    """PR 49.  Compiled, the ops behind the scan's own `jax.jit`s carry the
    whole path: `ssd_fwd` (forward and recompute) and `ssd_bwd` under
    `layer/attn_core/ssm/scan`, where `benchmarks/lib/trace_ssm.py` reads them;
    and the plain form's [.., chunk, chunk] masks, scores and cotangents (the
    chunk is the 128 positions here) are no buffer of the step."""
    if request.node.callspec.params["compiled_step"] != "hybrid":
        pytest.skip("the Mamba-2 step alone")
    calls = [path for _, _, op, path in _buffers(compiled_step) if op == "custom-call" and "/ssd_" in path]
    assert calls and all("layer/attn_core/ssm/scan/" in path for path in calls)
    assert any("rematted_computation" in path and "ssd_fwd" in path for path in calls)
    assert any(path.startswith("jit(_train_step)/transpose(") and "ssd_bwd/pallas_call" in path for path in calls)
    scan = [(name, shape) for name, shape, op, path in _buffers(compiled_step) if "ssm/scan" in path]
    # per head [b, chunks, heads, t, s]; the kernels' float32 dB and dC partials [b, programs, S, N] are 128 x 128 here too
    assert scan and not [(name, shape) for name, shape in scan if re.search(rf"\[\d+,\d+,\d+,{S},{S}\]", shape)]
    # the plain form's two copies of y a direction (its transpose out of [b, c, h, t, p] and its retile) are gone;
    # what XLA still copies under the name is ONE array a kernel call, y or dx into the positions-minor layout
    # its neighbours (the convolution's kernels, [b, C, S]) made it choose
    wide = [(name, shape) for name, shape, op, path in _buffers(compiled_step) if "ssm/scan" in path and op == "copy"
            and re.search(rf"bf16\[{B},{S},512\]|bf16\[{B},{S},8,64\]", shape)]
    assert len(wide) <= len(calls)


@pytest.mark.parametrize("groups", [None, 8], ids=["granite", "nemotron"])
def test_the_ssd_kernels_compile_at_the_two_cells_shapes(topo, groups):
    """Mosaic takes `ssd_fwd` and `ssd_bwd` (PR 49) at one 8,192-token sequence
    of 64 heads of 64, state 128, chunk 256, one group of B and C (16 heads a
    program) and eight (8), each as ONE custom call, beside XLA's running sums
    and small reductions, and no [.., 256, 256] array among the buffers.  (Alone,
    x, y and their cotangents are copied between [.., 64, 64] and [.., 4096],
    whose tiled layouts differ; in a step they come from and go to [b, S, 4096]
    arrays and the reshapes cancel: `test_the_hybrid_steps_scan_...`.)"""
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops import ssm as op

    one_chip = SingleDeviceSharding(topo.devices[0])
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    group = (1, 8192, 128) if groups is None else (1, 8192, groups, 128)
    args = (shaped((1, 8192, 64, 64), jnp.bfloat16), shaped((1, 8192, 64), jnp.float32), shaped((64,), jnp.float32),
            shaped(group, jnp.bfloat16), shaped(group, jnp.bfloat16), shaped((64,), jnp.float32))
    entering = shaped((1, 32, 128, 4096), jnp.float32)
    with _no_compile_cache():
        forward = jax.jit(lambda *a: op._kernel_forward(*a, 256)).lower(*args).compile()
        backward = jax.jit(lambda *a: op._kernel_backward(*a, 256)).lower(*args, entering, args[0]).compile()
    for compiled, name in ((forward, "ssd_fwd"), (backward, "ssd_bwd")):
        text = compiled.as_text()
        assert text.count("custom_call_target=\"tpu_custom_call\"") == 1 and name in text
        buffers = _buffers(text)
        assert not [shape for _, shape, _, _ in buffers if re.search(r"\[[\d,]*256,256\]", shape)]
    got = [(s.shape, s.dtype) for s in jax.tree.leaves(backward.out_info)]
    assert got == [(a.shape, a.dtype) for a in args]


@pytest.mark.parametrize("kind,held,k,want", [("swiglu", 2, 2, (512, 1024, 2048)), ("relu2", 2, 2, (512, 1024, 2048)),
                                              ("swiglu", 8, 2, (1536, 2048))],
                         ids=["swiglu", "relu2", "swiglu-one-assignment-a-token"])
def test_a_share_of_the_experts_compiles_with_one_switch_a_direction_of_at_most_four_rungs(topo, kind, held, k, want):
    """The step of a model that holds 2 of its 16 experts (the two share cells
    in small: three matrices an expert, and two), compiled for the v5e: the
    share's row buffers take one of `moe._rungs`' sizes (PR 48), so the scan
    body holds the forward's `conditional` and the backward's (the
    recompute's is dead: its residuals are the layer's inputs), one branch a
    rung, the grouped-matmul kernels in every branch and none refused.  And of
    one that holds 8 of 16, one assignment a token, whose ladder is 1.25x its
    uniform 1,024 rows and all (PR 53: the rung `mellum2` takes, in small)."""
    from ray_tpu.models import moe
    from ray_tpu.ops.grouped_matmul import REFUSED_SCOPE

    rungs = moe._rungs(B * S * k, held, 16, k)
    assert rungs == want
    cfg = TransformerConfig.tiny(**COMMON, n_layers=2, n_heads=2, n_kv_heads=2, d_model=256, d_ff=256, moe_d_ff=128, n_experts=16,
                                 experts_per_token=k, n_experts_held=held, n_shared_experts=1, expert_kind=kind)
    ctx = LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=1), devices=topo.devices[:1]), strategy="dp")
    state = jax.eval_shape(ctx._init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=ctx.batch_sharding)
    with _no_compile_cache(), ctx.mesh:
        text = ctx._train_step.lower(state, {"tokens": toks, "targets": toks}).compile().as_text()
    switches = re.findall(r" conditional\(.*branch_computations=\{([^}]*)\}", text)
    assert [len(branches.split(",")) for branches in switches] == [len(rungs)] * 2 and len(rungs) <= 4
    per_rung = {"swiglu": 8 + 3, "relu2": 5 + 2}[kind]  # moe_gmm + moe_tgmm: forward, the activation again, both gradients
    assert len(re.findall(r'custom_call_target="tpu_custom_call".*moe_t?gmm', text)) == per_rung * len(rungs)
    assert REFUSED_SCOPE not in text


def test_the_mellum_cells_window_kernels_and_grouped_matmuls_compile_at_its_shapes(topo):
    """PR 50: no new kernel, two shapes no cell had.  One 16,384-token
    sequence of 32 q heads of 128 over 4 K/V heads under a window of 1,024:
    tiles of 1024 (512 keys in the dkv kernel), grids that leave the tiles
    outside the window out, three custom calls forward + backward.  And the
    grouped matmuls at 2304 x 896 (k tiled by 256, n whole) on the lower rung's
    65,536 rows of 16 held experts, both ways round, none refused."""
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops.grouped_matmul import REFUSED_SCOPE, grouped_matmul
    from ray_tpu.ops.pallas import flash_attention as fa

    one_chip = SingleDeviceSharding(topo.devices[0])
    shaped = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    q, kv = shaped((1, 16384, 32, 128)), shaped((1, 16384, 4, 128))
    grad = jax.grad(lambda q, k, v, do: jnp.sum((fa.flash_attention(q, k, v, window=1024) * do).astype(jnp.float32)),
                    argnums=(0, 1, 2))
    with _no_compile_cache():
        text = jax.jit(grad).lower(q, kv, kv, q).compile().as_text()
        rows, sizes = shaped((65536, 2304)), shaped((16,), jnp.int32)
        up = jax.jit(grouped_matmul).lower(rows, shaped((16, 2304, 896)), sizes).compile().as_text()
        down = jax.jit(grouped_matmul).lower(shaped((65536, 896)), shaped((16, 896, 2304)), sizes).compile().as_text()
    kernels = re.findall(r'custom_call_target="tpu_custom_call"', text)
    assert len(kernels) == 3 and all(name in text for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))
    for compiled in (up, down):
        assert compiled.count('custom_call_target="tpu_custom_call"') == 1 and "moe_gmm" in compiled
        assert REFUSED_SCOPE not in compiled


def test_the_glm_cells_flash_kernels_at_heads_of_256_and_grouped_matmuls_compile_at_its_shapes(topo):
    """PR 54: no new kernel, heads no cell had.  One 8,192-token sequence of
    20 heads of 256 / 256: the forward kernel at its 1024 x 1024 tile scoped
    16.47 MB of VMEM and libtpu refused it (its limit is 16 MB), so
    `_head_blocks` halves the key tile; the backward's 1024 x 512 fits as it
    is.  Three custom calls forward + backward.  And the grouped matmuls at
    2048 x 1536 on the lower rung's 10,240 rows of 16 held experts, both ways
    round, none refused."""
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops.grouped_matmul import REFUSED_SCOPE, grouped_matmul
    from ray_tpu.ops.pallas import flash_attention as fa

    one_chip = SingleDeviceSharding(topo.devices[0])
    shaped = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    q = shaped((1, 8192, 20, 256))

    def grad(**blocks):
        return jax.grad(lambda q, k, v, do: jnp.sum((fa.flash_attention(q, k, v, **blocks) * do).astype(jnp.float32)),
                        argnums=(0, 1, 2))

    with _no_compile_cache():
        text = jax.jit(grad()).lower(q, q, q, q).compile().as_text()
        # Until PR 70 libtpu refused the forward at 1024 x 1024 here (16.47 of 16 MB of scoped VMEM: what the tile's choice
        # was made for).  Since PR 70 the diagonal's mask is a constant of the kernel (`_edge_mask`'s `lead`: no int32
        # position arrays a tile wide) and it compiles, by a margin nobody has measured: the halved key tile stays until a
        # sweep on the chip says otherwise (ROADMAP Speed 2f).
        full = jax.jit(lambda q, k, v: fa._flash(q, k, v, True, 256 ** -0.5, 1024, 1024, 1024, 512, None)).lower(q, q, q).compile()
        assert "flash_fwd" in full.as_text()
        rows, sizes = shaped((10240, 2048)), shaped((16,), jnp.int32)
        up = jax.jit(grouped_matmul).lower(rows, shaped((16, 2048, 1536)), sizes).compile().as_text()
        down = jax.jit(grouped_matmul).lower(shaped((10240, 1536)), shaped((16, 1536, 2048)), sizes).compile().as_text()
    kernels = re.findall(r'custom_call_target="tpu_custom_call"', text)
    assert len(kernels) == 3 and all(name in text for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))
    for compiled in (up, down):
        assert compiled.count('custom_call_target="tpu_custom_call"') == 1 and "moe_gmm" in compiled
        assert REFUSED_SCOPE not in compiled


def test_the_qwen3_next_cells_scan_kernels_and_grouped_matmuls_compile_at_its_shapes(topo):
    """A delta layer's recurrence through `gdn_chunked` as the layer calls it
    (PR 58: 16 key heads and 32 value heads of 128 / 128 at one 8,192-token
    sequence, q and k unrepeated, one decay a value head): `gdn_fwd` forward
    and for the backward, `gdn_bwd`, under `gdn/scan`, and no per-channel
    kernel (PR 57 ran the layer through `kda_fwd` / `kda_bwd`).  And the grouped
    matmuls at 2048 x 512 on the lowest rung's 10,240 rows of 32 held experts
    (320 rows an expert at most there: under one 512-row tile), both ways
    round, none refused."""
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops.gdn import gdn_chunked
    from ray_tpu.ops.grouped_matmul import REFUSED_SCOPE, grouped_matmul

    one_chip = SingleDeviceSharding(topo.devices[0])
    shaped = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    keys, values = shaped((1, 8192, 16, 128), jnp.float32), shaped((1, 8192, 32, 128))
    decay = shaped((1, 8192, 32), jnp.float32)

    def scan(q, k, v, g, beta, d_o):
        return jax.grad(lambda *a: jnp.sum(gdn_chunked(*a) * d_o), argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)

    with _no_compile_cache():
        lowered = jax.jit(scan).lower(keys, keys, values, decay, decay, shaped((1, 8192, 32, 128), jnp.float32))
        compiled = lowered.compile()
        text = compiled.as_text()
        rows, sizes = shaped((10240, 2048)), shaped((32,), jnp.int32)
        up = jax.jit(grouped_matmul).lower(rows, shaped((32, 2048, 512)), sizes).compile().as_text()
        down = jax.jit(grouped_matmul).lower(shaped((10240, 512)), shaped((32, 512, 2048)), sizes).compile().as_text()
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) == 2 and "gdn_fwd" in text and "gdn_bwd" in text
    assert "kda_fwd" not in text and "kda_bwd" not in text
    assert "gdn/scan" in lowered.as_text(debug_info=True) and "kda/scan" not in lowered.as_text(debug_info=True)
    got = [(s.shape, s.dtype) for s in jax.tree.leaves(compiled.out_info)]
    assert got == [(x.shape, x.dtype) for x in (keys, keys, values, decay, decay)]  # dq and dk summed over a group
    for compiled in (up, down):
        assert compiled.count('custom_call_target="tpu_custom_call"') == 1 and "moe_gmm" in compiled
        assert REFUSED_SCOPE not in compiled


@pytest.mark.parametrize("s,cx,hq,hk,cv", [(16384, 12288, 32, 32, 4096), (8192, 12288, 16, 16, 4096)], ids=["kimi-linear", "qwen3-next"])
def test_the_delta_layers_convolution_compiles_at_both_cells_shapes_and_nothing_leaves_positions_major(topo, s, cx, hq, hk, cv):
    """PR 60: `delta_conv` as the two delta layers call it (Kimi Linear: the
    whole fused projection, 32 + 32 + 32 heads of 128 at 16,384 positions;
    Qwen3-Next: the first 8,192 of `gdn_qkvz`'s 12,288 columns, 16 + 16 key
    heads and 32 value heads at 8,192), forward and backward: `delta_conv_fwd`
    and `delta_conv_bwd` and none of Mamba-2's kernels; no array of the
    sequence's length is laid out with the sequence minor ([., C, S] order: what
    `ssm_conv_*` wanted and a delta layer paid two relayouts a direction for),
    and the kernels read x itself, no slice of it."""
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops.delta_conv import delta_conv

    one_chip = SingleDeviceSharding(topo.devices[0])
    shaped = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    f32 = jnp.float32
    args = shaped((1, s, cx)), shaped((hq, 128, 4), f32), shaped((hk, 128, 4), f32), shaped((cv, 4), f32)
    probe = shaped((1, s, hq, 128), f32), shaped((1, s, hk, 128), f32), shaped((1, s, cv))

    def both(x, wq, wk, wv, dq, dk, dv):
        out, vjp = jax.vjp(delta_conv, x, wq, wk, wv)
        return out, vjp((dq, dk, dv))

    with _no_compile_cache():
        compiled = jax.jit(both).lower(*args, *probe).compile()
    text = compiled.as_text()
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) == 2 and "ssm_conv" not in text
    for kernel in ("delta_conv_fwd", "delta_conv_bwd"):
        (call,) = [line for line in text.splitlines() if re.search(rf"%{kernel}(\.\d+)? = ", line)]
        assert re.search(r"custom-call\(%x(\.\d+)?, %x(\.\d+)?,", call), call  # x as it came in, and its halo(s)
    assert not re.findall(r"\[1,\d{4,},\d{4,}\]\{1,2,0", text)  # a full-size array in any order but positions-major
    got = [(o.shape, o.dtype) for o in jax.tree.leaves(compiled.out_info)]
    assert got == [(p.shape, p.dtype) for p in probe] + [(a.shape, a.dtype) for a in args]


def test_a_block_diffusion_step_holds_the_flash_kernels_under_its_mask_and_no_rows_by_rows_array(topo):
    """PR 62: a small block-diffusion step (640 tokens, 1,280 rows `[x_t | x_0]`, blocks of 4) compiled for the
    described chip.  Its core is the three flash kernels under `attn/block_diffusion`, one call a layer and
    direction (a scan body holds each once), and no float array of rows x rows, of a copy x a copy or of one by the
    other reaches HBM: the mask is worked out inside the tiles from the rows' indices."""
    seq = 640
    cfg = TransformerConfig.tiny(n_heads=2, n_kv_heads=1, d_model=256, d_ff=256, attn_head_dim=128, max_seq_len=seq,
                                 remat=True, remat_policy="qkv_attn", vocab_size=V, dtype=jnp.bfloat16, diffusion_block=4)
    ctx = LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=1), devices=topo.devices[:1]), strategy="dp")
    state = jax.eval_shape(ctx._init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((1, seq), jnp.int32, sharding=ctx.batch_sharding)
    with _no_compile_cache(), ctx.mesh:
        text = ctx._train_step.lower(state, {"tokens": toks, "targets": toks}).compile().as_text()
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 3 and all("attn/block_diffusion" in line for line in kernels)
    assert all(any(name in line for line in kernels) for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))
    square = re.compile(rf"(?:f32|bf16)\[(?:\d+,)*(?:{seq}|{2 * seq}),(?:{seq}|{2 * seq})\]")
    assert [(name, shape) for name, shape, _, _ in _buffers(text) if square.search(shape)] == []
    assert "diffusion/noise" in text


def test_the_sdar_cells_flash_kernels_compile_at_its_shapes(topo):
    """PR 62: no new kernel, one mask no cell had.  The 16,384 rows of one 8,192-token sequence (noisy copy beside
    clean copy), 32 q heads of 128 over 4 K/V heads, blocks of 4: tiles of 1024 (512 keys in the backward), grids
    of 9 / 18 / 16 inner steps that walk the visible tiles alone, three custom calls forward + backward; and the
    plain forward's block-causal call over one copy."""
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops.attention import BlockDiffusion
    from ray_tpu.ops.pallas import flash_attention as fa

    one_chip = SingleDeviceSharding(topo.devices[0])
    shaped = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    q, kv = shaped((1, 16384, 32, 128)), shaped((1, 16384, 4, 128))
    grad = jax.grad(lambda q, k, v, do: jnp.sum(
        (fa.flash_attention(q, k, v, block_diffusion=BlockDiffusion(4, 8192)) * do).astype(jnp.float32)), argnums=(0, 1, 2))
    with _no_compile_cache():
        text = jax.jit(grad).lower(q, kv, kv, q).compile().as_text()
        plain = jax.jit(lambda q, k, v: fa.flash_attention(q, k, v, block_diffusion=BlockDiffusion(4))).lower(
            shaped((1, 8192, 32, 128)), shaped((1, 8192, 4, 128)), shaped((1, 8192, 4, 128))).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert all(name in text for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))
    assert plain.count('custom_call_target="tpu_custom_call"') == 1 and "flash_fwd" in plain


def test_the_dots3_cells_sparse_core_and_window_kernels_compile_at_its_shapes(topo):
    """PR 66.  One 8,192-token sequence of the 16 held heads of 192 | 128 over
    an int8 selection [8192, 8192]: the sparse core's forward, its two
    backward kernels and the target's kernel (`ops/pallas/sparse_attention.py`,
    512 x 512 tiles, the mask's tile one more operand), four custom calls; and
    the flash kernels at the sliding kind's 8 held heads of 256 | 128 under a
    window of 513, which is a multiple of no tile (tiles of 512, two key tiles
    a query tile), three more."""
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops import sparse_attention as sa
    from ray_tpu.ops.pallas import flash_attention as fa

    one_chip = SingleDeviceSharding(topo.devices[0])
    shaped = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    q, v, mask = shaped((1, 8192, 16, 192)), shaped((1, 8192, 16, 128)), shaped((1, 8192, 8192), jnp.int8)

    def sparse(q, k, v, mask, do):
        (out, lse), back = jax.vjp(lambda q, k, v: sa.selected_attention(q, k, v, mask), q, k, v)
        return back((do, jnp.zeros_like(lse))), sa.head_mean_probs(q, k, lse, mask)

    windowed = jax.grad(lambda q, k, v, do: jnp.sum((fa.flash_attention(q, k, v, window=513) * do).astype(jnp.float32)), argnums=(0, 1, 2))
    wq, wv = shaped((1, 8192, 8, 256)), shaped((1, 8192, 8, 128))
    with _no_compile_cache():
        text = jax.jit(sparse).lower(q, q, v, mask, v).compile().as_text()
        window_text = jax.jit(windowed).lower(wq, wq, wv, wv).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert all(name in text for name in ("dsa_attn_fwd", "dsa_attn_bwd_dq", "dsa_attn_bwd_dkv", "dsa_target"))
    assert window_text.count('custom_call_target="tpu_custom_call"') == 3


def test_the_dots3_cells_index_kernels_compile_at_its_shapes(topo):
    """PR 67.  One 8,192-token sequence of the indexer's 64 heads of 128 over
    one key a position: the scores' forward (a query tile's 64 heads, 4 MB, in
    VMEM twice beside a `[512, 256]` float32 tile) and the backward's two
    kernels (the query-tile kernel holds q, dq twice and a float32 dq: 25 MB
    of the 48 MB it asks for), three custom calls, and no `[256, 64, 8192]`
    block of a query block's products in the compiled text."""
    import re

    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops import sparse_attention as sa

    one_chip = SingleDeviceSharding(topo.devices[0])
    shaped = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731

    def both(qi, ki, w, d_scores):
        scores, back = jax.vjp(sa.index_scores, qi, ki, w)
        return scores, back(d_scores)

    with _no_compile_cache():
        text = jax.jit(both).lower(shaped((1, 8192, 64, 128)), shaped((1, 8192, 128)), shaped((1, 8192, 64), jnp.float32),
                                   shaped((1, 8192, 8192), jnp.float32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert all(name in text for name in ("dsa_index_fwd", "dsa_index_bwd_dq", "dsa_index_bwd_dk"))
    assert not re.search(r"\[(?:\d+,)*256,64,8192\]", text)  # the plain form's block of 256 queries: f32 products, pred compares


def test_a_cca_step_holds_the_flash_kernels_at_its_latent_heads_and_the_names_of_its_mixing_and_router(topo):
    """PR 68: a small ZAYA1-shaped step (512 tokens, 4 query / 2 key heads of 128 in a latent of 512 | 256 under a
    stream of 256, two taps a convolution, top-1 of 4 experts behind the network router, learned joins, a tied head)
    compiled for the described chip.  Its core is the three flash kernels under `layer/attn_core`, once a scan body
    and direction; the mixing around it is plain XLA under `cca/mix` (no Mosaic kernel there), the projections under
    `cca/proj`, the whole router under `moe/router`; and the router's state [512, 64] float32 is the one array beside
    the stream that every layer's body takes and returns."""
    seq = 512
    cfg = TransformerConfig.tiny(
        n_layers=3, layer_types=("cca",) * 3, n_heads=4, n_kv_heads=2, attn_head_dim=128, d_model=256, rotary_dim=64, rope_theta=5e6,
        tie_embeddings=True, n_experts=4, experts_per_token=1, moe_d_ff=128, router_kind="mlp", router_hidden=64,
        residual_scaling=True, max_seq_len=seq, remat=True, remat_policy="qkv_attn", vocab_size=V, dtype=jnp.bfloat16)
    ctx = LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=1), devices=topo.devices[:1]), strategy="dp")
    state = jax.eval_shape(ctx._init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((1, seq), jnp.int32, sharding=ctx.batch_sharding)
    with _no_compile_cache(), ctx.mesh:
        text = ctx._train_step.lower(state, {"tokens": toks, "targets": toks}).compile().as_text()
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    flash = [line for line in kernels if "layer/attn_core" in line]
    assert len(flash) == 3 and all(any(name in line for line in flash) for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))
    assert not any("cca/mix" in line or "cca/proj" in line or "moe/router" in line for line in kernels)
    for name in ("layer/attn_proj/cca/proj", "layer/attn_proj/cca/mix", "layer/mlp/moe/router"):
        assert name in text, name
    assert re.search(rf"f32\[1,{seq},64\]", text)  # the carried router state
