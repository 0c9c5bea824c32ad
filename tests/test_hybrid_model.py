"""The hybrid decoder (Granite 4.0-H's stack: Mamba-2 and NoPE attention layers
in one model, muP multipliers, tied head) against its plain float32 reference
(`benchmarks/lib/reference_hybrid.py`: the recurrence token by token), at tiny
widths on the CPU: logits, the objective, every gradient leaf, the chunked
scan against the recurrence across chunk lengths and under a decay that
underflows, causality across chunk boundaries, each multiplier, no rotary
embedding, the dense and expert programs left as they were, `fsdp=4` on the
CPU mesh, and what `pp` and an expert hybrid are told.

TOLERANCE: program and reference are both float32 and compute the same
function in another order (chunked masked products against a pass over
tokens), so they agree to float32 rounding accumulated over S = 96 steps:
1e-4 of each tensor's scale (measured: 9e-7 at the scan's output).  A bf16
decay (8 bits: `exp(dt*A)` off by up to 0.4% per step, compounding along the
chunk) misses it by 20 times at the scan's output;
`test_a_bf16_decay_is_outside_the_tolerance` shows that.

Weights are seeded through the program's own `init_state`; the norm scales
and `D` (which start at one) and the convolution's bias (zero) are then drawn
at random so a leaf that is never applied cannot pass.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)  # `benchmarks.lib` resolves from this checkout

from benchmarks.lib import reference_hybrid  # noqa: E402
from ray_tpu.models import LMTrainContext, TransformerConfig  # noqa: E402
from ray_tpu.models import transformer  # noqa: E402
from ray_tpu.models.mixers import MIXERS, attention  # noqa: E402
from ray_tpu.ops import kernel_pair, ssm  # noqa: E402
from ray_tpu.ops.pallas import ssm_conv  # noqa: E402
from ray_tpu.parallel import MeshSpec, build_mesh  # noqa: E402

SEQ = 96  # three chunks of 32, and not a multiple of 64
KINDS = ("mamba", "mamba", "attention", "mamba")  # two runs of one kind around a run of the other
BASE = dict(
    vocab_size=128, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2, d_ff=96, max_seq_len=SEQ,
    dtype=jnp.float32, param_dtype=jnp.float32, remat=False, tie_embeddings=True, rope_theta=None,
    layer_types=KINDS, ssm_heads=2, ssm_head_dim=16, ssm_state=8, ssm_conv=4,
    embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=8.0, attention_scale=1 / 16,
)
RTOL = 1e-4
REDRAWN = ("ln1", "ln2", "final_norm", "norm", "D", "conv_b")


@pytest.fixture(scope="module", autouse=True)
def chunk_of_32():
    """The program's chunk for this module: S = 96 crosses two boundaries."""
    patch = pytest.MonkeyPatch()
    patch.setattr(ssm, "CHUNK", 32)
    yield
    patch.undo()


def reference_config(cfg: TransformerConfig) -> dict:
    """The published key names `reference_hybrid` reads, from a TransformerConfig."""
    return {
        "num_hidden_layers": cfg.n_layers, "layer_types": list(cfg.layer_types), "rms_norm_eps": cfg.norm_eps,
        "attention_multiplier": cfg.attention_scale, "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier, "logits_scaling": cfg.logits_scaling,
        "position_embedding_type": "nope" if cfg.rope_theta is None else "rope", "rope_theta": cfg.rope_theta,
        "tie_word_embeddings": cfg.tie_embeddings,
    }


def one_device_ctx(cfg):
    return LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), strategy="dp")


def seeded_params(ctx, seed=0):
    params = ctx.init_state(seed)["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def draw(path, leaf):
        if path[-1].key in REDRAWN:
            return (leaf + 0.3 * jax.random.normal(next(keys), leaf.shape)).astype(leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(draw, params)


def batch_of(cfg, seed=0, batch=2, seq=SEQ):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    return {"tokens": jnp.asarray(toks[:, :-1]), "targets": jnp.asarray(toks[:, 1:])}


def close(got, want, rtol=RTOL):
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(got), want, rtol=rtol, atol=rtol * np.abs(want).max())


@pytest.fixture(scope="module")
def tiny(chunk_of_32):
    """Program and reference on the same float32 weights and batch."""
    cfg = TransformerConfig(**BASE)
    ctx = one_device_ctx(cfg)
    params, batch = seeded_params(ctx), batch_of(cfg)
    rcfg = reference_config(cfg)
    (loss, _), grads = jax.jit(jax.value_and_grad(ctx._loss, has_aux=True))(params, batch)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: reference_hybrid.objective(rcfg, p, batch["tokens"], batch["targets"]))(params)
    return dict(cfg=cfg, ctx=ctx, params=params, batch=batch, rcfg=rcfg,
                logits=ctx.apply(params, batch["tokens"]),
                ref_logits=reference_hybrid.logits(rcfg, params, batch["tokens"], last=SEQ),
                loss=loss, grads=grads, ref_loss=ref_loss, ref_grads=ref_grads)


# -- forward, objective, gradients ------------------------------------------------------


def test_the_stack_is_three_runs_and_the_parameters_two_stacks(tiny):
    cfg, params = tiny["cfg"], tiny["params"]
    assert cfg.layer_runs() == (("mamba", "dense", 0, 2), ("attention", "dense", 0, 1), ("mamba", "dense", 2, 1))
    assert params["layers"]["attn"]["wq"].shape[0] == 1 and params["mamba_layers"]["ssm"]["in_proj"].shape[0] == 3


def test_logits_equal_the_reference(tiny):
    close(tiny["logits"], tiny["ref_logits"])


def test_objective_equals_the_reference(tiny):
    np.testing.assert_allclose(float(tiny["loss"]), float(tiny["ref_loss"]), rtol=1e-5)


LEAVES = ["embed/tokens", "final_norm",
          "layers/attn/wq", "layers/attn/wk", "layers/attn/wv", "layers/attn/wo",
          "layers/mlp/w_gate", "layers/mlp/w_up", "layers/mlp/w_down", "layers/ln1", "layers/ln2",
          "mamba_layers/ssm/in_proj", "mamba_layers/ssm/conv_w", "mamba_layers/ssm/conv_b",
          "mamba_layers/ssm/dt_bias", "mamba_layers/ssm/A_log", "mamba_layers/ssm/D",
          "mamba_layers/ssm/norm", "mamba_layers/ssm/out_proj",
          "mamba_layers/mlp/w_gate", "mamba_layers/mlp/w_up", "mamba_layers/mlp/w_down",
          "mamba_layers/ln1", "mamba_layers/ln2"]


def test_the_leaf_list_is_every_leaf(tiny):
    paths = {"/".join(k.key for k in path) for path, _ in jax.tree_util.tree_flatten_with_path(tiny["params"])[0]}
    assert paths == set(LEAVES)


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_equals_the_reference(tiny, leaf):
    got, want = tiny["grads"], tiny["ref_grads"]
    for key in leaf.split("/"):
        got, want = got[key], want[key]
    assert np.abs(np.asarray(want)).max() > 0  # the leaf is used
    close(got, want)


@pytest.mark.parametrize("remat_policy", ["qkv_attn", "attn", None])
def test_every_gradient_leaf_under_each_remat_policy_equals_the_unchecked_steps(tiny, remat_policy):
    """What a policy saves changes which forward values the backward reads,
    never a value: same matmuls, dtypes and order (float32 rounding where XLA
    fuses the two programs differently)."""
    ctx = one_device_ctx(dataclasses.replace(tiny["cfg"], remat=True, remat_policy=remat_policy))
    (loss, _), grads = jax.jit(jax.value_and_grad(ctx._loss, has_aux=True))(tiny["params"], tiny["batch"])
    np.testing.assert_allclose(float(loss), float(tiny["loss"]), rtol=1e-6)
    got, want = (dict(jax.tree_util.tree_flatten_with_path(g)[0]) for g in (grads, tiny["grads"]))
    assert len(got) == len(LEAVES)
    for path, leaf in got.items():
        close(leaf, want[path], rtol=1e-5)


def test_qkv_attn_saves_a_mamba_layers_two_named_residuals_and_nothing_wide_in_float32(tiny):
    """One Mamba-2 layer in the model's dtype of the cells (bf16) under the
    policy: beside its arguments it keeps `in_proj`'s one [B, S, 2·inner + 2·N
    + heads] array and the [B, S, d] stream after `out_proj`, and no float32
    [B, S, features] array; under `attn` nothing of the layer's own."""
    from jax._src.ad_checkpoint import saved_residuals  # the list `jax.ad_checkpoint.print_saved_residuals` prints

    cfg = dataclasses.replace(tiny["cfg"], dtype=jnp.bfloat16, remat=True, remat_policy="qkv_attn")
    layer = jax.tree_util.tree_map(lambda a: a[0], tiny["params"]["mamba_layers"])
    x = jnp.zeros((2, SEQ, cfg.d_model), jnp.bfloat16)
    inner = cfg.ssm_heads * cfg.ssm_head_dim

    def saved(config):
        run = jax.checkpoint(lambda p, x: transformer.layer(MIXERS["mamba"], x, p, None, config, None)[0],
                             policy=transformer._remat_policy(config))
        return [(aval.shape, aval.dtype) for aval, why in saved_residuals(run, layer, x)
                if "from the argument" not in why]

    assert sorted(saved(cfg)) == [
        ((2, SEQ, cfg.d_model), jnp.bfloat16), ((2, SEQ, 2 * inner + 2 * cfg.ssm_state + cfg.ssm_heads), jnp.bfloat16)]
    assert saved(dataclasses.replace(cfg, remat_policy="attn")) == []


def _primitives(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its parameters."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _primitives(inner)


# -- the scan alone ---------------------------------------------------------------------


def _recurrence(x, dt, A, B, C, D):
    """The recurrence of `ops/ssm.py`'s docstring, token by token."""
    def step(state, inp):
        xt, dtt, bt, ct = inp
        state = jnp.exp(dtt * A)[..., None, None] * state + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :]
        return state, jnp.einsum("bhpn,bn->bhp", state, ct) + D[:, None] * xt

    b, _, h, p = x.shape
    _, y = jax.lax.scan(step, jnp.zeros((b, h, p, B.shape[-1])),
                        (x.swapaxes(0, 1), dt.swapaxes(0, 1), B.swapaxes(0, 1), C.swapaxes(0, 1)))
    return y.swapaxes(0, 1)


def _scan_inputs(seed=0, b=2, s=SEQ, h=3, p=16, n=8, a_scale=1.0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (b, s, h, p)), jax.nn.softplus(jax.random.normal(k[1], (b, s, h))),
            -a_scale * jnp.exp(jax.random.normal(k[2], (h,))), jax.random.normal(k[3], (b, s, n)),
            jax.random.normal(k[4], (b, s, n)), jax.random.normal(k[5], (h,)))


@pytest.mark.parametrize("chunk", [16, 32, SEQ])
@pytest.mark.parametrize("a_scale", [1.0, 200.0], ids=["mild", "underflowing"])
def test_ssd_chunked_equals_the_recurrence(chunk, a_scale):
    """`underflowing`: dt*A near -200 a step, so `exp(cum)` is 0 in float32
    after one position and a quotient of exponentials would be 0/0."""
    args = _scan_inputs(a_scale=a_scale)
    with jax.default_matmul_precision("highest"):
        want = _recurrence(*args)
        got = ssm.ssd_chunked(*args, chunk=chunk)
        d_got = jax.grad(lambda x, dt: jnp.sum(ssm.ssd_chunked(x, dt, *args[2:], chunk=chunk) ** 2),
                         argnums=(0, 1))(*args[:2])
        d_want = jax.grad(lambda x, dt: jnp.sum(_recurrence(x, dt, *args[2:]) ** 2), argnums=(0, 1))(*args[:2])
    assert np.all(np.isfinite(np.asarray(got)))
    close(got, want)
    for g, w in zip(d_got, d_want):
        assert np.all(np.isfinite(np.asarray(g)))
        close(g, w)


def test_a_bf16_decay_is_outside_the_tolerance():
    """The scan with `dt` and `A` rounded to bf16 (8 bits: each `exp(dt*A)` off
    by up to 0.4%, compounding along the chunk): what the tolerance exists to
    catch, measured 2e-3 of the output's scale against 9e-7 in float32."""
    args = _scan_inputs()
    rounded = [v.astype(jnp.bfloat16).astype(jnp.float32) for v in args[1:3]]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(_recurrence(*args))
        got = np.asarray(ssm.ssd_chunked(args[0], *rounded, *args[3:]))
    assert np.abs(got - want).max() > 10 * RTOL * np.abs(want).max()


def test_ssd_chunked_refuses_a_ragged_last_chunk():
    with pytest.raises(ValueError, match="multiple of the chunk"):
        ssm.ssd_chunked(*_scan_inputs(s=40), chunk=32)


def _four_shifted_adds(x, w, b):
    """SiLU of `b + sum_k w[:, k] * x_{t-(K-1)+k}` over a zero-padded copy, float32:
    the form `causal_conv1d_silu` had before PR 31, for NumPy or `jax.numpy`."""
    xp = np if isinstance(x, np.ndarray) else jnp
    k, s = w.shape[1], x.shape[1]
    padded = xp.concatenate([xp.zeros_like(x[:, : k - 1]), x], axis=1)
    pre = b + sum(padded[:, i: i + s] * w[:, i] for i in range(k))
    return pre / (1.0 + xp.exp(-pre))


# (S, C): the first three are shapes only the plain form takes (S no multiple of 128); the
# last two the Pallas pair takes when a step is lowered for TPU, and here, on the CPU, the
# plain form behind `platform_dependent` (the kernels themselves: the next test)
CONV_SHAPES = [(10, 5), (64, 128), (70, 256), (256, 16), (4096, 32)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("s, c", CONV_SHAPES)
def test_causal_conv1d_is_four_shifted_adds(s, c, dtype):
    """Forward and the three gradients of the hand-written backward against
    the four shifted adds and `jax.grad` of them, on the same (rounded) inputs
    in float32.  The first and last K - 1 positions are where a shift that
    wraps, or a halo taken from the wrong side, would show."""
    k = 4
    keys = jax.random.split(jax.random.PRNGKey(s + c), 4)
    x, dy = (jax.random.normal(key, (2, s, c)).astype(dtype) for key in (keys[0], keys[3]))
    w = (0.5 * jax.random.normal(keys[1], (c, k))).astype(dtype)
    b = (0.5 * jax.random.normal(keys[2], (c,))).astype(dtype)
    assert ssm_conv.supported(s, c, k) == (s >= 256)
    got, vjp = jax.vjp(ssm.causal_conv1d_silu, x, w, b)
    grads = vjp(dy)
    assert got.dtype == dtype and [g.dtype for g in grads] == [dtype] * 3
    f32 = [np.asarray(v.astype(jnp.float32)) for v in (x, w, b)]
    want = _four_shifted_adds(*f32)
    want_grads = jax.grad(lambda *a: jnp.sum(_four_shifted_adds(*a) * dy.astype(jnp.float32)), argnums=(0, 1, 2))(
        *map(jnp.asarray, f32))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2  # bf16: one rounding of each result (2**-8 of its scale)

    def same(a, b_, name):
        a, b_ = np.asarray(a.astype(jnp.float32)), np.asarray(b_)
        np.testing.assert_allclose(a, b_, rtol=tol, atol=tol * np.abs(b_).max(), err_msg=name)

    same(got[:, : k - 1], want[:, : k - 1], "y, the first K-1 positions")
    same(got[:, -(k - 1):], want[:, -(k - 1):], "y, the last K-1 positions")
    same(got, want, "y")
    same(grads[0][:, : k - 1], want_grads[0][:, : k - 1], "dx, the first K-1 positions")
    same(grads[0][:, -(k - 1):], want_grads[0][:, -(k - 1):], "dx, the last K-1 positions")
    for g, wg, name in zip(grads, want_grads, ("dx", "dw", "db")):
        same(g, wg, name)


@pytest.mark.parametrize("s, c, rows, lanes, dtype", [
    (512, 48, 16, 128, jnp.float32), (512, 48, 32, 256, jnp.float32),
    (4096, 32, None, None, jnp.float32), (4096, 32, None, None, jnp.bfloat16),
], ids=["3x4-blocks", "2x2-blocks", "default-blocks", "default-blocks-bfloat16"])
def test_each_conv_kernel_equals_the_four_shifted_adds_across_its_halos(s, c, rows, lanes, dtype):
    """`ssm_conv_fwd` / `ssm_conv_bwd` in interpret mode: with small blocks
    every block but the first and last has a neighbour on both sides; the
    default blocks cut 4,096 positions into two."""
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    x, dy = (jax.random.normal(key, (2, s, c)).astype(dtype) for key in (keys[0], keys[3]))
    w, b = jax.random.normal(keys[1], (c, 4)).astype(dtype), jax.random.normal(keys[2], (c,)).astype(dtype)
    want, vjp = jax.vjp(_four_shifted_adds, *(v.astype(jnp.float32) for v in (x, w, b)))
    blocks = dict(rows=rows, lanes=lanes, interpret=True)
    got = ssm_conv.conv_fwd(x.swapaxes(1, 2), w, b, **blocks).swapaxes(1, 2)
    dx, dw, db = ssm_conv.conv_bwd(x.swapaxes(1, 2), w, b, dy.swapaxes(1, 2), **blocks)
    assert got.dtype == dx.dtype == dtype and dw.dtype == db.dtype == jnp.float32
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    close(got.astype(jnp.float32), want, rtol=tol)
    for g, wg in zip((dx.swapaxes(1, 2), dw, db), vjp(dy.astype(jnp.float32))):
        close(g.astype(jnp.float32), wg, rtol=tol)


def test_a_mamba_layer_reaches_the_convolution_through_one_hand_written_backward(tiny):
    """The defect PR 31 removed cannot return by an innocent edit: the traced
    layer, forward and differentiated, pads nothing along the sequence (the
    padded float32 copy; the transpose of a shifted slice is a pad, one
    cotangent array per tap) and calls the convolution once, as the
    `custom_vjp` whose backward is `ops/ssm.py`'s own."""
    cfg = tiny["cfg"]
    layer = jax.tree_util.tree_map(lambda a: a[0], tiny["params"]["mamba_layers"])
    x = jnp.zeros((2, SEQ, cfg.d_model))

    def run(p, x):
        return transformer.layer(MIXERS["mamba"], x, p, None, cfg, None)[0]

    forward = jax.make_jaxpr(run)(layer, x).jaxpr
    backward = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(run(p, x)), argnums=(0, 1)))(layer, x).jaxpr
    for jaxpr, calls in ((forward, [kernel_pair.vjp(ssm.CONV).bwd, transformer._dense_ffn_bwd]), (backward, [])):
        eqns = list(_primitives(jaxpr))
        along_sequence = [e for e in eqns if e.primitive.name == "pad" and len(e.params["padding_config"]) == 3
                          and tuple(e.params["padding_config"][1]) != (0, 0, 0)]
        assert not along_sequence
        # the mixer's one call, then the FFN's; differentiated, each is its own forward and backward, inlined
        assert [e.params["bwd"].f for e in eqns if e.primitive.name == "custom_vjp_call"] == calls


@pytest.mark.parametrize("t", [0, 31, 32, 70])
def test_a_token_changes_outputs_from_its_position_on_only(tiny, t):
    """Convolution, scan and attention together: the state crosses chunk
    boundaries forward and nothing leaks backward."""
    tokens = np.asarray(tiny["batch"]["tokens"]).copy()
    changed = tokens.copy()
    changed[:, t] = (changed[:, t] + 1) % tiny["cfg"].vocab_size
    a, b = (np.asarray(tiny["ctx"].apply(tiny["params"], jnp.asarray(tk))) for tk in (tokens, changed))
    assert np.array_equal(a[:, :t], b[:, :t])
    assert np.abs(a[:, t:] - b[:, t:]).max(axis=-1).min() > 0  # every later position of every row moved


# -- the published facts each matter ---------------------------------------------------------


@pytest.mark.parametrize("field", ["embedding_multiplier", "residual_multiplier", "logits_scaling", "attention_scale"])
def test_each_multiplier_matters(tiny, field):
    cfg = dataclasses.replace(tiny["cfg"], **{field: None if field == "attention_scale" else 1.0})
    got = one_device_ctx(cfg).apply(tiny["params"], tiny["batch"]["tokens"])
    want = np.asarray(tiny["ref_logits"])
    diff = np.abs(np.asarray(got) - want).max() / np.abs(want).max()
    assert diff > 10 * RTOL


def test_no_rope_theta_means_no_rotary_embedding(tiny, monkeypatch):
    calls = []
    real = attention.apply_rope
    monkeypatch.setattr(attention, "apply_rope", lambda *a, **k: calls.append(1) or real(*a, **k))
    tokens = tiny["batch"]["tokens"]
    transformer.forward(tiny["params"], tokens, tiny["cfg"])
    assert not calls
    transformer.forward(tiny["params"], tokens, dataclasses.replace(tiny["cfg"], rope_theta=10000.0))
    assert len(calls) == 2  # q and k of the one attention layer's one traced body


def test_what_the_config_refuses():
    with pytest.raises(ValueError, match="layer_types"):
        TransformerConfig(**dict(BASE, layer_types=KINDS[:3]))
    with pytest.raises(ValueError, match="layer_types"):
        TransformerConfig(**dict(BASE, layer_types=("mamba", "window", "attention", "mamba")))
    # a stack with layer_types takes experts too (PR 37): what it refuses is an FFN list it cannot read
    assert TransformerConfig(**dict(BASE, n_experts=4, experts_per_token=2)).layer_pairs()[0] == ("mamba", "experts")
    with pytest.raises(ValueError, match="ffn_types"):
        TransformerConfig(**dict(BASE, ffn_types=("dense", "sparse", "dense", "dense")))
    with pytest.raises(ValueError, match="n_experts"):
        TransformerConfig(**dict(BASE, ffn_types=("dense", "experts", "dense", "dense")))
    with pytest.raises(ValueError, match="kda_heads"):
        TransformerConfig(**dict(BASE, layer_types=("kda",) * 4))
    with pytest.raises(ValueError, match="kv_lora_rank"):
        TransformerConfig(**dict(BASE, layer_types=("mla",) * 4))
    with pytest.raises(ValueError, match="ssm_heads"):
        TransformerConfig(**dict(BASE, ssm_heads=0))


def test_pp_refuses_a_hybrid_stack():
    cfg = TransformerConfig(**dict(BASE, n_layers=4))
    mesh = build_mesh(MeshSpec(data=1, pipeline=2), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="homogeneous stack"):  # as the context is built, before any trace
        LMTrainContext(cfg, mesh=mesh, strategy="pp")


# -- the programs that were there are as they were ---------------------------------------------


def _equations(jaxpr) -> int:
    return sum(1 for _ in _primitives(jaxpr))


EXPERT = dict(n_heads=4, n_kv_heads=4, d_ff=32, n_experts=8, experts_per_token=2, qk_norm=True,
              router_aux_loss_coef=0.01, router_z_loss_coef=0.001)


# One tiny model per family of stacks: Granite-like is `BASE`; Kimi-like pairs KDA with BOTH kinds of FFN beside a
# latent-attention layer (a share of the experts held); SambaY-like has the four kinds that read and hand on values.
KIMI = dict(
    n_layers=4, rope_theta=None, layer_types=("kda", "kda", "mla", "kda"),
    ffn_types=("dense", "experts", "experts", "experts"), kda_heads=2, kda_head_dim=16, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, n_experts=8, n_experts_held=4, first_expert_held=2,
    experts_per_token=2, moe_d_ff=32, n_shared_experts=1, norm_topk_prob=True, router_activation="sigmoid",
    routed_scaling_factor=2.0, router_aux_loss_coef=0.01)
SAMBAY = dict(
    n_layers=6, rope_theta=None, tie_embeddings=True, norm_kind="layer", attn_bias=True,
    layer_types=("s6", "diff_attention", "s6", "diff_attention", "gmu", "diff_cross"),
    layer_windows=(None, 16, None, None, None, None), layer_ids=(0, 1, 16, 17, 18, 19),
    s6_inner=128, s6_state=8, s6_dt_rank=8, s6_memory_layer=2, kv_source_layer=3)
FAMILIES = {"dense": {}, "expert": EXPERT, "granite": dict(BASE, max_seq_len=128), "kimi": KIMI, "sambay": SAMBAY}


@pytest.mark.parametrize("family, equations", [("dense", 700), ("expert", 2894), ("granite", 1979), ("kimi", 17742),
                                               ("sambay", 4648)])
def test_a_dense_and_an_expert_step_trace_to_the_parents_program(family, equations):
    """Counted at the parent of PR 30 with this function (the dense count is
    `tests/test_moe_model.py`'s 709 + 1 - 10; 710 / 2904 before PR 34's
    `head_cross_entropy`): the new fields' defaults add no equation, no slice
    of the stack and nothing of the scan.  The three hybrid steps were counted
    at the parent of PR 43, before a kind of mixer became one record; `kimi`,
    which holds a SHARE of its experts, again at PR 48 (15,438 before it: the
    share's block is now `moe._sized_experts`, whose backward traces the
    rung's forward again; the all-experts step, `expert`, did not move) and
    at PR 60 (17,451 before it: a KDA layer's convolution is
    `ops/delta_conv.py`'s `custom_vjp`, the norm inside it, and both of its
    forms are traced; no other family has a delta layer).  The three again
    at PR 64 (1,977 / 17,727 / 4,642 before it): `kernel_pair.vjp`'s forward
    gives its output and each state a `name` equation, one an array a trace of
    the forward (forward and recompute), and nothing else moved; a name no
    policy lists lowers to nothing."""
    ctx = one_device_ctx(TransformerConfig.tiny(**FAMILIES[family]))
    state = jax.eval_shape(ctx._init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    jaxpr = jax.make_jaxpr(ctx._train_step)(state, {"tokens": toks, "targets": toks})
    assert _equations(jaxpr.jaxpr) == equations
    if family in ("dense", "expert"):
        text = str(jaxpr)
        assert "ssm" not in text and "mamba" not in text


# Of `init_params(config, PRNGKey(0))`: the first two values of the embedding and the head, and in every stack of
# the LAST layer's first and last leaves that draw a key (the mixer's first, the FFN's last).  The dense model's
# were recorded at the parent of PR 30, the others' at the parent of PR 43.
SEED0 = {
    "dense": {"embed/tokens": [0.12550178170204163, -0.1132921501994133],
              "layers/attn/wq": [-0.08601520210504532, -0.02267324924468994],
              "layers/mlp/w_down": [0.048702314496040344, -0.0014959044056013227],
              "lm_head": [-0.08073218911886215, -0.1908739060163498]},
    "granite": {"embed/tokens": [0.12550178170204163, -0.1132921501994133],
                "layers/attn/wq": [-0.30530697107315063, -0.25446006655693054],
                "layers/mlp/w_down": [-0.0027661342173814774, -0.06458717584609985],
                "mamba_layers/ssm/in_proj": [0.24149473011493683, 0.045193642377853394],
                "mamba_layers/mlp/w_down": [0.06934718042612076, -0.09658616781234741]},
    "kimi": {"embed/tokens": [0.12550178170204163, -0.1132921501994133],
             "lm_head": [0.14543741941452026, -0.12132174521684647],
             "kda_layers_dense/kda/wqkv": [0.024863429367542267, -0.09627213329076767],
             "kda_layers_dense/mlp/w_down": [0.021224258467555046, -0.04864843562245369],
             "kda_layers_experts/kda/wqkv": [0.052536532282829285, -0.0427650548517704],
             "kda_layers_experts/mlp/shared/w_down": [0.05621757358312607, -0.011826579459011555],
             "kda_layers_experts/mlp/w_down": [0.0067697735503315926, 0.0608651265501976],
             "mla_layers/mla/wq": [0.21022771298885345, 0.08323755860328674],
             "mla_layers/mlp/shared/w_down": [0.08691354095935822, -0.0094215152785182],
             "mla_layers/mlp/w_down": [0.029987668618559837, 0.06039942055940628]},
    "sambay": {"embed/tokens": [0.12550178170204163, -0.1132921501994133],
               "s6_layers/s6/in_proj": [-0.09402994066476822, -0.14637696743011475],
               "s6_layers/mlp/w_down": [-0.009793443605303764, 0.003301437944173813],
               "diff_layers/diff/wqkv": [-0.1579943746328354, -0.179226815700531],
               "diff_layers/mlp/w_down": [0.01664189249277115, -0.053962916135787964],
               "gmu_layers/gmu/w1": [0.21022771298885345, 0.08323755860328674],
               "gmu_layers/mlp/w_down": [0.012459908612072468, -0.022547056898474693],
               "cross_layers/diff/wq": [0.10819514095783234, -0.15941713750362396],
               "cross_layers/mlp/w_down": [0.022529320791363716, -0.012618785724043846]},
}


@pytest.mark.parametrize("family", ["dense", "granite", "kimi", "sambay"])
def test_a_dense_models_weights_for_a_seed_did_not_move(family):
    """The key order of `init_params`, in every kind of stack: which stacks
    share the first key sequence, and the order of the draws inside each."""
    params = transformer.init_params(TransformerConfig.tiny(**FAMILIES[family]), jax.random.PRNGKey(0))
    if family == "dense":
        assert "mamba_layers" not in params
    assert {path.split("/")[0] for path in SEED0[family]} == set(params) - {"final_norm", "final_norm_b"}
    for path, want in SEED0[family].items():
        leaf = params
        for name in path.split("/"):
            leaf = leaf[name]
        leaf = leaf if path.split("/")[0] in ("embed", "lm_head") else leaf[-1]
        np.testing.assert_allclose(np.asarray(leaf).reshape(-1)[:2], want, rtol=1e-6, err_msg=path)


# -- across devices --------------------------------------------------------------------------


def test_conv_kernels_under_shard_map_equal_the_single_device_result():
    """At shapes the kernels take, a mesh puts the convolution under shard_map
    (GSPMD cannot partition a Mosaic call; on the CPU its body is the plain
    form): batch over `data`, replicated over `tensor`, and the weight
    gradients summed over the shards."""
    mesh = build_mesh(MeshSpec(data=2, tensor=2), devices=jax.devices()[:4])
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    x, dy = (jax.random.normal(key, (4, 256, 32)) for key in (keys[0], keys[3]))
    w, b = jax.random.normal(keys[1], (32, 4)), jax.random.normal(keys[2], (32,))
    assert ssm_conv.supported(256, 32, 4)

    def objective(x, w, b, **sharded):
        return jnp.sum(ssm.causal_conv1d_silu(x, w, b, **sharded) * dy)

    want = jax.grad(objective, argnums=(0, 1, 2))(x, w, b)
    got = jax.jit(jax.grad(lambda *a: objective(*a, mesh=mesh, batch_axes=("data", "fsdp")), argnums=(0, 1, 2)))(x, w, b)
    for g, wg in zip(got, want):
        close(g, wg, rtol=1e-5)


def test_fsdp4_step_equals_the_single_device_step(tiny):
    mesh = build_mesh(MeshSpec(data=1, fsdp=4), devices=jax.devices()[:4])
    ctx = LMTrainContext(tiny["cfg"], mesh=mesh, strategy="fsdp")
    in_proj = ctx.param_shardings["mamba_layers"]["ssm"]["in_proj"].spec
    out_proj = ctx.param_shardings["mamba_layers"]["ssm"]["out_proj"].spec
    assert in_proj[1] == "fsdp" and out_proj[2] == "fsdp"  # the model side of both projections
    batch = batch_of(tiny["cfg"], batch=4)
    losses = []
    for c in (ctx, tiny["ctx"]):
        copy = jax.device_put(jax.tree_util.tree_map(np.asarray, tiny["params"]), c.param_shardings)
        state = dict(c.init_state(0), params=copy)
        _, metrics = c.train_step(state, jax.tree_util.tree_map(np.asarray, batch))
        losses.append((float(metrics["loss"]), float(metrics["grad_norm"])))
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-5)
