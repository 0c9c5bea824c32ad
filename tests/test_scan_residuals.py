"""PR 64: a recurrence names what its backward reads, and `kda` keeps it.

`ops/kernel_pair.py` `vjp(pair).fwd` puts `KernelPair.residual_names` on the
op's output and on the states its backward starts from
(`tests/test_kernel_pairs.py` holds the names to the jaxpr), `kda` lists them
in `Mixer.saved`, and `remat_policy="qkv_attn"` collects every kind's
`saved`: the layer's recompute holds no forward scan kernel
(`tests/test_tpu_lowering.py` counts the kernels of the lowered steps).
`gdn`'s op names the same and its kind lists nothing of it
(`qwen3-next-ep16-1chip.seq8k` compiles over ISSUE 64's 15.6 GB with them
kept), so a `gdn` model is here as the kind whose list may grow: keeping its
names too changes no value.  With the plain forms the CPU runs: the arrays
the policy keeps, the gradients it leaves bit for bit what they were, and the
step counter that says which layers run their forward again."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import LMTrainContext, TransformerConfig, lm, transformer
from ray_tpu.models.mixers import MIXERS
from ray_tpu.ops import gdn, kda, selective_scan, ssm
from ray_tpu.parallel import MeshSpec, build_mesh

SEQ, BATCH = 128, 2
BASE = dict(n_heads=2, n_kv_heads=2, d_model=64, d_ff=64, max_seq_len=SEQ, remat=False, rope_theta=None)
# two layers of one kind (one run, one scan body), at the head size the kernels take: the ops run their `custom_vjp`
TINY = {
    "kda": TransformerConfig.tiny(**BASE, n_layers=2, layer_types=("kda", "kda"), kda_heads=1, kda_head_dim=128),
    "gdn": TransformerConfig.tiny(**BASE, n_layers=2, layer_types=("gdn", "gdn"), gdn_key_heads=1, gdn_value_heads=2,
                                  gdn_key_dim=128, gdn_value_dim=128),
}
POLICIES = ["qkv_attn", "attn", None]


def one_device_ctx(cfg):
    return LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), strategy="dp")


@pytest.fixture(scope="module", params=list(TINY))
def unchecked(request):
    """A tiny model of one delta kind, a batch, and the loss and gradients of the step without `jax.checkpoint`."""
    cfg = TINY[request.param]
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (BATCH, SEQ), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}
    (loss, _), grads = jax.jit(jax.value_and_grad(one_device_ctx(cfg)._loss, has_aux=True))(params, batch)
    return dict(kind=request.param, cfg=cfg, params=params, batch=batch, loss=loss, grads=grads)


def loss_and_grads(unchecked, policy):
    cfg = dataclasses.replace(unchecked["cfg"], remat=True, remat_policy=policy)
    (loss, _), grads = jax.jit(jax.value_and_grad(one_device_ctx(cfg)._loss, has_aux=True))(
        unchecked["params"], unchecked["batch"])
    return loss, dict(jax.tree_util.tree_flatten_with_path(grads)[0])


@pytest.mark.parametrize("policy", POLICIES, ids=str)
def test_every_gradient_leaf_of_a_delta_model_under_each_policy_equals_the_unchecked_steps(unchecked, policy):
    """`tests/test_hybrid_model.py`'s form: what a policy saves changes which
    forward values the backward reads, never a value (float32 rounding where
    XLA fuses the checked and the unchecked program differently)."""
    loss, got = loss_and_grads(unchecked, policy)
    np.testing.assert_allclose(float(loss), float(unchecked["loss"]), rtol=1e-6)
    want = dict(jax.tree_util.tree_flatten_with_path(unchecked["grads"])[0])
    assert got.keys() == want.keys() and len(got) > 10
    for path, leaf in got.items():
        assert float(jnp.abs(want[path]).max()) > 0, path  # the leaf is used
        scale = float(jnp.abs(want[path]).max())  # a sum's rounding is a share of its largest term, not of its result
        np.testing.assert_allclose(leaf, want[path], rtol=1e-5, atol=1e-5 * scale, err_msg=jax.tree_util.keystr(path))


def test_keeping_the_recurrences_residuals_leaves_every_gradient_leaf_bit_for_bit(unchecked, monkeypatch):
    """The o and the states the recurrence's first call wrote ARE the arrays
    its second call would have written: `"qkv_attn"` WITH the two names of the
    kind's recurrence (`kda`'s policy since PR 64; what `gdn`'s would be)
    against `"qkv_attn"` WITHOUT them (PR 63's policy, which runs the forward
    again; `gdn`'s still), every leaf bit-equal."""
    names = transformer.saved_names(dataclasses.replace(unchecked["cfg"], remat_policy="qkv_attn"))
    mine = MIXERS[unchecked["kind"]].recurrence
    assert (set(mine) <= set(names)) == (unchecked["kind"] == "kda")
    monkeypatch.setattr(transformer, "saved_names", lambda config: (*names, *mine))
    loss, got = loss_and_grads(unchecked, "qkv_attn")
    monkeypatch.setattr(transformer, "saved_names", lambda config: tuple(n for n in names if n not in mine))
    old_loss, old = loss_and_grads(unchecked, "qkv_attn")
    assert float(loss) == float(old_loss) and got.keys() == old.keys()
    for path, leaf in got.items():
        np.testing.assert_array_equal(leaf, old[path], err_msg=jax.tree_util.keystr(path))


def test_qkv_attn_keeps_what_the_recurrences_backward_reads_and_attn_keeps_none_of_it(unchecked):
    """One `kda` layer under the cells' policy keeps, beside its arguments and
    the projections' outputs, the recurrence's o in the float32 the op writes
    it in and the states its backward starts from (the state that enters each
    segment, and that of each pair of chunks: zeros of the kernel's shape in
    the plain form, `call.residuals`); a `gdn` layer the projections' outputs
    alone, and the same three of its recurrence once its names are listed;
    under `"attn"` nothing of the layer's own."""
    from jax._src.ad_checkpoint import saved_residuals  # the list `jax.ad_checkpoint.print_saved_residuals` prints

    kind, cfg = unchecked["kind"], dataclasses.replace(unchecked["cfg"], dtype=jnp.bfloat16, remat=True)
    stack = MIXERS[kind].stack
    layer = jax.tree_util.tree_map(lambda a: a[0], {k: v for k, v in unchecked["params"][stack].items() if k != "mlp"})
    x = jnp.zeros((BATCH, SEQ, cfg.d_model), jnp.bfloat16)

    def saved(policy, more=()):
        config = dataclasses.replace(cfg, remat_policy=policy)
        run = jax.checkpoint(lambda p, x: transformer.layer(MIXERS[kind], x, p, None, config, None, ffn="none")[0],
                             policy=jax.checkpoint_policies.save_only_these_names(*transformer.saved_names(config), *more))
        return sorted((aval.shape, str(aval.dtype)) for aval, why in saved_residuals(run, layer, x)
                      if "from the argument" not in why and "from a constant" not in why)

    heads = 1 if kind == "kda" else 2
    chunks = SEQ // kda.CHUNK
    pairs = (1, BATCH, chunks // 2) if kind == "kda" else (BATCH, chunks // 2)  # by segment, as the kernel walks, or not
    of_scan = [((*pairs, heads, 128, 128), "float32"),  # the state that enters each pair of chunks
               ((1, BATCH, heads, 128, 128), "float32"),  # and each segment (one here)
               ((BATCH, SEQ, heads, 128), "float32")]  # o
    projections = {"kda": [((BATCH, SEQ, 2 * 128 + 1), "bfloat16"), ((BATCH, SEQ, 3 * 128), "bfloat16")],
                   "gdn": [((BATCH, SEQ, 2 * 2), "bfloat16"), ((BATCH, SEQ, 2 * 128 + 2 * 2 * 128), "bfloat16")]}[kind]
    assert saved("qkv_attn") == sorted(of_scan + projections if kind == "kda" else projections)
    assert saved("qkv_attn", more=MIXERS[kind].recurrence) == sorted(of_scan + projections)
    assert saved("attn") == []


MAMBA = dict(ssm_heads=8, ssm_head_dim=64, ssm_state=128)
S6 = dict(s6_inner=512, s6_state=16, s6_dt_rank=16)


@pytest.mark.parametrize("layers,extra,policy,want", [
    (("kda", "kda", "mla"), {}, "qkv_attn", 0.0),  # `kimi-linear`'s kinds: the policy keeps the recurrence's names
    (("gdn", "gdn", "gdn", "attention"), {}, "qkv_attn", 100.0),  # `qwen3-next`'s: `gdn` lists nothing of its recurrence (no room)
    (("kda", "gdn", "attention"), {}, "qkv_attn", 50.0),
    (("kda", "kda", "mla"), {}, "attn", 100.0),  # the smaller-memory modes run the forward again
    (("gdn", "attention"), {}, None, 100.0),
    (("mamba", "mamba", "attention"), MAMBA, "qkv_attn", 100.0),  # `granite`, `nemotron3-nano`: no kind lists the SSD's names
    (("s6", "attention"), S6, "qkv_attn", 100.0),  # `phi4-mini-flash`'s S6 layers
    (("kda", "kda", "mamba", "attention"), MAMBA, "qkv_attn", 100 / 3),  # a share of the layers WITH a recurrence; attention is none
    (("attention", "attention"), {}, "qkv_attn", None),  # no such layer: the counter is absent
    (("mla",), {}, None, None),
], ids=lambda value: "-".join(value) if isinstance(value, tuple) else str(value) if not isinstance(value, dict) else "")
def test_scan_forward_rerun_pct_is_the_share_of_recurrence_layers_whose_names_the_policy_does_not_keep(layers, extra, policy, want):
    cfg = TransformerConfig.tiny(
        n_layers=len(layers), n_heads=2, n_kv_heads=2, d_model=256, d_ff=256, max_seq_len=SEQ, rope_theta=None, layer_types=layers,
        remat=True, remat_policy=policy, kda_heads=2, kda_head_dim=128, gdn_key_heads=1, gdn_value_heads=2, gdn_key_dim=128,
        gdn_value_dim=128, kv_lora_rank=64, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, **extra)
    assert lm.SCAN_RERUN == "scan_forward_rerun_pct" and lm.SCAN_RERUN in lm.STEP_COUNTERS
    assert lm._rerun_counters(cfg) == ({} if want is None else {lm.SCAN_RERUN: pytest.approx(want)})
    if want is not None:  # without `jax.checkpoint` JAX keeps every residual: no forward runs again
        assert lm._rerun_counters(dataclasses.replace(cfg, remat=False)) == {lm.SCAN_RERUN: 0.0}


def test_every_kind_with_a_recurrence_declares_its_records_names_and_kda_alone_keeps_them():
    records = {"kda": kda.PAIR, "gdn": gdn.PAIR, "mamba": ssm.SCAN, "s6": selective_scan.PAIR}
    assert {name: m.recurrence for name, m in MIXERS.items() if m.recurrence} == {
        name: pair.residual_names for name, pair in records.items()}
    kept = set(transformer.saved_names(TransformerConfig.tiny(remat_policy="qkv_attn")))
    assert {name for name, pair in records.items() if set(pair.residual_names) <= kept} == {"kda"}
    assert not {n for name, pair in records.items() if name != "kda" for n in pair.residual_names} & kept
    for policy in ("attn", None):  # the smaller-memory modes keep none of them
        assert not {n for pair in records.values() for n in pair.residual_names} & set(
            transformer.saved_names(TransformerConfig.tiny(remat_policy=policy)))


def test_the_counter_rides_among_the_steps_terms(unchecked):
    for policy, want in (("qkv_attn", 0.0 if unchecked["kind"] == "kda" else 100.0), ("attn", 100.0)):
        ctx = one_device_ctx(dataclasses.replace(unchecked["cfg"], remat=True, remat_policy=policy))
        _, terms = ctx._loss(unchecked["params"], unchecked["batch"])
        assert float(terms[lm.SCAN_RERUN]) == want
