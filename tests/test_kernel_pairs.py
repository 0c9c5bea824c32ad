"""The contract of `ray_tpu/ops/kernel_pair.py`, held against every declared
record at once: both forms of each direction return the same shapes and
dtypes where the kernels take the shapes; where they refuse them no kernel and
no choice is traced, in either direction; a call lowered for the CPU holds no
Mosaic kernel.  Shapes and lowerings only: no kernel runs here (the kernels'
arithmetic is `test_kda_kernel.py`'s, `test_gdn_kernel.py`'s,
`test_selective_scan_kernel.py`'s, `test_ssd_kernel.py`'s,
`test_delta_conv_kernel.py`'s and `test_hybrid_model.py`'s)."""

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops import delta_conv, gdn, kda, kernel_pair, selective_scan, sparse_attention, ssm

f32, bf16 = jnp.float32, jnp.bfloat16
shaped = jax.ShapeDtypeStruct


def delta(s, hk, hv, d, per_channel):
    """q, k, v, g, beta of a delta rule: `hv` value heads on `hk` key heads, g per channel or per head."""
    g = (1, s, hv, d) if per_channel else (1, s, hv)
    return shaped((1, s, hk, d), f32), shaped((1, s, hk, d), f32), shaped((1, s, hv, d), bf16), shaped(g, f32), shaped((1, s, hv), f32)


def s6(s, c, n=16):
    return (shaped((1, s, c), bf16), shaped((1, s, c), f32), shaped((c, n), f32), shaped((1, s, n), bf16),
            shaped((1, s, n), bf16), shaped((c,), f32))


def ssd(s, h, p, n=128):
    return (shaped((1, s, h, p), bf16), shaped((1, s, h), f32), shaped((h,), f32), shaped((1, s, n), bf16),
            shaped((1, s, n), bf16), shaped((h,), f32))


def conv(s, c, k=4):
    return shaped((1, s, c), bf16), shaped((c, k), bf16), shaped((c,), bf16)


def delta_conv_of(s, cx, hq, hk, d, cv, k=4):
    """x and the three weights of a delta layer's convolution: `hq` + `hk` heads of `d`, `cv` channels of v, of x's `cx` columns."""
    return shaped((1, s, cx), bf16), shaped((hq, d, k), f32), shaped((hk, d, k), f32), shaped((cv, k), f32)


def selected(s, h, d, dv):
    """q, k, v and the selection's mask (any dtype: nonzero is selected; float here, so that every argument has a cotangent)."""
    return shaped((1, s, h, d), bf16), shaped((1, s, h, d), bf16), shaped((1, s, h, dv), bf16), shaped((1, s, s), f32)


def index(s, j, d):
    """The indexer's ONE key a position, its `j` heads' queries and their weights (the record's order: the key first)."""
    return shaped((1, s, d), bf16), shaped((1, s, j, d), bf16), shaped((1, s, j), f32)


# record, the op that runs it, arguments the kernels take, arguments they refuse
PAIRS = {
    "kda": (kda.PAIR, kda.kda_chunked, delta(128, 1, 1, 128, True), delta(128, 1, 1, 64, True)),
    "gdn": (gdn.PAIR, gdn.gdn_chunked, delta(256, 1, 2, 128, False), delta(256, 1, 2, 64, False)),
    "s6": (selective_scan.PAIR, selective_scan.selective_scan, s6(256, 128), s6(256, 80)),
    "ssd": (ssm.SCAN, ssm.ssd_chunked, ssd(256, 4, 64), ssd(256, 4, 48)),
    "conv": (ssm.CONV, ssm.causal_conv1d_silu, conv(128, 16), conv(96, 16)),
    # one key head and two value heads of 128 read from a wider array (Qwen3-Next's layout); heads of 64 are refused
    # the sparse core at latent attention's two head sizes; a length no 128-tile divides is refused
    "selected": (sparse_attention.PAIR, sparse_attention.selected_attention, selected(256, 2, 192, 128), selected(192, 2, 192, 128)),
    # the indexer's scores at 8 heads of 128 over one key a position; heads of 64 are under the MXU's depth
    "index": (sparse_attention.INDEX, lambda ki, qi, w: sparse_attention.index_scores(qi, ki, w), index(256, 8, 128), index(256, 8, 64)),
    "delta_conv": (delta_conv.PAIR, delta_conv.delta_conv, delta_conv_of(32, 768, 1, 1, 128, 256), delta_conv_of(32, 768, 2, 2, 64, 256)),
}
EACH = pytest.mark.parametrize("name", PAIRS)


def gradients(op, args):
    """Of the sum of the op's output, or of each of its outputs (`delta_conv` has three)."""
    return jax.grad(lambda *a: sum(jnp.sum(out.astype(f32)) for out in jax.tree.leaves(op(*a))), argnums=range(len(args)))


def test_every_record_of_the_ops_is_held_here():
    declared = {id(value) for module in (delta_conv, gdn, kda, selective_scan, sparse_attention, ssm) for value in vars(module).values()
                if isinstance(value, kernel_pair.KernelPair)}
    assert declared == {id(pair) for pair, *_ in PAIRS.values()}


@EACH
def test_both_forms_of_each_direction_return_the_same_shapes_and_dtypes(name, monkeypatch):
    pair, op, taken, _ = PAIRS[name]
    seen = []

    def both(takes, kernel, plain, *inputs):
        assert takes
        kernel_out, plain_out = jax.eval_shape(kernel, *inputs), jax.eval_shape(plain, *inputs)
        assert jax.tree.structure(kernel_out) == jax.tree.structure(plain_out)
        assert [(o.shape, o.dtype) for o in jax.tree.leaves(kernel_out)] == [(o.shape, o.dtype) for o in jax.tree.leaves(plain_out)]
        seen.append(len(inputs))
        return plain(*inputs)

    monkeypatch.setattr(kernel_pair, "dispatch", both)
    cotangents = jax.eval_shape(gradients(op, taken), *taken)
    assert [(c.shape, c.dtype) for c in cotangents] == [(a.shape, a.dtype) for a in taken]
    forward, backward = seen  # one choice a direction; the backward reads the states and the output's cotangent too
    assert forward == len(taken) < backward


@EACH
def test_at_shapes_the_kernels_refuse_no_kernel_and_no_choice_is_traced_in_either_direction(name, no_kernel_runs):
    pair, op, _, refused = PAIRS[name]  # `no_kernel_runs` fails a choice that would take a kernel
    text = str(jax.make_jaxpr(gradients(op, refused))(*refused))
    assert "pallas_call" not in text and "platform_index" not in text
    # a record with `refused` hands the plain form alone to JAX's differentiation
    assert ("custom_vjp_call" in str(jax.make_jaxpr(op)(*refused))) == (pair.refused is None)


@EACH
def test_lowered_for_the_cpu_a_call_at_shapes_the_kernels_take_holds_no_mosaic_kernel(name):
    _, op, taken, _ = PAIRS[name]
    traced = jax.jit(gradients(op, taken)).trace(*taken)
    assert str(traced.jaxpr).count("pallas_call") >= 2  # both directions' kernels are traced: one branch of each choice
    assert "tpu_custom_call" not in traced.lower(lowering_platforms=("cpu",)).as_text()


@EACH
def test_residual_names_are_what_the_forward_of_a_backward_puts_on_out_and_on_the_states(name):
    """PR 64: `KernelPair.residual_names` are the two names `vjp(pair).fwd`
    gives its output (every array of it) and every state it hands the
    backward, so a checkpoint policy that lists them keeps all the backward
    reads of what the forward kernel wrote; read from the jaxpr of a `jax.vjp`
    of the op, whose results are the output and then the residuals.  What the
    backward holds beside them is the op's own arguments.  The primal path (no
    differentiation) names nothing."""
    pair, op, taken, _ = PAIRS[name]
    of_out, of_states = pair.residual_names
    assert (of_out, of_states) == (f"{pair.name}/out", f"{pair.name}/states")
    jaxpr = jax.make_jaxpr(lambda *a: jax.vjp(op, *a))(*taken).jaxpr
    named = {eqn.outvars[0]: eqn.params["name"] for eqn in jaxpr.eqns if eqn.primitive.name == "name"}
    out = jax.tree.leaves(jax.eval_shape(op, *taken))
    sized = lambda avals: sorted((a.size, str(a.dtype)) for a in avals)  # `delta_conv` cuts q and k into heads behind the name
    assert sized(var.aval for var, given in named.items() if given == of_out) == sized(out)
    states = [named.get(var) for var in jaxpr.outvars[len(out):] if var not in jaxpr.invars]
    assert set(states) <= {of_states} and len(named) == len(out) + len(states), (named, states)
    assert bool(states) == (name not in ("conv", "delta_conv", "index"))  # these backwards read their arguments alone
    assert " name[" not in str(jax.make_jaxpr(op)(*taken))


def test_the_cca_mixing_declares_no_pair_and_its_check_script_waits_for_one():
    """PR 68: the q|k mixing of a "cca" layer is plain JAX (no Mosaic kernel: PERF.md section 7 says what one would be
    worth); its module holds no `KernelPair`, names what `qkv_attn` keeps of it, and `scripts/cca_mix_check.py` holds the
    mixing to the reference's at the published widths, so that a later kernel PR has its check waiting."""
    import os

    from ray_tpu.models.mixers import cca

    assert not [v for v in vars(cca).values() if isinstance(v, kernel_pair.KernelPair)]
    assert cca.MIXER.saved == (cca.LATENT, cca.CONV1, cca.MIXED) and cca.MIXER.recurrence == ()
    assert os.path.exists(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts", "cca_mix_check.py"))
