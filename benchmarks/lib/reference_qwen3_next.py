"""Plain reference for kind "qwen3_next_decoder": Qwen3-Next (Hugging Face
`model_type: qwen3_next`; its linear layers are "Gated Delta Networks",
arXiv:2412.06464) in straightforward float32 `jax.numpy`, one sequence at a
time.  x is [S, d], `eps` is `rms_norm_eps`, no bias anywhere.

- norms: every RMSNorm of the stream (before each mixer, before each expert
  block, before the head) and of q and k is ZERO-CENTRED,
  `x * rsqrt(mean(x^2) + eps) * (1 + w)` with w the stored leaf; the gated
  norm inside a linear layer is the plain one, `... * w`.
- model: `h0 = embed[tokens]`; the layers; `logits = norm(h) @ lm_head`.
- layer i (0-based): `h = h + mixer_i(norm_1(h))`, `h = h + experts(norm_2(h))`;
  the mixer is softmax attention where `(i + 1) % full_attention_interval == 0`
  and the gated delta rule elsewhere.
- gated delta rule, u = norm_1(h), Hk = `linear_num_key_heads` heads of q and k
  of `linear_key_head_dim`, Hv = `linear_num_value_heads` heads of v of
  `linear_value_head_dim`: `[q | k | v | z] = u W_qkvz`, `[b | a] = u W_ba`;
  `[q | k | v] <- silu(conv([q | k | v]))`, the causal depthwise convolution
  of width `linear_conv_kernel_dim` written as that many SHIFTED ADDS, zeros
  before the start; value head j reads key head `j // (Hv / Hk)`; per head
  `q <- q / |q|_2 * Dk^-0.5`, `k <- k / |k|_2` (`x / sqrt(sum x^2 + 1e-6)`);
  `beta = sigmoid(b)`, `g = -exp(A_log) * softplus(a + dt_bias)`, ONE number a
  value head; the recurrence TOKEN BY TOKEN, a `lax.scan` over S with the
  state [Hv, Dk, Dv] from zero: `S_t = (I - beta_t k_t k_t^T) exp(g_t) S_{t-1}
  + beta_t k_t v_t^T`, `o_t = S_t^T q_t`;
  `o <- RMSNorm_head(o) * w * silu(z)` (over each head's Dv); `W_o`.
- gated attention, H = `num_attention_heads` q heads, `num_key_value_heads`
  k/v heads of `head_dim` D: `u W_q -> [H, 2 D]`, each head's first D its q
  and its last D its gate; `k = u W_k`, `v = u W_v`; q and k normed per head
  (zero-centred, one scale [D] for q's heads, one for k's); the rotary
  embedding (`rope_theta`) on the first `partial_rotary_factor * D` dims of
  each head, the rest passing; query head i reads key/value head `i // (H /
  Hkv)`; causal softmax at `D^-0.5` in query blocks;
  `o <- o * sigmoid(gate)`; `W_o`.
- experts, u = norm_2(h): `p = softmax(u W_r)` over all
  `share.num_experts_total`, the `num_experts_per_tok` largest, divided by
  their sum (`norm_topk_prob`); `y = sum_i p_i SwiGLU_{choice_i}(u) +
  sigmoid(u w_sg) * SwiGLU_shared(u)`.  The tree holds the experts `first ..
  first + held` only (one rank's share of an expert-parallel deployment): the
  sum runs over the chosen experts that are HELD, and what the absent ones
  would have added is left out, here as in the program.  `first` is
  `share.first_expert_held`, `held` is read off the leaves' shapes.

No chunking, no kernel, no cache, no sharding, and no import from
`ray_tpu.models` or `ray_tpu.ops`: it shares with the program only the layout
of the parameter tree it is handed (`gdn_layers` the linear layers, `layers`
the attention layers, each stack in the order its layers appear), so a wrong
chunk boundary, decay, solve, mask, gate or rotated width in the program
cannot be wrong twice.

Everything runs under `jax.default_matmul_precision("highest")`.  On the chip
`logits` streams one layer's weights at a time, upcast as they are used, and
every position of every layer is computed (the recurrence needs them all); the
head runs on the last `last` positions.  `jax.grad` of `objective` is the
reference gradient.  `tolerance(L)` is the dense reference's, unchanged.

Departures from the published forward, all noted: rotary pairs are ADJACENT
dims (2i, 2i+1) of the rotated part, the repo's convention in every cell (the
released code rotates halves of it: the same function under a fixed
permutation of the part's columns, the same for q and k, which seeded weights
do not see); the fused projections' columns are `[q | k | v | z]` and `[b |
a]`, whole parts side by side (the released checkpoint interleaves them by
key head: a permutation of columns, which matters to a checkpoint and not to
seeded weights); the gate value multiplies an expert's hidden row before
`W_down` in the program and the row after it here (a linear map commutes with
a scalar per row); the whole batch is packed sequences with no padding mask
and no reset of the state, the convolution or attention at a document
boundary (what the program does too; `assumed` in the configuration file); no
multi-token-prediction module (the row's `config` has no key for one).

`wrong=` exists for the tests that show the comparison catches a wrong
program, one mechanism at a time: "no_output_gate" (attention's output is
not gated), "rope_whole_head" (every dim of a head is rotated),
"norm_not_centred" (`1 + w` read as `w`), "decay_per_channel" (the head's one
decay drawn apart over the key's channels, x0.5 .. x1.5).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from benchmarks.lib.reference import QUERY_BLOCK, _local, _take_layer, rel_rms_error, tolerance
from benchmarks.lib.reference_kimi_linear import _conv, _delta_rule, _l2_normed, _swiglu  # the same plain pieces, written once

__all__ = ["logits", "objective", "layer_kinds", "expert_block", "delta_rule", "rel_rms_error", "tolerance", "WRONG"]

WRONG = ("no_output_gate", "rope_whole_head", "norm_not_centred", "decay_per_channel")
ROW_BLOCK = 1024  # rows of an expert block held at once: 32 experts x 1024 x 2048 float32 = 268 MB
STACKS = {"gdn": "gdn_layers", "attention": "layers"}


def layer_kinds(config: Dict[str, Any]) -> List[str]:
    """The mixer of each layer that runs: attention closes every period of `full_attention_interval`."""
    return ["attention" if (i + 1) % config["full_attention_interval"] == 0 else "gdn"
            for i in range(config["num_hidden_layers"])]


def _norm(x, w, eps: float, centred: bool = True):
    """RMSNorm over the last axis with the scale `1 + w` (`centred`) or `w`."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w if centred else w)


# -- the gated delta rule ---------------------------------------------------------------


def delta_rule(q, k, v, g, beta):
    """Token by token (`reference_kimi_linear._delta_rule`: a `lax.scan` over S,
    the state [H, K, V] from zero).  q, k [S, H, K], v [S, H, V], beta [S, H];
    g [S, H], ONE log decay a head, which is the rule with a decay a channel
    at equal channels, or [S, H, K] -> o [S, H, V]."""
    return _delta_rule(q, k, v, jnp.broadcast_to(g[..., None], k.shape) if g.ndim == 2 else g, beta)


def _gdn(x, w, *, eps: float, key_heads: int, key_dim: int, wrong: Tuple[str, ...] = ()):
    """x + gdn(norm_1(x)) on one sequence.  w: this layer's `gdn` leaves and
    `ln1`; the value heads and their size come from the leaves' shapes."""
    m = w["gdn"]
    s = x.shape[0]
    heads, v_dim = m["A_log"].shape[0], m["norm"].shape[0]
    qk, group = 2 * key_heads * key_dim, heads // key_heads
    h = _norm(x, w["ln1"], eps, "norm_not_centred" not in wrong)
    qkvz, ba = h @ m["wqkvz"], h @ m["wba"]
    conv = jax.nn.silu(_conv(qkvz[:, : qk + heads * v_dim], m["conv_w"]))
    q, k = (a.reshape(s, key_heads, key_dim) for a in jnp.split(conv[:, :qk], 2, axis=-1))
    v = conv[:, qk:].reshape(s, heads, v_dim)
    z = qkvz[:, qk + heads * v_dim:].reshape(s, heads, v_dim)
    q = jnp.repeat(_l2_normed(q) * key_dim ** -0.5, group, axis=1)  # value head j reads key head j // group
    k = jnp.repeat(_l2_normed(k), group, axis=1)
    beta = jax.nn.sigmoid(ba[:, :heads])
    g = -jnp.exp(m["A_log"]) * jax.nn.softplus(ba[:, heads:] + m["dt_bias"])
    if "decay_per_channel" in wrong:
        g = g[..., None] * jnp.linspace(0.5, 1.5, key_dim)
    o = _norm(delta_rule(q, k, v, g, beta), m["norm"], eps, centred=False) * jax.nn.silu(z)
    return x + o.reshape(s, heads * v_dim) @ m["wo"]


# -- gated attention --------------------------------------------------------------------


def _rotate(x, theta: float, width: int):
    """x [S, heads, D]: each adjacent pair (2i, 2i+1) of the first `width`
    dims of position p by the angle p * theta^(-2i / width); the rest passes."""
    part = x[..., :width]
    inv_freq = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = part[..., 0::2], part[..., 1::2]
    rotated = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(part.shape)
    return jnp.concatenate([rotated, x[..., width:]], axis=-1)


def _attention(x, w, *, eps: float, theta: float, rotated: int, causal: bool = True, wrong: Tuple[str, ...] = ()):
    """x + attention(norm_1(x)) on one sequence, queries in blocks.  w: this
    layer's `attn` leaves (wq [d, H, 2 D], wk / wv [d, Hkv, D], q_norm / k_norm
    [D], wo [H, D, d]) and `ln1`."""
    a = w["attn"]
    s = x.shape[0]
    centred = "norm_not_centred" not in wrong
    h = _norm(x, w["ln1"], eps, centred)
    q_gate = jnp.einsum("se,ehd->shd", h, a["wq"])
    head_dim = q_gate.shape[2] // 2
    q, gate = q_gate[..., :head_dim], q_gate[..., head_dim:]
    k = jnp.einsum("se,ehd->shd", h, a["wk"])
    v = jnp.einsum("se,ehd->shd", h, a["wv"])
    width = head_dim if "rope_whole_head" in wrong else rotated
    q = _rotate(_norm(q, a["q_norm"], eps, centred), theta, width)
    k = _rotate(_norm(k, a["k_norm"], eps, centred), theta, width)
    n_heads = q.shape[1]
    qg = q.reshape(s, k.shape[1], n_heads // k.shape[1], head_dim)  # query head i reads key/value head i // group
    block = min(QUERY_BLOCK, s)
    assert s % block == 0, (s, block)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(qg, start, block, axis=0)
        scores = jnp.einsum("qkgd,tkd->kgqt", qb, k) * head_dim ** -0.5
        if causal:
            qpos = start + jnp.arange(block)[:, None]
            scores = jnp.where(jnp.arange(s)[None, :] <= qpos, scores, -jnp.inf)
        ctx = jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(scores, axis=-1), v).reshape(block, n_heads, head_dim)
        if "no_output_gate" not in wrong:
            ctx = ctx * jax.nn.sigmoid(jax.lax.dynamic_slice_in_dim(gate, start, block, axis=0))
        return jnp.einsum("qhd,hde->qe", ctx, a["wo"])

    return x + jax.lax.map(one_block, jnp.arange(0, s, block)).reshape(s, x.shape[1])


# -- the expert block -------------------------------------------------------------------


def _route(h, router, *, top_k: int, renormalize: bool):
    """h [T, d] -> the gate values as a dense [T, E] weight, 0 where not chosen."""
    gates, chosen = jax.lax.top_k(jax.nn.softmax(h @ router, axis=-1), top_k)
    if renormalize:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(chosen, router.shape[1], dtype=h.dtype) * gates[..., None], axis=1)


def expert_block(h, mlp, *, first: int, top_k: int, renormalize: bool):
    """(the held experts' part of the routed sum, the gated shared expert) of
    normed rows h [T, d]: every held expert on every row, masked by who chose
    it.  The two are returned apart for the test that adds the shares up."""
    held = mlp["w_up"].shape[0]
    weight = _route(h, mlp["router"], top_k=top_k, renormalize=renormalize)[:, first: first + held]
    inner = jax.nn.silu(jnp.einsum("td,ndf->ntf", h, mlp["w_gate"])) * jnp.einsum("td,ndf->ntf", h, mlp["w_up"])
    routed = jnp.einsum("ntd,tn->td", jnp.einsum("ntf,nfd->ntd", inner, mlp["w_down"]), weight)
    shared = jax.nn.sigmoid(h @ mlp["shared"]["gate"]) * _swiglu(h, mlp["shared"])
    return routed, shared


def _experts(x, w, *, eps: float, wrong: Tuple[str, ...] = (), **routing):
    """x + experts(norm_2(x)) on one sequence, in row blocks."""
    s = x.shape[0]
    block = min(ROW_BLOCK, s)
    assert s % block == 0, (s, block)

    def one_block(xb):
        routed, shared = expert_block(_norm(xb, w["ln2"], eps, "norm_not_centred" not in wrong), w["mlp"], **routing)
        return xb + routed + shared

    return jax.lax.map(one_block, x.reshape(s // block, block, -1)).reshape(s, -1)


# -- the configuration as the reference reads it -----------------------------------------


def _facts(config: Dict[str, Any]):
    """(the mixer of each layer, keyword arguments of the three halves, eps)."""
    if config["decoder_sparse_step"] != 1 or config["mlp_only_layers"] or config["hidden_act"] != "silu":
        raise ValueError("the reference runs SwiGLU experts in every layer")
    eps = float(config["rms_norm_eps"])
    gdn = dict(eps=eps, key_heads=int(config["linear_num_key_heads"]), key_dim=int(config["linear_key_head_dim"]))
    rotated = int(config["head_dim"] * config["partial_rotary_factor"])
    attn = dict(eps=eps, theta=float(config["rope_theta"]), rotated=rotated)
    experts = dict(eps=eps, first=int(config["share"]["first_expert_held"]), top_k=int(config["num_experts_per_tok"]),
                   renormalize=bool(config["norm_topk_prob"]))
    return layer_kinds(config), gdn, attn, experts, eps


def _checked(wrong) -> Tuple[str, ...]:
    if set(wrong) - set(WRONG):
        raise ValueError(f"wrong names {sorted(set(wrong) - set(WRONG))}, not of {WRONG}")
    return tuple(sorted(wrong))


# -- the forward on the chip: layers streamed ---------------------------------------------

_gdn_jit = jax.jit(_gdn, static_argnames=("eps", "key_heads", "key_dim", "wrong"))
_attention_jit = jax.jit(_attention, static_argnames=("eps", "theta", "rotated", "causal", "wrong"))
_experts_jit = jax.jit(_experts, static_argnames=("eps", "first", "top_k", "renormalize", "wrong"))


@functools.partial(jax.jit, static_argnames=("eps", "centred"))
def _head(x, final_norm, head, *, eps: float, centred: bool):
    return _norm(x, final_norm, eps, centred) @ head


def logits(config: Dict[str, Any], params, tokens, *, last: int, causal: bool = True, wrong: Tuple[str, ...] = ()):
    """Reference logits [N, last, V] (float32) for the LAST `last` positions
    of each sequence of `tokens` [N, S], every position of every layer
    computed.  `params` is the program's parameter tree (any dtype, any
    sharding).  Layers outside, sequences inside: each layer's weights are
    fetched and upcast once.  `causal=False` and `wrong` exist for the tests
    that show the tolerance catches a wrong program (module docstring)."""
    kinds, gdn, attn, experts, eps = _facts(config)
    wrong = _checked(wrong)
    tokens = jnp.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        embed = _local(params["embed"]["tokens"][tokens])
        xs = [embed[i] for i in range(tokens.shape[0])]
        seen: Dict[str, int] = {}
        for kind in kinds:
            w = _local(_take_layer(params[STACKS[kind]], seen.get(kind, 0)))
            seen[kind] = seen.get(kind, 0) + 1
            if kind == "gdn":
                xs = [_gdn_jit(x, w, wrong=wrong, **gdn) for x in xs]
            else:
                xs = [_attention_jit(x, w, causal=causal, wrong=wrong, **attn) for x in xs]
            xs = [_experts_jit(x, w, wrong=wrong, **experts) for x in xs]
        head, final_norm = _local(params["lm_head"]), _local(params["final_norm"])
        return jnp.stack([_head(x[-last:], final_norm, head, eps=eps, centred="norm_not_centred" not in wrong)
                          for x in xs])


# -- the training objective: one pure function, for jax.grad ------------------------------


def objective(config: Dict[str, Any], params, tokens, targets):
    """Mean next-token cross entropy on tokens/targets [N, S] (the job has no
    auxiliary loss: both coefficients are 0), float32 throughout, nothing
    streamed.  `params` must be float32."""
    kinds, gdn, attn, experts, eps = _facts(config)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens]  # [N, S, d]
        seen: Dict[str, int] = {}
        for kind in kinds:
            index = seen.get(kind, 0)
            seen[kind] = index + 1
            w = jax.tree_util.tree_map(lambda a, i=index: a[i], params[STACKS[kind]])
            mix = functools.partial(_gdn, **gdn) if kind == "gdn" else functools.partial(_attention, **attn)
            x = jax.vmap(lambda xi: _experts(mix(xi, w), w, **experts))(x)
        out = _norm(x, params["final_norm"], eps) @ params["lm_head"]
        logp = jax.nn.log_softmax(out, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
