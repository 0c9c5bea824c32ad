"""Plain reference for kind "nemotron_h_decoder": Nemotron-H (arXiv:2504.03624;
Hugging Face `model_type: nemotron_h`, as NVIDIA-Nemotron-3-Nano-30B-A3B has
it) in straightforward float32 `jax.numpy`, one sequence at a time.  x is
[S, d]; every norm is an RMSNorm with a learned scale and
`layer_norm_epsilon`; no bias anywhere but the convolution's; NO rotary
embedding (`rope_theta` and `partial_rotary_factor` are keys the
`nemotron_h` attention does not use).

- model: `h = embed[tokens]`; for each character of `hybrid_override_pattern`
  (the first `num_hidden_layers` of them) ONE block, `h = h + block(norm_i(h))`;
  `logits = norm_f(h) @ lm_head` (untied).  The stack is a LIST OF SINGLE
  BLOCKS here, read from the pattern; the program pairs them.
- `M`, Mamba-2 (SSD, arXiv:2405.21060), H heads of P, state N, G groups:
  `in_proj: d -> [z: HP | xBC: HP + 2GN | dt: H]`; `xBC = silu(conv(xBC))`, the
  causal depthwise convolution of width `conv_kernel` written as that many
  SHIFTED ADDS with its bias, zeros before the start; split into x [S, H, P],
  B [S, G, N], C [S, G, N]; `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`;
  head j reads group `j // (H / G)`; the recurrence TOKEN BY TOKEN, a
  `lax.scan` over S with the [H, P, N] state from zero:
  `H_t = exp(dt_t A) H_{t-1} + dt_t x_t (outer) B_t`, `y_t = H_t C_t + D x_t`;
  `y = RMSNorm_per_group_of_(HP / G)(y * silu(z)) * scale[HP]`; `out_proj`.
- `*`, attention: `q: d -> heads x head_dim`, `k, v: d -> kv_heads x head_dim`
  (the head size is the configuration's own, not d / heads), causal softmax of
  `q k^T * head_dim^-0.5` in query blocks, each K/V head serving heads /
  kv_heads query heads, `o`.
- `E`, experts: `s = sigmoid(u W_r)` over all `share.num_experts_total`
  experts; the choice is the `num_experts_per_tok` largest of `s + b` (b the
  stored `e_score_correction_bias`; one group: `n_group` 1, `topk_group` 1); the
  gate values are the chosen s divided by their sum + 1e-20
  (`norm_topk_prob`), times `routed_scaling_factor`;
  `out = sum_e gate_e W_down,e relu(W_up,e u)^2 + W_down,s relu(W_up,s u)^2`:
  two matrices an expert, no gate (`mlp_hidden_act: relu2`).  The tree holds
  the experts `first .. first + held` only (one rank's share of an
  expert-parallel deployment): the sum runs over the chosen experts that are
  HELD, and what the absent ones would have added is left out, here as in the
  program.  `first` is `share.first_expert_held`, `held` is read off the
  leaves' shapes.

No chunking, no kernel, no cache, no sharding, and no import from `ray_tpu`:
it shares with the program only the layout of the parameter tree it is handed
(`block_leaves`: the program stacks its layers per PAIR of a mixer and what
follows it, `M E` -> (mamba, experts), `* E` -> (attention, experts), an `M`
or `*` with no `E` behind it -> (.., none); one stack per pair, `layers` /
`mamba_layers`, with `_<ffn>` when the model pairs that mixer with more than
one kind; a pair's first block is normed by `ln1`, its second by `ln2`), so a
wrong chunk boundary, group, decay or mask in the program cannot be wrong
twice.  The sizes (H, P, N, G, heads) are read off the leaves' shapes and the
configuration.

Everything runs under `jax.default_matmul_precision("highest")`.  On the chip
`logits` streams one block's weights at a time, upcast as they are used, and
every position of every block is computed (the recurrence needs them all); the
head runs on the last `last` positions.  `jax.grad` of `objective` is the
reference gradient.  `tolerance(L)` is the dense reference's, over the BLOCKS
(`num_hidden_layers`).

Departures, all noted: the whole batch is packed sequences with no padding
mask and no reset of the state or the convolution at a document boundary
(what the program does too; `assumed` in the configuration file).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from benchmarks.lib.reference import QUERY_BLOCK, _head, _local, _rms_norm, _take_layer, rel_rms_error, tolerance

__all__ = ["logits", "objective", "pattern", "layer_pairs", "block_leaves", "rel_rms_error", "tolerance"]

ROW_BLOCK = 2048  # rows of an expert block held at once
MIXERS = {"M": "mamba", "*": "attention"}
STACK = {"mamba": "mamba_layers", "attention": "layers"}


def pattern(config: Dict[str, Any]) -> str:
    """The blocks that run: the first `num_hidden_layers` characters of the published pattern."""
    blocks = config["hybrid_override_pattern"][: config["num_hidden_layers"]]
    if len(blocks) != config["num_hidden_layers"] or set(blocks) - set("M*E"):
        raise ValueError(f"hybrid_override_pattern gives {blocks!r} for {config['num_hidden_layers']} blocks of M, * and E")
    return blocks


def layer_pairs(config: Dict[str, Any]) -> List[Tuple[str, str]]:
    """The blocks as the program pairs them: a mixer and the expert block
    behind it, or a mixer alone.  An expert block with no mixer before it (a
    leading `E`, an `EE`) is not a pair: the published pattern has none."""
    blocks, pairs, i = pattern(config), [], 0
    while i < len(blocks):
        if blocks[i] == "E":
            raise ValueError(f"block {i} of {blocks!r} is an expert block with no mixer before it")
        follows = i + 1 < len(blocks) and blocks[i + 1] == "E"
        pairs.append((MIXERS[blocks[i]], "experts" if follows else "none"))
        i += 2 if follows else 1
    return pairs


def stack_name(pairs: List[Tuple[str, str]], mixer: str, ffn: str) -> str:
    several = len({f for m, f in pairs if m == mixer}) > 1
    return f"{STACK[mixer]}_{ffn}" if several else STACK[mixer]


def block_leaves(config: Dict[str, Any]) -> List[Tuple[str, str, int, str]]:
    """Where each single block's weights lie in the program's tree: (kind `M`,
    `*` or `E`; stack; index in the stack; the name of its norm's scale)."""
    pairs, out = layer_pairs(config), []
    seen: Dict[Tuple[str, str], int] = {}
    for mixer, ffn in pairs:
        name, index = stack_name(pairs, mixer, ffn), seen.get((mixer, ffn), 0)
        seen[mixer, ffn] = index + 1
        out.append(("M" if mixer == "mamba" else "*", name, index, "ln1"))
        if ffn == "experts":
            out.append(("E", name, index, "ln2"))
    return out


# -- the three blocks, each `x + block(norm(x))` on one sequence ---------------------


def _conv(x, w, b):
    """Causal depthwise convolution as K shifted adds: x [S, C], w [C, K]."""
    s, k = x.shape[0], w.shape[1]
    out = jnp.broadcast_to(b, x.shape)
    for i in range(k):
        shift = k - 1 - i  # w[:, i] multiplies x_{t - shift}
        shifted = jnp.concatenate([jnp.zeros((shift, x.shape[1]), x.dtype), x[: s - shift]], axis=0)
        out = out + shifted * w[:, i]
    return out


def _recurrence(x, dt, A, B, C, D):
    """Token by token.  x [S, H, P], dt [S, H], A [H], B/C [S, G, N], D [H] ->
    y [S, H, P]; head j reads group j // (H / G); the state [H, P, N] starts at zero."""
    heads, p = x.shape[1], x.shape[2]
    per_group = heads // B.shape[1]

    def step(state, inp):
        xt, dtt, bt, ct = inp
        bt, ct = jnp.repeat(bt, per_group, axis=0), jnp.repeat(ct, per_group, axis=0)  # [H, N]
        state = jnp.exp(dtt * A)[:, None, None] * state + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        return state, jnp.sum(state * ct[:, None, :], axis=-1) + D[:, None] * xt

    _, y = jax.lax.scan(step, jnp.zeros((heads, p, B.shape[2]), x.dtype), (x, dt, B, C))
    return y


def _mamba(x, norm, w, *, eps: float, groups: int, state: int):
    m = w["ssm"]
    s = x.shape[0]
    heads, inner = m["A_log"].shape[0], m["norm"].shape[0]
    gn = groups * state
    zxbcdt = _rms_norm(x, norm, eps) @ m["in_proj"]
    z, xbc, dt = zxbcdt[:, :inner], zxbcdt[:, inner: 2 * inner + 2 * gn], zxbcdt[:, 2 * inner + 2 * gn:]
    xbc = jax.nn.silu(_conv(xbc, m["conv_w"], m["conv_b"]))
    xs, b, c = xbc[:, :inner], xbc[:, inner: inner + gn], xbc[:, inner + gn:]
    dt = jax.nn.softplus(dt + m["dt_bias"])
    y = _recurrence(xs.reshape(s, heads, inner // heads), dt, -jnp.exp(m["A_log"]),
                    b.reshape(s, groups, state), c.reshape(s, groups, state), m["D"])
    gated = (y.reshape(s, inner) * jax.nn.silu(z)).reshape(s, groups, inner // groups)
    y = (gated * jax.lax.rsqrt(jnp.mean(gated * gated, axis=-1, keepdims=True) + eps)).reshape(s, inner) * m["norm"]
    return x + y @ m["out_proj"]


def _attention(x, norm, w, *, eps: float, causal: bool = True):
    """w["attn"]: wq [d, H, D], wk / wv [d, Hkv, D], wo [H, D, d]."""
    a = w["attn"]
    s = x.shape[0]
    h = _rms_norm(x, norm, eps)
    q = jnp.einsum("se,ehd->shd", h, a["wq"])
    k = jnp.einsum("se,ehd->shd", h, a["wk"])
    v = jnp.einsum("se,ehd->shd", h, a["wv"])
    n_heads, head_dim = q.shape[1], q.shape[2]
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
        return jnp.einsum("qhd,hde->qe", ctx, a["wo"])

    return x + jax.lax.map(one_block, jnp.arange(0, s, block)).reshape(s, x.shape[1])


def _relu2(h, w):
    return jnp.square(jax.nn.relu(h @ w["w_up"])) @ w["w_down"]


def route(h, router, bias, *, top_k: int, renormalize: bool, scaling: float):
    """h [T, d] -> the gate values as a dense [T, E] weight, 0 where not chosen."""
    scores = jax.nn.sigmoid(h @ router)
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    if renormalize:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    onehot = jax.nn.one_hot(chosen, router.shape[1], dtype=h.dtype)
    return jnp.sum(onehot * (gates * scaling)[..., None], axis=1)


def expert_parts(h, mlp, *, first: int, top_k: int, renormalize: bool, scaling: float):
    """(the held experts' part of the routed sum, the shared expert) of normed
    rows h [T, d]: every held expert on every row, masked by who chose it."""
    held = mlp["w_up"].shape[0]
    weight = route(h, mlp["router"], mlp["router_bias"], top_k=top_k, renormalize=renormalize,
                   scaling=scaling)[:, first: first + held]
    inner = jnp.square(jax.nn.relu(jnp.einsum("td,ndf->ntf", h, mlp["w_up"])))
    routed = jnp.einsum("ntd,tn->td", jnp.einsum("ntf,nfd->ntd", inner, mlp["w_down"]), weight)
    return routed, _relu2(h, mlp["shared"])


def _experts(x, norm, w, *, eps: float, **routing):
    s = x.shape[0]
    block = min(ROW_BLOCK, s)
    assert s % block == 0, (s, block)

    def one_block(xb):
        routed, shared = expert_parts(_rms_norm(xb, norm, eps), w["mlp"], **routing)
        return xb + routed + shared

    return jax.lax.map(one_block, x.reshape(s // block, block, -1)).reshape(s, -1)


def _facts(config: Dict[str, Any]):
    """(where the blocks' weights lie, keyword arguments of each kind of block)."""
    if config["n_group"] != 1 or config["topk_group"] != 1 or config["mlp_hidden_act"] != "relu2":
        raise ValueError("the reference routes in one group over two-matrix relu2 experts")
    eps = float(config["layer_norm_epsilon"])
    return block_leaves(config), {
        "M": dict(eps=eps, groups=int(config["n_groups"]), state=int(config["ssm_state_size"])),
        "*": dict(eps=eps),
        "E": dict(eps=eps, first=int(config["share"]["first_expert_held"]), top_k=int(config["num_experts_per_tok"]),
                  renormalize=bool(config["norm_topk_prob"]), scaling=float(config["routed_scaling_factor"])),
    }


_BLOCKS = {"M": _mamba, "*": _attention, "E": _experts}
_JITTED = {
    "M": jax.jit(_mamba, static_argnames=("eps", "groups", "state")),
    "*": jax.jit(_attention, static_argnames=("eps", "causal")),
    "E": jax.jit(_experts, static_argnames=("eps", "first", "top_k", "renormalize", "scaling")),
}


def logits(config: Dict[str, Any], params, tokens, *, last: int, causal: bool = True):
    """Reference logits [N, last, V] (float32) for the LAST `last` positions
    of each sequence of `tokens` [N, S], every position of every block
    computed.  `params` is the program's parameter tree (any dtype, any
    sharding).  Blocks outside, sequences inside: each block's weights are
    fetched and upcast once."""
    blocks, kwargs = _facts(config)
    tokens = jnp.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        embed = _local(params["embed"]["tokens"][tokens])
        xs = [embed[i] for i in range(tokens.shape[0])]
        for kind, stack, index, norm in blocks:
            w = _local(_take_layer(params[stack], index))
            extra = {"causal": causal} if kind == "*" else {}
            xs = [_JITTED[kind](x, w[norm], w, **kwargs[kind], **extra) for x in xs]
        head, final_norm = _local(params["lm_head"]), _local(params["final_norm"])
        return jnp.stack([_head(x[-last:], final_norm, head, eps=kwargs["*"]["eps"]) for x in xs])


def objective(config: Dict[str, Any], params, tokens, targets):
    """Mean next-token cross entropy on tokens/targets [N, S] (the model has
    no auxiliary loss), float32 throughout, nothing streamed.  `params` must
    be float32."""
    blocks, kwargs = _facts(config)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens]  # [N, S, d]
        for kind, stack, index, norm in blocks:
            w = jax.tree_util.tree_map(lambda a, i=index: a[i], params[stack])
            x = jax.vmap(functools.partial(_BLOCKS[kind], norm=w[norm], w=w, **kwargs[kind]))(x)
        out = _rms_norm(x, params["final_norm"], kwargs["*"]["eps"]) @ params["lm_head"]
        logp = jax.nn.log_softmax(out, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
