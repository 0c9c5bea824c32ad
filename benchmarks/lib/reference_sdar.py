"""Plain reference for kind "block_diffusion_moe_decoder": SDAR-30B-A3B-Chat
(`model_type: sdar_moe`, JetLM/SDAR-30B-A3B-Chat; SDAR, arXiv:2510.06303, a
block-diffusion model in BD3-LMs' sense, arXiv:2503.09573, over Qwen3-MoE's
block) in straightforward float32 `jax.numpy`, one sequence at a time, layer
by layer in a Python loop.  x is [rows, d]; every norm is an RMSNorm with a
learned scale and `rms_norm_eps`; no bias anywhere.

- a layer, for a row x at position p (everything but the line of `a` is
  row-wise): `u = norm_1(x)`; `q = rope(norm_head(u W_q), p)`, `k =
  rope(norm_head(u W_k), p)` (an RMSNorm with a learned scale [head_dim] over
  each head, the Qwen3 family's `q_norm` / `k_norm`), `v = u W_v`; `a =
  softmax(q k^T * head_dim^-0.5 + M) v`, each K/V head serving heads /
  kv_heads query heads; `x = x + a W_o`; `u' = norm_2(x)`; `r = softmax(u'
  W_r)` over all `share.num_experts_total` experts in float32, the
  `num_experts_per_tok` largest, their values divided by their sum
  (`norm_topk_prob`: over ALL the chosen, held here or not); `x = x + sum_e g_e
  W_down,e (silu(W_gate,e u') * W_up,e u')` over the chosen experts that are
  HELD (the tree holds the experts `first .. first + held` only, one rank's
  share of an expert-parallel deployment; what the absent ones would have
  added is left out, here as in the program: `reference_mellum.expert_part`).
- the rope: the default one, `inv_freq = theta^(-2i/D)`, rotating adjacent
  pairs (2i, 2i+1) of position p by `p * inv_freq[i]`, the whole head.
- the MASK M, built as an explicit boolean array from rules (`seen`), with the
  sequence cut into blocks of `assumed.block_length` tokens, b(i) = i // B:
  (a) the PLAIN forward, `logits`: S rows at positions 0..S-1, row i sees row
  j iff `b(j) <= b(i)`; row i's logits are the model's word on token i (no
  shift).  (b) the TRAINING forward, `training_logits`: the 2S rows `[x_t ‖
  x_0]` at positions `[0..S-1 ‖ 0..S-1]`; a noisy row i sees a noisy row j
  iff `b(j) == b(i)` and a clean row j iff `b(j) < b(i)`; a clean row i sees
  a clean row j iff `b(j) <= b(i)` and no noisy row; logits of the noisy rows.
- (c) the objective, `objective`: `(1/S) sum_i m_i / t_b(i) * (lse(logits_i) -
  logits_i[x_0,i])` over the noisy rows, the mean over sequences, plus
  `router_aux_loss_coef` times the load-balancing loss as Hugging Face's
  `load_balancing_loss_func` computes it over the router logits of ALL layers
  and all 2S rows concatenated; no z-loss.  `jax.grad` of it is the reference
  gradient.
- (d) the noise, `noise`: this file's OWN copy of the recipe the program
  documents (`ray_tpu/models/lm.py` `diffusion_noise`), so that the same key
  gives the same mask: `k_u, k_order, k_mask = split(key, 3)`; one `u ~ U[0,
  1)` a sequence; strata `frac(u + k / n)`; dealt to the blocks in the order
  `argsort(U[0, 1) [N, n])`; `t = eps + (1 - eps) * stratum`; `m = U[0, 1)
  [N, S] < t`; `x_t = mask id where m`.

No kernel, no scan over layers, no sort or grouping, no cache, no sharding and
no import from `ray_tpu`: it shares with the program only the layout of the
parameter tree it is handed (`params["layers"]`: `attn` wq [d, H, D], wk / wv
[d, Hkv, D], wo [H, D, d], q_norm / k_norm [D]; `mlp` router [d, E], w_gate /
w_up [held, d, F], w_down [held, F, d]; `ln1`, `ln2`).  Everything runs under
`jax.default_matmul_precision("highest")`.  On the chip the two forwards
stream one layer's weights at a time, upcast as they are used, queries in
blocks of `QUERY_BLOCK` and expert rows in blocks of `ROW_BLOCK`, so that
16,384 rows fit beside the training state; every row passes every layer but
the last, which (with the head) runs for the `last` query rows asked for.
`tolerance(L)` is the dense reference's.

THE CONTROL (`logits(..., lowered=...)`, `training_logits(..., lowered=...)`),
as `reference_mellum` has it: the configuration states float32 for the router,
for every norm's statistics and for the rope; `lowered` names which of `STATED`
the reference computes in bfloat16 instead, what a program that forgot an
upcast would produce; and `WEIGHTS` (as `reference_glm_moe_lite` has it) rounds
every weight it is handed to float8 (e4m3), the nearest precision below the
file's bfloat16 weights.  `scripts/precision_control.py` reads, on the chip at the
cell's sizes, how far that moves BOTH compared outputs (PERF.md section 6, PR 62).

Departures from the published description, all noted: rotary pairs are
adjacent dims as in the dense reference (Hugging Face's rotate_half is the
same function under a fixed permutation of each head's columns, which seeded
weights do not see); the batch is packed sequences with no padding mask and
attention crosses document boundaries (what the program does too; `assumed` in
the configuration file); the block length, the noise schedule and the mask id
are not in `config.json` and come from the file's `assumed`; the ids, the
logits and the loss are over this chip's slice of the vocabulary.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from benchmarks.lib.reference import QUERY_BLOCK, _local, _rms_norm, _take_layer, rel_rms_error, tolerance
# the held share's routed sum, `x + routed(norm_2(x))`, in row blocks; the control's parts, its dtype and its norm
from benchmarks.lib.reference_mellum import LOW, STATED, _experts, _norm

from benchmarks.lib.reference_glm_moe_lite import WEIGHTS, _float8_local

__all__ = ["logits", "training_logits", "objective", "noise", "seen", "rel_rms_error", "tolerance", "STATED", "WEIGHTS"]


# -- the mask and the noise -------------------------------------------------------------


def seen(qrow, krow, *, block: int, noisy_rows: int):
    """bool [q, k]: whether the query at row `qrow` of the call sees the key
    at row `krow`, the three rules written out.  Rows `< noisy_rows` are the
    noisy copy, the rest the clean copy; a row's position is its index in its
    own copy, its block `position // block`."""
    q_noisy, k_noisy = (qrow < noisy_rows)[:, None], (krow < noisy_rows)[None, :]
    qb = (jnp.where(qrow < noisy_rows, qrow, qrow - noisy_rows) // block)[:, None]
    kb = (jnp.where(krow < noisy_rows, krow, krow - noisy_rows) // block)[None, :]
    noisy_to_noisy = q_noisy & k_noisy & (kb == qb)
    noisy_to_clean = q_noisy & ~k_noisy & (kb < qb)
    clean_to_clean = ~q_noisy & ~k_noisy & (kb <= qb)
    return noisy_to_noisy | noisy_to_clean | clean_to_clean  # clean -> noisy: never


def noise(key, tokens, *, block: int, mask_id: int, eps: float):
    """(x_t [N, S], m [N, S] bool, t [N, S] float32) of tokens [N, S]: the
    module docstring's (d)."""
    n_seqs, seq = tokens.shape
    blocks = seq // block
    k_u, k_order, k_mask = jax.random.split(key, 3)
    u = jax.random.uniform(k_u, (n_seqs, 1))
    strata = jnp.mod(u + jnp.arange(blocks, dtype=jnp.float32) / blocks, 1.0)
    order = jnp.argsort(jax.random.uniform(k_order, (n_seqs, blocks)), axis=-1)
    t_block = eps + (1.0 - eps) * jnp.take_along_axis(strata, order, axis=-1)
    t = jnp.repeat(t_block, block, axis=-1)
    m = jax.random.uniform(k_mask, (n_seqs, seq)) < t
    return jnp.where(m, mask_id, tokens).astype(tokens.dtype), m, t


# -- a layer's attention half, `x + attention(norm_1(x))`, on the rows of one sequence -------


def _rotate(x, positions, theta: float, low: bool = False):
    """x [rows, heads, D]: each adjacent pair (2i, 2i+1) of the row at position p by the angle p * theta^(-2i/D);
    `low`: positions, angles, cos and sin in bfloat16."""
    d = x.shape[-1]
    dtype = LOW if low else jnp.float32
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(dtype)[:, None] * inv_freq.astype(dtype)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :].astype(x.dtype), jnp.sin(ang)[:, None, :].astype(x.dtype)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(x.shape)


def _attention(x, w, *, eps: float, theta: float, block: int, noisy_rows: int, masked: bool = True,
               queries: Optional[Tuple[int, int]] = None, lowered: Tuple[str, ...] = ()):
    """x [rows, d] -> x + attention, for every row or (`queries` = (first,
    count)) for those query rows alone, which still see every row's key."""
    a = w["attn"]
    rows = x.shape[0]
    positions = jnp.arange(rows)
    positions = jnp.where(positions < noisy_rows, positions, positions - noisy_rows)
    h = _norm(x, w["ln1"], eps, "norms" in lowered)
    q = jnp.einsum("se,ehd->shd", h, a["wq"])
    k = jnp.einsum("se,ehd->shd", h, a["wk"])
    v = jnp.einsum("se,ehd->shd", h, a["wv"])
    q, k = _norm(q, a["q_norm"], eps, "norms" in lowered), _norm(k, a["k_norm"], eps, "norms" in lowered)
    q, k = _rotate(q, positions, theta, "rope" in lowered), _rotate(k, positions, theta, "rope" in lowered)
    n_heads, head_dim = q.shape[1], q.shape[2]
    qg = q.reshape(rows, k.shape[1], n_heads // k.shape[1], head_dim)  # query head i reads key/value head i // group
    first, count = queries or (0, rows)
    size = min(QUERY_BLOCK, count)
    assert count % size == 0, (count, size)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(qg, start, size, axis=0)
        scores = jnp.einsum("qkgd,tkd->kgqt", qb, k) * head_dim ** -0.5
        if masked:
            scores = jnp.where(seen(start + jnp.arange(size), jnp.arange(rows), block=block, noisy_rows=noisy_rows),
                               scores, -jnp.inf)
        ctx = jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(scores, axis=-1), v).reshape(size, n_heads, head_dim)
        return jnp.einsum("qhd,hde->qe", ctx, a["wo"])

    out = jax.lax.map(one_block, first + jnp.arange(0, count, size)).reshape(count, x.shape[1])
    return jax.lax.dynamic_slice_in_dim(x, first, count, axis=0) + out


# -- the configuration as the reference reads it -------------------------------------------


def _facts(config: Dict[str, Any]):
    """(keyword arguments of the attention half without the rows' layout, of the experts' half, the block length)."""
    if config["hidden_act"] != "silu" or config.get("mlp_only_layers") or config.get("decoder_sparse_step", 1) != 1:
        raise ValueError("the reference runs SwiGLU experts in every layer")
    assumed = config["assumed"]
    eps = float(config["rms_norm_eps"])
    attn = dict(eps=eps, theta=float(config["rope_theta"]), block=int(assumed["block_length"]["value"]))
    experts = dict(eps=eps, first=int(config["share"]["first_expert_held"]), top_k=int(config["num_experts_per_tok"]),
                   renormalize=bool(config["norm_topk_prob"]))
    return attn, experts


def mask_id(config: Dict[str, Any]) -> int:
    """`assumed.mask_token_id`: the last row of the slice."""
    return config["vocab_size"] - 1


_attention_jit = jax.jit(_attention, static_argnames=("eps", "theta", "block", "noisy_rows", "masked", "queries", "lowered"))
_experts_jit = jax.jit(_experts, static_argnames=("eps", "first", "top_k", "renormalize", "lowered"))


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _head(x, final_norm, head, *, eps: float, low: bool = False):
    return _norm(x, final_norm, eps, low) @ head


def _forward(config, params, rows, *, noisy_rows: int, last: int, masked: bool = True, lowered: Tuple[str, ...] = ()):
    """Logits [N, last, V] of the LAST `last` rows of the noisy copy (of the
    one copy when `noisy_rows` is 0) of each sequence of `rows` [N, R] token
    ids.  Layers outside, sequences inside: each layer's weights are fetched
    and upcast once; the last layer and the head run for the asked rows alone.
    `lowered` is the control of the module docstring."""
    if set(lowered) - {*STATED, WEIGHTS}:
        raise ValueError(f"lowered names {sorted(set(lowered) - {*STATED, WEIGHTS})}, not of {STATED + (WEIGHTS,)}")
    local = _float8_local if WEIGHTS in lowered else _local
    lowered = tuple(sorted(part for part in lowered if part != WEIGHTS))
    attn, experts = _facts(config)
    rows = jnp.asarray(rows)
    copy = noisy_rows or rows.shape[1]
    n_layers = config["num_hidden_layers"]
    with jax.default_matmul_precision("highest"):
        embed = local(params["embed"]["tokens"][rows])
        xs = [embed[i] for i in range(rows.shape[0])]
        for index in range(n_layers):
            w = local(_take_layer(params["layers"], index))
            queries = (copy - last, last) if index == n_layers - 1 else None
            xs = [_attention_jit(x, w, noisy_rows=noisy_rows, masked=masked, queries=queries, lowered=lowered, **attn) for x in xs]
            xs = [_experts_jit(x, w, lowered=lowered, **experts)[0] for x in xs]
        head, final_norm = local(params["lm_head"]), local(params["final_norm"])
        return jnp.stack([_head(x, final_norm, head, eps=attn["eps"], low="norms" in lowered) for x in xs])


def logits(config: Dict[str, Any], params, tokens, *, last: int, masked: bool = True, lowered: Tuple[str, ...] = ()):
    """(a) The plain forward: reference logits [N, last, V] (float32) for the
    LAST `last` positions of each sequence of `tokens` [N, S] under the
    block-causal mask.  `params` is the program's parameter tree (any dtype,
    any sharding).  `masked=False` exists for the test that shows the
    tolerance catches a dropped mask."""
    return _forward(config, params, tokens, noisy_rows=0, last=last, masked=masked, lowered=lowered)


def training_logits(config: Dict[str, Any], params, noisy, clean, *, last: int, masked: bool = True,
                    lowered: Tuple[str, ...] = ()):
    """(b) The training forward: reference logits [N, last, V] for the LAST
    `last` NOISY rows of `[noisy ‖ clean]` ([N, S] each) under the explicit
    three-rule mask."""
    noisy, clean = jnp.asarray(noisy), jnp.asarray(clean)
    return _forward(config, params, jnp.concatenate([noisy, clean], axis=1), noisy_rows=noisy.shape[1], last=last,
                    masked=masked, lowered=lowered)


def objective(config: Dict[str, Any], params, tokens, key):
    """(c) (objective, its terms) on tokens [N, S] with the noise of `key`,
    float32 throughout, nothing streamed: the block-diffusion NELBO's data
    term + `router_aux_loss_coef` * load balancing over the 2S rows of all
    layers concatenated.  `params` must be float32."""
    attn, experts = _facts(config)
    n_experts, top_k = config["share"]["num_experts_total"], config["num_experts_per_tok"]
    eps_t = float(config["assumed"]["noise_schedule"]["eps"])
    seq = tokens.shape[1]
    noisy, m, t = noise(key, tokens, block=attn["block"], mask_id=mask_id(config), eps=eps_t)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][jnp.concatenate([noisy, tokens], axis=1)]  # [N, 2S, d]
        all_logits = []
        for index in range(config["num_hidden_layers"]):
            w = jax.tree_util.tree_map(lambda a, i=index: a[i], params["layers"])
            x = jax.vmap(functools.partial(_attention, w=w, noisy_rows=seq, **attn))(x)
            x, router_logits = jax.vmap(functools.partial(_experts, w=w, **experts))(x)
            all_logits.append(router_logits.reshape(-1, n_experts))
        out = _rms_norm(x[:, :seq], params["final_norm"], attn["eps"]) @ params["lm_head"]  # the noisy rows
        nll = -jnp.take_along_axis(jax.nn.log_softmax(out, axis=-1), tokens[..., None], axis=-1)[..., 0]
        ce = jnp.mean(jnp.sum(m * nll / t, axis=-1) / seq)
        rows = jnp.concatenate(all_logits, axis=0)  # [L * N * 2S, E], as Hugging Face concatenates
        probs = jax.nn.softmax(rows, axis=-1)
        _, chosen = jax.lax.top_k(probs, top_k)
        share = jnp.mean(jax.nn.one_hot(chosen, n_experts, dtype=jnp.float32), axis=0)  # f [K, E]
        lb = n_experts * jnp.sum(share * jnp.mean(probs, axis=0)[None, :])
    return ce + config["router_aux_loss_coef"] * lb, {"ce_loss": ce, "moe_lb_loss": lb}
