"""Language-model training step: loss, optimizer state, pjit factory.

This is the compiled SPMD "inner loop" that the Train library (ray_tpu.train)
drives from host actors — the TPU replacement for the reference's
DDP-wrapped user loop (python/ray/train/torch/train_loop_utils.py:92-98 +
NCCL allreduce).  Gradient reduction is not a runtime call: the mesh sharding
of params/batch makes XLA emit reduce-scatter/all-reduce over ICI.

Two objectives (`LMTrainContext._loss`).  A next-token model's: the mean
cross entropy of each position's logits against the token after it
(`head_cross_entropy`), plus the router's terms and a multi-token-prediction
module's.  A block-diffusion model's (`TransformerConfig.diffusion_block`):
the block-diffusion NELBO under a linear schedule (`diffusion_noise`,
`head_weighted_cross_entropy`): each block k of a sequence gets a masking
rate t_k, each of its tokens is replaced by the mask id with probability t_k,
the noisy copy runs the stack beside the clean one, and the loss is `(1/S)
sum_i m_i / t_b(i) * -log p(x_0,i | row i)` over the MASKED rows of the noisy
half, row i predicting token i (no shift; the batch's `targets` are not
read), plus the router's terms over all 2S rows.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from ray_tpu.models.mixers import MIXERS
from ray_tpu.models.mixers.dsa import INDEX_KL, SELECTED_PAIRS, SPARSE  # what a learned-sparse layer reports, and its kind
from ray_tpu.models.moe import router_losses
from ray_tpu.models.transformer import (
    LOGITS_AXES,
    TransformerConfig,
    _constrainer,
    biases_following_load,
    check_placement,
    diffusion_forward,
    forward,
    init_params,
    mtp_forward,
    mtp_rows,
    param_axes,
    saved_names,
    trunk,
    trunk_reports,
)
from ray_tpu.parallel.mesh import build_mesh
from ray_tpu.parallel.sharding import (
    Rules,
    fit_shardings,
    logical_to_spec,
    resolve_rules,
    tree_shardings,
)
from ray_tpu.train.run_record import DISPATCH, MAKE_BATCH, StepClock, note_step_counters
from ray_tpu.util import tracing
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def cross_entropy_loss(
    logits: jax.Array, targets: jax.Array, mask: Optional[jax.Array] = None
) -> jax.Array:
    """Mean next-token cross entropy of logits in hand: logits [B,S,V] (any
    float dtype; the softmax runs in theirs), targets [B,S], mask [B,S] or
    None.  The plain form: differentiated by JAX it keeps a [B,S,V]
    log-softmax and scatters into a [B,S,V] zero tensor.  A training step
    uses `head_cross_entropy`, which this is the reference of."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is None:
        return -jnp.mean(ll)
    mask = mask.astype(jnp.float32)
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def head_cross_entropy(constrain, x, head, targets, mask):
    """`cross_entropy_loss(einsum(x, head).astype(f32), targets, mask)` as ONE
    function with its backward written by hand: x [B,S,d] and head [d,V] in
    the model's dtype (`transformer.trunk`'s), targets [B,S], mask [B,S] or
    None -> the float32 scalar.

    The only [B,S,V] arrays that reach HBM are in x's dtype: the logits (the
    matmul's result, rounded as `forward` rounds it) and, in the backward,
    their cotangent.  Row max, log-sum-exp, the softmax and the loss are
    float32 inside the passes that read them; the target's logit is picked by
    compare-and-select against the column index (a gather's transpose is a
    scatter into a [B,S,V] zero tensor).  Residuals: x, head, the logits, the
    float32 [B,S] log-sum-exp.  `constrain` places the logits and their
    cotangent as the sharding rules say (`transformer._constrainer`).  The
    matmuls are named `lm_head`, the passes `loss`, in both directions."""
    return _head_cross_entropy_fwd(constrain, x, head, targets, mask)[0]


def _target_columns(logits, targets):
    """bool [B,S,V]: the target's column in each row."""
    return jax.lax.broadcasted_iota(targets.dtype, logits.shape, 2) == targets[..., None]


def _row_weights(targets, mask):
    """float32 [B,S]: what a row's negative log-likelihood weighs in the loss."""
    if mask is None:
        return jnp.full(targets.shape, 1.0 / targets.size, jnp.float32)
    mask = mask.astype(jnp.float32)
    return mask / jnp.maximum(jnp.sum(mask), 1.0)


def _weights_given(targets, weights):
    """float32 [B,S]: the weights as the caller made them (`head_weighted_cross_entropy`)."""
    del targets
    return weights.astype(jnp.float32)


def _head_cross_entropy_fwd(constrain, x, head, targets, mask, row_weights=_row_weights):
    with tracing.scope("lm_head"):
        logits = constrain(jnp.einsum("bse,ev->bsv", x, head), LOGITS_AXES)
    with tracing.scope("loss"):
        wide = logits.astype(jnp.float32)
        row_max = jnp.max(wide, axis=-1)
        lse = row_max + jnp.log(jnp.sum(jnp.exp(wide - row_max[..., None]), axis=-1))
        target_logit = jnp.sum(jnp.where(_target_columns(logits, targets), wide, 0.0), axis=-1)
        loss = jnp.sum((lse - target_logit) * row_weights(targets, mask))
    return loss, (x, head, logits, lse, targets, mask)


def _head_cross_entropy_bwd(constrain, residuals, g, row_weights=_row_weights):
    x, head, logits, lse, targets, mask = residuals
    with tracing.scope("loss"):
        # Not behind a barrier: XLA fuses this pass into the operand of both
        # matmuls below (the softmax computed twice, in registers) and never
        # writes the cotangent, which measured 3.9 ms a step faster than
        # writing it once (granite-h-micro's cell, PERF.md section 6, PR 34).
        softmax = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
        dlogits = jnp.where(_target_columns(logits, targets), softmax - 1.0, softmax)
        dlogits = dlogits * (g * row_weights(targets, mask))[..., None]
        dlogits = constrain(dlogits.astype(logits.dtype), LOGITS_AXES)
    with tracing.scope("lm_head"):
        dx = jnp.einsum("bsv,ev->bse", dlogits, head)
        dhead = jnp.einsum("bse,bsv->ev", x, dlogits)
    return dx, dhead, None, None


head_cross_entropy.defvjp(_head_cross_entropy_fwd, _head_cross_entropy_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def head_weighted_cross_entropy(constrain, x, head, targets, weights):
    """`head_cross_entropy` with each row's weight GIVEN: `sum_rows weights *
    (lse(logits) - logits[target])`, weights [B,S] float (not differentiated),
    the same passes, residuals and hand-written backward.  A block-diffusion
    step's loss: the weights are `m_i / t_b(i) / rows` (`diffusion_noise`),
    zero on the rows that were not masked."""
    return _head_cross_entropy_fwd(constrain, x, head, targets, weights, _weights_given)[0]


head_weighted_cross_entropy.defvjp(
    functools.partial(_head_cross_entropy_fwd, row_weights=_weights_given),
    functools.partial(_head_cross_entropy_bwd, row_weights=_weights_given))


NOISE_STREAM = 0x5DA2  # folded into `init_state`'s key: the key of a block-diffusion model's noise, beside the weights' draws


def diffusion_noise(key: jax.Array, tokens: jax.Array, *, block: int, mask_id: int, eps: float):
    """The forward process of a block-diffusion step under the linear schedule
    (alpha_t = 1 - t, so the NELBO weighs a masked token by 1/t): tokens
    [N, S] in n = S / block blocks -> (the noisy copy x_t [N, S]; m [N, S]
    bool, the masked tokens; t [N, S] float32, each token's block's rate).

    The recipe, which `benchmarks/lib/reference_sdar.py` copies to the letter
    (same key, same draws): `k_u, k_order, k_mask = jax.random.split(key, 3)`;
    one `u ~ U[0, 1)` a sequence from `k_u` ([N, 1]); the strata `frac(u + k /
    n)`, k < n, one rate in every 1/n of [0, 1) (MDLM's and BD3-LMs'
    low-discrepancy draw, which takes the draw of the rates out of the loss's
    step-to-step spread); the strata dealt to the blocks in the order
    `argsort(U[0, 1) [N, n] from k_order)`; `t = eps + (1 - eps) * stratum`;
    `m = U[0, 1) [N, S] from k_mask < t` of the token's block; `x_t = mask_id`
    where m, else the token.  All float32."""
    n_seqs, seq = tokens.shape
    blocks = seq // block
    k_u, k_order, k_mask = jax.random.split(key, 3)
    strata = (jax.random.uniform(k_u, (n_seqs, 1)) + jnp.arange(blocks, dtype=jnp.float32) / blocks) % 1.0
    order = jnp.argsort(jax.random.uniform(k_order, (n_seqs, blocks)), axis=-1)
    t = jnp.repeat(eps + (1.0 - eps) * jnp.take_along_axis(strata, order, axis=-1), block, axis=-1)
    masked = jax.random.uniform(k_mask, (n_seqs, seq)) < t
    return jnp.where(masked, jnp.asarray(mask_id, tokens.dtype), tokens), masked, t


# The step counters of the run's record (train/run_record.py), among the step's metrics: the key tiles a
# windowed flash forward visits and the grid steps a causal one has, of its rectangle of tile pairs (PR 55: the steps
# that copied; since PR 70 the steps there are, the same count); what a layer that holds a
# share of its experts was given (`models/moe.py` `router_losses`: rows per held expert, mean and busiest, the
# busiest expert's load over the mean, and the share of the T*K assignments whose rows the share's buffers moved);
# the multi-token-prediction module's cross entropy; a block-diffusion step's two: the share of the sequence's
# tokens the noise masked (0.5 in expectation) and the mask's pairs over the pairs of the tiles the flash forward
# visits (a constant of the traced step, like the two of PR 55); and the share of a flash forward's run steps whose tile
# the mask's edge does not cross, which run the body without the mask (PR 63; a constant of the traced step too); and the
# share of the layers with a `KernelPair` recurrence whose forward kernel the backward runs again (PR 64; a constant too); and
# the tile pairs the indexer's forward kernel runs, of all tile pairs (PR 67; a constant too); and the mean gate value
# of a router that is a network with a carried state, and the experts a layer has in use where the stored bias follows the load (PR 68).
WINDOW_TILES = "attn_window_tiles_visited_pct"
CAUSAL_STEPS = "attn_causal_steps_copying_pct"
MASKED_SHARE = "diffusion_masked_share"
DIFFUSION_FILL = "attn_diffusion_mask_fill_pct"
TILES_UNMASKED = "attn_tiles_unmasked_pct"
SCAN_RERUN = "scan_forward_rerun_pct"
CAUSAL_PAIRS = "dsa_causal_pairs"
INDEX_TILES = "dsa_index_tiles_visited_pct"
CHOICE_SHARE = "moe_choice_share"  # the layers' `choice_share` on its way from `_loss` to the bias it moves (`router_bias_update_rate`); no metric
EXPERTS_IN_USE = "moe_experts_in_use"  # `router_losses`, of a job with `router_bias_update_rate`: 16 of 16 is what the rule keeps up
GATE_MEAN = "moe_gate_mean"  # an "mlp" router's mean gate value (`models/moe.py` `router_losses`: top-1 of 16 starts near 1/16)
STEP_COUNTERS = (WINDOW_TILES, CAUSAL_STEPS, "moe_held_rows_mean", "moe_held_rows_max", "moe_load_max_over_mean", "moe_rows_moved_share",
                 "mtp_loss", MASKED_SHARE, DIFFUSION_FILL, TILES_UNMASKED, SCAN_RERUN, INDEX_KL, SELECTED_PAIRS, CAUSAL_PAIRS,
                 INDEX_TILES, GATE_MEAN, EXPERTS_IN_USE)


def _window_counters(config: TransformerConfig, seq: int) -> Dict[str, float]:
    """`WINDOW_TILES`: the key tiles the windowed flash forward visits over
    those a causal call would, the mean over the windowed layers, from the
    block sizes in use at this length (what the kernels would do: off TPU the
    XLA forms run and visit no tile).  Known when the step is traced; nothing
    for a model without a window or a length no tile divides."""
    from ray_tpu.ops.pallas.flash_attention import window_tiles_visited_pct

    visited = [window_tiles_visited_pct(seq, w) for w in config.layer_windows or () if w is not None]
    if not visited or None in visited:
        return {}
    return {WINDOW_TILES: sum(visited) / len(visited)}


def _causal_counters(config: TransformerConfig, seq: int) -> Dict[str, float]:
    """`CAUSAL_STEPS`: the grid steps the causal flash forward HAS, as % of
    the rectangle of a head's tile pairs (`flash_attention.
    causal_steps_copying_pct`, from the table of visible pairs the kernel is
    handed; until PR 70 the steps of that rectangle that made the pipeline
    copy, the same count under the name it keeps), the mean over the layers
    whose core is a causal attention call without a window
    (`Mixer.flash_heads`), at the tiles their head sizes give at this length.
    Known when the step is traced, as `WINDOW_TILES`, and like it a statement
    about the kernels at this length: it is noted whichever form the dispatch
    gives the step (`ops.attention`; off the chip and under the ring no flash
    kernel runs).  Nothing for a model without such a layer (a
    block-diffusion model's calls are not causal) or a length no tile
    divides."""
    from ray_tpu.ops.pallas.flash_attention import causal_forward_tiles, causal_steps_copying_pct

    if config.diffusion_block is not None:
        return {}

    heads = [MIXERS[mixer].flash_heads(config) for i, (mixer, _) in enumerate(config.layer_pairs())
             if config.layer_variant(i)[0] is None]
    tiles = [causal_forward_tiles(seq, *sizes) for sizes in heads if sizes is not None]
    if not tiles or None in tiles:
        return {}
    copying = {t: causal_steps_copying_pct(seq, *t, keys=True) for t in set(tiles)}  # once a tiling, not a layer
    return {CAUSAL_STEPS: sum(copying[t] for t in tiles) / len(tiles)}


def _diffusion_counters(config: TransformerConfig, seq: int) -> Dict[str, float]:
    """`DIFFUSION_FILL`: the pairs the training mask holds over the pairs of
    the tiles the flash forward visits for it (`flash_attention.
    diffusion_mask_fill_pct`), at the tiles the head size gives at this
    length; known when the step is traced and noted whichever form the
    dispatch gives the step, as `CAUSAL_STEPS`.  Nothing at a length no tile
    divides."""
    from ray_tpu.ops.pallas.flash_attention import diffusion_mask_fill_pct

    fill = diffusion_mask_fill_pct(seq, config.diffusion_block, config.head_dim, config.head_dim)
    return {} if fill is None else {DIFFUSION_FILL: fill}


def _unmasked_counters(config: TransformerConfig, seq: int) -> Dict[str, float]:
    """`TILES_UNMASKED`: the run steps of the flash forward that take the
    body without the mask, as % of a head's run steps (`flash_attention.
    tiles_unmasked_pct`, from the predicate the kernels are given), the mean
    over the layers whose core is an attention call (`Mixer.flash_heads`),
    each under its own window or the model's block-diffusion mask, at the
    tiles its head sizes give at this length.  Known when the step is traced
    and noted whichever form the dispatch gives the step, as `CAUSAL_STEPS`.
    Nothing for a model without such a layer or a length no tile divides."""
    from ray_tpu.ops.pallas.flash_attention import tiles_unmasked_pct

    calls = [(MIXERS[mixer].flash_heads(config), config.layer_variant(i)[0]) for i, (mixer, _) in enumerate(config.layer_pairs())]
    calls = [call for call in calls if call[0] is not None]
    shares = {(heads, window): tiles_unmasked_pct(seq, *heads, window=window, diffusion_block=config.diffusion_block)
              for heads, window in set(calls)}  # once a kind of call, not a layer
    if not calls or None in shares.values():
        return {}
    return {TILES_UNMASKED: sum(shares[call] for call in calls) / len(calls)}


def _rerun_counters(config: TransformerConfig) -> Dict[str, float]:
    """`SCAN_RERUN`: of the layers whose mixer runs a `KernelPair` recurrence
    (`Mixer.recurrence`: kda, gdn, Mamba-2's SSD, the selective scan), the %
    whose residual names the configured policy does NOT keep
    (`transformer.saved_names`), i.e. whose forward scan kernel the layer's
    recompute runs again before the backward kernel can start.  From the
    configuration alone, and noted whichever form the dispatch gives the
    step, as `CAUSAL_STEPS`.  0 without `remat` (JAX keeps every residual);
    nothing for a model without such a layer."""
    of_layers = [names for mixer, _ in config.layer_pairs() if (names := MIXERS[mixer].recurrence)]
    if not of_layers:
        return {}
    kept = set(saved_names(config))
    again = [config.remat and not set(names) <= kept for names in of_layers]
    return {SCAN_RERUN: 100.0 * sum(again) / len(again)}


def _index_counters(config: TransformerConfig, seq: int) -> Dict[str, float]:
    """`INDEX_TILES`: the (query tile, key tile) pairs the indexer's forward
    kernel runs, as % of all tile pairs at the tiles in use at this length
    (`ops/pallas/sparse_attention.py` `index_tiles_visited_pct`: the pairs on or
    under the diagonal), the mean over the learned-sparse layers, which share
    one shape; 100 at shapes the kernels refuse, where the plain form scores
    every pair.  Known when the step is traced and noted whichever form the
    dispatch gives the step, as `CAUSAL_STEPS`.  Nothing for a model without
    such a layer."""
    if not any(mixer == SPARSE.name for mixer, _ in config.layer_pairs()):
        return {}
    from ray_tpu.ops.pallas.sparse_attention import index_supported, index_tiles_visited_pct

    takes = index_supported((1, seq, config.index_heads, config.index_head_dim))
    return {INDEX_TILES: index_tiles_visited_pct(seq) if takes else 100.0}


def _index_terms(reports: Dict[str, jax.Array], seq: int) -> Dict[str, jax.Array]:
    """What the learned-sparse layers report (`mixers/dsa.py`), as the terms
    ride: `INDEX_KL`, the SUM over those layers of the indexer's KL term, the
    nats the objective adds to the cross entropy; `SELECTED_PAIRS`, the (query,
    key) pairs a layer's core attended, a sequence, the mean over the layers;
    `CAUSAL_PAIRS`, what a dense causal core would attend, `S (S + 1) / 2`.
    Nothing for a model without such a layer."""
    if INDEX_KL not in reports:
        return {}
    return {INDEX_KL: jnp.sum(reports[INDEX_KL]), SELECTED_PAIRS: jnp.mean(reports[SELECTED_PAIRS]),
            CAUSAL_PAIRS: jnp.float32(seq * (seq + 1) // 2)}


def _mtp_term(params, h, head, batch, config, rules, mesh):
    """(the multi-token-prediction module's cross entropy, its block's router
    statistics or None).  With `batch["targets"]` the token after each
    position, t_{i+1}, the module takes their embeddings beside the trunk's
    output `h` (`transformer.mtp_rows`) and its rows go through the model's
    own `head` against the targets shifted once more, t_{i+2}: the mean over
    the positions that have one (the last has none) and the batch's mask
    admits.  Head and loss are named inside `mtp`."""
    targets = batch["targets"]
    x, stats = mtp_rows(params, h, targets, config, rules=rules, mesh=mesh)
    after_next = jnp.roll(targets, -1, axis=1)
    has_one = jnp.broadcast_to(jnp.arange(targets.shape[1]) < targets.shape[1] - 1, targets.shape)
    if batch.get("mask") is not None:
        has_one = has_one & (jnp.roll(batch["mask"], -1, axis=1) > 0)
    with tracing.scope("mtp"):
        return head_cross_entropy(_constrainer(rules, mesh), x, head, after_next, has_one), stats


# What jax calls the three programs `LMTrainContext` builds (the `fun_name` of their `jax::trace` /
# `jax::lower` / `jax::compile` spans in the run's record, `jit(...)` stripped), and what a reader of the
# record may call them.
PROGRAMS = {"_init": "init", "_forward": "apply", "_train_step": "step"}


def default_optimizer(
    learning_rate: float = 3e-4, weight_decay: float = 0.1, **kw
) -> optax.GradientTransformation:
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(learning_rate, b1=0.9, b2=0.95, weight_decay=weight_decay, **kw),
    )


class LMTrainContext:
    """Sharded init/train-step bundle for one (config, mesh, rules) triple.

    Holds the jitted functions with in/out shardings attached so the host
    code never calls device_put by hand.
    """

    def __init__(
        self,
        config: TransformerConfig,
        mesh: Optional[Mesh] = None,
        strategy: str | Rules = "fsdp",
        optimizer: Optional[optax.GradientTransformation] = None,
    ):
        self.config = config
        self.mesh = mesh if mesh is not None else build_mesh()
        self.rules = resolve_rules(strategy)
        self.optimizer = optimizer or default_optimizer()
        self._step_clock = StepClock()
        check_placement(config, self.rules, self.mesh)  # refuse by name now, not when the step is traced

        raw_shardings = tree_shardings(param_axes(config), self.rules, self.mesh)
        abstract_params = jax.eval_shape(lambda: init_params(config, jax.random.PRNGKey(0)))
        self.param_shardings = fit_shardings(abstract_params, raw_shardings)
        # Optimizer state must be PINNED to the param shardings, not left to
        # propagation: XLA happily replicates adam moments (measured with
        # pp_fsdp), silently forfeiting the ZeRO optimizer-state sharding
        # that is fsdp's whole memory win.  Optax states mirror the param
        # tree, so a moment leaf is matched to its parameter by tree PATH:
        # the tail of its path is the parameter's (shapes cannot tell an
        # expert layer's `w_gate` from its `w_up`).  Leaves that mirror no
        # parameter (step counts) are replicated.
        self.repl = NamedSharding(self.mesh, P())
        by_path = {
            path: (pleaf.shape, psh)
            for (path, pleaf), psh in zip(
                jax.tree_util.tree_flatten_with_path(abstract_params)[0],
                jax.tree_util.tree_leaves(self.param_shardings),
            )
        }

        def pin(path, leaf):
            for start in range(len(path)):
                shape, sharding = by_path.get(path[start:], (None, None))
                if shape == leaf.shape:
                    return sharding
            return self.repl if leaf.ndim == 0 else None

        abstract_opt = jax.eval_shape(self.optimizer.init, abstract_params)
        self.opt_shardings = jax.tree_util.tree_map_with_path(pin, abstract_opt)
        self.batch_sharding = NamedSharding(
            self.mesh, logical_to_spec(("act_batch", "act_seq"), self.rules)
        )

        cfg, rules, opt = self.config, self.rules, self.optimizer

        # A block-diffusion model's state carries the key of its noise beside the step's count, drawn from the
        # seed that draws the weights (`init_state`): step k's noise is `fold_in(noise_key, k)`.  No other
        # model's state has the entry.
        noise_state = {} if cfg.diffusion_block is None else {"noise_key": self.repl}

        def _init(key):
            params = init_params(cfg, key)
            opt_state = opt.init(params)
            state = {"params": params, "opt_state": opt_state, "step": jnp.zeros((), jnp.int32)}
            if noise_state:
                state["noise_key"] = jax.random.fold_in(key, NOISE_STREAM)
            return state

        self._init = jax.jit(
            _init,
            out_shardings={
                "params": self.param_shardings,
                "opt_state": self.opt_shardings,
                "step": self.repl,
                **noise_state,
            },
        )

        def _load_of(router_stats):
            """Beside the terms of a job whose router bias follows the load: what `_train_step` moves it by."""
            return {CHOICE_SHARE: router_stats["choice_share"]} if cfg.router_bias_update_rate else {}

        def _diffusion_loss(params, batch, key):
            """A block-diffusion model's `_loss`: (the block-diffusion NELBO of
            the module docstring + the router's terms over all 2S rows, the
            terms).  The noise is drawn on the device from `key` under the
            scope `diffusion/noise`; a batch's `mask` (0 on padding) weighs
            into the rows' weights; `ce_loss` is the NELBO's data term."""
            constrain = _constrainer(rules, self.mesh)
            tokens = batch["tokens"]
            with tracing.scope("diffusion/noise"):
                noisy, masked, t = diffusion_noise(key, tokens, block=cfg.diffusion_block, mask_id=cfg.mask_id,
                                                   eps=cfg.diffusion_eps)
                m = masked.astype(jnp.float32)
                weights = m / t / m.size
                if batch.get("mask") is not None:
                    weights = weights * batch["mask"].astype(jnp.float32)
                counters = {MASKED_SHARE: jnp.mean(m)}
            x, head, router_stats = trunk(params, tokens, cfg, rules=rules, mesh=self.mesh, noisy=noisy)
            ce = head_weighted_cross_entropy(constrain, x, head, tokens, weights)
            with tracing.scope("loss"):
                counters.update(**_diffusion_counters(cfg, tokens.shape[1]), **_unmasked_counters(cfg, tokens.shape[1]),
                                **_rerun_counters(cfg))
                if router_stats is None:
                    return ce, counters
                terms = router_losses(router_stats, cfg)
                loss = ce + cfg.router_aux_loss_coef * terms["moe_lb_loss"] + cfg.router_z_loss_coef * terms["moe_z_loss"]
                return loss, {"ce_loss": ce, **terms, **counters, **_load_of(router_stats)}

        def _loss(params, batch, noise_key=None):
            """(the objective that is differentiated, its terms).  Dense: the
            cross entropy and no terms.  With experts: cross entropy +
            `router_aux_loss_coef` * load balancing + `router_z_loss_coef` *
            z-loss (formulas in models/moe.py), and the terms unweighted.
            With `mtp_depth`: + `mtp_loss_weight` * the module's cross entropy
            (`_mtp_term`), `ce_loss` and `mtp_loss` among the terms, the
            module's block one more layer of the router statistics.  Beside the
            terms ride the attention kernels' counters, constants of the
            traced step (`_window_counters`, `_causal_counters`,
            `_unmasked_counters`), the recurrences' (`_rerun_counters`) and the
            indexer's (`_index_counters`).  A
            block-diffusion model's is `_diffusion_loss`, from `noise_key`."""
            if cfg.diffusion_block is not None:
                return _diffusion_loss(params, batch, noise_key)
            constrain = _constrainer(rules, self.mesh)
            x, head, router_stats, reports = trunk_reports(
                params, batch["tokens"], cfg, rules=rules, mesh=self.mesh)
            ce = head_cross_entropy(constrain, x, head, batch["targets"], batch.get("mask"))
            beside = _index_terms(reports, batch["tokens"].shape[1])  # the terms beside the cross entropy, `ce_loss` apart from them
            if cfg.mtp_depth:
                beside["mtp_loss"], stats = _mtp_term(params, x, head, batch, cfg, rules, self.mesh)
                if stats is not None:
                    router_stats = jax.tree_util.tree_map(lambda a, b: jnp.concatenate([a, b], axis=0), router_stats, stats)
            with tracing.scope("loss"):
                seq = batch["tokens"].shape[1]
                counters = {**_window_counters(cfg, seq), **_causal_counters(cfg, seq), **_unmasked_counters(cfg, seq),
                            **_rerun_counters(cfg), **_index_counters(cfg, seq)}
                loss = ce + cfg.mtp_loss_weight * beside["mtp_loss"] if cfg.mtp_depth else ce
                if INDEX_KL in beside:
                    loss = loss + beside[INDEX_KL]
                if router_stats is None:
                    return loss, {"ce_loss": ce, **beside, **counters} if beside else counters
                terms = router_losses(router_stats, cfg)
                loss = (loss + cfg.router_aux_loss_coef * terms["moe_lb_loss"]
                        + cfg.router_z_loss_coef * terms["moe_z_loss"])
                return loss, {"ce_loss": ce, **beside, **terms, **counters, **_load_of(router_stats)}

        self._loss = _loss

        def _train_step(state, batch):
            # On the host only (no op's name changes): what runs after `_loss` returns, the backward
            # pass's transposition and partial evaluation, is under a name in the trace's `scopes`.
            # a block-diffusion step's noise: one key a step, from the state's key and the step's count
            noise = (jax.random.fold_in(state["noise_key"], state["step"]),) if noise_state else ()
            with tracing.scope("autodiff", host_only=True):
                (loss, terms), grads = jax.value_and_grad(_loss, has_aux=True)(
                    state["params"], batch, *noise)
            share = terms.pop(CHOICE_SHARE, None)
            with tracing.scope("optimizer"):
                updates, opt_state = opt.update(grads, state["opt_state"], state["params"])
                params = optax.apply_updates(state["params"], updates)
                grad_norm = optax.global_norm(grads)
                if share is not None:
                    # the one update that is not the optimizer's: each expert layer's stored bias follows this step's
                    # load (the optimizer's result for it, a weight decay of a leaf without a gradient, is dropped)
                    params = biases_following_load(params, state["params"], share, cfg)
            metrics = {
                "loss": loss,
                "grad_norm": grad_norm,
                "step": state["step"] + 1,
                **terms,
            }
            return (
                {"params": params, "opt_state": opt_state, "step": state["step"] + 1, **{k: state[k] for k in noise_state}},
                metrics,
            )

        # State out_shardings pinned, not propagated: GSPMD was measured to
        # replicate adam moments when left to choose, silently forfeiting
        # ZeRO optimizer-state sharding after the first step.
        self._train_step = jax.jit(
            _train_step,
            out_shardings=(
                {
                    "params": self.param_shardings,
                    "opt_state": self.opt_shardings,
                    "step": self.repl,
                    **noise_state,
                },
                self.repl,
            ),
            donate_argnums=(0,),
        )

        def _forward(params, tokens):
            return forward(params, tokens, cfg, rules=rules, mesh=self.mesh)

        self._forward = jax.jit(_forward)

        def _forward_mtp(params, tokens, next_tokens):
            return mtp_forward(params, tokens, next_tokens, cfg, rules=rules, mesh=self.mesh)

        self._forward_mtp = jax.jit(_forward_mtp)

        def _forward_diffusion(params, noisy, clean):
            return diffusion_forward(params, noisy, clean, cfg, rules=rules, mesh=self.mesh)

        self._forward_diffusion = jax.jit(_forward_diffusion)

    # -- public API -------------------------------------------------------
    def init_state(self, seed: int = 0) -> Dict[str, Any]:
        # A lifecycle span too: once per run, and in every run's record.
        with tracing.annotate("init_state", lifecycle=True), self.mesh:
            return self._init(jax.random.PRNGKey(seed))

    def make_batch(self, batch) -> Dict[str, jax.Array]:
        """Shard a host batch (pytree of [B, S] numpy arrays, every process
        holding the same global batch) onto the mesh.  make_array_from_callback
        hands each device its shard, which also works when the mesh spans
        processes (multi-host SPMD)."""
        import numpy as np

        def put(x):
            x = np.asarray(x)
            return jax.make_array_from_callback(
                x.shape, self.batch_sharding, lambda idx: x[idx]
            )

        return jax.tree_util.tree_map(put, batch)

    def train_step(self, state, batch) -> Tuple[Dict, Dict]:
        # Always on: the step's period, entry to entry, for the run record's
        # rows of the steady step and its stalled steps (train/run_record.py);
        # no span unless tracing is on.
        clock = self._step_clock
        clock.enter()
        if batch["tokens"].shape != clock.batch_shape:  # the global batch: every process holds it whole
            clock.note_batch(batch["tokens"].shape)
        if not all(isinstance(x, jax.Array) for x in jax.tree_util.tree_leaves(batch)):
            t0 = time.perf_counter()
            with tracing.annotate("train_step/make_batch"):
                batch = self.make_batch(batch)
            clock.mark(MAKE_BATCH, t0)
        t0 = time.perf_counter()
        with tracing.annotate("train_step/dispatch"), self.mesh:
            state, metrics = self._train_step(state, batch)
        clock.mark(DISPATCH, t0)
        # counters of the run's record: kept as device scalars, fetched at a poll, under the index of
        # this call (the clock's count of periods closed: 0 at the first)
        counters = {k: metrics[k] for k in STEP_COUNTERS if k in metrics}
        if counters:
            note_step_counters(counters, step=clock.steps)
        return state, metrics

    def apply(self, params, tokens) -> jax.Array:
        with self.mesh:
            return self._forward(params, tokens)

    def apply_diffusion(self, params, noisy, clean) -> jax.Array:
        """A block-diffusion model's training forward: the logits [B, S, V] of
        the noisy rows of `[noisy ‖ clean]` (`transformer.diffusion_forward`;
        `apply` is its plain forward, one copy under the block-causal mask)."""
        if self.config.diffusion_block is None:
            raise ValueError("apply_diffusion needs a block-diffusion model (diffusion_block)")
        with self.mesh:
            return self._forward_diffusion(params, noisy, clean)

    def apply_mtp(self, params, tokens, next_tokens) -> jax.Array:
        """The multi-token-prediction module's logits [B, S, V] for the token
        after the next (`mtp_depth` 1), from `tokens` and the token after each."""
        if not self.config.mtp_depth:
            raise ValueError("apply_mtp needs a model with a multi-token-prediction module (mtp_depth=1)")
        with self.mesh:
            return self._forward_mtp(params, tokens, next_tokens)
