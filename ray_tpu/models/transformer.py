"""Flagship model: a pre-norm decoder-only transformer, TPU-first.

What one `TransformerConfig` expresses: a stack of pre-norm layers, each a
(MIXER, FFN) pair around a residual stream.  The mixer (`layer_types`) is
one of the kinds of `ray_tpu/models/mixers/`, one module each with its
mathematics: causal softmax attention, a Mamba-2 selective state-space
layer, Kimi Delta Attention, Gated DeltaNet (a delta rule with one decay a
head), latent attention (with or without a rotary part), and
the four of SambaY's decoder-hybrid-decoder (a Mamba-1 selective scan,
differential attention, a Gated Memory Unit that reads one scan's output,
differential cross-attention over one attention layer's keys and values).
The FFN (`ffn_types`) is a dense SwiGLU, a dropless top-k mixture of experts
(`n_experts`; softmax or sigmoid router, an optional shared expert of a width
of its own, all experts or one rank's share of them, each expert a SwiGLU or
the two-matrix `W_down relu(W_up u)^2`: models/moe.py), or ABSENT ("none": the
layer is its mixer alone, no `ln2`, no `mlp` leaves, no `layer/mlp` scope).
Since a pre-norm pair is two single blocks one after the other, a stack whose
blocks are a mixer OR an FFN alone (Nemotron-H's `MEMEM*E...`) is pairs too:
`M E` is (mamba, experts), `* E` (attention, experts), an `M` straight before
a `*` (mamba, none).  Attention's head size is `d_model // n_heads` unless the
configuration states one (`attn_head_dim`).  Norms are RMSNorm
or LayerNorm with bias (`norm_kind`).  What is static in a layer beside its
pair is the layer's own too (`layer_variant`): its attention's causal window
(`layer_windows`) and its rotary embedding (`layer_ropes`, each an
`ops.rotary.Rope`: a base, optionally YaRN's rescaled frequencies and factor
on cos and sin; a layer without one rotates by the model's `rope_theta`), so
a stack of three window-1024 layers to one full layer, the full ones with a
YaRN rope (Mellum 2), is ONE kind of layer in one parameter stack, run as two
compiled bodies a period.  Mistral, InternLM2, OLMoE, the Granite
4.0-H hybrids, Kimi Linear, Phi-4-mini-flash, Nemotron-3-Nano and Mellum 2
run through it at their published widths (benchmarks/configs/), and
GLM-4.7-Flash with them: latent attention with a rope on part of each head
and a low-rank q (`mla_rope`, `q_lora_rank`), and a multi-token-prediction
module behind the trunk (`mtp_depth`: `mtp_rows`, weighed into the loss by
models/lm.py); and Qwen3-Next: "gdn" layers 3:1 with softmax attention whose
heads rotate a part of themselves (`rotary_dim`) and whose output passes a
sigmoid gate (`attn_output_gate`), zero-centred norms (`norm_zero_centred`)
and a shared expert behind a scalar gate (`shared_expert_gate`); and SDAR, a
block-diffusion model over Qwen3-MoE's block (`diffusion_block`: attention is
causal BETWEEN blocks of that many tokens and two-sided inside one; `forward`
runs one copy of a sequence under that mask, `trunk(..., noisy=x_t)` and
`diffusion_forward` the training step's noisy copy beside the clean one, whose
objective is models/lm.py's); and dots3-note-prev: latent attention of TWO
geometries in one stack (mixers/dsa.py: "mla_sparse" layers attend the keys a
learned indexer selects and report its KL term, `index_*`; "mla_window" layers
read `window_latent` under the layer's window), rescaled latents, head-wise
gates, and a model TOLD which heads it holds (`head_share`, beside
`n_experts_held`); what such layers report leaves the stack through
`trunk_reports`; and ZAYA1-8B: "cca" layers (mixers/cca.py: attention in a
compressed latent mixed along the sequence), top-1 experts behind a router
that is a network whose state runs from layer to layer (`router_kind` "mlp":
models/moe.py; `trunk`'s scans carry the state beside the stream), and a
learned scale and bias on both sides of every join (`residual_scaling`).

The reference has no model code of its own (it trains user-supplied torch
models through wrappers — python/ray/train/torch/train_loop_utils.py:92-98);
a TPU framework needs first-party models whose sharding the Train layer can
drive.  Design:

- Pure-functional: params are a plain pytree; `forward` is a jit-able
  function.  No module framework in the hot path.
- A kind of mixer is declared ONCE, as a `mixers.Mixer` record beside its
  code: its leaves (shape, logical axes, initializer: `init_params`,
  `param_axes` and `num_params` all read that one mapping), its checks, its
  half of a layer, the residuals its backward wants kept, what it reads of
  and hands to other layers.  This file reads the records and names no kind.
- Every parameter leaf has a *logical axes* annotation (`param_axes`), mapped
  to mesh axes by ray_tpu.parallel.sharding rules — one model, every
  parallelism strategy (DP/FSDP/TP/SP via rules, not rewrites).
- Layers are stacked per (mixer, FFN) PAIR on a leading `layers` axis
  (`params["layers"]` the attention layers, `params["mamba_layers"]` the
  Mamba-2 ones, ...: `Mixer.stack`; a mixer that the model pairs with BOTH
  kinds of FFN has one stack for each, `kda_layers_dense` and
  `kda_layers_experts`: `TransformerConfig.stack_name`) and the stack runs as
  ONE `lax.scan` per maximal run of one pair (one compiled body per pair,
  O(1) compile time in depth), with optional `jax.checkpoint`
  rematerialization for HBM.  A homogeneous model is the one-run case.

The model, with x [B, S, d] and every norm with a learned scale (and, a
LayerNorm, a bias) and `norm_eps`:

- `h0 = embed[tokens] * embedding_multiplier`; the layers;
  `logits = (norm(h) @ head) / logits_scaling`, the head the embedding table
  transposed when tied.
- every layer: `h = h + residual_multiplier * mixer(norm_1(h))`, then (unless
  the layer has no FFN) `h = h + residual_multiplier * FFN(norm_2(h))`, the
  dense FFN `W_down(silu(W_gate u) * (W_up u))` without bias.  The multipliers are
  Granite's muP form, 1.0 each = absent.

What crosses layers (an s6 layer's scan output; a diff_attention layer's k
and v) is RETURNED by the layer that makes it, carried by `trunk` beside the
stream and given to the later runs that read it as an argument
(`Mixer.hands` / `Mixer.reads`).  What EVERY layer reads from the one before
and hands to the one after (an "mlp" router's state, the FFN half's) is part
of the scans' carry, `carried`, beside the stream: an input and an output of
each layer's checkpoint, its cotangent running back through the chain; `pp`
refuses it by name until it crosses stages.  `tp`, `pp` and the sequence-parallel ring
refuse the differential kinds by name (`pp` any `layer_types`); what a kind
cannot run under given rules on a given mesh (the ring a window, a layer's
own rope, a per-head QK-norm; `pp` those and any stack that is not
homogeneous) is refused when the three first meet (`check_placement`, which
`LMTrainContext` calls as it is built), not deep inside a trace.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from ray_tpu.models.mixers import MIXERS, Leaf, Mixer
from ray_tpu.models.mixers.mla import Latent
from ray_tpu.models.mixers.base import (
    fitting_axis, joined, norm_scale, normal, ones, out_scale, proj_scale, residual_scaling_leaves, stream_norm, zeros,
)
# the names lm.py and the tests hold these two by
from ray_tpu.models.mixers.base import constrainer as _constrainer, rms_norm  # noqa: F401
from ray_tpu.models.moe import init_moe_params, load_following_bias, moe_ffn, moe_param_axes, router_state
from ray_tpu.ops.attention import ATTN_LSE, ATTN_OUT
from ray_tpu.ops.rotary import Rope
from ray_tpu.parallel.sharding import Rules, pipeline_axes, with_logical_constraint
from ray_tpu.util import tracing


# The logical axes of the logits (and of their cotangent).
LOGITS_AXES = ("act_batch", "act_seq", "act_vocab")

# The kinds of FFN ("none": the layer has no FFN half); the kinds of mixer are
# `MIXERS`, the first of them what a layer is when `layer_types` does not say.
# `TransformerConfig.stacks` lists a model's stacks in this order: append.
FFN_KINDS = ("dense", "experts", "none")
_DEFAULT_MIXER = next(iter(MIXERS))


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    d_ff: int = 11008
    max_seq_len: int = 4096
    rope_theta: Optional[float] = 10000.0  # None = no rotary embedding ("nope")
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    tie_embeddings: bool = False
    remat: bool = True
    # Remat granularity: None = full per-layer recompute (min memory);
    # "attn" = save what attention's backward needs of its forward, the
    # output and the flash kernel's log-sum-exp (f32 [B, H, S]), so the
    # kernel's forward runs once per layer; "qkv_attn" = additionally save
    # post-rope q/k/v (skips qkv matmul + rope recompute).  More saved =
    # more HBM.  Every other kind of mixer names what its backward needs of
    # its projections (its `Mixer.saved`; a Mamba-2 layer's two are in
    # mixers/mamba2.py) and "qkv_attn" saves those too, so under it no
    # mixer's input projection is recomputed, whatever the layer's kind;
    # under "attn" and None a Mamba-2 layer keeps its input only.
    # Convolution, scan and gated norm run again under every policy:
    # the scan's backward needs the last two, and keeping the convolved x|B|C
    # cost more than its kernel's second run (PERF.md section 6, PR 36).
    # XLA's own rematerialization duplicates work when a step compiles over
    # libtpu's limit (none of the cells does since `head_cross_entropy`);
    # `_dense_ffn`'s tie keeps the dense FFN's matmuls out of it.
    remat_policy: Optional[str] = None
    attention_impl: Optional[str] = None  # None=auto, see ops.attention
    # Microbatches per pipeline-stage schedule when the rules shard the
    # layer stack over the `pipeline` axis (strategy="pp"/"pp_fsdp").
    # None derives min(4 * n_stages, local batch) — ~20% GPipe bubble
    # without slicing microbatches below MXU-efficient sizes.
    pp_microbatches: Optional[int] = None
    # Sparse experts (models/moe.py).  `n_experts` None = a dense SwiGLU of
    # width `d_ff`; set, the FFN of every layer (or of the layers `ffn_types`
    # says) is `n_experts` SwiGLU experts of width `moe_d_ff` each (None =
    # `d_ff`), `experts_per_token` of them per token by the top-k of the
    # router's scores, dropless.  `router_activation`: "softmax" over all
    # experts, or "sigmoid" per expert with a stored bias that takes part in
    # the choice alone.  `norm_topk_prob`: renormalise the chosen gate values
    # to sum to one; `routed_scaling_factor` then multiplies them.
    # `n_shared_experts`: one more SwiGLU, that many experts wide, which every
    # token goes through.  `n_experts_held`: the layer holds only the experts
    # `first_expert_held .. + n_experts_held` of the `n_experts` its router
    # scores, one rank's share of an expert-parallel deployment, and computes
    # their part of the result alone.  The two coefficients weigh the
    # load-balancing loss and the router z-loss in the training objective.
    n_experts: Optional[int] = None
    experts_per_token: int = 0
    norm_topk_prob: bool = False
    router_aux_loss_coef: float = 0.0
    router_z_loss_coef: float = 0.0
    moe_d_ff: Optional[int] = None
    router_activation: str = "softmax"
    routed_scaling_factor: float = 1.0
    n_shared_experts: int = 0
    # `expert_kind`: "swiglu", `W_down(silu(W_gate u) * W_up u)`, or "relu2",
    # `W_down relu(W_up u)^2` (two matrices, no gate; Nemotron-H), of routed
    # and shared experts alike.  `shared_expert_d_ff`: the shared expert's
    # width where the model states it (None = `n_shared_experts * moe_d_ff`).
    # `routed_branch_init`: the K experts a token chooses join the stream as
    # ONE residual branch, so each routed expert's `w_down` starts at the
    # depth-scaled `out_scale / sqrt(K)` (False: each expert at `out_scale`,
    # as OLMoE's and Kimi Linear's seeds have it).  `router_share_init`: a
    # model that holds a share starts every share's block of the router
    # (`n_experts_held` columns) from ONE draw, so each token's K choices
    # start `K * n_experts_held / n_experts` on every share, this one too,
    # whatever the seed; the blocks then train apart (False: `n_experts`
    # independent columns, whose winners a seed draws among the shares).
    n_experts_held: Optional[int] = None
    first_expert_held: int = 0
    expert_kind: str = "swiglu"
    shared_expert_d_ff: Optional[int] = None
    routed_branch_init: bool = False
    router_share_init: bool = False
    # What ZAYA1 adds (arXiv:2511.17127).  `router_kind`: "linear", the logits one map of the token, or "mlp", a network
    # over a `router_hidden`-wide state that runs from layer to layer (models/moe.py), with a stored `router_bias`.
    # `residual_scaling`: every sub-block joins the stream as `(a_res * x + b_res) + (a_out * y + b_out)`, four learned
    # [d_model] vectors a sub-block (`res1` the mixer's, `res2` the FFN's; the identity at the seed), in every layer, all
    # of a kind that joins through them (`Mixer.scales_residual`).  `cca_taps`: the taps of a "cca" layer's two causal
    # convolutions over its q|k latent (mixers/cca.py), read only when some layer is "cca".  `router_bias_update_rate`:
    # after every training step each expert layer's stored `router_bias` moves by this much toward the experts under
    # the mean load and away from those over it, outside the gradient and the optimizer (`biases_following_load`;
    # 0: the bias stays what it is, and nothing of it is traced).
    router_kind: str = "linear"
    router_hidden: int = 0
    residual_scaling: bool = False
    cca_taps: Tuple[int, int] = (2, 2)
    router_bias_update_rate: float = 0.0
    # RMSNorm with a learned scale on q and k, before RoPE.  True: over the
    # whole projected q and k (OLMoE, OLMo 2); "per_head": over each head of
    # them, one scale of `head_dim` for q's heads and one for k's (the Qwen3
    # family).
    qk_norm: Union[bool, str] = False
    # What of "attention" layers the Qwen3-Next family adds.  `rotary_dim`:
    # the model's rope (`rope_theta`) rotates the first `rotary_dim` dims of
    # each head and passes the rest (None = the whole head).
    # `attn_output_gate`: `wq` is twice as wide, each head's columns its q and
    # its gate, and the core's output is multiplied by `sigmoid(gate)` before
    # `wo`.  `norm_zero_centred`: every RMSNorm of the stream (`ln1`, `ln2`,
    # `final_norm`) and of q and k (`qk_norm`) scales by `1 + w` with w stored
    # and started at 0, so weight decay pulls the scale to 1 and not to 0; the
    # norms INSIDE a mixer (a latent's, a delta rule's gated one) stay plain.
    # `shared_expert_gate`: the shared expert's output is multiplied by
    # `sigmoid(u w_sg)`, one scalar a token (`w_sg` [d, 1]).
    rotary_dim: Optional[int] = None
    attn_output_gate: bool = False
    norm_zero_centred: bool = False
    shared_expert_gate: bool = False
    # The mixer of each layer, one of `mixers.MIXERS`, one entry per layer;
    # None = attention everywhere.  The Mamba-2 sizes are read only when some
    # layer is "mamba": heads x head size = the mixer's inner width, the
    # state size N per head, the width of the causal depthwise convolution,
    # the groups of B and C (heads `g * heads / groups ..` read group g).
    layer_types: Optional[Tuple[str, ...]] = None
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_groups: int = 1
    # The FFN of each layer, "dense", "experts" or "none" (the layer is its
    # mixer alone), one entry per layer; None = experts everywhere when
    # `n_experts` is set, dense everywhere otherwise.
    ffn_types: Optional[Tuple[str, ...]] = None
    # The head size of "attention" layers where it is not d_model / n_heads
    # (Nemotron-H: 32 heads of 128 on a 2688-wide stream).
    attn_head_dim: Optional[int] = None
    # Kimi Delta Attention, read only when some layer is "kda": heads, the
    # one head size of q, k and v (also the width of the two low-rank gates),
    # the width of the three causal depthwise convolutions.
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    # Gated DeltaNet, read only when some layer is "gdn": the heads of q and k
    # and their size, the heads of v (a multiple: value head j reads key head
    # j // (value heads / key heads)) and their size, the width of the causal
    # depthwise convolutions.
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_key_dim: int = 0
    gdn_value_dim: int = 0
    gdn_conv: int = 4
    # Latent attention, read only when some layer is "mla" (`n_heads` heads):
    # the latent's width, the two parts of a q/k head, the size of a v head.
    # `q_lora_rank`: q is low-rank too, `RMSNorm(u W_qa) W_qb` (None = one
    # projection).  `mla_rope`: the rotary embedding of the `qk_rope_head_dim`
    # parts of q and of the one shared k_pe (None = nothing is rotated, Kimi
    # Linear's NoPE); `rope_theta` and `layer_ropes` are not read by "mla".
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    q_lora_rank: Optional[int] = None
    mla_rope: Optional[Rope] = None
    # Latent attention's two further kinds (mixers/dsa.py), both with rescaled latents, the layer's own rope
    # (`layer_ropes`) and a head-wise output gate.  "mla_sparse" reads the fields above and attends each query's
    # `index_topk` causal keys of largest indexer score (`index_heads` heads of `index_head_dim`, trained by a KL term
    # of the objective, models/lm.py); "mla_window" reads a second geometry, `window_latent` (its own heads, ranks and
    # head sizes), under the layer's window (`layer_windows`).  `head_share` = (index, of): the model holds that share
    # of each such kind's heads, one rank's share of a tensor-parallel attention, on one device.
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    window_latent: Optional[Latent] = None
    head_share: Optional[Tuple[int, int]] = None
    # Published multipliers (Granite's muP form), 1.0 each = absent: on the
    # embeddings, on each block's output before it joins the residual
    # stream, and a divisor of the logits.  `attention_scale` multiplies
    # q k^T before the softmax; None = head_dim ** -0.5.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    attention_scale: Optional[float] = None
    # "rms": RMSNorm with a learned scale; "layer": LayerNorm (mean and
    # variance) with a learned scale and bias (`ln1_b`, `ln2_b`,
    # `final_norm_b` beside the scales).  `attn_bias`: biases on the
    # projections of the differential kinds (`b_qkv` / `b_q`, `b_o`); the
    # other kinds of attention have none and refuse it.
    norm_kind: str = "rms"
    attn_bias: bool = False
    # Mamba-1 (S6), read only when some layer is "s6" or "gmu": the mixer's
    # inner width, the state size N per channel, the width of the causal
    # depthwise convolution, the rank of dt's projection (0 = ceil(d / 16)).
    # `s6_memory_layer`: the index IN THIS STACK of the s6 layer whose scan
    # output every later gmu layer reads.
    s6_inner: int = 0
    s6_state: int = 16
    s6_conv: int = 4
    s6_dt_rank: int = 0
    s6_memory_layer: Optional[int] = None
    # Differential attention ("diff_attention", "diff_cross"; `n_heads` /
    # `n_kv_heads` heads of `head_dim`, both even).  `kv_source_layer`: the
    # index in this stack of the diff_attention layer whose k and v every
    # later diff_cross layer reads.  `layer_windows`: per layer the causal
    # window of its attention (None = full causal), read by "attention" and
    # "diff_attention" layers; None = no layer has one.  `layer_ids`: the
    # PUBLISHED index of each layer (None = its index here), which
    # differential attention's lambda_init is a function of.  `layer_ropes`:
    # per layer its own rotary embedding (`ops.rotary.Rope`; None = the
    # model's `rope_theta`), read by the kinds that rotate (`Mixer.rotates`:
    # "attention"); None = no layer has one.
    kv_source_layer: Optional[int] = None
    layer_windows: Optional[Tuple[Optional[int], ...]] = None
    layer_ids: Optional[Tuple[int, ...]] = None
    layer_ropes: Optional[Tuple[Optional[Rope], ...]] = None
    # Multi-token prediction (DeepSeek-V3, arXiv:2412.19437 section 2.2) in the
    # training objective: `mtp_depth` modules behind the trunk (0 = none, 1 the
    # most), each ONE more layer of the stack's last pair with weights of its
    # own (`params["mtp"]`, `mtp_rows`) that predicts the token after the next
    # through the model's own embedding and head; `mtp_loss_weight` weighs its
    # cross entropy in the loss (models/lm.py).
    mtp_depth: int = 0
    mtp_loss_weight: float = 0.0
    # Block diffusion (BD3-LMs, arXiv:2503.09573; SDAR, arXiv:2510.06303): the
    # sequence is cut into blocks of `diffusion_block` tokens, generated left
    # to right, the tokens inside a block by masked diffusion (None = a
    # next-token model).  Every layer is "attention" under the block-diffusion
    # mask (ops/attention.py `BlockDiffusion`) and the objective is the
    # block-diffusion NELBO of models/lm.py: `diffusion_mask_id` is laid over
    # the masked tokens (None = the table's last row) and a block's share of
    # them t is drawn from [`diffusion_eps`, 1]; the draw's key is the train
    # state's (`lm.LMTrainContext.init_state`: one seed governs a run).
    diffusion_block: Optional[int] = None
    diffusion_mask_id: Optional[int] = None
    diffusion_eps: float = 1e-3

    def __post_init__(self):
        if self.layer_types is not None:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
            unknown = set(self.layer_types) - set(MIXERS)
            if unknown or len(self.layer_types) != self.n_layers:
                raise ValueError(
                    f"layer_types needs n_layers={self.n_layers} entries out of {list(MIXERS)}, "
                    f"got {len(self.layer_types)} with {sorted(unknown)} unknown"
                )
            for mixer in MIXERS.values():
                if mixer.name in self.layer_types:
                    mixer.validate(self)
            self._check_crossings()
        for name in ("layer_windows", "layer_ids", "layer_ropes"):
            if getattr(self, name) is not None:
                object.__setattr__(self, name, tuple(getattr(self, name)))
                if len(getattr(self, name)) != self.n_layers:
                    raise ValueError(f"{name} needs n_layers={self.n_layers} entries")
        for (kind, _), rope in zip(self.layer_pairs(), self.layer_ropes or ()):
            if rope is not None and not (isinstance(rope, Rope) and MIXERS[kind].rotates):
                raise ValueError(f"layer_ropes holds {rope!r} at a {kind} layer: an ops.rotary.Rope, at a layer "
                                 f"of a kind that rotates ({[m.name for m in MIXERS.values() if m.rotates]}), or None")
        if self.mtp_depth not in (0, 1) or self.mtp_loss_weight < 0:
            raise ValueError(f"mtp_depth is 0 (no multi-token prediction) or 1 (one module: the token after the next) with "
                             f"mtp_loss_weight >= 0, got mtp_depth={self.mtp_depth}, mtp_loss_weight={self.mtp_loss_weight}")
        if self.mtp_depth:
            if self.logits_scaling != 1.0:
                raise ValueError("mtp_depth takes the rows that enter the head as the trunk's output: logits_scaling must be 1.0")
            last = MIXERS[self.layer_pairs()[-1][0]]
            if last.reads or last.source is not None or last.reports:
                raise ValueError(f"mtp_depth: the module's block is one more {last.name} layer, a kind that crosses layers "
                                 f"({last.source or last.reads or last.reports}); it runs behind the trunk and can neither read, "
                                 f"hand on nor report")
        if self.diffusion_block is not None:
            others = sorted(set(self.layer_types or ()) - {_DEFAULT_MIXER})
            has = [name for name, there in (
                (f"layer_types {others}", bool(others)),
                ("layer_windows", self.layer_windows is not None and any(w is not None for w in self.layer_windows)),
                ("mtp_depth", bool(self.mtp_depth)),
            ) if there]
            if has or self.diffusion_block < 1 or not 0.0 < self.diffusion_eps <= 1.0:
                raise ValueError(
                    f"diffusion_block={self.diffusion_block} (>= 1; diffusion_eps={self.diffusion_eps} in (0, 1]) puts the "
                    f"block-diffusion mask in the causal one's place in every layer, all of them '{_DEFAULT_MIXER}': it takes no "
                    + ", no ".join(has or ["other kind of layer"]))
        if self.head_share is not None:
            object.__setattr__(self, "head_share", tuple(self.head_share))
            others = sorted({kind for kind, _ in self.layer_pairs() if not MIXERS[kind].holds_heads})
            if others or len(self.head_share) != 2:
                raise ValueError(f"head_share={self.head_share} is (index, of), the share of its heads a model holds whose "
                                 f"every layer is of a kind that reads it ({[m.name for m in MIXERS.values() if m.holds_heads]}); "
                                 f"this one has {others}")
        if self.qk_norm not in (False, True, "per_head"):
            raise ValueError(f"qk_norm is False, True (over the whole projection) or 'per_head', got {self.qk_norm!r}")
        if self.norm_kind not in ("rms", "layer"):
            raise ValueError(f"unknown norm_kind {self.norm_kind!r}; expected 'rms' or 'layer'")
        if self.norm_zero_centred and (self.norm_kind != "rms" or self.mtp_depth):
            raise ValueError("norm_zero_centred is the RMSNorms' of the stream and of q and k: not beside norm_kind "
                             "'layer', nor beside mtp_depth (the module's own norms are plain)")
        if self.shared_expert_gate and not self.shared_expert_width:
            raise ValueError("shared_expert_gate needs a shared expert (n_shared_experts)")
        if self.ffn_types is not None:
            object.__setattr__(self, "ffn_types", tuple(self.ffn_types))
            unknown = set(self.ffn_types) - set(FFN_KINDS)
            if unknown or len(self.ffn_types) != self.n_layers:
                raise ValueError(
                    f"ffn_types needs n_layers={self.n_layers} entries out of {FFN_KINDS}, "
                    f"got {len(self.ffn_types)} with {sorted(unknown)} unknown"
                )
            if "experts" in self.ffn_types and self.n_experts is None:
                raise ValueError("an experts layer needs n_experts")
        if self.n_experts is not None and not 0 < self.experts_per_token <= self.n_experts:
            raise ValueError(
                f"n_experts={self.n_experts} needs 0 < experts_per_token <= n_experts, "
                f"got {self.experts_per_token}"
            )
        if self.router_activation not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router_activation {self.router_activation!r}")
        object.__setattr__(self, "cca_taps", tuple(self.cca_taps))
        if self.router_kind not in ("linear", "mlp") or (self.router_kind == "mlp" and (
                self.n_experts is None or self.router_hidden < 1 or self.router_share_init or self.mtp_depth)):
            raise ValueError(f"router_kind is 'linear' or 'mlp', got {self.router_kind!r}; 'mlp' needs n_experts and "
                             f"router_hidden >= 1 (got {self.router_hidden}) and takes no router_share_init (its columns are a "
                             "network's) and no mtp_depth (the module's block lies behind the chain its router state runs along)")
        if self.router_bias_update_rate and (self.router_bias_update_rate < 0 or self.mtp_depth or self.n_experts is None or not (
                self.router_activation == "sigmoid" or self.router_kind == "mlp")):
            raise ValueError(f"router_bias_update_rate {self.router_bias_update_rate} moves a STORED router_bias (router_activation "
                             "'sigmoid' or router_kind 'mlp') of the stack's expert layers by a rate >= 0; mtp_depth's block is not one")
        if self.residual_scaling:
            others = sorted({kind for kind, _ in self.layer_pairs() if not MIXERS[kind].scales_residual})
            if others or self.mtp_depth:
                raise ValueError(f"residual_scaling is every layer's: each of a kind that joins the stream through it "
                                 f"({[m.name for m in MIXERS.values() if m.scales_residual]}), and no mtp_depth; this model has "
                                 f"{others or 'mtp_depth'}")
        if self.expert_kind not in ("swiglu", "relu2"):
            raise ValueError(f"unknown expert_kind {self.expert_kind!r}; expected 'swiglu' or 'relu2'")
        if self.n_experts_held is not None and not (
            self.n_experts is not None and self.n_experts_held > 0 and self.first_expert_held >= 0
            and self.first_expert_held + self.n_experts_held <= self.n_experts
        ):
            raise ValueError(
                f"n_experts_held={self.n_experts_held} from {self.first_expert_held} "
                f"is no share of n_experts={self.n_experts}"
            )
        if self.router_share_init:
            held = self.n_experts_held
            if held is None or self.n_experts % held or self.first_expert_held % held or (
                    self.experts_per_token * held) % self.n_experts:
                raise ValueError(
                    f"router_share_init needs n_experts={self.n_experts} in whole shares of n_experts_held={held}, "
                    f"this share at a multiple of it (first_expert_held={self.first_expert_held}), and "
                    f"experts_per_token={self.experts_per_token} a multiple of the number of shares"
                )

    def _check_crossings(self):
        """What crosses layers has ONE maker, a layer of the kind that hands
        it on, before every layer that reads it."""
        kinds = self.layer_types
        makers = {name: m for m in MIXERS.values() for name in m.hands}
        for maker in MIXERS.values():
            at = None if maker.source is None else getattr(self, maker.source)
            if at is not None and not (0 <= at < self.n_layers and kinds[at] == maker.name):
                raise ValueError(f"{maker.source}={at} is no {maker.name} layer of this stack")
        for reader in (m for m in MIXERS.values() if m.name in kinds):
            for maker in (makers[name] for name in reader.reads):
                at = getattr(self, maker.source)
                if at is None or kinds.index(reader.name) < at:
                    raise ValueError(f"a {reader.name} layer needs {maker.source}, a {maker.name} layer before it")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads if self.attn_head_dim is None else self.attn_head_dim

    @property
    def carries_router_state(self) -> bool:
        """Whether the expert layers hand a router state on, each to the next (`trunk`'s `carried`)."""
        return self.n_experts is not None and self.router_kind == "mlp"

    @property
    def mask_id(self) -> int:
        """The id a block-diffusion step lays over its masked tokens."""
        return self.vocab_size - 1 if self.diffusion_mask_id is None else self.diffusion_mask_id

    def layer_variant(self, i: int) -> Tuple[Optional[int], Optional[Rope], bool]:
        """What of layer i is STATIC beside its pair, so that a run of one
        compiled body cannot span a change of it: (its attention's window,
        its own rotary embedding, whether it hands values on to later layers)."""
        window = None if self.layer_windows is None else self.layer_windows[i]
        rope = None if self.layer_ropes is None else self.layer_ropes[i]
        source = MIXERS[_DEFAULT_MIXER if self.layer_types is None else self.layer_types[i]].source
        return window, rope, source is not None and getattr(self, source) == i

    def lambda_inits(self) -> Tuple[float, ...]:
        """Differential attention's `lambda_init` of each layer, from its published index."""
        ids = self.layer_ids or tuple(range(self.n_layers))
        return tuple(0.8 - 0.6 * math.exp(-0.3 * l) for l in ids)

    @property
    def expert_width(self) -> int:
        """ONE expert's width."""
        return self.d_ff if self.moe_d_ff is None else self.moe_d_ff

    @property
    def shared_expert_width(self) -> int:
        """The shared expert's width (0: the model has none)."""
        if self.shared_expert_d_ff is not None:
            return self.shared_expert_d_ff if self.n_shared_experts else 0
        return self.n_shared_experts * self.expert_width

    def layer_pairs(self) -> Tuple[Tuple[str, str], ...]:
        """(mixer, FFN) of each layer."""
        mixers = self.layer_types or (_DEFAULT_MIXER,) * self.n_layers
        ffns = self.ffn_types or ("dense" if self.n_experts is None else "experts",) * self.n_layers
        return tuple(zip(mixers, ffns))

    def stack_name(self, mixer: str, ffn: str) -> str:
        """The subtree of the parameters that stacks the layers of one pair:
        the mixer's own (`Mixer.stack`) when the model pairs that mixer with
        one kind of FFN, `<the mixer's>_<ffn>` when with more."""
        both = len({f for m, f in self.layer_pairs() if m == mixer}) > 1
        return f"{MIXERS[mixer].stack}_{ffn}" if both else MIXERS[mixer].stack

    def stacks(self) -> Dict[str, Tuple[str, str, int]]:
        """stack name -> (mixer, FFN, layers in it), every pair the model has,
        in the order of `MIXERS` x `FFN_KINDS`."""
        pairs = self.layer_pairs()
        return {
            self.stack_name(m, f): (m, f, pairs.count((m, f)))
            for m in MIXERS for f in FFN_KINDS if (m, f) in pairs
        }

    def layer_runs(self) -> Tuple[Tuple[str, str, int, int], ...]:
        """The stack as maximal runs of one pair (and one `layer_variant`):
        (mixer, FFN, first, count), where `first` counts layers of that pair,
        i.e. indexes the pair's own parameter stack.  A homogeneous model is
        one run."""
        runs, seen, last = [], {}, None
        for i, pair in enumerate(self.layer_pairs()):
            key = (pair, self.layer_variant(i))
            if key == last:
                runs[-1][3] += 1
            else:
                runs.append([*pair, seen.get(pair, 0), 1])
            seen[pair] = seen.get(pair, 0) + 1
            last = key
        return tuple(tuple(r) for r in runs)

    def run_starts(self) -> Tuple[int, ...]:
        """The index in the stack of each run's first layer."""
        counts = [count for _, _, _, count in self.layer_runs()]
        return tuple(sum(counts[:i]) for i in range(len(counts)))

    def n_layers_of(self, mixer: Optional[str] = None, ffn: Optional[str] = None) -> int:
        return sum(1 for m, f in self.layer_pairs() if mixer in (None, m) and ffn in (None, f))

    # -- presets ---------------------------------------------------------
    @staticmethod
    def tiny(**kw) -> "TransformerConfig":
        """Test-scale model for CPU-mesh tests."""
        base = dict(
            vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=128, max_seq_len=128, dtype=jnp.float32, remat=False,
        )
        base.update(kw)
        return TransformerConfig(**base)

    def num_params(self) -> int:
        """Every stored parameter, whatever pairs of mixer and FFN the layers are."""
        def size(tree):  # of `Leaf`s or of shapes
            return sum(math.prod(leaf.shape) for leaf in jax.tree_util.tree_leaves(tree))

        experts = 0
        if self.n_experts is not None:
            experts = size(jax.eval_shape(lambda key: init_moe_params(self, key), jax.ShapeDtypeStruct((2,), jnp.uint32)))
        def layers(m, f, n):
            return n * (size(_layer_leaves(self, m, f)) + (experts if f == "experts" else 0))

        mtp = size(_mtp_leaves(self)) + layers(*self.layer_pairs()[-1], 1) if self.mtp_depth else 0
        return size(_model_leaves(self)) + sum(layers(m, f, n) for m, f, n in self.stacks().values()) + mtp


def _model_leaves(config: TransformerConfig) -> Dict:
    """What the model holds beside its layers, as a tree of `Leaf`s (their
    shapes and axes as they are: nothing is stacked)."""
    c = config
    top = {"embed": {"tokens": Leaf((c.vocab_size, c.d_model), ("vocab", "embed"), normal(proj_scale(c)))},
           "final_norm": norm_scale(c, (c.d_model,))}
    if c.norm_kind == "layer":
        top["final_norm_b"] = zeros((c.d_model,))
    if not c.tie_embeddings:
        top["lm_head"] = Leaf((c.d_model, c.vocab_size), ("embed", "vocab"), normal(proj_scale(c)))
    return top


def _layer_leaves(config: TransformerConfig, mixer: str, ffn: str) -> Dict:
    """ONE layer of the (mixer, FFN) pair as a tree of `Leaf`s, in the order
    `init_params` draws their keys: the mixer's subtree, the FFN's (None for
    an expert FFN, which models/moe.py declares), the two norms; a layer
    without an FFN has neither `mlp` nor `ln2`."""
    c, d, m = config, config.d_model, MIXERS[mixer]
    dense = {
        "w_gate": Leaf((d, c.d_ff), ("embed", "mlp"), normal(proj_scale(c))),
        "w_up": Leaf((d, c.d_ff), ("embed", "mlp"), normal(proj_scale(c))),
        "w_down": Leaf((c.d_ff, d), ("mlp", "embed"), normal(out_scale(c))),
    }
    norms = {"ln1": norm_scale(c, (d,)), "ln2": norm_scale(c, (d,))}
    if c.norm_kind == "layer":
        norms.update(ln1_b=zeros((d,)), ln2_b=zeros((d,)))
    if c.residual_scaling:  # behind the norms: constants, which draw no key
        norms.update(res1=residual_scaling_leaves(c), res2=residual_scaling_leaves(c))
    if ffn == "none":
        return {m.subtree: m.leaves(c), **{name: norms[name] for name in ("ln1", "ln1_b", "res1") if name in norms}}  # the mixer half's
    return {m.subtree: m.leaves(c), "mlp": dense if ffn == "dense" else None, **norms}


def _mtp_leaves(config: TransformerConfig) -> Dict:
    """What the multi-token-prediction module holds beside its block
    (`params["mtp"]["block"]`, one layer of the stack's last pair): the norms
    of the next token's embedding and of the trunk's output, the projection
    of the two side by side back to the stream, the norm before the head."""
    d = config.d_model
    return {"enorm": ones((d,)), "hnorm": ones((d,)),
            "eh_proj": Leaf((2 * d, d), (None, "embed"), normal((2 * d) ** -0.5)), "norm": ones((d,))}


def param_axes(config: TransformerConfig) -> Dict:
    """Pytree of logical-axes tuples, congruent with init_params output."""
    def layer_axes(mixer, ffn, leading):
        axes = jax.tree_util.tree_map(lambda leaf: leading + leaf.axes, _layer_leaves(config, mixer, ffn))
        if ffn == "experts":
            axes["mlp"] = jax.tree_util.tree_map(
                lambda t: leading + t, moe_param_axes(config), is_leaf=lambda t: isinstance(t, tuple))
        return axes

    axes = jax.tree_util.tree_map(lambda leaf: leaf.axes, _model_leaves(config))
    for name, (mixer, ffn, _) in config.stacks().items():
        axes[name] = layer_axes(mixer, ffn, ("layers",))
    if config.mtp_depth:
        axes["mtp"] = {**jax.tree_util.tree_map(lambda leaf: leaf.axes, _mtp_leaves(config)),
                       "block": layer_axes(*config.layer_pairs()[-1], ())}
    return axes


def init_params(config: TransformerConfig, key: jax.Array) -> Dict:
    """Initialize the parameter pytree (truncated-normal / scaled init)."""
    c = config

    def draw(tree, k, stacked=()):
        """The arrays of a tree of `Leaf`s, `stacked` layers of each: a key
        of `k` for each leaf that draws, in the tree's own order."""
        if isinstance(tree, Leaf):
            return tree.init(next(k) if tree.draws else None, stacked + tree.shape).astype(c.param_dtype)
        if tree is None:  # an expert FFN
            return init_moe_params(c, next(k), leading=stacked, out_scale=out_scale(c))
        return {name: draw(sub, k, stacked) for name, sub in tree.items()}

    # The embedding, the first kind's stack (with its one kind of FFN: drawn
    # at length 0 where the model has none), the head and the second kind's
    # stack draw their keys from ONE sequence in this order, whatever the
    # model has, and every other stack from a sequence of its own: a model's
    # weights for a seed do not move when a kind of layer is added.
    stacks, top = c.stacks(), _model_leaves(c)
    first, second = (m.stack for m in list(MIXERS.values())[:2])
    k = iter(jax.random.split(key, 16))
    params = {"embed": draw(top.pop("embed"), k)}
    mixer, ffn, n = stacks.get(first, (_DEFAULT_MIXER, "dense" if c.n_experts is None else "experts", 0))
    layers = draw(_layer_leaves(c, mixer, ffn), k, (n,))
    if n:
        params[first] = layers
    params.update(draw(top, k))
    for i, (name, (mixer, ffn, n)) in enumerate(stacks.items()):
        if name != first:
            own = k if name == second else iter(jax.random.split(jax.random.fold_in(key, i + 1), 16))
            params[name] = draw(_layer_leaves(c, mixer, ffn), own, (n,))
    if c.mtp_depth:  # a sequence of its own, behind every stack's: `fold_in(key, i + 1)` ends at the stacks' count
        own = iter(jax.random.split(jax.random.fold_in(key, len(MIXERS) * len(FFN_KINDS) + 1), 16))
        params["mtp"] = {**draw(_mtp_leaves(c), own), "block": draw(_layer_leaves(c, *c.layer_pairs()[-1]), own)}
    return params


def _swiglu(constrain, h, w_gate, w_up, w_down):
    gate = jnp.einsum("bse,ef->bsf", h, w_gate)
    up = jnp.einsum("bse,ef->bsf", h, w_up)
    ff = constrain(jax.nn.silu(gate) * up, ("act_batch", "act_seq", "act_mlp"))
    return jnp.einsum("bsf,fe->bse", ff, w_down)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dense_ffn(constrain, h, w_gate, w_up, w_down):
    """The dense SwiGLU FFN, whose backward finishes as one unit.

    The forward is `_swiglu` as written and the backward its ordinary
    `jax.vjp` (same matmuls, dtypes and residuals under every remat policy:
    the residuals carry no checkpoint name), except that the four cotangents
    leave through ONE `optimization_barrier`.  Without it XLA's scheduler
    lets the weight-gradient matmuls drift past the start of attention's
    backward, so the [tokens, d_ff] buffers (`gate`, `up`, `silu*up` and
    their cotangents) stay alive beside attention's.  In a step that
    compiles over libtpu's rematerialization limit that excess is what
    `HloRematerialization` buys back by computing `gate` and the `d_ff`
    cotangent a second time per layer (PERF.md section 6, PR 25 and PR 27).
    `constrain` places `silu*up` as the layer's sharding rules say."""
    return _swiglu(constrain, h, w_gate, w_up, w_down)


def _dense_ffn_fwd(constrain, h, w_gate, w_up, w_down):
    return jax.vjp(functools.partial(_swiglu, constrain), h, w_gate, w_up, w_down)


def _dense_ffn_bwd(constrain, swiglu_vjp, d_out):
    return jax.lax.optimization_barrier(swiglu_vjp(d_out))


_dense_ffn.defvjp(_dense_ffn_fwd, _dense_ffn_bwd)


def _ffn_half(x, layer_params, config, constrain, rules, mesh, ffn=None, carried=None):
    """The second half of every layer, whatever its mixer: (x + FFN(ln2(x)),
    router statistics or None, `carried` as the next layer takes it).  `ffn`:
    "dense", "experts" (None: experts where the configuration has any) or
    "none": the mixer's result goes on as it is, and nothing is traced under
    `layer/mlp`.  `carried`: what every layer reads from the one before and
    hands to the one after, by name ({} in a model with nothing of the sort):
    `router_state`, which an expert layer behind an "mlp" router reads,
    replaces with its own and routes by."""
    c, dt = config, config.dtype
    router_stats, carried = None, carried or {}
    if ffn is None:
        ffn = "dense" if c.n_experts is None else "experts"
    if ffn == "none":
        return x, None, carried
    with tracing.scope("layer/mlp"):
        h = stream_norm(c, x, layer_params, "ln2")
        if ffn == "experts":
            state = None
            if c.carries_router_state:
                state = router_state(layer_params["mlp"], h, c, carried["router_state"])
                carried = {"router_state": state}
            down, router_stats = moe_ffn(layer_params["mlp"], h, c, rules=rules, mesh=mesh, router_state=state)
        else:
            mlp = layer_params["mlp"]
            down = _dense_ffn(
                constrain, h, mlp["w_gate"].astype(dt), mlp["w_up"].astype(dt), mlp["w_down"].astype(dt)
            )
        return joined(c, x, down, constrain, layer_params.get("res2")), router_stats, carried


def layer(
    mixer: Mixer,
    x: jax.Array,
    layer_params: Dict,
    positions: jax.Array,
    config: TransformerConfig,
    rules: Optional[Rules],
    mesh=None,
    ffn: Optional[str] = None,
    *,
    window: Optional[int] = None,
    data: Optional[Dict] = None,
    shared: Optional[Dict] = None,
    emit: bool = False,
    rope: Optional[Rope] = None,
    noisy_rows: Optional[int] = None,
    carried: Optional[Dict] = None,
):
    """One layer of any kind, the kind's `mix` and then the FFN half: (x,
    this layer's router statistics, None when the FFN is dense; what it hands
    on to later layers, by name; `carried` as the NEXT layer takes it).  `ffn`
    is the layer's kind of FFN (None: what the configuration's every layer
    has); `carried` what `_ffn_half` reads of the layer before (None: nothing);
    the rest is `Mixer.mix`'s, `rope` given only where the layer has one of
    its own, `noisy_rows` only in a block-diffusion model (how many of x's
    first rows are the noisy copy)."""
    x, handed = mixer.mix(x, layer_params, positions, config, rules, mesh,
                          window=window, data=data, shared=shared, emit=emit,
                          **({} if rope is None else {"rope": rope}),
                          **({} if noisy_rows is None else {"noisy_rows": noisy_rows}))
    x, stats, carried = _ffn_half(x, layer_params, config, _constrainer(rules, mesh), rules, mesh, ffn, carried)
    return x, stats, handed, carried


def _layer(x, layer_params, positions, config, rules, mesh=None, ffn=None, window=None):
    """One layer of the default kind, in the form the callers of a single
    stack hold it (a pipeline stage; benchmarks/tools/routing_flips.py):
    (x, router statistics or None)."""
    return layer(MIXERS[_DEFAULT_MIXER], x, layer_params, positions, config, rules, mesh, ffn, window=window)[:2]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _split_runs(stack: Dict, bounds: Tuple[Tuple[int, int], ...]):
    """One kind's parameter stack cut into its runs, (first, count) each: a
    tuple of trees of [count, ...] leaves.  The backward CONCATENATES the
    runs' gradients: the transpose of a slice is a pad to the stack's length,
    and a sum of pads would hold the whole stack's gradient once per run."""
    return tuple(
        jax.tree_util.tree_map(lambda a: jax.lax.slice_in_dim(a, first, first + count), stack)
        for first, count in bounds
    )


def _split_runs_fwd(stack, bounds):
    return _split_runs(stack, bounds), None


def _split_runs_bwd(bounds, _, d_runs):
    return (jax.tree_util.tree_map(lambda *parts: jnp.concatenate(parts, axis=0), *d_runs),)


_split_runs.defvjp(_split_runs_fwd, _split_runs_bwd)


def saved_names(config: TransformerConfig) -> Tuple[str, ...]:
    """The `checkpoint_name`s a layer's `jax.checkpoint` keeps under the
    configured remat granularity, validated (shared by every kind of layer: a
    name that a layer's kind does not carry matches nothing in it)."""
    # The attention op names its own residuals (ops/attention.py): a policy
    # that keeps the output without the log-sum-exp would still re-run the
    # kernel's forward in the backward pass.
    if config.remat_policy == "attn":
        return ATTN_OUT, ATTN_LSE
    if config.remat_policy == "qkv_attn":
        # No d-wide mixer projection is recomputed: attention's q, k, v
        # (latent and differential attention's too, both maps of the latter
        # in the one output and log-sum-exp) and what each kind names; `kda`
        # among it its recurrence's own residuals (the op names them as the
        # attention op does, `ops/kernel_pair.py`), so that its forward
        # kernel too runs once a layer.
        return ("q", "k", "v", ATTN_OUT, ATTN_LSE, *(name for m in MIXERS.values() for name in m.saved))
    if config.remat_policy is None:
        # Save nothing per layer: the backward re-runs the whole layer, the
        # flash forward and the recurrences' included.  The minimum-memory mode.
        return ()
    of_kinds = "; ".join(f"{m.name}: {', '.join(map(repr, m.saved))}" for m in MIXERS.values() if m.saved)
    raise ValueError(
        f"unknown remat_policy {config.remat_policy!r}; expected None (save nothing), 'attn' "
        f"(saves {ATTN_OUT!r}, {ATTN_LSE!r}) or 'qkv_attn' (those, 'q', 'k', 'v' and each kind's own: {of_kinds})"
    )


def _remat_policy(config: TransformerConfig):
    """The checkpoint policy of `saved_names` (shared by the scan and pipeline
    paths).  It says what JAX recomputes; over libtpu's limit XLA's pass may
    duplicate more (see `_dense_ffn`)."""
    names = saved_names(config)
    return jax.checkpoint_policies.save_only_these_names(*names) if names else None


def _refuse_unequal_stages(config: TransformerConfig) -> None:
    """What the pipeline schedule cannot run, by name: every stage scans ONE
    compiled body of the default kind over its share of ONE stack."""
    c = config
    if c.carries_router_state:
        raise ValueError("strategy 'pp' does not carry the router state (router_kind 'mlp': each expert layer reads the "
                         "state of the layer before) across its stages")
    if c.n_experts is not None:
        raise ValueError(
            "strategy 'pp' runs dense layers only: the router statistics of "
            "an expert layer do not come out of the pipeline schedule"
        )
    if c.diffusion_block is not None:
        raise ValueError("strategy 'pp' runs next-token models only: a block-diffusion step (diffusion_block) sends a noisy "
                         "and a clean copy of each sequence through the stack under a mask of its own")
    if (c.layer_types is not None or c.ffn_types is not None or c.layer_windows is not None
            or c.layer_ropes is not None or c.qk_norm == "per_head"):
        default, *others = MIXERS
        raise ValueError(
            f"strategy 'pp' runs a homogeneous stack of {default} layers only: the "
            f"stages of a stack with layer_types ({', '.join(others)}) or ffn_types "
            "(dense beside experts, or none) would hold unequal "
            "layers, and a value one layer hands to a later one does not cross stages; "
            "nor does it take layer_windows, layer_ropes (a window or a rope of a layer's own: "
            "one body a stage) or a per-head qk_norm"
        )


def check_placement(config: TransformerConfig, rules: Optional[Rules], mesh) -> None:
    """Refuse by name what this model cannot run under these rules on this
    mesh, before anything is traced: what each kind of mixer in the stack
    says of itself (`Mixer.placement`: the ring's limits), and what the
    pipeline schedule takes when the rules shard the layer stack."""
    if rules is None:
        return
    for kind in dict.fromkeys(m for m, _ in config.layer_pairs()):
        MIXERS[kind].placement(config, rules, mesh)
    if config.carries_router_state and any(fitting_axis(rules.get(name), mesh, 0) is not None
                                           for name in ("act_heads", "act_mlp", "expert")):
        raise ValueError("the router state (router_kind 'mlp') runs from layer to layer whole on each device: strategy 'tp' "
                         "and a mesh's expert axis do not take it")
    if pipeline_axes(rules, mesh, config.n_layers) is not None:
        _refuse_unequal_stages(config)
        if config.mtp_depth:
            raise ValueError("strategy 'pp' runs no multi-token-prediction module (mtp_depth): its block lies behind the "
                             "last stage and reads the first stage's embedding table")
    if config.mtp_depth and config.n_experts_held is not None and mesh is not None and mesh.size > 1:
        raise ValueError("mtp_depth beside n_experts_held on a mesh of more than one device: the module's block holds one "
                         "rank's share of the experts as the stack's layers do, and a share runs on one device")


def _run_layers_pipelined(
    layer_params: Dict,
    x: jax.Array,
    positions: jax.Array,
    config: TransformerConfig,
    mesh,
    axis: str,
    rules: Optional[Dict] = None,
    fsdp_axis: Optional[str] = None,
) -> jax.Array:
    """Run the [L, ...] layer stack as a GPipe pipeline: the stack reshapes
    to [P, L/P, ...] (stage-major), each pipeline-axis device scans its own
    L/P layers, and microbatches stream between stages with ppermute
    (parallel/pipeline.py).

    With `fsdp_axis` (strategy "pp_fsdp"), each stage's params additionally
    live SHARDED over that axis and are all-gathered once per step inside
    the stage body — optimizer state and params-at-rest take 1/(P*F) of the
    model per device instead of 1/P."""
    from ray_tpu.parallel.pipeline import pipeline_apply

    c = config
    n_stages = mesh.shape[axis]
    per_stage = c.n_layers // n_stages

    stacked = jax.tree_util.tree_map(
        lambda a: a.reshape(n_stages, per_stage, *a.shape[1:]), layer_params
    )

    fsdp_dims = None
    if fsdp_axis is not None and rules is not None:
        layer_axes = param_axes(c)[MIXERS[_DEFAULT_MIXER].stack]

        def dim_for(axes_tuple):
            # stacked leaf dims: [P, L/P, *per-layer dims]; logical name i
            # (after the leading "layers") lands at stacked dim i + 2.
            for i, name in enumerate(axes_tuple[1:]):
                if name is not None and rules.get(name) == fsdp_axis:
                    return i + 2
            return None

        fsdp_dims = jax.tree_util.tree_map(
            dim_for, layer_axes,
            is_leaf=lambda t: isinstance(t, tuple),
        )

    def stage_fn(stage_params, h):
        def body(carry, lp):
            return _layer(carry, lp, positions, c, None, None)

        out, _ = jax.lax.scan(body, h, stage_params)
        return out

    if c.remat:
        stage_fn = jax.checkpoint(stage_fn, policy=_remat_policy(c))
    return pipeline_apply(
        stage_fn, stacked, x, mesh,
        n_microbatches=c.pp_microbatches, axis=axis,
        fsdp_dims=fsdp_dims, fsdp_axis=fsdp_axis or "fsdp",
    )


def _embed(params, tokens, config, rules, mesh):
    """Token ids [B, S] -> their rows of the table, [B, S, d] in `config.dtype`, placed as the rules say."""
    c = config
    with tracing.scope("embed"):
        x = params["embed"]["tokens"].astype(c.dtype)[tokens]
        if c.embedding_multiplier != 1.0:
            x = x * jnp.asarray(c.embedding_multiplier, c.dtype)
        if rules is not None:
            x = with_logical_constraint(x, ("act_batch", "act_seq", "act_embed"), rules, mesh)
    return x


def forward(
    params: Dict,
    tokens: jax.Array,
    config: TransformerConfig,
    *,
    rules: Optional[Rules] = None,
    mesh=None,
) -> jax.Array:
    """Token ids [B, S] -> logits [B, S, vocab] (f32): `trunk`, then the
    head's matmul in the model's dtype, widened.  (The training objective
    never forms these: `lm.head_cross_entropy` takes the trunk's output.)  In a
    block-diffusion model this is the plain forward: one copy of the sequence
    under the block-causal mask, row i the logits of token i (no shift).

    `rules` come with the `mesh` they refer to: ring attention, the pipeline
    schedule and the flash kernel's shard_map are all built from it."""
    x, head, _ = trunk(params, tokens, config, rules=rules, mesh=mesh)
    with tracing.scope("lm_head"):
        logits = jnp.einsum("bse,ev->bsv", x, head).astype(jnp.float32)
        return _constrainer(rules, mesh)(logits, LOGITS_AXES)


def trunk(params: Dict, tokens: jax.Array, config: TransformerConfig, **kw):
    """`trunk_reports` without the layers' reports: (rows, head, router statistics)."""
    return trunk_reports(params, tokens, config, **kw)[:3]


def trunk_reports(
    params: Dict,
    tokens: jax.Array,
    config: TransformerConfig,
    *,
    rules: Optional[Rules] = None,
    mesh=None,
    noisy: Optional[jax.Array] = None,
):
    """Everything up to the head's matmul: (the rows that enter the head,
    [B, S, d] in `config.dtype`, normed and divided by `logits_scaling`; the
    head [d, vocab] in `config.dtype`, the embedding table transposed when
    tied; router statistics stacked over the layers, `[L, ...]` each, as
    `moe.router_losses` takes them, None for a dense model; what the layers
    REPORT, name -> float32 [layers that report it] in the stack's order
    (`Mixer.reports`), {} for a model whose kinds report nothing).

    `noisy` [B, S] (a block-diffusion model's training step): the noisy copy
    x_t of `tokens`.  The stack then runs the 2S rows `[x_t ‖ x_0]` at the
    positions `[0..S-1, 0..S-1]` under the block-diffusion mask, every layer
    and the router statistics over all of them, and the rows returned are the
    NOISY half's, [B, S, d]."""
    c = config
    if rules is not None and mesh is None:
        raise ValueError("forward(rules=...) needs the mesh the rules refer to")
    seq = tokens.shape[1]
    diffusion = {}  # what a block-diffusion model's layers are told, and no other's
    if c.diffusion_block is not None:
        if seq % c.diffusion_block:
            raise ValueError(f"diffusion_block={c.diffusion_block} does not divide the sequence's {seq} tokens")
        diffusion = {"noisy_rows": 0 if noisy is None else seq}
    if noisy is not None:
        if c.diffusion_block is None or noisy.shape != tokens.shape:
            raise ValueError("trunk(noisy=...) is a block-diffusion model's (diffusion_block), the noisy copy shaped as the tokens")
        tokens = jnp.concatenate([noisy, tokens], axis=1)
    x = _embed(params, tokens, c, rules, mesh)
    positions = jnp.arange(seq)
    if noisy is not None:
        positions = jnp.concatenate([positions, positions])
    pp = pipeline_axes(rules, mesh, c.n_layers)
    # `layers` names what the loop over the stack itself costs (each layer's
    # weights sliced out of the stack, gradients and residuals stacked back);
    # the regions of a layer are named inside it.
    router_stats, reports = None, {}
    with tracing.scope("layers"):
        if pp is not None:
            stack = params.get(MIXERS[_DEFAULT_MIXER].stack)  # None: a stack it refuses by name
            x = _run_layers_pipelined(stack, x, positions, c, mesh, pp[0], rules=rules, fsdp_axis=pp[1])
        else:
            # One scan per maximal run of one (mixer, FFN) pair, over that
            # run's slice of the pair's stack; a homogeneous model is one run
            # over its whole stack.
            runs = c.layer_runs()
            stacks = {}
            for name, (mixer, ffn, _) in c.stacks().items():
                bounds = tuple((first, count) for m, f, first, count in runs if (m, f) == (mixer, ffn))
                whole = len(bounds) == 1  # one run: the stack as it is, no slices to copy
                stacks[mixer, ffn] = iter([params[name]] if whole else _split_runs(params[name], bounds))
            per_run = []
            shared = {}  # what layers have handed on so far (`Mixer.hands`), by name
            # what every layer reads from the one before (`_ffn_half`), part of the scans' carry: before the first
            # expert layer the router's state is zeros, so that layer's own state is its down-projection alone
            carried = {}
            if c.carries_router_state:
                carried["router_state"] = _constrainer(rules, mesh)(
                    jnp.zeros((*x.shape[:2], c.router_hidden), jnp.float32), ("act_batch", "act_seq", None))
            for (kind, ffn, _, count), start in zip(runs, c.run_starts()):
                mixer = MIXERS[kind]
                window, rope, emit = c.layer_variant(start)

                layer_fn = functools.partial(layer, mixer, positions=positions, config=c, rules=rules, mesh=mesh,
                                             ffn=ffn, window=window, emit=emit, rope=rope, **diffusion)
                if c.remat:
                    layer_fn = jax.checkpoint(layer_fn, policy=_remat_policy(c))

                # The layer takes its per-layer data and what it reads of
                # earlier layers as ARGUMENTS (inputs of its checkpoint,
                # constants of the scan, their cotangents summed over the
                # readers) and returns what it hands on (a run that does is
                # one layer: `layer_variant`).  A kind with neither takes and
                # returns empty mappings: nothing of the traced program.
                def body(carry, xs, layer_fn=layer_fn, read={name: shared[name] for name in mixer.reads}):
                    x, stats, handed, state = layer_fn(carry[0], xs[0], data=xs[1], shared=read, carried=carry[1])
                    return (x, state), (stats, handed)

                data = {name: jnp.asarray(values[start: start + count], jnp.float32)
                        for name, values in mixer.data(c).items()}
                (x, carried), (run_stats, handed) = jax.lax.scan(body, (x, carried), (next(stacks[kind, ffn]), data))
                shared.update({name: handed[name][0] for name in mixer.hands if name in handed})
                for name in mixer.reports:
                    reports[name] = handed[name] if name not in reports else jnp.concatenate([reports[name], handed[name]])
                if run_stats is not None:
                    per_run.append(run_stats)
            # the expert layers' statistics, [expert layers, ...] in the stack's order
            if len(per_run) == 1:
                router_stats = per_run[0]
            elif per_run:
                router_stats = jax.tree_util.tree_map(lambda *a: jnp.concatenate(a, axis=0), *per_run)
    with tracing.scope("final_norm"):
        if noisy is not None:
            x = x[:, :seq]  # the head reads the noisy half alone
        x = stream_norm(c, x, params, "final_norm")
    with tracing.scope("lm_head"):
        head = (
            params["embed"]["tokens"].T if c.tie_embeddings else params["lm_head"]
        ).astype(c.dtype)
        if c.logits_scaling != 1.0:
            # logits / logits_scaling, applied to the [B, S, d] rows that enter
            # the head and not to the [B, S, vocab] logits that leave it: the
            # same function (a linear map commutes with a scalar; a power of
            # two, as published, does not even move a rounding) without a
            # pass over the logits.
            x = x / jnp.asarray(c.logits_scaling, x.dtype)
    return x, head, router_stats, reports


def biases_following_load(params: Dict, before: Dict, choice_share: jax.Array, config: TransformerConfig) -> Dict:
    """`params` with every expert layer's stored `router_bias` set to `before`'s one step of
    `moe.load_following_bias` on, at `config.router_bias_update_rate`: `choice_share` [expert layers, K, E] is the
    step's router statistic of that name as `trunk` stacks it, in the stack's order, and each run of expert layers
    takes its rows to its pair's stack.  What a training step does to the bias IN PLACE OF the optimizer's update
    (`before`: the step's own parameters, `params`: the optimizer's result; `models/lm.py` `_train_step`)."""
    c, out, at = config, dict(params), 0
    for kind, ffn, first, count in c.layer_runs():
        if ffn != "experts":
            continue
        name = c.stack_name(kind, ffn)
        bias = before[name]["mlp"]["router_bias"]
        moved = load_following_bias(bias[first: first + count], choice_share[at: at + count], c.router_bias_update_rate)
        out[name] = {**out[name], "mlp": {**out[name]["mlp"], "router_bias": bias.at[first: first + count].set(moved)}}
        before = {**before, name: out[name]}  # the pair's next run moves its own rows of the same leaf
        at += count
    return out


def mtp_rows(
    params: Dict,
    h: jax.Array,
    next_tokens: jax.Array,
    config: TransformerConfig,
    *,
    rules: Optional[Rules] = None,
    mesh=None,
):
    """The multi-token-prediction module behind the trunk (DeepSeek-V3,
    arXiv:2412.19437 section 2.2, depth 1), with `h` [B, S, d] the rows
    `trunk` returns (the main model's output behind its final norm) and
    `next_tokens` [B, S] the token AFTER each position (the batch's targets):

    `h'_i = [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)] W_eh` (`[2d, d]`, the
    embedding's half first), `h1 = Block(h')`, ONE more layer of the stack's
    last pair and variant, `layer` itself, with the module's own weights
    (`params["mtp"]["block"]`; the same positions 0..S-1, the same remat),
    and `RMSNorm_s(h1)`: (the rows that enter the head for the token after
    the next, [B, S, d]; the block's router statistics with a leading axis of
    one layer, or None).  The embedding table and the head are the model's
    own, so their gradients sum over both uses.  The whole module is named
    `mtp` (`mtp/proj` the two norms and `W_eh`; the block's names inside)."""
    c, p = config, params["mtp"]
    kind, ffn = c.layer_pairs()[-1]
    window, rope, _ = c.layer_variant(c.n_layers - 1)
    constrain = _constrainer(rules, mesh)
    with tracing.scope("mtp"):
        e = _embed(params, next_tokens, c, rules, mesh)
        with tracing.scope("mtp/proj"):
            both = jnp.concatenate([rms_norm(e, p["enorm"], c.norm_eps), rms_norm(h, p["hnorm"], c.norm_eps)], axis=-1)
            x = constrain(jnp.einsum("bsf,fe->bse", both, p["eh_proj"].astype(c.dtype)), ("act_batch", "act_seq", "act_embed"))
        block = functools.partial(layer, MIXERS[kind], positions=jnp.arange(next_tokens.shape[1]), config=c, rules=rules,
                                  mesh=mesh, ffn=ffn, window=window, rope=rope)
        if c.remat:
            block = jax.checkpoint(block, policy=_remat_policy(c))
        x, stats, *_ = block(x, p["block"])
        with tracing.scope("mtp/proj"):
            x = rms_norm(x, p["norm"], c.norm_eps)
    return x, None if stats is None else jax.tree_util.tree_map(lambda a: a[None], stats)


def mtp_forward(params: Dict, tokens: jax.Array, next_tokens: jax.Array, config: TransformerConfig, *,
                rules: Optional[Rules] = None, mesh=None) -> jax.Array:
    """Token ids and the token after each, [B, S] both -> the module's logits
    [B, S, vocab] (f32) for the token after the next: `trunk`, `mtp_rows` and
    the model's head, as the training objective runs them (it forms no logits:
    `lm.head_cross_entropy` takes the rows)."""
    h, head, _ = trunk(params, tokens, config, rules=rules, mesh=mesh)
    x, _ = mtp_rows(params, h, next_tokens, config, rules=rules, mesh=mesh)
    with tracing.scope("mtp"), tracing.scope("lm_head"):
        logits = jnp.einsum("bse,ev->bsv", x, head).astype(jnp.float32)
        return _constrainer(rules, mesh)(logits, LOGITS_AXES)


def diffusion_forward(params: Dict, noisy: jax.Array, clean: jax.Array, config: TransformerConfig, *,
                      rules: Optional[Rules] = None, mesh=None) -> jax.Array:
    """A block-diffusion model's TRAINING forward: the noisy copy x_t and the
    clean copy x_0 of each sequence, [B, S] both -> the logits of the noisy
    rows [B, S, vocab] (f32), row i what the model makes of token i given its
    own block's noisy tokens and the clean blocks before: `trunk(noisy=...)`
    and the head, as the objective runs them (it forms no logits:
    `lm.head_weighted_cross_entropy` takes the rows)."""
    x, head, _ = trunk(params, clean, config, rules=rules, mesh=mesh, noisy=noisy)
    with tracing.scope("lm_head"):
        logits = jnp.einsum("bse,ev->bsv", x, head).astype(jnp.float32)
        return _constrainer(rules, mesh)(logits, LOGITS_AXES)
