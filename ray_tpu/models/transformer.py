"""Flagship model: a pre-norm decoder-only transformer, TPU-first.

What one `TransformerConfig` expresses: a stack of pre-norm RMSNorm layers,
each a (MIXER, FFN) pair around a residual stream.  The mixer
(`layer_types`) is causal softmax attention (GQA, rotary embedding or none,
optional QK-norm, the published softmax scale), a Mamba-2 selective
state-space layer, Kimi Delta Attention ("kda", a gated delta rule with a
decay per channel) or latent attention without rotary embedding ("mla": keys
and values expanded from one low-rank latent, q/k heads wider than v heads);
the FFN (`ffn_types`) is a dense SwiGLU or a dropless top-k mixture of SwiGLU
experts (`n_experts`; softmax or sigmoid router, an optional shared expert,
all experts or one rank's share of them: models/moe.py).  Four more mixers
make SambaY's decoder-hybrid-decoder (below): a Mamba-1 selective scan
("s6"), differential attention ("diff_attention", per layer a causal window
or none), a Gated Memory Unit ("gmu") that reads one s6 layer's scan output,
and differential cross-attention ("diff_cross") over one diff_attention
layer's keys and values.  Norms are RMSNorm or LayerNorm with bias
(`norm_kind`), and the differential layers' projections may carry biases
(`attn_bias`).  Mistral, InternLM2, OLMoE, the Granite 4.0-H hybrids, Kimi
Linear and Phi-4-mini-flash run through it at their published widths
(benchmarks/configs/).

The reference has no model code of its own (it trains user-supplied torch
models through wrappers — python/ray/train/torch/train_loop_utils.py:92-98);
a TPU framework needs first-party models whose sharding the Train layer can
drive.  Design:

- Pure-functional: params are a plain pytree; `forward` is a jit-able
  function.  No module framework in the hot path.
- Every parameter leaf has a *logical axes* annotation (`param_axes`), mapped
  to mesh axes by ray_tpu.parallel.sharding rules — one model, every
  parallelism strategy (DP/FSDP/TP/SP via rules, not rewrites).
- Layers are stacked per (mixer, FFN) PAIR on a leading `layers` axis
  (`params["layers"]` the attention layers, `params["mamba_layers"]` the
  Mamba-2 ones, `kda_layers`, `mla_layers`; a mixer that the model pairs with
  BOTH kinds of FFN has one stack for each, `kda_layers_dense` and
  `kda_layers_experts`: `TransformerConfig.stack_name`) and the stack runs as
  ONE `lax.scan` per maximal run of one pair (one compiled body per pair,
  O(1) compile time in depth), with optional `jax.checkpoint`
  rematerialization for HBM.  A homogeneous model is the one-run case.
- Attention dispatches to the pallas flash kernel when lowered for TPU
  (under shard_map when there is a mesh), the XLA forms otherwise
  (ray_tpu.ops.attention), or ring attention when the mesh has a nontrivial
  `seq` axis.

The hybrid (Granite 4.0-H, `modeling_granitemoehybrid.py`; Mamba-2 / SSD,
arXiv:2405.21060), with x [B, S, d], every RMSNorm with a learned scale and
`norm_eps`, no bias anywhere but the convolution's:

- model: `h0 = embed[tokens] * embedding_multiplier`; the layers;
  `logits = (RMSNorm(h) @ head) / logits_scaling`.
- every layer: `h = h + residual_multiplier * mixer(RMSNorm_1(h))`, then
  `h = h + residual_multiplier * SwiGLU(RMSNorm_2(h))`.
- attention layer: q/k/v projections, rotary embedding only when
  `rope_theta` is set, causal softmax of `q k^T * attention_scale`
  (`head_dim ** -0.5` when None), output projection.
- Mamba-2 layer, `d_inner = ssm_heads * ssm_head_dim`, state N = `ssm_state`,
  one group: `in_proj: d -> [z: d_inner | xBC: d_inner + 2N | dt: ssm_heads]`;
  `xBC = silu(causal_depthwise_conv1d(xBC, width ssm_conv, with bias))`,
  split into x [S, heads, head_dim], B [S, N], C [S, N];
  `dt = softplus(dt + dt_bias)` per head; `A = -exp(A_log)` per head (a
  scalar).  Per head, with state H_t in R^{head_dim x N}:
  `H_t = exp(dt_t A) H_{t-1} + dt_t x_t (outer) B_t`, `y_t = H_t C_t + D x_t`
  (`ops/ssm.py`, in its chunked form).  Then
  `y = RMSNorm(y * silu(z))` over all d_inner channels and
  `out_proj: d_inner -> d`.

Kimi Linear (arXiv:2510.26692; `model_type: kimi_linear`), no bias and no
rotary embedding anywhere, H heads of size D = `kda_head_dim` in a KDA layer:

- KDA layer: `[q | k | v] = silu(causal_depthwise_conv1d(x W_qkv))`, width
  `kda_conv`, three convolutions over H*D channels each (one call over the
  3*H*D); per head `q <- q / |q|_2 * D^-0.5`, `k <- k / |k|_2`; the log decay
  `g = -exp(A_log[h]) * softplus((x W_f_down) W_f_up + dt_bias)` per channel,
  float32; `beta = sigmoid(x W_beta)` per head; the recurrence of
  `ops/kda.py` (state [D, D] per head, float32) in its chunked form;
  `o <- RMSNorm_head(o) * sigmoid((x W_g_down) W_g_up)` (norm over each
  head's D with one learned scale [D]; both gates low-rank, d -> D -> H*D);
  `W_o: H*D -> d`.
- MLA layer, `n_heads` heads: `q = x W_q -> [H, nope + rope]`;
  `[c | k_pe] = x W_kva -> [kv_lora_rank | rope]`; `c <- RMSNorm(c)`;
  `[k_nope | v] = c W_kvb -> [H, nope | v_head_dim]`; `k = [k_nope | k_pe]`,
  the one `k_pe` shared by the heads and, the model being NoPE, not rotated;
  causal softmax of `q k^T * (nope + rope)^-0.5`; `W_o: H * v_head_dim -> d`.
- FFN of an "experts" layer: the sigmoid router, the shared expert and the
  held experts of `models/moe.py`.

SambaY (Phi-4-mini-flash-reasoning, `model_type: phi4flash`, arXiv:2507.06607;
differential attention, arXiv:2410.05258; Mamba, arXiv:2312.00752): no
positional encoding anywhere; every norm a LayerNorm (mean and variance,
learned scale AND bias, `norm_eps`); layer l: `h = x + Mixer_l(LN1(x))`,
`y = h + W_down(silu(W_gate u) * (W_up u))` with `u = LN2(h)`, no bias in the
FFN; `logits = LN_f(y) @ embed.T`.  The mixers, u = LN1(x):

- "s6", Mamba-1 with `s6_inner` channels, state N = `s6_state`, `s6_dt_rank`:
  `[x | z] = u W_in` (no bias); `x = silu(conv1d_causal_depthwise(x, width
  s6_conv, with bias))`; `[dt_low | B | C] = x W_x` (dt_rank | N | N);
  `dt = softplus(dt_low W_dt + b_dt)` [S, inner]; `A = -exp(A_log)` [inner, N];
  `h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]`;
  `y_t[c] = sum_n C_t[n] h_t[c, n] + D[c] x_t[c]` (`ops/selective_scan.py`,
  in its chunked form); `out = (y * silu(z)) W_out`.  State, `dt * A`, its
  exponentials and the softplus in float32.  At the layer `s6_memory_layer`,
  `M = y` (with the D skip, before the gate) is handed on as well.
- "gmu": `out = (M * silu(u W_1)) W_2`, `W_1: d -> s6_inner`, `W_2: s6_inner
  -> d`, no bias.
- "diff_attention": `[q | k | v] = u W_qkv + b_qkv`, heads of `head_dim`;
  heads pair up adjacently: q pair p = q heads (2p, 2p+1) = (q1, q2); with
  G = n_heads / n_kv_heads, kv pair j = p // G: k heads (2j, 2j+1) = (k1, k2),
  `v = [v_2j | v_2j+1]` of width 2 * head_dim.
  `a1 = softmax(q1 k1^T / sqrt(head_dim) + mask) v`, `a2` likewise from
  (q2, k2); `lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init`, four
  learned vectors of head_dim a layer, `lambda_init = 0.8 - 0.6 exp(-0.3 l)`
  with l the layer's PUBLISHED index (`layer_ids`);
  `o = RMSNorm(a1 - lambda a2) * (1 - lambda_init)` over 2 * head_dim (one
  learned scale a layer), reshaped to two heads; `out = o W_o + b_o`.  The
  mask is causal, and with a window w (`layer_windows`) query i sees keys
  i - w + 1 .. i.  At the layer `kv_source_layer`, k and v (after bias) are
  handed on as well.
- "diff_cross": its own `q = u W_q + b_q`, lambda, norm and `W_o`; k and v
  are the `kv_source_layer`'s; full causal.

What crosses layers (M; k and v) is RETURNED by the layer that makes it,
carried by `trunk` beside the stream and given to the later runs as an
argument: under `jax.checkpoint` an input of the reading layer, not
recomputed by it, and its cotangents sum over the readers.  `tp`, `pp` and
the sequence-parallel ring refuse the differential kinds by name (`pp` any
`layer_types`); s6 and gmu replicate their inner width under `tp`, as
Mamba-2 does.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.moe import init_moe_params, moe_ffn, moe_param_axes
from ray_tpu.ops.attention import ATTN_LSE, ATTN_OUT, dot_product_attention
from ray_tpu.ops.rotary import apply_rope
from ray_tpu.ops.kda import kda_chunked
from ray_tpu.ops.selective_scan import selective_scan
from ray_tpu.ops.ssm import causal_conv1d_silu, ssd_chunked
from ray_tpu.parallel.sharding import Rules, with_logical_constraint


# The logical axes of the logits (and of their cotangent).
LOGITS_AXES = ("act_batch", "act_seq", "act_vocab")

# The kinds of mixer, and the subtree of the parameters that stacks each
# (`TransformerConfig.stack_name`); the kinds of FFN.
LAYER_KINDS = {
    "attention": "layers", "mamba": "mamba_layers", "kda": "kda_layers", "mla": "mla_layers",
    "s6": "s6_layers", "diff_attention": "diff_layers", "gmu": "gmu_layers", "diff_cross": "cross_layers",
}
FFN_KINDS = ("dense", "experts")
# The kinds whose layer function takes what earlier layers handed on (and
# per-layer data) beside its parameters, and returns what it hands on.
CROSS_KINDS = ("s6", "diff_attention", "gmu", "diff_cross")
# What crosses layers, by name: an s6 layer's scan output; a diff_attention layer's keys and values.
MEMORY, SHARED_K, SHARED_V = "memory", "shared_k", "shared_v"

# `checkpoint_name`s of a Mamba-2 layer's residuals (`_remat_policy`):
# `in_proj`'s output before its split into z, x|B|C and dt, and the residual
# stream after the mixer, as it enters the FFN half.
SSM_IN_PROJ = "ssm_in_proj"
SSM_MIXED = "ssm_mixed"
# Of a KDA layer's: the fused q|k|v projection before its convolution, the
# two low-rank gates' narrow halves with beta's logits (one array), and the
# residual stream after the mixer.  Of an MLA layer's, beside attention's own
# q, k, v: the residual stream after the mixer.
KDA_QKV = "kda_qkv"
KDA_LOW = "kda_low"
KDA_MIXED = "kda_mixed"
MLA_MIXED = "mla_mixed"
# Of an s6 layer's: `W_in`'s output before its split into x and z, and the
# stream after `W_out`.  Of a GMU's: `W_1`'s output and the stream after
# `W_2`.  Of the two differential kinds', beside attention's own q, k, v,
# output and log-sum-exp (both maps are one call): the stream after `W_o`.
S6_IN_PROJ = "s6_in_proj"
S6_MIXED = "s6_mixed"
GMU_GATE = "gmu_gate"
GMU_MIXED = "gmu_mixed"
DIFF_MIXED = "diff_mixed"


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
    # more HBM.  A Mamba-2 layer names what its backward needs of its two
    # projections (`SSM_IN_PROJ`, `SSM_MIXED`) and "qkv_attn" saves those
    # too, so under it no mixer's input projection is recomputed, whatever
    # the layer's kind; under "attn" and None a Mamba-2 layer keeps its input
    # only.  Convolution, scan and gated norm run again under every policy:
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
    n_experts_held: Optional[int] = None
    first_expert_held: int = 0
    # RMSNorm with a learned scale over the whole projected q and k, before
    # RoPE (OLMoE, OLMo 2).
    qk_norm: bool = False
    # The mixer of each layer, one of `LAYER_KINDS`, one entry per layer;
    # None = attention everywhere.  The Mamba-2 sizes are read only when some
    # layer is "mamba": heads x head size = the mixer's inner width, the
    # state size N per head, the width of the causal depthwise convolution.
    layer_types: Optional[Tuple[str, ...]] = None
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_conv: int = 4
    # The FFN of each layer, "dense" or "experts", one entry per layer; None =
    # experts everywhere when `n_experts` is set, dense everywhere otherwise.
    ffn_types: Optional[Tuple[str, ...]] = None
    # Kimi Delta Attention, read only when some layer is "kda": heads, the
    # one head size of q, k and v (also the width of the two low-rank gates),
    # the width of the three causal depthwise convolutions.
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    # Latent attention, read only when some layer is "mla" (`n_heads` heads):
    # the latent's width, the two parts of a q/k head, the size of a v head.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
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
    # differential attention's lambda_init is a function of.
    kv_source_layer: Optional[int] = None
    layer_windows: Optional[Tuple[Optional[int], ...]] = None
    layer_ids: Optional[Tuple[int, ...]] = None

    def __post_init__(self):
        if self.layer_types is not None:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
            unknown = set(self.layer_types) - set(LAYER_KINDS)
            if unknown or len(self.layer_types) != self.n_layers:
                raise ValueError(
                    f"layer_types needs n_layers={self.n_layers} entries out of {LAYER_KINDS}, "
                    f"got {len(self.layer_types)} with {sorted(unknown)} unknown"
                )
            if "mamba" in self.layer_types and not (
                self.ssm_heads > 0 and self.ssm_head_dim > 0 and self.ssm_state > 0
            ):
                raise ValueError("a mamba layer needs ssm_heads, ssm_head_dim and ssm_state")
            if "kda" in self.layer_types and not (self.kda_heads > 0 and self.kda_head_dim > 0):
                raise ValueError("a kda layer needs kda_heads and kda_head_dim")
            if "mla" in self.layer_types and not (
                self.kv_lora_rank > 0 and self.qk_nope_head_dim > 0 and self.v_head_dim > 0
            ):
                raise ValueError("an mla layer needs kv_lora_rank, qk_nope_head_dim and v_head_dim")
            self._check_cross_kinds()
        for name in ("layer_windows", "layer_ids"):
            if getattr(self, name) is not None:
                object.__setattr__(self, name, tuple(getattr(self, name)))
                if len(getattr(self, name)) != self.n_layers:
                    raise ValueError(f"{name} needs n_layers={self.n_layers} entries")
        if self.norm_kind not in ("rms", "layer"):
            raise ValueError(f"unknown norm_kind {self.norm_kind!r}; expected 'rms' or 'layer'")
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
        if self.n_experts_held is not None and not (
            self.n_experts is not None and self.n_experts_held > 0 and self.first_expert_held >= 0
            and self.first_expert_held + self.n_experts_held <= self.n_experts
        ):
            raise ValueError(
                f"n_experts_held={self.n_experts_held} from {self.first_expert_held} "
                f"is no share of n_experts={self.n_experts}"
            )

    def _check_cross_kinds(self):
        """What the kinds that read another layer's values need of the stack."""
        kinds = self.layer_types
        if ("s6" in kinds or "gmu" in kinds) and not (self.s6_inner > 0 and self.s6_state > 0):
            raise ValueError("an s6 or gmu layer needs s6_inner and s6_state")
        if ("diff_attention" in kinds or "diff_cross" in kinds) and (
            self.n_heads % 2 or self.n_kv_heads % 2 or self.n_heads % self.n_kv_heads
        ):
            raise ValueError("differential attention pairs adjacent heads: n_heads and n_kv_heads "
                             "must be even, n_heads a multiple of n_kv_heads")
        for reader, maker, source, name in (("gmu", "s6", self.s6_memory_layer, "s6_memory_layer"),
                                            ("diff_cross", "diff_attention", self.kv_source_layer, "kv_source_layer")):
            if source is not None and not (0 <= source < self.n_layers and kinds[source] == maker):
                raise ValueError(f"{name}={source} is no {maker} layer of this stack")
            if reader in kinds and (source is None or kinds.index(reader) < source):
                raise ValueError(f"a {reader} layer needs {name}, a {maker} layer before it")
        if self.attn_bias and ("attention" in kinds or "mla" in kinds):
            raise ValueError("attn_bias is the differential kinds' alone: 'attention' and 'mla' layers have no bias")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def dt_rank(self) -> int:
        return self.s6_dt_rank or -(-self.d_model // 16)

    def layer_variant(self, i: int) -> Tuple[Optional[int], bool]:
        """What of layer i is STATIC beside its pair, so that a run of one
        compiled body cannot span a change of it: (its attention's window,
        whether it hands values on to later layers)."""
        window = None if self.layer_windows is None else self.layer_windows[i]
        return window, i in (self.s6_memory_layer, self.kv_source_layer)

    def lambda_inits(self) -> Tuple[float, ...]:
        """Differential attention's `lambda_init` of each layer, from its published index."""
        ids = self.layer_ids or tuple(range(self.n_layers))
        return tuple(0.8 - 0.6 * math.exp(-0.3 * l) for l in ids)

    @property
    def expert_width(self) -> int:
        """ONE expert's width."""
        return self.d_ff if self.moe_d_ff is None else self.moe_d_ff

    def layer_pairs(self) -> Tuple[Tuple[str, str], ...]:
        """(mixer, FFN) of each layer."""
        mixers = self.layer_types or ("attention",) * self.n_layers
        ffns = self.ffn_types or ("dense" if self.n_experts is None else "experts",) * self.n_layers
        return tuple(zip(mixers, ffns))

    def stack_name(self, mixer: str, ffn: str) -> str:
        """The subtree of the parameters that stacks the layers of one pair:
        the mixer's own (`LAYER_KINDS`) when the model pairs that mixer with
        one kind of FFN, `<the mixer's>_<ffn>` when with both."""
        both = len({f for m, f in self.layer_pairs() if m == mixer}) > 1
        return f"{LAYER_KINDS[mixer]}_{ffn}" if both else LAYER_KINDS[mixer]

    def stacks(self) -> Dict[str, Tuple[str, str, int]]:
        """stack name -> (mixer, FFN, layers in it), every pair the model has,
        in the order of `LAYER_KINDS` x `FFN_KINDS`."""
        pairs = self.layer_pairs()
        return {
            self.stack_name(m, f): (m, f, pairs.count((m, f)))
            for m in LAYER_KINDS for f in FFN_KINDS if (m, f) in pairs
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
        d = self.d_model
        attn = d * self.head_dim * (2 * self.n_heads + 2 * self.n_kv_heads)
        if self.qk_norm:
            attn += self.head_dim * (self.n_heads + self.n_kv_heads)
        inner, conv = self.ssm_heads * self.ssm_head_dim, self._ssm_conv_channels
        ssm = (d * (2 * inner + 2 * self.ssm_state + self.ssm_heads)  # in_proj
               + conv * (self.ssm_conv + 1) + 3 * self.ssm_heads + inner  # conv, dt_bias/A_log/D, norm
               + inner * d)  # out_proj
        kh, kd = self.kda_heads, self.kda_head_dim
        kda = (4 * d * kh * kd  # q, k, v, o
               + 2 * (d * kd + kd * kh * kd) + d * kh  # the two low-rank gates, beta
               + 3 * kh * kd * self.kda_conv + kh + kh * kd + kd)  # convolutions, A_log, dt_bias, norm
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        mla = (d * self.n_heads * qk + d * (self.kv_lora_rank + self.qk_rope_head_dim) + self.kv_lora_rank
               + self.kv_lora_rank * self.n_heads * (self.qk_nope_head_dim + self.v_head_dim)
               + self.n_heads * self.v_head_dim * d)
        s6_in, rank, hd = self.s6_inner, self.dt_rank, self.head_dim
        s6 = (d * 2 * s6_in + s6_in * (self.s6_conv + 1)  # W_in, convolution
              + s6_in * (rank + 2 * self.s6_state) + rank * s6_in + s6_in  # W_x, W_dt, b_dt
              + s6_in * self.s6_state + s6_in + s6_in * d)  # A_log, D, W_out
        q_wide, kv_wide = self.n_heads * hd, 2 * self.n_kv_heads * hd
        bias = (q_wide + d) if self.attn_bias else 0
        cross = 2 * d * q_wide + bias + 4 * hd + 2 * hd  # W_q, W_o, the four lambda vectors, the norm
        diff = cross + d * kv_wide + (kv_wide if self.attn_bias else 0)
        mixer = {"attention": attn, "mamba": ssm, "kda": kda, "mla": mla,
                 "s6": s6, "diff_attention": diff, "gmu": 2 * d * s6_in, "diff_cross": cross}
        ffn = {"dense": 3 * d * self.d_ff}
        if self.n_experts is not None:
            held = self.n_experts if self.n_experts_held is None else self.n_experts_held
            ffn["experts"] = (d * self.n_experts + (held + self.n_shared_experts) * 3 * d * self.expert_width
                              + (self.n_experts if self.router_activation == "sigmoid" else 0))
        norm = d * (2 if self.norm_kind == "layer" else 1)  # scale, and LayerNorm's bias
        layers = sum(mixer[m] + ffn[f] + 2 * norm for m, f in self.layer_pairs())
        out = 0 if self.tie_embeddings else self.vocab_size * d
        return self.vocab_size * d + layers + norm + out

    @property
    def _ssm_conv_channels(self) -> int:
        """x | B | C, the channels the convolution runs over (one group)."""
        return self.ssm_heads * self.ssm_head_dim + 2 * self.ssm_state


def _mixer_axes(config: TransformerConfig, mixer: str) -> Dict:
    """{subtree name: logical axes of ONE stack's mixer leaves}."""
    L = ("layers",)
    if mixer == "attention":
        attn = {
            "wq": L + ("embed", "heads", "head_dim"),
            "wk": L + ("embed", "kv_heads", "head_dim"),
            "wv": L + ("embed", "kv_heads", "head_dim"),
            "wo": L + ("heads", "head_dim", "embed"),
        }
        if config.qk_norm:
            attn["q_norm"] = L + ("heads", "head_dim")
            attn["k_norm"] = L + ("kv_heads", "head_dim")
        return {"attn": attn}
    if mixer == "mamba":
        # The mixer's inner width carries no logical axis: `fsdp` shards the
        # two projections over `embed`, and under `tp` the scan's heads are
        # REPLICATED over `tensor` (its FFN still shards), not refused.
        return {"ssm": {
            "in_proj": L + ("embed", None),
            "conv_w": L + (None, None),
            "conv_b": L + (None,),
            "dt_bias": L + (None,),
            "A_log": L + (None,),
            "D": L + (None,),
            "norm": L + (None,),
            "out_proj": L + (None, "embed"),
        }}
    if mixer == "kda":  # as Mamba-2: the heads of a recurrence are replicated under `tp`
        return {"kda": {
            "wqkv": L + ("embed", None),
            "conv_w": L + (None, None),
            "f_down": L + ("embed", None),
            "f_up": L + (None, None),
            "g_down": L + ("embed", None),
            "g_up": L + (None, None),
            "w_beta": L + ("embed", None),
            "A_log": L + (None,),
            "dt_bias": L + (None,),
            "norm": L + (None,),
            "wo": L + (None, "embed"),
        }}
    if mixer == "s6":  # as Mamba-2: fsdp shards the projections over `embed`, tp replicates the inner width
        return {"s6": {
            "in_proj": L + ("embed", None),
            "conv_w": L + (None, None),
            "conv_b": L + (None,),
            "x_proj": L + (None, None),
            "dt_proj": L + (None, None),
            "dt_bias": L + (None,),
            "A_log": L + (None, None),
            "D": L + (None,),
            "out_proj": L + (None, "embed"),
        }}
    if mixer == "gmu":
        return {"gmu": {"w1": L + ("embed", None), "w2": L + (None, "embed")}}
    if mixer in ("diff_attention", "diff_cross"):  # heads carry no logical axis: tp is refused (`_diff_core`)
        diff = {
            ("wqkv" if mixer == "diff_attention" else "wq"): L + ("embed", None),
            "wo": L + (None, "embed"),
            **{name: L + (None,) for name in _LAMBDAS + ("subln",)},
        }
        if config.attn_bias:
            diff["bqkv" if mixer == "diff_attention" else "bq"] = L + (None,)
            diff["bo"] = L + (None,)
        return {"diff": diff}
    return {"mla": {
        "wq": L + ("embed", "heads", "head_dim"),
        "w_kva": L + ("embed", None),
        "kv_norm": L + (None,),
        "w_kvb": L + (None, "heads", "head_dim"),
        "wo": L + ("heads", "head_dim", "embed"),
    }}


# Differential attention's four learned vectors of `head_dim`.
_LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")


def _is_axes(t) -> bool:
    return isinstance(t, tuple)


def param_axes(config: TransformerConfig) -> Dict:
    """Pytree of logical-axes tuples, congruent with init_params output."""
    L = ("layers",)
    dense = {
        "w_gate": L + ("embed", "mlp"),
        "w_up": L + ("embed", "mlp"),
        "w_down": L + ("mlp", "embed"),
    }
    axes = {"embed": {"tokens": ("vocab", "embed")}, "final_norm": (None,)}
    biased = config.norm_kind == "layer"
    if biased:
        axes["final_norm_b"] = (None,)
    for name, (mixer, ffn, _) in config.stacks().items():
        mlp = dict(dense)
        if ffn == "experts":
            mlp = jax.tree_util.tree_map(lambda t: L + t, moe_param_axes(config), is_leaf=_is_axes)
        axes[name] = {**_mixer_axes(config, mixer), "mlp": mlp, "ln1": L + (None,), "ln2": L + (None,)}
        if biased:
            axes[name].update(ln1_b=L + (None,), ln2_b=L + (None,))
    if not config.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def init_params(config: TransformerConfig, key: jax.Array) -> Dict:
    """Initialize the parameter pytree (truncated-normal / scaled init)."""
    c = config
    pd = c.param_dtype

    def norm_init(kk, shape, scale):
        return (jax.random.normal(kk, shape, jnp.float32) * scale).astype(pd)

    hd = c.head_dim
    emb_scale = c.d_model ** -0.5
    proj_scale = c.d_model ** -0.5
    out_scale = (2 * c.n_layers * c.d_model) ** -0.5  # GPT-2-style depth scaling

    def log_uniform(kk, shape, low, high):
        return jnp.exp(jax.random.uniform(kk, shape, jnp.float32) * (math.log(high) - math.log(low)) + math.log(low))

    def inv_softplus(dt):
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(pd)

    def mixer_params(mixer, n, k):
        """One stack's mixer leaves, `n` layers, keys drawn from `k` in a fixed order."""
        if mixer == "attention":
            attn = {
                "wq": norm_init(next(k), (n, c.d_model, c.n_heads, hd), proj_scale),
                "wk": norm_init(next(k), (n, c.d_model, c.n_kv_heads, hd), proj_scale),
                "wv": norm_init(next(k), (n, c.d_model, c.n_kv_heads, hd), proj_scale),
                "wo": norm_init(next(k), (n, c.n_heads, hd, c.d_model), out_scale),
            }
            if c.qk_norm:
                attn["q_norm"] = jnp.ones((n, c.n_heads, hd), pd)
                attn["k_norm"] = jnp.ones((n, c.n_kv_heads, hd), pd)
            return {"attn": attn}
        if mixer == "mamba":
            # Mamba-2's own initial values (arXiv:2405.21060; `mamba_ssm`):
            # A = -(1..heads), D = 1, and a step dt = softplus(dt_bias) drawn
            # log-uniform in [1e-3, 1e-1] (dt_bias is its inverse softplus).
            heads, inner = c.ssm_heads, c.ssm_heads * c.ssm_head_dim
            in_proj = norm_init(next(k), (n, c.d_model, 2 * inner + 2 * c.ssm_state + heads), proj_scale)
            conv_w = norm_init(next(k), (n, c._ssm_conv_channels, c.ssm_conv), c.ssm_conv ** -0.5)
            dt = log_uniform(next(k), (n, heads), 1e-3, 1e-1)
            return {"ssm": {
                "in_proj": in_proj,
                "conv_w": conv_w,
                "conv_b": jnp.zeros((n, c._ssm_conv_channels), pd),
                "dt_bias": inv_softplus(dt),
                "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, heads + 1, dtype=jnp.float32)), (n, heads)).astype(pd),
                "D": jnp.ones((n, heads), pd),
                "norm": jnp.ones((n, inner), pd),
                "out_proj": norm_init(next(k), (n, inner, c.d_model), out_scale),
            }}
        if mixer == "kda":
            # The published kernels' initial values (`fla.layers.kda`): A drawn
            # uniform in [1, 16] per head, dt = softplus(dt_bias) log-uniform
            # in [1e-3, 1e-1] per channel; the convolutions as Mamba-2's here.
            heads, dim = c.kda_heads, c.kda_head_dim
            inner = heads * dim
            return {"kda": {
                "wqkv": norm_init(next(k), (n, c.d_model, 3 * inner), proj_scale),
                "conv_w": norm_init(next(k), (n, 3 * inner, c.kda_conv), c.kda_conv ** -0.5),
                "f_down": norm_init(next(k), (n, c.d_model, dim), proj_scale),
                "f_up": norm_init(next(k), (n, dim, inner), dim ** -0.5),
                "g_down": norm_init(next(k), (n, c.d_model, dim), proj_scale),
                "g_up": norm_init(next(k), (n, dim, inner), dim ** -0.5),
                "w_beta": norm_init(next(k), (n, c.d_model, heads), proj_scale),
                "A_log": jnp.log(jax.random.uniform(next(k), (n, heads), jnp.float32, 1.0, 16.0)).astype(pd),
                "dt_bias": inv_softplus(log_uniform(next(k), (n, inner), 1e-3, 1e-1)),
                "norm": jnp.ones((n, dim), pd),
                "wo": norm_init(next(k), (n, inner, c.d_model), out_scale),
            }}
        if mixer == "s6":
            # Mamba's own initial values (arXiv:2312.00752; `mamba_ssm`): A =
            # -(1..N) in every channel, D = 1, a step dt = softplus(dt_bias)
            # drawn log-uniform in [1e-3, 1e-1]; the convolution as Mamba-2's here.
            inner, rank, state = c.s6_inner, c.dt_rank, c.s6_state
            return {"s6": {
                "in_proj": norm_init(next(k), (n, c.d_model, 2 * inner), proj_scale),
                "conv_w": norm_init(next(k), (n, inner, c.s6_conv), c.s6_conv ** -0.5),
                "conv_b": jnp.zeros((n, inner), pd),
                "x_proj": norm_init(next(k), (n, inner, rank + 2 * state), inner ** -0.5),
                "dt_proj": norm_init(next(k), (n, rank, inner), rank ** -0.5),
                "dt_bias": inv_softplus(log_uniform(next(k), (n, inner), 1e-3, 1e-1)),
                "A_log": jnp.broadcast_to(
                    jnp.log(jnp.arange(1, state + 1, dtype=jnp.float32)), (n, inner, state)).astype(pd),
                "D": jnp.ones((n, inner), pd),
                "out_proj": norm_init(next(k), (n, inner, c.d_model), out_scale),
            }}
        if mixer == "gmu":
            return {"gmu": {
                "w1": norm_init(next(k), (n, c.d_model, c.s6_inner), proj_scale),
                "w2": norm_init(next(k), (n, c.s6_inner, c.d_model), out_scale),
            }}
        if mixer in ("diff_attention", "diff_cross"):
            # The four lambda vectors normal * 0.1 (arXiv:2410.05258), the norm's scale 1, biases 0.
            q_wide = c.n_heads * hd
            first = "qkv" if mixer == "diff_attention" else "q"
            wide = q_wide + (2 * c.n_kv_heads * hd if mixer == "diff_attention" else 0)
            diff = {
                "w" + first: norm_init(next(k), (n, c.d_model, wide), proj_scale),
                "wo": norm_init(next(k), (n, q_wide, c.d_model), out_scale),
                **{name: norm_init(next(k), (n, hd), 0.1) for name in _LAMBDAS},
                "subln": jnp.ones((n, 2 * hd), pd),
            }
            if c.attn_bias:
                diff["b" + first] = jnp.zeros((n, wide), pd)
                diff["bo"] = jnp.zeros((n, c.d_model), pd)
            return {"diff": diff}
        qk, rank = c.qk_nope_head_dim + c.qk_rope_head_dim, c.kv_lora_rank
        return {"mla": {
            "wq": norm_init(next(k), (n, c.d_model, c.n_heads, qk), proj_scale),
            "w_kva": norm_init(next(k), (n, c.d_model, rank + c.qk_rope_head_dim), proj_scale),
            "kv_norm": jnp.ones((n, rank), pd),
            "w_kvb": norm_init(next(k), (n, rank, c.n_heads, c.qk_nope_head_dim + c.v_head_dim), rank ** -0.5),
            "wo": norm_init(next(k), (n, c.n_heads, c.v_head_dim, c.d_model), out_scale),
        }}

    def stack_params(mixer, ffn, n, k):
        mixed = mixer_params(mixer, n, k)
        if ffn == "experts":
            mlp = init_moe_params(c, next(k), leading=(n,), out_scale=out_scale)
        else:
            mlp = {
                "w_gate": norm_init(next(k), (n, c.d_model, c.d_ff), proj_scale),
                "w_up": norm_init(next(k), (n, c.d_model, c.d_ff), proj_scale),
                "w_down": norm_init(next(k), (n, c.d_ff, c.d_model), out_scale),
            }
        norms = {"ln1": jnp.ones((n, c.d_model), pd), "ln2": jnp.ones((n, c.d_model), pd)}
        if c.norm_kind == "layer":
            norms.update(ln1_b=jnp.zeros((n, c.d_model), pd), ln2_b=jnp.zeros((n, c.d_model), pd))
        return {**mixed, "mlp": mlp, **norms}

    # The embedding, the stack `layers` (attention with its one kind of FFN),
    # the head and the stack `mamba_layers` draw their keys from ONE sequence
    # in this order, whatever the model has, and every other stack from a
    # sequence of its own: a model's weights for a seed do not move when a
    # kind of layer is added here.
    stacks = c.stacks()
    k = iter(jax.random.split(key, 16))
    params = {"embed": {"tokens": norm_init(next(k), (c.vocab_size, c.d_model), emb_scale)}}
    _, ffn, n = stacks.get("layers", ("attention", "dense" if c.n_experts is None else "experts", 0))
    first = stack_params("attention", ffn, n, k)
    if n:
        params["layers"] = first
    params["final_norm"] = jnp.ones((c.d_model,), pd)
    if c.norm_kind == "layer":
        params["final_norm_b"] = jnp.zeros((c.d_model,), pd)
    if not c.tie_embeddings:
        params["lm_head"] = norm_init(next(k), (c.d_model, c.vocab_size), emb_scale)
    for i, (name, (mixer, ffn, n)) in enumerate(stacks.items()):
        if name == "mamba_layers":
            params[name] = stack_params(mixer, ffn, n, k)
        elif name != "layers":
            params[name] = stack_params(mixer, ffn, n, iter(jax.random.split(jax.random.fold_in(key, i + 1), 16)))
    return params


def rms_norm(x: jax.Array, weight: jax.Array, eps: float, axis=-1) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=axis, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight.astype(x.dtype)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array, eps: float) -> jax.Array:
    """LayerNorm over the last axis: statistics in float32, the scale and the
    bias applied in x's dtype, as `rms_norm` applies its scale."""
    xf = x.astype(jnp.float32)
    centred = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(centred), axis=-1, keepdims=True)
    return (centred * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight.astype(x.dtype) + bias.astype(x.dtype)


def _norm(config: TransformerConfig, x: jax.Array, params: Dict, name: str) -> jax.Array:
    """The stream's norm called `name` in `params`, of the configured kind."""
    if config.norm_kind == "layer":
        return layer_norm(x, params[name], params[name + "_b"], config.norm_eps)
    return rms_norm(x, params[name], config.norm_eps)


def _fitting_axis(axis, mesh, dim: int) -> Optional[str]:
    """Resolve a rules entry to a single mesh axis name that divides dim."""
    if axis is None or mesh is None:
        return None
    if isinstance(axis, tuple):
        axis = axis[0] if axis else None
    if axis not in mesh.axis_names:
        return None
    return axis if dim % mesh.shape[axis] == 0 and mesh.shape[axis] > 1 else None


def _ring_axis(rules: Optional[Rules], mesh, q: jax.Array) -> Optional[str]:
    """The mesh axis to run ring attention over, or None for local attention.

    Non-None iff the strategy shards act_seq onto a real (>1) mesh axis that
    divides the sequence length — exactly the case where plain attention
    would silently all-gather the sequence."""
    if rules is None:
        return None
    return _fitting_axis(rules.get("act_seq"), mesh, q.shape[1])


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


def _layer(
    x: jax.Array,
    layer_params: Dict,
    positions: jax.Array,
    config: TransformerConfig,
    rules: Optional[Rules],
    mesh=None,
    ffn: Optional[str] = None,
    window: Optional[int] = None,
):
    """One attention layer: (x, this layer's router statistics; None when the
    FFN is dense).  `ffn` is the layer's kind of FFN (None: what the
    configuration's every layer has); `window` its causal window (None: full
    causal), which the sequence-parallel ring does not take."""
    c = config
    constrain = _constrainer(rules, mesh)
    dt = c.dtype
    from jax.ad_checkpoint import checkpoint_name

    # The scopes name each region in the compiled step's op metadata, which
    # is what a device trace can tell fusions apart by (PERF.md section 3).
    with jax.named_scope("layer/attn_proj"):
        h = _norm(c, x, layer_params, "ln1")
        q = jnp.einsum("bse,ehd->bshd", h, layer_params["attn"]["wq"].astype(dt))
        kk = jnp.einsum("bse,ehd->bshd", h, layer_params["attn"]["wk"].astype(dt))
        vv = jnp.einsum("bse,ehd->bshd", h, layer_params["attn"]["wv"].astype(dt))
        q = constrain(q, ("act_batch", "act_seq", "act_heads", "act_head_dim"))
        kk = constrain(kk, ("act_batch", "act_seq", "act_kv_heads", "act_head_dim"))
        if c.qk_norm:
            # over the WHOLE projection: heads * head_dim is one vector per position
            q = rms_norm(q, layer_params["attn"]["q_norm"], c.norm_eps, axis=(-2, -1))
            kk = rms_norm(kk, layer_params["attn"]["k_norm"], c.norm_eps, axis=(-2, -1))
        if c.rope_theta is not None:
            q = apply_rope(q, positions, theta=c.rope_theta)
            kk = apply_rope(kk, positions, theta=c.rope_theta)
        q = checkpoint_name(q, "q")
        kk = checkpoint_name(kk, "k")
        vv = checkpoint_name(vv, "v")
    batch_axes = head_ax = None
    if rules is not None:
        batch_axes = rules.get("act_batch")
        head_ax = _fitting_axis(rules.get("act_heads"), mesh, q.shape[2])
        if head_ax is not None and kk.shape[2] % mesh.shape[head_ax] != 0:
            head_ax = None  # GQA kv heads don't divide: replicate heads
    ring_axis = _ring_axis(rules, mesh, q)
    with jax.named_scope("layer/attn_core"):
        if ring_axis is not None:
            # Sequence parallelism: activations are seq-sharded, so full
            # attention would force XLA to all-gather the sequence.  Ring
            # attention keeps KV rotating over ICI instead
            # (ops/ring_attention.py; SURVEY.md §5.7 — novel, no reference
            # counterpart).
            from ray_tpu.ops.ring_attention import ring_attention_sharded

            if c.attention_scale is not None:
                raise ValueError("ring attention takes no attention_scale")
            if window is not None:
                raise ValueError("ring attention takes no window (layer_windows)")
            attn = ring_attention_sharded(
                q, kk, vv, mesh,
                seq_axis=ring_axis,
                batch_axes=batch_axes,
                head_axis=head_ax,
                causal=True,
            )
        else:
            attn = dot_product_attention(
                q, kk, vv, causal=True, scale=c.attention_scale, impl=c.attention_impl,
                mesh=mesh if rules is not None else None,
                batch_axes=batch_axes, head_axis=head_ax,
                **({} if window is None else {"window": window}),
            )
    with jax.named_scope("layer/attn_proj"):
        attn_out = jnp.einsum("bshd,hde->bse", attn, layer_params["attn"]["wo"].astype(dt))
        x = x + _scaled(c, constrain(attn_out, ("act_batch", "act_seq", "act_embed")))
    return _ffn_half(x, layer_params, c, constrain, rules, mesh, ffn)


def _constrainer(rules: Optional[Rules], mesh):
    """`(activation, logical axes) -> activation`, placed as the rules say
    (the identity without rules)."""
    if rules is None:
        return lambda h, axes: h
    return lambda h, axes: with_logical_constraint(h, axes, rules, mesh)


def _scaled(config: TransformerConfig, block_out: jax.Array) -> jax.Array:
    """A block's output as it joins the residual stream."""
    if config.residual_multiplier == 1.0:
        return block_out
    return block_out * jnp.asarray(config.residual_multiplier, block_out.dtype)


def _ffn_half(x, layer_params, config, constrain, rules, mesh, ffn=None):
    """The second half of every layer, whatever its mixer: (x + FFN(ln2(x)),
    router statistics or None).  `ffn`: "dense" or "experts" (None: experts
    where the configuration has any)."""
    c, dt = config, config.dtype
    router_stats = None
    if ffn is None:
        ffn = "dense" if c.n_experts is None else "experts"
    with jax.named_scope("layer/mlp"):
        h = _norm(c, x, layer_params, "ln2")
        if ffn == "experts":
            down, router_stats = moe_ffn(layer_params["mlp"], h, c, rules=rules, mesh=mesh)
        else:
            mlp = layer_params["mlp"]
            down = _dense_ffn(
                constrain, h, mlp["w_gate"].astype(dt), mlp["w_up"].astype(dt), mlp["w_down"].astype(dt)
            )
        x = x + _scaled(c, constrain(down, ("act_batch", "act_seq", "act_embed")))
    return x, router_stats


def _mamba_layer(
    x: jax.Array,
    layer_params: Dict,
    positions: jax.Array,
    config: TransformerConfig,
    rules: Optional[Rules],
    mesh=None,
    ffn: Optional[str] = None,
):
    """One Mamba-2 layer (module docstring): (x, router statistics or None).  Its regions sit
    INSIDE the two mixer scopes every layer has, so `layer/attn_proj` stays
    "the mixer's projections" and `layer/attn_core` "the mixer's core":
    `ssm/proj` (ln1, in_proj, out_proj, the residual add), `ssm/conv`
    (convolution + SiLU, one unit with its own backward: on TPU the kernels
    `ssm_conv_fwd` / `ssm_conv_bwd`; softplus; the gated RMSNorm, float32 over
    the scan's bf16 output), `ssm/scan` (the SSD, named in `ops/ssm.py`).

    Two residuals carry a `checkpoint_name`, for `_remat_policy` to save:
    `SSM_IN_PROJ`, the one array `in_proj` gives, and `SSM_MIXED`, the
    residual stream after `out_proj`.  With both kept the backward runs
    neither projection's forward again: `in_proj`'s consumers start from the
    saved array, and `out_proj`'s forward fed only the FFN half, which starts
    from the saved stream (its backward needs `y`, so convolution, scan and
    gated norm still run again; `ln1` too, for `in_proj`'s weight gradient)."""
    from jax.ad_checkpoint import checkpoint_name

    del positions  # a recurrence needs none
    c, dt, ssm = config, config.dtype, layer_params["ssm"]
    constrain = _constrainer(rules, mesh)
    sharded = {} if rules is None else dict(mesh=mesh, batch_axes=rules.get("act_batch"))
    heads, inner, n = c.ssm_heads, c.ssm_heads * c.ssm_head_dim, c.ssm_state
    with jax.named_scope("layer/attn_proj"):
        with jax.named_scope("ssm/proj"):
            h = _norm(c, x, layer_params, "ln1")
            zxbcdt = jnp.einsum("bse,ef->bsf", h, ssm["in_proj"].astype(dt))
            zxbcdt = checkpoint_name(zxbcdt, SSM_IN_PROJ)
            z, xbc, step = jnp.split(zxbcdt, [inner, 2 * inner + 2 * n], axis=-1)
        with jax.named_scope("ssm/conv"):
            xbc = causal_conv1d_silu(xbc, ssm["conv_w"], ssm["conv_b"], **sharded)
            step = jax.nn.softplus(step.astype(jnp.float32) + ssm["dt_bias"].astype(jnp.float32))
            xs, b_in, c_out = jnp.split(xbc, [inner, inner + n], axis=-1)
    with jax.named_scope("layer/attn_core"):
        y = ssd_chunked(
            xs.reshape(*xs.shape[:2], heads, c.ssm_head_dim), step,
            -jnp.exp(ssm["A_log"].astype(jnp.float32)), b_in, c_out, ssm["D"],
        )
    with jax.named_scope("layer/attn_proj"):
        with jax.named_scope("ssm/conv"):
            y = y.reshape(*y.shape[:2], inner)
            gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
            y = rms_norm(gated, ssm["norm"], c.norm_eps).astype(dt)  # one group: over all of d_inner
        with jax.named_scope("ssm/proj"):
            out = jnp.einsum("bsf,fe->bse", y, ssm["out_proj"].astype(dt))
            x = x + _scaled(c, constrain(out, ("act_batch", "act_seq", "act_embed")))
            x = checkpoint_name(x, SSM_MIXED)
    return _ffn_half(x, layer_params, c, constrain, rules, mesh, ffn)


def _l2_normed(x: jax.Array, scale: float = 1.0, eps: float = 1e-6) -> jax.Array:
    """x / |x|_2 over the last axis (a head), times `scale`, in float32."""
    xf = x.astype(jnp.float32)
    return xf * (jax.lax.rsqrt(jnp.sum(jnp.square(xf), axis=-1, keepdims=True) + eps) * scale)


def _kda_layer(
    x: jax.Array,
    layer_params: Dict,
    positions: jax.Array,
    config: TransformerConfig,
    rules: Optional[Rules],
    mesh=None,
    ffn: Optional[str] = None,
):
    """One Kimi Delta Attention layer (module docstring): (x, router
    statistics or None).  Its regions sit inside the two mixer scopes every
    layer has, as a Mamba-2 layer's do: `kda/proj` (ln1, the fused q|k|v
    projection, both low-rank gates, beta, `wo`, the residual add), `kda/conv`
    (the convolutions + SiLU in one call, on TPU Mamba-2's kernels; the L2
    norms, the decay's activation, the gated per-head RMSNorm), `kda/scan`
    (the chunked recurrence, named in `ops/kda.py`).

    Three residuals carry a `checkpoint_name`, for `_remat_policy` to save:
    `KDA_QKV`, the fused projection's one array; `KDA_LOW`, the narrow halves
    of the two gates with beta's logits (d -> 2 * head + heads, one array);
    `KDA_MIXED`, the residual stream after `wo`.  With them kept the backward
    runs none of the d-wide projections again (the gates' narrow-to-wide
    halves, the convolution, the recurrence and the gated norm run again)."""
    from jax.ad_checkpoint import checkpoint_name

    del positions  # the decay carries position
    c, dt, p = config, config.dtype, layer_params["kda"]
    f32 = jnp.float32
    constrain = _constrainer(rules, mesh)
    sharded = {} if rules is None else dict(mesh=mesh, batch_axes=rules.get("act_batch"))
    heads, dim = c.kda_heads, c.kda_head_dim
    inner = heads * dim
    with jax.named_scope("layer/attn_proj"):
        with jax.named_scope("kda/proj"):
            h = _norm(c, x, layer_params, "ln1")
            qkv = checkpoint_name(jnp.einsum("bse,ef->bsf", h, p["wqkv"].astype(dt)), KDA_QKV)
            narrow = jnp.concatenate([p["f_down"], p["g_down"], p["w_beta"]], axis=-1).astype(dt)
            low = checkpoint_name(jnp.einsum("bse,ef->bsf", h, narrow), KDA_LOW)
            decay_in = jnp.einsum("bsr,rf->bsf", low[..., :dim], p["f_up"].astype(dt))
            gate_in = jnp.einsum("bsr,rf->bsf", low[..., dim: 2 * dim], p["g_up"].astype(dt))
        with jax.named_scope("kda/conv"):
            qkv = causal_conv1d_silu(qkv, p["conv_w"], jnp.zeros((3 * inner,), p["conv_w"].dtype), **sharded)
            q, k, v = (a.reshape(*a.shape[:2], heads, dim) for a in jnp.split(qkv, 3, axis=-1))
            q, k = _l2_normed(q, dim ** -0.5), _l2_normed(k)
            step = jax.nn.softplus(decay_in.astype(f32) + p["dt_bias"].astype(f32))
            g = step.reshape(*step.shape[:2], heads, dim) * -jnp.exp(p["A_log"].astype(f32))[:, None]
            beta = jax.nn.sigmoid(low[..., 2 * dim:].astype(f32))
    with jax.named_scope("layer/attn_core"):
        o = kda_chunked(q, k, v, g, beta, **sharded)
    with jax.named_scope("layer/attn_proj"):
        with jax.named_scope("kda/conv"):
            gate = jax.nn.sigmoid(gate_in.astype(f32)).reshape(o.shape)
            o = (rms_norm(o, p["norm"], c.norm_eps) * gate).astype(dt)  # over each head's own channels
        with jax.named_scope("kda/proj"):
            out = jnp.einsum("bsf,fe->bse", o.reshape(*o.shape[:2], inner), p["wo"].astype(dt))
            x = x + _scaled(c, constrain(out, ("act_batch", "act_seq", "act_embed")))
            x = checkpoint_name(x, KDA_MIXED)
    return _ffn_half(x, layer_params, c, constrain, rules, mesh, ffn)


def _mla_layer(
    x: jax.Array,
    layer_params: Dict,
    positions: jax.Array,
    config: TransformerConfig,
    rules: Optional[Rules],
    mesh=None,
    ffn: Optional[str] = None,
):
    """One latent-attention layer without rotary embedding (module
    docstring): (x, router statistics or None).  `mla/proj` names its
    projections inside `layer/attn_proj`; the core is `dot_product_attention`
    with q/k heads of `nope + rope` and v heads of `v_head_dim` (the flash
    kernels take the two sizes).  q, k, v carry attention's own
    `checkpoint_name`s and the residual stream after `wo` `MLA_MIXED`."""
    from jax.ad_checkpoint import checkpoint_name

    del positions  # no rotary embedding
    c, dt, p = config, config.dtype, layer_params["mla"]
    constrain = _constrainer(rules, mesh)
    rank, nope = c.kv_lora_rank, c.qk_nope_head_dim
    with jax.named_scope("layer/attn_proj"), jax.named_scope("mla/proj"):
        h = _norm(c, x, layer_params, "ln1")
        q = jnp.einsum("bse,ehd->bshd", h, p["wq"].astype(dt))
        latent = jnp.einsum("bse,ef->bsf", h, p["w_kva"].astype(dt))
        kv = jnp.einsum("bsr,rhd->bshd", rms_norm(latent[..., :rank], p["kv_norm"], c.norm_eps),
                        p["w_kvb"].astype(dt))
        k_pe = jnp.broadcast_to(latent[..., None, rank:], (*kv.shape[:3], c.qk_rope_head_dim))
        kk = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
        q = constrain(q, ("act_batch", "act_seq", "act_heads", "act_head_dim"))
        kk = constrain(kk, ("act_batch", "act_seq", "act_heads", "act_head_dim"))
        q = checkpoint_name(q, "q")
        kk = checkpoint_name(kk, "k")
        vv = checkpoint_name(kv[..., nope:], "v")
    batch_axes = head_ax = None
    if rules is not None:
        batch_axes = rules.get("act_batch")
        head_ax = _fitting_axis(rules.get("act_heads"), mesh, q.shape[2])
    if _ring_axis(rules, mesh, q) is not None:
        raise ValueError("an mla layer runs local attention only (no sequence-parallel ring)")
    with jax.named_scope("layer/attn_core"):
        attn = dot_product_attention(
            q, kk, vv, causal=True, scale=q.shape[-1] ** -0.5, impl=c.attention_impl,
            mesh=mesh if rules is not None else None, batch_axes=batch_axes, head_axis=head_ax,
        )
    with jax.named_scope("layer/attn_proj"), jax.named_scope("mla/proj"):
        out = jnp.einsum("bshd,hde->bse", attn, p["wo"].astype(dt))
        x = x + _scaled(c, constrain(out, ("act_batch", "act_seq", "act_embed")))
        x = checkpoint_name(x, MLA_MIXED)
    return _ffn_half(x, layer_params, c, constrain, rules, mesh, ffn)


def _s6_layer(x, layer_params, data, shared, *, positions, config, rules, mesh=None, ffn=None, window=None, emit=False):
    """One Mamba-1 layer (module docstring): (x, router statistics or None,
    what it hands on).  Its regions sit inside the two mixer scopes every
    layer has: `s6/proj` (ln1, `W_in`, `W_x`, `W_dt` with the softplus, `W_out`,
    the residual add), `s6/conv` (convolution + SiLU, on TPU Mamba-2's kernels;
    the gate `y * silu(z)`), `s6/scan` (named in `ops/selective_scan.py`).
    With `emit` the scan's output goes on as `MEMORY`.

    `S6_IN_PROJ` (`W_in`'s one array) and `S6_MIXED` (the stream after
    `W_out`) carry a `checkpoint_name`: with both kept no d-wide projection
    runs again (`W_x`, `W_dt`, the convolution, the scan and the gate do)."""
    from jax.ad_checkpoint import checkpoint_name

    del positions, data, shared, window  # a recurrence needs none of them
    c, dt, p = config, config.dtype, layer_params["s6"]
    f32 = jnp.float32
    constrain = _constrainer(rules, mesh)
    sharded = {} if rules is None else dict(mesh=mesh, batch_axes=rules.get("act_batch"))
    rank, n = c.dt_rank, c.s6_state
    with jax.named_scope("layer/attn_proj"):
        with jax.named_scope("s6/proj"):
            h = _norm(c, x, layer_params, "ln1")
            xz = checkpoint_name(jnp.einsum("bse,ef->bsf", h, p["in_proj"].astype(dt)), S6_IN_PROJ)
            xs, z = jnp.split(xz, 2, axis=-1)
        with jax.named_scope("s6/conv"):
            xs = causal_conv1d_silu(xs, p["conv_w"], p["conv_b"], **sharded)
        with jax.named_scope("s6/proj"):
            low = jnp.einsum("bsf,fr->bsr", xs, p["x_proj"].astype(dt))
            step = jnp.einsum("bsr,rf->bsf", low[..., :rank], p["dt_proj"].astype(dt), preferred_element_type=f32)
            step = jax.nn.softplus(step + p["dt_bias"].astype(f32))
    with jax.named_scope("layer/attn_core"):
        y = selective_scan(xs, step, -jnp.exp(p["A_log"].astype(f32)), low[..., rank: rank + n],
                           low[..., rank + n:], p["D"], **sharded)
    with jax.named_scope("layer/attn_proj"):
        with jax.named_scope("s6/conv"):
            gated = (y.astype(f32) * jax.nn.silu(z.astype(f32))).astype(dt)
        with jax.named_scope("s6/proj"):
            out = jnp.einsum("bsf,fe->bse", gated, p["out_proj"].astype(dt))
            x = x + _scaled(c, constrain(out, ("act_batch", "act_seq", "act_embed")))
            x = checkpoint_name(x, S6_MIXED)
    return (*_ffn_half(x, layer_params, c, constrain, rules, mesh, ffn), {MEMORY: y} if emit else {})


def _gmu_layer(x, layer_params, data, shared, *, positions, config, rules, mesh=None, ffn=None, window=None, emit=False):
    """One Gated Memory Unit (module docstring): the memory an s6 layer
    handed on, gated by this layer's own projection of the stream.  All of it
    is `gmu` inside `layer/attn_proj` (the layer has no core).  `GMU_GATE`
    (`W_1`'s output) and `GMU_MIXED` (the stream after `W_2`) carry a
    `checkpoint_name`."""
    from jax.ad_checkpoint import checkpoint_name

    del positions, data, window, emit
    c, dt, p = config, config.dtype, layer_params["gmu"]
    f32 = jnp.float32
    constrain = _constrainer(rules, mesh)
    with jax.named_scope("layer/attn_proj"), jax.named_scope("gmu"):
        h = _norm(c, x, layer_params, "ln1")
        gate = checkpoint_name(jnp.einsum("bse,ef->bsf", h, p["w1"].astype(dt)), GMU_GATE)
        gated = (shared[MEMORY].astype(f32) * jax.nn.silu(gate.astype(f32))).astype(dt)
        out = jnp.einsum("bsf,fe->bse", gated, p["w2"].astype(dt))
        x = x + _scaled(c, constrain(out, ("act_batch", "act_seq", "act_embed")))
        x = checkpoint_name(x, GMU_MIXED)
    return (*_ffn_half(x, layer_params, c, constrain, rules, mesh, ffn), {})


def diff_head_maps(n_heads: int, n_kv_heads: int) -> Tuple[np.ndarray, np.ndarray]:
    """Differential attention's pairing as two gathers, one entry per q head
    i: the k head its map scores against, `2 * (i // 2 // G) + i % 2`, and
    the PAIR of v heads (one value of twice the width) it averages,
    `i // 2 // G`, with G = n_heads / n_kv_heads (module docstring)."""
    i = np.arange(n_heads)
    kv_pair = i // 2 // (n_heads // n_kv_heads)
    return 2 * kv_pair + i % 2, kv_pair


def _diff_core(q, k, v, p, lambda_init, config, rules, mesh, window):
    """Both softmax maps of every head pair and their combination: q
    [B, S, H, D], k and v [B, S, Hkv, D] -> [B, S, H * D] in the model's dtype.
    One attention call over H maps with q/k heads of D and values of 2 * D
    (the flash kernels' two head sizes), the heads gathered to their pairing
    around it, under `diff/window` or `diff/full`; then `diff/combine`, in
    float32 from the call's output: `a1 - lambda a2`, the RMSNorm over 2 * D,
    the scale `1 - lambda_init`."""
    c, f32 = config, jnp.float32
    b, s, heads, hd = q.shape
    if rules is not None and (_fitting_axis(rules.get("act_heads"), mesh, heads) is not None
                              or _ring_axis(rules, mesh, q) is not None):
        raise ValueError(
            "differential attention ('diff_attention', 'diff_cross') runs with its heads and its "
            "sequence whole: strategy 'tp' and the sequence-parallel ring do not take its pairing")
    k_of, v_of = diff_head_maps(heads, k.shape[2])
    with jax.named_scope("layer/attn_core"):
        with jax.named_scope("diff/full" if window is None else "diff/window"):
            keys = jnp.take(k, k_of, axis=2)
            values = jnp.take(v.reshape(b, s, v.shape[2] // 2, 2 * hd), v_of, axis=2)
            maps = dot_product_attention(
                q, keys, values, causal=True, scale=hd ** -0.5, impl=c.attention_impl,
                mesh=mesh if rules is not None else None,
                batch_axes=None if rules is None else rules.get("act_batch"), head_axis=None,
                **({} if window is None else {"window": window}),
            )
        with jax.named_scope("diff/combine"):
            maps = maps.astype(f32).reshape(b, s, heads // 2, 2, 2 * hd)
            lam = (jnp.exp(jnp.sum(p["lambda_q1"].astype(f32) * p["lambda_k1"].astype(f32)))
                   - jnp.exp(jnp.sum(p["lambda_q2"].astype(f32) * p["lambda_k2"].astype(f32))) + lambda_init)
            o = maps[..., 0, :] - lam * maps[..., 1, :]
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + c.norm_eps)
            o = o * (p["subln"].astype(f32) * (1.0 - lambda_init))
            return o.astype(c.dtype).reshape(b, s, heads * hd)


def _diff_layer(x, layer_params, data, shared, *, positions, config, rules, mesh=None, ffn=None, window=None,
                emit=False, cross=False):
    """One differential attention layer, or with `cross` one differential
    cross-attention layer (module docstring): (x, router statistics or None,
    what it hands on).  `diff/proj` names its projections inside
    `layer/attn_proj`; the core is `_diff_core`.  q (and a self layer's k and
    v) carry attention's own `checkpoint_name`s, the stream after `W_o`
    `DIFF_MIXED`.  With `emit` k and v (after the bias) go on as `SHARED_K`
    and `SHARED_V`; a cross layer reads those."""
    from jax.ad_checkpoint import checkpoint_name

    del positions  # no positional encoding
    c, dt, p = config, config.dtype, layer_params["diff"]
    constrain = _constrainer(rules, mesh)
    hd, q_wide = c.head_dim, c.n_heads * c.head_dim
    handed = {}
    with jax.named_scope("layer/attn_proj"), jax.named_scope("diff/proj"):
        h = _norm(c, x, layer_params, "ln1")
        first = "q" if cross else "qkv"
        proj = jnp.einsum("bse,ef->bsf", h, p["w" + first].astype(dt))
        if c.attn_bias:
            proj = proj + p["b" + first].astype(dt)
        heads_of = lambda a: a.reshape(*a.shape[:2], a.shape[-1] // hd, hd)  # noqa: E731
        q = checkpoint_name(heads_of(proj[..., :q_wide]), "q")
        if cross:
            kk, vv = shared[SHARED_K], shared[SHARED_V]
        else:
            kk, vv = (heads_of(a) for a in jnp.split(proj[..., q_wide:], 2, axis=-1))
            kk, vv = checkpoint_name(kk, "k"), checkpoint_name(vv, "v")
            if emit:
                handed = {SHARED_K: kk, SHARED_V: vv}
    o = _diff_core(q, kk, vv, p, data["lambda_init"], c, rules, mesh, window)
    with jax.named_scope("layer/attn_proj"), jax.named_scope("diff/proj"):
        out = jnp.einsum("bsf,fe->bse", o, p["wo"].astype(dt))
        if c.attn_bias:
            out = out + p["bo"].astype(dt)
        x = x + _scaled(c, constrain(out, ("act_batch", "act_seq", "act_embed")))
        x = checkpoint_name(x, DIFF_MIXED)
    return (*_ffn_half(x, layer_params, c, constrain, rules, mesh, ffn), handed)


_LAYER_FNS = {
    "attention": _layer, "mamba": _mamba_layer, "kda": _kda_layer, "mla": _mla_layer,
    "s6": _s6_layer, "diff_attention": _diff_layer, "gmu": _gmu_layer,
    "diff_cross": functools.partial(_diff_layer, cross=True),
}


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


def _remat_policy(config: TransformerConfig):
    """Validated checkpoint policy for the configured remat granularity
    (shared by the scan and pipeline paths, and by both kinds of layer: a
    name that a layer's kind does not carry matches nothing in it).  It says
    what JAX recomputes; over libtpu's limit XLA's pass may duplicate more
    (see `_dense_ffn`)."""
    # The attention op names its own residuals (ops/attention.py): a policy
    # that keeps the output without the log-sum-exp would still re-run the
    # kernel's forward in the backward pass.
    if config.remat_policy == "attn":
        return jax.checkpoint_policies.save_only_these_names(ATTN_OUT, ATTN_LSE)
    if config.remat_policy == "qkv_attn":
        # No d-wide mixer projection is recomputed: attention's q, k, v
        # (latent and differential attention's too, both maps of the latter
        # in the one output and log-sum-exp); a Mamba-2 layer's two named
        # residuals (`_mamba_layer`); a KDA layer's three (`_kda_layer`); an
        # s6 layer's two (`_s6_layer`); a GMU's two (`_gmu_layer`); the stream
        # behind an MLA or differential layer's output projection.
        return jax.checkpoint_policies.save_only_these_names(
            "q", "k", "v", ATTN_OUT, ATTN_LSE, SSM_IN_PROJ, SSM_MIXED,
            KDA_QKV, KDA_LOW, KDA_MIXED, MLA_MIXED,
            S6_IN_PROJ, S6_MIXED, GMU_GATE, GMU_MIXED, DIFF_MIXED,
        )
    if config.remat_policy is None:
        # Save nothing per layer: the backward re-runs the whole layer, the
        # flash forward included.  The minimum-memory mode.
        return None
    raise ValueError(
        f"unknown remat_policy {config.remat_policy!r}; expected None (save nothing), 'attn' "
        f"(saves {ATTN_OUT!r}, {ATTN_LSE!r}) or 'qkv_attn' (those and 'q', 'k', 'v', "
        f"{SSM_IN_PROJ!r}, {SSM_MIXED!r}, {KDA_QKV!r}, {KDA_LOW!r}, {KDA_MIXED!r}, {MLA_MIXED!r}, "
        f"{S6_IN_PROJ!r}, {S6_MIXED!r}, {GMU_GATE!r}, {GMU_MIXED!r}, {DIFF_MIXED!r})"
    )


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
    if c.n_experts is not None:
        raise ValueError(
            "strategy 'pp' runs dense layers only: the router statistics of "
            "an expert layer do not come out of the pipeline schedule"
        )
    if c.layer_types is not None or c.ffn_types is not None or c.layer_windows is not None:
        raise ValueError(
            "strategy 'pp' runs a homogeneous stack of attention layers only: the "
            "stages of a stack with layer_types (mamba, kda, mla, s6, diff_attention, "
            "gmu, diff_cross) or ffn_types (dense beside experts) would hold unequal "
            "layers, and a value one layer hands to a later one does not cross stages"
        )
    n_stages = mesh.shape[axis]
    per_stage = c.n_layers // n_stages

    stacked = jax.tree_util.tree_map(
        lambda a: a.reshape(n_stages, per_stage, *a.shape[1:]), layer_params
    )

    fsdp_dims = None
    if fsdp_axis is not None and rules is not None:
        layer_axes = param_axes(c)["layers"]

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
    never forms these: `lm.head_cross_entropy` takes the trunk's output.)

    `rules` come with the `mesh` they refer to: ring attention, the pipeline
    schedule and the flash kernel's shard_map are all built from it."""
    x, head, _ = trunk(params, tokens, config, rules=rules, mesh=mesh)
    with jax.named_scope("lm_head"):
        logits = jnp.einsum("bse,ev->bsv", x, head).astype(jnp.float32)
        return _constrainer(rules, mesh)(logits, LOGITS_AXES)


def trunk(
    params: Dict,
    tokens: jax.Array,
    config: TransformerConfig,
    *,
    rules: Optional[Rules] = None,
    mesh=None,
):
    """Everything up to the head's matmul: (the rows that enter the head,
    [B, S, d] in `config.dtype`, normed and divided by `logits_scaling`; the
    head [d, vocab] in `config.dtype`, the embedding table transposed when
    tied; router statistics stacked over the layers, `[L, ...]` each, as
    `moe.router_losses` takes them, None for a dense model)."""
    c = config
    if rules is not None and mesh is None:
        raise ValueError("forward(rules=...) needs the mesh the rules refer to")
    with jax.named_scope("embed"):
        x = params["embed"]["tokens"].astype(c.dtype)[tokens]
        if c.embedding_multiplier != 1.0:
            x = x * jnp.asarray(c.embedding_multiplier, c.dtype)
        if rules is not None:
            x = with_logical_constraint(x, ("act_batch", "act_seq", "act_embed"), rules, mesh)
    positions = jnp.arange(tokens.shape[1])

    # Pipeline parallelism: rules shard the LAYER STACK over the pipeline
    # axis — run the GPipe microbatch schedule instead of a plain scan
    # (each stage device holds n_layers/P layers).
    pp_axis = None
    if rules is not None and rules.get("layers") is not None:
        ax = rules["layers"]
        ax = ax[0] if isinstance(ax, tuple) else ax
        size = mesh.shape[ax] if ax in mesh.axis_names else 1
        if size > 1:
            # Explicit pp intent: misconfigurations are ERRORS, not silent
            # fallbacks — replicated layers instead of pipelining would only
            # surface as OOM/low MFU at scale.
            if c.n_layers % size != 0:
                raise ValueError(
                    f"strategy 'pp': n_layers={c.n_layers} not divisible by "
                    f"pipeline axis size {size}"
                )
            sharded_params = [
                k for k in ("embed", "heads", "kv_heads", "head_dim", "mlp",
                            "vocab", "expert")
                if rules.get(k) is not None
            ]
            # fsdp-at-rest composes with pp (strategy "pp_fsdp"): the
            # sharded param axes are all-gathered per stage per step inside
            # the schedule.  TP-style axes (which also shard activations)
            # do NOT — gathering them would silently undo the tensor split.
            act_axes = set()
            for k, v in rules.items():
                if k.startswith("act_") and k != "act_batch" and v is not None:
                    act_axes.update(v if isinstance(v, tuple) else (v,))
            pp_fsdp_axes = set()
            bad = []
            for k in sharded_params:
                v = rules[k]
                if isinstance(v, tuple) or v == ax or v in act_axes:
                    bad.append(k)
                else:
                    pp_fsdp_axes.add(v)
            if bad:
                raise ValueError(
                    "strategy 'pp' composes with data sharding and ONE "
                    "fsdp-at-rest param axis (strategy 'pp_fsdp'); param "
                    f"dims {bad} shard over activation/tensor axes the "
                    "pipeline schedule cannot gather away"
                )
            if len(pp_fsdp_axes) > 1:
                raise ValueError(
                    "strategy 'pp' composes with at most ONE fsdp-at-rest "
                    f"param axis, got {sorted(pp_fsdp_axes)} across "
                    f"{sharded_params}"
                )
            pp_axis = ax
            pp_fsdp_axis = pp_fsdp_axes.pop() if pp_fsdp_axes else None
    # `layers` names what the loop over the stack itself costs (each layer's
    # weights sliced out of the stack, gradients and residuals stacked back);
    # the regions of `_layer` are named inside it.
    router_stats = None
    with jax.named_scope("layers"):
        if pp_axis is not None:
            x = _run_layers_pipelined(
                params.get("layers"), x, positions, c, mesh, pp_axis,  # None: a stack it refuses by name
                rules=rules, fsdp_axis=pp_fsdp_axis,
            )
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
            shared = {}  # what layers have handed on so far (`CROSS_KINDS`), by name
            for (mixer, ffn, _, count), start in zip(runs, c.run_starts()):
                window, emits = c.layer_variant(start)
                static = {} if window is None else {"window": window}
                if mixer in CROSS_KINDS:
                    static["emit"] = emits
                layer_fn = functools.partial(
                    _LAYER_FNS[mixer], positions=positions, config=c, rules=rules, mesh=mesh, ffn=ffn, **static
                )
                if c.remat:
                    layer_fn = jax.checkpoint(layer_fn, policy=_remat_policy(c))
                if mixer in CROSS_KINDS:
                    # The layer takes its per-layer data and what was handed
                    # on as ARGUMENTS (inputs of its checkpoint, constants of
                    # the scan, their cotangents summed over the readers) and
                    # returns what it hands on (a run that does is one layer:
                    # `layer_variant`).
                    def body(carry, xs, layer_fn=layer_fn, shared=dict(shared)):
                        carry, stats, handed = layer_fn(carry, *xs, shared)
                        return carry, (stats, handed)

                    data = {"lambda_init": jnp.asarray(c.lambda_inits()[start: start + count], jnp.float32)}
                    x, (run_stats, handed) = jax.lax.scan(body, x, (next(stacks[mixer, ffn]), data))
                    if emits:
                        shared.update({name: value[0] for name, value in handed.items()})
                else:
                    x, run_stats = jax.lax.scan(layer_fn, x, next(stacks[mixer, ffn]))
                if run_stats is not None:
                    per_run.append(run_stats)
            # the expert layers' statistics, [expert layers, ...] in the stack's order
            if len(per_run) == 1:
                router_stats = per_run[0]
            elif per_run:
                router_stats = jax.tree_util.tree_map(lambda *a: jnp.concatenate(a, axis=0), *per_run)
    with jax.named_scope("final_norm"):
        x = _norm(c, x, params, "final_norm")
    with jax.named_scope("lm_head"):
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
    return x, head, router_stats
