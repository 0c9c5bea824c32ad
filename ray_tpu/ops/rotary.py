"""Rotary position embeddings (RoPE). Pure function, fuses into the
surrounding attention projections under XLA.

A rope is `Rope`: a base `theta`, optionally YaRN's rescaling of the
frequencies (arXiv:2309.00071, as Hugging Face's `_compute_yarn_parameters`
constructs it), and a factor on `cos` and `sin`.  Pair i of a head rotates
position p by the angle `p * inv_freq[i]`:

- default: `inv_freq[i] = theta^(-2i / D)`.
- YaRN (`factor` s, `original_max_position` L, `beta_fast`, `beta_slow`): the
  pair that makes r rotations over L positions is at
  `dim(r) = D ln(L / (2 pi r)) / (2 ln theta)`; `low = floor(dim(beta_fast))`,
  `high = ceil(dim(beta_slow))`, both clamped to the head;
  `ramp[i] = clip((i - low) / (high - low), 0, 1)`;
  `inv_freq[i] = theta^(-2i / D) * ((1 - ramp[i]) + ramp[i] / s)`: pairs up
  to `low` keep their frequency, pairs from `high` on are divided by s, linear
  between.  `cos` and `sin` are multiplied by `attention_factor` (None:
  `0.1 ln s + 1`).

A rope may rotate a PART of a head (`rotary_dim` R < D, a published
`partial_rotary_factor`: Qwen3-Next rotates 64 of 256): the first R dims are
rotated as a head of size R would be (`inv_freq[i] = theta^(-2i / R)`, R / 2
pairs), the other D - R pass unrotated.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Rope:
    """One rotary embedding: hashable, so static in a layer (module docstring)."""
    theta: float = 10000.0
    factor: Optional[float] = None  # YaRN's s; None = the default rope, and nothing below is read
    original_max_position: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None
    rotary_dim: Optional[int] = None  # the rotated width, the FIRST dims of a head; None = the whole head

    def __post_init__(self):
        if self.rotary_dim is not None and (self.rotary_dim <= 0 or self.rotary_dim % 2):
            raise ValueError(f"a rope rotates pairs: rotary_dim is even and positive, got {self.rotary_dim}")
        if self.factor is not None and not (self.factor >= 1.0 and self.original_max_position > 0):
            raise ValueError(f"a YaRN rope needs factor >= 1 and original_max_position > 0, got {self}")

    @property
    def scale(self) -> float:
        """What multiplies cos and sin (1.0: nothing does)."""
        if self.factor is None:
            return 1.0
        return 0.1 * math.log(self.factor) + 1.0 if self.attention_factor is None else self.attention_factor

    def correction_range(self, head_dim: int):
        """YaRN's (low, high): the pairs between which the frequencies go from kept to divided."""
        def dim_of(rotations: float) -> float:
            return head_dim * math.log(self.original_max_position / (rotations * 2 * math.pi)) / (2 * math.log(self.theta))

        return max(math.floor(dim_of(self.beta_fast)), 0), min(math.ceil(dim_of(self.beta_slow)), head_dim - 1)

    def inv_freq(self, head_dim: int) -> jax.Array:
        """Inverse frequencies for each rotated pair. [head_dim // 2], f32."""
        if self.factor is None:
            return rope_frequencies(head_dim, self.theta)
        low, high = self.correction_range(head_dim)
        pairs = np.arange(head_dim // 2, dtype=np.float64)
        ramp = np.clip((pairs - low) / max(high - low, 1e-3), 0.0, 1.0)
        kept = float(self.theta) ** (-2.0 * pairs / head_dim)
        return jnp.asarray(kept * ((1.0 - ramp) + ramp / self.factor), jnp.float32)


def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jax.Array:
    """Inverse frequencies for each rotated pair. [head_dim // 2], f32."""
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(x: jax.Array, positions: jax.Array, rope: Rope) -> jax.Array:
    """Rotate [..., seq, heads, head_dim] by absolute positions [seq] (or
    broadcastable [..., seq]) with `rope`: the whole head, or its first
    `rope.rotary_dim` dims with the rest passed on as they are."""
    if rope.rotary_dim is not None and rope.rotary_dim != x.shape[-1]:
        if rope.rotary_dim > x.shape[-1]:
            raise ValueError(f"rotary_dim {rope.rotary_dim} is wider than the head ({x.shape[-1]})")
        whole = dataclasses.replace(rope, rotary_dim=None)
        rotated = apply_rope(x[..., :rope.rotary_dim], positions, whole)
        return jnp.concatenate([rotated, x[..., rope.rotary_dim:]], axis=-1)
    freqs = rope.inv_freq(x.shape[-1])
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, D/2]
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    if rope.scale != 1.0:
        cos, sin = cos * rope.scale, sin * rope.scale
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    r1 = x1 * cos - x2 * sin
    r2 = x1 * sin + x2 * cos
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)
