"""Mamba-2's selective state-space scan (SSD, arXiv:2405.21060) in its chunked
form, and the causal depthwise convolution that feeds it.  Plain `jax.numpy`,
differentiated by JAX; no counterpart in the reference (SURVEY.md §5.7).

The recurrence, per batch row and per head h, with a state `H_t` of shape
[P, N] (head size x state size), a positive step `dt_t`, a negative scalar
`A` per head and one group of `B_t`, `C_t` in R^N shared by the heads:

    H_t = exp(dt_t * A) * H_{t-1} + dt_t * x_t (outer) B_t
    y_t = H_t C_t + D * x_t

`ssd_chunked` computes the same y without a pass over tokens.  The sequence
is cut into chunks of `chunk` positions; with `a_t = dt_t * A` and `cum_t`
its running sum inside the chunk:

- within a chunk, the quadratic masked form
  `y_t += sum_{s<=t} exp(cum_t - cum_s) * (C_t . B_s) * dt_s * x_s`:
  one [chunk, chunk] product `C B^T` per chunk, one decay mask per head;
- each chunk's own contribution to the state at its end,
  `sum_s exp(cum_last - cum_s) * dt_s * x_s (outer) B_s`;
- one `lax.scan` over the S / chunk chunk states, each decayed by
  `exp(cum_last)` of the chunk it crosses: the only serial part;
- the state that ENTERS a chunk, read out at every position of it:
  `y_t += exp(cum_t) * (H_enter C_t)`.

A difference of running sums is taken BEFORE the exponential, never a
quotient of exponentials after it: `exp(cum_t) / exp(cum_s)` is 0/0 once
`cum` passes -88 in float32, which a long chunk of a fast-decaying head does.

Precision: `dt`, `dt * A`, the running sums, their exponentials and the chunk
states are float32 whatever the inputs are; the matmul operands are the
inputs' dtype (bf16 in a training cell) with float32 accumulation.

The [B, S/chunk, H, chunk, chunk] decay masks and scores are written to HBM
in this form (0.5 GB in float32 at one 8,192-token sequence, 64 heads and a
chunk of 256).  A Pallas kernel that keeps them in VMEM is the first thing a
`perf_opt` issue on the scan would write; `ssm/scan`, the scope around all of
this, is what its gain would be read by (PERF.md section 3).

Sharding: nothing here names a mesh axis.  The heads are not sharded over
`tensor` (the model replicates the mixer's weights under `tp`); batch and
fsdp sharding are GSPMD's to propagate through the einsums.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# The published chunk length (`mamba_chunk_size`); the program's own constant.
CHUNK = 256


def causal_conv1d(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Causal depthwise convolution along the sequence: x [B, S, C], w [C, K],
    b [C] -> [B, S, C] with `y_t = b + sum_k w[:, k] * x_{t - (K-1) + k}` and
    zeros before the sequence's start (so `w[:, K-1]` multiplies `x_t`, as
    `torch.nn.Conv1d(groups=C, padding=K-1)` cut to S outputs has it).
    K shifted multiply-adds in float32: one elementwise pass."""
    k = w.shape[1]
    s = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0))).astype(jnp.float32)
    wf = w.astype(jnp.float32)
    out = b.astype(jnp.float32)
    for i in range(k):
        out = out + padded[:, i: i + s] * wf[:, i]
    return out.astype(x.dtype)


def ssd_chunked(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    B: jax.Array,
    C: jax.Array,
    D: jax.Array,
    chunk: Optional[int] = None,
) -> jax.Array:
    """The selective scan of the module docstring, chunked.

    x [b, S, H, P]; dt [b, S, H] (after softplus, positive); A [H] (negative);
    B, C [b, S, N] (one group); D [H].  Returns y [b, S, H, P] in x's dtype.
    `chunk` (None = `CHUNK`) is cut to S when S is shorter; S must be a
    multiple of it."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk or CHUNK, s)
    if s % chunk:
        raise ValueError(f"ssd_chunked: sequence length {s} is not a multiple of the chunk {chunk}")
    nc = s // chunk
    dtype = x.dtype
    f32 = jnp.float32

    with jax.named_scope("ssm/scan"):
        xc = x.reshape(b, nc, chunk, h, p)
        Bc = B.reshape(b, nc, chunk, n)
        Cc = C.reshape(b, nc, chunk, n)
        dtc = dt.astype(f32).reshape(b, nc, chunk, h)
        a = dtc * A.astype(f32)  # [b, c, l, h], <= 0
        cum = jnp.cumsum(a, axis=2)  # inclusive: cum_t = sum_{s<=t} a_s
        dtx = dtc[..., None] * xc.astype(f32)  # dt_s * x_s, float32

        # within a chunk: (C B^T o L) (dt x), L[t, s] = exp(cum_t - cum_s) for s <= t
        cum_h = cum.transpose(0, 1, 3, 2)  # [b, c, h, l]
        diff = cum_h[..., :, None] - cum_h[..., None, :]  # [b, c, h, t, s]
        causal = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
        cb = jnp.einsum("bctn,bcsn->bcts", Cc, Bc, preferred_element_type=f32)
        scores = (cb[:, :, None] * decay).astype(dtype)  # [b, c, h, t, s]
        y = jnp.einsum("bchts,bcshp->bcthp", scores, dtx.astype(dtype), preferred_element_type=f32)

        # each chunk's contribution to the state at its own end: [b, c, h, p, n]
        to_end = jnp.exp(cum[:, :, -1:, :] - cum)  # [b, c, l, h]
        dtx_end = (to_end[..., None] * dtx).astype(dtype)
        states = jnp.einsum("bcshp,bcsn->bchpn", dtx_end, Bc, preferred_element_type=f32)

        # the serial part: the state that enters each chunk
        chunk_decay = jnp.exp(cum[:, :, -1, :])  # [b, c, h]

        def cross(carry, inp):
            decay_c, state_c = inp
            return carry * decay_c[..., None, None] + state_c, carry

        _, entering = jax.lax.scan(
            cross, jnp.zeros((b, h, p, n), f32),
            (chunk_decay.transpose(1, 0, 2), states.transpose(1, 0, 2, 3, 4)))
        entering = entering.transpose(1, 0, 2, 3, 4)  # [b, c, h, p, n]

        # the entering state read out at every position of the chunk
        read = jnp.einsum("bctn,bchpn->bcthp", Cc.astype(f32), entering, preferred_element_type=f32)
        y = y + jnp.exp(cum)[..., None] * read
        y = y + D.astype(f32)[:, None] * xc.astype(f32)
        return y.astype(dtype).reshape(b, s, h, p)
