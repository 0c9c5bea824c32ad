"""Mamba-2's selective state-space scan (SSD, arXiv:2405.21060) in its chunked
form, and the causal depthwise convolution + SiLU that feeds it; no
counterpart in the reference (SURVEY.md §5.7).  The scan is one `custom_vjp`
whose two directions are Pallas kernels for TPU and plain `jax.numpy`,
differentiated by JAX, elsewhere (below).  The convolution is one `custom_vjp`
(`causal_conv1d_silu`) with a backward written by hand: JAX's own transpose of
K shifted multiply-adds is one full-size float32 cotangent array PER TAP (572
MB written and read back a layer at the benchmark's [8192, 4352]), where the
hand-written one is the same K taps run anti-causally over `dy * silu'(pre)`
plus three reductions, no float32 [B, S, C] array anywhere.  For a step
lowered for TPU at tile-aligned shapes both directions are Pallas kernels
(`ops/pallas/ssm_conv.py`: every full-size array crosses HBM once, in bf16);
every other shape and platform takes the plain form below, chosen from the
shapes alone.  Convolution and scan each declare a `kernel_pair.KernelPair`
(`CONV`, `SCAN`); `ops/kernel_pair.py`'s scaffold makes the choice and builds
the `custom_vjp`s.

The recurrence, per batch row and per head h, with a state `H_t` of shape
[P, N] (head size x state size), a positive step `dt_t`, a negative scalar
`A` per head and `B_t`, `C_t` in R^N, one group shared by all the heads or G
groups, head h reading group `h // (H / G)`:

    H_t = exp(dt_t * A) * H_{t-1} + dt_t * x_t (outer) B_t
    y_t = H_t C_t + D * x_t

`ssd_chunked` computes the same y without a pass over tokens.  The sequence
is cut into chunks of `chunk` positions; with `a_t = dt_t * A` and `cum_t`
its running sum inside the chunk:

- within a chunk, the quadratic masked form
  `y_t += sum_{s<=t} exp(cum_t - cum_s) * (C_t . B_s) * dt_s * x_s`:
  one [chunk, chunk] product `C B^T` per chunk and GROUP (not per head), one
  decay mask per head;
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

`ssd_chunked` is one `custom_vjp` at the shapes its kernels take (heads of 64
or 32, states of whole lane tiles, chunks of whole 128-blocks, at least a lane
tile of heads a group).  In a step lowered for TPU both directions are Mosaic
kernels (`ops/pallas/ssd.py`: `ssd_fwd`, `ssd_bwd`, PR 49): `C B^T`, the decay
masks, the scores and their cotangents, all [chunk, chunk], live in VMEM a
head and chunk at a time, the pass over chunk states in a VMEM scratch along
the grid's sequential axis, and what crosses HBM is x, y, B, C, their
cotangents, the [b, S, H] arrays and the state that enters each chunk (67 MB a
layer at one 8,192-token sequence of 64 heads of 64, state 128).  Everywhere
else (the CPU, refused shapes) it is `_plain_forward` below, differentiated by
JAX, where the [B, S/chunk, H, chunk, chunk] masks and scores are arrays of
their own (0.5 GB in float32 and 0.27 GB in bf16 at those shapes, written and
read back in each direction: `ssm/scan` took 7.2 ms a layer of which the
kernels leave 3.7, PERF.md section 6, PR 49).  The plain form sums the scan's
three terms in the matmuls' own [b, c, h, t, p] order and rounds to the
inputs' dtype THERE; its relayout to [b, S, h, p] moves the rounded array
behind an `optimization_barrier` (without it XLA hoists the consumer's
float32 convert across the relayout and copies float32, twice).  `ssm/scan`,
the scope around either form, is what the benchmark reads the scan by
(PERF.md section 3).

Sharding: nothing here names a mesh axis.  The heads are not sharded over
`tensor` (the model replicates the mixer's weights under `tp`); batch and
fsdp sharding are GSPMD's to propagate through the plain form's einsums, and
with a mesh the kernels run under `shard_map` over the batch axes, as the
convolution's do.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops import kernel_pair

# The published chunk length (`mamba_chunk_size`); the program's own constant.
CHUNK = 256


def _shifted(x: jax.Array, j: int) -> jax.Array:
    """`x_{t-j}` along axis 1, zeros where t - j falls outside the sequence
    (j > 0 looks back, j < 0 ahead): a view in x's own dtype, no padded copy."""
    if j == 0:
        return x
    zeros = jnp.zeros_like(x[:, : abs(j)])
    if j > 0:
        return jnp.concatenate([zeros, x[:, : x.shape[1] - j]], axis=1)
    return jnp.concatenate([x[:, -j:], zeros], axis=1)


def _conv_pre(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """The convolution before its activation, float32: b + sum_k w[:, k] * x_{t-(K-1)+k}."""
    k = w.shape[1]
    wf = w.astype(jnp.float32)
    out = b.astype(jnp.float32)
    for i in range(k):
        out = out + _shifted(x, k - 1 - i).astype(jnp.float32) * wf[:, i]
    return out


def _dsilu(pre: jax.Array) -> jax.Array:
    """d silu(pre) / d pre."""
    sig = jax.nn.sigmoid(pre)
    return sig * (1.0 + pre * (1.0 - sig))


def _conv_silu_plain(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    return jax.nn.silu(_conv_pre(x, w, b)).astype(x.dtype)


def _conv_silu_bwd_plain(x, w, b, dy):
    k = w.shape[1]
    f32 = jnp.float32
    dpre = dy.astype(f32) * _dsilu(_conv_pre(x, w, b))
    wf = w.astype(f32)
    dx = sum(_shifted(dpre, -(k - 1 - i)) * wf[:, i] for i in range(k)).astype(x.dtype)
    dw = jnp.stack([jnp.sum(dpre * _shifted(x, k - 1 - i).astype(f32), axis=(0, 1)) for i in range(k)], axis=1)
    return dx, dw, jnp.sum(dpre, axis=(0, 1))


# The kernel forms are functions of the module, not closures of a call: jax finds a branch it has traced by the function.
def _conv_silu_kernel(x, w, b):
    return CONV.module().conv_fwd(x.swapaxes(1, 2), w, b).swapaxes(1, 2)


def _conv_silu_bwd_kernel(x, w, b, dy):
    dx, dw, db = CONV.module().conv_bwd(x.swapaxes(1, 2), w, b, dy.swapaxes(1, 2))
    return dx.swapaxes(1, 2), dw, db


def _conv_forward(call, x, w, b):
    return (call(_conv_silu_kernel, _conv_silu_plain, x, w, b),)


def _conv_backward(call, x, w, b, dy):
    dx, dw, db = call(_conv_silu_bwd_kernel, _conv_silu_bwd_plain, x, w, b, dy)
    return dx, dw.astype(w.dtype), db.astype(b.dtype)


# No scope of its own: each mixer names the convolution (`ssm/conv`, `kda/conv`, ..).
CONV = kernel_pair.KernelPair(
    name="causal_conv1d_silu", scope=None, kernels="ssm_conv",
    takes=lambda kernels, x, w, b, chunk: kernels.supported(x.shape[1], x.shape[2], w.shape[1]),
    forward=_conv_forward, backward=_conv_backward, replicated=(1, 2),  # w and b
)


def causal_conv1d_silu(x: jax.Array, w: jax.Array, b: jax.Array, mesh=None, batch_axes=None) -> jax.Array:
    """SiLU of the causal depthwise convolution along the sequence: x [B, S, C],
    w [C, K], b [C] -> [B, S, C] with `pre_t = b + sum_k w[:, k] * x_{t-(K-1)+k}`
    and zeros before the sequence's start (so `w[:, K-1]` multiplies `x_t`, as
    `torch.nn.Conv1d(groups=C, padding=K-1)` cut to S outputs has it), and
    `y = pre * sigmoid(pre)`.  K shifted multiply-adds and the activation in
    float32, rounded to x's dtype once.  One differentiable unit whose backward
    is written by hand (module docstring).

    mesh / batch_axes say how x is sharded (w and b are replicated).  GSPMD
    partitions the plain form by itself; a Mosaic kernel it cannot, so with a
    mesh the kernels run under shard_map over the batch axes, each device on
    its own rows with the whole sequence."""
    return kernel_pair.run(CONV, x, w, b, mesh=mesh, batch_axes=batch_axes)


def _plain_forward(x, dt, A, B, C, D, chunk: int):
    """The chunked scan in plain `jax.numpy`, differentiated by JAX: the path
    off TPU and at shapes the kernels refuse, and what the tests hold the
    kernels to.  Its [B, S/chunk, H, chunk, chunk] decay masks and scores are
    arrays of their own (HBM buffers, where XLA compiles it)."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    groups = B.shape[2] if B.ndim == 4 else None  # None: the one-group form, as it was before groups
    nc = s // chunk
    dtype = x.dtype
    f32 = jnp.float32

    xc = x.reshape(b, nc, chunk, h, p)
    Bc = B.reshape(b, nc, chunk, *B.shape[2:])
    Cc = C.reshape(b, nc, chunk, *C.shape[2:])

    def by_group(a):  # [b, c, l, h, ...] -> [b, c, l, G, h / G, ...]
        return a.reshape(*a.shape[:3], groups, h // groups, *a.shape[4:])

    def by_head(a):  # [b, c, G, h / G, ...] -> [b, c, h, ...]
        return a.reshape(b, nc, h, *a.shape[4:])

    dtc, cum = _running_sums(dt, A, chunk)  # [b, c, l, h] float32: dt, and the running sum of dt * A (<= 0)
    dtx = dtc[..., None] * xc.astype(f32)  # dt_s * x_s, float32

    # within a chunk: (C B^T o L) (dt x), L[t, s] = exp(cum_t - cum_s) for s <= t
    cum_h = cum.transpose(0, 1, 3, 2)  # [b, c, h, l]
    diff = cum_h[..., :, None] - cum_h[..., None, :]  # [b, c, h, t, s]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    if groups is None:
        cb = jnp.einsum("bctn,bcsn->bcts", Cc, Bc, preferred_element_type=f32)
        scores = (cb[:, :, None] * decay).astype(dtype)  # [b, c, h, t, s]
    else:  # once per group, broadcast over the group's heads
        cb = jnp.einsum("bctgn,bcsgn->bcgts", Cc, Bc, preferred_element_type=f32)
        scores = by_head(cb[:, :, :, None] * decay.reshape(b, nc, groups, h // groups, chunk, chunk)).astype(dtype)
    y = jnp.einsum("bchts,bcshp->bchtp", scores, dtx.astype(dtype), preferred_element_type=f32)

    # each chunk's contribution to the state at its own end: [b, c, h, p, n]
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)  # [b, c, l, h]
    dtx_end = (to_end[..., None] * dtx).astype(dtype)
    if groups is None:
        states = jnp.einsum("bcshp,bcsn->bchpn", dtx_end, Bc, preferred_element_type=f32)
    else:
        states = by_head(jnp.einsum("bcsgjp,bcsgn->bcgjpn", by_group(dtx_end), Bc, preferred_element_type=f32))

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
    if groups is None:
        read = jnp.einsum("bctn,bchpn->bchtp", Cc.astype(f32), entering, preferred_element_type=f32)
    else:
        read = by_head(jnp.einsum("bctgn,bcgjpn->bcgjtp", Cc.astype(f32),
                                  entering.reshape(b, nc, groups, h // groups, p, n), preferred_element_type=f32))
    y = y + jnp.exp(cum_h)[..., None] * read
    y = y + D.astype(f32)[:, None, None] * xc.astype(f32).transpose(0, 1, 3, 2, 4)
    # summed and rounded in the matmuls' own order; the relayout moves the ROUNDED array (module docstring)
    y = y.astype(dtype).transpose(0, 1, 3, 2, 4).reshape(b, s, h * p)
    return jax.lax.optimization_barrier(y).reshape(b, s, h, p)


def _running_sums(dt, A, chunk: int):
    """(dt, the inclusive running sum of `dt * A` inside each chunk), both
    [b, S / chunk, chunk, H] float32: the two per-(position, head) arrays
    every form of the scan starts from."""
    b, s, h = dt.shape
    dtc = dt.astype(jnp.float32).reshape(b, s // chunk, chunk, h)
    return dtc, jnp.cumsum(dtc * A.astype(jnp.float32), axis=2)  # cum_t = sum_{s<=t} a_s, a <= 0


# Traced once a process, however many bodies of a loop over layers call them (PERF.md section 6, PR 48: `setup_trace_s`).
@functools.partial(jax.jit, static_argnums=(6,))
def _kernel_forward(x, dt, A, B, C, D, chunk: int):
    """(y, the state that enters each chunk as `ssd_fwd` lays it out)."""
    dtc, cum = _running_sums(dt, A, chunk)
    return SCAN.module().ssd_fwd(x, dtc.reshape(dt.shape), cum.reshape(dt.shape), B, C, D, chunk=chunk)


@functools.partial(jax.jit, static_argnums=(8,))
def _kernel_backward(x, dt, A, B, C, D, entering, dy, chunk: int):
    """The six cotangents, each in its argument's dtype.  The kernel gives
    those of x, B, C, D, of dt as a factor of `dt x`, and of the running sum
    `cum`; `cum` is a running sum of `dt * A` inside each chunk, so its
    cotangent runs back from each chunk's end and reaches dt through A and A
    through dt."""
    f32 = jnp.float32
    dtc, cum = _running_sums(dt, A, chunk)
    dx, d_dt, d_cum, dB, dC, dD = SCAN.module().ssd_bwd(
        x, dtc.reshape(dt.shape), cum.reshape(dt.shape), B, C, D, entering, dy, chunk=chunk)
    d_a = jax.lax.cumsum(d_cum.reshape(dtc.shape), axis=2, reverse=True).reshape(dt.shape)
    d_dt = d_dt + d_a * A.astype(f32)
    d_A = jnp.sum(d_a * dt.astype(f32), axis=(0, 1))
    return dx, d_dt.astype(dt.dtype), d_A.astype(A.dtype), dB.astype(B.dtype), dC.astype(C.dtype), dD.astype(D.dtype)


def _forward(call, x, dt, A, B, C, D):
    """(y, the state that enters each chunk).  The plain form's backward is
    JAX's own and starts from the arguments: zeros for the states."""
    b, s, h, p = x.shape

    def plain(x, dt, A, B, C, D):
        return _plain_forward(x, dt, A, B, C, D, call.chunk), jnp.zeros((b, s // call.chunk, B.shape[-1], h * p), jnp.float32)

    return call(functools.partial(_kernel_forward, chunk=call.chunk), plain, x, dt, A, B, C, D)


def _backward(call, x, dt, A, B, C, D, entering, dy):
    def plain(x, dt, A, B, C, D, entering, dy):
        return jax.vjp(functools.partial(_plain_forward, chunk=call.chunk), x, dt, A, B, C, D)[1](dy)

    return call(functools.partial(_kernel_backward, chunk=call.chunk), plain, x, dt, A, B, C, D, entering, dy)


# At shapes the kernels refuse the plain form runs OUTSIDE the `custom_vjp`, differentiated by JAX whole (`refused`).
SCAN = kernel_pair.KernelPair(
    name="ssd_chunked", scope="ssm/scan", kernels="ssd",
    takes=lambda kernels, x, dt, A, B, C, D, chunk: kernels.supported(
        x.shape[2], x.shape[3], B.shape[-1], B.shape[2] if B.ndim == 4 else 1, x.shape[1], chunk),
    forward=_forward, backward=_backward, replicated=(2, 5), refused=_plain_forward,  # A and D
)


def ssd_chunked(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    B: jax.Array,
    C: jax.Array,
    D: jax.Array,
    chunk: Optional[int] = None,
    mesh=None,
    batch_axes=None,
) -> jax.Array:
    """The selective scan of the module docstring, chunked.

    x [b, S, H, P]; dt [b, S, H] (after softplus, positive); A [H] (negative);
    B, C [b, S, N] (one group) or [b, S, G, N] (G groups, G dividing H); D
    [H].  Returns y [b, S, H, P] in x's dtype.  `chunk` (None = `CHUNK`) is
    cut to S when S is shorter; S must be a multiple of it.

    At shapes the kernels take (`ops/pallas/ssd.py` `supported`) this is one
    `custom_vjp` whose two directions are Mosaic kernels in a step lowered for
    TPU; at every other shape the plain form, differentiated by JAX.

    mesh / batch_axes say how the arguments are sharded (A and D are
    replicated).  GSPMD partitions the plain form by itself; a Mosaic kernel it
    cannot, so with a mesh the kernels run under shard_map over the batch axes,
    each device on its own rows with the whole sequence and every head."""
    if B.ndim == 4 and x.shape[2] % B.shape[2]:
        raise ValueError(f"ssd_chunked: {B.shape[2]} groups of B and C do not divide {x.shape[2]} heads")
    return kernel_pair.run(SCAN, x, dt, A, B, C, D, chunk=chunk or CHUNK, mesh=mesh, batch_axes=batch_axes)
