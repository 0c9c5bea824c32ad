"""What feeds a delta rule's recurrence (`ops/kda.py`, `ops/gdn.py`): the
causal depthwise convolution + SiLU of the fused q | k | v projection and the
per-head L2 norm of q and k, ONE differentiable unit, positions-major from end
to end.

    y = silu(causal_depthwise_conv1d(x[..., :C]))      rounded to x's dtype
    q = y_q / |y_q|_2 * D^-0.5,  k = y_k / |y_k|_2     per head of D, float32
    v = y_v

`delta_conv` is one `jax.custom_vjp`, declared as a `kernel_pair.KernelPair`
(`PAIR`) and run by `ops/kernel_pair.py`'s scaffold.  Everywhere but on TPU,
and at the shapes the kernels refuse, it is `ops/ssm.py`'s plain convolution
(`_conv_silu_plain`, its hand-written backward `_conv_silu_bwd_plain`) and the
norm as JAX differentiates it (`_plain_forward`, `_plain_backward`): what
`causal_conv1d_silu` + `jnp.split` + `mixers/base.py`'s `l2_normed` were in
the layers until PR 60, and what the tests hold the kernels to.  For TPU, at heads of 128,
two Pallas kernels (`ops/pallas/delta_conv.py`: `delta_conv_fwd`,
`delta_conv_bwd`) read x where it lies, [B, S, Cx] with the channels along
the lanes, and write q, k and v as the scan kernels read them: no [B, C, S]
array and no full-size float32 intermediate exists in either direction.
Mamba-2's and S6's convolution (`ssm.CONV`, `ops/pallas/ssm_conv.py`) is
another function, without a norm and with the sequence along the lanes, and
stays what it was.

The layout of the channels is the SHAPES of the three weights (q's and k's
by head, v's flat), so no argument says it: a layer hands its convolution's
weights over as it means them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops import kernel_pair, ssm

EPS = 1e-6  # under the norm's root


def _l2_normed(y: jax.Array, scale: float) -> jax.Array:
    """y / |y|_2 over the last axis (a head), times `scale`, in float32: a delta rule's q and k."""
    yf = y.astype(jnp.float32)
    return yf * (jax.lax.rsqrt(jnp.sum(jnp.square(yf), axis=-1, keepdims=True) + EPS) * scale)


def _weights(wq, wk, wv):
    """The three weights as one convolution's [C, K]: q's channels first, then k's, then v's."""
    k = wv.shape[1]
    return jnp.concatenate([wq.reshape(-1, k), wk.reshape(-1, k), wv], axis=0)


def _normed(y, wq, wk):
    """The convolution's y [B, S, C] -> (q [B, S, Hq * D], k [B, S, Hk * D] float32, v), each head of q and k normalised."""
    (hq, d, _), hk = wq.shape, wk.shape[0]
    by_head = lambda a, h, scale: _l2_normed(a.reshape(*a.shape[:2], h, d), scale).reshape(a.shape)
    return by_head(y[..., : hq * d], hq, d ** -0.5), by_head(y[..., hq * d: (hq + hk) * d], hk, 1.0), y[..., (hq + hk) * d:]


def _convolution(x, wq, wk, wv):
    """`ssm`'s plain convolution's arguments: the columns of x it reads, its [C, K] weights, no bias."""
    w = _weights(wq, wk, wv)
    return x[..., : w.shape[0]], w, jnp.zeros((w.shape[0],), w.dtype)


def _plain_forward(x, wq, wk, wv):
    return _normed(ssm._conv_silu_plain(*_convolution(x, wq, wk, wv)), wq, wk)


def _plain_backward(x, wq, wk, wv, dq, dk, dv):
    """(dx [B, S, C], dw [C, K] float32): the norm's cotangent as JAX takes it
    (rounded to y's dtype where y was), then the convolution's hand-written backward."""
    conv = _convolution(x, wq, wk, wv)
    _, through_norm = jax.vjp(lambda y: _normed(y, wq, wk), ssm._conv_silu_plain(*conv))
    dx, dw, _ = ssm._conv_silu_bwd_plain(*conv, *through_norm((dq, dk, dv)))
    return dx, dw


# The kernel forms are functions of the module, not closures of a call: jax finds a branch it has traced by the function.
def _kernel_forward(x, wq, wk, wv):
    return tuple(PAIR.module().conv_fwd(x, _weights(wq, wk, wv), q_heads=wq.shape[0], k_heads=wk.shape[0]))


def _kernel_backward(x, wq, wk, wv, dq, dk, dv):
    return tuple(PAIR.module().conv_bwd(x, _weights(wq, wk, wv), dq, dk, dv, q_heads=wq.shape[0], k_heads=wk.shape[0]))


def _forward(call, x, wq, wk, wv):
    return (tuple(call(_kernel_forward, _plain_forward, x, wq, wk, wv)),)


def _backward(call, x, wq, wk, wv, d_out):
    """(dx in x's shape: zeros in the columns the convolution does not read; the three weights' cotangents)."""
    dx, dw = call(_kernel_backward, _plain_backward, x, wq, wk, wv, *d_out)
    dx = jnp.pad(dx, ((0, 0), (0, 0), (0, x.shape[2] - dx.shape[2])))
    nq, nk = wq.shape[0] * wq.shape[1], wk.shape[0] * wk.shape[1]
    return (dx, dw[:nq].reshape(wq.shape).astype(wq.dtype), dw[nq: nq + nk].reshape(wk.shape).astype(wk.dtype),
            dw[nq + nk:].astype(wv.dtype))


# No scope of its own: each mixer names the convolution (`kda/conv`, `gdn/conv`).
PAIR = kernel_pair.KernelPair(
    name="delta_conv", scope=None, kernels="delta_conv",
    takes=lambda kernels, x, wq, wk, wv, chunk: kernels.supported(
        x.shape[1], x.shape[2], wq.shape[0], wk.shape[0], wq.shape[1], wv.shape[0], wv.shape[1]),
    forward=_forward, backward=_backward, replicated=(1, 2, 3),  # the weights
)


def delta_conv(x: jax.Array, wq: jax.Array, wk: jax.Array, wv: jax.Array, mesh=None, batch_axes=None):
    """A delta layer's q, k and v from its fused projection.

    x [B, S, Cx]; wq [Hq, D, K], wk [Hk, D, K], wv [Cv, K]: the depthwise
    weights of the first (Hq + Hk) * D + Cv columns of x, in that order (a
    wider x keeps what follows them to itself).  `pre_t = sum_k w[:, k] *
    x_{t-(K-1)+k}` with zeros before the sequence's start, as
    `ssm.causal_conv1d_silu` has it without a bias; `y = pre * sigmoid(pre)`
    in float32, rounded to x's dtype once.  Returns (q [B, S, Hq, D],
    k [B, S, Hk, D], v [B, S, Cv]): q and k in float32, each head
    `y / sqrt(|y|^2 + 1e-6)` of the rounded y, q times `D^-0.5`; v is y.

    mesh / batch_axes say how x is sharded (the weights are replicated), as
    `causal_conv1d_silu` takes them."""
    if not (wq.ndim == wk.ndim == 3 and wv.ndim == 2 and wq.shape[1:] == wk.shape[1:] and wq.shape[2] == wv.shape[1]):
        raise ValueError(f"delta_conv: weights {wq.shape}, {wk.shape}, {wv.shape} are not [Hq, D, K], [Hk, D, K], [Cv, K]")
    if wq.shape[0] * wq.shape[1] + wk.shape[0] * wk.shape[1] + wv.shape[0] > x.shape[2]:
        raise ValueError(f"delta_conv: x has {x.shape[2]} columns, the weights convolve more")
    q, k, v = kernel_pair.run(PAIR, x, wq, wk, wv, mesh=mesh, batch_axes=batch_axes)
    return q.reshape(*q.shape[:2], *wq.shape[:2]), k.reshape(*k.shape[:2], *wk.shape[:2]), v
