"""Component timing on the real chip: where does the train step spend time?

Times (a) pure-matmul proxy of the model's param flops, (b) attention
forward, (c) attention fwd+bwd, (d) full train step fwd+bwd.  Run on the
TPU to locate the MFU gap before optimizing.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def timeit(fn, *args, iters=5, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main():
    dev = jax.devices()[0]
    print("device:", dev.device_kind)

    B, S, H, Dh, E, F, V, L = 8, 2048, 12, 128, 1536, 4096, 32000, 24

    # (a) pure matmul proxy: one big bf16 matmul, report achieved TFLOP/s
    m, k, n = 8192, 8192, 8192
    a = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.bfloat16)
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.bfloat16)
    mm = jax.jit(lambda a, b: a @ b)
    dt = timeit(mm, a, b)
    print(f"matmul {m}x{k}x{n} bf16: {2*m*k*n/dt/1e12:.1f} TFLOP/s ({dt*1e3:.2f} ms)")

    # (b/c) attention fwd and fwd+bwd
    from ray_tpu.ops.pallas.flash_attention import flash_attention

    q = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, Dh), jnp.bfloat16)
    k_ = jax.random.normal(jax.random.PRNGKey(3), (B, S, H, Dh), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(4), (B, S, H, Dh), jnp.bfloat16)

    fa = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    dt = timeit(fa, q, k_, v)
    attn_flops = 4 * B * H * S * S * Dh / 2  # causal halves the work
    print(f"flash fwd: {dt*1e3:.2f} ms  ({attn_flops/dt/1e12:.1f} TFLOP/s)  x{L} layers = {L*dt*1e3:.1f} ms")

    def loss_fn(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True).astype(jnp.float32))

    fab = jax.jit(jax.grad(loss_fn, argnums=(0, 1, 2)))
    dt = timeit(fab, q, k_, v)
    print(f"flash fwd+bwd(grad): {dt*1e3:.2f} ms  x{L} layers = {L*dt*1e3:.1f} ms")

    # reference: xla attention fwd+bwd
    from ray_tpu.ops.attention import blockwise_attention

    def loss_bw(q, k, v):
        return jnp.sum(blockwise_attention(q, k, v, causal=True).astype(jnp.float32))

    bwb = jax.jit(jax.grad(loss_bw, argnums=(0, 1, 2)))
    dt = timeit(bwb, q, k_, v)
    print(f"blockwise fwd+bwd(grad): {dt*1e3:.2f} ms  x{L} layers = {L*dt*1e3:.1f} ms")

    # plain softmax attention fwd+bwd (XLA fused)
    def plain(q, k, v):
        qf = q.astype(jnp.float32) * (Dh ** -0.5)
        logits = jnp.einsum("bshd,bthd->bhst", qf, k.astype(jnp.float32))
        mask = jnp.tril(jnp.ones((S, S), bool))
        logits = jnp.where(mask, logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1).astype(jnp.bfloat16)
        return jnp.einsum("bhst,bthd->bshd", p, v)

    pb = jax.jit(jax.grad(lambda q, k, v: jnp.sum(plain(q, k, v).astype(jnp.float32)), argnums=(0, 1, 2)))
    try:
        dt = timeit(pb, q, k_, v)
        print(f"plain-xla fwd+bwd(grad): {dt*1e3:.2f} ms  x{L} layers = {L*dt*1e3:.1f} ms")
    except Exception as e:
        print("plain-xla OOM/fail:", type(e).__name__)

    # (d) full train step (current bench config)
    from ray_tpu.models import LMTrainContext, TransformerConfig
    from ray_tpu.parallel import MeshSpec, build_mesh

    cfg = TransformerConfig(
        vocab_size=V, d_model=E, n_layers=L, n_heads=H, n_kv_heads=H,
        d_ff=F, max_seq_len=S, param_dtype=jnp.bfloat16, remat=True,
    )
    mesh = build_mesh(MeshSpec(data=1), devices=[dev])
    ctx = LMTrainContext(cfg, mesh=mesh, strategy="dp")
    state = ctx.init_state(seed=0)
    toks = jax.random.randint(jax.random.PRNGKey(5), (B, S + 1), 0, V)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}

    for _ in range(2):
        state, metrics = ctx.train_step(state, batch)
    float(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(5):
        state, metrics = ctx.train_step(state, batch)
    float(metrics["loss"])
    dt = (time.perf_counter() - t0) / 5
    n_params = cfg.num_params()
    tokens_per_s = B * S / dt
    print(f"full step: {dt*1e3:.1f} ms  {tokens_per_s:.0f} tok/s  mfu={6*n_params*tokens_per_s/197e12:.3f}")


if __name__ == "__main__":
    main()
