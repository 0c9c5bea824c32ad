"""Headline benchmark: flagship-model training throughput on real TPU.

Prints ONE JSON line: tokens/sec/chip on a Llama-family decoder train step
(fwd+bwd+adam, bf16 compute), plus achieved MFU.  vs_baseline is achieved
MFU / 0.45 — the north-star target from BASELINE.json ("Llama-7B DDP at
>=45% MFU"); the reference itself has no TPU numbers to compare against
(SURVEY.md §6: GPU-only).

The long-context sweep re-measures the same model shape at seq 2048,
4096, 8192 and 16384 (constant tokens/step — batch halves as sequence
doubles), the regime where the flash-attention backward and remat
policy earn their keep.  The 16k point switches to full per-layer
recompute (remat_policy=None) because the qkv_attn stash overflows
single-chip HBM there — its extra recompute flops are NOT credited, so
compare points via `mfu_attn_incl` (adds 12*L*d*seq flops/token for
the score/value matmuls, fwd+bwd), not the 6ND parameter-MFU.

Model is scaled to fit one chip's HBM (the driver runs single-chip); the
multi-chip path — including ring attention over a seq-sharded mesh — is
exercised by __graft_entry__.dryrun_multichip and tests/test_ops_attention.
"""

from __future__ import annotations

import json
import time


# per-chip dense bf16 peak; longest-prefix keys first ("TPU v5p" must win
# over "TPU v5" under the startswith lookup below)
PEAK_BF16_FLOPS = {
    "TPU v6 lite": 918e12,
    "TPU v5 lite": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 197e12,
    "TPU v4": 275e12,
}


def _measure(cfg, mesh, batch_size: int, seq: int, steps: int, peak: float):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import LMTrainContext

    ctx = LMTrainContext(cfg, mesh=mesh, strategy="dp")
    state = ctx.init_state(seed=0)
    key = jax.random.PRNGKey(1)
    toks = jax.random.randint(key, (batch_size, seq + 1), 0, cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}

    # warmup / compile; the host fetch of the loss is the sync
    for _ in range(2):
        state, metrics = ctx.train_step(state, batch)
    float(metrics["loss"])

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = ctx.train_step(state, batch)
    # steps chain through donated state, so fetching the last loss implies
    # all prior steps completed.
    float(metrics["loss"])
    dt = time.perf_counter() - t0

    tokens_per_s = steps * batch_size * seq / dt
    n_params = cfg.num_params()
    # 6ND fwd+bwd (+remat recompute ≈ 8ND counted conservatively as 6ND)
    param_flops_per_tok = 6 * n_params
    # score/value matmuls: 4*L*d*seq fwd per token, x3 for fwd+bwd
    attn_flops_per_tok = 12 * cfg.n_layers * cfg.d_model * seq
    del state
    return {
        "tokens_per_s": round(tokens_per_s, 1),
        "mfu": round(param_flops_per_tok * tokens_per_s / peak, 4),
        "mfu_attn_incl": round(
            (param_flops_per_tok + attn_flops_per_tok) * tokens_per_s / peak, 4
        ),
    }


def main():
    from ray_tpu._private import compile_cache

    compile_cache.apply_default()  # before jax loads

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig
    from ray_tpu.parallel import MeshSpec, build_mesh

    dev = jax.devices()[0]
    # A throughput is a statement about a chip: no chip, or a chip whose
    # peak is not in the table, is an error and never a default.
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU; jax found platform {dev.platform!r} "
            f"({dev.device_kind})"
        )
    peak = next(
        (v for k, v in PEAK_BF16_FLOPS.items() if dev.device_kind.startswith(k)),
        None,
    )
    if peak is None:
        raise SystemExit(
            f"bench.py: no bf16 peak on record for device_kind "
            f"{dev.device_kind!r}; add it to PEAK_BF16_FLOPS with its source"
        )
    mesh = build_mesh(MeshSpec(data=1), devices=[dev])

    # ~940M params: the widest llama-family shape that fits v5e HBM (16G)
    # with bf16 params + f32 adam moments.  d_model=2048 maps onto the MXU
    # far better than deeper/narrower configs (measured: d1536/L24 -> 0.46
    # MFU, d2048/L16 -> 0.51 on v5e).  remat saves post-rope q/k/v + the
    # flash-attention output, recomputing only the cheap matmuls in bwd.
    # bs16 x seq1024 beats bs8 x seq2048 at equal tokens/step (0.578 vs
    # 0.518 measured): half the quadratic attention work per token, which
    # the 6ND accounting doesn't credit (mfu_attn_incl does).
    def make_cfg(seq_len: int) -> TransformerConfig:
        return TransformerConfig(
            vocab_size=32000,
            d_model=2048,
            n_layers=16,
            n_heads=16,
            n_kv_heads=16,
            d_ff=5504,
            max_seq_len=seq_len,
            param_dtype=jnp.bfloat16,
            remat=True,
            # 16k: the qkv_attn stash (~5 GB) overflows v5e HBM — switch
            # to full per-layer recompute (remat_policy=None), the
            # blockwise/remat long-seq mode (SURVEY §5.7); shorter points
            # keep the faster policy.
            remat_policy=None if seq_len >= 16384 else "qkv_attn",
        )

    head = _measure(make_cfg(1024), mesh, 16, 1024, steps=10, peak=peak)

    # Long-context sweep to 16k: constant 16k tokens/step (batch halves as
    # sequence doubles) — SURVEY §5.7, the axis the reference doesn't
    # have.  The flash kernel streams K/V blocks, so HBM stays flat and
    # no ring/offload switch is needed single-chip through 16k (the
    # seq-sharded ring path is exercised by dryrun_multichip).  A point
    # that fails takes the run down with it: a result with a hole in it
    # would still read as a pass.
    sweep = {}
    for bs, seq in ((8, 2048), (4, 4096), (2, 8192), (1, 16384)):
        sweep[str(seq)] = _measure(
            make_cfg(seq), mesh, bs, seq, steps=6, peak=peak
        )

    n_params = make_cfg(1024).num_params()
    print(
        json.dumps(
            {
                "metric": "train_tokens_per_sec_per_chip",
                "value": head["tokens_per_s"],
                "unit": "tokens/s",
                "vs_baseline": round(head["mfu"] / 0.45, 4),
                "mfu": head["mfu"],
                "n_params": n_params,
                "device": dev.device_kind,
                "seq_sweep": sweep,
            }
        )
    )


if __name__ == "__main__":
    main()
