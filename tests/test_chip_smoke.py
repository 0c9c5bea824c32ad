"""CPU twin of chip_smoke.py: the same driver, loop and checks, a tiny model,
JaxConfig(platform="cpu").  What it keeps true without a chip: the main path
(init -> JaxTrainer.fit -> train.report -> shutdown) passes its own checks,
the driver process never initialises a JAX backend while it runs, nothing it
started outlives shutdown, and a failed run comes back as Result.error and is
counted as a failure."""

import json
import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import chip_smoke  # noqa: E402

# 128-aligned like chip_smoke.BENCH_MODEL, so a TPU lowering of this very
# loop would pick the kernel; lowered for CPU it must hold none.
TINY = dict(
    vocab_size=256, d_model=256, n_layers=2, n_heads=2, n_kv_heads=2,
    d_ff=256, max_seq_len=128, dtype="float32", remat=True,
    remat_policy="qkv_attn",
)

_DRIVER = """
import json, sys
import chip_smoke
plan = chip_smoke.make_plan(chips=1, platform="cpu", model={tiny!r}, batch_per_chip=2)
result, obs, bad = chip_smoke.run(plan)
print(json.dumps({{"bad": bad, "obs": obs, "jax_imported": "jax" in sys.modules}}))
"""


def test_cpu_twin_passes_and_driver_stays_off_jax():
    # A fresh interpreter: this pytest process initialised its CPU backend
    # long ago, so only a subprocess can show a driver that never did.
    proc = subprocess.run(
        [sys.executable, "-c", _DRIVER.format(tiny=TINY)],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    # run() itself fails the smoke if a backend was initialised; on today's
    # code the driver does not even import jax.
    assert out["bad"] == []
    assert out["jax_imported"] is False
    obs = out["obs"]
    assert len(obs["losses"]) == 1 + chip_smoke.STEADY_STEPS
    assert obs["losses"][-1] < obs["losses"][0]
    assert obs["tpu_custom_calls"] == 0
    assert obs["compile_cache_dir"] == os.environ.get(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(REPO_ROOT, ".jax_cache")
    )


def _boom(config):
    raise RuntimeError("boom at step 0")


def test_failed_fit_is_a_returned_error_and_a_smoke_failure(ray_start_regular):
    from ray_tpu.train import JaxConfig, JaxTrainer, ScalingConfig

    result = JaxTrainer(
        _boom,
        scaling_config=ScalingConfig(num_workers=1),
        backend_config=JaxConfig(platform="cpu"),
    ).fit()  # returns, does not raise
    assert result.error is not None
    plan = chip_smoke.make_plan(chips=1, platform="cpu", model=TINY)
    bad = chip_smoke.check(result, plan)
    assert any("Result.error" in reason and "boom" in reason for reason in bad)
