"""Regression tests for review findings (round 1).

Mirrors the reference's targeted failure tests (ray: python/ray/tests/
test_actor_failures.py, test_reference_counting*.py).
"""

import time

import numpy as np
import pytest

import ray_tpu


def test_kill_pending_actor_not_resurrected(ray_start_regular):
    """kill() on a not-yet-scheduled actor must cancel its creation task
    (previously the queued creation resurrected the actor to ALIVE)."""

    @ray_tpu.remote(num_cpus=4)
    class Hog:
        def ping(self):
            return "pong"

    @ray_tpu.remote(num_cpus=4)
    class Pending:
        def ping(self):
            return "pong"

    hog = Hog.remote()
    ray_tpu.get(hog.ping.remote(), timeout=30)  # occupies all 4 CPUs
    pending = Pending.remote()  # cannot schedule while hog lives
    ray_tpu.kill(pending)
    ray_tpu.kill(hog)
    time.sleep(0.5)  # let resources free + dispatch run
    with pytest.raises(ray_tpu.exceptions.ActorDiedError):
        ray_tpu.get(pending.ping.remote(), timeout=10)


def test_exit_actor_from_concurrent_actor(ray_start_regular):
    """exit_actor() inside a max_concurrency>1 actor must terminate the
    process (previously SystemExit was swallowed by the thread pool)."""

    @ray_tpu.remote(max_concurrency=4)
    class C:
        def stop(self):
            ray_tpu.exit_actor()

        def ping(self):
            return "pong"

    c = C.remote()
    assert ray_tpu.get(c.ping.remote(), timeout=30) == "pong"
    c.stop.remote()
    time.sleep(1.0)
    with pytest.raises(ray_tpu.exceptions.ActorDiedError):
        ray_tpu.get(c.ping.remote(), timeout=10)


def test_flash_attention_ragged_lengths():
    """Non-block-divisible sequence lengths must not silently drop tails:
    the kernel shrinks its tile to a divisor, refuses a length no tile
    divides (no quiet switch to another algorithm), and the dispatcher
    routes such lengths to the XLA forms."""
    import jax

    from ray_tpu.ops.attention import dot_product_attention, reference_attention
    from ray_tpu.ops.pallas.flash_attention import flash_attention

    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, 192, 2, 32))
    k = jax.random.normal(kk, (1, 192, 2, 32))
    v = jax.random.normal(kv, (1, 192, 2, 32))
    ref = reference_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5, rtol=2e-5)
    with pytest.raises(ValueError, match="no tile divides"):
        flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    out = dot_product_attention(q, k, v, causal=True, block_size=128)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5, rtol=2e-5)


def test_train_step_with_mask():
    """Batches may carry an optional loss mask."""
    import jax

    from ray_tpu.models import LMTrainContext, TransformerConfig
    from ray_tpu.parallel import MeshSpec, build_mesh

    cfg = TransformerConfig.tiny()
    ctx = LMTrainContext(cfg, mesh=build_mesh(MeshSpec(data=8)), strategy="dp")
    state = ctx.init_state(seed=0)
    toks = jax.random.randint(jax.random.PRNGKey(0), (8, 17), 0, cfg.vocab_size)
    mask = (jax.numpy.arange(16)[None, :] < 10).astype(np.float32).repeat(8, axis=0)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:], "mask": mask}
    state, metrics = ctx.train_step(state, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_dep_error_fails_task_queued_behind_blocked_bucket(ray_start_regular):
    """Bucketed dispatch probes only bucket heads: a task whose dependency
    errored must still fail fast even while queued behind an unplaceable
    sibling of the same shape (regression: it hung until the head placed)."""
    import time

    import pytest

    import ray_tpu
    from ray_tpu.exceptions import TaskError

    @ray_tpu.remote
    class Hog:
        def ping(self):
            return "ok"

    @ray_tpu.remote
    def boom():
        raise RuntimeError("producer failed")

    @ray_tpu.remote
    def consumer(x):
        return x

    @ray_tpu.remote
    def sleeper():
        time.sleep(30)

    # Occupy every CPU with actors so plain tasks cannot place.
    hogs = [Hog.options(num_cpus=1).remote() for _ in range(4)]
    ray_tpu.get([h.ping.remote() for h in hogs], timeout=30)
    # num_cpus=0: the producer must actually RUN (and fail) while the
    # CPU-shaped bucket stays blocked by the sleepers.
    bad = boom.options(num_cpus=0).remote()
    blocked = [sleeper.remote() for _ in range(2)]  # bucket heads, unplaceable
    dependent = consumer.remote(bad)
    # The dependent must fail with the producer's error promptly, NOT wait
    # for a CPU to free up.
    with pytest.raises(TaskError, match="producer failed"):
        ray_tpu.get(dependent, timeout=20)
    for h in hogs:
        ray_tpu.kill(h)
    del blocked
