"""MoE expert parallelism, dashboard endpoints, timeline, CLI."""

import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import os

import ray_tpu
from ray_tpu.models import TransformerConfig
from ray_tpu.models.moe import init_moe_params, moe_ffn

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(ray_tpu.__file__)))
from ray_tpu.parallel import MeshSpec, build_mesh, resolve_rules


def _moe_cfg(**kw):
    return TransformerConfig.tiny(d_model=16, d_ff=32, n_experts=4, experts_per_token=2, **kw)


def test_moe_forward_shapes_and_mixing():
    cfg = _moe_cfg()
    params = init_moe_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
    y, stats = moe_ffn(params, x, cfg)
    assert y.shape == x.shape
    assert stats["choice_share"].shape == (2, 4) and stats["mean_prob"].shape == (4,)
    np.testing.assert_allclose(np.asarray(stats["choice_share"]).sum(axis=1), 1.0, rtol=1e-6)
    assert float(stats["z"]) > 0.0
    assert not np.allclose(np.asarray(y), 0.0)
    # Deterministic under jit.
    y2, _ = jax.jit(lambda p, h: moe_ffn(p, h, cfg))(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), rtol=1e-5)


def test_moe_expert_parallel_matches_single_device():
    """The dropless layer under `ep` (tokens replicated over the expert axis,
    each rank its own experts' groups, a psum) == the single-device layer,
    output and gradients."""
    cfg = _moe_cfg()
    params = init_moe_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16))

    def loss(p, h, **kw):
        y, stats = moe_ffn(p, h, cfg, **kw)
        return jnp.sum(y ** 2) + stats["z"], (y, stats)

    (_, (ref, ref_stats)), ref_grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(params, x)

    mesh = build_mesh(MeshSpec(data=2, expert=4))
    rules = resolve_rules("ep")
    with mesh:
        (_, (out, stats)), grads = jax.jit(jax.value_and_grad(
            lambda p, h: loss(p, h, rules=rules, mesh=mesh), argnums=(0, 1), has_aux=True)
        )(params, x)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=1e-5, rtol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(ref_stats), jax.tree_util.tree_leaves(stats)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(ref_grads), jax.tree_util.tree_leaves(grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4)


@pytest.fixture
def rt():
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


def test_dashboard_endpoints_and_timeline(rt):
    from ray_tpu.dashboard import Dashboard

    @ray_tpu.remote
    def f(x):
        return x + 1

    ray_tpu.get([f.remote(i) for i in range(4)], timeout=60)
    dash = Dashboard(port=0)
    try:
        def fetch(path):
            with urllib.request.urlopen(dash.url + path, timeout=30) as r:
                return json.loads(r.read())

        nodes = fetch("/api/nodes")
        assert any(n["is_head"] for n in nodes)
        tasks = fetch("/api/tasks")
        assert any(t["state"] == "FINISHED" for t in tasks)
        metrics = fetch("/api/metrics")
        assert metrics["tasks_finished"] >= 4
        tl = fetch("/api/timeline")
        assert len(tl) >= 4
        # Task/span rows are complete ("X") events; object lifecycle
        # markers (create/seal/free) ride along as instants ("i").
        assert all(
            (ev["ph"] == "X" and ev["dur"] >= 1)
            or (ev["ph"] == "i" and ev["cat"] == "object")
            for ev in tl
        )
        assert any(ev["ph"] == "X" and ev["dur"] >= 1 for ev in tl)
        assert fetch("/api/summary").get("FINISHED", 0) >= 4
        # unknown route -> 404 with route listing
        try:
            urllib.request.urlopen(dash.url + "/nope", timeout=30)
            assert False
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        dash.shutdown()


def test_cli_status_and_timeline(tmp_path, monkeypatch):
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli", "status"],
        capture_output=True,
        text=True,
        timeout=120,
        env={**os.environ, "PYTHONPATH": REPO_ROOT},
    )
    assert out.returncode == 0, out.stderr[-500:]
    data = json.loads(out.stdout)
    assert "nodes" in data and "resources" in data

    tl_path = tmp_path / "tl.json"
    out2 = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli", "timeline", "-o", str(tl_path)],
        capture_output=True,
        text=True,
        timeout=120,
        env={**os.environ, "PYTHONPATH": REPO_ROOT},
    )
    assert out2.returncode == 0, out2.stderr[-500:]
    # fresh runtime: no tasks, only the runtime's own start (a lifecycle span: always recorded)
    assert [e["name"] for e in json.loads(tl_path.read_text())] == ["runtime::init"]
