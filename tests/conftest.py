"""Test config.

JAX tests run on a virtual 8-device CPU mesh (the TPU analogue of the
reference's fake multi-node fixtures): env must be set before jax import.
Core runtime tests boot a real multi-process runtime per fixture, mirroring
ray_start_regular / ray_start_cluster (ray: python/ray/tests/conftest.py:305,386).
"""

import os

# Force-override: whatever the outer environment pins (a machine with a chip
# sets JAX_PLATFORMS=tpu,cpu), unit tests always run on the virtual 8-device
# CPU mesh and never open an accelerator.  The config knob is flipped too,
# post-import and before any backend is initialized, so a platform plugin
# that registered itself at import cannot win.  Spawned worker processes
# inherit this env.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")
# Lock-discipline assertions on for the whole suite (SURVEY §5.2 — the
# Python analogue of the reference's clang GUARDED_BY + TSAN CI): every
# "caller holds self.lock" internal verifies ownership at entry.
os.environ.setdefault("RAY_TPU_DEBUG_LOCKS", "1")

import time

import jax

jax.config.update("jax_platforms", "cpu")

import pytest


def wait_for_resource_release(resource, target, timeout_s=10.0):
    """Poll available_resources()[resource] until it returns to `target`
    (lease reuse holds reservations across same-shape tasks; the pool
    only refills once the lease idles out or is demand-revoked).  Shared
    by the autoscaler test files — returns the last observed value so
    callers can assert on it."""
    import ray_tpu

    deadline = time.monotonic() + timeout_s
    avail = None
    while time.monotonic() < deadline:
        avail = ray_tpu.available_resources().get(resource)
        if avail == target:
            break
        time.sleep(0.2)
    return avail


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: minutes-scale soak/e2e tests excluded from tier-1 "
        "(-m 'not slow'); run explicitly via -m slow",
    )


@pytest.fixture
def ray_start_regular():
    import ray_tpu

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    yield cluster
    cluster.shutdown()


# -- ops with a Mosaic kernel beside a plain form (ray_tpu/ops/kernel_pair.py) ------------------


def as_lowered_for_tpu(patch):
    """From here to `patch`'s undoing, every op's choice of form is the one a
    step LOWERED FOR TPU makes: the kernel form at shapes the kernels take, the
    plain form at the others.  `patch` is a `pytest.MonkeyPatch`.  The caller
    makes the kernels it will reach runnable here (`interpret=True` partials)."""
    from ray_tpu.ops import kernel_pair

    patch.setattr(kernel_pair, "dispatch",
                  lambda takes, kernel, plain, *inputs: kernel(*inputs) if takes else plain(*inputs))


def only_the_delta_convolution_runs_its_kernels(patch, rows=32):
    """From here to `patch`'s undoing a delta layer's convolution ALONE takes
    its kernel form, interpreted in blocks of `rows` positions
    (`ops/pallas/delta_conv.py`), as in a step lowered for TPU; every other op
    keeps the plain form it has on the CPU.  For a whole model at heads of 128."""
    import functools

    from ray_tpu.ops import delta_conv, kernel_pair
    from ray_tpu.ops.pallas import delta_conv as kernels

    mine = (delta_conv._kernel_forward, delta_conv._kernel_backward)
    for name in ("conv_fwd", "conv_bwd"):
        patch.setattr(kernels, name, functools.partial(getattr(kernels, name), rows=rows, interpret=True))
    patch.setattr(kernel_pair, "dispatch",
                  lambda takes, kernel, plain, *inputs: kernel(*inputs) if takes and kernel in mine else plain(*inputs))


def without_file_locations(text: str) -> str:
    """Lowered text with `debug_info=True`, less the lines that name a source
    FILE (`#locN = loc("/path/x.py":..)`).  A function jax traced earlier in
    the process (a jitted rung of the experts, a kernel's wrapper) keeps the
    frames of its first trace, another test file's among them, so a statement
    about what a program does NOT hold reads its ops' and scopes' names alone:
    with the frames it held or failed by which file a worker ran before."""
    import re

    return "\n".join(line for line in text.splitlines() if not re.match(r'#loc\d+ = loc\("[^"]*\.py":', line))


@pytest.fixture
def lowered_for_tpu_on_the_cpu(monkeypatch):
    as_lowered_for_tpu(monkeypatch)


@pytest.fixture
def no_kernel_runs(monkeypatch):
    """As `lowered_for_tpu_on_the_cpu`, but a choice that would take a kernel fails the test."""
    from ray_tpu.ops import kernel_pair

    def dispatch(takes, kernel, plain, *inputs):
        assert not takes, f"a step lowered for TPU would call {kernel}"
        return plain(*inputs)

    monkeypatch.setattr(kernel_pair, "dispatch", dispatch)
