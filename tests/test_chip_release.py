"""A chip belongs to one process at a time, and a process that held chips
releases them while it exits: the two ends of that hand-over.  `shutdown`
returns when this host's workers are gone, and a TPU worker outwaits a
predecessor that is still going (`/dev/vfio/<group>`: "Device or resource
busy") before it lets libtpu open the chips."""

import errno
import os
import time

import pytest

import ray_tpu
from ray_tpu._private.runtime import _exited
from ray_tpu.train import backend


@pytest.fixture
def vfio(monkeypatch):
    """Two group files; `answers[path]` is what each open of it does in turn
    (an errno, or None for success), the last one repeating."""
    answers, opened, slept = {}, [], []
    monkeypatch.setattr("glob.glob", lambda pattern: sorted(answers) if pattern == "/dev/vfio/[0-9]*" else [])

    def fake_open(path, flags):
        opened.append(path)
        todo = answers[path]
        code = todo.pop(0) if len(todo) > 1 else todo[0]
        if code is not None:
            raise OSError(code, os.strerror(code), path)
        return 1000

    monkeypatch.setattr(os, "open", fake_open)
    monkeypatch.setattr(os, "close", lambda fd: None)
    monkeypatch.setattr(time, "sleep", slept.append)
    return answers, opened, slept


def test_wait_for_chips_outwaits_a_busy_group(vfio):
    answers, opened, slept = vfio
    answers.update({"/dev/vfio/0": [errno.EBUSY, errno.EBUSY, None], "/dev/vfio/1": [None]})
    backend._wait_for_chips()
    assert opened == ["/dev/vfio/0"] * 3 + ["/dev/vfio/1"] and slept == [0.25, 0.25]


def test_wait_for_chips_leaves_every_other_error_to_libtpu(vfio):
    answers, opened, slept = vfio
    answers.update({"/dev/vfio/0": [errno.EACCES], "/dev/vfio/1": [errno.ENOENT]})
    backend._wait_for_chips()
    assert opened == ["/dev/vfio/0", "/dev/vfio/1"] and slept == []


def test_wait_for_chips_gives_up_at_its_deadline(vfio):
    answers, opened, slept = vfio
    answers["/dev/vfio/0"] = [errno.EBUSY]
    assert backend._wait_for_chips(timeout_s=0.0) < 1.0 and opened == ["/dev/vfio/0"] and slept == []


def test_wait_for_chips_is_nothing_on_a_host_without_group_files(vfio):
    _, opened, slept = vfio
    assert backend._wait_for_chips() < 1.0 and opened == [] and slept == []


def test_exited_counts_a_zombie_as_gone_and_a_running_process_as_not():
    assert not _exited(os.getpid())
    pid = os.fork()
    if pid == 0:
        os._exit(0)
    try:
        deadline = time.monotonic() + 10
        while not _exited(pid) and time.monotonic() < deadline:
            time.sleep(0.01)
        os.kill(pid, 0)  # still in the process table: a zombie, not reaped yet
        assert _exited(pid)
    finally:
        os.waitpid(pid, 0)
    assert _exited(pid) and _exited(None)


def test_shutdown_returns_when_its_workers_are_gone():
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote
        def pid_of_worker():
            return os.getpid()

        pids = set(ray_tpu.get([pid_of_worker.remote() for _ in range(4)], timeout=60))
    finally:
        ray_tpu.shutdown()
    assert pids and all(_exited(pid) for pid in pids)
