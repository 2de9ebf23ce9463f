"""The end of a session leaves no process behind: `ray_tpu.shutdown()` returns
with every worker it started reaped, a worker that is busy in a call that does
not return included, and a worker does not outlive a driver that was killed
from outside (which reaps nothing)."""

import os
import signal
import subprocess
import sys
import textwrap
import time

import pytest

import ray_tpu
from ray_tpu._private import api
from ray_tpu._private.procutil import drain_procs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _gone(pid: int) -> bool:
    """No such process, or one that is dead and only waits to be reaped."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] == "Z"
    except OSError:
        return True


def _wait_for(path: str, timeout_s: float = 60.0) -> str:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if os.path.exists(path) and os.path.getsize(path):
            with open(path) as f:
                return f.read()
        time.sleep(0.05)
    raise AssertionError(f"{path} was not written within {timeout_s} s")


# a task the driver hands to a leased worker itself runs on that worker's
# direct server's thread; one that goes through the GCS (what a task that asks
# for a chip does) runs on the worker's MAIN thread, which then does not see
# the closed socket until the task returns
DISPATCH = pytest.mark.parametrize("direct", ["1", "0"], ids=["direct", "through-the-gcs"])


@DISPATCH
@pytest.mark.parametrize("busy", ["task", "actor"])
def test_shutdown_reaps_a_worker_that_is_busy_in_a_call(tmp_path, monkeypatch, busy, direct):
    """A plain task, and an actor's method, asleep in C for far longer than
    `drain_procs` waits: every `Popen` of the node has a return code when
    `shutdown` returns, and no pid of them is alive."""
    monkeypatch.setenv("RAY_TPU_DIRECT_DISPATCH", direct)
    marker = str(tmp_path / "pid")

    def sleep_in_c(path):
        with open(path, "w") as f:
            f.write(str(os.getpid()))
        time.sleep(600)

    class Sleeper:
        def sleep(self, path):
            sleep_in_c(path)

    ray_tpu.init(num_cpus=2, num_workers=2, max_workers=3)
    try:
        if busy == "task":
            ref = ray_tpu.remote(sleep_in_c).remote(marker)
        else:
            ref = ray_tpu.remote(Sleeper).remote().sleep.remote(marker)
        busy_pid = int(_wait_for(marker))
        procs = list(api._node._procs)
        assert busy_pid in [p.pid for p in procs] and len(procs) >= 2
    finally:
        t = time.monotonic()
        ray_tpu.shutdown()
        took = time.monotonic() - t
    del ref
    assert all(p.returncode is not None for p in procs), [
        (p.pid, p.returncode) for p in procs]
    assert all(_gone(p.pid) for p in procs)
    assert took < 30.0


@pytest.mark.parametrize("stubborn", [False, True], ids=["exits", "has-to-be-killed"])
def test_drain_procs_returns_with_every_process_reaped(capfd, stubborn):
    """A process that outlasts the deadline is killed, waited for until it is
    dead, and named; one that exits in time is not mentioned."""
    code = "import time; time.sleep(600)" if stubborn else "pass"
    procs = [subprocess.Popen([sys.executable, "-c", code]) for _ in range(2)]
    drain_procs(procs, deadline_s=2.0 if not stubborn else 0.3)
    assert [p.returncode for p in procs] == [-signal.SIGKILL if stubborn else 0] * 2
    said = capfd.readouterr().err
    for p in procs:
        assert (f"worker {p.pid} had not exited" in said) == stubborn


@DISPATCH
def test_a_busy_worker_does_not_outlive_a_killed_driver(tmp_path, direct):
    """SIGKILL to a driver whose worker is inside a task that does not
    return: the driver reaps nothing, its GCS's socket closes, and every
    worker of the session is gone a few seconds later, the busy one too."""
    pids, busy = str(tmp_path / "pids"), str(tmp_path / "busy")
    driver = subprocess.Popen([sys.executable, "-c", textwrap.dedent(f"""
        import os, time
        import ray_tpu
        from ray_tpu._private import api

        def sleep_in_c(path):
            with open(path, "w") as f:
                f.write(str(os.getpid()))
            time.sleep(600)

        ray_tpu.init(num_cpus=2, num_workers=2, max_workers=3)
        ref = ray_tpu.remote(sleep_in_c).remote({busy!r})
        while not os.path.exists({busy!r}):
            time.sleep(0.05)
        with open({pids!r} + ".tmp", "w") as f:
            f.write(" ".join(str(p.pid) for p in api._node._procs))
        os.replace({pids!r} + ".tmp", {pids!r})
        time.sleep(600)
        """)], env={**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
                    "RAY_TPU_DIRECT_DISPATCH": direct})
    try:
        workers = [int(p) for p in _wait_for(pids, 120.0).split()]
        busy_pid = int(_wait_for(busy))
        assert busy_pid in workers and len(workers) >= 2
    finally:
        driver.kill()
        driver.wait(30)
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline and not all(_gone(p) for p in workers):
        time.sleep(0.1)
    left = [p for p in workers if not _gone(p)]
    for p in left:  # leave nothing behind whatever the verdict
        os.kill(p, signal.SIGKILL)
    assert not left, f"workers {left} outlived their driver (the busy one: {busy_pid})"
