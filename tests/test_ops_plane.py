"""Ops plane: CLI status/list/logs, log monitor, job submission.

(reference test pattern: dashboard/state CLI tested against live single-node
sessions — SURVEY.md §4; jobs via JobSubmissionClient SDK e2e.)
"""

from __future__ import annotations

import io
import json
import os
import time
from contextlib import redirect_stdout

import pytest

import ray_tpu


@pytest.fixture
def session():
    ray_tpu.shutdown()
    ctx = ray_tpu.init(num_cpus=4, num_workers=1, max_workers=4)
    yield ctx
    ray_tpu.shutdown()


def _run_cli(argv) -> str:
    from ray_tpu.scripts import cli

    buf = io.StringIO()
    with redirect_stdout(buf):
        cli.main(argv)
    return buf.getvalue()


def test_cli_status(session):
    out = _run_cli(["--session", session["session_dir"], "status"])
    assert "workers:" in out
    assert "CPU" in out
    out_json = _run_cli(["--session", session["session_dir"], "status", "--json"])
    state = json.loads(out_json)
    assert state["num_workers"] >= 1


def test_cli_list_nodes_and_actors(session):
    @ray_tpu.remote
    class A:
        def ping(self):
            return 1

    a = A.options(name="cli-probe").remote()
    ray_tpu.get(a.ping.remote())
    nodes = json.loads(_run_cli(["--session", session["session_dir"], "list", "nodes"]))
    assert any(n["alive"] for n in nodes)
    actors = json.loads(_run_cli(["--session", session["session_dir"], "list", "actors"]))
    assert any(x.get("name") == "cli-probe" for x in actors)
    ray_tpu.kill(a)


def test_cli_logs_lists_files(session):
    # worker-0.log exists once the pre-spawned worker starts
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        out = _run_cli(["--session", session["session_dir"], "logs"])
        if "worker-0.log" in out:
            return
        time.sleep(0.2)
    raise AssertionError(f"no worker log listed: {out!r}")


def test_log_monitor_streams_appended_lines(tmp_path):
    from ray_tpu._private.log_monitor import LogMonitor

    log_dir = tmp_path / "logs"
    log_dir.mkdir()
    seen = []
    mon = LogMonitor(str(log_dir), sink=lambda src, line: seen.append((src, line)),
                     poll_interval_s=0.05).start()
    try:
        with open(log_dir / "worker-7.log", "a") as f:
            f.write("hello\nworld\n")
            f.flush()
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and len(seen) < 2:
            time.sleep(0.05)
        # partial lines are held back until the newline arrives
        with open(log_dir / "worker-7.log", "a") as f:
            f.write("par")
            f.flush()
        time.sleep(0.2)
        with open(log_dir / "worker-7.log", "a") as f:
            f.write("tial\n")
            f.flush()
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and len(seen) < 3:
            time.sleep(0.05)
    finally:
        mon.stop()
    assert ("worker-7", "hello") in seen
    assert ("worker-7", "world") in seen
    assert ("worker-7", "partial") in seen


def test_job_submit_succeeds_and_logs(session):
    from ray_tpu.job_submission import JobSubmissionClient

    client = JobSubmissionClient()
    job_id = client.submit_job(
        entrypoint="python -c \"print('hello from job'); print(6*7)\"")
    status = client.wait_until_finished(job_id, timeout=60)
    assert status == "SUCCEEDED"
    logs = client.get_job_logs(job_id)
    assert "hello from job" in logs
    assert "42" in logs
    jobs = client.list_jobs()
    assert any(j["job_id"] == job_id and j["status"] == "SUCCEEDED" for j in jobs)


def test_job_failure_reported(session):
    from ray_tpu.job_submission import JobSubmissionClient

    client = JobSubmissionClient()
    job_id = client.submit_job(entrypoint="python -c 'raise SystemExit(3)'")
    assert client.wait_until_finished(job_id, timeout=60) == "FAILED"


def test_job_stop(session):
    from ray_tpu.job_submission import JobSubmissionClient

    client = JobSubmissionClient()
    job_id = client.submit_job(entrypoint="python -c 'import time; time.sleep(60)'")
    # let it actually start
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if client.get_job_status(job_id) == "RUNNING":
            break
        time.sleep(0.1)
    client.stop_job(job_id)
    assert client.wait_until_finished(job_id, timeout=30) == "STOPPED"


def test_worker_stack_dump(session):
    """On-demand live thread stacks from a worker through the control plane
    (reference capability: dashboard reporter py-spy profiling)."""
    import time

    from ray_tpu._private import api as _api

    @ray_tpu.remote
    class Sleeper:
        def nap(self):
            time.sleep(5)
            return "done"

    s = Sleeper.remote()
    ref = s.nap.remote()
    time.sleep(0.5)  # ensure the method is mid-sleep
    w = _api._worker
    workers = w.rpc({"type": "list_workers"})["workers"]
    target = next(x for x in workers if x["actor_id"])
    reply = w.rpc({"type": "worker_stacks", "wid": target["wid"]})
    assert reply["ok"], reply
    assert "nap" in reply["stacks"] or "sleep" in reply["stacks"]
    assert ray_tpu.get(ref, timeout=30) == "done"
    # dead-worker error path
    bad = w.rpc({"type": "worker_stacks", "wid": "nonexistent"})
    assert not bad.get("ok")


def test_cli_list_tasks_objects_workers(session):
    """State API breadth: `ray_tpu list tasks|objects|workers`
    (reference: util/state/state_cli.py `ray list`)."""
    import json as _json

    import numpy as np

    @ray_tpu.remote
    def work(x):
        return x + 1

    assert ray_tpu.get(work.remote(1), timeout=30) == 2
    big = ray_tpu.put(np.zeros(300_000))
    # `put` reports the object one-way on the driver's connection and the
    # CLI lists through a connection of its own, which the GCS serves on
    # another thread: a round trip on the driver's connection, in order
    # behind the report, says that the row is in the table
    from ray_tpu.util import state

    assert state.list_objects(filters=[("object_id", "=", big.hex())])
    sd = session["session_dir"]
    out = _run_cli(["--session", sd, "list", "objects"])
    rows = _json.loads(out)
    assert any(r["object_id"] == big.hex() for r in rows)
    out = _run_cli(["--session", sd, "list", "workers"])
    assert any(w["kind"] == "driver" for w in _json.loads(out))
    out = _run_cli(["--session", sd, "list", "tasks"])
    assert isinstance(_json.loads(out), list)
    del big
