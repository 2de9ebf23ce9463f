"""OOM-defense TPU safety + owner-death stub handling + fn-store pinning.

Round-4 advisor fixes: the OOM killer must not SIGKILL a worker holding TPU
chips (a killed process may leave its chip unusable until the runtime
releases it — reference analogue: worker_killing_policy keeps GPU-group
workers last); chips of an OOM-killed worker are quarantined, not returned;
a pending direct-result stub whose owner dies fails with OwnerDiedError
(reference: ray.exceptions.OwnerDiedError) instead of blocking waiters; and
fn:-store eviction never drops blobs still referenced by pending/running
specs or retained lineage.
"""

import collections
import time

import pytest

from ray_tpu._private.gcs import DEFAULT_NODE, GcsServer, _Worker
from ray_tpu._private.ray_config import RayConfig


class _FakeConn:
    """Records GCS replies/pushes without a real socket."""

    def __init__(self):
        self.sent = []

    def send(self, msg):
        self.sent.append(msg)


@pytest.fixture
def gcs(tmp_path):
    srv = GcsServer(
        socket_path=str(tmp_path / "gcs.sock"),
        total_resources={"CPU": 8.0, "TPU": 4.0},
        spawn_worker_cb=lambda *a, **k: None,
    )
    yield srv
    try:
        srv.stop()
    except Exception:
        pass


def _add_worker(gcs, wid, pid, chips=(), running=True):
    w = _Worker(wid, _FakeConn(), pid, "worker", DEFAULT_NODE,
                tpu_chips=chips)
    if running:
        w.idle = False
        w.running_tasks["t-" + wid] = {
            "kind": "task", "task_id": "t-" + wid, "_ts": float(pid),
            "retries_used": 0, "max_retries": 3, "num_returns": 1}
    gcs.workers[wid] = w
    return w


def test_oom_victim_prefers_chip_free_worker(gcs):
    _add_worker(gcs, "w-chip", pid=100, chips=(0, 1))
    _add_worker(gcs, "w-plain", pid=200)
    pid, _why = gcs._pick_oom_victim()
    assert pid == 200


def test_oom_victim_never_tpu_worker_by_default(gcs):
    _add_worker(gcs, "w-chip", pid=100, chips=(0, 1))
    assert gcs._pick_oom_victim() is None


def test_oom_victim_tpu_worker_requires_opt_in(gcs, monkeypatch):
    _add_worker(gcs, "w-chip", pid=100, chips=(0, 1))
    monkeypatch.setenv("RAY_TPU_OOM_KILL_TPU_WORKERS", "1")
    RayConfig.reset()
    try:
        pid, _why = gcs._pick_oom_victim()
        assert pid == 100
    finally:
        monkeypatch.delenv("RAY_TPU_OOM_KILL_TPU_WORKERS")
        RayConfig.reset()


def test_oom_killed_chip_worker_quarantines_chips(gcs):
    import time as _time

    node = gcs.nodes[DEFAULT_NODE]
    w = _add_worker(gcs, "w-chip", pid=100, chips=(0, 1))
    node.chip_pool = [2, 3]  # 0,1 are held by the worker
    w.oom_why = "killed: host memory over threshold"
    w.oom_ts = _time.monotonic()
    gcs._on_worker_death("w-chip")
    assert sorted(node.quarantined_chips) == [0, 1]
    assert sorted(node.chip_pool) == [2, 3]  # wedge-suspect chips withheld


def test_stale_oom_tag_does_not_quarantine(gcs):
    """An oom_why from a kill that never landed (tag older than the 30s
    freshness window) must not quarantine chips on an unrelated death."""
    node = gcs.nodes[DEFAULT_NODE]
    w = _add_worker(gcs, "w-chip", pid=100, chips=(0, 1))
    node.chip_pool = [2, 3]
    w.oom_why = "killed: host memory over threshold"
    w.oom_ts = 0.0  # ancient
    gcs._on_worker_death("w-chip")
    assert node.quarantined_chips == []
    assert sorted(node.chip_pool) == [0, 1, 2, 3]


def test_unquarantine_chips_rpc(gcs):
    node = gcs.nodes[DEFAULT_NODE]
    node.quarantined_chips = [0, 1, 5]
    conn = _FakeConn()
    gcs._handle(conn, {"type": "unquarantine_chips", "rid": 1,
                       "chips": [0, 5]}, None)
    assert sorted(conn.sent[-1]["restored"]) == [0, 5]
    assert node.quarantined_chips == [1]
    assert 0 in node.chip_pool and 5 in node.chip_pool
    # None = restore everything
    gcs._handle(conn, {"type": "unquarantine_chips", "rid": 2}, None)
    assert node.quarantined_chips == []
    assert 1 in node.chip_pool


def test_normal_chip_worker_death_returns_chips(gcs):
    node = gcs.nodes[DEFAULT_NODE]
    _add_worker(gcs, "w-chip", pid=100, chips=(0, 1))
    node.chip_pool = [2, 3]
    gcs._on_worker_death("w-chip")
    assert node.quarantined_chips == []
    assert sorted(node.chip_pool) == [0, 1, 2, 3]


def test_quarantined_chips_in_list_nodes(gcs):
    gcs.nodes[DEFAULT_NODE].quarantined_chips = [7]
    conn = _FakeConn()
    gcs._handle(conn, {"type": "list_nodes", "rid": 1}, None)
    nodes = conn.sent[-1]["nodes"]
    assert nodes[0]["quarantined_chips"] == [7]


def test_owner_death_fails_pending_stub(gcs):
    """A will_publish promise from a process that then dies must error the
    stub (OwnerDiedError) and answer parked waiters, not strand them."""
    import ray_tpu._private.serialization as ser

    owner = _add_worker(gcs, "w-owner", pid=300, running=False)
    oid = "tdeadbeefr0000"
    gcs._handle(owner.conn, {"type": "will_publish", "oid": oid,
                             "wid": "w-owner"}, "w-owner")
    assert gcs.objects[oid]["status"] == "pending"
    assert gcs.objects[oid]["pub_wid"] == "w-owner"
    waiter = _FakeConn()
    gcs._wait_object(waiter, {"type": "wait_object", "oid": oid, "rid": 9,
                              "timeout": 60.0})
    assert not waiter.sent  # parked
    gcs._on_worker_death("w-owner")
    ent = gcs.objects[oid]
    assert ent["status"] == "error"
    assert waiter.sent, "waiter must be answered on owner death"
    err = ser.loads(ent["inline"])
    from ray_tpu.exceptions import OwnerDiedError

    assert isinstance(err, OwnerDiedError)


def test_published_object_unaffected_by_owner_death(gcs):
    """Once the owner publishes, its later death must not clobber the value."""
    owner = _add_worker(gcs, "w-owner", pid=300, running=False)
    oid = "tcafef00dr0000"
    gcs._handle(owner.conn, {"type": "will_publish", "oid": oid,
                             "wid": "w-owner"}, "w-owner")
    gcs._on_object_ready(oid, where="inline", inline=b"blob", size=4,
                         is_error=False)
    gcs._on_worker_death("w-owner")
    ent = gcs.objects[oid]
    assert ent["status"] != "error"
    assert ent["inline"] == b"blob"


def test_gcs_submit_clears_stale_publish_promise(gcs):
    """A direct spec redirected to the GCS path: the old owner's
    will_publish promise must be dropped so its death can't error the
    now-GCS-owned stub."""
    owner = _add_worker(gcs, "w-owner", pid=300, running=False)
    owner.idle = False  # not schedulable: the GCS task must stay pending
    oid = "tfeedf00dr0000"
    gcs._handle(owner.conn, {"type": "will_publish", "oid": oid,
                             "wid": "w-owner"}, "w-owner")
    assert gcs.objects[oid].get("pub_wid") == "w-owner"
    gcs._submit_task({"kind": "task", "task_id": "tfeedf00d",
                      "func": b"\x80\x04N.", "deps": [], "num_returns": 1,
                      "resources": {"CPU": 1.0}, "max_retries": 0,
                      "retries_used": 0, "name": "t", "strategy": None})
    assert "pub_wid" not in gcs.objects[oid]
    gcs._on_worker_death("w-owner")
    assert gcs.objects[oid]["status"] == "pending"  # not errored


@pytest.mark.parametrize("since_boot", [10.0, 1e6])
def test_fn_eviction_pins_referenced_shas(gcs, monkeypatch, since_boot):
    """fn: blobs referenced by pending specs / lineage survive eviction,
    and what nothing references or touched goes: also on a host that booted
    seconds ago, where the monotonic clock is less than the freshness
    window (keys that were never stamped must not look fresh there)."""
    conn = _FakeConn()
    # a pending task and a lineage entry each reference one sha
    gcs.pending_tasks.append({"kind": "task", "task_id": "tp",
                              "func_sha": "sha-pending", "num_returns": 1})
    gcs.lineage["tl"] = {"kind": "task", "task_id": "tl",
                         "func_sha": "sha-lineage", "num_returns": 1}
    gcs.kv["fn:sha-pending"] = b"P"
    gcs.kv["fn:sha-lineage"] = b"L"
    for i in range(2048):
        gcs.kv[f"fn:bulk{i:05d}"] = b"x"
    # the overflowing put triggers eviction of (len - 2048) oldest keys
    with monkeypatch.context() as clock:
        clock.setattr(time, "monotonic", lambda: since_boot)
        gcs._handle(conn, {"type": "kv_put", "rid": 1, "key": "fn:overflow",
                           "value": b"o"}, None)
    assert "fn:sha-pending" in gcs.kv
    assert "fn:sha-lineage" in gcs.kv
    # eviction still happened — oldest unpinned keys went
    n_fn = sum(1 for k in gcs.kv if k.startswith("fn:"))
    assert n_fn == 2048


def test_pinned_fn_keys_cover_actor_queues(gcs):
    a = collections.namedtuple("A", "queue")(
        queue=collections.deque([{"func_sha": "sha-actorq"}]))
    gcs.actors["a1"] = a
    assert "fn:sha-actorq" in gcs._pinned_fn_keys_locked()
