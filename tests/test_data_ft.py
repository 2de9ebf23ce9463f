"""Data-plane fault-tolerance policy: `on_block_error` accounting,
system-vs-application retry classes, datasource read retries, pool
supervision units, and owned-ref teardown.

Fast deterministic coverage for the machinery the `data_chaos` tier
exercises under real SIGKILLs (reference policy surface: Ray Data
`max_errored_blocks` / actor-pool supervision,
python/ray/data/_internal/execution/):

- "skip" counts errored blocks EXACTLY (never silently): counts, block
  ids, the `ray_tpu_data_blocks_errored_total` counter and the
  `data.block_errored` event all agree;
- "raise" surfaces the first UDF failure as a `DataBlockError` carrying
  the block id and stage name;
- SYSTEM errors (here a synthetic `ObjectLostError` from the UDF — the
  same `.cause` shape a dead actor produces) are retried with bound +
  jittered backoff and never consume the errored-block budget;
- `_read_with_retries` retries transient `OSError`s with per-file
  attribution and never retries `FileNotFoundError`;
- `_ActorPool` replacement honors the restart budget; `release_owned`
  is idempotent and empties the ledger.
"""

import os

import numpy as np
import pytest

import ray_tpu
from ray_tpu import data as rd
from ray_tpu._private import api as _api
from ray_tpu._private import events as _events
from ray_tpu._private.ray_config import RayConfig
from ray_tpu.data.datasource import _read_with_retries
from ray_tpu.data.execution import (StreamingExecutor, _ActorPool,
                                    _actor_dead, _backoff_delay,
                                    _is_system_error, _robust_get)
from ray_tpu.exceptions import (ActorDiedError, DataBlockError,
                                ObjectLostError, RayTaskError)

BLOCK_ROWS = 50  # range(400, parallelism=8) → 8 blocks of 50 rows


@pytest.fixture(scope="module")
def ray_session():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_workers=2, max_workers=6)
    yield
    ray_tpu.shutdown()


def _block_of(batch) -> int:
    return int(batch["id"][0]) // BLOCK_ROWS


def _failing(bad_blocks):
    rows = BLOCK_ROWS  # captured by value: workers can't import this module

    def fn(batch):
        bidx = int(batch["id"][0]) // rows
        if bidx in bad_blocks:
            raise ValueError(f"udf boom on block {bidx}")
        return {"id": batch["id"]}

    return fn


def _pipeline(fn):
    return rd.range(400, parallelism=8).map_batches(fn)


def _metric_total(name: str) -> float:
    from ray_tpu.util import metrics

    return sum(value
               for m in metrics.snapshot() if m["name"] == name
               for _tags, value in m["series"])


def _drain(ex: StreamingExecutor) -> list:
    blocks = []
    try:
        for item in ex.execute():
            got = (_robust_get(item, rng=ex._rng)
                   if hasattr(item, "hex") else item)
            ex._free_if_owned(item)
            blocks.extend(got if isinstance(got, list) else [got])
    finally:
        ex.release_owned()
    return blocks


# ------------------------------------------------------- policy accounting


def test_skip_policy_counts_exactly(ray_session):
    _events.reset()
    errored0 = _metric_total("ray_tpu_data_blocks_errored_total")
    ex = StreamingExecutor(_pipeline(_failing({1, 5}))._stages(),
                           on_block_error="skip")
    blocks = _drain(ex)
    ids = np.sort(np.concatenate([np.asarray(b["id"]) for b in blocks]))
    want = np.array([i for i in range(400)
                     if i // BLOCK_ROWS not in (1, 5)])
    assert np.array_equal(ids, want)  # exactly the 2 bad blocks dropped
    assert ex.errored_blocks == 2
    assert len(ex.errored_block_ids) == 2
    assert _metric_total("ray_tpu_data_blocks_errored_total") == errored0 + 2
    ev = [e for e in _events.recent() if e["etype"] == "data.block_errored"]
    assert len(ev) == 2 and all(e["block_id"] in ex.errored_block_ids
                                for e in ev)


def test_skip_policy_through_dataset_surface(ray_session):
    ds = _pipeline(_failing({3})).execute_options(on_block_error="skip")
    rows = ds.take_all()
    assert len(rows) == 350  # one 50-row block skipped, rest intact
    assert {r["id"] // BLOCK_ROWS for r in rows} == set(range(8)) - {3}


def test_raise_policy_surfaces_block_id(ray_session):
    with pytest.raises(DataBlockError) as ei:
        _pipeline(_failing({2})).take_all()
    err = ei.value
    assert err.kind == "application"
    assert isinstance(err.block_id, int)
    assert err.stage  # stage name attached
    assert "udf boom" in str(err)


def test_max_errored_blocks_budget(ray_session):
    # budget 1, two bad blocks → the second skip overflows and raises
    ds = _pipeline(_failing({1, 5})).execute_options(
        on_block_error="skip", max_errored_blocks=1)
    with pytest.raises(DataBlockError) as ei:
        ds.take_all()
    assert ei.value.kind == "application"
    assert "max_errored_blocks=1" in str(ei.value)
    # budget 1, one bad block → fits
    ds = _pipeline(_failing({5})).execute_options(
        on_block_error="skip", max_errored_blocks=1)
    assert len(ds.take_all()) == 350


def _flaky_once(dirpath):
    """Raises a SYSTEM-shaped error the FIRST time each bad block runs —
    the retry (a fresh task) sees the sentinel file and succeeds."""
    rows = BLOCK_ROWS

    def fn(batch):
        import os as _os

        from ray_tpu.exceptions import ObjectLostError as _Lost

        bidx = int(batch["id"][0]) // rows
        sentinel = _os.path.join(dirpath, f"b{bidx}")
        if bidx in (2, 6) and not _os.path.exists(sentinel):
            open(sentinel, "w").close()
            raise _Lost(f"synthetic block loss (block {bidx})")
        return {"id": batch["id"]}

    return fn


def test_system_retries_do_not_consume_errored_budget(ray_session, tmp_path):
    retries0 = _metric_total("ray_tpu_data_block_retries_total")
    # max_errored_blocks=0: ANY application skip would raise immediately —
    # proving the system-error path never touches that budget
    ex = StreamingExecutor(_pipeline(_flaky_once(str(tmp_path)))._stages(),
                           on_block_error="skip", max_errored_blocks=0)
    blocks = _drain(ex)
    ids = np.sort(np.concatenate([np.asarray(b["id"]) for b in blocks]))
    assert np.array_equal(ids, np.arange(400))  # every row recovered
    assert ex.errored_blocks == 0
    assert ex.errored_block_ids == []
    assert _metric_total("ray_tpu_data_block_retries_total") >= retries0 + 2


def test_system_retry_budget_exhaustion_raises_system_kind(
        ray_session, monkeypatch):
    def always_lost(batch):
        raise ObjectLostError("every attempt loses the block")

    monkeypatch.setenv("RAY_TPU_DATA_MAX_BLOCK_RETRIES", "1")
    monkeypatch.setenv("RAY_TPU_DATA_RETRY_BACKOFF_S", "0.01")
    RayConfig.reset()
    try:
        ex = StreamingExecutor(
            rd.range(40, parallelism=2).map_batches(always_lost)._stages(),
            on_block_error="skip")
        with pytest.raises(DataBlockError) as ei:
            _drain(ex)
        assert ei.value.kind == "system"
        assert ex.errored_blocks == 0  # system failures are never "errored"
    finally:
        monkeypatch.delenv("RAY_TPU_DATA_MAX_BLOCK_RETRIES")
        monkeypatch.delenv("RAY_TPU_DATA_RETRY_BACKOFF_S")
        RayConfig.reset()


def test_error_classes_and_backoff_bounds():
    assert _is_system_error(ObjectLostError("x"))
    assert _is_system_error(ActorDiedError("x"))
    assert _is_system_error(RayTaskError("f", "tb", ActorDiedError("x")))
    assert not _is_system_error(RayTaskError("f", "tb", ValueError("x")))
    assert not _is_system_error(ValueError("x"))
    import random

    rng = random.Random(7)
    for attempt in range(12):
        d = _backoff_delay(attempt, 0.25, rng)
        assert 0.0 <= d <= 0.25 * 8  # full jitter, capped at 8x base


def test_executor_rejects_bad_policy():
    with pytest.raises(ValueError, match="on_block_error"):
        StreamingExecutor([], on_block_error="explode")


# ------------------------------------------------------ datasource retries


def test_read_retries_transient_io(monkeypatch):
    monkeypatch.setenv("RAY_TPU_DATA_READ_RETRY_BACKOFF_S", "0.001")
    RayConfig.reset()
    try:
        calls = []

        def reader(path):
            calls.append(path)
            if len(calls) < 3:
                raise OSError("transient EIO")
            return [{"rows": path}]

        assert _read_with_retries(reader, "/d/f.csv") == [{"rows": "/d/f.csv"}]
        assert len(calls) == 3  # default budget: 2 retries on top of try 1
    finally:
        monkeypatch.delenv("RAY_TPU_DATA_READ_RETRY_BACKOFF_S")
        RayConfig.reset()


def test_read_retries_exhaustion_attributes_file(monkeypatch):
    monkeypatch.setenv("RAY_TPU_DATA_READ_RETRY_BACKOFF_S", "0.001")
    RayConfig.reset()
    try:
        calls = []

        def reader(path):
            calls.append(path)
            raise OSError("disk on fire")

        with pytest.raises(OSError) as ei:
            _read_with_retries(reader, "/data/broken.parquet")
        assert "/data/broken.parquet" in str(ei.value)
        assert "3 attempt(s)" in str(ei.value)
        assert len(calls) == 3
    finally:
        monkeypatch.delenv("RAY_TPU_DATA_READ_RETRY_BACKOFF_S")
        RayConfig.reset()


def test_read_never_retries_missing_file():
    calls = []

    def reader(path):
        calls.append(path)
        raise FileNotFoundError(path)

    with pytest.raises(FileNotFoundError):
        _read_with_retries(reader, "/gone.csv")
    assert len(calls) == 1  # a missing file will not reappear


# ------------------------------------------------- pool supervision units


def _actor_stage():
    ds = rd.range(100).map_batches(lambda b: b, compute="actors",
                                   concurrency=2)
    return next(s for s in ds._stages() if s.compute == "actors")


def _wait_dead(actor, timeout=20.0):
    import time

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if _actor_dead(actor):
            return True
        time.sleep(0.1)
    return False


def test_pool_replaces_dead_actor_and_returns_orphans(ray_session):
    pool = _ActorPool(_actor_stage(), size=2)
    try:
        # a failure on a LIVE actor is a plain task failure: no replacement
        pool._outstanding["aa" * 8] = 0
        pool._load[0] += 1
        assert pool.note_failed("aa" * 8) == ([], 0)
        assert pool.replacements == 0

        victim = pool.actors[0]
        ray_tpu.kill(victim)
        assert _wait_dead(victim), "killed actor never reported dead"
        pool._outstanding["bb" * 8] = 0  # the failure that trips the probe
        pool._outstanding["cc" * 8] = 0  # its in-flight sibling (orphan)
        pool._outstanding["dd" * 8] = 1  # survivor's work: must be kept
        pool._load[0] += 2
        orphans, replaced = pool.note_failed("bb" * 8)
        assert orphans == ["cc" * 8]
        assert replaced == 1
        assert pool.replacements == 1
        assert len(pool.actors) == 2  # back at target size
        assert pool._outstanding == {"dd" * 8: 0}  # survivor reindexed
    finally:
        pool.shutdown()


def test_pool_restart_budget_zero_means_no_respawn(ray_session, monkeypatch):
    monkeypatch.setenv("RAY_TPU_DATA_ACTOR_RESTART_BUDGET", "0")
    RayConfig.reset()
    try:
        pool = _ActorPool(_actor_stage(), size=1)
        try:
            victim = pool.actors[0]
            ray_tpu.kill(victim)
            assert _wait_dead(victim)
            pool._outstanding["ee" * 8] = 0
            pool._load[0] += 1
            with pytest.raises(DataBlockError) as ei:
                pool.note_failed("ee" * 8)
            assert ei.value.kind == "system"
            assert pool.replacements == 0
        finally:
            pool.shutdown()
    finally:
        monkeypatch.delenv("RAY_TPU_DATA_ACTOR_RESTART_BUDGET")
        RayConfig.reset()


# ------------------------------------------------------- owned-ref ledger


def test_release_owned_is_idempotent_and_empties_ledger(ray_session):
    ex = StreamingExecutor(rd.range(400, parallelism=8)
                           .map_batches(lambda b: b)._stages())
    gen = ex.execute()
    next(gen)  # partial consumption leaves intermediate refs owned
    gen.close()  # generator finally also releases — must not conflict
    ex.release_owned()
    assert not ex.owned
    ex.release_owned()  # second call is a no-op
    assert not ex.owned


def test_error_of_reports_errors_without_raising(ray_session):
    @ray_tpu.remote
    def ok():
        return 1

    @ray_tpu.remote
    def boom():
        raise RuntimeError("task exploded")

    w = _api._get_worker()
    good, bad = ok.remote(), boom.remote()
    ray_tpu.wait([good, bad], num_returns=2, timeout=30)
    assert w.error_of(good.hex()) is None
    err = w.error_of(bad.hex())
    assert isinstance(err, RayTaskError)
    assert isinstance(err.cause, RuntimeError)
