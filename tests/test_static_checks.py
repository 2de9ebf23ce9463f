"""Tier-1 tooling check: the graft_check AST invariant suite.

Three halves (PR 10 + the interprocedural v2):

- the REAL tree must be clean: `python -m tools.graft_check` semantics —
  zero unsuppressed findings over ray_tpu/ with the checked-in baseline
  (every suppression justified, none stale) — in well under the 15s
  budget;

- every checker must actually FIRE: per-checker negative tests feed small
  fixture snippets (an `await` under a lock, a missing persist, a lock-
  order cycle split across methods, a handler reading a field no client
  sends, ...) and assert the right check id at the right line — and a
  registry test asserts EVERY id `--list` reports has a firing fixture,
  so a future checker can't land untested;

- the incremental machinery works: the on-disk analysis cache replays
  findings and call-graph summaries without reparsing, `--changed`/scope
  filters reporting while analysis stays tree-wide, and `--format json`
  emits CI-consumable output.
"""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.graft_check import (load_baseline, run_checks,  # noqa: E402
                               run_default)
from tools.graft_check.checkers import (AsyncBlockingChecker,  # noqa: E402
                                        BoundedRetryChecker,
                                        EventLiteralChecker,
                                        LockDisciplineChecker,
                                        LockOrderChecker,
                                        MetricNamesChecker,
                                        PersistOrderChecker,
                                        ResourceLeakChecker,
                                        RpcFieldSchemaChecker,
                                        RpcPairingChecker,
                                        ShmLifecycleChecker,
                                        SilentSwallowChecker,
                                        SpmdConsistencyChecker,
                                        TransitiveBlockingChecker,
                                        all_check_ids)


def _run(tree_dir, checkers, **kw):
    return run_checks(str(tree_dir), checkers, **kw)


def _ids(report):
    return [(f.check_id, f.path, f.line) for f in report.findings]


def _write_tree(tmp_path, files):
    for name, src in files.items():
        p = tmp_path / name
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)


# --------------------------------------------------------------- real tree


@pytest.fixture(scope="module")
def tree_report():
    """One full-tree run shared by the real-tree tests (parsing ray_tpu/
    twice would double this module's wall clock for no coverage)."""
    t0 = time.monotonic()
    report = run_default()
    report.elapsed_s = time.monotonic() - t0
    return report


def test_tree_is_clean_under_budget(tree_report):
    """The headline gate: zero unsuppressed findings over ray_tpu/ with
    the checked-in baseline, in well under the 15s budget."""
    assert not tree_report.parse_errors, "\n".join(
        f.render() for f in tree_report.parse_errors)
    assert not tree_report.findings, "\n".join(
        f.render() for f in tree_report.findings)
    assert tree_report.elapsed_s < 15.0, (
        f"graft_check took {tree_report.elapsed_s:.1f}s (budget 15s)")


def test_warm_cache_full_tree_under_one_second(tree_report):
    """The perf gate for the incremental loop (tools/precommit.sh): with
    the analysis cache warm — tree_report just populated it — a full-tree
    run costs stats + the finish()-phase replay, no parsing. The CFG and
    SPMD facts must replay from the cache too, or the v3 checkers would
    quietly reintroduce the parse cost the cache exists to avoid."""
    t0 = time.monotonic()
    report = run_default()
    dt = time.monotonic() - t0
    assert report.ok, [f.render() for f in report.findings]
    assert dt < 1.0, f"warm-cache full-tree run took {dt:.2f}s (budget 1s)"


def test_baseline_entries_all_used(tree_report):
    """Redundant with the stale-baseline findings above, but asserts the
    mechanism directly: every baseline entry matched >= 1 finding."""
    baseline = load_baseline(
        os.path.join(REPO, "tools", "graft_check", "baseline.txt"))
    assert baseline, "baseline file should exist with justified entries"
    suppressed_keys = {f.key for f in tree_report.suppressed}
    unused = [e for e in baseline if e.key not in suppressed_keys]
    assert not unused, f"stale baseline entries: {unused}"


def test_cli_lists_every_check_id(capsys):
    from tools.graft_check.__main__ import main

    assert main(["--list"]) == 0
    out = capsys.readouterr().out
    for check_id, _desc in all_check_ids():
        assert check_id in out
    for expected in ("async-blocking", "transitive-blocking",
                     "await-under-lock", "blocking-under-lock",
                     "guarded-attr", "lock-order", "persist-order",
                     "shm-lifecycle", "shm-prefix", "resource-leak",
                     "spmd-consistency", "silent-swallow", "rpc-pairing",
                     "rpc-table", "rpc-method-literal", "rpc-field-schema",
                     "metric-name", "metric-expected", "stale-baseline"):
        assert expected in out, f"--list is missing {expected}"


def test_cli_nonzero_on_violation(tmp_path, capsys):
    from tools.graft_check.__main__ import main

    (tmp_path / "m.py").write_text(
        "import time\n"
        "async def f():\n"
        "    time.sleep(1)\n")
    assert main([str(tmp_path), "--no-baseline", "--no-cache",
                 "--quiet"]) == 1
    assert "async-blocking" in capsys.readouterr().out


def test_cli_github_format(tmp_path, capsys):
    """--format github emits one ::error workflow command per finding,
    with %/newlines escaped so multi-line messages stay one annotation."""
    from tools.graft_check.__main__ import main

    (tmp_path / "m.py").write_text(
        "import time\n"
        "async def f():\n"
        "    time.sleep(1)\n")
    assert main([str(tmp_path), "--no-baseline", "--no-cache",
                 "--quiet", "--format", "github"]) == 1
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln.startswith("::error")]
    assert lines, out
    (line,) = [ln for ln in lines if "async-blocking" in ln]
    assert "file=" in line and ",line=3," in line
    assert "title=graft_check async-blocking" in line
    assert "::[async-blocking]" in line
    assert "\n" not in line.rstrip("\n")


def test_cli_json_format(tmp_path, capsys):
    from tools.graft_check.__main__ import main

    (tmp_path / "m.py").write_text(
        "import time\n"
        "async def f():\n"
        "    time.sleep(1)\n")
    assert main([str(tmp_path), "--no-baseline", "--no-cache",
                 "--format", "json"]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["parse_errors"] == []
    assert payload["suppressed"] == 0
    (finding,) = [f for f in payload["findings"]
                  if f["check_id"] == "async-blocking"]
    assert finding["path"] == "m.py" and finding["line"] == 3
    assert finding["symbol"] == "f" and "message" in finding


# ----------------------------------------------------------- async-blocking


def test_async_blocking_fires(tmp_path):
    (tmp_path / "m.py").write_text(
        "import asyncio, time\n"
        "async def bad():\n"
        "    time.sleep(0.1)\n"                      # line 3: fires
        "    w.rpc({'type': 'kv_get'})\n"            # line 4: fires
        "    ray_tpu.get(ref)\n"                     # line 5: fires
        "    chan.read()\n"                          # line 6: fires
        "async def fine():\n"
        "    await asyncio.sleep(0.1)\n"             # awaited: ok
        "    done, _ = ray_tpu.wait([r], timeout=0)\n"  # poll: ok
        "    def blocking_helper():\n"
        "        time.sleep(1)\n"                    # nested sync def: ok
        "    chan.poll()\n")                         # non-blocking: ok
    report = _run(tmp_path, [AsyncBlockingChecker()])
    assert _ids(report) == [("async-blocking", "m.py", 3),
                            ("async-blocking", "m.py", 4),
                            ("async-blocking", "m.py", 5),
                            ("async-blocking", "m.py", 6)]


# ------------------------------------------------------ transitive-blocking


_TRANSITIVE_FIXTURE = (
    "import time\n"
    "class C:\n"
    "    async def handler(self):\n"
    "        self._drain()\n"                        # line 4: fires
    "        self._poll(timeout=0)\n"                # poll kwarg: ok
    "        await self._adrain()\n"                 # awaited async: ok
    "    def _drain(self):\n"
    "        self._flush()\n"
    "    def _flush(self):\n"
    "        time.sleep(0.5)\n"                      # the primitive
    "    def _poll(self, timeout=None):\n"
    "        time.sleep(timeout or 1)\n"
    "    async def _adrain(self):\n"
    "        pass\n")


def test_transitive_blocking_fires_with_chain(tmp_path):
    (tmp_path / "m.py").write_text(_TRANSITIVE_FIXTURE)
    report = _run(tmp_path, [TransitiveBlockingChecker()])
    got = [f for f in report.findings
           if f.check_id == "transitive-blocking"]
    assert [(f.path, f.line) for f in got] == [("m.py", 4)]
    # the finding carries the whole call chain down to the primitive
    assert "C._drain" in got[0].message
    assert "C._flush() (m.py:8)" in got[0].message
    assert "time.sleep() (m.py:10)" in got[0].message
    assert got[0].symbol == "C.handler"


def test_transitive_blocking_crosses_modules(tmp_path):
    """A helper imported from another module is followed too."""
    _write_tree(tmp_path, {
        "util.py": ("import time\n"
                    "def fetch_all(x):\n"
                    "    time.sleep(1)\n"),
        "srv.py": ("from util import fetch_all\n"
                   "async def handle():\n"
                   "    fetch_all(1)\n")})           # line 3: fires
    report = _run(tmp_path, [TransitiveBlockingChecker()])
    assert _ids(report) == [("transitive-blocking", "srv.py", 3)]


def test_transitive_blocking_generator_and_executor_exempt(tmp_path):
    (tmp_path / "m.py").write_text(
        "import time\n"
        "def gen():\n"
        "    yield 1\n"
        "    time.sleep(1)\n"
        "async def ok():\n"
        "    gen()\n"                    # calling a generator: no body runs
        "    loop.run_in_executor(None, helper)\n"   # passed, not called
        "def helper():\n"
        "    time.sleep(1)\n")
    report = _run(tmp_path, [TransitiveBlockingChecker()])
    assert not report.findings


# ------------------------------------------------------------ lock checks


def test_await_under_lock_fires(tmp_path):
    (tmp_path / "m.py").write_text(
        "class C:\n"
        "    async def bad(self):\n"
        "        with self._lock:\n"
        "            await self.g()\n"               # line 4: fires
        "    async def fine(self):\n"
        "        async with self._alock:\n"
        "            await self.g()\n")              # asyncio lock: ok
    report = _run(tmp_path, [LockDisciplineChecker()])
    assert ("await-under-lock", "m.py", 4) in _ids(report)
    assert not any(f.line == 7 for f in report.findings)


def test_nested_def_under_lock_is_exempt(tmp_path):
    """A def nested inside a `with lock:` block runs later (callback /
    executor target), not while the lock is held."""
    (tmp_path / "m.py").write_text(
        "import time\n"
        "class C:\n"
        "    def ok(self):\n"
        "        with self._lock:\n"
        "            def drain():\n"
        "                time.sleep(0.1)\n"          # runs later: ok
        "            self._pool.submit(drain)\n")
    report = _run(tmp_path, [LockDisciplineChecker()])
    assert not report.findings


def test_blocking_under_lock_fires(tmp_path):
    (tmp_path / "m.py").write_text(
        "import time\n"
        "class C:\n"
        "    def bad(self):\n"
        "        with self._lock:\n"
        "            time.sleep(0.1)\n"              # line 5: fires
        "            self._store.rpc({'type': 'serve_put'})\n"  # 6: fires
        "            self._persist_rep(st, tag)\n"   # line 7: fires
        "    def fine(self):\n"
        "        time.sleep(0.1)\n"                  # no lock: ok
        "        with self._lock:\n"
        "            self.n += 1\n")
    report = _run(tmp_path, [LockDisciplineChecker()])
    got = [k for k in _ids(report) if k[0] == "blocking-under-lock"]
    assert got == [("blocking-under-lock", "m.py", 5),
                   ("blocking-under-lock", "m.py", 6),
                   ("blocking-under-lock", "m.py", 7)]


def test_guarded_attr_fires(tmp_path):
    (tmp_path / "m.py").write_text(
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.items = []\n"
        "        self.done = False\n"
        "    def put(self, x):\n"
        "        with self._lock:\n"
        "            self.items = self.items + [x]\n"
        "            self.done = True\n"
        "    def peek(self):\n"
        "        return self.items[0]\n"             # line 12: fires
        "    def is_done(self):\n"
        "        return self.done\n"                 # bool flag: ok
        "    def _count_locked(self):\n"
        "        return len(self.items)\n")          # _locked suffix: ok
    report = _run(tmp_path, [LockDisciplineChecker()])
    got = [k for k in _ids(report) if k[0] == "guarded-attr"]
    assert got == [("guarded-attr", "m.py", 12)]


def test_a_clock_is_no_lock(tmp_path):
    """`with self._clock.dispatch(...)` times a call: it opens no critical
    section, so neither a sleep inside it nor a bare read elsewhere of what
    it wrote is a finding; `self._rlock` still is a lock."""
    (tmp_path / "m.py").write_text(
        "import threading, time\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._rlock = threading.RLock()\n"
        "        self.state = []\n"
        "        self.items = []\n"
        "    def timed(self, x):\n"
        "        with self._clock.dispatch('insert'):\n"
        "            time.sleep(0.1)\n"
        "            self.state = self.state + [x]\n"
        "    def put(self, x):\n"
        "        with self._rlock:\n"
        "            self.items = self.items + [x]\n"
        "    def peek(self):\n"
        "        return self.state[0], self.items[0]\n")  # line 15: items only
    report = _run(tmp_path, [LockDisciplineChecker()])
    assert [(k, line) for k, _, line in _ids(report)] == [("guarded-attr", 15)]
    assert "C.items" in report.findings[0].message
    from tools.graft_check.core import LOCK_NAME_RE

    # only the word `clock` is let go, not every `lock` behind a `c`
    assert [bool(LOCK_NAME_RE.search(n)) for n in (
        "self._clock.dispatch", "clock", "phase_clock", "self._synclock",
        "funcLock", "self._clock_lock", "_lock", "RLock")] == [
        False, False, False, True, True, True, True, True]


# -------------------------------------------------------------- lock-order


_LOCK_ORDER_FIXTURE = (
    "import threading\n"
    "class A:\n"
    "    def __init__(self):\n"
    "        self._lock_a = threading.Lock()\n"
    "        self._lock_b = threading.Lock()\n"
    "    def one(self):\n"
    "        with self._lock_a:\n"
    "            self._take_b()\n"          # a -> b through the call graph
    "    def _take_b(self):\n"
    "        with self._lock_b:\n"
    "            pass\n"
    "    def two(self):\n"
    "        with self._lock_b:\n"
    "            with self._lock_a:\n"      # b -> a lexically
    "                pass\n")


def test_lock_order_cycle_fires_with_both_paths(tmp_path):
    (tmp_path / "m.py").write_text(_LOCK_ORDER_FIXTURE)
    report = _run(tmp_path, [LockOrderChecker()])
    got = [f for f in report.findings if f.check_id == "lock-order"]
    assert len(got) == 1, _ids(report)
    msg = got[0].message
    # the report names BOTH acquisition paths, interprocedural one included
    assert "Acquisition path 1" in msg and "Acquisition path 2" in msg
    assert "A.one" in msg and "A.two" in msg
    assert "A._take_b" in msg  # the call-graph hop is spelled out
    assert "m.py:A._lock_a" in msg and "m.py:A._lock_b" in msg


def test_lock_order_multi_item_with_fires(tmp_path):
    """`with a, b:` acquires b while a is held — the edge must exist, so
    an opposite-order `with b: with a:` elsewhere is still a cycle."""
    (tmp_path / "m.py").write_text(
        "import threading\n"
        "class A:\n"
        "    def one(self):\n"
        "        with self._lock_a, self._lock_b:\n"
        "            pass\n"
        "    def two(self):\n"
        "        with self._lock_b:\n"
        "            with self._lock_a:\n"
        "                pass\n")
    report = _run(tmp_path, [LockOrderChecker()])
    got = [f for f in report.findings if f.check_id == "lock-order"]
    assert len(got) == 1, _ids(report)
    assert "_lock_a" in got[0].message and "_lock_b" in got[0].message


def test_lock_order_consistent_ordering_is_clean(tmp_path):
    (tmp_path / "m.py").write_text(
        "import threading\n"
        "class A:\n"
        "    def one(self):\n"
        "        with self._lock_a:\n"
        "            with self._lock_b:\n"
        "                pass\n"
        "    def two(self):\n"
        "        with self._lock_a:\n"
        "            with self._lock_b:\n"   # same global order: ok
        "                pass\n")
    report = _run(tmp_path, [LockOrderChecker()])
    assert not report.findings


def test_lock_order_distinct_classes_not_unified(tmp_path):
    """`self._lock` of two different classes are different locks — no
    false cycle from the shared attribute name."""
    (tmp_path / "m.py").write_text(
        "import threading\n"
        "class A:\n"
        "    def f(self):\n"
        "        with self._lock:\n"
        "            g()\n"
        "class B:\n"
        "    def g(self):\n"
        "        with self._lock:\n"
        "            pass\n"
        "def g():\n"
        "    pass\n")
    report = _run(tmp_path, [LockOrderChecker()])
    assert not report.findings


# ------------------------------------------------------------ persist-order


def test_persist_order_fires(tmp_path):
    (tmp_path / "controller.py").write_text(
        "class C:\n"
        "    def scale_up(self):\n"
        "        h = Replica.options(name='r').remote()\n"  # line 3: fires
        "        return h\n"
        "    def scale_down(self, inst):\n"
        "        self.storage.put(inst.to_dict())\n"
        "        self.provider.terminate_node(inst.node_id)\n"  # ok\n
        "    def sweep(self):\n"
        "        self.provider.terminate_node('leak')\n"    # line 9: fires
        "    def _kill_replica(self, h):\n"
        "        ray_tpu.kill(h)\n")                 # helper body: exempt
    checker = PersistOrderChecker(scope=("controller.py",))
    report = _run(tmp_path, [checker])
    assert _ids(report) == [("persist-order", "controller.py", 3),
                            ("persist-order", "controller.py", 9)]


def test_persist_order_scope(tmp_path):
    """Modules outside the control-plane scope are not checked."""
    (tmp_path / "other.py").write_text(
        "def f(p):\n"
        "    p.terminate_node('n')\n")
    report = _run(tmp_path, [PersistOrderChecker(scope=("controller.py",))])
    assert not report.findings


# ------------------------------------------------------------ shm lifecycle


def test_shm_lifecycle_fires(tmp_path):
    (tmp_path / "leaky.py").write_text(
        "from ray_tpu.experimental.channel.mutable_shm import "
        "create_mutable_channel\n"
        "def make():\n"
        "    ch = create_mutable_channel(1024)\n"    # line 3: fires
        "    return ch.path\n")
    (tmp_path / "paired.py").write_text(
        "from ray_tpu.experimental.channel.mutable_shm import "
        "create_mutable_channel\n"
        "def make():\n"
        "    ch = create_mutable_channel(1024)\n"
        "    try:\n"
        "        return ch.read()\n"
        "    finally:\n"
        "        ch.unlink()\n")                     # paired: ok
    (tmp_path / "factory.py").write_text(
        "from ray_tpu.experimental.channel.mutable_shm import "
        "create_mutable_channel\n"
        "def make():\n"
        "    return create_mutable_channel(1024)\n")  # ownership out: ok
    report = _run(tmp_path, [ShmLifecycleChecker()])
    got = [k for k in _ids(report) if k[0] == "shm-lifecycle"]
    assert got == [("shm-lifecycle", "leaky.py", 3)]


def test_shm_prefix_literal_fires(tmp_path):
    (tmp_path / "m.py").write_text(
        "import glob\n"
        "PREFIX = 'rtpu_chan_'\n"                    # line 2: fires
        "def leaked():\n"
        "    return glob.glob('/dev/shm/rtpu_chan_*')\n")  # line 4: fires
    report = _run(tmp_path, [ShmLifecycleChecker()])
    got = [k for k in _ids(report) if k[0] == "shm-prefix"]
    assert got == [("shm-prefix", "m.py", 2), ("shm-prefix", "m.py", 4)]


def test_shm_prefix_allowed_in_constants(tmp_path):
    d = tmp_path / "_private"
    d.mkdir()
    (d / "constants.py").write_text("SHM_CHANNEL_PREFIX = 'rtpu_chan_'\n")
    report = _run(tmp_path, [ShmLifecycleChecker()])
    assert not report.findings


# -------------------------------------------------------------- rpc pairing


def _rpc_fixture(tmp_path, client_body):
    (tmp_path / "gcs.py").write_text(
        "class Server:\n"
        "    def handle(self, msg):\n"
        "        t = msg['type']\n"
        "        if t == 'known_rpc':\n"
        "            self.storage.put('kv', 'k', 1)\n"
        "        elif t == 'other_rpc':\n"
        "            self.storage.put('nope', 'k', 1)\n")
    (tmp_path / "gcs_storage.py").write_text("TABLES = ('kv',)\n")
    (tmp_path / "client.py").write_text(client_body)
    return RpcPairingChecker(gcs_module="gcs.py",
                             gcs_storage_module="gcs_storage.py",
                             method_name_modules=("constants.py",))


def test_rpc_pairing_fires(tmp_path):
    checker = _rpc_fixture(
        tmp_path,
        "def call(w):\n"
        "    w.rpc({'type': 'known_rpc'})\n"         # paired: ok
        "    w.rpc({'type': 'unknown_rpc'})\n")      # line 3: fires
    report = _run(tmp_path, [checker])
    assert ("rpc-pairing", "client.py", 3) in _ids(report)
    assert not any(f.line == 2 and f.path == "client.py"
                   for f in report.findings)


def test_rpc_table_fires(tmp_path):
    checker = _rpc_fixture(tmp_path, "")
    report = _run(tmp_path, [checker])
    # gcs.py line 7 writes table 'nope' which gcs_storage never creates
    assert ("rpc-table", "gcs.py", 7) in _ids(report)
    assert not any(f.path == "gcs.py" and f.line == 5
                   for f in report.findings)


def test_rpc_method_literal_fires(tmp_path):
    checker = _rpc_fixture(
        tmp_path,
        "LOOP = '__ray_tpu_bogus_loop__'\n")         # line 1: fires
    report = _run(tmp_path, [checker])
    assert ("rpc-method-literal", "client.py", 1) in _ids(report)


# --------------------------------------------------------- rpc field schema


_SCHEMA_SERVER = (
    "class Server:\n"
    "    def _handle(self, conn, msg):\n"
    "        t = msg['type']\n"
    "        if t == 'ping':\n"
    "            conn.send({'rid': msg['rid'], 'seq': msg['seq']})\n"  # l5
    "        if t == 'fwd':\n"
    "            self._deep(msg)\n"
    "        if t == 'built':\n"
    "            conn.send({'rid': msg['rid'], 'x': msg.get('x')})\n"
    "        if t == 'orphan':\n"                    # line 10: dead arm
    "            conn.send({'rid': msg['rid']})\n"
    "    def _deep(self, msg):\n"
    "        return msg['deep']\n")                  # line 13: via forward

_SCHEMA_CLIENT = (
    "def call(w):\n"
    "    w.rpc({'type': 'ping', 'extra': 1})\n"      # line 2: dead 'extra'
    "    w.rpc({'type': 'fwd'})\n"
    "def _mk():\n"
    "    return {'type': 'built', 'x': 1}\n"
    "def send_built(w):\n"
    "    w.send_no_reply(_mk())\n")


def _schema_report(tmp_path):
    _write_tree(tmp_path, {"gcs.py": _SCHEMA_SERVER,
                           "client.py": _SCHEMA_CLIENT})
    return _run(tmp_path, [RpcFieldSchemaChecker(gcs_module="gcs.py")])


def test_rpc_field_schema_missing_field_fires(tmp_path):
    report = _schema_report(tmp_path)
    missing = [f for f in report.findings
               if "hard-reads" in f.message]
    # ping hard-reads msg['seq'] no client sends; fwd's helper hard-reads
    # msg['deep'] through the call-graph forward
    assert ("rpc-field-schema", "gcs.py", 5) in [
        (f.check_id, f.path, f.line) for f in missing]
    assert any("'deep'" in f.message and f.path == "gcs.py"
               for f in missing)


def test_rpc_field_schema_dead_field_fires(tmp_path):
    report = _schema_report(tmp_path)
    dead = [f for f in report.findings if "never" in f.message
            and f.path == "client.py"]
    assert [(f.check_id, f.path, f.line) for f in dead] == [
        ("rpc-field-schema", "client.py", 2)]
    assert "'extra'" in dead[0].message


def test_rpc_field_schema_dead_arm_fires(tmp_path):
    report = _schema_report(tmp_path)
    dead_arms = [f for f in report.findings
                 if "dead protocol surface" in f.message]
    assert [(f.path, f.line) for f in dead_arms] == [("gcs.py", 10)]
    assert "'orphan'" in dead_arms[0].message


def test_rpc_field_schema_helper_returned_payload_resolves(tmp_path):
    """`w.send_no_reply(_mk())` counts as a client site for 'built' via
    the helper's return dict — so 'built' is neither a dead arm nor does
    its soft-read x produce noise."""
    report = _schema_report(tmp_path)
    assert not any("'built'" in f.message for f in report.findings)


def test_rpc_field_schema_wholesale_and_incomplete_suppress(tmp_path):
    _write_tree(tmp_path, {
        "gcs.py": ("class S:\n"
                   "    def _handle(self, conn, msg):\n"
                   "        t = msg['type']\n"
                   "        if t == 'store':\n"
                   "            self.db.put('tbl', msg)\n"  # wholesale
                   "        if t == 'splat':\n"
                   "            conn.send({'rid': msg['rid']})\n"
                   "        if t == 'dyn':\n"
                   "            k = msg['key']\n"
                   "            conn.send({'rid': msg['rid'], 'v': msg[k]})\n"),
        "client.py": ("def call(w, extra):\n"
                      "    w.rpc({'type': 'store', 'anything': 1})\n"
                      "    w.rpc({'type': 'splat', **extra})\n"
                      "    w.rpc({'type': 'dyn', 'key': 'x', 'x': 1})\n")})
    report = _run(tmp_path, [RpcFieldSchemaChecker(gcs_module="gcs.py")])
    # wholesale store: 'anything' is not dead; ** site: type skipped;
    # dyn's msg[k] computed read: 'x' must NOT be reported dead
    assert not report.findings


def test_rpc_field_schema_dynamic_client_suppresses_dead_arm(tmp_path):
    """A payload built too dynamically to resolve must not get its arm
    reported dead: the spelled-out type string is the escape hatch."""
    _write_tree(tmp_path, {
        "gcs.py": ("class S:\n"
                   "    def _handle(self, conn, msg):\n"
                   "        t = msg['type']\n"
                   "        if t == 'maybe':\n"
                   "            conn.send({'rid': msg['rid']})\n"),
        "client.py": ("def call(w, flag):\n"
                      "    m = ({'type': 'maybe'} if flag\n"
                      "         else {'type': 'maybe', 'x': 1})\n"
                      "    w.rpc(m)\n")})
    report = _run(tmp_path, [RpcFieldSchemaChecker(gcs_module="gcs.py")])
    assert not report.findings


def test_rpc_field_schema_branch_built_payload_resolves(tmp_path):
    """`m = {...}` rebuilt per branch with the same type unions the keys
    instead of going opaque."""
    _write_tree(tmp_path, {
        "gcs.py": ("class S:\n"
                   "    def _handle(self, conn, msg):\n"
                   "        t = msg['type']\n"
                   "        if t == 'put':\n"
                   "            conn.send({'rid': msg['rid'],\n"
                   "                       'a': msg.get('a'),\n"
                   "                       'b': msg.get('b')})\n"),
        "client.py": ("def call(w, flag):\n"
                      "    if flag:\n"
                      "        m = {'type': 'put', 'a': 1}\n"
                      "    else:\n"
                      "        m = {'type': 'put', 'b': 2}\n"
                      "    w.rpc(m)\n")})
    report = _run(tmp_path, [RpcFieldSchemaChecker(gcs_module="gcs.py")])
    assert not report.findings


# ------------------------------------------------------------ resource-leak


_LEAK_FIXTURE = (
    "def leaky():\n"
    "    ch = create_mutable_channel(1024)\n"   # line 2: fires
    "    publish(ch.path)\n"                    # can raise -> leak
    "    ch.close()\n"
    "    ch.unlink()\n")


def test_resource_leak_fires_on_exception_path(tmp_path):
    (tmp_path / "m.py").write_text(_LEAK_FIXTURE)
    report = _run(tmp_path, [ResourceLeakChecker()])
    (f,) = [x for x in report.findings if x.check_id == "resource-leak"]
    assert (f.path, f.line, f.symbol) == ("m.py", 2, "leaky")
    assert "exception path" in f.message and "`ch`" in f.message


def test_resource_leak_clean_shapes(tmp_path):
    (tmp_path / "m.py").write_text(
        "def fin():\n"
        "    ch = create_mutable_channel(1)\n"
        "    try:\n"
        "        publish(ch.path)\n"
        "    finally:\n"
        "        ch.close()\n"
        "def ctx(p):\n"
        "    with open(p) as f:\n"
        "        return f.read()\n"
        "def factory():\n"
        "    ch = create_mutable_channel(1)\n"      # returned: caller owns
        "    return ch\n"
        "def stored(self):\n"
        "    ch = create_mutable_channel(1)\n"      # self owns it now
        "    self._ch = ch\n"
        "def handed_off():\n"
        "    ch = create_mutable_channel(1)\n"      # registry owns it now
        "    register(ch)\n")
    report = _run(tmp_path, [ResourceLeakChecker()])
    assert not report.findings, _ids(report)


def test_resource_leak_semaphore_needs_finally(tmp_path):
    (tmp_path / "m.py").write_text(
        "class C:\n"
        "    def bad(self):\n"
        "        self._admission.acquire()\n"   # line 3: fires
        "        work()\n"
        "        self._admission.release()\n"
        "    def good(self):\n"
        "        self._admission.acquire()\n"
        "        try:\n"
        "            work()\n"
        "        finally:\n"
        "            self._admission.release()\n"
        "    def cross_method_hold(self):\n"
        "        self._admission.acquire()\n"   # no release here at all:
        "        self.held = True\n")           # a protocol, not a leak
    report = _run(tmp_path, [ResourceLeakChecker()])
    got = [k for k in _ids(report) if k[0] == "resource-leak"]
    assert got == [("resource-leak", "m.py", 3)]


def test_resource_leak_router_token_not_transferred_by_use(tmp_path):
    """The PR 11 bug shape: a router slot id PASSED to the transport call
    is still this function's obligation — only done()/return/a deferred-
    release closure discharge it."""
    (tmp_path / "m.py").write_text(
        "class H:\n"
        "    def bad(self):\n"
        "        rid = self._router.pick()\n"    # line 3: fires
        "        res = transport(rid)\n"         # use, NOT a transfer
        "        self._router.done(rid)\n"
        "        return res\n"
        "    def good(self):\n"
        "        rid = self._router.pick()\n"
        "        try:\n"
        "            return transport(rid)\n"
        "        finally:\n"
        "            self._router.done(rid)\n"
        "    def deferred(self):\n"
        "        rid = self._router.pick()\n"
        "        return Resp(lambda r=rid: self._router.done(r))\n")
    report = _run(tmp_path, [ResourceLeakChecker()])
    got = [k for k in _ids(report) if k[0] == "resource-leak"]
    assert got == [("resource-leak", "m.py", 3)]


def test_resource_leak_interprocedural_factory(tmp_path):
    """`x = helper()` where the helper (transitively, cross-module)
    returns a fresh acquisition is an acquisition in the CALLER."""
    _write_tree(tmp_path, {
        "lib.py": ("def make_chan(n):\n"
                   "    ch = create_mutable_channel(n)\n"
                   "    return ch\n"
                   "def make_wrapped(n):\n"
                   "    return make_chan(n)\n"),
        "use.py": ("from lib import make_chan, make_wrapped\n"
                   "def bad():\n"
                   "    ch = make_chan(1)\n"        # line 3: fires
                   "    publish(ch.path)\n"
                   "    ch.close()\n"
                   "def bad2():\n"
                   "    ch = make_wrapped(1)\n"     # line 7: fires
                   "    publish(ch.path)\n"
                   "    ch.close()\n"
                   "def good():\n"
                   "    ch = make_chan(1)\n"
                   "    try:\n"
                   "        publish(ch.path)\n"
                   "    finally:\n"
                   "        ch.close()\n")})
    report = _run(tmp_path, [ResourceLeakChecker()])
    got = [k for k in _ids(report) if k[0] == "resource-leak"]
    assert got == [("resource-leak", "use.py", 3),
                   ("resource-leak", "use.py", 7)]
    assert all("factory" in f.message for f in report.findings)


def test_resource_leak_loop_reacquisition(tmp_path):
    """Per-iteration acquire with an unprotected use leaks once per lap;
    a finally inside the loop is clean (the back edge must not smear the
    next iteration's release onto this one's escape)."""
    (tmp_path / "m.py").write_text(
        "def bad(paths):\n"
        "    for p in paths:\n"
        "        f = open(p)\n"       # line 3: fires
        "        data = f.read()\n"
        "        f.close()\n"
        "def good(paths):\n"
        "    for p in paths:\n"
        "        f = open(p)\n"
        "        try:\n"
        "            f.read()\n"
        "        finally:\n"
        "            f.close()\n")
    report = _run(tmp_path, [ResourceLeakChecker()])
    got = [k for k in _ids(report) if k[0] == "resource-leak"]
    assert got == [("resource-leak", "m.py", 3)]


# --------------------------------------------------------- spmd-consistency


_SPMD_CONSTANTS = ("MESH_AXIS_DP = 'dp'\n"
                   "MESH_AXIS_TP = 'tp'\n"
                   "MESH_AXES = (MESH_AXIS_DP, MESH_AXIS_TP)\n")

_SPMD_FIXTURE = {
    "_private/constants.py": _SPMD_CONSTANTS,
    "train/step.py": (
        "from jax import lax\n"
        "from jax.sharding import PartitionSpec as P\n"
        "def f(x):\n"
        "    return lax.psum(x, 'dpp')\n"),       # line 4: unknown axis
}


def test_spmd_axis_vocabulary_fires(tmp_path):
    _write_tree(tmp_path, _SPMD_FIXTURE)
    report = _run(tmp_path, [SpmdConsistencyChecker()])
    (f,) = [x for x in report.findings
            if x.check_id == "spmd-consistency"]
    assert (f.path, f.line) == ("train/step.py", 4)
    assert "'dpp'" in f.message and "MESH_AXES" in f.message


def test_spmd_constant_names_resolve(tmp_path):
    """Axis values spelled as constants-module names resolve to their
    strings; in-vocabulary uses stay clean."""
    _write_tree(tmp_path, {
        "_private/constants.py": _SPMD_CONSTANTS,
        "train/step.py": (
            "from jax import lax\n"
            "from ray_tpu._private.constants import MESH_AXIS_DP\n"
            "def f(x):\n"
            "    return lax.pmean(x, MESH_AXIS_DP)\n"
            "def g(x, axis_name='tp'):\n"
            "    return lax.psum(x, axis_name)\n")})
    report = _run(tmp_path, [SpmdConsistencyChecker()])
    assert not report.findings, _ids(report)


def test_spmd_duplicate_axis_in_spec_fires(tmp_path):
    _write_tree(tmp_path, {
        "_private/constants.py": _SPMD_CONSTANTS,
        "train/step.py": (
            "from jax.sharding import PartitionSpec as P\n"
            "BAD = P('dp', 'dp')\n"               # line 2: duplicate
            "OK = P('dp', None, 'tp')\n")})
    report = _run(tmp_path, [SpmdConsistencyChecker()])
    got = [f for f in report.findings if "appears 2x" in f.message]
    assert [(f.path, f.line) for f in got] == [("train/step.py", 2)]


def test_spmd_over_rank_spec_fires(tmp_path):
    """Arity is counted over NAMED axes, not spec length: a spec is as
    long as the ARRAY rank, and trailing None entries (replicated dims)
    are valid on any mesh."""
    _write_tree(tmp_path, {
        "_private/constants.py": _SPMD_CONSTANTS,
        "train/step.py": (
            "from jax.sharding import PartitionSpec as P\n"
            "BAD = P(('dp', 'tp'), 'dp', None)\n"   # names 3 axes, 2 exist
            "OK = P('dp', None, None, None)\n")})   # rank-4 array: fine
    report = _run(tmp_path, [SpmdConsistencyChecker()])
    got = [f for f in report.findings if "names 3 mesh axes" in f.message]
    assert [(f.path, f.line) for f in got] == [("train/step.py", 2)]
    assert not any(f.line == 3 for f in report.findings), _ids(report)


def test_spmd_dynamic_values_and_out_of_scope_skipped(tmp_path):
    _write_tree(tmp_path, {
        "_private/constants.py": _SPMD_CONSTANTS,
        "train/step.py": (
            "from jax import lax\n"
            "def f(x, mesh):\n"
            "    return lax.psum(x, mesh.axis_names[0])\n"),  # dynamic: ok
        "serve/other.py": (
            "from jax import lax\n"
            "def f(x):\n"
            "    return lax.psum(x, 'not_an_axis')\n")})      # out of scope
    report = _run(tmp_path, [SpmdConsistencyChecker()])
    assert not report.findings, _ids(report)


def test_spmd_real_tree_vocabulary_matches_mesh(tree_report):
    """The hoisted MESH_AXES in constants.py IS parallel/mesh.py's AXES —
    if they drift, the whole vocabulary check is checking the wrong
    thing."""
    from ray_tpu._private.constants import MESH_AXES

    import ast as _ast

    src = open(os.path.join(REPO, "ray_tpu", "parallel",
                            "mesh.py")).read()
    assert "AXES = MESH_AXES" in src
    assert MESH_AXES == ("dp", "fsdp", "ep", "pp", "sp", "tp")
    _ast.parse(src)


# ----------------------------------------------------------- silent-swallow


def test_silent_swallow_fires_and_exemptions(tmp_path):
    (tmp_path / "m.py").write_text(
        "import logging\n"
        "logger = logging.getLogger(__name__)\n"
        "def bad():\n"
        "    try:\n"
        "        work()\n"
        "    except Exception:\n"     # line 6: fires
        "        pass\n"
        "def bare():\n"
        "    try:\n"
        "        work()\n"
        "    except:\n"               # line 11: fires
        "        pass\n"
        "def base():\n"
        "    try:\n"
        "        work()\n"
        "    except BaseException:\n"  # line 16: fires
        "        pass\n"
        "def narrowed():\n"
        "    try:\n"
        "        sock.close()\n"
        "    except OSError:\n"        # narrow: ok
        "        pass\n"
        "def logged():\n"
        "    try:\n"
        "        work()\n"
        "    except Exception as e:\n"  # logs: ok
        "        logger.debug('failed: %r', e)\n")
    report = _run(tmp_path, [SilentSwallowChecker()])
    got = [k for k in _ids(report) if k[0] == "silent-swallow"]
    assert got == [("silent-swallow", "m.py", 6),
                   ("silent-swallow", "m.py", 11),
                   ("silent-swallow", "m.py", 16)]


# ------------------------------------------------------------- metric names


def test_metric_name_fires(tmp_path):
    (tmp_path / "m.py").write_text(
        "from ray_tpu.util.metrics import Counter, Histogram, get_or_create\n"
        "import collections\n"
        "c1 = Counter('requests_total')\n"           # line 3: bad prefix
        "c2 = Counter('ray_tpu_Bad_Case')\n"         # line 4: bad case
        "c3 = Counter('ray_tpu_good_total')\n"       # ok
        "h = get_or_create(Histogram, 'lat_seconds')\n"  # line 6: bad
        "cc = collections.Counter('not a metric')\n"     # ignored
        "f1 = Counter(f'ray_tpu_x_{1}_total')\n"         # ok head
        "f2 = Counter(f'serve_{1}_total')\n")            # line 9: bad head
    report = _run(tmp_path, [MetricNamesChecker(expected=())])
    got = [k for k in _ids(report) if k[0] == "metric-name"]
    assert got == [("metric-name", "m.py", 3), ("metric-name", "m.py", 4),
                   ("metric-name", "m.py", 6), ("metric-name", "m.py", 9)]


def test_metric_expected_fires(tmp_path):
    (tmp_path / "m.py").write_text(
        "from ray_tpu.util.metrics import Counter\n"
        "c = Counter('ray_tpu_present_total')\n")
    report = _run(tmp_path, [MetricNamesChecker(
        expected=("ray_tpu_present_total", "ray_tpu_gone_total"))])
    got = [f for f in report.findings if f.check_id == "metric-expected"]
    assert len(got) == 1 and "ray_tpu_gone_total" in got[0].message


# ----------------------------------------------------------------- baseline


def test_baseline_suppresses_and_stale_fires(tmp_path):
    (tmp_path / "m.py").write_text(
        "import time\n"
        "async def bad():\n"
        "    time.sleep(1)\n")
    bl = tmp_path / "baseline.txt"
    bl.write_text(
        "async-blocking  m.py  bad  # fixture justification\n"
        "async-blocking  m.py  vanished  # no longer exists\n")
    baseline = load_baseline(str(bl))
    report = run_checks(str(tmp_path), [AsyncBlockingChecker()], baseline,
                        baseline_path="baseline.txt")
    assert len(report.suppressed) == 1
    stale = [f for f in report.findings if f.check_id == "stale-baseline"]
    assert len(stale) == 1 and "vanished" in stale[0].message
    assert len(report.findings) == 1  # ONLY the stale entry remains


def test_baseline_count_pin_catches_new_violation(tmp_path):
    """`=N` pins the exact finding count: a NEW violation at an already-
    baselined symbol must overflow the pin, not hide behind it."""
    (tmp_path / "m.py").write_text(
        "import time\n"
        "async def bad():\n"
        "    time.sleep(1)\n"
        "    time.sleep(2)\n")
    bl = tmp_path / "baseline.txt"
    bl.write_text("async-blocking  m.py  bad  =1  # pinned to one sleep\n")
    report = run_checks(str(tmp_path), [AsyncBlockingChecker()],
                        load_baseline(str(bl)), baseline_path="baseline.txt")
    assert len(report.suppressed) == 2
    overflow = [f for f in report.findings if f.check_id == "stale-baseline"]
    assert len(overflow) == 1 and "matched 2" in overflow[0].message
    # with the accurate pin the tree is clean again
    bl.write_text("async-blocking  m.py  bad  =2  # pinned to both sleeps\n")
    report = run_checks(str(tmp_path), [AsyncBlockingChecker()],
                        load_baseline(str(bl)), baseline_path="baseline.txt")
    assert not report.findings and len(report.suppressed) == 2


@pytest.mark.parametrize("check_id,fixture,checker_cls", [
    ("transitive-blocking", _TRANSITIVE_FIXTURE, TransitiveBlockingChecker),
    ("lock-order", _LOCK_ORDER_FIXTURE, LockOrderChecker),
    ("resource-leak", _LEAK_FIXTURE, ResourceLeakChecker),
    ("spmd-consistency", _SPMD_FIXTURE, SpmdConsistencyChecker),
    ("silent-swallow", ("def f():\n"
                        "    try:\n"
                        "        work()\n"
                        "    except Exception:\n"
                        "        pass\n"), SilentSwallowChecker),
])
def test_baseline_and_count_pin_cover_new_checkers(tmp_path, check_id,
                                                   fixture, checker_cls):
    """Every post-v1 id (the v2 interprocedural ones AND the v3 CFG/SPMD/
    swallow ones) rides the same baseline machinery: suppression by (id,
    file, symbol) works, `=N` pins are enforced, and removing the
    violation turns the entry stale."""
    files = fixture if isinstance(fixture, dict) else {"m.py": fixture}
    _write_tree(tmp_path, files)
    report = _run(tmp_path, [checker_cls()])
    (finding,) = [f for f in report.findings if f.check_id == check_id]
    bl = tmp_path / "baseline.txt"
    entry = f"{check_id}  {finding.path}  {finding.symbol}"
    bl.write_text(f"{entry}  =1  # fixture\n")
    report = run_checks(str(tmp_path), [checker_cls()],
                        load_baseline(str(bl)), baseline_path="baseline.txt")
    assert not report.findings and len(report.suppressed) == 1
    # a wrong pin overflows instead of hiding
    bl.write_text(f"{entry}  =2  # fixture\n")
    report = run_checks(str(tmp_path), [checker_cls()],
                        load_baseline(str(bl)), baseline_path="baseline.txt")
    stale = [f for f in report.findings if f.check_id == "stale-baseline"]
    assert len(stale) == 1 and "matched 1" in stale[0].message
    # fixing the violation makes the entry stale
    (tmp_path / finding.path).write_text("def fine():\n    pass\n")
    bl.write_text(f"{entry}  =1  # fixture\n")
    report = run_checks(str(tmp_path), [checker_cls()],
                        load_baseline(str(bl)), baseline_path="baseline.txt")
    stale = [f for f in report.findings if f.check_id == "stale-baseline"]
    assert len(stale) == 1


def test_baseline_requires_justification(tmp_path):
    bl = tmp_path / "baseline.txt"
    bl.write_text("async-blocking  m.py  bad\n")  # no justification
    with pytest.raises(ValueError, match="malformed baseline entry"):
        load_baseline(str(bl))


# ------------------------------------------------- every checker must fire


#: check id -> (fixture files, checker factory). The registry test below
#: asserts this covers EVERY id `--list` reports, so a future checker
#: cannot land without a firing fixture.
FIRING_FIXTURES = {
    "async-blocking": (
        {"m.py": "import time\nasync def f():\n    time.sleep(1)\n"},
        lambda: [AsyncBlockingChecker()]),
    "transitive-blocking": (
        {"m.py": _TRANSITIVE_FIXTURE},
        lambda: [TransitiveBlockingChecker()]),
    "await-under-lock": (
        {"m.py": ("class C:\n"
                  "    async def f(self):\n"
                  "        with self._lock:\n"
                  "            await self.g()\n")},
        lambda: [LockDisciplineChecker()]),
    "blocking-under-lock": (
        {"m.py": ("import time\n"
                  "class C:\n"
                  "    def f(self):\n"
                  "        with self._lock:\n"
                  "            time.sleep(1)\n")},
        lambda: [LockDisciplineChecker()]),
    "guarded-attr": (
        {"m.py": ("class C:\n"
                  "    def __init__(self):\n"
                  "        self._lock = object()\n"
                  "    def w(self):\n"
                  "        with self._lock:\n"
                  "            self.items = [1]\n"
                  "    def r(self):\n"
                  "        return self.items\n")},
        lambda: [LockDisciplineChecker()]),
    "lock-order": (
        {"m.py": _LOCK_ORDER_FIXTURE},
        lambda: [LockOrderChecker()]),
    "persist-order": (
        {"controller.py": ("class C:\n"
                           "    def f(self):\n"
                           "        self.provider.terminate_node('n')\n")},
        lambda: [PersistOrderChecker(scope=("controller.py",))]),
    "shm-lifecycle": (
        {"m.py": ("def f():\n"
                  "    ch = create_mutable_channel(1)\n"
                  "    return ch.path\n")},
        lambda: [ShmLifecycleChecker()]),
    "shm-prefix": (
        {"m.py": "P = 'rtpu_chan_'\n"},
        lambda: [ShmLifecycleChecker()]),
    "rpc-pairing": (
        {"gcs.py": ("def h(msg):\n"
                    "    t = msg['type']\n"
                    "    if t == 'known':\n"
                    "        pass\n"),
         "client.py": "def c(w):\n    w.rpc({'type': 'nope'})\n"},
        lambda: [RpcPairingChecker(gcs_module="gcs.py",
                                   gcs_storage_module="gcs_storage.py")]),
    "rpc-table": (
        {"gcs.py": ("class S:\n"
                    "    def h(self):\n"
                    "        self.storage.put('ghost', 'k', 1)\n"),
         "gcs_storage.py": "TABLES = ('kv',)\n"},
        lambda: [RpcPairingChecker(gcs_module="gcs.py",
                                   gcs_storage_module="gcs_storage.py")]),
    "rpc-method-literal": (
        {"m.py": "LOOP = '__ray_tpu_bogus__'\n"},
        lambda: [RpcPairingChecker()]),
    "rpc-field-schema": (
        {"gcs.py": _SCHEMA_SERVER, "client.py": _SCHEMA_CLIENT},
        lambda: [RpcFieldSchemaChecker(gcs_module="gcs.py")]),
    "resource-leak": (
        {"m.py": _LEAK_FIXTURE},
        lambda: [ResourceLeakChecker()]),
    "spmd-consistency": (
        dict(_SPMD_FIXTURE),
        lambda: [SpmdConsistencyChecker()]),
    "silent-swallow": (
        {"m.py": ("def f():\n"
                  "    try:\n"
                  "        work()\n"
                  "    except Exception:\n"
                  "        pass\n")},
        lambda: [SilentSwallowChecker()]),
    "bounded-retry": (
        {"m.py": ("def f(w):\n"
                  "    while True:\n"
                  "        try:\n"
                  "            return w.rpc({'type': 'ping'})\n"
                  "        except Exception:\n"
                  "            continue\n")},
        lambda: [BoundedRetryChecker()]),
    "metric-name": (
        {"m.py": ("from ray_tpu.util.metrics import Counter\n"
                  "c = Counter('bad_name')\n")},
        lambda: [MetricNamesChecker(expected=())]),
    "metric-expected": (
        {"m.py": "x = 1\n"},
        lambda: [MetricNamesChecker(expected=("ray_tpu_gone_total",))]),
    "event-type-literal": (
        {"m.py": "def f(gcs):\n    gcs.emit_event('node.bogus', {})\n"},
        lambda: [EventLiteralChecker()]),
}

#: ids that fire through dedicated machinery, with their own tests above.
_SPECIAL_IDS = {"stale-baseline"}


def test_every_registered_checker_has_firing_fixture():
    """`--list`-driven audit: a checker registered in the default suite
    without an entry here fails — no checker lands untested."""
    listed = {check_id for check_id, _ in all_check_ids()}
    assert listed - _SPECIAL_IDS == set(FIRING_FIXTURES), (
        "every registered check id needs a firing fixture in "
        "FIRING_FIXTURES (or an explicit _SPECIAL_IDS entry with its own "
        "dedicated test)")


@pytest.mark.parametrize("check_id", sorted(FIRING_FIXTURES))
def test_firing_fixture_fires(check_id, tmp_path):
    files, make = FIRING_FIXTURES[check_id]
    _write_tree(tmp_path, files)
    report = _run(tmp_path, make())
    assert any(f.check_id == check_id for f in report.findings), (
        f"{check_id} fixture produced {_ids(report)}")


# --------------------------------------------------- cache / changed scope


def test_analysis_cache_roundtrip_and_invalidation(tmp_path):
    tree = tmp_path / "tree"
    tree.mkdir()
    (tree / "m.py").write_text(
        "import time\nasync def f():\n    time.sleep(1)\n")
    cache = tmp_path / "cache.bin"
    r1 = run_checks(str(tree), [AsyncBlockingChecker()],
                    cache_path=str(cache))
    assert cache.exists()
    # warm run replays cached findings (no reparse path)
    r2 = run_checks(str(tree), [AsyncBlockingChecker()],
                    cache_path=str(cache))
    assert _ids(r1) == _ids(r2) == [("async-blocking", "m.py", 3)]
    # (path, mtime, size) key: editing the file invalidates its entry
    (tree / "m.py").write_text("async def f():\n    pass\n")
    r3 = run_checks(str(tree), [AsyncBlockingChecker()],
                    cache_path=str(cache))
    assert not r3.findings
    # a vanished file's entry is pruned, not replayed
    (tree / "n.py").write_text(
        "import time\nasync def g():\n    time.sleep(1)\n")
    run_checks(str(tree), [AsyncBlockingChecker()], cache_path=str(cache))
    (tree / "n.py").unlink()
    r4 = run_checks(str(tree), [AsyncBlockingChecker()],
                    cache_path=str(cache))
    assert not r4.findings


def test_cache_replays_call_graph_summaries(tmp_path):
    """Interprocedural checkers must work from CACHED module summaries —
    a warm run reparses nothing but still resolves the call chain."""
    tree = tmp_path / "tree"
    tree.mkdir()
    (tree / "m.py").write_text(_TRANSITIVE_FIXTURE)
    cache = tmp_path / "cache.bin"
    r1 = run_checks(str(tree), [TransitiveBlockingChecker()],
                    cache_path=str(cache))
    r2 = run_checks(str(tree), [TransitiveBlockingChecker()],
                    cache_path=str(cache))
    assert _ids(r1) == _ids(r2)
    assert any(f.check_id == "transitive-blocking" for f in r2.findings)
    # facts-based checkers replay their collected facts the same way
    _write_tree(tree, {"gcs.py": _SCHEMA_SERVER,
                       "client.py": _SCHEMA_CLIENT})
    rs1 = run_checks(str(tree), [RpcFieldSchemaChecker(gcs_module="gcs.py")],
                     cache_path=str(cache))
    rs2 = run_checks(str(tree), [RpcFieldSchemaChecker(gcs_module="gcs.py")],
                     cache_path=str(cache))
    assert _ids(rs1) == _ids(rs2)
    assert any(f.check_id == "rpc-field-schema" for f in rs2.findings)


def test_corrupt_cache_is_rebuilt(tmp_path):
    tree = tmp_path / "tree"
    tree.mkdir()
    (tree / "m.py").write_text(
        "import time\nasync def f():\n    time.sleep(1)\n")
    cache = tmp_path / "cache.bin"
    cache.write_bytes(b"\x80garbage")
    report = run_checks(str(tree), [AsyncBlockingChecker()],
                        cache_path=str(cache))
    assert _ids(report) == [("async-blocking", "m.py", 3)]


def test_scope_filters_reporting_not_analysis(tmp_path):
    """--changed semantics: findings are filtered to the scoped files,
    but cross-file analysis still sees the whole tree (a scoped client's
    pairing is judged against the UNSCOPED server module)."""
    _write_tree(tmp_path, {
        "a.py": "import time\nasync def f():\n    time.sleep(1)\n",
        "b.py": "import time\nasync def g():\n    time.sleep(1)\n",
        "gcs.py": ("def h(msg):\n"
                   "    t = msg['type']\n"
                   "    if t == 'known':\n"
                   "        pass\n"),
        "client.py": "def c(w):\n    w.rpc({'type': 'nope'})\n"})
    checkers = lambda: [AsyncBlockingChecker(),  # noqa: E731
                        RpcPairingChecker(gcs_module="gcs.py",
                                          gcs_storage_module="gs.py")]
    full = _run(tmp_path, checkers())
    assert {f.path for f in full.findings} == {"a.py", "b.py", "client.py"}
    scoped = _run(tmp_path, checkers(), scope=["b.py", "client.py"])
    assert {f.path for f in scoped.findings} == {"b.py", "client.py"}
    # the pairing finding survived scoping even though gcs.py is outside
    assert any(f.check_id == "rpc-pairing" for f in scoped.findings)


def test_scope_never_hides_parse_errors(tmp_path):
    """An unparsable file voids tree-wide analysis, so --changed runs
    must still fail loud even when the broken file is out of scope."""
    _write_tree(tmp_path, {
        "ok.py": "def fine():\n    pass\n",
        "broken.py": "def oops(:\n"})
    report = run_checks(str(tmp_path), [AsyncBlockingChecker()],
                        scope=["ok.py"])
    assert [f.path for f in report.parse_errors] == ["broken.py"]


def test_scope_judges_stale_entries_only_for_scoped_files(tmp_path):
    (tmp_path / "m.py").write_text("def fine():\n    pass\n")
    bl = tmp_path / "baseline.txt"
    bl.write_text("async-blocking  m.py  gone  # stale on full runs\n")
    baseline = load_baseline(str(bl))
    full = run_checks(str(tmp_path), [AsyncBlockingChecker()], baseline,
                      baseline_path="baseline.txt")
    assert any(f.check_id == "stale-baseline" for f in full.findings)
    scoped = run_checks(str(tmp_path), [AsyncBlockingChecker()], baseline,
                        baseline_path="baseline.txt", scope=["other.py"])
    assert not scoped.findings


@pytest.mark.skipif(shutil.which("git") is None, reason="needs git")
def test_changed_relpaths_from_git(tmp_path, monkeypatch):
    import tools.graft_check as gc

    repo = tmp_path / "repo"
    pkg = repo / "pkg"
    pkg.mkdir(parents=True)
    (pkg / "a.py").write_text("A = 1\n")
    (pkg / "b.py").write_text("B = 1\n")
    env_git = ["git", "-C", str(repo), "-c", "user.email=t@t",
               "-c", "user.name=t"]
    subprocess.run(["git", "-C", str(repo), "init", "-q"], check=True)
    subprocess.run(env_git + ["add", "."], check=True)
    subprocess.run(env_git + ["commit", "-qm", "seed"], check=True)
    (pkg / "a.py").write_text("A = 2\n")          # tracked modification
    (pkg / "c.py").write_text("C = 1\n")          # untracked
    (repo / "outside.py").write_text("X = 1\n")   # outside the scan root
    monkeypatch.setattr(gc, "REPO_ROOT", str(repo))
    assert sorted(gc.changed_relpaths(str(pkg))) == ["a.py", "c.py"]
