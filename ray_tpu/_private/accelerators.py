"""TPU accelerator detection and chip-isolation helpers.

(reference capability: python/ray/_private/accelerators/tpu.py —
`TPU_VISIBLE_CHIPS` per-worker isolation (:36), chips-per-host detection
(:100), GKE/GCE topology env detection (:17-65), and the pod-slice head
resource `TPU-{accelerator_type}-head` (:170, :529-534). Detection here is
env-var driven so tests can simulate topologies without hardware, matching
the reference's own test strategy — SURVEY.md §4.2.)
"""

from __future__ import annotations

import collections
import glob
import os
import re
import threading
import time

# The env var JAX/libtpu reads to restrict a process to a chip subset.
TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
# libtpu's description of a sub-host process: how many chips it drives
# along each axis, within a one-process "slice" of its own. Without them a
# second process on the host either fails to load libtpu (the whole host is
# taken) or claims every chip (reference: tpu.py sets the chips-per-host
# and host bounds next to TPU_VISIBLE_CHIPS; these are libtpu's current
# names for the same pair).
TPU_CHIPS_PER_PROCESS_BOUNDS_ENV = "TPU_CHIPS_PER_PROCESS_BOUNDS"
TPU_PROCESS_BOUNDS_ENV = "TPU_PROCESS_BOUNDS"
_CHIPS_PER_PROCESS_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}
# JAX's persistent compilation cache. Placed from outside when the variable
# is set; otherwise one fixed directory inside the checkout (the path is
# part of the cache key, so it must not move between runs).
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
# Authoritative record of the chips the GCS bound to this worker process
# (set alongside TPU_VISIBLE_CHIPS at spawn; read back at registration).
WORKER_CHIPS_ENV = "RAY_TPU_WORKER_CHIPS"
# Opt-out: don't set TPU_VISIBLE_CHIPS on chip workers (reference:
# RAY_EXPERIMENTAL_NOSET_TPU_VISIBLE_CHIPS).
NOSET_VISIBLE_CHIPS_ENV = "RAY_TPU_NOSET_TPU_VISIBLE_CHIPS"


def detect_num_tpu_chips() -> int:
    """TPU chip count without importing jax (reference: tpu.py:100
    chips-per-host logic — there via GKE env vars / GCE metadata; here via
    env override or device files)."""
    env = os.environ.get("RAY_TPU_CHIPS")
    if env:
        return int(env)
    return count_chip_nodes(glob.glob("/dev/accel*") + glob.glob("/dev/vfio/*"))


def count_chip_nodes(dev_paths: list[str]) -> int:
    """Chips among device-node paths: `/dev/accel<N>` (accel driver) or
    `/dev/vfio/<N>` (one IOMMU group per chip). `/dev/vfio/vfio` is the
    VFIO control node every vfio host has, not a chip."""
    return sum(1 for p in dev_paths
               if re.fullmatch(r"/dev/(accel|vfio/)\d+", p))


def detect_tpu_labels() -> dict:
    """Topology labels for the node, from the same env vars GKE/GCE TPU VMs
    export (reference: tpu.py:17-65 — TPU_ACCELERATOR_TYPE, TPU_TOPOLOGY,
    TPU_NAME, TPU_WORKER_ID). These feed NodeLabel scheduling and the SLICE
    placement strategy."""
    labels = {}
    accel = os.environ.get("TPU_ACCELERATOR_TYPE")
    if accel:
        labels["ray_tpu.io/accelerator-type"] = accel
    topo = os.environ.get("TPU_TOPOLOGY")
    if topo:
        labels["ray_tpu.io/tpu-topology"] = topo
    pod = os.environ.get("TPU_NAME")
    if pod:
        labels["ray_tpu.io/tpu-pod-name"] = pod
    wid = os.environ.get("TPU_WORKER_ID")
    if wid is not None and wid != "":
        labels["ray_tpu.io/tpu-worker-id"] = wid
    return labels


def tpu_head_resource_name(accelerator_type: str) -> str:
    """The per-slice rendezvous resource: exactly one unit on worker 0 of a
    pod slice, letting users schedule one coordinating actor per slice
    (reference: tpu.py:170,529-534 `TPU-{pod_type}-head`)."""
    return f"TPU-{accelerator_type}-head"


def head_resources() -> dict:
    """Extra resources this host contributes (the slice-head marker)."""
    accel = os.environ.get("TPU_ACCELERATOR_TYPE")
    wid = os.environ.get("TPU_WORKER_ID", "0")
    if accel and wid == "0":
        return {tpu_head_resource_name(accel): 1.0}
    return {}


def detect_host_resources(num_cpus=None, num_tpus=None, resources=None,
                          labels=None) -> tuple[dict, dict]:
    """(total_resources, labels) for a host — shared by the head Node and
    follower NodeAgent so both advertise identically for the same hardware."""
    import os as _os

    total = {"CPU": float(num_cpus if num_cpus is not None
                          else (_os.cpu_count() or 1))}
    ntpu = num_tpus if num_tpus is not None else detect_num_tpu_chips()
    if ntpu:
        total["TPU"] = float(ntpu)
        total.update(head_resources())
    if resources:
        total.update({k: float(v) for k, v in resources.items()})
    merged_labels = {**detect_tpu_labels(), **(labels or {})}
    return total, merged_labels


def export_compile_cache_env(env=os.environ) -> str:
    """The one decision on where compiled programs are kept, for a process
    that computes on the chip itself (call before `import jax`) and for
    every chip worker's spawn env: wherever the variable already points,
    else the fixed directory in the checkout. Nothing sets a cache directory
    through jax.config."""
    return env.setdefault(COMPILE_CACHE_ENV, DEFAULT_COMPILE_CACHE_DIR)


# JAX's three stages of making a program, by the duration event of each
_COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend"}
_cache_events: collections.Counter | None = None
_stage_seconds = dict.fromkeys(("trace", "lower", "backend", "retrieval"), 0.0)
_programs: dict = {}  # "jit(<name>)" -> its row of compile_cache_counts()
_compile_lock = threading.Lock()  # the two above: several threads compile
# .activity: what this thread is doing; and the listeners' notes from one
# event of a compilation to the next (.outcome, .retrieval, .counted)
_compiling = threading.local()
_MAX_COUNTED = 1 << 16  # outermost intervals a thread remembers


def note_thread_activity(activity) -> None:
    """`activity()` names what the calling thread is doing (the engine
    thread gives the loop phase that is open): stamped on every compilation
    this thread makes from now on, as its program's `last` in
    `compile_cache_counts()["programs"]`."""
    _compiling.activity = activity


def _count_stage(stage: str, name: str, seconds: float) -> None:
    """One duration event of JAX's, on the thread that did the work. The
    interval ends now and began `seconds` ago on this thread, so what lies
    inside it (every `jit` and `jnp` call of a trace fires a trace event of
    its own, before the outer one) was reported already: its seconds leave
    the totals again, and each second is counted once, under the outermost
    stage that was open."""
    note, end = vars(_compiling), time.time()
    start = end - seconds
    fetched = note.pop("retrieval", 0.0) if stage == "backend" else 0.0
    # this thread's outermost intervals so far, in order: [start, end, stage,
    # seconds, the cache's seconds, the bare name of an unclaimed trace]
    counted = note.setdefault("counted", [])
    with _compile_lock:
        while counted and (counted[-1][0] + counted[-1][1]) / 2 > start:
            _, _, inner, took, read, _ = counted.pop()
            _stage_seconds[inner] -= took
            _stage_seconds["retrieval"] -= read
        _stage_seconds[stage] += seconds
        _stage_seconds["retrieval"] += fetched
        if stage != "trace":
            row = _programs.setdefault(name, {
                "traces": 0, "trace_s": 0.0, "lowers": 0, "lower_s": 0.0,
                "compiles": 0, "backend_s": 0.0, "hits": 0, "misses": 0,
                "last": None})
        if stage == "lower":
            row["lowers"] += 1
            row["lower_s"] += seconds
            # the trace that led here ended just before, under the bare name
            if counted and counted[-1][5] == name.partition("(")[2][:-1]:
                row["traces"] += 1
                row["trace_s"] += counted[-1][3]
                counted[-1][5] = None
        elif stage == "backend":
            row["compiles"] += 1
            row["backend_s"] += seconds
            outcome = note.pop("outcome", None)
            if outcome:
                row[outcome] += 1
            activity = note.get("activity")
            row["last"] = {"t": end, "thread": threading.current_thread().name,
                           "phase": activity() if activity else None}
        counted.append([start, end, stage, seconds, fetched,
                        name if stage == "trace" else None])
        # an interval this many events back is taken to be closed: nothing
        # still open began before it (one with more intervals directly inside
        # it than this would count the oldest of them twice)
        if len(counted) > _MAX_COUNTED:
            del counted[:_MAX_COUNTED // 2]


def compile_cache_counts() -> dict:
    """What this process spent on making programs since the first call (so
    call once before compiling), from JAX's monitoring events, which fire
    only when JAX traces, lowers or compiles. `requests`, `hits`, `misses`:
    programs looked up in the persistent compilation cache, found, and
    compiled then written (a second run on a kept directory shows hits and
    no misses). `seconds`: in tracing, in lowering and in the backend
    (compiling, or reading from the cache and loading; `retrieval` is the
    cache's own part of that), each second once (`_count_stage`), so
    trace + lower + backend is never more than the compiling threads' wall
    time. `programs`: a row for every program that was lowered, under the
    name the lowering and the compile events give it (`jit(<function>)`):
    events and seconds by stage, `trace_s` the outermost trace that led to
    the lowering (a nested trace has no row: it is in its caller's), hits
    and misses, and `last`: when its last compilation ended, on which
    thread, and what that thread was doing (`note_thread_activity`)."""
    global _cache_events
    if _cache_events is None:
        import jax.monitoring

        _cache_events = counts = collections.Counter()
        prefix = "/jax/compilation_cache/"

        def on_event(event: str, **_kw) -> None:
            if event.startswith(prefix):
                counts[event[len(prefix):]] += 1
                # the listeners run on the compiling thread, and the
                # duration event of the same compilation follows
                if event.endswith("cache_hits"):
                    _compiling.outcome = "hits"
                elif event.endswith("cache_misses"):
                    _compiling.outcome = "misses"

        def on_duration(event: str, seconds: float, **kw) -> None:
            if event in _COMPILE_STAGES:
                _count_stage(_COMPILE_STAGES[event],
                             kw.get("fun_name") or "?", seconds)
            elif event == prefix + "cache_retrieval_time_sec":
                _compiling.retrieval = seconds

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
    with _compile_lock:
        seconds = dict(_stage_seconds)
        programs = {name: dict(row) for name, row in _programs.items()}
    return {"dir": os.environ.get(COMPILE_CACHE_ENV),
            "requests": _cache_events["compile_requests_use_cache"],
            "hits": _cache_events["cache_hits"],
            "misses": _cache_events["cache_misses"],
            "seconds": seconds, "programs": programs,
            # `last` by program, newest 16: only what
            # chipbench/tests/test_rehearsal_loop.py still asks for, a file a
            # `benchmark` issue alone may edit; nothing else reads it
            "recent": sorted(({"program": name, **row["last"]}
                              for name, row in programs.items() if row["last"]),
                             key=lambda e: e["t"])[-16:]}


def process_start_time() -> float:
    """When the OS started this process, on the wall clock (`time.time()`,
    the clock of `tracing._emit_span`, which every process of the host
    shares), to the kernel's tick of 10 ms."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])  # since boot
    age = time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf("SC_CLK_TCK")
    return time.time() - age


def device_report() -> dict:
    """The devices this process computes on, as JAX reports them — stamped
    on every result a chip program prints."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu() -> None:
    """For a program that measures on the chip in its own process: raise
    unless JAX's default backend is the TPU. There is no CPU stand-in for a
    device measurement."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"this program measures on a TPU chip and JAX's backend here "
            f"is {backend!r}: no chip, no result")


def chips_required(resources: dict) -> int:
    """Whole chips a task/actor binds. A custom-resource `TPU` amount below
    one binds no chip: that work runs in a host-platform worker."""
    v = float(resources.get("TPU", 0.0))
    return int(v) if v >= 1.0 else 0


def validate_num_tpus(num_tpus) -> None:
    if num_tpus is not None and float(num_tpus) != int(num_tpus):
        raise ValueError(
            f"num_tpus must be a whole number of chips (got {num_tpus}): a "
            f"chip belongs to one process, so a fraction of one would bind "
            f"no chip and compute on the host CPU")


def apply_chip_env(env: dict, chips: tuple | list) -> None:
    """Stamp a worker-spawn env with its chip binding (before any jax
    import in the child, so backend init only sees these chips). The
    platform is pinned: a chip worker that cannot reach its chip dies at
    backend init instead of carrying on on JAX's CPU backend."""
    ids = ",".join(str(c) for c in chips)
    env[WORKER_CHIPS_ENV] = ids
    env["JAX_PLATFORMS"] = "tpu"
    export_compile_cache_env(env)
    if os.environ.get(NOSET_VISIBLE_CHIPS_ENV) != "1":
        env[TPU_VISIBLE_CHIPS_ENV] = ids
        bounds = _CHIPS_PER_PROCESS_BOUNDS.get(len(chips))
        if bounds:
            env[TPU_CHIPS_PER_PROCESS_BOUNDS_ENV] = bounds
            env[TPU_PROCESS_BOUNDS_ENV] = "1,1,1"


def apply_host_env(env: dict) -> None:
    """Spawn env of a worker the GCS bound no chip to. A chip belongs to
    one process, so such a worker must not reach for one whatever platform
    the host env presets: hard-set, not setdefault."""
    env["JAX_PLATFORMS"] = "cpu"


def current_worker_chips() -> list[int]:
    """The chips the GCS bound to this worker process ([] for CPU workers)."""
    raw = os.environ.get(WORKER_CHIPS_ENV, "")
    return [int(c) for c in raw.split(",") if c != ""]
