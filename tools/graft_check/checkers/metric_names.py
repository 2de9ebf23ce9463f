"""metric-name / metric-expected: one exported metric namespace.

Every `Counter`/`Gauge`/`Histogram` constructed with a literal name in the
package (including via `metrics.get_or_create(Counter, ...)`) must match
``ray_tpu_[a-z0-9_]+`` — snake_case under the `ray_tpu_` prefix — so
dashboards, Prometheus relabeling, and docs rely on one namespace. The
flagship EXPECTED_METRICS families must keep being constructed somewhere:
a rename fails here, not in a scrape.

This is the former `tools/check_metric_names.py` (wired into tier-1 since
PR 4), re-homed as a graft_check checker; the old module remains as a thin
shim over this one.
"""

from __future__ import annotations

import ast
import re
from typing import Iterable, List, Optional, Set

from tools.graft_check.core import Checker, Finding, ParsedModule

NAME_ID = "metric-name"
EXPECTED_ID = "metric-expected"

NAME_RE = re.compile(r"^ray_tpu_[a-z0-9_]+$")
_HEAD_RE = re.compile(r"^ray_tpu_[a-z0-9_]*$")
METRIC_CTORS = {"Counter", "Gauge", "Histogram"}
# module objects whose .Counter etc. are NOT metrics
_NON_METRIC_BASES = {"collections", "typing"}

# Flagship EXPORTED metric families (literal constructor names only — the
# per-phase DAG step histograms use an f-string and are covered by the
# namespace head check). Dashboards, Prometheus relabeling rules, and the
# README "Observability" tables key on these exact strings: a rename or
# removal must fail this check, not be discovered in a scrape.
EXPECTED_METRICS = (
    "ray_tpu_dag_recoveries_total",
    "ray_tpu_dag_step_backpressure_drain_seconds",
    "ray_tpu_autoscaler_instance_transitions_total",
    "ray_tpu_autoscaler_reconcile_seconds",
    "ray_tpu_storage_retries_total",
    "ray_tpu_storage_commit_seconds",
    "ray_tpu_serve_requests_total",
    # serve control-plane fault tolerance (serve/controller.py): controller
    # crash-restart recoveries, replicas re-adopted without restart, and
    # active health-probe failures driving drain-and-replace
    "ray_tpu_serve_controller_recoveries_total",
    "ray_tpu_serve_replicas_readopted_total",
    "ray_tpu_serve_replica_health_check_failures_total",
    # PD disaggregation transfer plane + TTFT split (llm/kv_transfer.py,
    # llm/pd.py)
    "ray_tpu_llm_pd_transfer_bytes_total",
    "ray_tpu_llm_pd_kv_pages_total",
    "ray_tpu_llm_pd_ttft_seconds",
    # streamed PD admission (ISSUE 15): pages pulled onto the decode host
    # ahead of slot activation by the batched puller / inline sync pull,
    # and the per-decode-step wall-time histogram split by attention impl
    # (ragged vs gather — the decode-kernel half of the PD win)
    "ray_tpu_llm_pd_pages_prefetched_total",
    "ray_tpu_llm_decode_step_seconds",
    # arena object-store accounting (CoreWorker._record_store_metrics)
    "ray_tpu_object_store_used",
    "ray_tpu_object_store_capacity",
    "ray_tpu_object_store_evictions_total",
    # serve/PD request-path phase attribution (serve/request_context.py):
    # always-on pre-bound phase histograms for the serving hot path —
    # proxy accept/parse/route/handle, handle pick/RTT, replica
    # queue-wait/execute, engine admission-wait/inter-token, PD per-page
    # transfer waits — plus prefix-router outcomes and the GCS's
    # server-side per-RPC-type latency histogram (gcs.py, unregistered —
    # folded into metrics_snapshot under the "gcs" source)
    "ray_tpu_serve_proxy_phase_seconds",
    "ray_tpu_serve_handle_phase_seconds",
    "ray_tpu_serve_replica_phase_seconds",
    "ray_tpu_llm_engine_phase_seconds",
    "ray_tpu_llm_pd_phase_seconds",
    "ray_tpu_serve_router_prefix_route_total",
    "ray_tpu_gcs_rpc_seconds",
    # quantized + ZeRO-sharded training collectives (util/collective/
    # collective.py, train/session.py): per-rank bytes-on-wire (the int8
    # ring's ~4x win keys on this), collective wall time, and per-worker
    # optimizer-state footprint (the ZeRO ~W x drop keys on this)
    "ray_tpu_collective_bytes_total",
    "ray_tpu_collective_seconds",
    "ray_tpu_train_opt_state_bytes",
    # request cancellation + overload shedding (serve/request_context.py):
    # cancels by the stage that applied them (proxy/handle/replica/engine/
    # pd) and requests refused by admission control (router window /
    # replica queue bound) instead of queued
    "ray_tpu_serve_request_cancellations_total",
    "ray_tpu_serve_requests_shed_total",
    # training fault tolerance v2: collective-aware failure detection
    # (util/collective), node drain (gcs), and the train hang watchdog /
    # preemption-grace checkpoint (train/controller.py + session.py)
    "ray_tpu_collective_failures_total",
    "ray_tpu_nodes_draining",
    "ray_tpu_train_hangs_detected_total",
    "ray_tpu_train_preempt_checkpoints_total",
    # sharded proxy plane (serve/controller.py + serve/proxy.py): running
    # shard count from the controller's fleet reconcile, and each shard's
    # view of how stale the shm-broadcast routing table is (age counts
    # from the controller's last publish — its liveness heartbeat)
    "ray_tpu_serve_proxy_shards",
    "ray_tpu_serve_routing_table_age_seconds",
    # streamed answers an HTTP proxy is delivering, now and at most
    # (serve/http_server.py; a delivery holds no request-pool thread)
    "ray_tpu_serve_proxy_streams_open",
    # scheduler decision attribution (gcs.py, unregistered — folded into
    # metrics_snapshot under the "gcs" source): decision latency by
    # kind/outcome, decisions/s counters (the scale harness's scheduler
    # throughput probe), and the pending-work gauge per kind
    "ray_tpu_sched_decision_seconds",
    "ray_tpu_sched_decisions_total",
    "ray_tpu_sched_pending",
    # data-plane fault tolerance (data/execution.py): per-pipeline block
    # resubmissions after SYSTEM failures, map-pool actors replaced by
    # supervision, and APPLICATION-errored blocks skipped under the
    # `on_block_error="skip"` policy (never silently dropped)
    "ray_tpu_data_block_retries_total",
    "ray_tpu_data_actor_replacements_total",
    "ray_tpu_data_blocks_errored_total",
)


def _ctor_name(func: ast.expr) -> Optional[str]:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        base = func.value
        if isinstance(base, ast.Name) and base.id in _NON_METRIC_BASES:
            return None
        return func.attr
    return None


def _literal_name_arg(call: ast.Call) -> Optional[ast.expr]:
    """The metric-name argument of a constructor call, or of
    `get_or_create(<Ctor>, name, ...)`."""
    fn = _ctor_name(call.func)
    if fn in METRIC_CTORS:
        if call.args:
            return call.args[0]
        return next((k.value for k in call.keywords if k.arg == "name"), None)
    if fn == "get_or_create" and len(call.args) >= 2:
        first = _ctor_name(call.args[0]) if isinstance(
            call.args[0], (ast.Name, ast.Attribute)) else None
        if first in METRIC_CTORS:
            return call.args[1]
    return None


def iter_metric_names(tree: ast.AST):
    """Yield (lineno, descriptor, constructed_name, canonical) for every
    literal metric-name construction in `tree`. `constructed_name` is the
    exact name when it is a plain literal (None for f-strings), and
    `descriptor` is what violation reports print (the old
    check_metric_names.py wire format — its shim rides this)."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        arg = _literal_name_arg(node)
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            yield (node.lineno, arg.value, arg.value,
                   bool(NAME_RE.match(arg.value)))
        elif isinstance(arg, ast.JoinedStr):
            # f-string name: the leading LITERAL segment must already carry
            # the canonical prefix (e.g. f"ray_tpu_dag_step_{p}_s") —
            # otherwise dynamic names would be a blind spot
            head = arg.values[0] if arg.values else None
            head_str = (head.value if isinstance(head, ast.Constant)
                        and isinstance(head.value, str) else "")
            yield (node.lineno, f"<f-string head {head_str!r}>", None,
                   bool(_HEAD_RE.match(head_str)))


def scan_module(mod: ParsedModule):
    """(findings, literal metric names constructed in this module)."""
    bad: List[Finding] = []
    names: Set[str] = set()
    for lineno, descriptor, name, canonical in iter_metric_names(mod.tree):
        if name is not None:
            names.add(name)
        if not canonical:
            bad.append(Finding(
                NAME_ID, mod.relpath, lineno, mod.symbol_at(lineno),
                f"metric name {descriptor} does not match "
                f"{NAME_RE.pattern}"))
    return bad, names


class MetricNamesChecker(Checker):
    ids = (
        (NAME_ID,
         "every literal Counter/Gauge/Histogram name matches "
         "ray_tpu_[a-z0-9_]+"),
        (EXPECTED_ID,
         "every EXPECTED_METRICS family is still constructed somewhere"),
    )

    facts_name = "metric-names"

    def __init__(self, expected=EXPECTED_METRICS):
        self._expected = tuple(expected)
        self._last = None  # (module, scan result): check_module + collect
        #                    run back-to-back on the same module — one walk

    def _scan(self, mod: ParsedModule):
        if self._last is None or self._last[0] is not mod:
            self._last = (mod, scan_module(mod))
        return self._last[1]

    def check_module(self, mod: ParsedModule) -> Iterable[Finding]:
        bad, _names = self._scan(mod)
        return bad

    def collect(self, mod: ParsedModule):
        _bad, names = self._scan(mod)
        return sorted(names)

    def finish(self, project=None) -> Iterable[Finding]:
        present: Set[str] = set()
        first_mod: Optional[str] = None
        if project is not None:
            for rel, names in project.facts(self.facts_name).items():
                if first_mod is None:
                    first_mod = rel
                present.update(names)
        return [Finding(EXPECTED_ID, first_mod or "<tree>", 0,
                        "<module>",
                        f"expected exported metric {name!r} is no longer "
                        f"constructed anywhere in the scanned tree")
                for name in self._expected if name not in present]
