"""`ray_tpu` ops CLI — status / list / logs / microbenchmark / job submit.

Run as `python -m ray_tpu.scripts.cli <command>` (or the `ray-tpu` shim).

(reference capability: python/ray/scripts/scripts.py — `ray status`/`ray
list`/`ray logs`/`ray submit`; state listing mirrors util/state/state_cli.py
but reads the GCS `cluster_state`/`list_nodes` messages directly over the
session socket instead of a dashboard head.)
"""

from __future__ import annotations

import argparse
import glob
import itertools
import json
import os
import sys
import time


def find_sessions(base: str = "/tmp/ray_tpu") -> list[str]:
    """Session dirs with a live GCS socket, newest first."""
    dirs = sorted(glob.glob(os.path.join(base, "session_*")),
                  key=os.path.getmtime, reverse=True)
    return [d for d in dirs if os.path.exists(os.path.join(d, "gcs.sock"))]


class GcsClient:
    """Thin read-only client on the session socket (no worker registration)."""

    def __init__(self, session_dir: str):
        from ray_tpu._private.protocol import connect_unix

        self.session_dir = session_dir
        self.conn = connect_unix(os.path.join(session_dir, "gcs.sock"), timeout=5.0)
        self._rid = itertools.count(1)

    def rpc(self, msg: dict) -> dict:
        msg["rid"] = next(self._rid)
        self.conn.send(msg)
        return self.conn.recv()

    def close(self):
        self.conn.close()


def _pick_session(args) -> str:
    if getattr(args, "session", None):
        return args.session
    sessions = find_sessions()
    if not sessions:
        print("no live ray_tpu session found under /tmp/ray_tpu", file=sys.stderr)
        sys.exit(1)
    return sessions[0]


def cmd_status(args):
    sd = _pick_session(args)
    c = GcsClient(sd)
    try:
        state = c.rpc({"type": "cluster_state"})["state"]
    finally:
        c.close()
    if args.json:
        print(json.dumps(state, indent=1, default=str))
        return
    print(f"session: {os.path.basename(sd)}")
    print(f"workers: {state['num_workers']}   live actors: {state['num_actors']}   "
          f"pending tasks: {state['pending_tasks']}")
    print("resources:")
    total, avail = state["total_resources"], state["available_resources"]
    for k in sorted(total):
        print(f"  {k:24s} {total[k] - avail.get(k, 0):.1f} / {total[k]:.1f} used")
    tc = state.get("task_counter", {})
    if tc:
        print("tasks: " + "  ".join(f"{k}={v}" for k, v in sorted(tc.items())))
    demand = state.get("pending_demand") or {}
    if any(demand.values()):
        print("pending demand: " + "  ".join(
            f"{k}={v}" for k, v in sorted(demand.items()) if v))
    draining = {nid: i for nid, i in (state.get("nodes") or {}).items()
                if i.get("draining")}
    if draining:
        print("draining nodes:")
        now = time.time()
        for nid, info in draining.items():
            deadline = info.get("drain_deadline")
            left = (f"  {max(0.0, deadline - now):.0f}s left"
                    if deadline else "")
            print(f"  {nid}  reason={info.get('drain_reason') or '?'}{left}")
    pend = {a: i for a, i in state.get("actors", {}).items()
            if i["state"] not in ("alive", "dead")}
    if pend:
        print("non-running actors (`ray_tpu explain <id>` says why):")
        for aid, info in pend.items():
            print(f"  {aid}  {info['state']}  name={info.get('name')}")


def cmd_list(args):
    sd = _pick_session(args)
    c = GcsClient(sd)
    try:
        if args.kind == "nodes":
            rows = c.rpc({"type": "list_nodes"})["nodes"]
        elif args.kind == "actors":
            state = c.rpc({"type": "cluster_state"})["state"]
            rows = [{"actor_id": aid, **info}
                    for aid, info in state.get("actors", {}).items()]
        elif args.kind == "placement-groups":
            rows_map = c.rpc({"type": "pg_table"})["table"]
            rows = [{"pg_id": k, **v} for k, v in rows_map.items()]
        elif args.kind == "tasks":
            rows = c.rpc({"type": "task_events"})["events"]
        elif args.kind == "objects":
            rows = c.rpc({"type": "list_objects"})["objects"]
        elif args.kind == "workers":
            rows = c.rpc({"type": "list_workers"})["workers"]
        elif args.kind == "jobs":
            keys = c.rpc({"type": "kv_keys", "prefix": "job:"})["keys"]
            rows = []
            for k in keys:
                v = c.rpc({"type": "kv_get", "key": k})["value"]
                if v:
                    rows.append(json.loads(v) if isinstance(v, (str, bytes)) else v)
        else:
            print(f"unknown kind {args.kind}", file=sys.stderr)
            sys.exit(2)
    finally:
        c.close()
    print(json.dumps(rows, indent=1, default=str))


def cmd_logs(args):
    sd = _pick_session(args)
    log_dir = os.path.join(sd, "logs")
    names = sorted(os.listdir(log_dir)) if os.path.isdir(log_dir) else []
    if args.source is None:
        for n in names:
            path = os.path.join(log_dir, n)
            print(f"{n}\t{os.path.getsize(path)} bytes")
        return
    matches = [n for n in names if n.startswith(args.source)]
    if not matches:
        print(f"no log matching {args.source!r} (have: {', '.join(names)})",
              file=sys.stderr)
        sys.exit(1)
    path = os.path.join(log_dir, matches[0])
    with open(path, "rb") as f:
        if args.follow:
            f.seek(0, os.SEEK_END if args.tail == 0 else os.SEEK_SET)
            if args.tail:
                _print_tail(f, args.tail)
            while True:
                chunk = f.read()
                if chunk:
                    sys.stdout.write(chunk.decode("utf-8", "replace"))
                    sys.stdout.flush()
                else:
                    time.sleep(0.25)
        elif args.tail:
            _print_tail(f, args.tail)
        else:
            sys.stdout.write(f.read().decode("utf-8", "replace"))


def _print_tail(f, n_lines: int):
    f.seek(0)
    lines = f.read().decode("utf-8", "replace").splitlines()
    for line in lines[-n_lines:]:
        print(line)


def cmd_stack(args):
    """Dump live thread stacks of a worker (reference capability: dashboard
    on-demand py-spy profiling of live workers)."""
    sd = _pick_session(args)
    c = GcsClient(sd)
    try:
        workers = c.rpc({"type": "list_workers"})["workers"]
        live = [w for w in workers if not w["dead"]]
        if args.worker is None:
            for w in live:
                print(f"{w['wid'][:12]}  pid={w['pid']:<7} kind={w['kind']:<7} "
                      f"node={w['node_id']} actor={w['actor_id'] or '-'}")
            return
        target = next((w for w in live
                       if w["wid"].startswith(args.worker)
                       or str(w["pid"]) == args.worker), None)
        if target is None:
            print(f"no live worker matching {args.worker!r}", file=sys.stderr)
            sys.exit(1)
        if getattr(args, "profile", 0):
            reply = c.rpc({"type": "worker_profile", "wid": target["wid"],
                           "duration_s": args.profile,
                           "hz": getattr(args, "hz", 50.0)})
        else:
            reply = c.rpc({"type": "worker_stacks", "wid": target["wid"]})
        if not reply.get("ok"):
            print(f"stack dump failed: {reply.get('error')}", file=sys.stderr)
            sys.exit(1)
        print(reply["stacks"])
    finally:
        c.close()


def cmd_start(args):
    """Start a head session (`ray_tpu start --head`) or join an existing one
    as a follower host (`ray_tpu start --address host:port`) and block.
    (reference capability: `ray start` head/worker modes, scripts.py:679.)"""
    if args.head:
        from ray_tpu._private.node import Node

        node = Node(num_cpus=args.num_cpus, num_tpus=args.num_tpus,
                    num_workers=args.num_workers,
                    max_workers=args.max_workers)
        print(f"head started: session={node.session_id}")
        print(f"  session dir: {node.session_dir}")
        print(f"  address:     {node.address}")
        print(f"  join:        ray_tpu start --address {node.address}")
        print(f"  driver:      ray_tpu.init(address={node.address!r})")
        if args.dashboard:
            from ray_tpu.dashboard import start_dashboard

            head = start_dashboard(node.session_dir, port=args.dashboard_port)
            print(f"  dashboard:   http://127.0.0.1:{head.port}")
        monitor_proc = None
        if args.autoscaling_config:
            # the autoscaler runs as its own MONITOR process (reference:
            # autoscaler/_private/monitor.py spawned by `ray start --head`)
            import subprocess

            monitor_proc = subprocess.Popen(
                [sys.executable, "-m", "ray_tpu._private.monitor",
                 "--address", node.address,
                 "--autoscaling-config", args.autoscaling_config]
                + (["--keep-nodes-on-exit"] if args.keep_nodes_on_exit
                   else []))
            print(f"  monitor:     pid {monitor_proc.pid} "
                  f"({args.autoscaling_config})")
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            if monitor_proc is not None:
                monitor_proc.terminate()
            node.shutdown()
    elif args.address:
        if args.autoscaling_config:
            print("warning: --autoscaling-config only applies to --head "
                  "(the monitor runs next to the GCS); ignoring",
                  file=sys.stderr)
        from ray_tpu._private.node_agent import NodeAgent

        agent = NodeAgent(address=args.address,
                          num_cpus=args.num_cpus, num_tpus=args.num_tpus)
        print(f"node agent {agent.host_id} joined {args.address}")
        agent.serve_forever()
    else:
        print("specify --head or --address", file=sys.stderr)
        sys.exit(2)


def cmd_unquarantine(args):
    """Re-enable TPU chips quarantined by an OOM kill, once the operator
    has confirmed the chips answer again (the GCS-side
    recovery path for `unquarantine_chips`)."""
    sd = _pick_session(args)
    c = GcsClient(sd)
    try:
        msg = {"type": "unquarantine_chips"}
        if args.node:
            msg["node_id"] = args.node
        if args.chips:
            msg["chips"] = [int(x) for x in args.chips.split(",")]
        reply = c.rpc(msg)
        restored = reply.get("restored") or []
        if restored:
            print(f"restored chips: {restored}")
        else:
            print("no quarantined chips matched")
    finally:
        c.close()


def cmd_drain(args):
    """Mark a node DRAINING ahead of planned maintenance or a known
    preemption: the scheduler stops placing work there, resident train
    workers get the drain notice (grace checkpoint at the next step
    boundary), and an attached autoscaler terminates the node after the
    grace window."""
    from ray_tpu._private.ray_config import RayConfig

    sd = _pick_session(args)
    c = GcsClient(sd)
    try:
        grace = (RayConfig.get("drain_grace_s") if args.grace is None
                 else float(args.grace))
        reply = c.rpc({"type": "node_drain", "node_id": args.node_id,
                       "grace_s": grace, "reason": args.reason})
        if reply.get("ok"):
            print(f"node {args.node_id} draining (grace {grace}s)")
        else:
            print(f"drain failed: {reply.get('error')}", file=sys.stderr)
            sys.exit(1)
    finally:
        c.close()


def cmd_monitor(args):
    from ray_tpu._private import monitor

    argv = ["--address", args.address,
            "--autoscaling-config", args.autoscaling_config]
    if args.keep_nodes_on_exit:
        argv.append("--keep-nodes-on-exit")
    return monitor.main(argv)


def cmd_timeline(args):
    """Export collected task events as a chrome://tracing JSON file
    (reference capability: `ray timeline`, GcsTaskManager + profile events).
    Rows for actor workers are labeled with the actor's class/name from the
    GCS actor table; compiled-DAG step spans group under their DAG id."""
    from ray_tpu._private.task_events import (export_chrome_trace,
                                              fetch_worker_names)

    sd = _pick_session(args)
    c = GcsClient(sd)
    try:
        events = c.rpc({"type": "task_events"}).get("events", [])
        # control-plane event log rides along as one `ctrl:<node>` row per
        # node, so scheduling churn lines up against the task spans
        cluster = c.rpc({"type": "list_events"}).get("events", [])
        names = fetch_worker_names(c.rpc)
    finally:
        c.close()
    out = args.output or "timeline.json"
    export_chrome_trace(events + cluster, out, names)
    print(f"wrote {len(events)} task + {len(cluster)} cluster events to "
          f"{out} (open in chrome://tracing)")


def _print_event_row(ev: dict) -> None:
    ts = time.strftime("%H:%M:%S", time.localtime(ev.get("ts", 0)))
    extras = " ".join(
        f"{k}={v}" for k, v in sorted(ev.items())
        if k not in ("seq", "ts", "etype", "severity", "source", "node",
                     "message") and v not in (None, "", [], {}))
    print(f"{ev.get('seq', 0):>6} {ts} {ev.get('severity', ''):<7} "
          f"{ev.get('etype', ''):<20} {ev.get('node', '') or '-':<12} "
          f"{ev.get('message', '')}" + (f"  [{extras}]" if extras else ""))


def cmd_events(args):
    """Structured cluster event log (reference capability: `ray list
    cluster-events` / the dashboard event feed): node joins/leaves/drains,
    actor lifecycle with death causes, PG placement, autoscaler instance
    transitions, serve reconciles, train attempts. --follow polls on the
    server-side seq watermark so only new events ship."""
    sd = _pick_session(args)
    c = GcsClient(sd)

    def fetch(after_seq: int = 0, limit: int = 0) -> list:
        return c.rpc({"type": "list_events",
                      "severity": args.severity or "",
                      "etype": args.type or "", "node": args.node or "",
                      "after_seq": after_seq,
                      "limit": limit}).get("events", [])

    try:
        rows = fetch(limit=args.limit)
        if args.json:
            print(json.dumps(rows, indent=1, default=str))
            if not args.follow:
                return
        else:
            for ev in rows:
                _print_event_row(ev)
        if not args.follow:
            return
        last = max((ev.get("seq", 0) for ev in rows), default=0)
        while True:
            time.sleep(1.0)
            fresh = fetch(after_seq=last)
            for ev in fresh:
                last = max(last, ev.get("seq", 0))
                if args.json:
                    print(json.dumps(ev, default=str))
                else:
                    _print_event_row(ev)
    except KeyboardInterrupt:
        pass
    finally:
        c.close()


def cmd_explain(args):
    """Scheduler decision attribution (\"why is my actor pending\"): the
    live per-node rejection table for a pending actor/PG, or the recorded
    decision trace (queue wait, node, lease RTT) once it placed."""
    sd = _pick_session(args)
    c = GcsClient(sd)
    try:
        reply = c.rpc({"type": "sched_explain", "target": args.target})
    finally:
        c.close()
    if args.json:
        print(json.dumps(reply, indent=1, default=str))
        return
    if not reply.get("found"):
        print(reply.get("error") or f"no actor or placement group "
                                    f"{args.target!r}", file=sys.stderr)
        sys.exit(1)
    kind, state = reply.get("kind"), reply.get("state")
    print(f"{kind} {args.target}: {state}")
    trace = reply.get("trace") or {}
    if trace:
        items = "  ".join(f"{k}={v}" for k, v in sorted(trace.items())
                          if k != "history" and v is not None)
        print(f"  trace: {items}")
    if reply.get("queue_wait_s") is not None:
        print(f"  waiting for {reply['queue_wait_s']:.1f}s")
    rej = reply.get("rejections")
    if rej:
        print("  per-node rejection table:")
        width = max(len(k) for k in rej)
        for node_id, why in sorted(rej.items()):
            print(f"    {node_id:<{width}}  {why}")
    elif reply.get("note"):
        print(f"  {reply['note']}")


def cmd_dag(args):
    """Compiled-DAG registry: `ray_tpu dag list` shows every live compiled
    DAG (plane, actors, channels, fallback reason); `ray_tpu dag show <id>`
    prints one DAG's full record plus per-node step-phase timing aggregated
    from the always-on ray_tpu_dag_step_* histograms."""
    from ray_tpu.util.state import summarize_dag_metrics

    sd = _pick_session(args)
    c = GcsClient(sd)
    try:
        dags = c.rpc({"type": "dag_list"}).get("dags", [])
        if args.action == "list":
            if args.json:
                print(json.dumps(dags, indent=1, default=str))
                return
            print(f"{'dag_id':<18} {'plane':<9} {'actors':>6} "
                  f"{'channels':>8}  fallback_reason")
            for d in sorted(dags, key=lambda d: d.get("created_at", 0)):
                print(f"{d['dag_id']:<18} {d.get('plane', '?'):<9} "
                      f"{len(d.get('actors', [])):>6} "
                      f"{d.get('channels', 0):>8}  "
                      f"{d.get('fallback_reason') or '-'}")
            return
        # show: an exact id always wins; a prefix must be unambiguous
        matches = [d for d in dags if d["dag_id"] == args.dag_id]
        if not matches and args.dag_id:
            matches = [d for d in dags
                       if d["dag_id"].startswith(args.dag_id)]
        if args.dag_id is None or not matches:
            print(f"no compiled DAG matching {args.dag_id!r} "
                  f"(have: {', '.join(d['dag_id'] for d in dags) or 'none'})",
                  file=sys.stderr)
            sys.exit(1)
        if len(matches) > 1:
            print(f"ambiguous DAG prefix {args.dag_id!r}: "
                  f"{', '.join(d['dag_id'] for d in matches)}",
                  file=sys.stderr)
            sys.exit(1)
        rec = matches[0]
        snap = c.rpc({"type": "metrics_snapshot"}).get("metrics", {})
    finally:
        c.close()
    print(json.dumps({"dag": rec,
                      "steps": summarize_dag_metrics(snap, rec["dag_id"])},
                     indent=1, default=str))


def _print_span(span: dict, depth: int = 0) -> None:
    start, end = span.get("start"), span.get("end")
    dur = f"{(end - start) * 1e3:9.2f} ms" if start and end else " " * 12
    line = f"{dur}  {'  ' * depth}{span.get('name') or span.get('span_kind')}"
    if not span.get("ok", True):
        line += "  [FAILED]"
    if span.get("pid"):
        line += f"  (pid {span['pid']})"
    print(line)
    for child in span.get("children", ()):
        _print_span(child, depth + 1)


def cmd_trace(args):
    """Serve request tracing: `ray_tpu trace list` shows the flight-recorder
    log of recent request summaries (always-on, last N per process);
    `ray_tpu trace show <request_id>` prints the sampled cross-process span
    tree for one request (trace id == request id), falling back to the
    flight-recorder summary when that request wasn't span-sampled."""
    from ray_tpu.util.tracing import assemble

    sd = _pick_session(args)
    c = GcsClient(sd)
    try:
        if args.action == "list":
            rows = c.rpc({"type": "list_requests"}).get("requests", [])
            if args.json:
                print(json.dumps(rows, indent=1, default=str))
                return
            print(f"{'request_id':<34} {'component':<11} {'status':<7} "
                  f"{'dur_ms':>9}  phases")
            for r in rows[-50:]:
                phases = " ".join(
                    f"{k}={v * 1e3:.1f}ms"
                    for k, v in (r.get("phases") or {}).items())
                print(f"{r.get('request_id', '?'):<34} "
                      f"{r.get('component', '?'):<11} "
                      f"{str(r.get('status', '')):<7} "
                      f"{(r.get('duration_s') or 0) * 1e3:>9.2f}  {phases}")
            return
        if not args.request_id:
            print("trace show needs a request id", file=sys.stderr)
            sys.exit(2)
        events = c.rpc({"type": "task_events"}).get("events", [])
        tree = assemble(events, args.request_id)
        if tree is not None:
            print(f"trace {args.request_id}")
            _print_span(tree["root"])
            return
        rows = [r for r in c.rpc({"type": "list_requests"}).get(
            "requests", []) if r.get("request_id") == args.request_id]
        if rows:
            print(f"request {args.request_id} was not span-sampled "
                  "(RAY_TPU_SERVE_SPAN_SAMPLE_EVERY); flight-recorder "
                  "summary:")
            print(json.dumps(rows, indent=1, default=str))
            return
        print(f"no trace or request summary for {args.request_id!r}",
              file=sys.stderr)
        sys.exit(1)
    finally:
        c.close()


def cmd_dashboard(args):
    from ray_tpu.dashboard.head import DashboardHead

    sd = _pick_session(args)
    head = DashboardHead(sd, args.host, args.port)
    print(f"dashboard on http://{args.host}:{head.port}")
    try:
        head.httpd.serve_forever()
    except KeyboardInterrupt:
        head.stop()


def cmd_summary(args):
    """Aggregate task counts/failures/time per task name (reference
    capability: `ray summary tasks`, util/state summarize)."""
    from ray_tpu.util.state import summarize_task_events

    sd = _pick_session(args)
    c = GcsClient(sd)
    try:
        events = c.rpc({"type": "task_events"}).get("events", [])
    finally:
        c.close()
    summary = summarize_task_events(events)
    print(f"{'task':<32} {'count':>7} {'failed':>7} {'total_s':>9}")
    for name, rec in sorted(summary.items(),
                            key=lambda kv: -kv[1]["count"]):
        print(f"{name[:32]:<32} {rec['count']:>7} {rec['failed']:>7} "
              f"{rec['total_s']:>9.3f}")


def cmd_grafana(args):
    """Write Grafana dashboard JSON + provisioning YAML + a Prometheus
    scrape config (reference capability: the dashboard's
    grafana_dashboard_factory + metrics_head artifact generation)."""
    from ray_tpu.dashboard.grafana import provision

    written = provision(args.out, dashboard_host=args.dashboard_host,
                        prometheus_host=args.prometheus_host)
    for p in written:
        print(p)


def cmd_client_proxy(args):
    """Serve Ray-Client-style proxied connections (util/client/proxier)."""
    import time as _time

    from ray_tpu.util.client import start_proxy

    proxy = start_proxy(args.address, args.host, args.port)
    print(f"client proxy on {proxy.address} -> {args.address}")
    try:
        while True:
            _time.sleep(3600)
    except KeyboardInterrupt:
        proxy.stop()


def cmd_microbenchmark(args):
    from ray_tpu._private import ray_perf

    ray_perf.main()


def cmd_submit(args):
    from ray_tpu.job_submission import JobSubmissionClient

    client = JobSubmissionClient()
    job_id = client.submit_job(
        entrypoint=" ".join(args.entrypoint),
        metadata={"submitted_via": "cli"})
    print(f"submitted job {job_id}")
    if args.no_wait:
        return
    status = client.wait_until_finished(job_id)
    for line in client.get_job_logs(job_id).splitlines():
        print(line)
    print(f"job {job_id}: {status}")
    sys.exit(0 if status == "SUCCEEDED" else 1)


def cmd_serve(args):
    """Declarative serve workflow (reference: serve/scripts.py —
    `serve deploy config.yaml`, `serve build import_path`, `serve status`)."""
    import yaml

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve import schema as serve_schema

    if args.action == "build":
        if not args.target:
            raise SystemExit("serve build needs an import_path "
                             "(module:attribute)")
        app_schema = serve_schema.ServeApplicationSchema(
            import_path=args.target)
        target = app_schema.resolve_target()
        cfg = serve_schema.build(target, import_path=args.target)
        text = yaml.safe_dump(cfg, sort_keys=False)
        if args.output:
            with open(args.output, "w") as f:
                f.write(text)
            print(f"wrote {args.output}")
        else:
            print(text, end="")
        return
    addr = args.address or os.environ.get("RAY_TPU_ADDRESS")
    if addr:
        ray_tpu.init(address=addr)
    else:  # attach to the newest live session on this host
        sd = _pick_session(args)
        os.environ["RAY_TPU_ADDRESS"] = f"unix:{os.path.join(sd, 'gcs.sock')}"
        os.environ["RAY_TPU_SESSION"] = os.path.basename(sd)[len("session_"):]
        ray_tpu.init()
    if args.action == "deploy":
        if not args.target:
            raise SystemExit("serve deploy needs a config YAML path")
        serve.deploy(args.target)
        print(f"deployed applications from {args.target}")
    elif args.action == "status":
        out = {"applications": serve.status()}
        try:
            plane = serve.proxy_status()
        except Exception:  # noqa: BLE001 — controller without the RPC yet
            plane = None
        if plane is not None:
            out["proxy_plane"] = plane
        print(json.dumps(out, indent=1, default=str))


def cmd_job(args):
    from ray_tpu.job_submission import JobSubmissionClient

    client = JobSubmissionClient()
    if args.action == "status":
        print(client.get_job_status(args.job_id))
    elif args.action == "logs":
        print(client.get_job_logs(args.job_id))
    elif args.action == "stop":
        client.stop_job(args.job_id)
        print(f"stop requested for {args.job_id}")
    elif args.action == "list":
        print(json.dumps(client.list_jobs(), indent=1, default=str))


def main(argv=None):
    p = argparse.ArgumentParser(prog="ray_tpu", description=__doc__)
    p.add_argument("--session", help="session dir (default: newest live one)")
    sub = p.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("status", help="cluster resources / actors / tasks")
    sp.add_argument("--json", action="store_true")
    sp.set_defaults(fn=cmd_status)

    sp = sub.add_parser("list", help="list cluster state")
    sp.add_argument("kind", choices=["nodes", "actors", "placement-groups",
                                     "jobs", "tasks", "objects", "workers"])
    sp.set_defaults(fn=cmd_list)

    sp = sub.add_parser("logs", help="show/tail a process log")
    sp.add_argument("source", nargs="?", help="e.g. worker-0 (omit to list)")
    sp.add_argument("-f", "--follow", action="store_true")
    sp.add_argument("-n", "--tail", type=int, default=0)
    sp.set_defaults(fn=cmd_logs)

    sp = sub.add_parser("microbenchmark", help="run core runtime microbenchmarks")
    sp.set_defaults(fn=cmd_microbenchmark)

    sp = sub.add_parser("stack", help="live thread stacks of a worker")
    sp.add_argument("--profile", type=float, default=0, metavar="SECONDS",
                    help="sample for SECONDS and print a collapsed-stack "
                         "profile instead of one snapshot")
    sp.add_argument("--hz", type=float, default=50.0)
    sp.add_argument("worker", nargs="?", help="wid prefix or pid (omit to list)")
    sp.set_defaults(fn=cmd_stack)

    sp = sub.add_parser("start", help="start a head session or join as follower")
    sp.add_argument("--head", action="store_true")
    sp.add_argument("--address", help="GCS host:port to join as follower")
    sp.add_argument("--num-cpus", type=float, default=None)
    sp.add_argument("--num-tpus", type=float, default=None)
    sp.add_argument("--num-workers", type=int, default=0)
    sp.add_argument("--max-workers", type=int, default=16)
    sp.add_argument("--dashboard", action="store_true")
    sp.add_argument("--dashboard-port", type=int, default=0)
    sp.add_argument("--autoscaling-config", default=None,
                    help="JSON/YAML autoscaler config; spawns the monitor "
                         "process (see ray_tpu/_private/monitor.py)")
    sp.add_argument("--keep-nodes-on-exit", action="store_true",
                    help="monitor leaves provider nodes running on exit")
    sp.set_defaults(fn=cmd_start)

    sp = sub.add_parser("unquarantine",
                        help="re-enable chips quarantined by an OOM kill")
    sp.add_argument("--node", help="node id (default: the head's local node)")
    sp.add_argument("--chips", help="comma-separated chip ids (default: all)")
    sp.set_defaults(fn=cmd_unquarantine)

    sp = sub.add_parser("drain",
                        help="drain a node: stop scheduling there, notify "
                             "resident train workers, then terminate")
    sp.add_argument("node_id", help="node id (see `list --what nodes`)")
    sp.add_argument("--grace", type=float, default=None,
                    help="grace window seconds (default: drain_grace_s)")
    sp.add_argument("--reason", default="cli",
                    help="recorded with the drain (default: cli)")
    sp.set_defaults(fn=cmd_drain)

    sp = sub.add_parser("monitor",
                        help="run the autoscaler monitor process "
                             "against a live cluster")
    sp.add_argument("--address", required=True)
    sp.add_argument("--autoscaling-config", required=True)
    sp.add_argument("--keep-nodes-on-exit", action="store_true")
    sp.set_defaults(fn=cmd_monitor)

    sp = sub.add_parser("timeline", help="export task timeline (chrome trace)")
    sp.add_argument("-o", "--output", help="output path (default timeline.json)")
    sp.set_defaults(fn=cmd_timeline)

    sp = sub.add_parser("events",
                        help="structured cluster event log (node/actor/PG "
                             "lifecycle, drains, autoscaler, serve, train)")
    sp.add_argument("-f", "--follow", action="store_true",
                    help="poll for new events (seq watermark)")
    sp.add_argument("--severity",
                    help="minimum severity (DEBUG/INFO/WARNING/ERROR)")
    sp.add_argument("--type", help="exact event type, e.g. node.drain")
    sp.add_argument("--node", help="only events attributed to this node")
    sp.add_argument("-n", "--limit", type=int, default=0,
                    help="newest N matching events (default: all retained)")
    sp.add_argument("--json", action="store_true")
    sp.set_defaults(fn=cmd_events)

    sp = sub.add_parser("explain",
                        help="why is this actor/placement-group pending? "
                             "(per-node rejection table / decision trace)")
    sp.add_argument("target", help="actor id or placement group id")
    sp.add_argument("--json", action="store_true")
    sp.set_defaults(fn=cmd_explain)

    sp = sub.add_parser("dag", help="compiled-DAG registry: list / show")
    sp.add_argument("action", choices=["list", "show"])
    sp.add_argument("dag_id", nargs="?",
                    help="show: dag id (or unique prefix)")
    sp.add_argument("--json", action="store_true",
                    help="list: raw JSON instead of the table")
    sp.set_defaults(fn=cmd_dag)

    sp = sub.add_parser("trace",
                        help="serve request tracing: list recent request "
                             "summaries / show one request's span tree")
    sp.add_argument("action", choices=["list", "show"])
    sp.add_argument("request_id", nargs="?",
                    help="show: the request id (from trace list, the "
                         "flight recorder, or /api/requests)")
    sp.add_argument("--json", action="store_true",
                    help="list: raw JSON instead of the table")
    sp.set_defaults(fn=cmd_trace)

    sp = sub.add_parser("dashboard", help="serve the HTTP dashboard")
    sp.add_argument("--host", default="127.0.0.1")
    sp.add_argument("--port", type=int, default=0)
    sp.set_defaults(fn=cmd_dashboard)

    sp = sub.add_parser("summary", help="per-task-name execution summary")
    sp.set_defaults(fn=cmd_summary)

    sp = sub.add_parser("grafana",
                        help="write Grafana/Prometheus provisioning artifacts")
    sp.add_argument("--out", default="./ray_tpu_metrics",
                    help="output directory (default ./ray_tpu_metrics)")
    sp.add_argument("--dashboard-host", default="127.0.0.1:8265",
                    help="where Prometheus scrapes /metrics")
    sp.add_argument("--prometheus-host", default="127.0.0.1:9090",
                    help="where Grafana reaches Prometheus")
    sp.set_defaults(fn=cmd_grafana)

    sp = sub.add_parser("client-proxy",
                        help="serve proxied client connections (ray client)")
    sp.add_argument("--address", required=True,
                    help="GCS address (host:port) to bridge clients to")
    sp.add_argument("--host", default="127.0.0.1")
    sp.add_argument("--port", type=int, default=10001)
    sp.set_defaults(fn=cmd_client_proxy)

    sp = sub.add_parser("serve",
                        help="declarative serve: deploy/build/status "
                             "(reference: `serve deploy` / `serve build`)")
    sp.add_argument("action", choices=["deploy", "build", "status"])
    sp.add_argument("target", nargs="?",
                    help="deploy: config YAML path; build: import_path "
                         "(module:attribute) of a bound Application")
    sp.add_argument("-o", "--output", help="build: write YAML here "
                                           "(default stdout)")
    sp.add_argument("--address", help="GCS address of a running cluster "
                                      "(deploy/status attach to it)")
    sp.set_defaults(fn=cmd_serve)

    sp = sub.add_parser("submit", help="submit a job (command) to the cluster")
    sp.add_argument("--no-wait", action="store_true")
    sp.add_argument("entrypoint", nargs=argparse.REMAINDER)
    sp.set_defaults(fn=cmd_submit)

    sp = sub.add_parser("job", help="job status / logs / stop / list")
    sp.add_argument("action", choices=["status", "logs", "stop", "list"])
    sp.add_argument("job_id", nargs="?")
    sp.set_defaults(fn=cmd_job)

    args = p.parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    main()
