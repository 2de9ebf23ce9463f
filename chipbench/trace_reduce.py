"""From a `jax.profiler` trace to numbers, in two stages.

1. `rows_from_xplane(path)`: the `.xplane.pb` file -> plain rows, with
   nothing but JAX: for every device plane the events of its op line
   `[name, start_ns, dur_ns]`, the names among them that are Pallas launches,
   and the host's `chipbench:*` and `ray_tpu:*` annotations.
2. `reduce_rows(rows, ...)`: rows -> busy and window seconds, the device
   operations that took most time, the longest idle gaps named by what the
   host was doing, kernel time, exposed collective time.

Stage 2 is plain Python on plain data and is checked on a small recorded
trace (tests/data/trace_rows.json).
"""

from __future__ import annotations

import glob
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
# the benchmark's own annotations and the engine's (`util/tracing.py`
# `device_annotation`: `ray_tpu:engine:<phase>`, `ray_tpu:engine:dispatch:<program>`)
HOST_PREFIXES = ("chipbench:", "ray_tpu:")
KERNEL_TARGET = "tpu_custom_call"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")
GAP_FLOOR_NS = 20_000  # a gap shorter than 20 us is launch spacing, not idling


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def rows_from_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    rows = {"devices": {}, "host": [], "lines": {}}
    kernels = set()
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m:
                rows["lines"].setdefault(plane.name, []).append(line.name)
                if line.name == OP_LINE:
                    events = rows["devices"][m.group(1)] = []
                    for e in line.events:
                        text = e.name  # the whole HLO instruction: read it once
                        events.append([op_name(text), int(e.start_ns), int(e.duration_ns)])
                        if KERNEL_TARGET in text:
                            kernels.add(events[-1][0])
            elif plane.name.startswith("/host:"):
                rows["host"].extend(
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events if e.name.startswith(HOST_PREFIXES))
    rows["kernels"] = sorted(kernels)
    return rows


def op_name(event_name: str) -> str:
    """The trace names a device event by the whole text of its HLO
    instruction, `%fusion.12 = bf16[...] fusion(...)`: keep `fusion.12`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def self_times(events: list) -> list:
    """Duration of each event less that of the events nested directly inside
    it: a `while` op spans its whole loop, and its body's ops are events of
    their own on the same line."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    own = [e[2] for e in events]
    stack = []
    for i in order:
        _, start, dur = events[i]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= dur
        stack.append(i)
    return [max(0, x) for x in own]


def _union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _name_gaps(host: list, gaps: list) -> dict:
    """Idle nanoseconds by the innermost (shortest) host annotation open at
    each gap's middle, its prefix taken off. One sweep over both in time
    order: a serve slice holds tens of thousands of engine spans."""
    spans = sorted(host, key=lambda h: h[1])
    out: dict = {}
    open_spans, i = [], 0
    for length, mid in sorted(gaps, key=lambda g: g[1]):
        while i < len(spans) and spans[i][1] <= mid:
            open_spans.append(spans[i])
            i += 1
        open_spans = [h for h in open_spans if h[1] + h[2] > mid]
        name = "no_annotation"
        if open_spans:
            name = min(open_spans, key=lambda h: h[2])[0]
            name = next(name[len(p):] for p in HOST_PREFIXES if name.startswith(p))
        out[name] = out.get(name, 0) + length
    return out


def reduce_rows(rows: dict, kernel_ops: dict | None = None) -> dict | None:
    """`kernel_ops` maps an op name of the compiled program to the label of
    the Pallas kernel it calls (from the program's HLO text). Without it the
    kernels are the ops the trace itself marks as Pallas launches
    (`rows["kernels"]`), each under its own name: `kernel_calls` then holds
    every one of them whatever its rank among the device ops."""
    if kernel_ops is None:
        kernel_ops = {n: n for n in rows.get("kernels", [])}
    devices = {k: v for k, v in rows["devices"].items() if v}
    if not devices:
        return None
    busy_ns, window_ns, coll_ns, kernel_ns = [], [], [], []
    op_time: dict = {}
    kernel_calls: dict = {}
    gaps = []
    for events in devices.values():
        start = min(s for _, s, _ in events)
        end = max(s + d for _, s, d in events)
        merged = _union([[s, s + d] for _, s, d in events if d > 0])
        busy_ns.append(sum(e - s for s, e in merged))
        window_ns.append(end - start)
        coll_ns.append(sum(d for n, _, d in events if COLLECTIVE.match(n)))
        kernel_ns.append(sum(d for n, _, d in events if n in kernel_ops))
        for (n, _, d), own in zip(events, self_times(events)):
            key = ("pallas:" + kernel_ops[n] + ":" + n
                   if kernel_ops.get(n, n) != n else n)
            op_time[key] = op_time.get(key, 0) + own
            if n in kernel_ops:
                c = kernel_calls.setdefault(kernel_ops[n], [0, 0])
                c[0] += 1
                c[1] += d
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            if s1 - e0 >= GAP_FLOOR_NS:
                gaps.append((s1 - e0, (s1 + e0) // 2))
    n = len(devices)
    gap_by_name = _name_gaps(rows["host"], gaps)

    def top(seconds_by_name: dict) -> list:
        return [[k, v / n / 1e9] for k, v in
                sorted(seconds_by_name.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "devices": n,
        "device_events": sum(len(events) for events in devices.values()) / n,
        "busy_s": sum(busy_ns) / n / 1e9,
        "window_s": sum(window_ns) / n / 1e9,
        "collective_s": sum(coll_ns) / n / 1e9,
        "kernel_s": sum(kernel_ns) / n / 1e9,
        "kernel_calls": {k: {"calls": c / n, "seconds": ns / n / 1e9}
                         for k, (c, ns) in kernel_calls.items()},
        "breakdown": {"device_ops": top(op_time), "idle_gaps": top(gap_by_name)},
    }


KERNEL_LINE = re.compile(
    r"^\s*%?([\w.\-]+) = (.*?) custom-call\((.*?)\), custom_call_target=\"tpu_custom_call\"")
SHAPE = re.compile(r"\b(?:bf16|f16|f32|f64|s8|s16|s32|s64|u8|u16|u32|u64|pred)\[")


def kernel_ops_from_hlo(hlo_text: str) -> dict:
    """Op name -> signature label `<operands>in_<results>out` for every
    Pallas (Mosaic) custom call of a compiled program's text. The trace
    names a device event by its op, and nothing in the program names its
    kernels yet, so the signature is what tells one kernel from another."""
    out = {}
    for line in hlo_text.splitlines():
        m = KERNEL_LINE.match(line)
        if m:
            out[m.group(1)] = (f"{m.group(3).count('%')}in_"
                               f"{len(SHAPE.findall(m.group(2)))}out")
    return out


def reduce_dir(trace_dir: str, kernel_ops: dict | None = None,
               keep_rows: str | None = None) -> dict | None:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    rows = rows_from_xplane(path)
    if keep_rows:
        with open(keep_rows, "w") as f:
            json.dump({"lines": rows["lines"], "kernels": rows["kernels"],
                       "host": rows["host"][:200],
                       "devices": {k: v[:2000] for k, v in rows["devices"].items()}}, f)
    return reduce_rows(rows, kernel_ops)
