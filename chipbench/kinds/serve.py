"""Traffic kind `serve`: the cell's configuration behind
`serve.run(build_openai_app(LLMConfig))` and the HTTP proxy, one replica on
one chip, an open loop of streamed `/v1/completions` at the rate fixed in
the traffic file. Latencies are timed from the instant a request was due.

The replica is a process of its own and holds the chip; this process stays
off JAX. Counters come from `TPUEngine.stats()` before and after the
window. A traced run traces a SLICE of the window (the traffic file's
`trace` group), through the hook that `chipbench.program.SeededLLMConfig`
starts inside the replica, and reads the counters once more at each end of
the slice (`TraceSlice`); it comes back with a device trace or raises.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time

import numpy as np

from chipbench import harness, program, traffic as gen


def llm_config(conf: dict, seed: int, trace_ctl: str | None, on_chip: bool = True):
    from ray_tpu.llm import ModelLoadingConfig

    prog = conf["program"]
    return program.SeededLLMConfig(
        model_family=prog["family"],
        model_loading_config=ModelLoadingConfig(
            model_id=prog["model_id"], tokenizer=conf.get("tokenizer", "byte")),
        model_kwargs=program.model_kwargs(prog),
        engine_kwargs=dict(conf["engine"]),
        deployment_config=dict(conf.get("deployment_config", {})),
        seed=seed, trace_ctl=trace_ctl,
        accelerator_type="TPU" if on_chip else None)


def deploy(config, timeout_s: float) -> tuple:
    """serve.run + the HTTP proxy; (host, port) once the replica is healthy."""
    from ray_tpu import serve
    from ray_tpu.llm import build_openai_app

    serve.start(http_port=0)
    serve.run(build_openai_app(config), name="chipbench")
    deadline = time.monotonic() + timeout_s
    while True:
        health = [h for st in serve.status().values()
                  for h in st["replica_health"].values()]
        if health and all(h == "healthy" for h in health):
            return serve.http_address()
        if time.monotonic() > deadline:
            raise harness.BenchError(
                f"the replica is not healthy after {timeout_s:.0f}s: {health}")
        time.sleep(0.25)


def wait_chips_free(chips: int, timeout_s: float = 120.0) -> None:
    import ray_tpu

    deadline = time.monotonic() + timeout_s
    while ray_tpu.available_resources().get("TPU", 0) < chips:
        if time.monotonic() > deadline:
            raise harness.BenchError(f"{chips} chip(s) not free again")
        time.sleep(0.2)


def warm_up(client: gen.Client, warmup: list, seed: int, wave: int = 0) -> list:
    """The traffic file's warm-up requests `[prompt_tokens, max_tokens]`, one
    after another: every prefill bucket, prefix span and decode page bound
    the window will use compiles (or loads) here. Then `wave` short requests
    of those shapes at once, so that every slot has held a row."""
    rng = np.random.default_rng(harness.rng_seed(seed, 0x3A23))
    bodies = [{"prompt": gen.text(p, rng), "max_tokens": o, "temperature": 0.0}
              for p, o in warmup]
    answers = [client.post("/v1/completions", b) for b in bodies]
    bad = [a for a in answers if a["status"] != 200]
    if bad:
        raise harness.BenchError(f"warm-up requests failed: {bad[:3]}")
    if not wave:
        return answers
    # other texts: a repeated prompt would hit the prefix cache and warm the
    # continuation programs instead of the ones distinct prompts use
    bodies = [{"prompt": gen.text(warmup[i % len(warmup)][0], rng),
               "max_tokens": min(16, warmup[i % len(warmup)][1]), "temperature": 0.0}
              for i in range(wave)]
    waved = [None] * len(bodies)

    def one(i):
        waved[i] = client.post("/v1/completions", {**bodies[i], "stream": True},
                              lambda count: None)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(len(bodies))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(600.0)
    bad = [a for a in waved if a is None or a["status"] != 200]
    if bad:
        raise harness.BenchError(f"warm-up requests failed: {bad[:3]}")
    return answers


# `stop_trace` takes about 0.14 ms a device event on a serving replica: 21-46 s
# for the five cells' slices (PERF.md section 6, PR 41). Ten times the usual
# 25-30 s; past it the run is an error that names the remedy
STOP_TRACE_LIMIT_S = 300.0
START_TRACE_LIMIT_S = 60.0
DEVICE_SPAN_FLOOR = 0.8  # of the slice: under it the device's buffers ran full


def trace_slice(mix: dict, seconds: float) -> tuple:
    """(offset_s, seconds) of the traced slice: the traffic file's `trace`
    group, cut in proportion where the window is shorter than the two
    together (the CPU rehearsal's) so that the slice ends inside it."""
    group = mix.get("trace")
    if not group:
        raise harness.BenchError(
            "the traffic file has no `trace` group {\"offset_s\", \"seconds\"}: a "
            "traced serve run traces a slice of its window and takes no default")
    offset, length = float(group["offset_s"]), float(group["seconds"])
    if offset + length > seconds:
        scale = 0.9 * seconds / (offset + length)
        offset, length = offset * scale, length * scale
    return offset, length


class TraceSlice(threading.Thread):
    """Beside the open loop: `start` after `offset_s` of the window, the
    counters once the hook says `started`, `stop` after `seconds` more, the
    counters again, then the wait for the hook's `done`. `result()` gives
    {"stats_t0", "stats_t1", how long each reading took, "trace_span_s",
    "trace_stop_s"} or raises what went wrong here."""

    def __init__(self, ctl: str, client: gen.Client, offset_s: float, seconds: float):
        super().__init__(daemon=True, name="chipbench-trace-slice")
        self.ctl, self.client = ctl, client
        self.offset_s, self.seconds = offset_s, seconds
        self.reads, self.facts, self.error = {}, None, None

    def _await(self, name: str, limit_s: float, what: str, remedy: str = "") -> None:
        deadline = time.monotonic() + limit_s
        while not os.path.exists(os.path.join(self.ctl, name)):
            if time.monotonic() > deadline:
                raise harness.BenchError(
                    f"{what} within {limit_s:.0f} s (the slice: {self.seconds:.1f} s, "
                    f"{self.offset_s:.1f} s into the window){remedy}")
            time.sleep(0.02)

    def _read(self, key: str) -> None:
        t = time.perf_counter()
        self.reads[key] = self.client.post("/v1/stats", {})["answer"]
        self.reads[key + "_took_s"] = time.perf_counter() - t

    def run(self) -> None:
        try:
            time.sleep(self.offset_s)
            open(os.path.join(self.ctl, "start"), "w").close()
            self._await("started", START_TRACE_LIMIT_S,
                        "the replica's trace hook did not start")
            t0 = time.perf_counter()
            # in a thread of its own: a replica with a queue answers seconds
            # late (its `loop.thread_s` says when), and `stop` does not wait
            first = threading.Thread(target=self._read, args=("stats_t0",), daemon=True)
            first.start()
            time.sleep(self.seconds)
            open(os.path.join(self.ctl, "stop"), "w").close()
            span, stopped = time.perf_counter() - t0, time.time()
            self._read("stats_t1")
            first.join(self.client.timeout_s)
            if "stats_t0" not in self.reads:
                raise harness.BenchError("no answer to `/v1/stats` at the slice's start")
            self._await("done", STOP_TRACE_LIMIT_S,
                        "`jax.profiler.stop_trace()` in the replica did not return",
                        "; it takes about 0.14 ms a device event there: shorten "
                        "`trace.seconds` in the traffic file")
            with open(os.path.join(self.ctl, "done")) as f:
                done = float(f.read())
            self.facts = {**self.reads, "trace_span_s": span,
                          "trace_stop_s": done - stopped}
        except Exception as e:  # noqa: BLE001 — raised again in `result`
            self.error = e

    def result(self) -> dict:
        self.join(self.offset_s + self.seconds + START_TRACE_LIMIT_S
                  + STOP_TRACE_LIMIT_S + 30.0)
        if self.error is not None:
            raise self.error
        if self.facts is None:
            raise harness.BenchError("the trace slice's thread did not end")
        return self.facts


def reduce_trace(trace_ctl: str, out_dir: str, span_s: float, on_chip: bool):
    """The slice's device trace as numbers, or the error that says why there
    is none. Only the CPU rehearsal, which has no device plane, gets None."""
    from chipbench import trace_reduce

    trace = trace_reduce.reduce_dir(
        os.path.join(trace_ctl, "trace"),
        keep_rows=os.path.join(out_dir, "trace_rows.json"))
    shutil.rmtree(trace_ctl, ignore_errors=True)  # tens of MB a run
    if not on_chip:
        return trace
    if trace is None:
        raise harness.BenchError(
            "the traced slice left no device trace: no `.xplane.pb` under "
            f"{trace_ctl}/trace, or no device plane with events in it")
    if trace["window_s"] < DEVICE_SPAN_FLOOR * span_s:
        raise harness.BenchError(
            f"the device's trace was cut short: its buffers held "
            f"{trace['device_events']:.0f} events over {trace['window_s']:.2f} s of a "
            f"{span_s:.2f} s slice; shorten `trace.seconds` in the traffic file")
    return trace


def run(cell: dict, args, out_dir: str, t_start: float, *,
        on_chip: bool = True) -> dict:
    """`on_chip=False` is the CPU rehearsal of the tests: the same path with
    a host-only engine at a tiny size; run.py never passes it."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm.tokenizer import load_tokenizer

    conf, mix = cell["config_file"], cell["traffic_file"]
    trace_ctl, tracing, sliced = None, None, {}
    if args.trace:
        offset_s, slice_s = trace_slice(mix, args.seconds)
        trace_ctl = os.path.join(out_dir, "trace_ctl")
        shutil.rmtree(trace_ctl, ignore_errors=True)  # a marker of an earlier run
        os.makedirs(trace_ctl)
    requests = gen.schedule(mix, args.seed, args.seconds)
    ray_tpu.init(num_tpus=cell["chips"] if on_chip else None)
    try:
        try:
            host, port = deploy(llm_config(conf, args.seed, trace_ctl, on_chip),
                                conf.get("ready_timeout_s", 1100.0))
            ready_s = time.time() - t_start
            client = gen.Client(host, port)
            warm_up(client, mix["warmup"], args.seed, mix.get("warmup_wave", 0))
            # the request the comparison with the reference is made on
            check = conf["check"]
            text = gen.text(check["sample_tokens"], np.random.default_rng(
                harness.rng_seed(args.seed, 0xC4EC)))
            sample = client.post("/v1/completions", {
                "prompt": text, "max_tokens": check["positions"],
                "temperature": 0.0})
            stats0, t_stats0 = client.post("/v1/stats", {})["answer"], time.perf_counter()
            setup_s = time.time() - t_start
            if trace_ctl:
                tracing = TraceSlice(trace_ctl, client, offset_s, slice_s)
                tracing.start()
            records, window_s = gen.open_loop(client, requests, args.seconds)
            stats1 = client.post("/v1/stats", {})["answer"]
            stats_span_s = time.perf_counter() - t_stats0  # the drain included
            if tracing:
                sliced = tracing.result()
        finally:
            serve.shutdown()
        with open(os.path.join(out_dir, "requests.jsonl"), "w") as f:
            for r in records:
                f.write(json.dumps(r) + "\n")
        summary = gen.summarize(records, window_s)
        if on_chip:
            wait_chips_free(cell["chips"])
        prompt_ids = load_tokenizer(conf.get("tokenizer", "byte")).encode(text)
        from chipbench import check as checks

        verdict = ray_tpu.get(
            ray_tpu.remote(num_tpus=1 if on_chip else None)(checks.serve_check).remote(
                conf, args.seed, prompt_ids, sample["token_ids"], on_chip),
            timeout=900.0)
    finally:
        ray_tpu.shutdown()

    compiles = (stats1["compile_cache"]["requests"] - stats0["compile_cache"]["requests"])
    device = {**stats1["device"],
              "memory_peak_bytes": stats1["device_memory"]["peak_bytes_in_use"]}
    facts = {"ready_s": ready_s, "setup_s": setup_s, "window_s": window_s,
             "stats0": stats0, "stats1": stats1, "stats_span_s": stats_span_s,
             "client": summary,
             "compiles_in_window": compiles, "check": verdict,
             "rate_rps": mix["rate_rps"], "requests": len(requests), **sliced}
    breakdown = None
    if trace_ctl:
        trace = facts["trace"] = reduce_trace(
            trace_ctl, out_dir, sliced["trace_span_s"], on_chip)
        if trace:
            device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
            breakdown = trace["breakdown"]
    with open(os.path.join(out_dir, "facts.json"), "w") as f:
        json.dump(facts, f)
    ok = (summary["failed"] == 0 and summary["completed"] > 0
          and compiles == 0 and verdict["ok"]
          and all(device[k] == verdict["device"][k] for k in ("platform", "kind", "count")))
    return {"correct": bool(ok), "attempted": summary["attempted"],
            "failed": summary["failed"], "device": device,
            "end_to_end": {**summary, "setup_s": setup_s},
            "breakdown": breakdown, "facts": facts}
