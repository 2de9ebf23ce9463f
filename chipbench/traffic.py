"""The one general generator of serving traffic, and its open-loop client.

A traffic mix is a data file of parameters (chipbench/traffic/<name>.json,
kind `serve`):

    rate_rps        mean arrivals per second, fixed in the cell
    classes         [{"weight", "prompt": <length spec>, "output": <length spec>}]
    length spec     {"dist": "lognormal", "median", "sigma", "min", "max"} |
                    {"dist": "uniform", "min", "max"}
    shuffle_block   optional: how far `--seed` may move an arrival (below)

Every seed gets the same SET of sizes and gaps: the quantiles of the stated
distributions at (i + 0.5) / n (gaps: of the exponential, so the arrivals
are Poisson's). `--seed` draws their ORDER, the prompt texts and, in the
runner, the weights. Without `shuffle_block` the seed permutes all n sizes
and all n gaps. With `shuffle_block: b` it permutes them inside consecutive
blocks of b arrivals of one base order, so that whatever part of the
schedule a window cuts off holds nearly the same work under every seed: a
saturated cell completes only the head of its queue, and which documents
stand there is work (PERF.md, PR 23).
"""

from __future__ import annotations

import dataclasses
import http.client
import json
import math
import statistics
import threading
import time

import numpy as np

from chipbench import harness


@dataclasses.dataclass
class Request:
    index: int
    due_s: float
    prompt_tokens: int
    max_tokens: int
    prompt: str


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """The n stratified quantiles of a length spec, as whole tokens."""
    u = _quantiles(n)
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    else:
        raise harness.BenchError(f"unknown length dist {spec['dist']!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def _shuffled(values, rng, block: int) -> np.ndarray:
    """`values` permuted inside consecutive blocks of `block`."""
    values = np.asarray(values)
    return np.concatenate([rng.permutation(values[i:i + block])
                           for i in range(0, len(values), block)])


def text(n_tokens: int, rng) -> str:
    """ASCII text the byte tokenizer turns into n_tokens (BOS included)."""
    return "".join(map(chr, rng.integers(97, 123, max(0, n_tokens - 1))))


def schedule(traffic: dict, seed: int, seconds: float) -> list:
    """The requests due in [0, seconds), in order of their due time."""
    base = np.random.default_rng(harness.rng_seed(0, 0x5E47))  # the base order
    rng = np.random.default_rng(harness.rng_seed(seed, 0x5E47))
    text_rng = np.random.default_rng(harness.rng_seed(seed, 0x7E87))
    n = max(1, round(traffic["rate_rps"] * seconds))
    classes = traffic["classes"]
    total = sum(c["weight"] for c in classes)
    counts = [int(n * c["weight"] / total) for c in classes]
    counts[0] += n - sum(counts)
    shapes = np.concatenate([
        np.stack([base.permutation(lengths(c["prompt"], k)),
                  base.permutation(lengths(c["output"], k))], axis=1)
        for c, k in zip(classes, counts)])
    gaps = base.permutation(-np.log1p(-_quantiles(n)))  # mean 1
    block = int(traffic.get("shuffle_block", n))
    shapes = shapes[_shuffled(base.permutation(n), rng, block)]
    gaps = _shuffled(gaps, rng, block)
    due = np.cumsum(gaps) * seconds / (gaps.sum() + 1.0)
    out = []
    for i, (p, o) in enumerate(shapes):
        body = text(int(p), text_rng)
        out.append(Request(i, float(due[i]), len(body) + 1, int(o), body))
    return out


# ------------------------------------------------------------ the client


class Client:
    """POSTs `/v1/completions` to the proxy; streamed answers are read token
    by token."""

    def __init__(self, host: str, port: int, timeout_s: float = 300.0):
        self.host, self.port, self.timeout_s = host, port, timeout_s

    def post(self, path: str, body: dict, on_token=None) -> dict:
        """Returns {"status", "token_ids", "usage"}; with `on_token`, streams
        and calls it with the number of tokens of every chunk as it arrives,
        and returns the `finish_reason` of the stream's closing chunk (None:
        the stream ended without one). An answer that is empty because its
        first greedy token was EOS is only that closing chunk: `on_token(0)`
        then says when the user learned the answer."""
        conn = http.client.HTTPConnection(self.host, self.port, timeout=self.timeout_s)
        try:
            conn.request("POST", path, json.dumps(body),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                return {"status": resp.status, "token_ids": [],
                        "error": resp.read()[:200].decode("utf-8", "replace")}
            if on_token is None:
                answer = json.loads(resp.read())
                choices = answer.get("choices") or [{}]
                return {"status": 200, "usage": answer.get("usage"),
                        "token_ids": choices[0].get("token_ids", []),
                        "answer": answer}
            ids, finish = [], None
            while True:
                line = resp.readline()
                if not line or line.strip() == b"data: [DONE]":
                    break
                if line.startswith(b"data: "):
                    choice = json.loads(line[6:])["choices"][0]
                    new = choice.get("token_ids", [])
                    if new:
                        on_token(len(new))
                        ids.extend(new)
                    if choice.get("finish_reason") is not None:
                        finish = choice["finish_reason"]
                        if not ids:
                            on_token(0)
            return {"status": 200, "token_ids": ids, "finish_reason": finish}
        finally:
            conn.close()


def open_loop(client: Client, requests: list, seconds: float, *,
              drain_timeout_s: float = 120.0) -> tuple:
    """Send every request at its due time whatever the server does, one
    thread per request in flight. Times are seconds from the window's start;
    the window is [0, seconds]. Returns (records, seconds). Nothing is sent
    after the window; the run then waits for the answers still on their way
    (a saturated mix leaves a queue behind), because the first token of the
    request in progress at the window's end says how far it had come."""
    records = [None] * len(requests)
    t0 = time.perf_counter()

    def one(req: Request) -> None:
        rec = {"index": req.index, "due_s": req.due_s,
               "prompt_tokens": req.prompt_tokens, "max_tokens": req.max_tokens,
               "sent_s": time.perf_counter() - t0, "first_s": None,
               "last_s": None, "tokens": 0, "tokens_in_window": 0,
               "status": None}
        records[req.index] = rec

        def on_token(count):
            now = time.perf_counter() - t0
            if rec["first_s"] is None:
                rec["first_s"] = now
            rec["last_s"] = now
            if now <= seconds:
                rec["tokens_in_window"] += count

        try:
            ans = client.post("/v1/completions", {
                "prompt": req.prompt, "max_tokens": req.max_tokens,
                "temperature": 0.0, "stream": True}, on_token)
            rec["tokens"] = len(ans["token_ids"])
            # answered: tokens came, or the closing chunk of an answer that is
            # empty by EOS did; a stream that broke before either is "empty"
            answered = rec["tokens"] or ans.get("finish_reason") is not None
            rec["status"] = "ok" if ans["status"] == 200 and answered else (
                f"http_{ans['status']}" if ans["status"] != 200 else "empty")
        except Exception as e:  # noqa: BLE001 — every failure is a record
            rec["status"] = f"{type(e).__name__}: {e}"[:120]
        rec["done_s"] = time.perf_counter() - t0

    threads = []
    for req in requests:
        wait = req.due_s - (time.perf_counter() - t0)
        if wait > 0:
            time.sleep(wait)
        th = threading.Thread(target=one, args=(req,), daemon=True)
        th.start()
        threads.append(th)
    rest = seconds - (time.perf_counter() - t0)
    if rest > 0:
        time.sleep(rest)
    deadline = time.monotonic() + drain_timeout_s
    for th in threads:
        th.join(max(0.0, deadline - time.monotonic()))
    return [r for r in records if r is not None], float(seconds)


def summarize(records: list, window_s: float) -> dict:
    """Client-side numbers of a window. A tail is the tail of ALL requests:
    one that failed, or never answered, counts as the slowest."""
    ok = [r for r in records if r["status"] == "ok"]
    failed = [r for r in records if r["status"] != "ok"]
    inf = float("inf")
    ttft = [(r["first_s"] - r["due_s"]) * 1e3 if r["status"] == "ok" else inf
            for r in records]
    tpot = [(r["last_s"] - r["first_s"]) / (r["tokens"] - 1) * 1e3
            for r in ok if r["tokens"] > 1]
    late = [(r["sent_s"] - r["due_s"]) * 1e3 for r in records]
    # Every token the window delivered, over all of the window. An output
    # token counts as it arrives. A prompt counts as its prefill proceeds:
    # evenly from the first token of the request before it (prefill is first
    # come, first served), or its own sending if that is later, to its own
    # first token. So the prompt in progress when the window ends counts
    # for the part done; counting a prompt whole at its first token moved
    # the rate in steps of a document, 1-2 % of a saturated window.
    prompt_in, output_in, before = 0.0, 0, None
    for r in sorted((r for r in records if r["first_s"] is not None),
                    key=lambda r: r["first_s"]):
        start = r["sent_s"] if before is None else max(before, r["sent_s"])
        if r["first_s"] <= window_s:
            prompt_in += r["prompt_tokens"]
            output_in += r["tokens_in_window"]
        elif start < window_s:
            prompt_in += r["prompt_tokens"] * (window_s - start) / (r["first_s"] - start)
        before = r["first_s"]
    done = [r for r in ok if r["done_s"] <= window_s]
    out = {"attempted": len(records), "failed": len(failed), "completed": len(ok),
           "served_tok_s": (prompt_in + output_in) / window_s,
           "prompt_tokens_in_window": prompt_in,
           "output_tokens_in_window": output_in,
           "completed_in_window": len(done),
           "completed_tok_s": sum(r["prompt_tokens"] + r["tokens"] for r in done) / window_s}
    if ttft:
        out["ttft_p50_ms"] = harness.percentile(ttft, 50)
        out["ttft_p95_ms"] = harness.percentile(ttft, 95)
    if tpot:
        out["tpot_p50_ms"] = statistics.median(tpot)
        out["tpot_p95_ms"] = harness.percentile(tpot, 95)
    if late:
        out["late_p95_ms"] = harness.percentile(late, 95)
    return {k: (None if isinstance(v, float) and math.isinf(v) else v)
            for k, v in out.items()}
