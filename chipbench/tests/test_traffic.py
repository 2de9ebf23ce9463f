"""The traffic generator is deterministic in --seed, gives every seed the
same work in another order, and its client reports how late it ran."""

import http.server
import json
import threading
import time

import numpy as np
import pytest

from chipbench import harness, traffic

MIX = harness.load_json(harness.BENCH_DIR, "traffic", "chat-steady.json")


def test_schedule_is_deterministic_in_the_seed():
    a = traffic.schedule(MIX, 2**31 + 77, 30)
    b = traffic.schedule(MIX, 2**31 + 77, 30)
    c = traffic.schedule(MIX, 78, 30)
    assert a == b and a != c
    assert len(a) == round(MIX["rate_rps"] * 30)
    assert all(0 <= r.due_s < 30 for r in a)
    assert [r.due_s for r in a] == sorted(r.due_s for r in a)
    assert all(len(r.prompt) + 1 == r.prompt_tokens for r in a)


def test_every_seed_gets_the_same_work_in_another_order():
    a = traffic.schedule(MIX, 1, 30)
    c = traffic.schedule(MIX, 2, 30)
    for field in ("prompt_tokens", "max_tokens"):
        assert sorted(getattr(r, field) for r in a) == sorted(getattr(r, field) for r in c)
        assert [getattr(r, field) for r in a] != [getattr(r, field) for r in c]
    gaps = [np.diff([0.0] + [r.due_s for r in x]) for x in (a, c)]
    assert np.allclose(np.sort(gaps[0]), np.sort(gaps[1])) and not np.allclose(*gaps)
    assert a[-1].due_s == pytest.approx(c[-1].due_s)
    assert all(x.prompt != y.prompt for x, y in zip(a, c))
    spec = MIX["classes"][0]["prompt"]
    lens = [r.prompt_tokens for r in a]
    assert min(lens) >= spec["min"] and max(lens) <= spec["max"]
    assert abs(np.median(lens) - spec["median"]) < 0.15 * spec["median"]
    # the gaps are the exponential's quantiles: Poisson arrivals at the rate
    assert np.std(gaps[0]) == pytest.approx(np.mean(gaps[0]), rel=0.1)


@pytest.mark.parametrize("block", [4, 8])
def test_shuffle_block_keeps_the_work_of_every_block(block):
    """With `shuffle_block` the seed moves an arrival inside its block only:
    whatever head of the schedule a window completes holds the same work."""
    mix = {**MIX, "shuffle_block": block}
    a = traffic.schedule(mix, 1, 30)
    c = traffic.schedule(mix, 2**31 + 2, 30)
    whole = traffic.schedule({k: v for k, v in mix.items() if k != "shuffle_block"}, 1, 30)
    assert [r.prompt_tokens for r in a] != [r.prompt_tokens for r in c]
    for i in range(0, len(a), block):
        for field in ("prompt_tokens", "max_tokens"):
            assert (sorted(getattr(r, field) for r in a[i:i + block])
                    == sorted(getattr(r, field) for r in c[i:i + block]))
        assert a[min(i + block, len(a)) - 1].due_s == pytest.approx(
            c[min(i + block, len(a)) - 1].due_s)
    assert (sorted(r.prompt_tokens for r in whole[:block])
            != sorted(r.prompt_tokens for r in a[:block]))


def test_classes_share_the_queue_by_weight():
    mix = {"rate_rps": 10, "classes": [
        {"weight": 3, "prompt": {"dist": "uniform", "min": 300, "max": 400},
         "output": {"dist": "uniform", "min": 4, "max": 8}},
        {"weight": 1, "prompt": {"dist": "uniform", "min": 20, "max": 30},
         "output": {"dist": "uniform", "min": 4, "max": 8}}]}
    reqs = traffic.schedule(mix, 5, 20)
    assert len(reqs) == 200
    assert sum(r.prompt_tokens >= 300 for r in reqs) == 150
    assert 20 < sum(r.prompt_tokens >= 300 for r in reqs[:40]) < 40   # mixed


class _Stub(http.server.BaseHTTPRequestHandler):
    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.end_headers()
        for i in range(body["max_tokens"]):
            time.sleep(0.01)
            chunk = {"choices": [{"token_ids": [i]}]}
            self.wfile.write(b"data: " + json.dumps(chunk).encode() + b"\n\n")
            self.wfile.flush()
        self.wfile.write(b"data: [DONE]\n\n")

    def log_message(self, *a):
        pass


def test_open_loop_times_from_due_and_reports_lateness():
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Stub)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        client = traffic.Client("127.0.0.1", server.server_address[1])
        reqs = [traffic.Request(i, 0.05 * i, 10, 5, "x" * 9) for i in range(20)]
        records, window_s = traffic.open_loop(client, reqs, 1.2)
    finally:
        server.shutdown()
    out = traffic.summarize(records, window_s)
    assert out["attempted"] == 20 and out["failed"] == 0 and out["completed"] == 20
    assert all(r["tokens"] == 5 and r["sent_s"] >= r["due_s"] for r in records)
    assert window_s == 1.2
    assert 0 < sum(r["tokens_in_window"] for r in records) <= 100
    assert out["completed_tok_s"] <= out["served_tok_s"]
    assert 0 <= out["late_p95_ms"] < 50
    assert 10 <= out["ttft_p50_ms"] < 100      # one 10 ms token after the due time
    assert 8 <= out["tpot_p50_ms"] < 30
    # a failed request is the slowest of all: it may not leave the tail
    records[0]["status"] = "http_500"
    assert traffic.summarize(records[:10], window_s)["ttft_p95_ms"] is None


def _rec(i, sent, first, done, prompt, tokens, in_window):
    return {"index": i, "due_s": sent, "sent_s": sent, "first_s": first, "last_s": done,
            "done_s": done, "prompt_tokens": prompt, "max_tokens": tokens,
            "tokens": tokens, "tokens_in_window": in_window, "status": "ok"}


def test_a_rate_is_every_token_delivered_over_all_of_the_window():
    """By hand, window 10 s. Output tokens count as they arrive; a prompt
    counts as its prefill proceeds, from the first token before it (or its
    own sending, if later) to its own first token."""
    records = [
        _rec(0, 0.0, 2.0, 4.0, 1000, 20, 20),     # whole: 1000 + 20
        _rec(1, 1.0, 6.0, 12.0, 2000, 30, 20),    # prefilled inside, 20 of 30 tokens inside
        _rec(2, 7.0, 15.0, 20.0, 4000, 10, 0),    # sent at 7 (after 6): 3 of 8 s done = 1500
        _rec(3, 8.0, 18.0, 22.0, 9000, 10, 0),    # its prefill starts at 15: nothing
    ]
    out = traffic.summarize(records, 10.0)
    assert out["prompt_tokens_in_window"] == pytest.approx(1000 + 2000 + 1500)
    assert out["output_tokens_in_window"] == 40
    assert out["served_tok_s"] == pytest.approx(454.0)
    assert out["completed_tok_s"] == pytest.approx(102.0) and out["completed_in_window"] == 1
    # a request that never answered is failed, the slowest of all, and counts for nothing
    records[2].update(first_s=None, last_s=None, status=None, tokens=0)
    out = traffic.summarize(records, 10.0)
    assert out["failed"] == 1 and out["ttft_p95_ms"] is None
    assert out["prompt_tokens_in_window"] == pytest.approx(3000 + 9000 * 2 / 10)


class _EosStub(http.server.BaseHTTPRequestHandler):
    """The server's stream by the prompt: "eos" is answered with the closing
    chunk alone (the first greedy token was EOS), "cut" breaks before any
    chunk, anything else gets its tokens and then the closing chunk."""

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.end_headers()
        if body["prompt"] == "cut":
            return
        chunks = [{"token_ids": [i], "finish_reason": None}
                  for i in range(0 if body["prompt"] == "eos" else body["max_tokens"])]
        for choice in chunks + [{"text": "", "finish_reason": "stop"}]:
            time.sleep(0.01)
            self.wfile.write(b"data: " + json.dumps({"choices": [choice]}).encode() + b"\n\n")
            self.wfile.flush()
        self.wfile.write(b"data: [DONE]\n\n")

    def log_message(self, *a):
        pass


def test_an_answer_that_is_empty_by_eos_is_an_answer():
    """Only the closing chunk: answered with nothing, when that chunk came.
    A stream that broke before its closing chunk stays failed."""
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _EosStub)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        client = traffic.Client("127.0.0.1", server.server_address[1])
        reqs = [traffic.Request(i, 0.05 * i, 80, 4, p)
                for i, p in enumerate(["text", "eos", "cut", "text"])]
        records, window_s = traffic.open_loop(client, reqs, 0.5)
    finally:
        server.shutdown()
    assert [r["status"] for r in records] == ["ok", "ok", "empty", "ok"]
    eos, cut = records[1], records[2]
    assert eos["tokens"] == 0 and eos["tokens_in_window"] == 0
    assert eos["first_s"] == eos["last_s"] and eos["sent_s"] < eos["first_s"] <= eos["done_s"]
    assert cut["first_s"] is None and cut["tokens"] == 0
    out = traffic.summarize(records, window_s)
    assert out["attempted"] == 4 and out["failed"] == 1 and out["completed"] == 3
    # its prompt counts like any other, it gives no gap between tokens, and
    # its time to the answer is in the tail with the others
    assert out["prompt_tokens_in_window"] == 3 * 80 and out["output_tokens_in_window"] == 8
    both = [r for r in records if r["tokens"] > 1]
    assert out["tpot_p50_ms"] == pytest.approx(np.median(
        [(r["last_s"] - r["first_s"]) / 3 * 1e3 for r in both]))
    assert out["ttft_p50_ms"] is not None and out["ttft_p95_ms"] is None   # the cut one
    assert traffic.summarize([eos], window_s)["ttft_p95_ms"] == pytest.approx(
        (eos["first_s"] - eos["due_s"]) * 1e3)
    assert "tpot_p50_ms" not in traffic.summarize([eos], window_s)


def test_summarize_reads_a_recorded_run_as_before():
    """Twenty rows of a run on the chip (chat-steady, seed 2147483813, PR 24),
    request 71 among them: its first greedy token was EOS and the client of
    that day recorded it `empty`. `summarize` gives what it gave then, to
    every digit; with that row as the client records it now, the one failure
    goes and nothing else moves but the prompt it adds."""
    with open(harness.BENCH_DIR + "/tests/data/requests_chat_2147483813.jsonl") as f:
        rows = [json.loads(line) for line in f]
    before = traffic.summarize(rows, 40.0)
    assert before == {
        "attempted": 20, "failed": 1, "completed": 19, "served_tok_s": 193.125,
        "prompt_tokens_in_window": 5324.0, "output_tokens_in_window": 2401,
        "completed_in_window": 19, "completed_tok_s": 193.125,
        "ttft_p50_ms": 58.57733142606669, "ttft_p95_ms": 180.66016392090845,
        "tpot_p50_ms": 41.253652611111924, "tpot_p95_ms": 43.30903782222195,
        "late_p95_ms": 1.4337381543043648}
    row, = [r for r in rows if r["status"] == "empty"]
    assert row["index"] == 71 and row["prompt_tokens"] == 80
    row.update(status="ok", first_s=row["done_s"], last_s=row["done_s"])
    now = traffic.summarize(rows, 40.0)
    assert now["failed"] == 0 and now["completed"] == now["completed_in_window"] == 20
    assert now["prompt_tokens_in_window"] == 5324.0 + 80
    assert now["served_tok_s"] == now["completed_tok_s"] == (5324 + 80 + 2401) / 40.0
    for same in ("output_tokens_in_window", "tpot_p50_ms", "tpot_p95_ms", "late_p95_ms"):
        assert now[same] == before[same], same
