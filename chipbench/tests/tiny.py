"""Tiny stand-ins for the cells' files: the same keys at toy widths, for the
CPU rehearsals. Nothing here is a configuration of the benchmark."""

import copy
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def gpt2_cell(mesh=None):
    conf = _load("configs", "gpt2-large.json")
    conf["sizes"].update(d_model=64, n_layers=2, n_heads=4, n_kv_heads=4, d_head=16,
                         d_ff=128, vocab_size=211, max_seq_len=64)
    conf["program"] = {"family": "gpt2", "model_id": "124m", "model_kwargs": {
        "d_model": 64, "n_layers": 2, "n_heads": 4, "d_ff": 128, "vocab_size": 211,
        "max_seq_len": 64, "dtype": "float32"}}
    conf["mesh"] = mesh or {}
    conf["check"].update(sample_tokens=32, logits_rel_tol=1e-3, loss_abs_tol=1e-3,
                         grad_rel_tol=1e-2)
    traffic = _load("traffic", "train-b4-s1024.json")
    traffic.update(batch=4, seq=32, warmup_steps=2, block_seconds=0.2,
                   max_steps_per_s=200, trace_seconds=0.5)
    return {"name": "tiny.train", "chips": 1, "config_file": conf,
            "traffic_file": traffic, "run_seconds": 1}


def mixtral_cell(capacity_factor=4.0):
    conf = _load("configs", "mixtral-8x7b.json")
    conf["sizes"].update(d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_head=16,
                         d_ff=96, vocab_size=300, max_seq_len=512)
    conf["program"]["model_id"] = "tiny"
    conf["program"]["model_kwargs"] = {
        "d_model": 64, "n_layers": 2, "n_heads": 4, "n_kv_heads": 2, "d_ff": 96,
        "vocab_size": 300, "max_seq_len": 512, "dtype": "float32",
        "param_dtype": "float32",
        "moe": {"num_experts": 8, "top_k": 2, "capacity_factor": capacity_factor}}
    conf["engine"] = {"kv_layout": "paged", "page_size": 16, "max_slots": 4,
                      "max_len": 256, "min_bucket": 32, "num_pages": 64,
                      "prefill_chunk": 64, "enable_prefix_cache": True}
    conf["check"].update(sample_tokens=24, positions=6, logits_rel_tol=2e-3,
                         logits_median_tol=2e-3,
                         served_gap_tol=1e-2, router_tie=1e-4)
    conf["ready_timeout_s"] = 300.0
    traffic = copy.deepcopy(_load("traffic", "chat-steady.json"))
    traffic.update(rate_rps=6.0, warmup=[[20, 8], [40, 4]], warmup_wave=4)
    traffic["classes"][0]["prompt"].update(median=24, min=8, max=100)
    traffic["classes"][0]["output"].update(median=6, min=2, max=12)
    return {"name": "tiny.serve", "chips": 1, "config_file": conf,
            "traffic_file": traffic, "run_seconds": 2}
