"""Traffic kind `train`: AdamW steps of the cell's configuration through
`JaxTrainer`, one worker holding the cell's chips, `train/spmd.py`'s step on
the configuration's mesh, a fresh batch each step from a running
`ray_tpu.data` `range -> map_batches` pipeline.

`train_tok_s_per_chip` is every token of the window over all of its wall
time, per chip: steps x batch x seq / window wall / chips. The window is cut
into blocks of consecutive steps, each ended by `block_until_ready`, a fetch
of the loss and `train.report`; the window's clock runs through all of that.
The wall time of each block (to its `block_until_ready`) is a reading of its
own: `block_tok_s_per_chip` is tokens per block / the MEDIAN block time /
chips, which a single stall cannot move, and `train_stall_pct` is the share
of the window that the median block does not account for. Both stand beside
the judged rate as per-layer metrics and tell an outlying run apart: one
long block, or every block slower.
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import time

from chipbench import flops, harness, program


def token_dataset(rows: int, batch: int, seq: int, vocab: int, seed: int):
    """`rows` seeded random token rows [seq + 1], made by map tasks as the
    loop consumes them: every row differs, the same seed gives the same rows."""
    import ray_tpu.data as rdata

    entropy = harness.rng_seed(seed, 0x7041)

    def tokens(block: dict) -> dict:
        import numpy as np

        return {"tokens": np.stack([
            np.random.default_rng(entropy + [int(i)]).integers(
                0, vocab, seq + 1, dtype=np.int32) for i in block["id"]])}

    return rdata.range(rows, parallelism=max(1, rows // (batch * 8))).map_batches(
        tokens, batch_size=batch)


def run(cell: dict, args, out_dir: str, t_start: float, *,
        on_chip: bool = True) -> dict:
    """`on_chip=False` is the CPU rehearsal of the tests: the same path on
    host workers at a tiny size; run.py never passes it."""
    import ray_tpu
    from ray_tpu import train

    conf, traffic = cell["config_file"], cell["traffic_file"]
    sizes = conf["sizes"]
    steps_budget = int((args.seconds + traffic["trace_seconds"] + 5)
                       * traffic["max_steps_per_s"]) + traffic["warmup_steps"] + 8
    ray_tpu.init(num_tpus=cell["chips"] if on_chip else None)
    try:
        trainer = train.JaxTrainer(
            worker_loop,
            train_loop_config={
                "t_start": t_start, "seed": args.seed, "seconds": args.seconds,
                "trace": bool(args.trace), "out_dir": out_dir, "on_chip": on_chip,
                "chips": cell["chips"], "conf": conf, "traffic": traffic},
            scaling_config=train.ScalingConfig(
                num_workers=1, use_tpu=on_chip,
                resources_per_worker={"TPU": cell["chips"]} if on_chip else None),
            run_config=train.RunConfig(
                name="chipbench", storage_path=os.path.join(out_dir, "train_results")),
            datasets={"train": token_dataset(
                steps_budget * traffic["batch"], traffic["batch"],
                traffic["seq"], sizes["vocab_size"], args.seed)})
        facts = trainer.fit().metrics["facts"]
    finally:
        ray_tpu.shutdown()
    return {"correct": facts.pop("correct"), "attempted": facts["steps"],
            "failed": facts["failed_steps"], "device": facts.pop("device"),
            "end_to_end": {"train_tok_s_per_chip": facts["train_tok_s_per_chip"],
                           "setup_s": facts["setup_s"]},
            "breakdown": facts.pop("breakdown", None), "facts": facts}


# ------------------------------------------------ inside the chip worker


def worker_loop(config: dict) -> None:
    """`train_loop_per_worker`: runs in the worker the GCS bound the chips to."""
    import jax
    import numpy as np
    import optax

    from ray_tpu import train
    from ray_tpu._private import accelerators
    from ray_tpu.models import transformer
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train.spmd import init_opt_state, init_sharded, make_train_step

    t_start, conf, traffic = config["t_start"], config["conf"], config["traffic"]
    out_dir, chips = config["out_dir"], config["chips"]
    accelerators.compile_cache_counts()  # count from before the first compile
    if config["on_chip"]:
        accelerators.require_tpu()
    device = accelerators.device_report()
    ready_s = time.time() - t_start
    # a rehearsal's arithmetic runs on the first entry; it prints no metric
    peaks = (harness.peaks_for(device["kind"]) if config["on_chip"]
             else next(iter(harness.PEAKS.values())))
    if config["on_chip"] and device["count"] != chips:
        raise harness.BenchError(f"the worker sees {device['count']} devices, "
                                 f"the cell asks for {chips}")
    cfg = program.transformer_config(conf["program"])
    spec = MeshSpec(**conf["mesh"])
    mesh = spec.build(jax.devices()[:spec.size()])
    axes = transformer.logical_axes(cfg)
    opt = optax.adamw(traffic["lr"])

    def loss_fn(p, tokens):
        return transformer.loss_fn(p, tokens, cfg)

    step, _, batch_sharding = make_train_step(loss_fn, axes, mesh, opt)
    params = init_sharded(lambda key: transformer.init(key, cfg), axes, mesh,
                          program.seed_key(config["seed"]))
    opt_state = init_opt_state(opt, params)
    batch, seq = traffic["batch"], traffic["seq"]
    batches = iter(train.get_dataset_shard("train").iter_batches(batch_size=batch))

    def next_batch():
        return jax.device_put(np.asarray(next(batches)["tokens"], np.int32),
                              batch_sharding)

    first = next_batch()
    compiled = step.lower(params, opt_state, first).compile()
    ma = compiled.memory_analysis()
    step_hbm = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    hlo = compiled.as_text()
    # warm-up: the step, the transfer, the fetch; then the size of a block
    params, opt_state, loss = compiled(params, opt_state, first)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(traffic["warmup_steps"]):
        params, opt_state, loss = compiled(params, opt_state, next_batch())
    float(loss)
    step_est = (time.perf_counter() - t0) / traffic["warmup_steps"]
    per_block = max(1, round(traffic["block_seconds"] / step_est))
    compiles_before = accelerators.compile_cache_counts()["requests"]
    setup_s = time.time() - t_start

    blocks, losses, wait_s = [], [], 0.0

    def run_block() -> float:
        nonlocal params, opt_state, wait_s
        b0 = time.perf_counter()
        for _ in range(per_block):
            t = time.perf_counter()
            tokens = next_batch()
            wait_s += time.perf_counter() - t
            with jax.profiler.TraceAnnotation("chipbench:train_step"):
                params, opt_state, loss = compiled(params, opt_state, tokens)
        jax.block_until_ready(loss)
        took = time.perf_counter() - b0
        with jax.profiler.TraceAnnotation("chipbench:report"):
            losses.append(float(loss))
            train.report({"step": len(losses) * per_block, "loss": losses[-1]})
        return took

    w0 = time.perf_counter()
    while time.perf_counter() - w0 < config["seconds"]:
        blocks.append(run_block())
    window_s = time.perf_counter() - w0
    window_wait_s = wait_s
    # the traced blocks come after the window: the host-clock readings above
    # are taken with the profiler off, the device's from a trace of their own
    trace_dir, trace_span = os.path.join(out_dir, "trace"), None
    if config["trace"]:
        jax.profiler.start_trace(trace_dir)
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < traffic["trace_seconds"]:
            run_block()
        trace_span = time.perf_counter() - t0
        jax.profiler.stop_trace()
    compiles_in_window = accelerators.compile_cache_counts()["requests"] - compiles_before
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())

    steps = len(blocks) * per_block
    block_med = statistics.median(blocks)
    tokens_per_block = per_block * batch * seq
    finite = all(x == x and abs(x) != float("inf") for x in losses)
    facts = {
        "device": {**device, "memory_peak_bytes": peak},
        "ready_s": ready_s, "setup_s": setup_s, "window_s": window_s,
        "steps": steps, "failed_steps": 0 if finite else steps,
        "steps_per_block": per_block, "block_s": blocks,
        "block_median_s": block_med,
        "train_tok_s_per_chip": steps * batch * seq / window_s / chips,
        "block_median_tok_s_per_chip": tokens_per_block / block_med / chips,
        "input_wait_s": window_wait_s, "losses": losses,
        "step_hbm_bytes": step_hbm, "tpu_custom_call": "tpu_custom_call" in hlo,
        "compiles_in_window": compiles_in_window,
        "compile_cache": accelerators.compile_cache_counts(),
        "flops_per_token": flops.train_flops_per_token(conf["sizes"], seq),
        "peak_flops_per_s": peaks["bf16_flops_per_s"],
        "peak_hbm_bytes_per_s": peaks["hbm_bytes_per_s"],
        "batch": batch, "seq": seq, "chips": chips, "sizes": conf["sizes"],
    }
    del opt_state
    from chipbench import check

    facts["check"] = check.train_check(
        conf, cfg, params, mesh, loss_fn, batch_sharding, config["seed"])
    facts["correct"] = bool(finite and compiles_in_window == 0
                            and facts["check"]["ok"])
    if config["trace"]:
        from chipbench import trace_reduce

        summary = trace_reduce.reduce_dir(
            trace_dir, trace_reduce.kernel_ops_from_hlo(hlo),
            keep_rows=os.path.join(out_dir, "trace_rows.json"))
        shutil.rmtree(trace_dir, ignore_errors=True)  # tens of MB a run
        facts["trace"] = summary
        facts["trace_span_s"] = trace_span
        if summary:
            facts["device"].update(busy_s=summary["busy_s"],
                                   window_s=summary["window_s"])
            facts["breakdown"] = summary["breakdown"]
    with open(os.path.join(out_dir, "blocks.json"), "w") as f:
        json.dump({k: facts[k] for k in (
            "steps_per_block", "block_s", "block_median_s", "window_s",
            "train_tok_s_per_chip", "block_median_tok_s_per_chip",
            "input_wait_s", "losses", "compiles_in_window", "check")}, f)
    train.report({"facts": facts})
