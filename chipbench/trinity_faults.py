"""Faults for a configuration of the `trinity` family (gated attention with q/k
norms, window layers under rope and full layers without positions, sandwich
norms, a scaled embedding, expert layers that hold a SHARE of the experts), by
hand on the chip:

    python3 chipbench/trinity_faults.py --config <name> [--faults a,b,...]
        --seeds <first> <count> [--tie-seconds <s>] [--out <file>]

`check.serve_check` (its `FAULTS` are the benchmark's and name trees that every
configuration has) with a deliberately wrong PROGRAM side against the same
reference, from the same weights. For each seed the sound program's greedy
tokens are taken once; then every fault's logits at those tokens.

- `gate_left_out`: the heads' output goes to W_o without sigmoid(x W_g);
- `qk_norms_left_out`: q and k are rotated and multiplied as projected;
- `rope_on_full_layer`: the full layer takes the window layers' rope;
- `window_ignored`: the window layers see every earlier key;
- `post_norms_left_out`: a sublayer's output joins the residual without its norm;
- `embedding_unscaled`: the embedding is not multiplied by sqrt(d);
- `weights_over_held`: a held slot's weight is normalised over the slots this
  chip holds, not over all the token's chosen experts;
- `absent_expert_wrapped`: an absent expert's slot is computed by the held
  expert `e mod held` instead of being left out;
- `window_page_zeroed`: `window_faults.zero_window_page` after the insert.

Every seed has to read `ok: false`; the readings are recorded in the
configuration file's `check.faults`. One process that holds the chip; not part
of a run.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


CONFIG_FAULTS = {
    "rope_on_full_layer": lambda cfg: dict(full_layer_rope=True),
    "window_ignored": lambda cfg: dict(window=cfg.max_seq_len),
    "embedding_unscaled": lambda cfg: dict(embedding_multiplier=1.0),
}
CODE_FAULTS = ("gate_left_out", "qk_norms_left_out", "post_norms_left_out",
               "weights_over_held", "absent_expert_wrapped", "window_page_zeroed")
FAULTS = CODE_FAULTS + tuple(CONFIG_FAULTS)


def broken_config(cfg, fault: str):
    """The program's configuration with `fault` in it."""
    return dataclasses.replace(cfg, **CONFIG_FAULTS[fault](cfg))


def _patches(fault: str, conf: dict) -> list:
    """(module, attribute, the wrong function) of a planted fault."""
    import jax.numpy as jnp

    from chipbench import window_faults
    from ray_tpu import ops
    from ray_tpu.models import decoding, decoding_paged as dp, transformer
    from ray_tpu.ops import moe

    def everywhere(name, wrong):
        return [(m, name, wrong) for m in (transformer, decoding, dp) if hasattr(m, name)]

    if fault == "gate_left_out":
        return everywhere("attn_gated", lambda out, x, p, cfg: out)
    if fault == "qk_norms_left_out":
        return everywhere("qk_normed", lambda q, k, p, cfg: (q, k))
    if fault == "post_norms_left_out":
        return everywhere("_residual", lambda h, delta, layer_p, post, cfg: h + delta)
    if fault == "weights_over_held":
        sound, share = ops.sigmoid_topk, conf["sizes"]["experts_held"]

        def wrong(router_logits, select_bias, *, k, scale=1.0):
            idx, w, aux = sound(router_logits, select_bias, k=k, scale=scale)
            mine = (idx >= share[0]) & (idx <= share[-1])
            here = jnp.sum(jnp.where(mine, w, 0.0), axis=-1, keepdims=True)
            return idx, w / (here + 1e-20) * scale, aux
        return [(ops, "sigmoid_topk", wrong)]
    if fault == "absent_expert_wrapped":
        def wrong(expert_idx, first, held):
            return (expert_idx - first) % held, jnp.ones(expert_idx.shape, bool)
        return [(moe, "held_slots", wrong), (ops, "held_slots", wrong)]
    if fault == "window_page_zeroed":
        sound = dp.insert_sequence_paged

        def wrong(state, slot, kv, length, *rest, **kw):
            return window_faults.zero_window_page(
                sound(state, slot, kv, length, *rest, **kw), slot, length)
        return [(dp, "insert_sequence_paged", wrong)]
    raise ValueError(f"no planted fault {fault!r}")


@contextlib.contextmanager
def planted(fault: str, conf: dict):
    """`fault` in the program that `check.serve_check` builds and drives. The
    jitted steps are traced anew inside and after: a trace of the sound code
    would be found again by its arguments."""
    from chipbench import program
    from ray_tpu.models import decoding, decoding_paged as dp

    if fault in CONFIG_FAULTS:
        sound = program.transformer_config
        patches = [(program, "transformer_config",
                    lambda prog: broken_config(sound(prog), fault))]
    else:
        patches = _patches(fault, conf)
    steps = (decoding.prefill, dp.decode_step_paged_ragged, dp.prefill_with_prefix)
    kept = [(holder, name, getattr(holder, name)) for holder, name, _ in patches]
    for holder, name, wrong in patches:
        setattr(holder, name, wrong)
    for step in steps:
        step.clear_cache()
    try:
        yield
    finally:
        for holder, name, sound in kept:
            setattr(holder, name, sound)
        for step in steps:
            step.clear_cache()


def sweep(conf: dict, seeds: list, faults: list, on_chip: bool = True,
          tie_seconds: float | None = None):
    """One row a seed and fault: the greedy tokens of the sound program's own
    steps (once a seed), then the wrong program's logits at those tokens
    against the reference. `tie_seconds`: the longest a FAULT's tie search
    may take (a wrong program keeps the search going until its time is up;
    None: the file's `max_tie_seconds`, which the sound run always has)."""
    from chipbench import check, check_sweep

    wrong_conf = conf if tie_seconds is None else {
        **conf, "check": {**conf["check"], "max_tie_seconds": tie_seconds}}

    for fault in faults:
        if fault not in FAULTS:
            raise ValueError(f"no fault {fault!r} (has: {FAULTS})")
    for seed in seeds:
        prompt = check_sweep.sample_prompt(conf, seed)
        sound = check.serve_check(conf, seed, prompt, None, on_chip)
        for fault in faults:
            with planted(fault, conf):
                v = check.serve_check(wrong_conf, seed, prompt, sound["served_ids"], on_chip)
            yield {"seed": seed, "fault": fault, "ok": v["ok"],
                   "sound_ok": sound["ok"], "sound_median": sound["logits_rel_err_median"],
                   "logits_rel_err": v["logits_rel_err"],
                   "logits_rel_err_median": v["logits_rel_err_median"],
                   "served_gap_max": max(v["served_gap_in_logit_std"]),
                   "reference_s": v["reference_s"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--seeds", required=True, nargs=2, type=int, metavar=("FIRST", "COUNT"))
    ap.add_argument("--tie-seconds", type=float, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from chipbench import harness
    from ray_tpu._private import accelerators

    if accelerators.detect_num_tpu_chips() < 1:
        print("chipbench.trinity_faults: no TPU chip here", file=sys.stderr)
        return 2
    harness.prepare_env()
    conf = harness.load_json(harness.BENCH_DIR, "configs", args.config + ".json")
    rows = []
    for row in sweep(conf, range(args.seeds[0], args.seeds[0] + args.seeds[1]),
                     args.faults.split(","), tie_seconds=args.tie_seconds):
        rows.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0 if not any(r["ok"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
