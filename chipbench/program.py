"""What the benchmark takes from the program: its model families by name,
its parameter initialiser, and — for serving — an `LLMConfig` whose weights
come from `--seed` in one jitted call on the device."""

from __future__ import annotations

import dataclasses
import os
import threading
import time

from ray_tpu.llm import LLMConfig


def transformer_config(program: dict):
    """`program` group of a configuration file -> the program's
    TransformerConfig: {"family", "model_id", "model_kwargs"}; a value under
    a key ending in `dtype` names a jax.numpy dtype, a `moe` group becomes
    the program's MoEConfig."""
    from ray_tpu import models

    return getattr(models, program["family"] + "_config")(
        program["model_id"], **model_kwargs(program))


def model_kwargs(program: dict) -> dict:
    import jax.numpy as jnp

    from ray_tpu.models.transformer import MoEConfig

    kw = dict(program.get("model_kwargs", {}))
    for k, v in kw.items():
        if k.endswith("dtype"):
            kw[k] = getattr(jnp, v)
    if isinstance(kw.get("moe"), dict):
        kw["moe"] = MoEConfig(**kw["moe"])
    return kw


def seed_key(seed: int):
    """A PRNG key from a `--seed` of any size (the driver's pass 2**31)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def init_params(cfg, seed: int, shardings=None):
    """The program's own initialiser as ONE program on the device, in the
    dtype the configuration serves or trains in."""
    import jax

    from ray_tpu.models import transformer

    return jax.jit(lambda key: transformer.init(key, cfg),
                   out_shardings=shardings)(seed_key(seed))


def _trace_on_request(ctl_dir: str) -> None:
    """Runs in the process that holds the chip: trace while `<ctl>/start`
    exists and `<ctl>/stop` does not. Only the chip's holder can trace it,
    and the replica is a process of its own. The Python tracer is off: with
    it on the host's time a step read 2.4-2.7 times higher and `stop_trace`
    took minutes on a loaded replica (PERF.md section 6, PRs 24 and 41); the
    host tracer stays on, so the engine's `TraceAnnotation`s are in the trace."""
    import jax

    start, stop = os.path.join(ctl_dir, "start"), os.path.join(ctl_dir, "stop")
    while not os.path.exists(start):
        if os.path.exists(stop):
            return
        time.sleep(0.02)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(os.path.join(ctl_dir, "trace"), profiler_options=options)
    with open(os.path.join(ctl_dir, "started"), "w") as f:
        f.write(repr(time.time()))
    while not os.path.exists(stop):
        time.sleep(0.02)
    jax.profiler.stop_trace()
    with open(os.path.join(ctl_dir, "done.tmp"), "w") as f:
        f.write(repr(time.time()))
    os.replace(os.path.join(ctl_dir, "done.tmp"), os.path.join(ctl_dir, "done"))


@dataclasses.dataclass
class SeededLLMConfig(LLMConfig):
    """The program's LLMConfig with weights from `seed` in one jitted call on
    the device, and the trace hook started when `trace_ctl` names a directory.

    `LLMConfig.build_model` has no seed (PRNGKey(0), leaf by leaf, eagerly)
    and this PR may not give it one, so NO cell runs the program's weight
    initialiser: `setup_s` and `ready_s.serve` of the serve cells time this
    one and would not move if the program's were repaired or broken
    (PERF.md section 2 says so where it defines `setup_s`)."""

    seed: int = 0
    trace_ctl: str | None = None

    def build_model(self):
        from ray_tpu import models

        cfg = getattr(models, self.model_family + "_config")(
            self.model_loading_config.model_id, **self.model_kwargs)
        params = init_params(cfg, self.seed)
        if self.trace_ctl:
            threading.Thread(target=_trace_on_request, args=(self.trace_ctl,),
                             daemon=True, name="chipbench-trace").start()
        return cfg, params
