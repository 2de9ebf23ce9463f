"""Data→train pipeline benchmark: BASELINE config 3 (image pipeline
feeding HBM prefetch).

(reference gate: release/release_tests.yaml:1670-1721 — the
multimodal/image-pipeline release tests assert the data plane keeps the
accelerator fed; their acceptance metric is throughput with the GPU not
starving. Here: image files → decode → augment (remote workers, CPU) →
streaming_split → driver-side train step on the chip with a device-put
prefetch window; we record images/s end-to-end and the DEVICE-WAIT
FRACTION — the share of wall time the train loop blocks on the data plane
instead of stepping. Bar: device_wait_frac < 0.10.)

Measures in this process on the TPU or raises (no CPU stand-in). Writes
DATA_BENCH.json at the repo root.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)  # run as a script: benchmarks/ is sys.path[0]

from ray_tpu._private import accelerators  # noqa: E402


def _make_corpus(d: str, n: int, size: int) -> list[str]:
    """Synthesize a JPEG shard corpus (decode cost is the point)."""
    import numpy as np
    from PIL import Image

    os.makedirs(d, exist_ok=True)
    rng = np.random.default_rng(0)
    paths = []
    for i in range(n):
        p = os.path.join(d, f"img{i:05d}.jpg")
        if not os.path.exists(p):
            arr = rng.integers(0, 256, (size, size, 3), dtype=np.uint8)
            Image.fromarray(arr).save(p, quality=85)
        paths.append(p)
    return paths


def _measure() -> dict:
    import numpy as np

    os.environ.setdefault("RAY_TPU_WARM_POOL_SIZE", "2")
    import jax
    import jax.numpy as jnp
    import optax

    import ray_tpu
    import ray_tpu.data as rdata
    from ray_tpu.models import vit

    # ViT-L/16: the step must be heavy enough that ONE host core's
    # JPEG decode (~200 img/s) can keep the chip fed — the release
    # gate's criterion is overlap, and a too-small model on a 1-core
    # host measures the host, not the pipeline
    img, batch, n_imgs, epochs = 224, 32, 512, 3
    cfg = vit.vit_config("l16", image_size=img, num_classes=1000,
                         dtype=jnp.bfloat16)

    corpus = _make_corpus(f"/tmp/ray_tpu_imgbench_{img}", n_imgs, 256)
    # worker processes must NOT touch the chip: this process owns it, the
    # decode/augment tasks are host-side (the Node spawner pins
    # JAX_PLATFORMS=cpu on workers it bound no chip to)
    ray_tpu.init(num_cpus=4, num_workers=3, max_workers=4)

    def augment(b):
        imgs = b["image"].astype(np.float32) / 255.0
        # random crop to the train size + horizontal flip: the classic
        # input-pipeline cost the release gate exercises
        rng = np.random.default_rng(int(b["image"].sum()) & 0xFFFF)
        h = rng.integers(0, imgs.shape[1] - img + 1)
        w = rng.integers(0, imgs.shape[2] - img + 1)
        imgs = imgs[:, h:h + img, w:w + img, :]
        if rng.random() < 0.5:
            imgs = imgs[:, :, ::-1, :]
        labels = rng.integers(0, cfg.num_classes, imgs.shape[0])
        return {"image": np.ascontiguousarray(imgs), "label": labels}

    params = vit.init(jax.random.PRNGKey(0), cfg)
    n_params = sum(math.prod(p.shape) for p in jax.tree.leaves(params))
    opt = optax.adamw(1e-4)
    opt_state = opt.init(params)

    def loss_fn(p, images, labels):
        logits = vit.forward(p, images, cfg)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.take_along_axis(lp, labels[:, None], axis=1).mean()

    @jax.jit
    def step(p, s, images, labels):
        loss, grads = jax.value_and_grad(loss_fn)(p, images, labels)
        upd, s = opt.update(grads, s, p)
        return optax.apply_updates(p, upd), s, loss

    def batches():
        """One epoch: read → augment on remote workers → streaming_split →
        device-put prefetch window of 2 (iter_jax_batches semantics,
        driver-side so the split iterator composes)."""
        import collections

        ds = rdata.read_images(corpus).map_batches(augment, batch_size=batch)
        it = ds.streaming_split(1)[0]
        pending: collections.deque = collections.deque()
        for b in it.iter_batches(batch_size=batch):
            if len(b["label"]) < batch:
                continue  # drop ragged tail: jit shapes stay static
            fut = jax.device_put({"image": b["image"],
                                  "label": b["label"].astype(np.int32)})
            pending.append(fut)
            while len(pending) >= 2:
                yield pending.popleft()
        while pending:
            yield pending.popleft()

    # warmup epoch fragment: compile + warm the worker pool
    warm = next(iter(batches()))
    params, opt_state, l0 = step(params, opt_state, warm["image"], warm["label"])
    jax.block_until_ready(l0)

    images_seen = 0
    wait_s = 0.0
    step_s = 0.0
    t_run0 = time.perf_counter()
    loss = None
    for _ in range(epochs):
        gen = batches()
        while True:
            t0 = time.perf_counter()
            try:
                b = next(gen)
            except StopIteration:
                break
            t1 = time.perf_counter()
            params, opt_state, loss = step(params, opt_state,
                                           b["image"], b["label"])
            jax.block_until_ready(loss)
            t2 = time.perf_counter()
            wait_s += t1 - t0
            step_s += t2 - t1
            images_seen += batch
    total = time.perf_counter() - t_run0
    ray_tpu.shutdown()
    return {
        "device": accelerators.device_report(),
        "images_per_sec": round(images_seen / total, 1),
        "device_wait_frac": round(wait_s / total, 4),
        "step_frac": round(step_s / total, 4),
        "images_seen": images_seen,
        "epochs": epochs,
        "batch": batch,
        "image_size": img,
        "model_params": n_params,
        "final_loss": float(loss) if loss is not None else None,
        "device_wait_ok": bool(wait_s / total < 0.10),
    }


def main():
    accelerators.export_compile_cache_env()  # before jax is imported
    accelerators.require_tpu()
    out = {"ts": time.strftime("%Y-%m-%d %H:%M"), **_measure()}
    # merge discipline: DATA_BENCH.json is shared with data_bench.py's
    # `fault_tolerance` A/B section — a rerun here must not clobber it
    from ray_tpu.scripts._artifacts import merge_artifact

    merge_artifact("DATA_BENCH.json", "results", out)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
