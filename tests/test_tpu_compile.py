"""The Pallas kernels of the main path, compiled for a TPU that is described
and not attached (on-chip-measurement guide, section 2.3): what Mosaic or the
TPU compiler refuses at the real widths fails here, at no chip time.

Nothing runs, so this says nothing about results — `chip_smoke.py` compares
the kernels with their references on the chip. Shapes are the ones it uses:
GPT-2 774M attention at s1024, Llama-1B decode (page 64).
"""

from __future__ import annotations

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops.ragged_paged_attention import ragged_decode_attention


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compilation cache off: an
    entry compiled for an absent chip is written but cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """One chip of it."""
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def flash_as_on_chip(monkeypatch):
    """`ops.attention._flash_ok` asks jax.default_backend(), the CPU here:
    steered to what it answers on the chip."""
    import sys

    import ray_tpu.ops.attention  # noqa: F401
    monkeypatch.setattr(sys.modules["ray_tpu.ops.attention"], "_flash_ok",
                        lambda q: q.shape[1] % 256 == 0 and q.shape[1] >= 1024)


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


GPT2_774M_TRAIN = (8, 20, 1024, 64)    # [B, H, T, D]: 20 heads x 64 at s1024
LLAMA_1B_PREFILL = (1, 32, 2048, 64)   # one prompt in the 2048 bucket


def _flash_qkv(chip, shape):
    return [jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)] * 3


@pytest.mark.parametrize("shape", [GPT2_774M_TRAIN, LLAMA_1B_PREFILL])
def test_flash_forward_compiles(chip, shape):
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, True, None),
        *_flash_qkv(chip, shape))
    assert "tpu_custom_call" in text


def test_flash_forward_and_backward_compile(chip):
    def loss(q, k, v):
        return flash_attention(q, k, v, True, None).astype(jnp.float32).sum()

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)),
                          *_flash_qkv(chip, GPT2_774M_TRAIN))
    assert text.count("tpu_custom_call") >= 3  # fwd, dkv, dq


def _ragged_launch(chip, shape, window=None):
    """The per-head launch lowered at (batch, kv heads, query heads per kv
    head, head dim, page, table columns)."""
    B, Hkv, G, Dh, P, nb = shape

    def sds(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=chip)

    return jax.jit(
        lambda *a: ragged_decode_attention(*a, impl="kernel", window=window)).lower(
        sds((B, Hkv, G, Dh), jnp.bfloat16),
        sds((B * nb + 1, P, Hkv, Dh), jnp.bfloat16),
        sds((B * nb + 1, P, Hkv, Dh), jnp.bfloat16),
        sds((B, nb), jnp.int32), sds((B,), jnp.int32))


@pytest.mark.parametrize("shape,window", [
    ((16, 8, 4, 64, 64, 32), None),      # Llama-1B decode, 16 slots x 2048: heads of 64
    ((16, 8, 4, 64, 64, 17), 1024),      # ... and under a window
    ((8, 12, 1, 64, 64, 16), None),      # GPT-2 124M: 12 heads of 64, one query head each
    ((8, 2, 8, 128, 16, 8), None),       # GQA with fewer than 8 kv heads
    ((16, 16, 1, 128, 64, 16), None),    # ouro-2.6b: one query head a KV head
    ((104, 4, 8, 128, 64, 32), None),    # granite-4.0-h-micro, KV heads packed two a row
    ((32, 8, 4, 128, 64, 128), None),    # mixtral-8x7b
    ((48, 4, 8, 128, 64, 256), None),    # mellum2-12b-a2.5b's full layers
    ((48, 4, 8, 128, 64, 17), 1024),     # ... and its window layers
    ((24, 8, 6, 128, 64, 520), None),    # trinity-large-preview's full layer: a group of 6
    ((24, 8, 6, 128, 64, 65), 4096),     # ... and its window layers, a sweep of 65 pages
])
def test_ragged_decode_kernel_compiles(chip, shape, window):
    assert "tpu_custom_call" in _ragged_launch(chip, shape, window).compile().as_text()


def _abstract_step_inputs(chip, cfg, slots, max_len, num_pages, page):
    """(weights, paged decode state) of `cfg` as shapes on the described chip."""
    from ray_tpu.models import decoding_paged, transformer

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)

    return (on_chip(jax.eval_shape(lambda: transformer.init(jax.random.PRNGKey(0), cfg))),
            on_chip(jax.eval_shape(lambda: decoding_paged.init_paged_state(
                cfg, slots, max_len, num_pages, page))))


def _kernel_calls(text: str) -> list[str]:
    """The Pallas kernels of a compiled program's text, by `pallas_call` name."""
    import re

    return [c.split(".")[0] for c in re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)]


def _prefill_1024(chip, params, cfg):
    from ray_tpu.models import decoding

    tokens = jax.ShapeDtypeStruct((1, 1024), jnp.int32, sharding=chip)
    n = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    return decoding.prefill.lower(params, tokens, n, cfg).compile()


def test_paged_decode_step_with_kernel_compiles(chip):
    """The engine's decode program at Llama-1B widths, depth cut to two
    layers (the layer scan compiles one body whatever the depth)."""
    from ray_tpu.models import decoding_paged, llama_config

    cfg = llama_config("1b", max_seq_len=2048, n_layers=2)
    slots, max_len, page = 16, 2048, 64
    params, state = _abstract_step_inputs(
        chip, cfg, slots, max_len, slots * (max_len // page) + 1, page)
    text = decoding_paged.decode_step_paged_ragged.lower(
        params, state, cfg, 8, True).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("step,max_len", [("ragged", 8320)])
def test_paged_decode_step_holds_the_pool_once(chip, step, max_len):
    """The decode program at Mixtral widths as `mixtral-8x7b.chat-steady`
    runs it (4 layers, 1,024 pages of 64, 32 slots): the page pools are
    updated in place. With the pools among the layer scan's inputs and
    outputs the compiler held a second pool and half a pool of slices as
    temporaries (1,627,396,096 bytes; as the scan's carry: 9,415,168)."""
    from ray_tpu.models import decoding_paged, mixtral_config
    from ray_tpu.models.transformer import MoEConfig

    cfg = mixtral_config("8x7b", n_layers=4, param_dtype=jnp.bfloat16, max_seq_len=32768,
                         moe=MoEConfig(num_experts=8, top_k=2, capacity_factor=4.0))
    params, state = _abstract_step_inputs(chip, cfg, 32, max_len, 1024, 64)
    lowered = decoding_paged.decode_step_paged_ragged.lower(params, state, cfg, 32, True)
    m = lowered.compile().memory_analysis()
    pools = 2 * 4 * 1024 * 64 * cfg.kv_heads * cfg.head_dim * 2
    assert m.temp_size_in_bytes < 64 * 2**20
    assert m.alias_size_in_bytes >= pools


def test_latent_decode_step_holds_the_pool_once(chip):
    """The decode program of `kimi-vl-a3b.longdoc-saturated` (the leading
    dense layer and two of its expert layers: each layer scan compiles one
    body whatever the depth): ONE pool of latent rows, [L, pages, 64, 640],
    carried through both layer scans and scattered in place; the absorbed
    attention is the `ragged_latent_attention` kernel, once a scan."""
    from ray_tpu.models import decoding_paged, kimi_vl_config

    cfg = kimi_vl_config("a3b", n_layers=3, param_dtype=jnp.bfloat16, max_seq_len=16640)
    params, state = _abstract_step_inputs(chip, cfg, 32, 16640, 3328, 64)
    assert set(state) == {"kp", "block", "length", "last_token", "active"}
    assert state["kp"].shape == (3, 3328, 64, 640)
    compiled = decoding_paged.decode_step_paged_ragged.lower(
        params, state, cfg, 256, True).compile()
    m = compiled.memory_analysis()
    pool = 3 * 3328 * 64 * 640 * 2
    assert m.alias_size_in_bytes >= pool
    # nothing pool-sized, and no layer's experts copied out of the stack for
    # the grouped products (0.37 GB when the scan sliced them; 2.3 MB now)
    assert m.temp_size_in_bytes < 64 * 2**20
    # one a layer scan; 32 rows take the one-hot dispatch: no grouped product
    assert _kernel_calls(compiled.as_text()) == ["ragged_latent_attention"] * 2
    # a 1024-token prefill sorts its slots: the `grouped_matmul` kernel (gate,
    # up, down: three a layer scan) multiplies the stack's experts where they
    # lie (sliced by the scan, one layer's [64, 2048, 1408] was copied out for
    # each: 369,098,752 bytes), with no padded copy of the sorted rows
    prefill = _prefill_1024(chip, params, cfg)
    assert _kernel_calls(prefill.as_text()).count("grouped_matmul") == 3
    assert "ragged-dot" not in prefill.as_text()
    assert prefill.memory_analysis().temp_size_in_bytes < 64 * 2048 * 1408 * 2


def test_window_and_full_decode_step_holds_both_kinds_of_pool_once(chip):
    """The decode program of `mellum2-12b-a2.5b.mixed-saturated` at two of
    its periods (the scan compiles one period whatever the depth): four pools,
    the full layers' and the window layers', carried through the scan of
    periods (and the scan of a period's window layers inside it) and
    scattered in place — each kind of layer is traced once and takes the
    pools of its kind, no branch between carried pools. One window launch
    and one full launch, each under its own name. The temporaries are the Q,
    K and V projections of the whole stack re-laid once a call (150,994,944 +
    2 x 18,874,368 bytes here; a plain layer scan re-lays the same bytes a
    layer at a time), far under a pool."""
    from ray_tpu.models import decoding_paged, mellum_config

    cfg = mellum_config("12b-a2.5b", n_layers=8, param_dtype=jnp.bfloat16, max_seq_len=16640)
    slots, page, ring = 48, 64, decoding_paged.window_ring(cfg, 64, 1024)
    assert ring == 33
    params, _ = _abstract_step_inputs(chip, cfg, slots, 16640, 8, page)
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        jax.eval_shape(lambda: decoding_paged.init_paged_state(
            cfg, slots, 16640, 2688, page, slots * ring + 1, ring)))
    assert state["kp"].shape == (2, 2688, 64, 4, 128)
    assert state["wkp"].shape == (6, 1585, 64, 4, 128) and state["wblock"].shape == (48, 33)
    compiled = decoding_paged.decode_step_paged_ragged.lower(
        params, state, cfg, 256, True).compile()
    m = compiled.memory_analysis()
    pools = 2 * (2 * 2688 + 6 * 1585) * 64 * 4 * 128 * 2
    assert m.alias_size_in_bytes >= pools
    window_pool = 6 * 1585 * 64 * 4 * 128 * 2              # K, or V, of the window layers
    assert m.temp_size_in_bytes < window_pool // 3          # 154,710,528: no pool's copy
    assert _kernel_calls(compiled.as_text()) == [
        "ragged_window_attention", "ragged_paged_attention"]
    # a 1024-token prefill: flash on every layer (the window cuts nothing off
    # a sequence of its own length), the grouped products where the experts lie
    prefill = _prefill_1024(chip, params, cfg)
    calls = _kernel_calls(prefill.as_text())
    assert calls.count("grouped_matmul") == 3 * 2 and "ragged-dot" not in prefill.as_text()


# cell -> (table columns the step sweeps, its kernels in program order, the
# temporaries it may hold where the configuration's file records none)
DECODE_STEPS = {
    "ouro-2.6b.reason-saturated": (16, ["ragged_paged_attention"], None),
    "mellum2-12b-a2.5b.mixed-saturated": (
        256, ["ragged_window_attention", "ragged_paged_attention"], None),
    "granite-4.0-h-micro.chat-saturated": (
        32, ["ssm_state_update", "ssm_state_update", "ragged_paged_attention"], None),
    # the step of PR 42's tree at these shapes: 9,511,936 bytes
    "mixtral-8x7b.doc-saturated": (128, ["ragged_paged_attention"], 9_511_936),
    # (in the text's order) the period's three window layers with their held
    # experts' three grouped products, the leading dense layer, the period's
    # full layer with its own three
    "trinity-large-preview.agent-saturated": (
        512, ["ragged_window_attention", "grouped_matmul", "grouped_matmul", "grouped_matmul",
              "ragged_window_attention", "ragged_paged_attention", "grouped_matmul",
              "grouped_matmul", "grouped_matmul"], None),
}


@pytest.mark.parametrize("cell", DECODE_STEPS)
def test_decode_step_reads_the_pools_where_they_lie(chip, cell):
    """The decode program of every per-head configuration as its cell runs it
    (the file's model, slots and pool), with the kernel that walks a row's own
    pages: every pool aliased input to output, no more temporaries than the
    file's `aot.decode_step_temp_bytes` (the launch leaves the pools in HBM
    and copies blocks of pages into VMEM: nothing pool-sized is re-laid around
    it), and its launches under the names the trace readers match, one op a
    layer scan."""
    import math

    from chipbench import harness, program
    from ray_tpu.models import decoding_paged

    bound, kernels, temp = DECODE_STEPS[cell]
    conf = harness.resolve_cell(cell)["config_file"]
    cfg, eng = program.transformer_config(conf["program"]), conf["engine"]
    params, state = _abstract_step_inputs(
        chip, cfg, eng["max_slots"], eng["max_len"], eng["num_pages"], eng["page_size"])
    compiled = decoding_paged.decode_step_paged_ragged.lower(
        params, state, cfg, bound, True).compile()
    m = compiled.memory_analysis()
    pools = sum(math.prod(state[k].shape) * state[k].dtype.itemsize
                for k in ("kp", "vp", "wkp", "wvp") if k in state)
    assert m.alias_size_in_bytes >= pools
    assert m.temp_size_in_bytes <= (temp or conf["aot"]["decode_step_temp_bytes"])
    assert _kernel_calls(compiled.as_text()) == kernels


SERVE_CACHES = ["mellum2-12b-a2.5b", "mixtral-8x7b", "kimi-vl-a3b", "ouro-2.6b",
                "trinity-large-preview"]


def _serve_cache(name):
    """(cfg, the arguments of `init_paged_state` after it, the longest prompt
    bucket a writer sees, the pools' shapes) of a serve configuration as its
    cell runs it: only the cache's shapes matter here, so the depth is the
    file's and nothing else of the model is built."""
    from ray_tpu import models

    bf = jnp.bfloat16
    if name == "mellum2-12b-a2.5b":    # both kinds of pool, a ring of 33 for each of 48 slots
        cfg = models.mellum_config("12b-a2.5b", n_layers=12, param_dtype=bf, max_seq_len=16640)
        full, ring = (3, 2688, 64, 4, 128), (9, 1585, 64, 4, 128)
        return cfg, (48, 16640, 2688, 64, 48 * 33 + 1, 33), 1024, {
            "kp": full, "vp": full, "wkp": ring, "wvp": ring}
    if name == "trinity-large-preview":   # a dense layer among the 4 window layers, a ring of 97
        cfg = models.trinity_config("large-preview", n_layers=5, n_dense_layers=1,
                                    vocab_size=25024, param_dtype=bf, max_seq_len=33280,
                                    experts_held=32)
        full, ring = (1, 9216, 64, 8, 128), (4, 2329, 64, 8, 128)
        return cfg, (24, 33280, 9216, 64, 24 * 97 + 1, 97), 2048, {
            "kp": full, "vp": full, "wkp": ring, "wvp": ring}
    if name == "mixtral-8x7b":
        cfg = models.mixtral_config("8x7b", n_layers=4, param_dtype=bf, max_seq_len=8320)
        return cfg, (32, 8320, 1024, 64), 1024, dict.fromkeys(("kp", "vp"), (4, 1024, 64, 8, 128))
    if name == "kimi-vl-a3b":          # one pool of latent rows
        cfg = models.kimi_vl_config("a3b", n_layers=9, param_dtype=bf, max_seq_len=16640)
        return cfg, (32, 16640, 3328, 64), 1024, {"kp": (9, 3328, 64, 640)}
    cfg = models.ouro_config("2.6b", param_dtype=bf, max_seq_len=1088)   # 192 planes
    return cfg, (16, 1088, 88, 64), 512, dict.fromkeys(("kp", "vp"), (192, 88, 64, 16, 128))


@pytest.mark.parametrize("writer", ["write_kv_pages", "insert_sequence_paged",
                                    "insert_sequence_paged_prefix"])
@pytest.mark.parametrize("name", SERVE_CACHES)
def test_page_writers_update_the_pools_where_they_lie(chip, name, writer):
    """The three programs that put whole pages into the pools, at every serve
    configuration's shapes (`mellum2`: both kinds of pool and a ring of 33; a
    latent pool; a looped stack's 192 planes): every pool aliased input to
    output, temporaries under 1 % of the smallest pool, no `copy` of a pool's
    shape in the compiled text. As one scatter along the page axis
    (`pool.at[:, ids].set`) the `mellum2` programs held 954,035,712 /
    954,422,784 / 954,422,784 bytes of temporaries and eight pool-sized
    copies: a pool whose (Hkv, Dh) tile is (4, 128) was re-laid whole, in
    and out, around the scatter (18.0 ms a call on the chip: PERF.md
    section 6, PR 39); `_set_pages` writes a page at a time in place."""
    import math
    import re

    from ray_tpu.models import decoding_paged as dp

    cfg, cache, bucket, shapes = _serve_cache(name)
    # leading dense layers are window layers stacked apart from the periods':
    # an insert joins the two stacks' K and V of the bucket once (a copy of
    # what is written, no pool's)
    joined = 2 * (cfg.n_planes - cfg.n_full_layers) * bucket * cfg.kv_heads * cfg.head_dim * 2 \
        if cfg.n_dense_layers and cfg.window else 0

    def sds(s, dt=jnp.int32):
        return jax.ShapeDtypeStruct(s, dt, sharding=chip)

    state = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                         jax.eval_shape(lambda: dp.init_paged_state(cfg, *cache)))
    pools = {k: v for k, v in state.items() if k in ("kp", "vp", "wkp", "wvp")}
    assert {k: v.shape for k, v in pools.items()} == shapes
    rows = (cfg.latent_lanes,) if cfg.mla else (cfg.kv_heads, cfg.head_dim)
    kv = {x: sds((cfg.n_planes, bucket, *rows), cfg.dtype) for x in ("k" if cfg.mla else "kv")}
    ids, row = sds((bucket // 64,)), sds(state["block"].shape[1:])
    ring = sds(state["wblock"].shape[1:]) if cfg.window else None
    if writer == "write_kv_pages":
        lowered = dp.write_kv_pages.lower(state, kv, ids, ring, sds(()) if cfg.window else None,
                                          dense_layers=cfg.n_dense_layers)
    elif writer == "insert_sequence_paged":
        lowered = dp.insert_sequence_paged.lower(state, sds(()), kv, sds(()), sds(()), row,
                                                 cfg, ring)
    else:
        lowered = dp.insert_sequence_paged_prefix.lower(
            state, sds(()), kv, ids, row, sds(()), sds(()), cfg, ring)
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    nbytes = [2 * math.prod(v.shape) for v in pools.values()]
    assert m.alias_size_in_bytes >= sum(nbytes)
    assert m.temp_size_in_bytes < min(nbytes) // 100 + joined
    text = compiled.as_text()
    for shape in {v.shape for v in pools.values()}:
        dims = ",".join(map(str, shape))
        assert not re.search(r"= bf16\[" + dims + r"\]\{[^}]*\} copy\(", text), shape


def test_mixtral_prefill_chunk_multiplies_the_experts_where_they_lie(chip):
    """The 1,024-token prefill of `mixtral-8x7b.doc-saturated` (4 layers,
    published widths): three `grouped_matmul` kernels in the layer scan, at
    K 4,096 and 14,336, and temporaries under one layer's experts (no slice of
    the stack, no padded copy); its decode step holds no grouped product."""
    from ray_tpu.models import decoding_paged, mixtral_config
    from ray_tpu.models.transformer import MoEConfig

    cfg = mixtral_config("8x7b", n_layers=4, param_dtype=jnp.bfloat16, max_seq_len=8320,
                         moe=MoEConfig(num_experts=8, top_k=2, capacity_factor=4.0))
    params, state = _abstract_step_inputs(chip, cfg, 32, 8320, 1024, 64)
    prefill = _prefill_1024(chip, params, cfg)
    assert _kernel_calls(prefill.as_text()).count("grouped_matmul") == 3
    assert "ragged-dot" not in prefill.as_text()
    assert prefill.memory_analysis().temp_size_in_bytes < 8 * 4096 * 14336 * 2
    step = decoding_paged.decode_step_paged_ragged.lower(
        params, state, cfg, 32, True).compile().as_text()
    assert "grouped_matmul" not in step and "ragged-dot" not in step


# (sampling, k_bucket) -> the program has a sort of the vocabulary
@pytest.mark.parametrize("sampling,k_bucket,sorts", [
    (False, 0, False),      # every live row greedy: what every cell runs
    (True, 0, False),       # a live row samples, none cuts
    (True, 8, False),       # the largest live top_k in (4, 8]
    (True, 128, False),     # `decoding.TOP_K_MAX_BUCKET`
    (True, 98304, True),    # beyond it: the whole vocabulary, as the old sampler
])
def test_sampler_form_sorts_no_vocabulary(chip, sampling, k_bucket, sorts):
    """`sample_per_row` at the widths of `mellum2-12b-a2.5b.mixed-saturated`
    (48 rows x 98,304 logits, 18,874,368 bytes): the argmax form holds no
    sort and no temporary of the logits' size (the sampler up to PR 32: one
    sort, temporaries 19,293,696 bytes), the categorical form neither (the
    noise and the second argmax fuse), and `lax.top_k` lowers to the
    compiler's `TopK` custom call at every bucket up to the largest and to
    a sort at the whole vocabulary, which is the old sampler's cost and no
    more (this compiler keeps `TopK` up to k = 512, where the chip shows it
    slower than the sort: `decoding.TOP_K_MAX_BUCKET`)."""
    import re

    from ray_tpu.models import decoding

    B, V = 48, 98304

    def sds(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=chip)

    compiled = decoding.sample_per_row.lower(
        sds((B, V), jnp.float32), sds((2,), jnp.uint32), sds((B,), jnp.float32),
        sds((B,), jnp.int32), sampling, k_bucket).compile()
    text = compiled.as_text()
    assert bool(re.search(r"\bsort\(", text)) == sorts
    assert k_bucket in (0, V) or k_bucket == decoding.top_k_bucket(k_bucket, V)
    assert ('custom_call_target="TopK"' in text) == (0 < k_bucket < V)
    if not sorts:
        assert compiled.memory_analysis().temp_size_in_bytes < B * V * 4 // 16
    if not sampling:
        assert "custom_call_target" not in text


def test_kernel_names_reach_the_compiled_program(chip):
    """`pallas_call(name=...)` names the HLO instruction of each kernel's
    custom call, which is what a device trace names the op by: the trace
    reduction tells the kernels apart by name, not only by operand count."""
    import re

    def loss(q, k, v):
        return flash_attention(q, k, v, True, None).astype(
            jnp.float32).sum()

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)),
                          *_flash_qkv(chip, GPT2_774M_TRAIN))
    calls = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
    # under jax.grad the transforms wrap the name: jvp(flash_fwd) prints as
    # `jvp_flash_fwd_`, the backward pair as `transpose_jvp_flash_bwd_dq__`
    assert sorted(re.sub(r"^((jvp|transpose)_)*|_+$", "", c.split(".")[0])
                  for c in calls) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    # ... and exactly these three, by the operands and results that
    # chipbench/layer_metrics/flash_roofline_pct.train.json finds them by
    from chipbench import trace_reduce

    assert sorted(trace_reduce.kernel_ops_from_hlo(text).values()) == [
        "3in_2out", "6in_1out", "6in_2out"]

    for shape in [(16, 8, 4, 64, 64, 32), (16, 16, 1, 128, 64, 16)]:   # either launch
        assert _kernel_calls(_ragged_launch(chip, shape).compile().as_text()) == [
            "ragged_paged_attention"]
    # the chunked delta rule of a 2,048-token chunk, solar-open2-250b's heads
    from ray_tpu.ops import ssm

    def sds(*s):
        return jax.ShapeDtypeStruct(s, jnp.float32, sharding=chip)

    T, H, D = 2048, 64, 128
    scan = jax.jit(lambda *a: ssm._kda_chunk_scan_pallas(*a, 64, ssm.KDA_SUB, False)).lower(
        sds(T, H, D), sds(T, H, D), sds(T, H, D), sds(T, H, D), sds(T, H), sds(H, D, D))
    assert _kernel_calls(scan.compile().as_text()) == ["kda_chunk_scan"]


# ---- granite-4.0-h-micro.chat-saturated: a recurrent state a slot beside pages

GRANITE_CELL = (104, 1600, 2601, 64)    # slots, max_len, pages, page: the cell's engine


def _granite(chip):
    """(cfg, weights, state) of `granite-4.0-h-micro` as its cell runs it:
    all 40 layers (a scan of 4 periods compiles one period), every width."""
    from ray_tpu.models import granite_config

    cfg = granite_config("4.0-h-micro", param_dtype=jnp.bfloat16, max_seq_len=1600)
    params, state = _abstract_step_inputs(chip, cfg, *GRANITE_CELL)
    assert state["ssm"].shape == (36, 104, 64, 64, 128) and state["ssm"].dtype == jnp.float32
    assert state["conv"].shape == (36, 104, 3, 4352)
    # KV heads of 64, two a row of 128 lanes (cfg.kv_packed)
    assert state["kp"].shape == state["vp"].shape == (4, 2601, 64, 4, 128)
    return cfg, params, state


def _held_bytes(state) -> int:
    import math

    return sum(math.prod(state[k].shape) * state[k].dtype.itemsize
               for k in ("ssm", "conv", "kp", "vp"))


def test_granite_decode_step_updates_state_and_pools_where_they_lie(chip):
    """The decode program of `granite-4.0-h-micro.chat-saturated` at the
    cell's shapes: the recurrent state (7.85 GB; it cannot be held twice
    beside 6.38 GB of weights), the convolution's tails and both pools alias
    input to output; `ssm_state_update` once a scan of state-space layers
    (before and after a period's attention layer), the ragged launch once.

    Temporaries 1,879,040 bytes. They were 4.69 GB, which did not fit, in two
    earlier forms of this PR: the published `in_proj` [36, 2048, 8512] as ONE
    matrix was re-laid whole, transposed, at the head of the step (8,512
    columns are 66.5 x 128 lanes; 2 x 1.17 GB), and pools of [.., 8, 64] were
    copied whole into a layout padded to 128 lanes (2 x 1.17 GB for 2 x 0.63
    GB of pool): hence the mixer's three input matrices and `kv_packed`."""
    from ray_tpu.models import decoding_paged

    cfg, params, state = _granite(chip)
    compiled = decoding_paged.decode_step_paged_ragged.lower(
        params, state, cfg, 32, True).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= _held_bytes(state)
    assert m.temp_size_in_bytes < 16 * 2**20
    assert _kernel_calls(compiled.as_text()) == [
        "ssm_state_update", "ssm_state_update", "ragged_paged_attention"]
    total = (m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
             + m.temp_size_in_bytes)
    assert total < 15.8e9                              # of 16,909,336,064 on the chip


@pytest.mark.parametrize("writer", ["insert_sequence_paged", "insert_sequence_paged_prefix",
                                    "write_kv_pages", "activate_slot"])
def test_granite_writers_update_state_and_pools_where_they_lie(chip, writer):
    """What puts a prefilled row into the cache at the cell's shapes: a
    1,024-token prompt's pages of the four attention layers and the row's
    recurrent state (75.5 MB) and tail into its slot, all aliased, no
    temporary of a pool's or the state's size."""
    from ray_tpu.models import decoding_paged as dp

    cfg, _, state = _granite(chip)

    def sds(s, dt=jnp.int32):
        return jax.ShapeDtypeStruct(s, dt, sharding=chip)

    kv = {x: sds((4, 1024, 8, 64), cfg.dtype) for x in "kv"}
    row_state = {"ssm": sds((36, 64, 64, 128), jnp.float32),
                 "conv": sds((36, 3, 4352), cfg.dtype)}
    ids, row = sds((16,)), sds(state["block"].shape[1:])
    if writer == "insert_sequence_paged":
        lowered = dp.insert_sequence_paged.lower(
            state, sds(()), {**kv, **row_state}, sds(()), sds(()), row, cfg)
    elif writer == "insert_sequence_paged_prefix":
        lowered = dp.insert_sequence_paged_prefix.lower(
            state, sds(()), {**kv, **row_state}, ids, row, sds(()), sds(()), cfg)
    elif writer == "write_kv_pages":
        lowered = dp.write_kv_pages.lower(state, kv, ids)
    else:
        lowered = dp.activate_slot.lower(state, sds(()), row, sds(()), sds(()), None, row_state)
    m = lowered.compile().memory_analysis()
    assert m.alias_size_in_bytes >= _held_bytes(state)
    assert m.temp_size_in_bytes < 2**20


def test_granite_prefill_fits_beside_the_state(chip):
    """The largest program the cell's mix reaches, the 1,024-token prefill
    (the chunked scan in plain XLA, no kernel of its own; flash attention on
    the four attention layers), beside weights, state and pools."""
    cfg, params, state = _granite(chip)
    prefill = _prefill_1024(chip, params, cfg)
    m = prefill.memory_analysis()
    assert m.temp_size_in_bytes < 256 * 2**20          # 178,619,392
    assert m.output_size_in_bytes < 96 * 2**20         # the row's state, its K and V
    held = _held_bytes(state) + m.argument_size_in_bytes
    assert held + m.temp_size_in_bytes + m.output_size_in_bytes < 16.0e9
    assert "ssm_state_update" not in prefill.as_text()


def test_trinity_chunk_and_check_prefill_fit_beside_the_weights(chip, flash_as_on_chip):
    """`trinity-large-preview.agent-saturated`: the largest chunk program (2,048
    tokens over a 32,768-token prefix, scores one KV head's group of 6 at a
    time) beside both pools, and the check's own unchunked prefill of a
    bucket of 8,192, twice the window: the masked window attention a KV
    head's group at a time (1.6 GB of scores, not 12.9), the full layer in the
    flash kernel. chipbench/tests/test_trinity.py holds the whole account."""
    import math

    from chipbench import harness, program
    from ray_tpu.models import decoding, decoding_paged as dp

    conf = harness.resolve_cell("trinity-large-preview.agent-saturated")["config_file"]
    cfg, eng, aot = program.transformer_config(conf["program"]), conf["engine"], conf["aot"]
    params, state = _abstract_step_inputs(
        chip, cfg, eng["max_slots"], eng["max_len"], eng["num_pages"], eng["page_size"])

    def sds(s, dt=jnp.int32):
        return jax.ShapeDtypeStruct(s, dt, sharding=chip)

    def total(compiled):
        m = compiled.memory_analysis()
        return (m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
                + m.temp_size_in_bytes)

    kv = lambda layers, tokens: sds((layers, tokens, 8, 128), jnp.bfloat16)  # noqa: E731
    chunk = dp.prefill_with_prefix.lower(
        params, sds((1, 2048)), kv(1, 32768), kv(1, 32768), sds(()), sds(()), cfg,
        kv(4, 4096), kv(4, 4096)).compile()
    assert "grouped_matmul" in _kernel_calls(chunk.as_text())
    pools = aot["full_pool_bytes"] + aot["window_pool_bytes"]
    assert chunk.memory_analysis().temp_size_in_bytes < 1.3e9     # 6 x 2048 x 34816 x 4 and change
    # on the chip (`kernel`) every layer's attention is one flash launch, the
    # three launches of the scans' bodies, and no temporary is a score tensor's
    # size (a fifth of what the file's note, which a `benchmark` PR has to
    # correct, says of the XLA form)
    flash = dp.prefill_with_prefix.lower(
        params, sds((1, 2048)), kv(1, 32768), kv(1, 32768), sds(()), sds(()), cfg,
        kv(4, 4096), kv(4, 4096), kernel=True).compile()
    assert _kernel_calls(flash.as_text()).count("flash_prefix_attention") == 3
    assert "grouped_matmul" in _kernel_calls(flash.as_text())
    assert flash.memory_analysis().temp_size_in_bytes < 400e6
    assert aot["prefill_chunk_2048_prefix_32768_temp_bytes"] > 1.1e9
    assert total(chunk) + pools < 15.49e9
    check = decoding.prefill.lower(params, sds((1, 8192)), sds(()), cfg).compile()
    assert check.memory_analysis().temp_size_in_bytes < 3e9
    assert total(check) < 15.49e9
    assert math.isclose(total(check), aot["check_prefill_8192_bytes"], rel_tol=0.01)


@pytest.mark.parametrize("cell,chunk,span,launches", [
    ("mellum2-12b-a2.5b.mixed-saturated", 1024, 16384, 2),
    ("mellum2-12b-a2.5b.mixed-saturated", 256, 4096, 0),     # a tail's bucket: the XLA form
    ("mixtral-8x7b.doc-saturated", 1024, 8192, 1),
    ("mixtral-8x7b.doc-saturated", 512, 2048, 0),
    ("kimi-vl-a3b.longdoc-saturated", 1024, 16384, 0),       # latent: the XLA form
])
def test_chunk_programs_attend_in_the_flash_launch(chip, cell, chunk, span, launches):
    """The chunking cells' continuation programs with `kernel` as the chip's
    engine passes it: a launch in every scan's body (full and window layers)
    of a whole chunk's program, and none where the shapes refuse it (a tail's
    bucket under 1,024, a latent pool). `mellum2`'s chunk of 1,024 over 16,384 holds 330,524,672
    bytes of temporaries where the XLA form holds 620,175,360 (a score tensor
    of 32 x 1,024 x 17,408 float32 is 2.3 GB, which `_SCORES_AT_ONCE` splits)."""
    from chipbench import harness, program
    from ray_tpu.models import decoding_paged as dp

    conf = harness.resolve_cell(cell)["config_file"]
    cfg, eng = program.transformer_config(conf["program"]), conf["engine"]
    params, state = _abstract_step_inputs(chip, cfg, 2, 2 * eng["page_size"], 8, eng["page_size"])

    def sds(s, dt=jnp.int32):
        return jax.ShapeDtypeStruct(s, dt, sharding=chip)

    def kv(layers, tokens):
        return sds((layers, tokens, *state["kp"].shape[3:]), state["kp"].dtype)

    n_window = cfg.n_layers - cfg.n_full_layers if cfg.window else 0
    window = (kv(n_window, cfg.window),) * 2 if n_window else ()
    full = kv(cfg.n_full_layers, span)
    compiled = dp.prefill_with_prefix.lower(
        params, sds((1, chunk)), full, None if cfg.mla else full, sds(()), sds(()), cfg,
        *window, kernel=True).compile()
    assert _kernel_calls(compiled.as_text()).count("flash_prefix_attention") == launches
    if launches:
        assert compiled.memory_analysis().temp_size_in_bytes < 400e6


def test_solar_chunk_program_scans_in_one_launch_and_drops_its_temporaries(chip, monkeypatch):
    """`solar-open2-250b.reasondoc-saturated`: the 2,048-token continuation
    over a 32,768-token prefix, as the CPU's backend traces it (the chunked
    delta rule in plain XLA: the pairwise decays, the Gram matrices and the
    triangular systems of all 32 chunks are temporaries in HBM) and as the
    chip's does (one `kda_chunk_scan` launch in the body of the KDA layers'
    scan, those kept in VMEM): 1,107,490,304 -> 601,015,808 bytes of
    temporaries by the compiler's account."""
    from chipbench import harness, program
    from ray_tpu.models import decoding
    from ray_tpu.models import decoding_paged as dp
    from ray_tpu.ops import ssm

    conf = harness.resolve_cell("solar-open2-250b.reasondoc-saturated")["config_file"]
    cfg = program.transformer_config(conf["program"])
    params, _ = _abstract_step_inputs(chip, cfg, 2, 128, 8, 64)

    def sds(s, dt=jnp.int32):
        return jax.ShapeDtypeStruct(s, dt, sharding=chip)

    tokens = sds((1, 2048))
    kv = jax.eval_shape(lambda p, t, n: decoding.prefill(p, t, n, cfg)[1], params, tokens, sds(()))
    row = jax.tree.map(lambda x: sds(x.shape, x.dtype), {"ssm": kv["ssm"], "conv": kv["conv"]})
    prefix = sds((1, 32768, 8, 128), jnp.bfloat16)

    def compiled():
        dp.prefill_with_prefix.clear_cache()
        return dp.prefill_with_prefix.lower(params, tokens, prefix, prefix, sds(()), sds(()),
                                            cfg, row_state=row, kernel=True).compile()

    xla = compiled()
    assert "kda_chunk_scan" not in _kernel_calls(xla.as_text())
    monkeypatch.setattr(ssm, "kda_scan_in_kernel", ssm.kda_scan_tiles)
    try:
        launch = compiled()
    finally:
        dp.prefill_with_prefix.clear_cache()
    assert _kernel_calls(launch.as_text()).count("kda_chunk_scan") == 1
    before, after = (c.memory_analysis().temp_size_in_bytes for c in (xla, launch))
    assert before > 1.0e9 and after < 0.65e9


def test_solar_chunk_program_runs_the_mixer_around_its_scan_in_three_launches(chip, monkeypatch):
    """The same continuation program with the mixer's predicate steered too,
    as the chip's backend traces it since PR 53: the body of the KDA layers'
    scan calls `kda_conv`, `kda_split`, `kda_chunk_scan` and `kda_gate_norm`
    once each; `in_qkv` writes its bfloat16 product alone (the one float32
    [2048, 24576] of the program is what `kda_conv` writes: XLA's product had
    written both, 302 MB a layer); the temporaries are no larger than the
    601,015,808 bytes they were (512,207,872). The decode program at the
    cell's 32 slots is not touched: its rows are under a block of the
    launches, whatever the backend."""
    import re

    from chipbench import harness, program
    from ray_tpu.models import decoding
    from ray_tpu.models import decoding_paged as dp
    from ray_tpu.ops import ssm

    conf = harness.resolve_cell("solar-open2-250b.reasondoc-saturated")["config_file"]
    cfg, eng = program.transformer_config(conf["program"]), conf["engine"]
    params, state = _abstract_step_inputs(chip, cfg, eng["max_slots"], 128, 8, eng["page_size"])

    def sds(s, dt=jnp.int32):
        return jax.ShapeDtypeStruct(s, dt, sharding=chip)

    tokens = sds((1, eng["prefill_chunk"]))
    kv = jax.eval_shape(lambda p, t, n: decoding.prefill(p, t, n, cfg)[1], params, tokens, sds(()))
    row = jax.tree.map(lambda x: sds(x.shape, x.dtype), {"ssm": kv["ssm"], "conv": kv["conv"]})
    prefix = sds((1, 32768, 8, 128), jnp.bfloat16)
    monkeypatch.setattr(ssm, "kda_scan_in_kernel", ssm.kda_scan_tiles)
    monkeypatch.setattr(ssm, "kda_mixer_in_kernel", ssm.kda_mixer_tiles)
    dp.prefill_with_prefix.clear_cache()
    dp.decode_step_paged_ragged.clear_cache()
    try:
        chunk = dp.prefill_with_prefix.lower(params, tokens, prefix, prefix, sds(()), sds(()),
                                             cfg, row_state=row, kernel=True).compile()
        step = dp.decode_step_paged_ragged.lower(
            params, state, cfg, eng["max_slots"], True).compile()
    finally:
        dp.prefill_with_prefix.clear_cache()
        dp.decode_step_paged_ragged.clear_cache()
    text = chunk.as_text()
    assert _kernel_calls(text) == [
        "kda_conv", "kda_split", "kda_chunk_scan", "kda_gate_norm",        # the KDA layers' scan
        "grouped_matmul", "grouped_matmul", "grouped_matmul",
        "flash_prefix_attention", "grouped_matmul", "grouped_matmul", "grouped_matmul"]
    T, C = eng["prefill_chunk"], cfg.ssm.conv_dim
    assert [op.split(".")[0] for op in re.findall(rf"%([\w.\-]+) = f32\[{T},{C}\]", text)] == [
        "kda_conv"]
    assert chunk.memory_analysis().temp_size_in_bytes <= 601_015_808
    assert _kernel_calls(step.as_text()) == [
        "kda_state_update", "grouped_matmul", "grouped_matmul", "grouped_matmul",
        "ragged_paged_attention", "grouped_matmul", "grouped_matmul", "grouped_matmul"]


@pytest.mark.parametrize("mesh_axes,chips", [({}, 1), ({"dp": 2, "fsdp": 2}, 4)],
                         ids=["one_chip", "mesh_2x2"])
def test_gpt2_large_train_step_runs_the_flash_forward_once_and_keeps_its_output(
        topo, flash_as_on_chip, mesh_axes, chips):
    """The step of `gpt2-large.train-1chip` (batch 4 x 1,024, AdamW): a
    layer's checkpoint keeps the flash kernel's output and log-sum-exp
    (`ops.FLASH_KEPT`), so the program holds three Pallas calls, the forward
    once, under the signatures the benchmark's reader finds them by. On one
    chip its bytes say that both stacks are kept and kept lane-dense: 15.18e9
    with neither (the step before PR 56), 16.05e9 as PR 56 built it, 15.85e9
    since PR 58 (the log-sum-exp and `delta` cross every kernel boundary as
    rows [B, H, 1, T], so the five padded 42 MB columns [B, H, T, 1] that were
    live at the peak are gone, and the window's floor came down from 15.9e9
    with them), 16.79e9 with the output kept in the kernel's [B, H, T, 64],
    whose 64 lanes pad to 128. No float32 column [.., 1024, 1] is left in the
    compiled text.
    Under a mesh the kernels run per shard in `_flash_per_shard`'s shard_map,
    and the names have to reach the checkpoint through it."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from chipbench.trace_reduce import kernel_ops_from_hlo
    from ray_tpu.models import gpt2_config, transformer
    from ray_tpu.parallel import DEFAULT_RULES, MeshSpec, param_shardings
    from ray_tpu.train.spmd import make_train_step

    cfg = gpt2_config("774m")
    mesh = MeshSpec(**mesh_axes).build(list(topo.devices[:chips]))
    axes, opt = transformer.logical_axes(cfg), optax.adamw(3e-4)
    step, _, batch_sharding = make_train_step(
        lambda p, t: transformer.loss_fn(p, t, cfg), axes, mesh, opt)

    def abstract(tree, shardings):
        return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                            tree, shardings)

    p_sh = param_shardings(mesh, axes, DEFAULT_RULES)
    params = abstract(jax.eval_shape(lambda k: transformer.init(k, cfg),
                                     jax.random.PRNGKey(0)), p_sh)
    o_shape = jax.eval_shape(opt.init, params)
    o_sh = optax.tree_map_params(opt, lambda _, s: s, o_shape, p_sh,
                                 transform_non_params=lambda _: NamedSharding(mesh, P()))
    batch = jax.ShapeDtypeStruct((4, 1025), jnp.int32, sharding=batch_sharding)
    compiled = step.lower(params, abstract(o_shape, o_sh), batch).compile()
    text = compiled.as_text()
    assert sorted(kernel_ops_from_hlo(text).values()) == [
        "3in_2out", "6in_1out", "6in_2out"]
    # no per-query array as a column (one float32 a 128-lane row), whole or
    # per shard: f32[4,20,1024,1] on one chip
    assert not re.findall(r"f32\[[0-9,]*1024,1\]", text)
    if chips == 1:
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 - m.alias_size_in_bytes + m.temp_size_in_bytes)
        assert 15.7e9 < total < 16.3e9


def test_train_and_first_chunk_flash_programs_are_what_they_were():
    """The continuation's launch is a sibling, not a variant: the train
    step's three kernels (forward and backward at GPT-2 774M's heads) and the
    forward a prompt's first chunk takes (trinity's 48 heads of 128 over
    2,048) trace to the jaxprs they had before it came (PR 45's tree, read in
    PR 47). A change to those kernels changes these digests on purpose and
    says so here. PR 58 moved both on purpose: the forward kernel writes its
    log-sum-exp as rows [B, H, 1, T] (one transposition of a [block_q, 128]
    plane at `_finalize`), the dq kernel reads the rows of it and of `delta`
    and turns them once a q block into two [block_q, 128] scratch planes, and
    `delta` is born [B, H, T]: the column [B, H, T, 1] is gone from all three
    (before: (59767, "df9f178b79978772") and (21687, "46245d7ec9aa1222"))."""
    import hashlib

    from ray_tpu.ops.flash_attention import flash_attention_forward

    def digest(fn, shape):
        q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        text = str(jax.make_jaxpr(fn)(q, q, q))
        return len(text), hashlib.sha256(text.encode()).hexdigest()[:16]

    def loss(q, k, v):
        return flash_attention(q, k, v, True, None, None, None, False).astype(
            jnp.float32).sum()

    assert digest(jax.grad(loss, argnums=(0, 1, 2)), (4, 20, 1024, 64)) == (
        61300, "e2c6d64a285ea644")
    assert digest(lambda q, k, v: flash_attention_forward(q, k, v, scale=0.1),
                  (1, 48, 2048, 128)) == (21757, "f8044aa5b940753e")
