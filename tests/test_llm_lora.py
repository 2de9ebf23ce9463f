"""Multi-LoRA serving: per-slot adapters in the batched decode step,
multiplexed adapter loading with eviction.

(reference: python/ray/llm/_internal/serve/utils/lora_serve_utils.py —
LoRA adapters load dynamically by model id onto the engine and serve
through multiplexing; SURVEY.md §2.4 LLM. Correctness bar: idx-0/zero
adapters are bit-identical to the base model; a loaded adapter matches the
same weights merged densely into the base params, token-exact.)
"""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.llm.config import LLMConfig, LoraConfig, ModelLoadingConfig
from ray_tpu.llm.engine import SamplingParams, TPUEngine
from ray_tpu.models import llama_config, transformer

RANK = 4


def _tiny_cfg(family="llama"):
    import jax.numpy as jnp

    if family == "mixtral":  # dropless experts: the layer scan closes over them
        from ray_tpu.models import mixtral_config
        from ray_tpu.models.transformer import MoEConfig

        return mixtral_config("tiny", vocab_size=256, max_seq_len=128,
                              d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
                              d_ff=96, dtype=jnp.float32, moe=MoEConfig(
                                  num_experts=4, top_k=2, capacity_factor=2.0))
    return llama_config("tiny", vocab_size=256, max_seq_len=128,
                        d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
                        d_ff=128, dtype=jnp.float32)


def _params(cfg, seed=0):
    import jax

    return transformer.init(jax.random.PRNGKey(seed), cfg)


def _rand_adapter(cfg, seed, scale=0.1):
    rng = np.random.default_rng(seed)
    L, E = cfg.n_layers, cfg.d_model
    H, Hkv, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    return {
        "A_q": rng.normal(0, scale, (L, E, RANK)).astype(np.float32),
        "B_q": rng.normal(0, scale, (L, RANK, H, Dh)).astype(np.float32),
        "A_v": rng.normal(0, scale, (L, E, RANK)).astype(np.float32),
        "B_v": rng.normal(0, scale, (L, RANK, Hkv, Dh)).astype(np.float32),
    }


def _merge(params, cfg, w, scale=1.0):
    """Densely fold the adapter into wq/wv: the ground truth the batched
    gather path must match."""
    import jax
    import jax.numpy as jnp

    merged = jax.tree.map(lambda x: x, params)
    layers = dict(merged["layers"])
    attn = dict(layers["attn"]) if "attn" in layers else None
    # params["layers"] is a stacked pytree: leaves have leading L axis
    new_attn = dict(merged["layers"]["attn"])
    dq = jnp.einsum("ler,lrhd->lehd", jnp.asarray(w["A_q"]),
                    jnp.asarray(w["B_q"])) * scale
    dv = jnp.einsum("ler,lrhd->lehd", jnp.asarray(w["A_v"]),
                    jnp.asarray(w["B_v"])) * scale
    new_attn["wq"] = merged["layers"]["attn"]["wq"] + dq.astype(
        merged["layers"]["attn"]["wq"].dtype)
    new_attn["wv"] = merged["layers"]["attn"]["wv"] + dv.astype(
        merged["layers"]["attn"]["wv"].dtype)
    out = dict(merged)
    out_layers = dict(merged["layers"])
    out_layers["attn"] = new_attn
    out["layers"] = out_layers
    return out


PROMPT = [5, 9, 17, 33, 2, 71]
SP = SamplingParams(max_tokens=12, temperature=0.0)


def test_zero_adapter_matches_base_exactly():
    cfg = _tiny_cfg()
    params = _params(cfg)
    base = TPUEngine(cfg, params, max_slots=2, max_len=128)
    want = base.generate(PROMPT, SP)
    base.shutdown()

    eng = TPUEngine(cfg, params, max_slots=2, max_len=128,
                    max_loras=2, lora_rank=RANK)
    # no adapter at all
    assert eng.generate(PROMPT, SP) == want
    # an explicitly loaded ALL-ZERO adapter
    zeros = {k: np.zeros_like(v) for k, v in _rand_adapter(cfg, 0).items()}
    eng.load_lora("zero", zeros)
    assert eng.generate(PROMPT, SP, lora="zero") == want
    eng.shutdown()


@pytest.mark.parametrize("family", ["llama", "mixtral"])
def test_adapter_matches_dense_merge_token_exact(family):
    cfg = _tiny_cfg(family)
    params = _params(cfg)
    w = _rand_adapter(cfg, 7)
    alpha = 2.0
    scale = alpha / RANK

    merged_eng = TPUEngine(cfg, _merge(params, cfg, w, scale),
                           max_slots=2, max_len=128)
    want = merged_eng.generate(PROMPT, SP)
    merged_eng.shutdown()

    eng = TPUEngine(cfg, params, max_slots=2, max_len=128,
                    max_loras=2, lora_rank=RANK)
    eng.load_lora("ad", w, alpha=alpha)
    got = eng.generate(PROMPT, SP, lora="ad")
    assert got == want, (got, want)
    # and it actually DIFFERS from base
    assert eng.generate(PROMPT, SP) != want
    eng.shutdown()


def test_per_slot_isolation_mixed_batch():
    """Base and adapter requests decode in the SAME batched step without
    contaminating each other."""
    cfg = _tiny_cfg()
    params = _params(cfg)
    eng = TPUEngine(cfg, params, max_slots=4, max_len=128,
                    max_loras=2, lora_rank=RANK)
    eng.load_lora("a", _rand_adapter(cfg, 1))
    eng.load_lora("b", _rand_adapter(cfg, 2))
    reqs = [eng.submit(PROMPT, SP),
            eng.submit(PROMPT, SP, lora="a"),
            eng.submit(PROMPT, SP, lora="b"),
            eng.submit(PROMPT, SP)]
    outs = []
    for r in reqs:
        toks = []
        while True:
            t = r.out_queue.get(timeout=60)
            from ray_tpu.llm.engine import _SENTINEL, _EngineError

            if t is _SENTINEL:
                break
            if isinstance(t, _EngineError):
                raise t.exc
            toks.append(t)
        outs.append(toks)
    eng.shutdown()
    base_eng = TPUEngine(cfg, params, max_slots=4, max_len=128)
    base = base_eng.generate(PROMPT, SP)
    base_eng.shutdown()
    assert outs[0] == base and outs[3] == base  # base rows untouched
    assert outs[1] != base and outs[2] != base  # adapter rows differ
    assert outs[1] != outs[2]                   # per-slot, not global


def _merged_tokens(cfg, params, w, prompt, sp, scale=1.0):
    eng = TPUEngine(cfg, _merge(params, cfg, w, scale) if w else params,
                    max_slots=2, max_len=128)
    try:
        return eng.generate(prompt, sp)
    finally:
        eng.shutdown()


def test_adapter_rows_under_page_pressure_match_the_dense_merge():
    """A pool too small for the batch: later requests wait in the backlog
    for pages, and every row still decodes its own adapter, token-exact
    against that adapter merged densely into the base weights."""
    cfg = _tiny_cfg()
    params = _params(cfg)
    adapters = {"a": _rand_adapter(cfg, 1), "b": _rand_adapter(cfg, 2)}
    # a row holds 2 pages of 16 (bucket 32; positions up to 6 + 12 lie inside);
    # 5 usable pages host two rows at once, the other two wait
    eng = TPUEngine(cfg, params, max_slots=4, max_len=128, page_size=16,
                    num_pages=6, max_loras=2, lora_rank=RANK)
    try:
        for name, w in adapters.items():
            eng.load_lora(name, w)
        order = [None, "a", "b", "a"]
        reqs = [eng.submit(PROMPT, SP, lora=name) for name in order]
        got = [list(r) for r in reqs]
        st = eng.stats()
        assert st["free_pages"] == 5 and st["free_slots"] == 4
    finally:
        eng.shutdown()
    want = {name: _merged_tokens(cfg, params, adapters.get(name), PROMPT, SP)
            for name in set(order)}
    assert got == [want[name] for name in order]
    assert len({tuple(t) for t in want.values()}) == 3


def test_adapters_on_a_tensor_parallel_mesh_match_one_chip():
    """The bank is replicated over the mesh that splits heads and page pools;
    an adapter row decodes what it decodes on one device."""
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >=2 devices")
    cfg = _tiny_cfg()
    params = _params(cfg)
    w = _rand_adapter(cfg, 7)
    outs = []
    for mesh in (None, Mesh(devs[:2], ("tp",))):
        eng = TPUEngine(cfg, params, max_slots=2, max_len=128, mesh=mesh,
                        max_loras=1, lora_rank=RANK)
        try:
            eng.load_lora("ad", w)
            outs.append((eng.generate(PROMPT, SP, lora="ad"),
                         eng.generate(PROMPT, SP)))
        finally:
            eng.shutdown()
    assert outs[0] == outs[1] and outs[0][0] != outs[0][1]
    assert outs[0][0] == _merged_tokens(cfg, params, w, PROMPT, SP)


def test_an_aborted_adapter_row_returns_its_pages_and_its_reference():
    """Cancelled mid-stream, a row gives back slot, pages and the adapter's
    reference in one pass: the adapter can be unloaded at once."""
    import time

    from ray_tpu.exceptions import RequestCancelledError

    cfg = _tiny_cfg()
    eng = TPUEngine(cfg, _params(cfg), max_slots=2, max_len=128, page_size=16,
                    max_loras=1, lora_rank=RANK)
    try:
        eng.load_lora("x", _rand_adapter(cfg, 3))
        req = eng.submit(PROMPT, SamplingParams(max_tokens=100), lora="x")
        assert req.out_queue.get(timeout=60) is not None   # the row is live
        eng.abort_request(req.rid)
        with pytest.raises(RequestCancelledError):
            list(req)
        deadline = time.time() + 30
        while eng.stats()["free_slots"] < 2 and time.time() < deadline:
            time.sleep(0.01)
        st = eng.stats()
        assert st["free_slots"] == 2 and st["free_pages"] == st["num_pages"] - 1
        eng.unload_lora("x")
        assert eng.generate(PROMPT, SP) == _merged_tokens(cfg, _params(cfg), None,
                                                          PROMPT, SP)
    finally:
        eng.shutdown()


@pytest.mark.parametrize("option", [dict(enable_prefix_cache=True),
                                    dict(prefill_chunk=64)],
                         ids=["enable_prefix_cache", "prefill_chunk"])
def test_bank_is_refused_with_the_cached_admission(option):
    """Block hashes do not name the adapter and the continuation prefill
    applies none: refused at construction, not served wrong."""
    cfg = _tiny_cfg()
    with pytest.raises(ValueError, match="max_loras.*" + next(iter(option))):
        TPUEngine(cfg, _params(cfg), max_slots=2, max_len=128, max_loras=1,
                  lora_rank=RANK, **option)


def test_load_unload_refcounts():
    cfg = _tiny_cfg()
    eng = TPUEngine(cfg, _params(cfg), max_slots=2, max_len=128,
                    max_loras=1, lora_rank=RANK)
    w = _rand_adapter(cfg, 3)
    eng.load_lora("x", w)
    with pytest.raises(ValueError, match="already loaded"):
        eng.load_lora("x", w)
    with pytest.raises(RuntimeError, match="no free lora slots"):
        eng.load_lora("y", w)
    req = eng.submit(PROMPT, SamplingParams(max_tokens=40), lora="x")
    with pytest.raises(RuntimeError, match="live requests"):
        eng.unload_lora("x")
    # drain the stream, then the slot frees
    from ray_tpu.llm.engine import _SENTINEL

    while req.out_queue.get(timeout=60) is not _SENTINEL:
        pass
    eng.unload_lora("x")
    eng.load_lora("y", w)  # slot reusable
    assert eng.list_loras() == ["y"]
    with pytest.raises(KeyError):
        eng.submit(PROMPT, SP, lora="x")
    eng.shutdown()


@pytest.fixture
def cluster():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8, num_workers=2, max_workers=8)
    yield
    from ray_tpu import serve

    serve.shutdown()
    ray_tpu.shutdown()


def test_lora_served_through_multiplex(cluster, tmp_path):
    """End to end: requests whose `model` names an adapter load it through
    the multiplex cache; the LRU evicts and reloads adapters."""
    from ray_tpu import serve
    from ray_tpu.llm.server import build_openai_app

    cfg = _tiny_cfg()
    adir = tmp_path / "adapters"
    adir.mkdir()
    for name, seed in (("ad1", 11), ("ad2", 12)):
        np.savez(adir / f"{name}.npz", alpha=np.float32(RANK),
                 **_rand_adapter(cfg, seed))
    # zero adapter: served output must equal base output
    np.savez(adir / "adzero.npz",
             **{k: np.zeros_like(v)
                for k, v in _rand_adapter(cfg, 0).items()})

    llm_config = LLMConfig(
        model_loading_config=ModelLoadingConfig(model_id="tiny",
                                                tokenizer="byte"),
        model_kwargs=dict(vocab_size=256, max_seq_len=128, d_model=64,
                          n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128),
        engine_kwargs=dict(max_slots=4, max_len=128),
        deployment_config=dict(num_replicas=1), accelerator_type=None,
        lora_config=LoraConfig(dynamic_lora_loading_path=str(adir),
                               max_num_adapters_per_replica=2,
                               lora_rank=RANK),
    )
    import jax.numpy as jnp  # model dtype default float32 via model_kwargs?

    handle = serve.run(build_openai_app(llm_config), name="llm",
                       route_prefix="/llm")
    body = {"prompt": "hello", "max_tokens": 8, "temperature": 0.0}
    base = handle.call_sync({"path": "/llm/completions", "method": "POST",
                             "body": body}, timeout_s=120)
    zero = handle.call_sync({"path": "/llm/completions", "method": "POST",
                             "body": {**body, "model": "adzero"}},
                            timeout_s=120)
    assert zero["choices"][0]["text"] == base["choices"][0]["text"]
    assert zero["model"] == "adzero"
    out1 = handle.call_sync({"path": "/llm/completions", "method": "POST",
                             "body": {**body, "model": "ad1"}}, timeout_s=120)
    assert out1["choices"][0]["text"] != base["choices"][0]["text"]
    # third adapter exceeds max 2 per replica: LRU evicts, request succeeds
    out2 = handle.call_sync({"path": "/llm/completions", "method": "POST",
                             "body": {**body, "model": "ad2"}}, timeout_s=120)
    assert out2["model"] == "ad2"
    # evicted adapter reloads transparently
    re1 = handle.call_sync({"path": "/llm/completions", "method": "POST",
                            "body": {**body, "model": "ad1"}}, timeout_s=120)
    assert re1["choices"][0]["text"] == out1["choices"][0]["text"]
    # unknown adapter -> clean error, not a hang
    with pytest.raises(Exception, match="adbogus|FileNotFound"):
        handle.call_sync({"path": "/llm/completions", "method": "POST",
                          "body": {**body, "model": "adbogus"}},
                         timeout_s=60)
