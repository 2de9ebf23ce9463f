"""LLM stack tests: decode correctness, continuous batching, serve + PD + batch.

(reference test model: release/llm_tests/ + serve tests; the decode path is
validated against the full-forward model — SURVEY.md §4.)
"""

import threading
import time

import jax
import jax.numpy as jnp
import pytest

import ray_tpu
from ray_tpu.models import transformer
from ray_tpu.models.transformer import TransformerConfig

TINY = dict(vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=128, max_seq_len=128, dtype=jnp.float32, remat=False)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = TransformerConfig(**TINY)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _naive_greedy(params, cfg, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        logits, _ = transformer.forward(params, jnp.asarray([toks]), cfg)
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def test_engine_matches_full_forward(tiny_model):
    from ray_tpu.llm import SamplingParams, TPUEngine

    cfg, params = tiny_model
    eng = TPUEngine(cfg, params, max_slots=4, max_len=64, min_bucket=8)
    prompt = [1, 5, 9, 2, 7]
    out = eng.generate(prompt, SamplingParams(max_tokens=8, temperature=0.0))
    assert out == _naive_greedy(params, cfg, prompt, 8)
    eng.shutdown()


def test_engine_continuous_batching_isolated_sequences(tiny_model):
    from ray_tpu.llm import SamplingParams, TPUEngine

    cfg, params = tiny_model
    eng = TPUEngine(cfg, params, max_slots=4, max_len=64, min_bucket=8)
    prompts = [[1, 5, 9], [3, 3, 8, 2], [7], [2, 4, 6, 8, 10]]
    want = [_naive_greedy(params, cfg, p, 6) for p in prompts]
    got = [None] * len(prompts)

    def run(i):
        got[i] = eng.generate(prompts[i], SamplingParams(max_tokens=6))

    threads = [threading.Thread(target=run, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert got == want  # interleaved decoding must not cross-contaminate rows
    eng.shutdown()


def test_engine_oversubscription_queues(tiny_model):
    """More requests than slots: the waiting queue drains as slots free."""
    from ray_tpu.llm import SamplingParams, TPUEngine

    cfg, params = tiny_model
    eng = TPUEngine(cfg, params, max_slots=2, max_len=64, min_bucket=8)
    reqs = [eng.submit([i + 1, i + 2], SamplingParams(max_tokens=4))
            for i in range(6)]
    from ray_tpu.llm.engine import _SENTINEL

    outs = []
    for r in reqs:
        ids = []
        while True:
            tok = r.out_queue.get(timeout=60)
            if tok is _SENTINEL:
                break
            ids.append(tok)
        outs.append(ids)
    assert all(len(o) == 4 for o in outs)
    eng.shutdown()


def test_engine_stream_and_stats(tiny_model):
    from ray_tpu.llm import SamplingParams, TPUEngine

    cfg, params = tiny_model
    eng = TPUEngine(cfg, params, max_slots=2, max_len=64, min_bucket=8)
    toks = list(eng.stream([1, 2, 3], SamplingParams(max_tokens=5)))
    assert len(toks) == 5
    s = eng.stats()
    assert s["max_slots"] == 2 and s["active"] == 0
    eng.shutdown()


def test_byte_tokenizer_roundtrip():
    from ray_tpu.llm import ByteTokenizer

    t = ByteTokenizer()
    ids = t.encode("hello, TPU!")
    assert ids[0] == t.BOS
    assert t.decode(ids) == "hello, TPU!"
    assert t.vocab_size == 259


@pytest.fixture
def llm_cluster():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=32, num_workers=2, max_workers=16)
    yield
    from ray_tpu import serve

    serve.shutdown()
    ray_tpu.shutdown()


def _tiny_llm_config(**engine_kwargs):
    from ray_tpu.llm import LLMConfig, ModelLoadingConfig

    return LLMConfig(
        model_loading_config=ModelLoadingConfig(model_id="tiny", tokenizer="byte"),
        model_family="llama", accelerator_type=None,
        model_kwargs=dict(vocab_size=300, max_seq_len=128, d_model=64,
                          n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
                          dtype=jnp.float32, remat=False),
        engine_kwargs={"max_slots": 4, "max_len": 128, "min_bucket": 16,
                       **engine_kwargs},
    )


@pytest.mark.parametrize("engine_kwargs,refused", [
    ({"kv_layout": "paged"}, None),
    ({"kv_layout": "slot"}, "kv_layout"),
    ({"attn_impl": "gather"}, "attn_impl"),
    ({"speculative_k": 2}, "speculative_k"),
])
def test_from_config_names_the_options_that_were_removed(engine_kwargs, refused):
    """Configuration files still say `kv_layout: paged`; every other value,
    and the options of the gather step and of speculation, are refused by
    name, before the model is built."""
    from ray_tpu.llm import TPUEngine

    config = _tiny_llm_config(**engine_kwargs)
    if refused:
        with pytest.raises(ValueError, match=f"{refused}.*removed"):
            TPUEngine.from_config(config)
        return
    eng = TPUEngine.from_config(config)
    try:
        st = eng.stats()
        assert st["page_size"] == 64 and st["decode_attn"] == "ragged_reference"
        assert "kv_layout" not in st and "attn_impl" not in st
    finally:
        eng.shutdown()


def test_llm_server_openai_surface(llm_cluster):
    from ray_tpu import serve
    from ray_tpu.llm import build_openai_app

    handle = serve.run(build_openai_app(_tiny_llm_config()), name="llm",
                       route_prefix="/llm")
    out = handle.completions.remote(
        {"prompt": "hi", "max_tokens": 8}).result(timeout_s=120)
    assert out["object"] == "text_completion"
    assert out["usage"]["completion_tokens"] <= 8
    assert isinstance(out["choices"][0]["text"], str)
    chat = handle.chat.remote(
        {"messages": [{"role": "user", "content": "hey"}],
         "max_tokens": 4}).result(timeout_s=120)
    assert chat["object"] == "chat.completion"
    assert "message" in chat["choices"][0]
    # /v1/stats surfaces engine observability over the same HTTP entry
    st = handle.remote({"path": "/v1/stats"}).result(timeout_s=60)
    assert st["max_slots"] >= 1 and "decode_attn" in st
    serve.delete("llm")


def test_pd_disaggregation(llm_cluster):
    from ray_tpu import serve
    from ray_tpu.llm import build_pd_openai_app

    handle = serve.run(build_pd_openai_app(_tiny_llm_config()), name="pd",
                       route_prefix="/pd")
    out = handle.remote({"prompt": "abc", "max_tokens": 6}).result(timeout_s=180)
    assert isinstance(out["choices"][0]["text"], str)
    # no stop tokens → the budget is spent exactly (first token + decode)
    assert out["usage"]["completion_tokens"] == 6
    # first-token latency is reported SEPARATELY from completion latency
    assert 0 < out["usage"]["ttft_s"] <= out["usage"]["total_time_s"]
    serve.delete("pd")


def test_batch_processor(llm_cluster):
    import ray_tpu.data as rdata
    from ray_tpu.llm import build_llm_processor

    ds = rdata.from_items([{"prompt": f"item {i}"} for i in range(6)])
    proc = build_llm_processor(
        _tiny_llm_config(), concurrency=1, batch_size=3,
        sampling_params={"max_tokens": 4, "temperature": 0.0})
    out = proc(ds).take_all()
    proc.shutdown()
    assert len(out) == 6
    assert all("generated" in r and isinstance(r["generated"], str) for r in out)
    assert sorted(str(r["prompt"]) for r in out) == sorted(f"item {i}" for i in range(6))
