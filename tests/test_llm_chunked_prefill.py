"""Chunked prefill: long prompts stream into the KV pool chunk by chunk,
interleaved with decode steps (round-4; reference capability: vLLM
chunked prefill — VERDICT r3 weak item 6).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import SamplingParams, TPUEngine
from ray_tpu.llm.engine import _iter_request
from ray_tpu.models import transformer
from ray_tpu.models.transformer import TransformerConfig

TINY = dict(vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=128, max_seq_len=128, dtype=jnp.float32, remat=False)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = TransformerConfig(**TINY)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_len", 128)
    kw.setdefault("min_bucket", 8)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 16)
    return TPUEngine(cfg, params, **kw)


def _naive_greedy(params, cfg, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        logits, _ = transformer.forward(params, jnp.asarray([toks]), cfg)
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def _chunked_exact(cfg, params):
    """Prompts of 3-4 chunks each, ragged tails, against the whole-prompt
    prefill's greedy outputs; returns the engine's stats."""
    eng = _engine(cfg, params)
    try:
        rng = np.random.default_rng(0)
        for n in (33, 48, 61):
            prompt = [int(x) for x in rng.integers(1, 100, size=n)]
            got = eng.generate(prompt, SamplingParams(max_tokens=6,
                                                      temperature=0.0))
            assert got == _naive_greedy(params, cfg, prompt, 6), n
        return eng.stats()
    finally:
        eng.shutdown()


def _attended_pairs(cfg, prompts, chunk):
    """Query-key pairs of the chunks past a prompt's first, over the layers."""
    return cfg.n_layers * sum(p + 1 for n in prompts for p in range(chunk, n))


def test_chunked_prefill_token_exact(tiny_model):
    """Outputs of a chunk-streamed admission are EXACTLY the whole-prompt
    prefill's outputs (greedy)."""
    cfg, params = tiny_model
    st = _chunked_exact(cfg, params)
    assert st["prefill_chunks_run"] >= 9  # chunking actually engaged
    # an engine that sees no TPU keeps the XLA form
    assert st["prefill"] == {"continuations_kernel": 0, "continuations_xla": 7,
                             "attended_pairs": _attended_pairs(cfg, (33, 48, 61), 16)}


def test_chunked_prefill_token_exact_in_the_flash_launch(
        tiny_model, flash_interpreted, continuations_as_on_chip):
    """The same through the continuation's flash launch (interpreted, blocks
    of 16 or 8): every chunk past a prompt's first takes it and is counted."""
    cfg, params = tiny_model
    st = _chunked_exact(cfg, params)
    assert flash_interpreted and set(flash_interpreted) == {None}
    assert st["prefill"] == {"continuations_kernel": 7, "continuations_xla": 0,
                             "attended_pairs": _attended_pairs(cfg, (33, 48, 61), 16)}


def test_a_refused_shape_keeps_the_xla_form_and_is_counted(
        tiny_model, continuations_as_on_chip, monkeypatch):
    """Heads of 16 are no whole 128-lane row: on the chip too this model's
    continuations keep the XLA form (`ops.prefix_blocks` refuses), no launch
    is traced, and the engine counts them under `continuations_xla`."""
    from ray_tpu import ops

    def no_launch(*a, **kw):
        raise AssertionError("a refused shape reached the flash launch")

    monkeypatch.setattr(ops, "flash_prefix_attention", no_launch)
    cfg, params = tiny_model
    st = _chunked_exact(cfg, params)
    assert (st["prefill"]["continuations_kernel"], st["prefill"]["continuations_xla"]) == (0, 7)


def test_short_prompts_skip_chunking(tiny_model):
    cfg, params = tiny_model
    eng = _engine(cfg, params)
    try:
        out = eng.generate([1, 2, 3, 4, 5],
                           SamplingParams(max_tokens=4, temperature=0.0))
        assert out == _naive_greedy(params, cfg, [1, 2, 3, 4, 5], 4)
        assert eng.stats()["prefill_chunks_run"] == 0
    finally:
        eng.shutdown()


def test_decode_interleaves_with_long_prefill(tiny_model):
    """A short running request keeps emitting tokens WHILE a long prompt
    is admitted chunk by chunk — the stall chunked prefill exists to
    avoid."""
    cfg, params = tiny_model
    eng = _engine(cfg, params)
    try:
        short = eng.submit([7, 8, 9],
                           SamplingParams(max_tokens=40, temperature=0.0))
        # let it start decoding
        first = short.out_queue.get(timeout=60)
        rng = np.random.default_rng(1)
        long_prompt = [int(x) for x in rng.integers(1, 100, size=60)]
        long_req = eng.submit(long_prompt,
                              SamplingParams(max_tokens=4, temperature=0.0))
        # drain both: the long request finishing proves chunked admission
        # completed while the short one was mid-stream
        long_out = list(_iter_request(long_req))
        rest = list(_iter_request(short))
        assert long_out == _naive_greedy(params, cfg, long_prompt, 4)
        assert [first] + rest == _naive_greedy(params, cfg, [7, 8, 9], 40)
    finally:
        eng.shutdown()


def test_chunked_plus_prefix_cache(tiny_model):
    """Chunked prefill composes with prefix caching: the cached prefix is
    skipped and only the suffix streams in chunks; outputs stay exact."""
    cfg, params = tiny_model
    eng = _engine(cfg, params, enable_prefix_cache=True)
    try:
        rng = np.random.default_rng(2)
        prefix = [int(x) for x in rng.integers(1, 100, size=40)]  # 5 blocks
        for tail_n in (25, 30):
            prompt = prefix + [int(x) for x in
                               rng.integers(1, 100, size=tail_n)]
            got = eng.generate(prompt, SamplingParams(max_tokens=5,
                                                      temperature=0.0))
            assert got == _naive_greedy(params, cfg, prompt, 5), tail_n
        st = eng.stats()["prefix_cache"]
        assert st["hits"] >= 1 and st["tokens_reused"] >= 40
    finally:
        eng.shutdown()


def test_validation(tiny_model):
    cfg, params = tiny_model
    with pytest.raises(ValueError, match="prefill_chunk"):
        TPUEngine(cfg, params, page_size=8,
                  prefill_chunk=12)  # not a power of two
