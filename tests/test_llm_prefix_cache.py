"""Hash-block prefix caching over the paged KV pool (round-4, VERDICT 6).

Repeated prompt prefixes skip their share of prefill compute: full
page-size blocks are chain-hashed to pages still resident in HBM, a hit
wires those pages into the new sequence's block table, and only the suffix
runs through a continuation prefill. (reference capability: vLLM automatic
prefix caching + prefix_aware request router.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import SamplingParams, TPUEngine
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
    kw.setdefault("max_len", 64)
    kw.setdefault("min_bucket", 8)
    kw.setdefault("page_size", 8)
    kw.setdefault("enable_prefix_cache", True)
    return TPUEngine(cfg, params, **kw)


def _naive_greedy(params, cfg, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        logits, _ = transformer.forward(params, jnp.asarray([toks]), cfg)
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def test_cache_hit_matches_uncached_logits(tiny_model):
    """The cached-prefix continuation must produce EXACTLY the tokens the
    full prefill produces (greedy): logits-equality via output equality."""
    cfg, params = tiny_model
    eng = _engine(cfg, params)
    try:
        rng = np.random.default_rng(0)
        prefix = [int(x) for x in rng.integers(1, 100, size=24)]  # 3 blocks
        for tail in ([3, 1, 4], [2, 7, 1, 8, 2, 8], [9]):
            prompt = prefix + tail
            expect = _naive_greedy(params, cfg, prompt, 6)
            got = eng.generate(prompt, SamplingParams(max_tokens=6,
                                                      temperature=0.0))
            assert got == expect, (tail, got, expect)
        st = eng.stats()["prefix_cache"]
        assert st["hits"] >= 2  # 2nd and 3rd prompts reused the prefix
        assert st["tokens_reused"] >= 2 * 24
    finally:
        eng.shutdown()


def test_exact_repeat_reuses_all_full_blocks(tiny_model):
    cfg, params = tiny_model
    eng = _engine(cfg, params)
    try:
        prompt = list(range(1, 26))  # 25 tokens: 3 full blocks of 8
        out1 = eng.generate(prompt, SamplingParams(max_tokens=4,
                                                   temperature=0.0))
        out2 = eng.generate(prompt, SamplingParams(max_tokens=4,
                                                   temperature=0.0))
        assert out1 == out2
        st = eng.stats()["prefix_cache"]
        assert st["hits"] == 1 and st["misses"] == 1
        assert st["tokens_reused"] == 24  # 3 blocks × 8
    finally:
        eng.shutdown()


def test_divergent_prefix_no_false_hit(tiny_model):
    """Chain hashing: a changed EARLY block must invalidate later blocks
    even when those later blocks' tokens are identical."""
    cfg, params = tiny_model
    eng = _engine(cfg, params)
    try:
        a = [1] * 8 + [5] * 8 + [9, 9]
        b = [2] * 8 + [5] * 8 + [9, 9]  # same block 1, different block 0
        out_a = eng.generate(a, SamplingParams(max_tokens=4, temperature=0.0))
        out_b = eng.generate(b, SamplingParams(max_tokens=4, temperature=0.0))
        assert out_a == _naive_greedy(params, cfg, a, 4)
        assert out_b == _naive_greedy(params, cfg, b, 4)
        assert eng.stats()["prefix_cache"]["hits"] == 0
    finally:
        eng.shutdown()


def test_cache_eviction_under_page_pressure(tiny_model):
    """A tiny pool: cached zero-ref blocks must be evicted (LRU) so new
    requests still get pages, and everything still decodes correctly."""
    cfg, params = tiny_model
    eng = _engine(cfg, params, num_pages=13)  # tight: 12 usable pages
    try:
        rng = np.random.default_rng(1)
        for trial in range(6):
            prompt = [int(x) for x in rng.integers(1, 100, size=17)]
            out = eng.generate(prompt, SamplingParams(max_tokens=4,
                                                      temperature=0.0))
            assert out == _naive_greedy(params, cfg, prompt, 4), trial
        # invariant: every page is free, cached, or nothing — none leaked
        st = eng.stats()
        assert (st["free_pages"]
                + st["prefix_cache"]["reclaimable_pages"]) == 12
    finally:
        eng.shutdown()


def test_concurrent_mixed_prompts(tiny_model):
    """Cache + continuous batching together: concurrent requests with
    shared and distinct prefixes all match the naive forward."""
    cfg, params = tiny_model
    eng = _engine(cfg, params)
    try:
        shared = list(range(40, 56))  # 2 full blocks
        prompts = [shared + [i, i + 1] for i in range(1, 5)]
        prompts.append([7] * 10)  # unrelated
        reqs = [eng.submit(p, SamplingParams(max_tokens=5, temperature=0.0))
                for p in prompts]
        from ray_tpu.llm.engine import _iter_request

        outs = [list(_iter_request(r)) for r in reqs]
        for p, o in zip(prompts, outs):
            assert o == _naive_greedy(params, cfg, p, 5), p
    finally:
        eng.shutdown()


def test_stats_surface(tiny_model):
    cfg, params = tiny_model
    eng = _engine(cfg, params)
    try:
        eng.generate([1, 2, 3, 4, 5, 6, 7, 8, 9],
                     SamplingParams(max_tokens=2, temperature=0.0))
        st = eng.stats()["prefix_cache"]
        assert set(st) == {"hits", "misses", "hit_rate", "tokens_reused",
                           "cached_blocks", "reclaimable_pages"}
        assert st["cached_blocks"] >= 1  # the first full block registered
    finally:
        eng.shutdown()


def test_matched_blocks_survive_eviction_pressure(tiny_model):
    """Allocation for a cache-hit request may need to evict: the evictor
    must take OTHER zero-ref blocks, never the prefix it just matched
    (pinned-before-alloc regression; an unpinned match here would KeyError
    and kill the scheduler)."""
    cfg, params = tiny_model
    eng = _engine(cfg, params, num_pages=8)  # 7 usable pages: tight
    try:
        rng = np.random.default_rng(7)
        c_prompt = [int(x) for x in rng.integers(1, 100, size=17)]
        a_prompt = [int(x) for x in rng.integers(1, 100, size=25)]
        for p in (c_prompt, a_prompt):
            assert eng.generate(p, SamplingParams(max_tokens=4,
                                                  temperature=0.0)) \
                == _naive_greedy(params, cfg, p, 4)
        # B shares A's 3 full blocks; its private need (3) exceeds the free
        # pool (2), forcing eviction of C's zero-ref blocks while A's
        # matched blocks are pinned
        b_prompt = a_prompt[:24] + [int(x) for x in
                                    rng.integers(1, 100, size=8)]
        out = eng.generate(b_prompt, SamplingParams(max_tokens=8,
                                                    temperature=0.0))
        assert out == _naive_greedy(params, cfg, b_prompt, 8)
        st = eng.stats()["prefix_cache"]
        assert st["hits"] >= 1 and st["tokens_reused"] >= 24
    finally:
        eng.shutdown()
