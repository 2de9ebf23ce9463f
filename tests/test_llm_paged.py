"""Paged KV cache + engine upgrades: correctness vs the full forward,
page-pool pressure/backlog, and tensor-parallel multi-chip serving.

(reference capability: vLLM paged attention + tensor_parallel_size —
llm/_internal/serve/engines/vllm/vllm_engine.py:114, vllm_models.py:215 —
re-designed TPU-first: static-shape page pool + jax.sharding TP.)
"""

import threading

import jax
import jax.numpy as jnp
import pytest

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


def test_paged_engine_matches_full_forward(tiny_model):
    from ray_tpu.llm import SamplingParams, TPUEngine

    cfg, params = tiny_model
    eng = TPUEngine(cfg, params, max_slots=4, max_len=64, min_bucket=8,
                    page_size=8)
    try:
        prompt = [1, 5, 9, 2, 7]
        out = eng.generate(prompt, SamplingParams(max_tokens=8, temperature=0.0))
        assert out == _naive_greedy(params, cfg, prompt, 8)
        st = eng.stats()
        assert st["free_pages"] == st["num_pages"] - 1  # all returned (0=scratch)
    finally:
        eng.shutdown()


def _family(name):
    from ray_tpu.models import gpt2_config, kimi_vl_config, mixtral_config
    from ray_tpu.models.transformer import MoEConfig

    if name == "gpt2":      # dense, learned positions, biases, tied head
        return gpt2_config("124m", vocab_size=211, max_seq_len=128, d_model=64,
                           n_layers=2, n_heads=4, d_ff=128, dtype=jnp.float32)
    if name == "mixtral":   # dropless sparse experts, grouped-query attention, rope
        return mixtral_config("tiny", vocab_size=300, max_seq_len=128, d_model=64,
                              n_layers=2, n_heads=4, n_kv_heads=2, d_ff=96,
                              dtype=jnp.float32,
                              moe=MoEConfig(num_experts=8, top_k=2, capacity_factor=4.0))
    return kimi_vl_config("tiny", vocab_size=300, max_seq_len=128, dtype=jnp.float32,
                          n_layers=3, n_dense_layers=1)   # latent cache, a dense layer


@pytest.mark.parametrize("family", ["gpt2", "mixtral", "kimi_vl"])
def test_default_engine_matches_full_forward(family):
    """An engine given nothing but sizes (no option that selects a path)
    decodes what the model's full forward decodes, for each kind of model
    the cells serve, two prompts sharing the steps."""
    from ray_tpu.llm import SamplingParams, TPUEngine

    cfg = _family(family)
    params = transformer.init(jax.random.PRNGKey(1), cfg)
    eng = TPUEngine(cfg, params, max_slots=2, max_len=128)
    try:
        prompts = [[1, 5, 9, 2, 7], [3] * 70]
        reqs = [eng.submit(p, SamplingParams(max_tokens=6)) for p in prompts]
        assert [list(r) for r in reqs] == [_naive_greedy(params, cfg, p, 6)
                                           for p in prompts]
        st = eng.stats()
        assert st["page_size"] == 64 and st["free_pages"] == st["num_pages"] - 1
    finally:
        eng.shutdown()


def test_paged_concurrent_sequences_isolated(tiny_model):
    from ray_tpu.llm import SamplingParams, TPUEngine

    cfg, params = tiny_model
    eng = TPUEngine(cfg, params, max_slots=4, max_len=64, min_bucket=8,
                    page_size=8)
    try:
        prompts = [[1, 5, 9], [3, 3, 8, 2], [7], [2, 4, 6, 8, 10]]
        want = [_naive_greedy(params, cfg, p, 6) for p in prompts]
        got = [None] * len(prompts)

        def run(i):
            got[i] = eng.generate(prompts[i], SamplingParams(max_tokens=6))

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert got == want
    finally:
        eng.shutdown()


def test_paged_pool_pressure_backlogs_then_completes(tiny_model):
    """With a pool too small for all sequences at once, later requests wait
    for pages and still complete correctly (vLLM-style admission control)."""
    from ray_tpu.llm import SamplingParams, TPUEngine

    cfg, params = tiny_model
    # each sequence needs ~3 pages (bucket 8 + 16 generated → pages to pos 24
    # at page 8); pool of 7 usable pages → only 2 sequences fit at once
    eng = TPUEngine(cfg, params, max_slots=4, max_len=64, min_bucket=8,
                    page_size=8, num_pages=8)
    try:
        prompts = [[1, 5, 9], [3, 3, 8, 2], [7, 1], [2, 4, 6]]
        want = [_naive_greedy(params, cfg, p, 16) for p in prompts]
        got = [None] * len(prompts)

        def run(i):
            got[i] = eng.generate(prompts[i], SamplingParams(max_tokens=16))

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert got == want
        assert eng.stats()["free_pages"] == 7
    finally:
        eng.shutdown()


def test_tensor_parallel_engine_matches_single_chip(tiny_model):
    """TP over a 2-device mesh produces identical greedy tokens."""
    from jax.sharding import Mesh

    from ray_tpu.llm import SamplingParams, TPUEngine

    cfg, params = tiny_model
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >=2 devices")
    mesh = Mesh(devs[:2], ("tp",))
    eng = TPUEngine(cfg, params, max_slots=2, max_len=64, min_bucket=8,
                    mesh=mesh)
    try:
        prompt = [1, 5, 9, 2, 7, 4]
        out = eng.generate(prompt, SamplingParams(max_tokens=8, temperature=0.0))
        assert out == _naive_greedy(params, cfg, prompt, 8)
    finally:
        eng.shutdown()


def test_tensor_parallel_paged_engine(tiny_model):
    from jax.sharding import Mesh

    from ray_tpu.llm import SamplingParams, TPUEngine

    cfg, params = tiny_model
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >=2 devices")
    mesh = Mesh(devs[:2], ("tp",))
    eng = TPUEngine(cfg, params, max_slots=2, max_len=64, min_bucket=8,
                    page_size=8, mesh=mesh)
    try:
        prompt = [3, 1, 4, 1, 5]
        out = eng.generate(prompt, SamplingParams(max_tokens=6, temperature=0.0))
        assert out == _naive_greedy(params, cfg, prompt, 6)
    finally:
        eng.shutdown()


def test_paged_infeasible_request_rejected_up_front(tiny_model):
    from ray_tpu.llm import SamplingParams, TPUEngine

    cfg, params = tiny_model
    eng = TPUEngine(cfg, params, max_slots=2, max_len=64, min_bucket=8,
                    page_size=8, num_pages=4)
    try:
        with pytest.raises(ValueError, match="KV pages"):
            eng.submit(list(range(40)), SamplingParams(max_tokens=16))
        # feasible work still runs afterwards (no wedged admission)
        out = eng.generate([1, 2, 3], SamplingParams(max_tokens=4))
        assert len(out) <= 4
    finally:
        eng.shutdown()


def test_paged_backlog_revived_after_idle(tiny_model):
    """A request backlogged under page pressure must be admitted once pages
    free, even if the engine went fully idle in between."""
    from ray_tpu.llm import SamplingParams, TPUEngine

    cfg, params = tiny_model
    eng = TPUEngine(cfg, params, max_slots=2, max_len=64, min_bucket=8,
                    page_size=8, num_pages=7)
    try:
        # first request takes most pages; second must wait, then complete
        a = eng.submit(list(range(20)), SamplingParams(max_tokens=20))
        b = eng.submit(list(range(18)), SamplingParams(max_tokens=8))
        out_a = list(__import__("ray_tpu.llm.engine", fromlist=["_iter_request"])._iter_request(a))
        out_b = list(__import__("ray_tpu.llm.engine", fromlist=["_iter_request"])._iter_request(b))
        assert len(out_a) <= 20 and len(out_b) <= 8
    finally:
        eng.shutdown()


def test_paged_constructor_validation(tiny_model):
    from ray_tpu.llm import TPUEngine

    cfg, params = tiny_model
    with pytest.raises(ValueError, match="power of two"):
        TPUEngine(cfg, params, page_size=0, max_len=64)
    with pytest.raises(ValueError, match="multiple of"):
        TPUEngine(cfg, params, page_size=32, max_len=72)


# heads of 128, whose pages the kernel copies by hand, four a block here; heads
# of 16, whose pages the pipeline brings, one a block
@pytest.mark.parametrize("d_head,block", [(128, 4), (16, 1)])
def test_ragged_block_positions_counts_the_walked_blocks(monkeypatch, d_head, block):
    """`stats()["cache"]["ragged_block_positions"]`: for each live row and
    decode step, the positions held by the blocks of pages that the per-head
    launch walks (ops/ragged_paged_attention.py: the row's live pages rounded
    up to whole blocks), hand-counted here for rows of 1, 65, 254 and 700
    positions, alone and then together; never under `context_tokens`, the
    positions of them that are attended."""
    import ray_tpu.ops.ragged_paged_attention as rpa
    from ray_tpu.llm import SamplingParams, TPUEngine

    P = 64
    cfg = TransformerConfig(**{**TINY, "max_seq_len": 1024, "d_head": d_head})
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    # a budget of two blocks of 4 pages, K and V: float32 pages of 2 heads
    monkeypatch.setattr(rpa, "_BLOCK_VMEM_BYTES", 2 * 4 * (2 * P * 2 * d_head * 4))
    eng = TPUEngine(cfg, params, max_slots=4, max_len=768, page_size=P)
    try:
        attended = walked = 0
        for n in (1, 65, 254, 700):
            eng.generate([1 + i % 100 for i in range(n)],
                         SamplingParams(max_tokens=4, temperature=0.0))
            for pos in range(n, n + 3):        # three decode steps, the row alone
                pages = pos // P + 1           # 1; 2; 4, 4, 5; 11
                bound = 1 << (pages - 1).bit_length()
                blocks = -(-pages // min(block, bound))
                attended += pos + 1
                walked += blocks * min(block, bound) * P
            cache = eng.stats()["cache"]
            assert cache["context_tokens"] == attended
            assert cache["ragged_block_positions"] == walked
        assert walked == {4: 3 * 64 + 3 * 128 + (256 + 256 + 512) + 3 * 768,
                          1: 3 * 64 + 3 * 128 + (256 + 256 + 320) + 3 * 704}[block]
        threads = [threading.Thread(target=eng.generate, args=(
            [1 + i % 100 for i in range(n)], SamplingParams(max_tokens=6, temperature=0.0)))
            for n in (1, 65, 700)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        cache = eng.stats()["cache"]
        assert cache["context_tokens"] == attended + sum(
            n + i + 1 for n in (1, 65, 700) for i in range(5))
        # a row walks whole pages: 5 steps each of at least 1, 2 and 11 pages
        grew = cache["ragged_block_positions"] - walked
        assert grew % P == 0 and grew >= 5 * (1 + 2 + 11) * P
        assert cache["ragged_block_positions"] >= cache["context_tokens"]
    finally:
        eng.shutdown()


def test_latent_cache_reports_no_ragged_blocks():
    """A latent pool decodes through `_latent_kernel`, a page a step: the
    per-head launch's counter is not in its record."""
    from ray_tpu.llm import TPUEngine

    cfg = _family("kimi_vl")
    eng = TPUEngine(cfg, transformer.init(jax.random.PRNGKey(0), cfg),
                    max_slots=2, max_len=64, page_size=8)
    try:
        assert "ragged_block_positions" not in eng.stats()["cache"]
    finally:
        eng.shutdown()
