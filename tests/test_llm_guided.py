"""Guided (constrained) decoding: FSM token masks in the batched engine.

(reference: ray.llm guided_decoding passthrough to vLLM structured output
— vllm_engine_stage.py:278 builds GuidedDecodingParams from
choice/regex/json specs. This engine owns its decode loop, so the
constraint is a token-id FSM whose masks bias logits per slot per step;
see ray_tpu/llm/guided.py. Correctness bar: constrained outputs are
ALWAYS admitted by the FSM, and an all-permissive FSM is bit-identical
to unconstrained decoding.)
"""

import numpy as np
import pytest

from ray_tpu.llm.engine import SamplingParams, TPUEngine
from ray_tpu.llm.guided import GuidedFSM, bias_row
from ray_tpu.models import llama_config, transformer

VOCAB = 64
EOS = 1


def _engine(**kw):
    import jax
    import jax.numpy as jnp

    cfg = llama_config("tiny", vocab_size=VOCAB, max_seq_len=256,
                       d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
                       d_ff=128, dtype=jnp.float32)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    return TPUEngine(cfg, params, max_slots=4, max_len=256, **kw)


PROMPT = [5, 9, 17, 33, 2, 7]


def test_choices_constraint_exact():
    choices = [[10, 11, 12], [10, 20], [30, 31, 32, 33]]
    fsm = GuidedFSM.from_choices(choices, VOCAB, EOS)
    eng = _engine()
    try:
        for seed_tok in (3, 4, 6, 8):
            out = eng.generate(
                PROMPT + [seed_tok],
                SamplingParams(max_tokens=8, temperature=0.0,
                               stop_token_ids=(EOS,), guided=fsm))
            # the emitted sequence (sans eos) must be exactly one choice
            body = [t for t in out if t != EOS]
            assert body in choices, (seed_tok, out)
    finally:
        eng.shutdown()


def test_permissive_fsm_matches_unconstrained():
    eng = _engine()
    try:
        base = eng.generate(PROMPT, SamplingParams(max_tokens=10))
        allow_all = GuidedFSM(
            masks=np.ones((1, VOCAB), bool),
            trans=np.zeros((1, VOCAB), np.int32))
        guided = eng.generate(PROMPT, SamplingParams(max_tokens=10,
                                                     guided=allow_all))
        assert guided == base
    finally:
        eng.shutdown()


def test_token_sets_template():
    digits = list(range(40, 50))
    fsm = GuidedFSM.from_token_sets([digits, digits, [55]], VOCAB, EOS)
    eng = _engine()
    try:
        out = eng.generate(PROMPT, SamplingParams(
            max_tokens=8, stop_token_ids=(EOS,), guided=fsm))
        body = [t for t in out if t != EOS]
        assert len(body) == 3
        assert body[0] in digits and body[1] in digits and body[2] == 55
    finally:
        eng.shutdown()


def test_mixed_guided_and_free_batch():
    fsm = GuidedFSM.from_choices([[10, 11], [20, 21]], VOCAB, EOS)
    eng = _engine()
    try:
        free = eng.submit(PROMPT, SamplingParams(max_tokens=6))
        g = eng.submit(PROMPT + [8], SamplingParams(
            max_tokens=6, stop_token_ids=(EOS,), guided=fsm))
        free_toks = list(free)
        g_body = [t for t in g if t != EOS]
        assert g_body in ([10, 11], [20, 21])
        assert len(free_toks) == 6  # unguided row unaffected by the bias
    finally:
        eng.shutdown()


def test_guided_with_sampling_temperature():
    # even at high temperature every sampled token obeys the mask
    fsm = GuidedFSM.from_choices([[10, 11, 12], [20, 21]], VOCAB, EOS)
    eng = _engine()
    try:
        for _ in range(3):
            out = eng.generate(PROMPT, SamplingParams(
                max_tokens=8, temperature=1.5, top_k=0,
                stop_token_ids=(EOS,), guided=fsm))
            body = [t for t in out if t != EOS]
            assert body in ([10, 11, 12], [20, 21]), out
    finally:
        eng.shutdown()


def test_guided_rejects_bad_configs():
    eng = _engine()
    try:
        small = GuidedFSM.from_choices([[1]], 8, 2)
        with pytest.raises(ValueError, match="vocab"):
            eng.submit(PROMPT, SamplingParams(guided=small))
    finally:
        eng.shutdown()


def test_fsm_builders():
    fsm = GuidedFSM.from_choices([[3, 4], [3, 5]], 16, 0)
    # root allows only 3; after 3, allows 4 or 5; after either, only eos
    assert set(np.nonzero(fsm.masks[fsm.start])[0]) == {3}
    s1 = fsm.step(fsm.start, 3)
    assert set(np.nonzero(fsm.masks[s1])[0]) == {4, 5}
    s2 = fsm.step(s1, 4)
    assert set(np.nonzero(fsm.masks[s2])[0]) == {0}
    # bias row: allowed 0.0, else very negative
    b = bias_row(fsm, fsm.start)
    assert b[3] == 0.0 and b[4] < -1e8

    with pytest.raises(ValueError, match="empty"):
        GuidedFSM.from_choices([[]], 16, 0)
    with pytest.raises(ValueError, match="vocab"):
        GuidedFSM.from_choices([[99]], 16, 0)


def test_server_guided_choice_end_to_end():
    """OpenAI-surface guided_choice (reference: guided_decoding params on
    the serve path): the completion text is exactly one of the choices."""
    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import LLMConfig, ModelLoadingConfig, build_openai_app

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8, num_workers=2, max_workers=8)
    try:
        cfg = LLMConfig(
            model_loading_config=ModelLoadingConfig(model_id="tiny",
                                                    tokenizer="byte"),
            model_family="llama", accelerator_type=None,
            model_kwargs=dict(vocab_size=300, max_seq_len=128, d_model=64,
                              n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
                              dtype=jnp.float32, remat=False),
            engine_kwargs={"max_slots": 4, "max_len": 128, "min_bucket": 16},
        )
        handle = serve.run(build_openai_app(cfg), name="llmg",
                           route_prefix="/llmg")
        out = handle.completions.remote(
            {"prompt": "pick:", "max_tokens": 16,
             "guided_choice": ["yes", "no"]}).result(timeout_s=120)
        assert out["choices"][0]["text"] in ("yes", "no"), out
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_regex_fsm_constrains_engine():
    import re

    # yes|no followed by 1+ digits, over the byte-id alphabet (ord == id)
    fsm = GuidedFSM.from_regex("(ok|no)[0-9]+", 300, EOS_BYTE := 258)
    cfg_vocab = 300
    import jax
    import jax.numpy as jnp

    cfg = llama_config("tiny", vocab_size=cfg_vocab, max_seq_len=256,
                       d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
                       d_ff=128, dtype=jnp.float32)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    eng = TPUEngine(cfg, params, max_slots=2, max_len=256)
    try:
        for seed in (3, 5, 11):
            out = eng.generate([seed, 7, 19], SamplingParams(
                max_tokens=10, stop_token_ids=(EOS_BYTE,), guided=fsm))
            text = "".join(chr(t) for t in out if t != EOS_BYTE)
            assert re.fullmatch(r"(ok|no)[0-9]+", text), (seed, text)
    finally:
        eng.shutdown()


def test_regex_builder_semantics():
    f = GuidedFSM.from_regex("a[bc]?d*", 300, 258)
    s = f.start
    assert f.masks[s, ord("a")] and not f.masks[s, ord("b")]
    s1 = f.step(s, ord("a"))
    # after 'a': accepting (eos), or b/c, or d
    assert f.masks[s1, 258] and f.masks[s1, ord("b")] and f.masks[s1, ord("d")]
    s2 = f.step(s1, ord("c"))
    assert f.masks[s2, 258] and f.masks[s2, ord("d")] and not f.masks[s2, ord("b")]
    s3 = f.step(s2, ord("d"))
    assert f.masks[s3, ord("d")] and f.masks[s3, 258]

    # negated class + dot + plus
    g = GuidedFSM.from_regex("[^x]y+", 300, 258)
    assert not g.masks[g.start, ord("x")] and g.masks[g.start, ord("q")]

    with pytest.raises(ValueError, match="unbalanced|unexpected"):
        GuidedFSM.from_regex("(ab", 300, 258)
    with pytest.raises(ValueError, match="unterminated"):
        GuidedFSM.from_regex("[ab", 300, 258)
    with pytest.raises(ValueError, match="empty"):
        GuidedFSM.from_regex("", 300, 258)


def test_server_guided_regex_end_to_end():
    import re

    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import LLMConfig, ModelLoadingConfig, build_openai_app

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8, num_workers=2, max_workers=8)
    try:
        cfg = LLMConfig(
            model_loading_config=ModelLoadingConfig(model_id="tiny",
                                                    tokenizer="byte"),
            model_family="llama", accelerator_type=None,
            model_kwargs=dict(vocab_size=300, max_seq_len=128, d_model=64,
                              n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
                              dtype=jnp.float32, remat=False),
            engine_kwargs={"max_slots": 4, "max_len": 128, "min_bucket": 16},
        )
        handle = serve.run(build_openai_app(cfg), name="llmr",
                           route_prefix="/llmr")
        out = handle.completions.remote(
            {"prompt": "id:", "max_tokens": 12,
             "guided_regex": "[A-Z][a-z]+-[0-9][0-9]"}).result(timeout_s=120)
        text = out["choices"][0]["text"]
        assert re.fullmatch(r"[A-Z][a-z]+-[0-9][0-9]", text), out
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_budget_aware_closing_completes_unbounded_patterns():
    """An unbounded `+` must not overrun max_tokens mid-pattern: the FSM's
    distance-to-accept switches decoding to budget-decreasing tokens."""
    import re

    import jax
    import jax.numpy as jnp

    fsm = GuidedFSM.from_regex("[a-z]+-[0-9]+", 300, 258)
    # closing tables: accepting states stop NOW; others step strictly closer
    assert fsm.dist[fsm.start] >= 3  # needs letter, dash, digit minimum
    cfg = llama_config("tiny", vocab_size=300, max_seq_len=256,
                       d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
                       d_ff=128, dtype=jnp.float32)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    eng = TPUEngine(cfg, params, max_slots=2, max_len=256)
    try:
        for budget in (4, 5, 8):
            out = eng.generate([9, 3, 17], SamplingParams(
                max_tokens=budget, stop_token_ids=(258,), guided=fsm))
            text = "".join(chr(t) for t in out if t != 258)
            assert re.fullmatch(r"[a-z]+-[0-9]+", text), (budget, text)
            assert len(out) <= budget
    finally:
        eng.shutdown()


def test_regex_parser_clean_errors():
    for bad in ("a|", "(", "ab(", "a|*"):
        with pytest.raises(ValueError):
            GuidedFSM.from_regex(bad, 300, 258)


def test_budget_feasibility_masks_long_branches():
    """'a|bcdef' at budget 3: entering the 'b' branch is infeasible (needs
    5 more tokens) and must be masked BEFORE the model steps into it."""
    import re

    import jax
    import jax.numpy as jnp

    fsm = GuidedFSM.from_regex("a|bcdef", 300, 258)
    row = bias_row(fsm, fsm.start, remaining=3)
    assert row[ord("a")] == 0.0
    assert row[ord("b")] < -1e8  # infeasible branch pre-masked
    # with enough budget both branches open
    row = bias_row(fsm, fsm.start, remaining=7)
    assert row[ord("a")] == 0.0 and row[ord("b")] == 0.0

    cfg = llama_config("tiny", vocab_size=300, max_seq_len=128,
                       d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
                       d_ff=128, dtype=jnp.float32)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    eng = TPUEngine(cfg, params, max_slots=2, max_len=128)
    try:
        for seed in (2, 9, 30):
            out = eng.generate([seed, 4], SamplingParams(
                max_tokens=3, stop_token_ids=(258,), guided=fsm))
            text = "".join(chr(t) for t in out if t != 258)
            assert re.fullmatch(r"a|bcdef", text), (seed, text)
    finally:
        eng.shutdown()


def test_regex_escapes_and_class_edge_cases():
    # shorthand classes are real classes, not literal letters
    f = GuidedFSM.from_regex(r"\d+", 300, 258)
    assert f.masks[f.start, ord("5")] and not f.masks[f.start, ord("d")]
    f = GuidedFSM.from_regex(r"[\w]", 300, 258)
    assert f.masks[f.start, ord("_")] and f.masks[f.start, ord("Z")]
    # unknown alphanumeric escape raises instead of silently matching 'q'
    with pytest.raises(ValueError, match="unsupported escape"):
        GuidedFSM.from_regex(r"\q", 300, 258)
    # escaped punctuation stays literal
    f = GuidedFSM.from_regex(r"\.\+", 300, 258)
    assert f.masks[f.start, ord(".")] and not f.masks[f.start, ord("x")]
    # empty / inverted-to-empty / backwards classes raise
    with pytest.raises(ValueError, match="empty"):
        GuidedFSM.from_regex("[]", 300, 258)
    with pytest.raises(ValueError, match="empty range"):
        GuidedFSM.from_regex("[z-a]", 300, 258)
    # escaped range bound applies the escape to the bound itself
    f = GuidedFSM.from_regex(r"[\--0]", 300, 258)  # '-' .. '0'
    assert f.masks[f.start, ord("-")] and f.masks[f.start, ord("/")]


def test_regex_dfa_state_cap():
    # (Σ)*aΣ^n subset-construction blowup must be rejected, not compiled
    with pytest.raises(ValueError, match="DFA states"):
        GuidedFSM.from_regex(".*a" + "." * 20, 300, 258)
