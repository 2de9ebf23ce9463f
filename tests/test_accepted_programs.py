"""The programs the accepted configurations compile are the parent's: the
jaxprs of the unchunked prefill, a chunk's continuation and the decode step
of every served family of the benchmark, at its tiny size, digested on the
tree BEFORE PR 50 touched `ops/ssm.py`, `models/transformer.py`,
`models/decoding.py`, `models/decoding_paged.py` and `llm/engine.py`
(commit ec3b9ad) and compared here on every tree after it. PR 49 was refused
for one number, granite's `setup_s`, after a change to this shared code; a
program that moved shows here before a chip is asked.

A change that moves one of these programs ON PURPOSE records the new digest
here and says so in CHANGES.md. To print a tree's digests:
`python tests/test_accepted_programs.py`.
"""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_tpu import models  # noqa: E402
from ray_tpu.models import decoding, transformer  # noqa: E402
from ray_tpu.models import decoding_paged as dp  # noqa: E402

VOCAB, PAGE, MAX_LEN, SLOTS, PAGES = 300, 16, 256, 2, 24
BUCKET, SPAN = 32, 64

FAMILIES = {
    "granite": {},
    "trinity": dict(experts_held=8, first_expert=16, select_bias_init_std=0.02),
    "mellum": {},
    "kimi_vl": {},
    "ouro": {},
    "mixtral": {},
}

# (characters of the jaxpr's text, the first 16 of its sha256), from the parent
PARENT = {
    ("granite", "prefill"): (67326, "9f7ad2f22f53e40a"),
    ("granite", "prefill_with_prefix"): (69623, "da8e8fa7b80303fe"),
    ("granite", "decode_step"): (83958, "fd0a825741dccf0c"),
    ("trinity", "prefill"): (93751, "6075ae01b3ae319a"),
    ("trinity", "prefill_with_prefix"): (105114, "40cb117a2ab48a09"),
    ("trinity", "decode_step"): (224281, "f7cd101adc00fdd8"),
    ("mellum", "prefill"): (56122, "39ecffce03e53ba9"),
    ("mellum", "prefill_with_prefix"): (66740, "1add3bd801c98c07"),
    ("mellum", "decode_step"): (96842, "86e4df2d0925ba4c"),
    ("kimi_vl", "prefill"): (43704, "6f248329a792c8d4"),
    ("kimi_vl", "prefill_with_prefix"): (49710, "9c067e1cc76c4961"),
    ("kimi_vl", "decode_step"): (66036, "1a3d449d2f541189"),
    ("ouro", "prefill"): (17788, "63db8a180b41fcf0"),
    ("ouro", "prefill_with_prefix"): (22112, "93e6203a56b5a35d"),
    ("ouro", "decode_step"): (41885, "fd51fd93fd274690"),
    ("mixtral", "prefill"): (24674, "26619a4f3be029e4"),
    ("mixtral", "prefill_with_prefix"): (27490, "56de4f148a369fb9"),
    ("mixtral", "decode_step"): (43339, "de0c0ca650a05a43"),
}


def _cfg(family):
    return getattr(models, family + "_config")(
        "tiny", vocab_size=VOCAB, max_seq_len=512, dtype=jnp.float32, **FAMILIES[family])


def _abstract(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def _programs(cfg) -> dict:
    """name -> (function of abstract arguments, the arguments)."""
    sds = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: transformer.init(jax.random.PRNGKey(0), cfg))
    tokens, i32 = sds((1, BUCKET), jnp.int32), sds((), jnp.int32)
    kv = jax.eval_shape(lambda p, t, n: decoding.prefill(p, t, n, cfg)[1],
                        params, tokens, i32)
    state = jax.eval_shape(
        lambda: dp.init_paged_state(cfg, SLOTS, MAX_LEN, PAGES, PAGE))

    def span(rows):  # a prefill's [L, T, ...] as a gathered prefix of SPAN
        return sds((rows.shape[0], SPAN) + state["kp"].shape[3:], rows.dtype)

    extra, n_full = {}, state["kp"].shape[0]
    k_pre = span(kv["k"])
    k_pre = sds((n_full,) + k_pre.shape[1:], k_pre.dtype)
    v_pre = None if cfg.mla else k_pre
    if cfg.window:
        win = sds((state["wkp"].shape[0], cfg.window) + state["wkp"].shape[3:], cfg.dtype)
        extra = dict(window_k=win, window_v=win)
    if cfg.ssm:
        extra = dict(row_state={"ssm": kv["ssm"], "conv": kv["conv"]})
    return {
        "prefill": (lambda p, t, n: decoding.prefill(p, t, n, cfg), (params, tokens, i32)),
        "prefill_with_prefix": (
            lambda p, t, k, v, done, n, more: dp.prefill_with_prefix(
                p, t, k, v, done, n, cfg, **more),
            (params, tokens, k_pre, v_pre, i32, i32, extra)),
        "decode_step": (
            lambda p, s: dp.decode_step_paged_ragged(p, s, cfg, 4, False),
            (params, state)),
    }


def digest(family: str, program: str) -> tuple:
    fn, args = _programs(_cfg(family))[program]
    text = str(jax.make_jaxpr(fn)(*args))
    return len(text), hashlib.sha256(text.encode()).hexdigest()[:16]


CASES = [(f, p) for f in FAMILIES for p in ("prefill", "prefill_with_prefix", "decode_step")]


@pytest.mark.parametrize("family,program", CASES)
def test_the_accepted_program_is_the_parents(family, program):
    assert digest(family, program) == PARENT[family, program]


if __name__ == "__main__":
    for case in CASES:
        print(f"    {case!r}: {digest(*case)!r},")
