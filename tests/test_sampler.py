"""The decode sampler does the work the live rows ask for (ISSUE 34): an
argmax when nobody samples, a categorical with no cut when nobody has a
`top_k`, `lax.top_k` at the largest live `top_k`'s bucket otherwise. Every
form that applies to an input returns the tokens the sort-based sampler
returned for it, which is kept here as the reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decoding

ROWS = 8


def _sorted_reference(logits, key, temperatures, top_ks):
    """`sample_per_row` as it was up to PR 32: a descending sort of the whole
    vocabulary for every row, greedy rows included."""
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperatures, 1e-6)[:, None]
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    idx = jnp.clip(top_ks - 1, 0, V - 1)
    kth = jnp.take_along_axis(sorted_desc, idx[:, None], axis=-1)
    kth = jnp.where(top_ks[:, None] > 0, kth, -jnp.inf)
    scaled = jnp.where(scaled < kth, -1e30, scaled)
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temperatures <= 0.0, greedy, sampled)


def _sorted_sample(logits, key, temperature, top_k):
    """`sample` (the first token after a prefill) as it was up to PR 32."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperature, 1e-6)
    if top_k > 0:
        kth = jnp.sort(scaled, axis=-1)[:, -top_k][:, None]
        scaled = jnp.where(scaled < kth, -1e30, scaled)
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


MIXED = (0.0, 0.7, 1.0, 0.0, 1.3, 0.2, 2.0, 0.9)
# name -> (temperatures, top_ks); "V" stands for the vocabulary's size
CASES = {
    "all_greedy": ((0.0,) * ROWS, (0,) * ROWS),
    "all_greedy_stale_top_k": ((0.0,) * ROWS, (5, 0, 64, 0, 1, 0, 0, 9)),
    "mixed_no_top_k": (MIXED, (0,) * ROWS),
    "all_sampling_no_top_k": ((0.8,) * ROWS, (0,) * ROWS),
    "mixed_top_k_1": (MIXED, (1,) * ROWS),
    "mixed_top_k_2": (MIXED, (2,) * ROWS),
    "mixed_top_k_5": (MIXED, (5,) * ROWS),
    "mixed_top_k_64": (MIXED, (64,) * ROWS),      # a bucket's edge
    "mixed_top_k_65": (MIXED, (65,) * ROWS),      # one over it
    "mixed_top_k_V": (MIXED, ("V",) * ROWS),
    "mixed_top_k_over_V": (MIXED, (1 << 20,) * ROWS),
    "mixed_top_k_by_row": (MIXED, (0, 1, 2, 5, 64, 65, "V", 8)),
    "greedy_beside_sampling": ((0.0, 0.8) + (0.0,) * 6, (0, 5) + (0,) * 6),
    "one_sampling_edge_8_9": ((0.0, 0.0, 0.8, 0.8) + (0.0,) * 4,
                              (0, 0, 8, 9, 0, 0, 0, 0)),
}


def _inputs(vocab, case, ties):
    temps, ks = CASES[case]
    temperatures = jnp.asarray(temps, jnp.float32)
    top_ks = jnp.asarray([vocab if k == "V" else k for k in ks], jnp.int32)
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(vocab + len(case)),
                                     (ROWS, vocab), jnp.float32)
    if ties:  # few distinct values, so the k-th largest is shared by many
        logits = jnp.round(logits)
    return logits, temperatures, top_ks


def _forms(vocab, temperatures, top_ks):
    """Every (sampling, k_bucket) that may be chosen for rows that ask for
    this: the engine picks the first that applies, and a fuller one (other
    live rows asking for more) must not move these rows' tokens."""
    temps, ks = np.asarray(temperatures), np.asarray(top_ks)
    samples = temps > 0
    largest = int(ks[samples].max()) if samples.any() else 0
    forms = []
    if not samples.any():
        forms.append((False, 0))
    if largest == 0:
        forms.append((True, 0))
    exact = decoding.top_k_bucket(max(largest, 1), vocab)
    return forms + sorted({(True, exact), (True, min(2 * exact, vocab)),
                           (True, vocab)})


@pytest.mark.parametrize("ties", [False, True], ids=["normal", "ties"])
@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("vocab", [257, 32000])
def test_every_form_that_applies_gives_the_sorted_samplers_tokens(vocab, case, ties):
    logits, temperatures, top_ks = _inputs(vocab, case, ties)
    key = jax.random.PRNGKey(20260928)
    want = np.asarray(_sorted_reference(logits, key, temperatures, top_ks))
    forms = _forms(vocab, temperatures, top_ks)
    for sampling, k_bucket in forms:
        got = decoding.sample_per_row(logits, key, temperatures, top_ks,
                                      sampling, k_bucket)
        np.testing.assert_array_equal(np.asarray(got), want,
                                      err_msg=f"{sampling=} {k_bucket=}")
    if case == "mixed_no_top_k":
        # the sampled rows did sample: a test of argmaxes would pass above
        assert (want != np.asarray(jnp.argmax(logits, -1))).any()


def test_a_stale_top_k_beyond_the_bucket_is_clipped_not_an_error():
    """A released slot keeps its `top_k` on the device; the form follows the
    live rows, so the entry may exceed the bucket. The live rows' tokens are
    the reference's, the dead row's is any token of the vocabulary."""
    logits, temperatures, _ = _inputs(257, "mixed_top_k_5", False)
    top_ks = jnp.asarray([5, 5, 5, 5, 200, 5, 5, 5], jnp.int32)
    key = jax.random.PRNGKey(7)
    want = np.asarray(_sorted_reference(logits, key, temperatures, top_ks))
    got = np.asarray(decoding.sample_per_row(logits, key, temperatures,
                                             top_ks, True, 8))
    live = np.arange(ROWS) != 4
    np.testing.assert_array_equal(got[live], want[live])
    assert 0 <= got[4] < 257


@pytest.mark.parametrize("k,vocab,bucket", [
    (0, 32000, 0), (1, 32000, 1), (2, 32000, 2), (3, 32000, 4), (5, 32000, 8),
    (8, 32000, 8), (9, 32000, 16), (40, 32000, 64), (64, 32000, 64),
    (65, 32000, 128), (128, 32000, 128), (129, 32000, 32000),
    (20000, 32000, 32000), (1 << 20, 32000, 32000),
    (40, 64, 64), (33, 48, 48), (200, 257, 257), (257, 257, 257)])
def test_top_k_bucket(k, vocab, bucket):
    assert decoding.top_k_bucket(k, vocab) == bucket


@pytest.mark.parametrize("temperature", [0.0, 0.8])
@pytest.mark.parametrize("top_k", [0, 1, 5, 64, 257])
def test_first_token_sampler_equals_its_sorted_form(top_k, temperature):
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(top_k), (2, 257))
    key = jax.random.PRNGKey(11)
    want = _sorted_sample(logits, key, temperature, top_k)
    got = decoding.sample(logits, key, temperature, top_k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
