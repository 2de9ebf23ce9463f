"""The serve check's tie search is complete inside the freedom it has, and
no wider: cases the search of PR 23 left out settle, a wrong tree and the
control still fail with every tie free, and no flip is taken whose float32
margin is over `router_tie`. On the CPU: a reference made by hand for the
search alone, the tiny Mixtral for the check as a whole."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, check_sweep, harness, program
from chipbench.reference import mixtral

import tiny

N, K, V, L = 8, 4, 96, 2            # prompt tokens, positions, vocabulary, layers
TOL = {"router_tie": 0.2, "max_tie_seconds": 20.0, "logits_rel_tol": 0.15,
       "logits_median_tol": 0.10, "served_gap_tol": 0.2}


class ByHand:
    """A reference by hand: logits = base + one table of rows for every group
    of (layer, token) pairs, looked up by the depths held there; margins 1.0
    but for the ties given, which may depend on a depth held elsewhere."""

    def __init__(self, groups, ties):
        self.base = np.random.default_rng(0).normal(size=(N + K - 1, V))
        self.groups, self.ties = groups, ties

    def __call__(self, depth):
        logits = self.base.copy()
        for pairs, table in self.groups:
            rows = table.get(tuple(int(depth[p]) for p in pairs))
            if rows is not None:
                logits[N - 1:] += rows
        margin = np.ones((L, N + K - 1, 2))
        for where, value, given in self.ties:
            if all(depth[p] == d for p, d in given.items()):
                margin[where] = value
        return logits, margin


def _rows(amplitudes, axis):
    """Position j off by amplitudes[j] along vocabulary axis `axis`."""
    out = np.zeros((K, V))
    out[:, axis] = amplitudes
    return out


def _search(ref, got, tol=TOL, served=None):
    """(what the search found, the comparison). `served` [K]: the tokens a
    served path returned, held to `served_gap_tol` as `serve_check` does."""
    def judged(per_pos, want):
        e, gap = np.asarray(per_pos), np.zeros(K)
        if served is not None:
            gap = (want.max(-1) - want[np.arange(K), served]) / want.std(-1)
        return np.append(np.maximum(e / tol["logits_rel_tol"], gap / tol["served_gap_tol"]),
                         np.median(e) / tol["logits_median_tol"])

    def settled(per_pos, want):
        return judged(per_pos, want).max() <= 1.0

    return check.tie_search(ref, got, N, L, tol, judged), settled


def _flipped(ref, *pairs):
    """What a program reads that routed `pairs` (layer, token, depth) so."""
    depth = np.zeros((L, N + K - 1), np.int8)
    for l, t, d in pairs:
        depth[l, t] = d
    return ref(depth)[0][N - 1:]


def _search_of_pr23(ref, got, settled, tol=TOL):
    """The search as it stood (chipbench/check.py of PR 23): single flips to
    the first expert left out, each tried once, kept on the sum."""
    errors = lambda w: [check._rel(g, x) for g, x in zip(got, w[N - 1:])]  # noqa: E731
    swaps = np.zeros((L, N + K - 1), np.int8)
    want, margin = ref(swaps)
    per_pos, margin, tried = errors(want), margin[..., 0], set()
    while not settled(per_pos, want):
        ties = [(float(margin[l, t]), int(l), int(t))
                for l, t in zip(*np.nonzero(margin < tol["router_tie"]))
                if (int(l), int(t)) not in tried]
        if not ties:
            break
        _, l, t = min(ties)
        tried.add((l, t))
        trial = swaps.copy()
        trial[l, t] = 1
        w, mg = ref(trial)
        if sum(errors(w)) < sum(per_pos):
            swaps, want, margin, per_pos = trial, w, mg[..., 0], errors(w)
    return settled(per_pos, want)


OWN = N - 1 + 2                      # the own token of position 2


def _two_layers_one_token():
    """The program flipped token OWN in BOTH layers; one flip alone reads
    worse than none, and layer 1's tie shows only once layer 0 is flipped."""
    a = np.array([0, 0, 6.0, 0])
    table = {(0, 0): _rows(a, 0), (1, 0): _rows(1.3 * a, 1), (0, 1): _rows(1.3 * a, 2)}
    ref = ByHand([([(0, OWN), (1, OWN)], table)],
                 [((0, OWN, 0), 0.03, {}), ((1, OWN, 0), 0.05, {(0, OWN): 1})])
    return ref, _flipped(ref, (0, OWN, 1), (1, OWN, 1))


def _second_other_side():
    """The program took the SECOND expert left out at token OWN of layer 1."""
    a = np.array([0, 0, 6.0, 0])
    table = {(0,): _rows(a, 0), (1,): _rows(1.3 * a, 1)}
    ref = ByHand([([(1, OWN)], table)],
                 [((1, OWN, 0), 0.02, {}), ((1, OWN, 1), 0.06, {})])
    return ref, _flipped(ref, (1, OWN, 2))


def _refused_then_needed():
    """Two prompt tokens flipped. The one of smaller margin alone reads worse
    than none, so it is refused first; once the other is taken it helps."""
    a = np.full(K, 3.0)
    table = {(0, 0): _rows(a, 0), (1, 0): _rows(1.3 * a, 1), (0, 1): _rows(0.6 * a, 2)}
    ref = ByHand([([(0, 2), (0, 5)], table)],
                 [((0, 2, 0), 0.01, {}), ((0, 5, 0), 0.04, {})])
    return ref, _flipped(ref, (0, 2, 1), (0, 5, 1))


def _sum_hides_the_maximum():
    """One flip at a prompt token brings the one position that is out in,
    and lifts the three others by more than it brought that one down."""
    table = {(0,): _rows([0, 0, 3.0, 0], 0), (1,): _rows([1.2, 1.2, 0, 1.2], 1)}
    ref = ByHand([([(1, 3)], table)], [((1, 3, 0), 0.05, {})])
    return ref, ref.base[N - 1:]


def test_the_search_works_on_the_served_token_where_that_is_what_is_out():
    """Every position within its tolerance, but as routed the reference
    holds another token 0.3 standard deviations over the served one at
    position 1. The flip that takes that away lifts three other positions by
    more than it brings position 1 down: refused on the sum, kept on the rule."""
    base = ByHand([], []).base[N - 1:]
    served = base.argmax(-1)
    other = int(np.argsort(base[1])[-2])
    lift = np.zeros((K, V))
    lift[1, other] = base[1, served[1]] - base[1, other] + 0.3 * base[1].std()
    ref = ByHand([([(0, 4)], {(0,): lift, (1,): _rows([0.5, 0, 0.5, 0.5], 3)})],
                 [((0, 4, 0), 0.07, {})])
    found, settled = _search(ref, base, served=served)
    before = found["before_ties"]["logits_rel_err"]
    assert max(before) < TOL["logits_rel_tol"] and not settled(before, ref(np.zeros((L, 11)))[0][N - 1:])
    assert settled(found["per_pos"], found["want"])
    taken, = found["ties_taken"]
    assert taken["sum"][1] > taken["sum"][0] and taken["out"][0] > 1.0 >= taken["out"][1]
    assert taken["position"] == 1 and (taken["layer"], taken["token"]) == (0, 4)


@pytest.mark.parametrize("case", [_two_layers_one_token, _second_other_side,
                                  _refused_then_needed, _sum_hides_the_maximum])
def test_the_search_settles_what_the_search_of_pr23_left_out(case):
    ref, got = case()
    # the sum may rise where the maximum comes in: the last case ends at 12 %
    tol = {**TOL, "logits_median_tol": 0.15}
    found, settled = _search(ref, got, tol)
    assert max(found["before_ties"]["logits_rel_err"]) > tol["logits_rel_tol"]
    assert settled(found["per_pos"], found["want"]), found
    assert all(f["margin"] < TOL["router_tie"] for f in found["ties_taken"])
    assert not _search_of_pr23(ref, got, settled, tol)
    if case is _two_layers_one_token:
        assert [(f["layer"], f["depth"], f["joint"]) for f in found["ties_taken"]] == [
            (0, 1, 2), (1, 1, 2)]
        assert max(found["per_pos"]) == 0.0
    if case is _second_other_side:
        assert [(f["layer"], f["depth"]) for f in found["ties_taken"]] == [(1, 2)]
    if case is _refused_then_needed:
        assert found["ties_reopened"] == 1 and found["ties_taken"][-1]["reopened"]
        assert max(found["per_pos"]) == 0.0
    if case is _sum_hides_the_maximum:
        taken, = found["ties_taken"]
        assert taken["sum"][1] > taken["sum"][0] and taken["err"][1] < taken["err"][0]


@pytest.mark.parametrize("case", [_two_layers_one_token, _second_other_side,
                                  _refused_then_needed])
def test_no_flip_is_taken_over_router_tie(case):
    """With `router_tie` under the margin of a flip the program made, the
    reference does not follow, and the comparison stays failed."""
    found, settled = _search(*case(), {**TOL, "router_tie": 0.035})
    assert not settled(found["per_pos"], found["want"]) and not found["ties_taken"]
    assert found["ties_tried"] + found["joint_trials"] >= 1


def test_a_run_that_settles_at_once_is_not_searched():
    ref, _ = _second_other_side()
    found, _ = _search(ref, _flipped(ref))
    assert found["ties_tried"] == found["joint_trials"] == 0 and not found["ties_taken"]
    assert max(found["per_pos"]) == 0.0


def test_the_search_ends_on_its_time_limit():
    found, settled = _search(*_refused_then_needed(), {**TOL, "max_tie_seconds": 0.0})
    assert not settled(found["per_pos"], found["want"]) and not found["ties_taken"]


# ------------------------------------------- the tiny Mixtral, as a whole


@pytest.fixture(scope="module")
def conf():
    c = tiny.mixtral_cell()["config_file"]
    c["check"]["max_tie_seconds"] = 4.0
    return c


@pytest.fixture(scope="module")
def weights(conf):
    return program.init_params(program.transformer_config(conf["program"]), 2**31 + 9)


@functools.partial(jax.jit, static_argnames=("top_k", "eps"))
def _route_of_pr23(layers, i, x, swap, *, top_k, eps):
    """chipbench/reference/mixtral.py `_route` as PR 23 had it."""
    h = mixtral._rms_norm(x, mixtral._at(layers["norm2"]["w"], i), eps)
    logits = h @ mixtral._at(layers["mlp"]["router"], i).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, top_k + 1)
    last = jnp.where(swap, top_k, top_k - 1)[:, None]
    top = jnp.concatenate([top[:, :top_k - 1],
                           jnp.take_along_axis(top, last, axis=1)], axis=1)
    idx = jnp.concatenate([idx[:, :top_k - 1],
                           jnp.take_along_axis(idx, last, axis=1)], axis=1)
    top = top / top.sum(-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], idx].set(top)
    ranked = jnp.sort(logits, axis=-1)
    return h, gates, ranked[:, -top_k] - ranked[:, -top_k - 1]


@pytest.mark.parametrize("depth", [0, 1, 2])
def test_route_by_depth(weights, depth):
    """Depth 0 is plain top-k, depth 1 the `swap` of PR 23 to every bit, and
    depth 2 takes the second expert left out; the margins are the router
    logits' gaps to the two experts left out first."""
    x = jax.random.normal(jax.random.PRNGKey(3), (40, 64), jnp.float32)
    d = jnp.full((40,), depth, jnp.int32)
    h, gates, margin = mixtral._route(weights["layers"], 1, x, d, top_k=2, eps=1e-6)
    logits = np.asarray(h @ weights["layers"]["mlp"]["router"][1].astype(jnp.float32))
    order = np.argsort(-logits, axis=-1)
    taken = np.stack([order[:, 0], order[:, 1 + depth]], axis=1)
    assert (np.sort(np.argsort(-np.asarray(gates), axis=-1)[:, :2], axis=1)
            == np.sort(taken, axis=1)).all()
    assert np.allclose(np.asarray(gates).sum(-1), 1.0, atol=1e-6)
    assert (np.count_nonzero(np.asarray(gates), axis=-1) == 2).all()
    ranked = -np.sort(-logits, axis=-1)
    assert np.allclose(margin, ranked[:, 1:2] - ranked[:, 2:4], atol=1e-6)
    if depth < 2:
        was = _route_of_pr23(weights["layers"], 1, x, d.astype(bool), top_k=2, eps=1e-6)
        assert all((np.asarray(a) == np.asarray(b)).all()
                   for a, b in zip((h, gates, margin[:, 0]), was))


def test_a_token_routed_the_other_way_in_two_layers_settles(conf, weights):
    """The program is the reference itself with one position's own token
    routed the other way in both layers: only the joint search follows."""
    sizes, tol = conf["sizes"], conf["check"]
    tokens = jnp.asarray(np.random.default_rng(5).integers(0, 300, 29), jnp.int32)
    n, k = 24, 6
    own = n - 1 + 3
    target = np.zeros((2, 29), np.int8)
    target[0, own] = 1
    m0 = float(mixtral.forward(weights, tokens, sizes)[1][0, own, 0])
    m1 = float(mixtral.forward(weights, tokens, sizes, target)[1][1, own, 0])
    target[1, own] = 1
    got = np.asarray(mixtral.forward(weights, tokens, sizes, target)[0])[n - 1:]
    tol = {**tol, "router_tie": 1.05 * max(m0, m1)}

    def judged(per_pos, want):
        return np.asarray(per_pos) / tol["logits_rel_tol"]

    found = check.tie_search(lambda d: mixtral.forward(weights, tokens, sizes, d),
                             got, n, 2, tol, judged)
    assert found["before_ties"]["logits_rel_err"][3] > 0.01
    assert max(found["per_pos"]) < 1e-6
    assert {(f["layer"], f["token"], f["depth"]) for f in found["ties_taken"]} >= {
        (0, own, 1), (1, own, 1)}
    assert all(f["margin"] < tol["router_tie"] for f in found["ties_taken"])


def test_sound_tree_passes_and_the_control_fails(conf):
    row, = check_sweep.sweep(conf, [2**31 + 9], on_chip=False)
    assert row["ok"] and row["control_fails"] and row["positions"] == 6
    assert max(row["logits_rel_err"]) < 1e-5 < conf["check"]["logits_rel_tol"]
    assert min(row["control"]) > 10 * conf["check"]["logits_rel_tol"]
    assert row["ties_tried"] == row["joint_trials"] == 0
    assert check_sweep.summary([row], None)["not_ok"] == []


@pytest.mark.parametrize("fault", check.FAULTS)
def test_a_wrong_tree_fails_with_every_tie_free(conf, fault):
    """`router_tie` so wide that every (layer, token) may go either way:
    what the search can reach does not hide a wrong tree."""
    free = {**conf, "check": {**conf["check"], "router_tie": 1e9}}
    row, = check_sweep.sweep(free, [12], fault, on_chip=False)
    assert not row["ok"] and row["ties_tried"] + row["joint_trials"] > 20
    assert max(row["logits_rel_err"]) > conf["check"]["logits_rel_tol"]
    assert row["reference_s"] < conf["check"]["max_tie_seconds"] + 2.0
    assert check_sweep.summary([row], fault)["not_ok"] == [12]


def test_an_answer_cut_short_by_eos_is_compared_where_it_is(conf):
    prompt = check_sweep.sample_prompt(conf, 12)
    whole = check.serve_check(conf, 12, prompt, None, on_chip=False)
    assert whole["ok"] and whole["positions"] == len(whole["served_ids"]) == 6
    cut = check.serve_check(conf, 12, prompt, whole["served_ids"][:2], on_chip=False)
    assert cut["ok"] and cut["positions"] == 2
    assert max(cut["logits_rel_err"]) < 1e-5
    with pytest.raises(harness.BenchError):
        check.serve_check(conf, 12, prompt, [], on_chip=False)
    with pytest.raises(harness.BenchError):
        check.serve_check(conf, 12, prompt, None, on_chip=False, fault="no_such")
