"""Ragged paged attention: kernel/reference consistency + engine wiring.

The decode step's acceptance contract (ISSUE 15): the Pallas kernel
(interpret mode on CPU) is BIT-consistent with the pure-JAX reference the
CPU engine decodes with, and the ragged step agrees with the model's full
forward over the same tokens.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decoding, decoding_paged as dp, transformer
from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.ops.ragged_paged_attention import (
    ragged_decode_attention, ragged_decode_attention_reference)

pytestmark = pytest.mark.pd

TINY = dict(vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=128, max_seq_len=128, dtype=jnp.float32, remat=False)
PAGE = 16
MAX_LEN = 64


@pytest.fixture(scope="module")
def tiny_model():
    cfg = TransformerConfig(**TINY)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _rand_case(rng, *, B=8, Hkv=2, G=2, Dh=16, P=16, N=33, nb=4):
    q = jnp.asarray(rng.standard_normal((B, Hkv, G, Dh)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((N, P, Hkv, Dh)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((N, P, Hkv, Dh)), jnp.float32)
    tbl = jnp.asarray(rng.integers(1, N, size=(B, nb)), jnp.int32)
    # mixed positions: first page only, page boundaries, mid-page, full
    pos = jnp.asarray([0, 5, P - 1, P, 2 * P - 1, nb * P - 17,
                       nb * P - 1, 10][:B], jnp.int32)
    return q, kp, vp, tbl, pos


# the shapes the cells run (Hkv x G x Dh, and whether the pool packs its heads)
CELL_SHAPES = {"mixtral-8x4x128": (8, 4, 128, False), "mellum2-4x8x128": (4, 8, 128, False),
               "ouro-16x1x128": (16, 1, 128, False),
               # granite's 8 x 4 x 64 as `_pack_queries` hands it over: 4 heads
               # of 128 lanes, the other head's lanes zero
               "granite-packed-4x8x128": (8, 4, 64, True),
               # chip_smoke's Llama-1B, heads of 64 as they are: the pages are
               # not the kernel's to copy by hand, and a block is one page
               "llama-1b-8x4x64": (8, 4, 64, False)}
CELL_PAGE = 64
BLOCKS = 4            # blocks of pages the full-attention table is wide
WINDOW_PAGES = 4


def _row_mix(mix, P, T, nb):
    """Positions of a batch of 8 that a block boundary can get wrong (T: the
    positions of a block, nb: the table's pages; -1: an inactive row)."""
    return {
        # one position, a page's last lane, exactly one block, a block and a
        # page, the first lane of the second block and of the second page
        "edges": [0, P - 1, T - 1, T + P - 1, T, P, 2 * T - 1, 1],
        # the longest row the table allows beside rows of one page
        "longest-beside-short": [nb * P - 1, 3, P - 1, 0, nb * P - 1, 17, P - 2, nb * P - 2],
        "inactive-between-live": [-1, 40, -1, -1, T + 5, -1, nb * P - 1, -1],
    }[mix]


def _cell_case(shape, window, mix, seed=0):
    """bfloat16 pools as a cell stores them, a table whose pages lie
    scattered and out of order in the pool, one of `_row_mix`'s batches."""
    from ray_tpu.ops.ragged_paged_attention import pages_per_block

    Hkv, G, Dh, packed = CELL_SHAPES[shape]
    rng = np.random.default_rng(seed)
    B, P = 8, CELL_PAGE
    q = jnp.asarray(rng.standard_normal((B, Hkv, G, Dh)), jnp.bfloat16)
    if packed:
        q, Hkv, G, Dh = dp._pack_queries(q, Dh), Hkv * Dh // 128, G * 128 // Dh, 128
    W = WINDOW_PAGES * P if window else None
    n = pages_per_block(P, Hkv, Dh, 2, WINDOW_PAGES + 1 if window else 1 << 20, W)
    nb = WINDOW_PAGES + 1 if window else BLOCKS * n
    N = B * nb + 1
    kp = jnp.asarray(rng.standard_normal((N, P, Hkv, Dh)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((N, P, Hkv, Dh)), jnp.bfloat16)
    tbl = jnp.asarray(1 + rng.permutation(N - 1).reshape(B, nb), jnp.int32)
    # a window's rows reach past it: the same mixes, two windows further on
    pos = np.asarray(_row_mix(mix, P, n * P, BLOCKS * n if window else nb))
    return q, kp, vp, tbl, jnp.asarray(pos, jnp.int32), W


def _dense(q, kp, vp, tbl, pos, window=None):
    """One dense masked softmax over the gathered pages, float32; a row at
    pos < 0 attends nothing and reads 0."""
    B, Hkv, G, Dh = q.shape
    P, nb = kp.shape[1], tbl.shape[1]
    k = kp[tbl].reshape(B, nb * P, Hkv, Dh).astype(jnp.float32)
    v = vp[tbl].reshape(B, nb * P, Hkv, Dh).astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", q.astype(jnp.float32), k) * (Dh ** -0.5)
    kpos = jnp.arange(nb * P)[None, :]
    if window is not None:   # column j is logical page pos // P - W // P + j
        kpos = kpos + ((pos // P - window // P) * P)[:, None]
    mask = (kpos >= 0) & (kpos <= pos[:, None])
    if window is not None:
        mask &= kpos > pos[:, None] - window
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    out = jnp.einsum("bkgs,bskd->bkgd", jax.nn.softmax(s, axis=-1), v)
    return jnp.where((pos >= 0)[:, None, None, None], out, 0.0)


CASES = ([pytest.param(("small", seed), id=f"small-float32-{seed}") for seed in range(3)]
         + [pytest.param((shape, window, mix), id=f"{shape}-{'window' if window else 'full'}-{mix}")
            for shape in CELL_SHAPES for window in (False, True)
            for mix in ("edges", "longest-beside-short", "inactive-between-live")])


def _case(case):
    if case[0] == "small":
        return (*_rand_case(np.random.default_rng(case[1])), None)
    return _cell_case(*case)


@pytest.mark.parametrize("case", CASES)
def test_kernel_bit_consistent_with_reference(case):
    """The tier-1 acceptance bar: interpret-mode kernel output is BITWISE
    equal to the reference the CPU engine decodes with, at the shapes the
    cells run, with and without a window, over rows that end on every kind
    of boundary of a page and of a block."""
    q, kp, vp, tbl, pos, window = _case(case)
    ref = ragged_decode_attention(q, kp, vp, tbl, pos, impl="reference", window=window)
    ker = ragged_decode_attention(q, kp, vp, tbl, pos, impl="kernel",
                                  interpret=True, window=window)
    assert np.array_equal(np.asarray(ref, np.float32), np.asarray(ker, np.float32)), \
        f"kernel diverged from reference: max diff " \
        f"{np.max(np.abs(np.asarray(ref, np.float32) - np.asarray(ker, np.float32)))}"


@pytest.mark.parametrize("case", CASES)
def test_reference_matches_dense_masked_softmax(case):
    """Semantics: the online-softmax sweep over blocks of pages equals one
    dense masked softmax over the gathered pages (bfloat16 pools: the
    probabilities enter the second product rounded to the pool's dtype)."""
    q, kp, vp, tbl, pos, window = _case(case)
    out = ragged_decode_attention_reference(q, kp, vp, tbl, pos,
                                            scale=q.shape[-1] ** -0.5, window=window)
    tol = 1e-5 if kp.dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(_dense(q, kp, vp, tbl, pos, window)),
                               atol=tol, rtol=tol)


def _mixed_state(cfg, params, *, lengths, P=PAGE, max_len=MAX_LEN, spare=0):
    """A paged state with one active row per length (full reservation,
    like the engine's default grant) and `spare` inactive slots after them."""
    MP = max_len // P
    slots = len(lengths) + spare
    state = dp.init_paged_state(cfg, slots, max_len, slots * MP + 1, P)
    free = list(range(1, slots * MP + 1))
    for slot, n in enumerate(lengths):
        bucket = P
        while bucket < n:
            bucket *= 2
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = 1 + np.arange(n) % (cfg.vocab_size - 2)
        logits, kv = decoding.prefill(params, jnp.asarray(padded),
                                      jnp.int32(n), cfg)
        pages = [free.pop() for _ in range(MP)]
        row = np.zeros((MP,), np.int32)
        row[:MP] = pages
        state = dp.insert_sequence_paged(
            state, slot, kv, jnp.int32(n),
            jnp.asarray(int(jnp.argmax(logits)), jnp.int32),
            jnp.asarray(row), cfg)
    return state


def test_decode_step_ragged_matches_the_full_forward(tiny_model):
    """Multi-step agreement on a mixed-length batch, at a tight page bound
    AND the full table: every row's logits are those of `transformer.forward`
    over the row's tokens so far."""
    cfg, params = tiny_model
    # max length + steps stays inside the 2-page bound (the engine
    # recomputes the bound per step; here it is pinned)
    lengths = [3, 17, 27, 9]
    state = _mixed_state(cfg, params, lengths=lengths)
    MP = MAX_LEN // PAGE
    rows = [list(1 + np.arange(n) % (cfg.vocab_size - 2)) + [int(t)]
            for n, t in zip(lengths, np.asarray(state["last_token"]))]

    for _step in range(3):
        want = np.stack([np.asarray(transformer.forward(
            params, jnp.asarray([toks]), cfg)[0][0, -1]) for toks in rows])
        _, l_f = dp.decode_step_paged_ragged(params, _copy(state), cfg, MP, False)
        state, l_r = dp.decode_step_paged_ragged(params, state, cfg, 2, False)
        np.testing.assert_allclose(np.asarray(l_r), want, atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(l_f), want, atol=2e-5, rtol=1e-5)
        toks = np.argmax(want, -1).astype(np.int32)
        assert np.array_equal(np.argmax(np.asarray(l_r), -1), toks)
        state = decoding.commit_tokens(state, jnp.asarray(toks))
        for toks_row, t in zip(rows, toks):
            toks_row.append(int(t))


# ---- the in-place step (pools as the layer scan's carry) against the form
# it replaced: pools scanned in per layer and stacked back out


def _stacked_step(cfg, attend, pages_bound=None):
    """The decode step as it stood before the pools rode the scan's carry,
    kept here as the oracle: `state["kp"]`, `state["vp"]` are scanned INPUTS,
    each layer scatters into its own [num_pages, P, Hkv, Dh] slice and the
    slices are stacked back as OUTPUTS. `attend` is the attention core. With
    a LoRA bank the bank's layers are scanned inputs too."""
    from ray_tpu import ops
    from ray_tpu.models.decoding import _attn_qkv, _mlp_block, _rope
    from ray_tpu.models.transformer import _norm

    @jax.jit
    def step(params, state, lora_bank=None, slot_lora=None):
        dt = cfg.dtype
        B = state["block"].shape[0]
        P = state["kp"].shape[2]
        tokens = state["last_token"][:, None]
        pos = state["length"]
        page_ids = jnp.take_along_axis(state["block"], (pos // P)[:, None], axis=1)[:, 0]
        page_ids = jnp.where(state["active"], page_ids, 0)
        offsets = pos % P
        tbl = state["block"][:, :pages_bound]
        x = params["embed"].astype(dt)[tokens]
        if cfg.pos == "learned":
            x = x + params["pos_embed"].astype(dt)[pos][:, None]
        cos, sin = _rope(cfg)
        G = cfg.n_heads // cfg.kv_heads
        lscale = None if lora_bank is None else lora_bank["scale"][slot_lora]

        def block(h, layer_in):
            layer_p, kp, vp, *lora_l = layer_in
            q, k, v = _attn_qkv(_norm(h, layer_p["norm1"], cfg), layer_p["attn"], cfg,
                                lora_l, slot_lora, lscale)
            if cfg.pos == "rope":
                q = ops.apply_rope(q, cos, sin, positions=pos[:, None])
                k = ops.apply_rope(k, cos, sin, positions=pos[:, None])
            kp = kp.at[page_ids, offsets].set(k[:, 0].astype(kp.dtype))
            vp = vp.at[page_ids, offsets].set(v[:, 0].astype(vp.dtype))
            qh = q[:, 0].reshape(B, cfg.kv_heads, G, cfg.head_dim)
            # as the step does: a row that is not active walks no page
            out = attend(qh, kp, vp, tbl, jnp.where(state["active"], pos, -1),
                         scale=cfg.head_dim ** -0.5, dt=dt)
            out = out.reshape(B, 1, cfg.n_heads, cfg.head_dim).astype(dt)
            out = jnp.einsum("bthd,hde->bte", out, layer_p["attn"]["wo"].astype(dt))
            if cfg.bias:
                out = out + layer_p["attn"]["bo"].astype(dt)
            h = h + out
            h = h + _mlp_block(_norm(h, layer_p["norm2"], cfg), layer_p, cfg)
            return h, (kp, vp)

        bank = () if lora_bank is None else tuple(
            lora_bank[k] for k in ("A_q", "B_q", "A_v", "B_v"))
        x, (kp, vp) = jax.lax.scan(
            block, x, (params["layers"], state["kp"], state["vp"]) + bank)
        x = _norm(x, params["final_norm"], cfg)
        head = params["embed"].astype(dt).T if cfg.tie_embeddings else params["lm_head"].astype(dt)
        return {**state, "kp": kp, "vp": vp,
                "length": jnp.where(state["active"], pos + 1, pos)}, \
            (x[:, 0] @ head).astype(jnp.float32)

    return step


def _ragged_core(impl):
    return lambda qh, kp, vp, tbl, pos, *, scale, dt: ragged_decode_attention(
        qh, kp, vp, tbl, pos, scale=scale, impl=impl, interpret=True)


@pytest.fixture
def kernel_interpreted(monkeypatch):
    """`decode_step_paged_ragged(kernel=True)` on the CPU: the same Pallas
    kernel, interpreted."""
    import ray_tpu.ops.ragged_paged_attention as rpa

    real = rpa._ragged_kernel_call
    monkeypatch.setattr(rpa, "_ragged_kernel_call",
                        lambda *a, interpret, **kw: real(*a, interpret=True, **kw))


# the step's attention code, and the attention core of its oracle
STEPS = {"ragged-reference": (False, _ragged_core("reference")),
         "ragged-kernel": (True, _ragged_core("kernel"))}


def _lora_bank(cfg, rank=4):
    """Two random adapters (bank rows 1 and 2; row 0 is the null adapter)."""
    rng = np.random.default_rng(5)
    bank = decoding.init_lora_bank(cfg, 2, rank)
    for key in ("A_q", "B_q", "A_v", "B_v"):
        w = rng.normal(0, 0.1, bank[key].shape).astype(np.float32)
        w[:, 0] = 0.0
        bank[key] = jnp.asarray(w)
    bank["scale"] = jnp.asarray([0.0, 0.5, 1.0], jnp.float32)
    return bank


def _copy(state):
    return {k: jnp.array(v) for k, v in state.items()}


@pytest.mark.parametrize("lora", [False, True], ids=["base", "lora"])
@pytest.mark.parametrize("name", list(STEPS))
def test_in_place_step_is_bit_equal_to_the_stacked_scan(tiny_model, kernel_interpreted,
                                                        name, lora):
    """Four steps over a mixed-length batch with one INACTIVE slot, an
    admission (`write_kv_pages` + `activate_slot`) and a release between
    them: logits and both pools equal the oracle's to every bit. `lora`: the
    rows carry the null adapter, two adapters and (the admitted row) the
    first again, and the adapters move the logits."""
    cfg, params = tiny_model
    kernel, core = STEPS[name]
    oracle = _stacked_step(cfg, core, 4)
    adapters = (_lora_bank(cfg), jnp.asarray([0, 1, 2, 1], jnp.int32)) if lora else ()
    MP = MAX_LEN // PAGE
    state = _mixed_state(cfg, params, lengths=[3, 17, 27], spare=1)
    want = _copy(state)

    def admit(s):
        """Slot 3 takes a 21-token prompt into the pool's last pages."""
        padded = np.zeros((1, 2 * PAGE), np.int32)
        padded[0, :21] = 5 + np.arange(21)
        logits, kv = decoding.prefill(params, jnp.asarray(padded), jnp.int32(21), cfg)
        row = np.arange(3 * MP + 1, 4 * MP + 1, dtype=np.int32)
        s = dp.write_kv_pages(s, kv, jnp.asarray(row))
        return dp.activate_slot(s, 3, jnp.asarray(row), jnp.int32(21),
                                jnp.asarray(int(jnp.argmax(logits)), jnp.int32))

    between = {1: admit, 2: lambda s: dp.release_slot_paged(s, 1)}
    for i in range(4):
        if i in between:
            state, want = between[i](state), between[i](want)
        if lora:
            _, base_logits = dp.decode_step_paged_ragged(params, _copy(state), cfg, 4, kernel)
        state, logits = dp.decode_step_paged_ragged(params, state, cfg, 4, kernel, *adapters)
        want, logits_want = oracle(params, want, *adapters)
        assert np.array_equal(np.asarray(logits), np.asarray(logits_want)), (name, i)
        if lora:  # row 0 is the base model's to the bit, the live adapter rows are not
            moved = np.abs(np.asarray(logits) - np.asarray(base_logits)).max(-1)
            assert moved[0] == 0 and (moved[[1, 2] if i < 2 else [2, 3]] > 1e-3).all(), moved
        for key in ("kp", "vp", "length"):
            assert np.array_equal(np.asarray(state[key]), np.asarray(want[key])), (name, i, key)
        assert state["kp"].shape == (cfg.n_layers, 4 * MP + 1, PAGE, cfg.kv_heads, cfg.head_dim)
        toks = np.argmax(np.asarray(logits), -1).astype(np.int32)
        state = {**state, "last_token": jnp.asarray(toks)}     # donated: one each
        want = {**want, "last_token": jnp.asarray(toks)}


def test_an_inactive_row_writes_only_its_layers_scratch_page(tiny_model):
    """Every row inactive, with a stale table and length left behind: a step
    changes page 0 of EACH layer (the reserved scratch page, at the layer's
    own offset in the flat pool) and no other page."""
    cfg, params = tiny_model
    rng = np.random.default_rng(3)
    state = dp.init_paged_state(cfg, 2, MAX_LEN, 9, PAGE)
    shape = state["kp"].shape
    state = {**state,
             "kp": jnp.asarray(rng.standard_normal(shape), jnp.float32),
             "vp": jnp.asarray(rng.standard_normal(shape), jnp.float32),
             "block": jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32),
             "length": jnp.asarray([5, 20], jnp.int32),
             "last_token": jnp.asarray([7, 9], jnp.int32)}
    before = {k: np.asarray(state[k]) for k in ("kp", "vp")}
    after, _ = dp.decode_step_paged_ragged(params, _copy(state), cfg, 4, False)
    for key in ("kp", "vp"):
        got = np.asarray(after[key])
        assert np.array_equal(got[:, 1:], before[key][:, 1:])
        assert all((got[l, 0] != before[key][l, 0]).any() for l in range(cfg.n_layers))
    assert np.array_equal(np.asarray(after["length"]), [5, 20])
