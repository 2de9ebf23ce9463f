"""The Mamba-2 mixer's state-space part (arXiv 2405.21060, "SSD"): a causal
depthwise convolution over the last `d_conv` inputs and, a head, the
recurrence

    h_t = exp(dt_t A) h_{t-1} + dt_t (x_t outer B_t)        h [P, N]
    y_t = h_t C_t                                            (+ D x_t: the caller's)

with x_t [P] of the head, B_t and C_t [N] shared by the heads (one group),
dt_t > 0 and A < 0 scalars of the head.

Two forms of the same recurrence, as attention has a prefill and a decode
form:

- `ssm_chunk_scan`: a whole sequence in chunks (plain XLA: matrix products).
  Inside a chunk of Q steps the outputs are (C B^T * the masked decay matrix
  * dt) x, between chunks a state is carried; a `lax.scan` over the chunks,
  so one chunk's [H, Q, Q] decay matrix is live at a time. A position with
  dt = 0 neither decays nor feeds the state: that is how padding past a
  prompt's length, and up to a whole chunk, leaves the state as it was.
- `ssm_state_update`: one token a row, for the LIVE rows of the batch, on a
  state [layers, rows, H, P, N] that is read and written WHERE IT LIES: the
  decode step. `impl="kernel"` is a Pallas TPU kernel (`ssm_state_update` in
  a device trace): a grid step a row, a row's [H, P, N] block in and out
  through the aliased operand, nothing of the other layers touched. The
  grid walks `live_rows`' schedule: the live rows first and then the last
  of them again and again, which the pipeline neither fetches nor writes
  anew, so a step moves the state of the rows that hold a sequence and of
  no other (stepping all 96 slots with 35 of them live, the launch read
  and wrote 61 dead slots' 4.6 GB a step). `impl="reference"` is the same
  arithmetic in `jax.numpy` on the layer's slice, written back with a
  `dynamic_update_slice` (the CPU path); a row that is not live is stepped
  with dt = 0 there, which keeps its state bit for bit. A row being
  prefilled in chunks is in no slot yet and is never disturbed.

The state is float32 whatever the activations are: a running sum over
thousands of steps with a decay near 1.

A second recurrence lives beside it: the gated delta rule with a decay a KEY
CHANNEL (Kimi Delta Attention, arXiv 2510.26692), a head's state a matrix
S [dk, dv]:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

with q_t, k_t [dk] (the caller's: normalised), v_t [dv], g_t [dk] <= 0 the log
decay of each key channel, beta_t in (0, 2). One step is `S <- Diag(exp(g)) S;
u = beta (v - S^T k); S <- S + k u^T; o = S^T q`. The same two forms:

- `kda_chunk_scan`: a sequence in chunks of Q. With G the running sum of g
  inside a chunk, S_i = Diag(exp(G_i)) S_0 + sum_{j<=i} Diag(exp(G_i - G_j))
  k_j u_j^T, where the corrected values u solve a unit lower-triangular system
  (I + A) U = beta (V - (K exp(G)) S_0), A_ij = beta_i sum_c k_ic k_jc
  exp(G_ic - G_jc) for j < i (the WY / UT transform); then O = (Q exp(G)) S_0
  + tril(B) U with B the same decayed Gram matrix of q against k, and S_Q =
  Diag(exp(G_Q)) S_0 + (K exp(G_Q - G))^T U. The decays exp(G_i - G_j), i >=
  j, are never split into exp(G_i) exp(-G_j) (exp of a POSITIVE sum overflows
  under strong gates): no exponent is ever positive. Everything is float32,
  every matrix product at float32 accuracy. A position with g = 0 AND beta =
  0 changes nothing: padding. Two forms, chosen inside the op from what it
  can observe (`kda_scan_in_kernel`), as `ops.attention` chooses the flash
  kernel:
  - ONE Pallas launch (`kda_chunk_scan` in a device trace) on a TPU, outside
    a mesh, where `kda_scan_tiles` (keys of 128, values of whole 128-lane
    tiles, heads in pairs, a chunk of 8 to 64 that is a power of two): grid
    (pairs of heads, steps of four chunks), the steps of a pair in order, its
    two states carried in VMEM from chunk to chunk; q, k, g, v blocks of [T,
    H D] as the projections leave them; nothing of a chunk but its inputs is
    read from HBM and nothing but o and the last state written. The decays go
    through a BINARY hierarchy of references (`_kda_scan_kernel`): a pair i >
    j meets at the level where their groups of 2 m positions first join, as
    exp(G_i - G_r) exp(G_r - G_j) through the earlier half's last position r,
    both factors at most 1, one MXU product a level (of the later halves'
    rows alone); (I + A)^-1 is joined by the same levels (T - T A21 T) and
    applied to the right-hand side. There is no sub-block (it is ONE
    position): on the v5e a product at six bfloat16 passes costs about a
    quarter of a microsecond whatever its size up to 128 x 128, the VPU's
    pair-by-pair part of sub-blocks of 8 cost four levels' worth, and forward
    substitution on the VPU ten times the levels' (PERF.md section 6, PR 51).
  - plain XLA elsewhere (the CPU, heads that do not tile; the reference the
    launch is compared with, and what its gradient is taken through:
    `custom_vjp`): W_v = (I + A)^-1 beta V and W_k = (I + A)^-1 beta K exp(G)
    are made for all chunks at once (A does not depend on S_0) and the
    `lax.scan` over chunks is matrix products alone: U = W_v - W_k S_0. Inside
    a sub-block of `sub` positions the decays are taken pair by pair, between
    sub-blocks through the earlier one's last position; the triangular system
    by forward substitution (rows inside a sub-block, then block by block).
- `kda_state_update`: one token a row for the LIVE rows, in place on
  [layers, rows, H, dk, dv], through `live_rows`' schedule as
  `ssm_state_update` (`kda_state_update` in a device trace; `jax.numpy` on
  the layer's slice for `impl="reference"`, a row that is not live kept as it
  is).

Around `kda_chunk_scan`'s launch the KDA mixer's elementwise work over a
sequence runs in three more (`kda_conv`, `kda_split`, `kda_gate_norm` in a
device trace), in the scan's own layout, where `kda_mixer_in_kernel` says so:
the convolutions of the projection as it was written (bfloat16 in, float32
sums out, the rows before a block read from the block before it), the SiLU
with q's and k's norms a head, and after the scan the gated norm a head,
written in the activations' dtype for `out_proj`. Their XLA forms are the
callers' (`causal_conv`, and `kda_split` and `kda_out` of
`models/transformer.py`), which choose.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


def causal_conv(x, tail, w, b=None):
    """Depthwise causal convolution of x [T, C] after the `K - 1` inputs
    `tail` [K - 1, C] that came before it (zeros at a row's start), weights w
    [K, C] (w[K - 1] multiplies the current input), bias b [C] (None: no
    bias): K shifted products, float32 sums. Returns [T, C] in x's dtype."""
    K, T = w.shape[0], x.shape[0]
    padded = jnp.concatenate([tail.astype(x.dtype), x], axis=0).astype(F32)
    out = 0.0 if b is None else b.astype(F32)[None]
    for j in range(K):
        out = out + padded[j:j + T] * w[j].astype(F32)[None]
    return out.astype(x.dtype)


def conv_tail(x, tail, length):
    """The `K - 1` inputs a row's next token convolves with: of tail
    [K - 1, C] followed by x [T, C] (`length` of them real), the ones that
    end at position length - 1."""
    padded = jnp.concatenate([tail.astype(x.dtype), x], axis=0)
    return jax.lax.dynamic_slice_in_dim(padded, length, tail.shape[0], axis=0)


def ssm_chunk_scan(x, dt, A, B, C, *, chunk: int, state=None, dtype=jnp.bfloat16):
    """x [T, H, P], dt [T, H] (float32, 0 at padded positions), A [H]
    (negative), B and C [T, N] -> (y [T, H, P] float32 without the D x skip,
    the state after the last position [H, P, N] float32). `state`: the state
    before position 0 (None: zeros). Matrix products take operands in `dtype`
    and sum in float32; decays are float32 throughout."""
    T, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, T)
    pad = -T % Q
    if pad:  # dt = 0: steps that change nothing
        x, dt, B, C = (jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
                       for a in (x, dt, B, C))
    n = (T + pad) // Q
    dt = dt.astype(F32)
    a = dt * A.astype(F32)[None]                                  # [T, H] log decay
    xs = (x.astype(F32) * dt[..., None]).astype(dtype)            # dt_t x_t
    chunks = tuple(t.reshape(n, Q, *t.shape[1:]) for t in (xs, a, B.astype(dtype),
                                                           C.astype(dtype)))
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def one(S, xs_a_B_C):
        xs, a, B, C = xs_a_B_C
        cum = jnp.cumsum(a, axis=0)                               # [Q, H], inclusive
        # inside the chunk: y_i += sum_{j <= i} (C_i . B_j) exp(cum_i - cum_j) dt_j x_j
        scores = jnp.einsum("in,jn->ij", C, B, preferred_element_type=F32)
        decay = jnp.exp(jnp.where(causal[None], cum.T[:, :, None] - cum.T[:, None, :],
                                  -jnp.inf))                      # [H, Q, Q]
        y = jnp.einsum("hij,jhp->ihp", (scores[None] * decay).astype(dtype), xs,
                       preferred_element_type=F32)
        # what came before the chunk: y_i += exp(cum_i) C_i . S
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "in,hpn->ihp", C, S.astype(dtype), preferred_element_type=F32)
        # the state after the chunk: the old one decayed over all of it, and
        # every input decayed from its own position to the chunk's end
        to_end = jnp.exp(cum[-1][None] - cum)                     # [Q, H]
        S = (jnp.exp(cum[-1])[:, None, None] * S
             + jnp.einsum("jhp,jn->hpn", (xs.astype(F32) * to_end[..., None]).astype(dtype),
                          B, preferred_element_type=F32))
        return S, y

    S0 = jnp.zeros((H, P, N), F32) if state is None else state.astype(F32)
    S, y = jax.lax.scan(one, S0, chunks)
    return y.reshape(n * Q, H, P)[:T], S


def live_rows(live):
    """`live` [R] bool -> (schedule [R] int32, count [1] int32): the live
    rows' indices in order, then the last of them repeated; at least one row
    is scheduled (row 0 of none that is live: the caller steps it with
    dt = 0). The decode step makes it once and every layer's launch walks it."""
    R = live.shape[0]
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    count = jnp.maximum(jnp.sum(live, dtype=jnp.int32), 1)
    return order[jnp.minimum(jnp.arange(R, dtype=jnp.int32), count - 1)], count.reshape(1)


def _update_kernel(layer_ref, rows_ref, count_ref, s_ref, decay_ref, dtx_ref, b_ref, c_ref,
                   o_ref, y_ref):
    """One row: s [H, P, N] -> o = decay * s + dtx (outer) B, y = o . C.
    `decay` and `dtx` come transposed, [P, H]: head h's values are a column,
    which broadcasts along the lanes (N) without a relayout. Past the
    schedule's live rows a grid step does nothing: its blocks are the step
    before's, still in place."""
    del layer_ref, rows_ref

    @pl.when(pl.program_id(0) < count_ref[0])
    def _step():
        H = s_ref.shape[2]
        decay, dtx = decay_ref[0], dtx_ref[0]                     # [P, H]
        b, c = b_ref[0], c_ref[0]                                 # [1, N]
        lane = jax.lax.broadcasted_iota(jnp.int32, decay.shape, 1)
        y = jnp.zeros(decay.shape, F32)
        for h in range(H):  # static: a head is eight vregs
            new = decay[:, h:h + 1] * s_ref[0, 0, h] + dtx[:, h:h + 1] * b
            o_ref[0, 0, h] = new
            y = jnp.where(lane == h, jnp.sum(new * c, axis=-1, keepdims=True), y)
        y_ref[0] = y


def _update_kernel_call(state, layer, rows, count, decay, dtx, B, C, *, interpret: bool):
    L, R, H, P, N = state.shape
    row = lambda r, layer, rows, count: (rows[r], 0, 0)           # noqa: E731
    block = lambda r, layer, rows, count: (layer[0], rows[r], 0, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # the layer, the rows' schedule, the live rows' count
        grid=(R,),
        in_specs=[pl.BlockSpec((1, 1, H, P, N), block),
                  pl.BlockSpec((1, P, H), row), pl.BlockSpec((1, P, H), row),
                  pl.BlockSpec((1, 1, N), row), pl.BlockSpec((1, 1, N), row)],
        out_specs=[pl.BlockSpec((1, 1, H, P, N), block),
                   pl.BlockSpec((1, P, H), row)])
    state, y = pl.pallas_call(
        _update_kernel,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((R, P, H), F32)],
        grid_spec=grid_spec,
        # operand 3 (after the three prefetched) is the state: updated in place
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # a row's block in and out, double-buffered: 4 x H P N floats
            vmem_limit_bytes=max(32 * 2**20, 6 * H * P * N * 4)),
        interpret=interpret,
        name="ssm_state_update",
    )(layer.reshape(1).astype(jnp.int32), rows, count, state, decay, dtx, B, C)
    return state, y


def ssm_state_update(state, layer, x, dt, A, B, C, *, live=None, schedule=None,
                     impl: str = "reference", interpret: bool = False):
    """One token a row on layer `layer` of `state` [L, R, H, P, N] (float32):
    x [R, H, P], dt [R, H] (float32), A [H], B and C [R, N] -> (the state,
    that layer's LIVE rows updated where they lie and the others as they
    were; y [R, H, P] float32 = h_t C_t without the D x skip, 0 for a row
    that is not live). `live` [R] bool (None: every row); `schedule` is
    `live_rows(live)` where the caller has made it already."""
    R, H, P = x.shape
    if live is None:
        live = jnp.ones((R,), bool)
    dt = jnp.where(live[:, None], dt.astype(F32), 0.0)
    decay = jnp.exp(dt * A.astype(F32)[None])                     # [R, H]
    dtx = x.astype(F32) * dt[..., None]                           # [R, H, P]
    B, C = B.astype(F32), C.astype(F32)
    if impl == "kernel":
        rows, count = live_rows(live) if schedule is None else schedule
        state, y = _update_kernel_call(
            state, layer, rows, count, jnp.broadcast_to(decay[:, None, :], (R, P, H)),
            dtx.transpose(0, 2, 1), B[:, None], C[:, None], interpret=interpret)
        y = y.transpose(0, 2, 1)
    elif impl == "reference":
        s = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
        s = decay[..., None, None] * s + dtx[..., None] * B[:, None, None, :]
        y = jnp.sum(s * C[:, None, None, :], axis=-1)
        state = jax.lax.dynamic_update_index_in_dim(state, s, layer, 0)
    else:
        raise ValueError(f"impl must be 'kernel' or 'reference', got {impl!r}")
    return state, jnp.where(live[:, None, None], y, 0.0)


# --------------------------------------------- the gated delta rule (KDA)

HIGHEST = jax.lax.Precision.HIGHEST
# positions of a sub-block of the XLA form's in-chunk decays: at the published
# sizes a layer's 2,048-token chunk reads 13.4 ms with 8 and 17.4 ms with 16
# on the chip (PERF.md section 6, PR 50); only tests pass another, to run
# several sub-blocks in a chunk of 8 or 16. The Pallas launch, which runs
# those sizes on a TPU since PR 51, has no sub-block (module docstring)
KDA_SUB = 8


def _unit_lower_solve(A, rhs, sub: int):
    """(I + A)^-1 rhs for A [..., Q, Q] strictly lower triangular, rhs
    [..., Q, M], float32: the diagonal sub-blocks inverted row by row (for all
    of them at once), then forward substitution block by block."""
    Q = A.shape[-1]
    n = Q // sub
    eye = jnp.eye(sub, dtype=F32)
    diag = jnp.stack([A[..., b * sub:(b + 1) * sub, b * sub:(b + 1) * sub]
                      for b in range(n)], axis=-3)                  # [..., n, sub, sub]
    # row i of a block's inverse from the rows before it, every block of
    # every matrix in the lanes: multiply-adds on the VPU, exact float32
    d = jnp.moveaxis(diag.reshape(-1, sub, sub), 0, -1)             # [i, j, blocks]
    rows = []
    for i in range(sub):
        row = jnp.broadcast_to(eye[i][:, None], d.shape[1:])
        for j in range(i):
            row = row - d[i, j][None, :] * rows[j]
        rows.append(row)
    inv = jnp.moveaxis(jnp.stack(rows), -1, 0).reshape(diag.shape)
    out = []
    for b in range(n):
        r = rhs[..., b * sub:(b + 1) * sub, :]
        if b:
            r = r - jnp.einsum("...ij,...jm->...im", A[..., b * sub:(b + 1) * sub, :b * sub],
                               jnp.concatenate(out, axis=-2), precision=HIGHEST)
        out.append(jnp.einsum("...ij,...jm->...im", inv[..., b, :, :], r, precision=HIGHEST))
    return jnp.concatenate(out, axis=-2)


def _decayed_grams(x, k, G, sub: int):
    """sum_c x_ic k_jc exp(G_ic - G_jc) for i >= j (0 above the diagonal): x
    [X, Q, H, D] (several left operands at once), k and G [Q, H, D] -> [X, H,
    Q, Q] float32. No exponent is positive (module docstring)."""
    Q, H, D = k.shape
    n = Q // sub
    xb = x.reshape(x.shape[0], n, sub, H, D)
    kb, Gb = k.reshape(n, sub, H, D), G.reshape(n, sub, H, D)
    # inside a sub-block: pair by pair
    lower = jnp.tril(jnp.ones((sub, sub), bool))
    pair = jnp.exp(jnp.where(lower[None, :, :, None, None],
                             Gb[:, :, None] - Gb[:, None, :], -jnp.inf))  # [n, i, j, H, D]
    inside = jnp.sum(xb[:, :, :, None] * (kb[:, None] * pair)[None], axis=-1)  # [X, n, i, j, H]
    gram = jnp.zeros((x.shape[0], H, Q, Q), F32)
    for b in range(n):
        gram = gram.at[:, :, b * sub:(b + 1) * sub, b * sub:(b + 1) * sub].set(
            inside[:, b].transpose(0, 3, 1, 2))
    # between sub-blocks: through the earlier one's last position
    for b in range(n - 1):
        ref = Gb[b, -1]                                             # [H, D]
        down = kb[b] * jnp.exp(ref[None] - Gb[b])                   # [sub, H, D]
        later = slice((b + 1) * sub, Q)
        up = x[:, later] * jnp.exp(G[later] - ref[None])[None]
        gram = gram.at[:, :, later, b * sub:(b + 1) * sub].set(
            jnp.einsum("xihd,jhd->xhij", up, down, preferred_element_type=F32,
                       precision=HIGHEST))
    return gram


def _kda_prepare(q, k, v, G, beta, grams, sub: int):
    """What the chunks of `kda_chunk_scan` need that does not depend on the
    state before them, all chunks at once: q, k, G [n, Q, H, D], v [n, Q, H,
    Dv], beta [n, Q, H], grams [n, 2, H, Q, Q] (`_decayed_grams` of q and of
    k) -> (W_k | q exp(G) stacked on the rows [n, H, 2 Q, D], W_v [n, H, Q,
    Dv], tril(B) [n, H, Q, Q], k exp(G_Q - G) [n, H, D, Q], exp(G_Q) [n, H, D])."""
    Q, D = k.shape[1], k.shape[-1]
    strict = jnp.tril(jnp.ones((Q, Q), bool), -1)
    A = jnp.where(strict, grams[:, 1], 0.0) * beta.transpose(0, 2, 1)[..., None]  # [n, H, Q, Q]
    decayed = jnp.exp(G)                                            # [n, Q, H, D]
    rhs = jnp.concatenate([k * decayed, v], axis=-1) * beta[..., None]
    W = _unit_lower_solve(A, rhs.transpose(0, 2, 1, 3), sub)        # [n, H, Q, D + Dv]
    q_dec = (q * decayed).transpose(0, 2, 1, 3)                     # [n, H, Q, D]
    k_end = (k * jnp.exp(G[:, -1:] - G)).transpose(0, 2, 3, 1)      # [n, H, D, Q]
    return (jnp.concatenate([W[..., :D], q_dec], axis=2), W[..., D:], grams[:, 0], k_end,
            decayed[:, -1])


def _kda_chunk_scan_xla(q, k, v, g, beta, state, *, chunk: int, sub: int):
    """`kda_chunk_scan` in plain XLA over whole chunks, all chunks' state-free
    part at once (module docstring): the form of the CPU and of shapes that
    do not tile, the reference the launch is compared with, and what its
    gradient is taken through."""
    T, H, D = k.shape
    Dv = v.shape[-1]
    Q, sub = chunk, min(sub, chunk)
    if Q % sub:
        raise ValueError(f"chunk {chunk} must be a multiple of the sub-block {sub}")
    n = T // Q
    q, k, v, g, beta = (t.reshape(n, Q, *t.shape[1:]) for t in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=1)                                       # [n, Q, H, D], inclusive
    # the pairwise decays of a sub-block are [sub, sub, H, D] a sub-block:
    # the Gram matrices are made a few chunks at a time, the rest at once
    group = max(1, min(n, 512 // Q))
    if n % group:
        group = 1
    grams = jax.lax.map(
        jax.vmap(lambda one: _decayed_grams(jnp.stack(one[:2]), one[1], one[2], sub)),
        tuple(t.reshape(n // group, group, *t.shape[1:]) for t in (q, k, G)))
    prepared = _kda_prepare(q, k, v, G, beta, grams.reshape(n, *grams.shape[2:]), sub)
    dot = functools.partial(jnp.einsum, preferred_element_type=F32, precision=HIGHEST)

    def one(S, chunk_in):
        Wk_q, W_v, B, k_end, decay_end = chunk_in
        both = dot("hik,hkv->hiv", Wk_q, S)                         # W_k S | (q exp(G)) S
        U = W_v - both[:, :Q]
        o = both[:, Q:] + dot("hij,hjv->hiv", B, U)
        S = decay_end[..., None] * S + dot("hkj,hjv->hkv", k_end, U)
        return S, o

    S, o = jax.lax.scan(one, state, prepared)                       # o [n, H, Q, Dv]
    return o.transpose(0, 2, 1, 3).reshape(T, H, Dv), S


# heads a grid step of the launch, stacked on the rows: their [Q, Q] matrices
# are the diagonal blocks of one [2 Q, 2 Q] matrix (128 x 128 at the published
# chunk of 64: one MXU tile), so each product of the chain serves both heads
_KDA_STACK = 2


def _dot32(a, b, contract=((1,), (0,))):
    """A float32 matrix product at float32 accuracy (Mosaic's
    `contract_precision<fp32>`: six bfloat16 passes)."""
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=HIGHEST,
                               preferred_element_type=F32)


def _kda_scan_kernel(q_ref, k_ref, g_ref, v_ref, beta_ref, s0_ref, o_ref, s_ref, st_ref,
                     wide_ref, square_ref, *, Q: int, chunks: int):
    """Two heads (`_KDA_STACK`) over `chunks` chunks of Q positions a grid
    step; grid (head pairs, steps), the steps of a pair in order, the pair's
    states carried transposed ([dv, dk]: a decay a key channel scales lanes)
    in `st_ref`. Everything of a chunk stays in VMEM and vregs.

    The decays exp(G_i - G_j), i > j, go through a position r with j <= r < i
    as exp(G_i - G_r) exp(G_r - G_j), both factors at most 1: a binary
    hierarchy of references. At level m (1, 2, 4, .. Q / 2) the positions are
    groups of 2 m; the later half of a group sees the earlier half through
    the earlier half's last position, one matrix product a level (every pair
    i > j meets at exactly one level: where their groups first join). (I +
    A)^-1 comes by the same hierarchy: T = diag(T1, T2) of a group's halves
    becomes [[T1, 0], [-T2 A21 T1, T2]] = T - T A21 T. No loop runs over
    sub-blocks: a level is whole-matrix products and masks."""
    P, R = _KDA_STACK, _KDA_STACK * Q
    pair, step = pl.program_id(0), pl.program_id(1)
    D, Dv = q_ref.shape[-1] // P, v_ref.shape[-1] // P

    @pl.when(step == 0)
    def _first():
        for h in range(P):
            st_ref[h] = s0_ref[h].T

    row = jax.lax.broadcasted_iota(jnp.int32, (R, R), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (R, R), 1)
    eye = row == col
    running = ((row >= col) & (row // Q == col // Q)).astype(F32)
    row_d = jax.lax.broadcasted_iota(jnp.int32, (R, D), 0)
    levels = []  # (m, the rows of the later halves, [later half, earlier half] of a group)
    m = 1
    while m < Q:
        levels.append((m, (row_d // m) % 2 == 1,
                       ((row // m) % 2 == 1) & ((col // m) % 2 == 0)
                       & (row // (2 * m) == col // (2 * m))))
        m *= 2
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (Q, beta_ref.shape[-1]), 1)

    def one_chunk(c, carry):
        rows = pl.ds(pl.multiple_of(c * Q, Q), Q)

        def stacked(ref, width):
            return jnp.concatenate([ref[rows, h * width:(h + 1) * width] for h in range(P)],
                                   axis=0)

        q, k, g, v = stacked(q_ref, D), stacked(k_ref, D), stacked(g_ref, D), stacked(v_ref, Dv)
        betas = beta_ref[rows, :]
        beta = jnp.concatenate(
            [jnp.sum(jnp.where(head_lane == pair * P + h, betas, 0.0), axis=1, keepdims=True)
             for h in range(P)], axis=0)                                # [R, 1]
        G = _dot32(running, g)                                          # inclusive, a head
        by_eights = G.reshape(R // 8, 8, D)

        def row_of_eight(p):  # row p of every eight rows, to all eight
            return jnp.broadcast_to(by_eights[:, p:p + 1], by_eights.shape).reshape(R, D)

        def later_halves(x, m, via):
            """The rows of the later halves of every 2 m, packed [R / 2, .]:
            whole tiles of eight rows by slices, rows inside a tile by strided
            reads of `via`."""
            if m >= 8:
                return jnp.concatenate([x[s + m:s + 2 * m] for s in range(0, R, 2 * m)], axis=0)
            via[...] = x
            return jnp.concatenate([via[pl.ds(m + p, R // (2 * m), stride=2 * m), :]
                                    for p in range(m)], axis=0)

        def to_later_halves(x, m):
            """... and [R / 2, R] back to those rows, zeros in the others."""
            if m >= 8:
                zero = jnp.zeros((m, R), F32)
                return jnp.concatenate(
                    [y for s in range(0, R // 2, m) for y in (zero, x[s:s + m])], axis=0)
            n = R // (2 * m)
            for p in range(m):
                square_ref[pl.ds(m + p, n, stride=2 * m), :] = x[p * n:(p + 1) * n]
            return jnp.where((row // m) % 2 == 1, square_ref[...], 0.0)

        # the decayed Gram matrices of q and of k against k, below the diagonal;
        # only the later halves' rows stream through a level's product
        A = jnp.zeros((R, R), F32)
        B = jnp.where(eye, jnp.sum(q * k, axis=1, keepdims=True), 0.0)
        for m, later, joins in levels:
            if m == 1:
                through = jnp.where(later, g, 0.0)                      # G_i - G_(i - 1)
            else:
                if m == 2:
                    ref = jnp.where(row_d % 8 < 4, row_of_eight(1), row_of_eight(5))
                elif m == 4:
                    ref = row_of_eight(3)
                else:
                    ref = jnp.concatenate(
                        [jnp.broadcast_to(G[s + m - 1:s + m], (2 * m, D))
                         for s in range(0, R, 2 * m)], axis=0)
                through = jnp.where(later, G - ref, ref - G)
            decay = jnp.exp(through)
            qd, kd = q * decay, k * decay
            both = _dot32(jnp.concatenate([later_halves(qd, m, wide_ref),
                                           later_halves(kd, m, wide_ref)], axis=0),
                          kd, ((1,), (1,)))
            B = B + jnp.where(joins, to_later_halves(both[:R // 2], m), 0.0)
            A = A + jnp.where(joins, to_later_halves(both[R // 2:], m), 0.0)
        A = A * beta
        T = jnp.where(eye, 1.0, 0.0)
        for m, _, joins in levels:
            off = jnp.where(joins, A, 0.0)
            if m == 1:
                T = T - off
            else:  # - T2 A21 T1: rows of the later halves in either product
                inner = to_later_halves(_dot32(later_halves(off, m, square_ref), T), m)
                T = T - to_later_halves(_dot32(later_halves(T, m, square_ref), inner), m)
        # with the carried states: U = T beta (V - K exp(G) S), O = Q exp(G) S + B U
        decayed = jnp.exp(G)
        k_dec, q_dec = k * decayed, q * decayed
        head = lambda x, h: x[h * Q:(h + 1) * Q]                        # noqa: E731
        states = [st_ref[h] for h in range(P)]
        seen = [_dot32(jnp.concatenate([head(k_dec, h), head(q_dec, h)], axis=0), states[h],
                       ((1,), (1,))) for h in range(P)]                 # [2 Q, dv] a head
        U = _dot32(T, beta * (v - jnp.concatenate([s[:Q] for s in seen], axis=0)))
        o = jnp.concatenate([s[Q:] for s in seen], axis=0) + _dot32(B, U)
        for h in range(P):
            o_ref[rows, h * Dv:(h + 1) * Dv] = head(o, h)
            G_h = head(G, h)
            k_end = head(k, h) * jnp.exp(G_h[Q - 1:Q] - G_h)
            st_ref[h] = (jnp.exp(G_h[Q - 1:Q]) * states[h]
                         + _dot32(head(U, h), k_end, ((0,), (0,))))
        return carry

    jax.lax.fori_loop(0, chunks, one_chunk, 0)

    @pl.when(step == pl.num_programs(1) - 1)
    def _last():
        for h in range(P):
            s_ref[h] = st_ref[h].T


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"), inline=True)
def _kda_scan_launch(q, k, v, g, beta, state, *, chunk: int, interpret: bool):
    """The launch over q, k, g [T, H dk], v [T, H dv] as they come from the
    projections (a head's columns side by side: no heads-major copy), beta [T,
    H], state [H, dk, dv]; T whole chunks. Jitted for its trace cache alone:
    a prefill program calls it in each of the two scans over its KDA layers
    and a replica warms a dozen such programs of two or three lengths; the
    body's few hundred operations, traced inside those programs' traces, took
    1.2 s a call on the chip's host (PERF.md section 6, PR 51)."""
    T, H = beta.shape
    D, Dv = q.shape[1] // H, v.shape[1] // H
    P, n = _KDA_STACK, T // chunk
    chunks = next(c for c in (4, 2, 1) if n % c == 0)
    rows = chunk * chunks
    at = lambda pair, step: (step, pair)                                # noqa: E731
    of_pair = lambda pair, step: (pair, 0, 0)                           # noqa: E731
    return pl.pallas_call(
        functools.partial(_kda_scan_kernel, Q=chunk, chunks=chunks),
        out_shape=[jax.ShapeDtypeStruct((T, H * Dv), F32),
                   jax.ShapeDtypeStruct((H, D, Dv), F32)],
        grid=(H // P, n // chunks),
        in_specs=[pl.BlockSpec((rows, P * D), at), pl.BlockSpec((rows, P * D), at),
                  pl.BlockSpec((rows, P * D), at), pl.BlockSpec((rows, P * Dv), at),
                  pl.BlockSpec((rows, H), lambda pair, step: (step, 0)),
                  pl.BlockSpec((P, D, Dv), of_pair)],
        out_specs=[pl.BlockSpec((rows, P * Dv), at), pl.BlockSpec((P, D, Dv), of_pair)],
        scratch_shapes=[pltpu.VMEM((P, Dv, D), F32), pltpu.VMEM((P * chunk, D), F32),
                        pltpu.VMEM((P * chunk, P * chunk), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="kda_chunk_scan",
    )(q, k, g, v, beta, state)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _kda_chunk_scan_pallas(q, k, v, g, beta, state, chunk, sub, interpret):
    """`kda_chunk_scan` as the launch, over whole chunks; `sub` is the XLA
    form's, for the gradient."""
    T, H, _ = k.shape
    o, state = _kda_scan_launch(*(a.reshape(T, -1) for a in (q, k, v, g)), beta, state,
                                chunk=chunk, interpret=interpret)
    return o.reshape(T, H, -1), state


def _kda_chunk_scan_pallas_fwd(q, k, v, g, beta, state, chunk, sub, interpret):
    return (_kda_chunk_scan_pallas(q, k, v, g, beta, state, chunk, sub, interpret),
            (q, k, v, g, beta, state))


def _kda_chunk_scan_pallas_bwd(chunk, sub, interpret, inputs, cotangents):
    # the XLA form's own gradient, as `grouped_matmul` keeps `ragged_dot`'s
    return jax.vjp(functools.partial(_kda_chunk_scan_xla, chunk=chunk, sub=sub),
                   *inputs)[1](cotangents)


_kda_chunk_scan_pallas.defvjp(_kda_chunk_scan_pallas_fwd, _kda_chunk_scan_pallas_bwd)


def kda_scan_tiles(heads: int, dk: int, dv: int, chunk: int) -> bool:
    """Whether the launch can take these shapes: keys of one 128-lane tile
    and values of whole ones, heads in pairs, the chunk a power of two (the
    hierarchy's halves) from 8 (a sublane tile) to 64 (a pair's chunks one
    128 x 128 tile, which the strided reads of `_kda_scan_kernel` ask for)."""
    return (dk == 128 and dv % 128 == 0 and heads % _KDA_STACK == 0
            and 8 <= chunk <= 64 and chunk & (chunk - 1) == 0)


def _on_one_tpu() -> bool:
    """On a TPU, outside a mesh (GSPMD cannot partition a Mosaic kernel)."""
    mesh = jax.sharding.get_abstract_mesh()
    return jax.default_backend() == "tpu" and (mesh.empty or mesh.size == 1)


def kda_scan_in_kernel(heads: int, dk: int, dv: int, chunk: int) -> bool:
    """Whether `kda_chunk_scan` runs these shapes in its Pallas launch HERE:
    on a TPU, outside a mesh, where they tile; as `ops.attention._flash_ok`
    chooses the flash kernel. The engine asks it once, for its counter."""
    return _on_one_tpu() and kda_scan_tiles(heads, dk, dv, chunk)


def kda_chunk_scan(q, k, v, g, beta, *, chunk: int, sub: int = KDA_SUB, state=None,
                   interpret: bool = False):
    """q, k [T, H, dk] (float32, normalised by the caller), v [T, H, dv], g
    [T, H, dk] (float32 log decay <= 0 a key channel), beta [T, H] (float32)
    -> (o [T, H, dv] float32, the state after the last position [H, dk, dv]
    float32). `state`: the state before position 0 (None: zeros). A padded
    position has g = 0 and beta = 0. `chunk` positions a step of the scan,
    `sub` (dividing it) a sub-block of the XLA form's in-chunk decays.
    Everything is float32, the matrix products at float32 accuracy: the
    corrected values are differences of near-equal terms and the state is
    read as an operand in every chunk.

    One Pallas launch (`kda_chunk_scan` in a device trace) where
    `kda_scan_in_kernel` says so, the XLA form elsewhere; `interpret=True`
    runs the launch interpreted wherever the shapes tile (tests). Its
    gradient is the XLA form's on either path."""
    T, H, D = k.shape
    q, k, v, g, beta = (a.astype(F32) for a in (q, k, v, g, beta))
    pad = -T % chunk
    if pad:  # g = 0 and beta = 0: steps that change nothing
        q, k, v, g, beta = (jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
                            for a in (q, k, v, g, beta))
    state = jnp.zeros((H, D, v.shape[-1]), F32) if state is None else state.astype(F32)
    if kda_scan_in_kernel(H, D, v.shape[-1], chunk) or (
            interpret and kda_scan_tiles(H, D, v.shape[-1], chunk)):
        o, state = _kda_chunk_scan_pallas(q, k, v, g, beta, state, chunk, sub, interpret)
    else:
        o, state = _kda_chunk_scan_xla(q, k, v, g, beta, state, chunk=chunk, sub=sub)
    return o[:T], state


# ---- the KDA mixer's elementwise work on either side of the scan's launch:
# ---- three small launches in the layout the scan's launch takes, [T, H D]
# ---- with a head's 128 columns one lane tile. What XLA makes of the same
# ---- arithmetic writes the projection twice (bfloat16 and float32), re-lays
# ---- the squares to [T / 8, 8, H, D] for each norm's sum and broadcasts the
# ---- roots back through HBM (PERF.md section 6, PR 53). The XLA forms are the
# ---- callers' (`causal_conv` here, `kda_split` and `kda_out` of
# ---- models/transformer.py): the CPU's path, the decode step's, the reference
# ---- of each launch and what its gradient is taken through.

_LANES = 128       # a head of the launches: one lane tile
MIXER_ROWS = 128   # the smallest row block of the launches
_STRIP = 32        # rows a loop step of a launch's body holds in registers
# a launch's block: the largest of these rows that divides T by as many of
# these lane tiles as divide the columns. On the chip at [2048, 64 x 128] the
# launches read 0.51, 0.65 and 0.28 ms alone, 590-620 GB/s each, and no block
# from a quarter to four times these moved one by more than 5 % (PERF.md
# section 6, PR 53)
_BLOCK_ROWS, _BLOCK_TILES = (512, 256, MIXER_ROWS), (4, 2, 1)


def kda_mixer_tiles(T: int, d_head: int, d_conv: int) -> bool:
    """Whether the mixer's launches can take T positions of these heads: a
    head one 128-lane tile, whole row blocks of at least 128 positions (every
    prefill bucket from 128 up; a decode step's few rows are not), and a
    convolution whose tail fits the eight rows a block sees before itself."""
    return (d_head == _LANES and T >= MIXER_ROWS and T % MIXER_ROWS == 0
            and 1 <= d_conv - 1 <= 8)


def kda_mixer_in_kernel(T: int, d_head: int, d_conv: int) -> bool:
    """Whether the KDA mixer's convolutions, split and gated norm run in
    their Pallas launches HERE: on a TPU, outside a mesh, where they tile (as
    `kda_scan_in_kernel`, which does not depend on T). The engine asks it
    once, at the smallest block, for its counter."""
    return _on_one_tpu() and kda_mixer_tiles(T, d_head, d_conv)


def _largest(options, n: int) -> int:
    return next(o for o in options if n % o == 0)


def _block(T: int, width: int) -> tuple:
    """(rows, columns) of a launch's block over [T, width]."""
    return _largest(_BLOCK_ROWS, T), _LANES * _largest(_BLOCK_TILES, width // _LANES)


def _tiles(ref):
    """The lane tiles of a block: a head each."""
    return (slice(h, h + _LANES) for h in range(0, ref.shape[1], _LANES))


def _strips(rows: int, body, carry=0):
    """`body(the strip's rows, carry)` over a block's rows, `_STRIP` at a time."""
    def one(i, carry):
        return body(pl.ds(pl.multiple_of(i * _STRIP, _STRIP), _STRIP), carry)

    return jax.lax.fori_loop(0, rows // _STRIP, one, carry)


def with_gradient_of(xla, launch, *operands):
    """`launch(*operands)`, differentiated as `xla(*operands)` (which returns
    the same shapes): the launches have no backward pass of their own, as
    `kda_chunk_scan`'s has none."""
    run = jax.custom_vjp(launch)
    run.defvjp(lambda *operands: (launch(*operands), operands),
               lambda operands, cotangents: jax.vjp(xla, *operands)[1](cotangents))
    return run(*operands)


def _kda_conv_kernel(x_ref, halo_ref, tail_ref, w_ref, o_ref):
    """A block [rows, cols] of the depthwise causal convolution: position t
    sums w[K - 1 - s] x[t - s] over s < K, the oldest input first as
    `causal_conv` does. A strip's shifted inputs are sublane rotations of the
    strip behind the eight rows before it; those come from the strip before
    (carried), at a block's start from the block before (`halo_ref`: its last
    sixteen rows) and at the row's start from `tail_ref` ([8, cols], the tail
    in its last K - 1 rows)."""
    K = w_ref.shape[0]
    w = [w_ref[j:j + 1, :] for j in range(K)]
    before = jnp.where(pl.program_id(1) == 0, tail_ref[...],
                       halo_ref[...].astype(F32)[-8:])

    def strip(rows, before):
        x = x_ref[rows, :].astype(F32)
        behind = jnp.concatenate([before, x], axis=0)
        out = pltpu.roll(behind, K - 1, 0)[8:] * w[0]
        for j in range(1, K - 1):
            out = out + pltpu.roll(behind, K - 1 - j, 0)[8:] * w[j]
        o_ref[rows, :] = out + x * w[K - 1]
        return x[-8:]

    _strips(x_ref.shape[0], strip, before)


@functools.partial(jax.jit, static_argnames=("interpret",), inline=True)
def kda_conv_launch(x, tail, w, *, interpret: bool):
    """x [T, C] as projected (bfloat16 on the chip), tail [K - 1, C], w [K, C]
    -> the convolution's float32 sums [T, C], before the SiLU. Grid (column
    blocks of a few lane tiles, row blocks). Jitted for its trace cache, as `_kda_scan_launch`."""
    T, C = x.shape
    K = w.shape[0]
    rows, cols = _block(T, C)
    at = lambda c, r: (r, c)                                            # noqa: E731
    top = lambda c, r: (0, c)                                           # noqa: E731
    return pl.pallas_call(
        _kda_conv_kernel,
        out_shape=jax.ShapeDtypeStruct((T, C), F32),
        grid=(C // cols, T // rows),
        in_specs=[pl.BlockSpec((rows, cols), at),
                  # the sixteen rows (a bfloat16 tile) before the block
                  pl.BlockSpec((16, cols), lambda c, r: (jnp.maximum(r * (rows // 16) - 1, 0), c)),
                  pl.BlockSpec((8, cols), top), pl.BlockSpec((K, cols), top)],
        out_specs=pl.BlockSpec((rows, cols), at),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="kda_conv",
    )(x, x, jnp.pad(tail.astype(F32), ((8 - (K - 1), 0), (0, 0))), w.astype(F32))


def kda_conv(x, tail, w, *, interpret: bool = False):
    """`causal_conv(x.astype(float32), tail, w)` of the KDA mixer's projection
    as one Pallas launch (`kda_conv` in a device trace): x is read as
    projected and the float32 sums are written once; no concatenation with
    the tail and no float32 copy of x. For shapes that `kda_mixer_tiles`."""
    return with_gradient_of(lambda x, tail, w: causal_conv(x.astype(F32), tail, w),
                            functools.partial(kda_conv_launch, interpret=interpret), x, tail, w)


def _kda_split_kernel(q_ref, k_ref, v_ref, qo_ref, ko_ref, vo_ref, *, scale: float):
    """Blocks [rows, a few heads] of the convolved q, k and v: the SiLU, and q
    and k divided by sqrt(|.|^2 + 1e-6) a head (a lane tile: the sum runs over
    the lanes and its root goes back over them in registers), q times `scale`."""
    def strip(rows, carry):
        for x_ref, o_ref, by in ((q_ref, qo_ref, scale), (k_ref, ko_ref, None)):
            for lanes in _tiles(x_ref):
                x = jax.nn.silu(x_ref[rows, lanes])
                x = x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
                o_ref[rows, lanes] = x if by is None else x * by
        vo_ref[rows, :] = jax.nn.silu(v_ref[rows, :])
        return carry

    _strips(q_ref.shape[0], strip)


@functools.partial(jax.jit, static_argnames=("interpret",), inline=True)
def kda_split_launch(x, *, interpret: bool):
    """x [T, 3 H D] float32 (q | k | v, convolved) -> q, k, v [T, H D]
    float32, the layout `_kda_scan_launch` takes. Grid (row blocks, blocks of
    heads): three blocks of x in, three out."""
    T, HD = x.shape[0], x.shape[1] // 3
    rows, cols = _block(T, HD)

    def part(i):  # q, k or v: the i-th third of the columns
        return pl.BlockSpec((rows, cols), lambda r, h: (r, i * (HD // cols) + h))

    return pl.pallas_call(
        functools.partial(_kda_split_kernel, scale=_LANES ** -0.5),
        out_shape=[jax.ShapeDtypeStruct((T, HD), F32)] * 3,
        grid=(T // rows, HD // cols),
        in_specs=[part(0), part(1), part(2)],
        out_specs=[part(0)] * 3,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="kda_split",
    )(x, x, x)


def _kda_gate_norm_kernel(o_ref, gate_ref, w_ref, y_ref, *, eps: float):
    """Blocks [rows, a few heads]: the RMS norm over each head's values (a
    lane tile) times the norm's weight, times sigmoid(gate), in float32; the
    result in the activations' dtype."""
    w = w_ref[...]

    def strip(rows, carry):
        for lanes in _tiles(o_ref):
            o = o_ref[rows, lanes]
            y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * w
            y_ref[rows, lanes] = (y * jax.nn.sigmoid(gate_ref[rows, lanes])).astype(y_ref.dtype)
        return carry

    _strips(o_ref.shape[0], strip)


@functools.partial(jax.jit, static_argnames=("eps", "dtype", "interpret"), inline=True)
def kda_gate_norm_launch(o, gate, w, *, eps: float, dtype, interpret: bool):
    """o, gate [T, H D] float32, w [D] -> [T, H D] in `dtype`: what `out_proj`
    multiplies. Grid (row blocks, blocks of heads)."""
    T, HD = o.shape
    rows, cols = _block(T, HD)
    at = lambda r, h: (r, h)                                            # noqa: E731
    return pl.pallas_call(
        functools.partial(_kda_gate_norm_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct((T, HD), dtype),
        grid=(T // rows, HD // cols),
        in_specs=[pl.BlockSpec((rows, cols), at), pl.BlockSpec((rows, cols), at),
                  pl.BlockSpec((1, _LANES), lambda r, h: (0, 0))],
        out_specs=pl.BlockSpec((rows, cols), at),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="kda_gate_norm",
    )(o, gate, w.astype(F32).reshape(1, _LANES))


def _kda_step(s, decay, k, q, v, beta):
    """One head's step: s [dk, dv], decay, k, q [dk, 1] (columns), v, beta
    [1, dv] (rows) -> (the new state, o [1, dv]). The kernel and the reference
    both call it, a head at a time, so that they agree bit for bit."""
    s = decay * s
    u = beta * (v - jnp.sum(k * s, axis=0, keepdims=True))
    s = s + k * u
    return s, jnp.sum(q * s, axis=0, keepdims=True)


def _kda_update_kernel(layer_ref, rows_ref, count_ref, s_ref, decay_ref, k_ref, q_ref, v_ref,
                       beta_ref, o_ref, y_ref):
    """One row: s [H, dk, dv] -> `_kda_step` a head. `decay`, `k` and `q` come
    transposed, [dk, H]: head h's values are a column, which broadcasts along
    the lanes (dv) without a relayout; `v` and `beta` [H, dv] a row a head.
    Past the schedule's live rows a grid step does nothing (`_update_kernel`)."""
    del layer_ref, rows_ref

    @pl.when(pl.program_id(0) < count_ref[0])
    def _step():
        H = s_ref.shape[2]
        decay, k, q = decay_ref[0], k_ref[0], q_ref[0]              # [dk, H]
        for h in range(H):  # static: a head is sixteen vregs
            new, y = _kda_step(s_ref[0, 0, h], decay[:, h:h + 1], k[:, h:h + 1],
                               q[:, h:h + 1], v_ref[0, pl.ds(h, 1), :],
                               beta_ref[0, pl.ds(h, 1), :])
            o_ref[0, 0, h] = new
            y_ref[0, pl.ds(h, 1), :] = y


def _kda_update_kernel_call(state, layer, rows, count, decay, k, q, v, beta, *,
                            interpret: bool):
    L, R, H, D, Dv = state.shape
    col = lambda r, layer, rows, count: (rows[r], 0, 0)           # noqa: E731
    block = lambda r, layer, rows, count: (layer[0], rows[r], 0, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # the layer, the rows' schedule, the live rows' count
        grid=(R,),
        in_specs=[pl.BlockSpec((1, 1, H, D, Dv), block),
                  pl.BlockSpec((1, D, H), col), pl.BlockSpec((1, D, H), col),
                  pl.BlockSpec((1, D, H), col),
                  pl.BlockSpec((1, H, Dv), col), pl.BlockSpec((1, H, Dv), col)],
        out_specs=[pl.BlockSpec((1, 1, H, D, Dv), block),
                   pl.BlockSpec((1, H, Dv), col)])
    return pl.pallas_call(
        _kda_update_kernel,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((R, H, Dv), F32)],
        grid_spec=grid_spec,
        # operand 3 (after the three prefetched) is the state: updated in place
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # a row's block in and out, double-buffered: 4 x H dk dv floats
            vmem_limit_bytes=max(32 * 2**20, 6 * H * D * Dv * 4)),
        interpret=interpret,
        name="kda_state_update",
    )(layer.reshape(1).astype(jnp.int32), rows, count, state, decay, k, q, v, beta)


def kda_state_update(state, layer, q, k, v, g, beta, *, live=None, schedule=None,
                     impl: str = "reference", interpret: bool = False):
    """One token a row on layer `layer` of `state` [L, R, H, dk, dv]
    (float32): q, k, g [R, H, dk], v [R, H, dv], beta [R, H] -> (the state,
    that layer's LIVE rows updated where they lie and the others as they
    were; o [R, H, dv] float32 = S_t^T q_t, 0 for a row that is not live).
    `live` [R] bool (None: every row); `schedule` is `live_rows(live)` where
    the caller has made it already."""
    R, H, Dv = v.shape
    if live is None:
        live = jnp.ones((R,), bool)
    q, k, v = (a.astype(F32) for a in (q, k, v))
    # a row that is not live: no decay, no correction (the kernel steps only
    # row 0 of a batch with none live, and so changes nothing)
    decay = jnp.exp(jnp.where(live[:, None, None], g.astype(F32), 0.0))
    beta = jnp.broadcast_to(jnp.where(live[:, None], beta.astype(F32), 0.0)[..., None],
                            (R, H, Dv))
    cols = tuple(a.transpose(0, 2, 1) for a in (decay, k, q))       # [R, dk, H]
    if impl == "kernel":
        rows, count = live_rows(live) if schedule is None else schedule
        state, o = _kda_update_kernel_call(state, layer, rows, count, *cols, v, beta,
                                           interpret=interpret)
    elif impl == "reference":
        s = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)

        def head(one):  # a head at a time, as the kernel: the same bits (the
            # products of a batched form were contracted otherwise, an ulp off)
            s_h, decay_h, k_h, q_h, v_h, beta_h = one
            return _kda_step(s_h, decay_h[:, None], k_h[:, None], q_h[:, None],
                             v_h[None], beta_h[None])

        new, o = jax.lax.map(lambda row: jax.lax.map(head, row), (
            s, *(c.transpose(0, 2, 1) for c in cols), v, beta))
        o = o[:, :, 0]
        s = jnp.where(live[:, None, None, None], new, s)
        state = jax.lax.dynamic_update_index_in_dim(state, s, layer, 0)
    else:
        raise ValueError(f"impl must be 'kernel' or 'reference', got {impl!r}")
    return state, jnp.where(live[:, None, None], o, 0.0)
