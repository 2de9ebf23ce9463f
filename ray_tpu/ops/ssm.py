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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


def causal_conv(x, tail, w, b):
    """Depthwise causal convolution of x [T, C] after the `K - 1` inputs
    `tail` [K - 1, C] that came before it (zeros at a row's start), weights w
    [K, C] (w[K - 1] multiplies the current input), bias b [C]: K shifted
    products, float32 sums. Returns [T, C] in x's dtype."""
    K, T = w.shape[0], x.shape[0]
    padded = jnp.concatenate([tail.astype(x.dtype), x], axis=0).astype(F32)
    out = b.astype(F32)[None]
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
