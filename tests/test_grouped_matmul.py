"""`ops.grouped_matmul`: the Pallas kernel in interpret mode against
`jax.lax.ragged_dot` (what every platform but the TPU lowers, and what the
kernel replaced) and against a float32 loop over the groups.

The real widths compiled for a described v5e are tests/test_tpu_compile.py's;
the comparison on the chip is `chip_smoke.py`'s.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu import ops
from ray_tpu.ops.grouped_matmul import _VMEM_LIMIT, grouped_matmul_kernel, tiles_for


def _loop_f32(lhs, rhs, sizes):
    """Group by group in float32; rows past the groups' sum stay zero."""
    out, row = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32), 0
    for g, n in enumerate(np.asarray(sizes)):
        out[row:row + n] = np.asarray(lhs[row:row + n], np.float32) @ np.asarray(
            rhs[g], np.float32)
        row += n
    return out


def _stacked(layer, sizes, layers=3):
    """[L*E] sizes with one layer's non-zero: the layer scan's form."""
    out = np.zeros((layers, len(sizes)), np.int32)
    out[layer] = sizes
    return out.reshape(-1)


# name: (M, K, N, group sizes, tiles or None for the shapes' own)
CASES = {
    "even_groups": (256, 256, 256, [64] * 4, (64, 128, 128)),
    "all_rows_in_one_group": (256, 256, 256, [0, 0, 256, 0], (64, 128, 128)),
    "empty_groups_start_middle_end": (256, 256, 256, [0, 0, 100, 0, 0, 56, 100, 0], (64, 256, 128)),
    "boundary_inside_a_row_tile": (256, 128, 256, [3, 70, 1, 182], (128, 128, 256)),
    "three_groups_in_one_tile": (128, 128, 128, [10, 20, 30, 68], (128, 128, 128)),
    "rows_past_the_groups_sum": (256, 128, 128, [40, 0, 50], (64, 128, 128)),
    "tail_fills_whole_tiles": (256, 128, 128, [10, 5], (64, 128, 128)),
    "fewer_rows_than_a_tile": (48, 128, 128, [16, 0, 32], None),
    "rows_no_multiple_of_the_tile": (200, 128, 128, [150, 50], (128, 128, 128)),
    "stacked_first_layer": (128, 128, 128, _stacked(0, [30, 0, 90, 8]), (64, 128, 128)),
    "stacked_middle_layer": (128, 128, 128, _stacked(1, [30, 0, 90, 8]), (64, 128, 128)),
    "stacked_last_layer": (128, 128, 128, _stacked(2, [30, 0, 90, 8]), (64, 128, 128)),
    # the two document cells' K and N (gate/up and down), few rows, the
    # tiles the kernel takes from those shapes
    "kimi_vl_a3b_gate": (64, 2048, 1408, [20, 0, 44], None),
    "kimi_vl_a3b_down": (64, 1408, 2048, [20, 0, 44], None),
    "mixtral_8x7b_gate": (32, 4096, 14336, [32, 0], None),
    "mixtral_8x7b_down": (32, 14336, 4096, [0, 32], None),
}


@pytest.mark.parametrize("case", list(CASES) + ["moe_sorted"])
def test_kernel_equals_ragged_dot_and_the_float32_loop(case, monkeypatch):
    if case == "moe_sorted":
        # the dispatch through the kernel and through ragged_dot, stacked
        N, k, E, L, D, F = 64, 2, 4, 2, 128, 256
        ks = jax.random.split(jax.random.PRNGKey(3), 6)
        x = jax.random.normal(ks[0], (N, D), jnp.bfloat16)
        idx = jax.random.randint(ks[1], (N, k), 0, E)
        w = jax.random.uniform(ks[2], (N, k), jnp.float32, 0.1, 1.0)
        gate, up = (jax.random.normal(kk, (L, E, D, F), jnp.bfloat16) * 0.1 for kk in ks[3:5])
        down = jax.random.normal(ks[5], (L, E, F, D), jnp.bfloat16) * 0.1
        want = ops.moe_sorted(x, idx, w, gate, up, down, layer=jnp.int32(1))
        monkeypatch.setattr("ray_tpu.ops.moe.grouped_matmul", lambda a, b, s, groups: (
            grouped_matmul_kernel(a, b, s, groups, interpret=True)))
        got = ops.moe_sorted(x, idx, w, gate, up, down, layer=jnp.int32(1))
        scale = float(jnp.abs(want.astype(jnp.float32)).max())
        # bfloat16 rounding, of h and of the output
        assert float(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)).max()) \
            <= 2 * 2.0 ** -8 * scale
        return
    M, K, N, sizes, tiles = CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(M + K + N), 2)
    lhs = jax.random.normal(ks[0], (M, K), jnp.bfloat16)
    rhs = jax.random.normal(ks[1], (len(sizes), K, N), jnp.bfloat16)
    sizes = jnp.asarray(sizes, jnp.int32)
    got = grouped_matmul_kernel(lhs, rhs, sizes, tiles=tiles, interpret=True)
    assert got.shape == (M, N) and got.dtype == jnp.bfloat16
    got = np.asarray(got, np.float32)
    exact = _loop_f32(lhs, rhs, sizes)
    # one rounding to bfloat16 of a float32 sum: half a spacing, 2**-8 relative
    np.testing.assert_allclose(got, exact, rtol=2.0 ** -8, atol=2.0 ** -8 * np.sqrt(K) / 64)
    ragged = np.asarray(jax.lax.ragged_dot(lhs, rhs, sizes), np.float32)
    # both round the same sums, taken in another order: equal but where a sum
    # lies at a rounding boundary
    assert (got == ragged).mean() > 0.99
    np.testing.assert_allclose(got, ragged, rtol=2.0 ** -7, atol=2.0 ** -8 * np.sqrt(K) / 64)
    rows_with_group = int(sizes.sum())
    assert not got[rows_with_group:].any()


def test_off_the_tpu_it_is_ragged_dot_and_differentiates():
    """A program lowered for the CPU holds `ragged_dot` and no kernel; the
    gradients are `ragged_dot`'s own."""
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    lhs = jax.random.normal(ks[0], (32, 16))
    rhs = jax.random.normal(ks[1], (3, 16, 8))
    sizes = jnp.asarray([10, 0, 22], jnp.int32)
    assert "tpu_custom_call" not in jax.jit(ops.grouped_matmul).lower(lhs, rhs, sizes).as_text()
    assert (ops.grouped_matmul(lhs, rhs, sizes) == jax.lax.ragged_dot(lhs, rhs, sizes)).all()

    def loss(fn):
        return lambda a, b: (fn(a, b, sizes) ** 2).sum()

    got = jax.grad(loss(ops.grouped_matmul), argnums=(0, 1))(lhs, rhs)
    want = jax.grad(loss(jax.lax.ragged_dot), argnums=(0, 1))(lhs, rhs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6)


# kimi-vl-a3b's and Mixtral's 1,024-token chunks (96 and 256 rows a group), a
# 512-token chunk of Mixtral's, all rows in one group, a toy
@pytest.mark.parametrize("M,K,N,groups,tm", [
    (6144, 2048, 1408, 64, 128), (6144, 1408, 2048, 64, 128), (2048, 4096, 14336, 8, 256),
    (2048, 14336, 4096, 8, 256), (1024, 4096, 14336, 8, 128), (2048, 4096, 14336, 1, 256),
    (48, 100, 72, 4, 48)])
def test_tiles_come_from_the_shapes_and_fit(M, K, N, groups, tm):
    got_tm, tk, tn = tiles_for(M, K, N, groups)
    assert K % tk == 0 and N % tn == 0 and got_tm == tm
    assert (tn % 128 == 0 or tn == N) and (tk % 128 == 0 or tk == K)
    # the pipeline holds each block twice
    assert 2 * (tm * K + K * tn + tm * tn) * 2 < _VMEM_LIMIT
