"""Rotary position embeddings (RoPE), half-rotation convention (Llama-style),
plain or with YaRN's rescaled frequencies (Peng et al. 2023)."""

from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN as `transformers` computes it (`_compute_yarn_parameters`): the
    frequencies that turn more than `beta_fast` times over
    `original_max_position` positions are kept, those that turn less than
    `beta_slow` times are divided by `factor`, a linear ramp between; cos
    and sin are both scaled by the attention factor (default 0.1 ln(factor)
    + 1), so the scores carry its square."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float | None = None

    @property
    def scale(self) -> float:
        if self.attention_factor is not None:
            return self.attention_factor
        return 0.1 * math.log(self.factor) + 1.0

    def ramp_bounds(self, head_dim: int, theta: float) -> tuple:
        """(low, high): the ramp runs from frequency index `low` (kept) to
        `high` (divided by `factor`), whole indices (`truncate` on)."""
        def index(turns):
            return (head_dim * math.log(self.original_max_position / (turns * 2 * math.pi))
                    / (2 * math.log(theta)))

        low, high = math.floor(index(self.beta_fast)), math.ceil(index(self.beta_slow))
        return max(low, 0), min(high, head_dim - 1)


def rope_frequencies(head_dim: int, max_len: int, *, theta: float = 10000.0,
                     dtype=jnp.float32, yarn: Yarn | None = None):
    """[max_len, head_dim//2] cos/sin tables."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if yarn is not None:
        low, high = yarn.ramp_bounds(head_dim, theta)
        ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                        / max(high - low, 1e-3), 0.0, 1.0)
        inv_freq = inv_freq * (1.0 - ramp) + inv_freq / yarn.factor * ramp
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    cos, sin = jnp.cos(freqs), jnp.sin(freqs)
    if yarn is not None:
        cos, sin = cos * yarn.scale, sin * yarn.scale
    return cos.astype(dtype), sin.astype(dtype)


def apply_rope(x, cos, sin, *, positions=None):
    """x: [B, T, H, D]; cos/sin: [max_len, D//2]; positions: [B, T] or [T]."""
    B, T, H, D = x.shape
    if positions is None:
        c = cos[:T][None, :, None, :]
        s = sin[:T][None, :, None, :]
    else:
        c = cos[positions]
        s = sin[positions]
        if c.ndim == 2:  # [T, D/2] → [1, T, 1, D/2]
            c, s = c[None, :, None, :], s[None, :, None, :]
        else:            # [B, T, D/2] → [B, T, 1, D/2]
            c, s = c[:, :, None, :], s[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.astype(x.dtype)
