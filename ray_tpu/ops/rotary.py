"""Rotary position embeddings (RoPE), half-rotation layout.

Computed in float32 and cast back; `positions` is passed explicitly so
sequence-parallel shards can feed their global offsets.
"""
from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN (arXiv:2309.00071) as a model's `config.json` states it
    (`rope_type: yarn`): the pairs that turn fewer than `beta_slow` times
    over the original context are slowed by `factor`, those that turn
    more than `beta_fast` times keep their frequency, the pairs between
    are blended linearly by index; cos and sin are multiplied by
    `attention_factor` (0: 0.1 ln(factor) + 1), so scores carry its
    square."""
    factor: float
    original_max_len: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 0.0

    @property
    def cos_sin_factor(self) -> float:
        return self.attention_factor or 0.1 * math.log(self.factor) + 1.0

    def ramp(self, head_dim: int, theta: float) -> np.ndarray:
        """(head_dim // 2,) in [0, 1]: the share of the slowed frequency
        in pair i."""
        def turns_at(n):      # the pair index that turns n times over
            return head_dim * math.log(
                self.original_max_len / (2 * math.pi * n)) \
                / (2 * math.log(theta))

        low = max(math.floor(turns_at(self.beta_fast)), 0)
        high = min(math.ceil(turns_at(self.beta_slow)), head_dim - 1)
        if low == high:
            high += 0.001
        return np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                       / (high - low), 0.0, 1.0)


def rope_frequencies(head_dim: int, *, theta: float = 10000.0,
                     yarn: YarnScaling | None = None):
    """Inverse frequencies, shape (head_dim // 2,)."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if yarn is None:
        return inv
    r = yarn.ramp(head_dim, theta)
    return inv * (1.0 - r) + inv / yarn.factor * r


def apply_rope(x, positions, *, theta: float = 10000.0,
               yarn: YarnScaling | None = None, rotary_dim: int = 0):
    """x: (B, T, H, D); positions: (B, T) or (T,) int32 global positions.
    `rotary_dim` (0: all of D): the rope turns the first that many
    dimensions of a head, paired and with frequencies (YaRN's ramp too)
    as a head of that size would have them, and passes the rest through."""
    if 0 < rotary_dim < x.shape[-1]:
        return jnp.concatenate([
            apply_rope(x[..., :rotary_dim], positions, theta=theta,
                       yarn=yarn), x[..., rotary_dim:]], axis=-1)
    d = x.shape[-1]
    inv_freq = rope_frequencies(d, theta=theta, yarn=yarn)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # (B, T, D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if yarn is not None:
        cos, sin = cos * yarn.cos_sin_factor, sin * yarn.cos_sin_factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
