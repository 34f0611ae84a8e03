"""Normalization ops.

RMSNorm is bandwidth-bound elementwise+reduce; XLA fuses it into adjacent
ops on TPU, so the default path is plain jnp (a handwritten Pallas kernel
buys nothing here and would block fusion with the surrounding matmul).
Statistics are computed in float32 regardless of input dtype.
"""
from __future__ import annotations

import jax.numpy as jnp


def rms_norm(x, weight, *, eps: float = 1e-6):
    """x * rsqrt(mean(x^2)) * weight, stats in f32, output in x.dtype."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jnp.reciprocal(jnp.sqrt(var + eps))
    return (y * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x, weight, bias, *, eps: float = 1e-5):
    """(x - mean) * rsqrt(var + eps) * weight + bias, stats in f32,
    output in x.dtype."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jnp.reciprocal(jnp.sqrt(var + eps))
    return (y * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dtype)
