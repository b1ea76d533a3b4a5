"""What every decoder layer shares: RMSNorm, the rotary embedding on
interleaved pairs and on half-rotated ones, the SwiGLU MLP and the
fp32-master parameter."""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    """x / sqrt(mean(x^2) + eps) * gain, the statistics in fp32, the
    result in x's dtype."""
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv * gain.astype(jnp.float32)).astype(x.dtype)


def rope_interleaved(x: jax.Array, positions: jax.Array,
                     theta: float) -> jax.Array:
    """Rotate the pairs (2i, 2i+1) of the last axis by
    `position * theta^(-2i/d)` (`rope_interleave: true`). x
    `[B, S, ..., d]`, positions `[B, S]`; the angles are fp32."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None] * freq        # [B, S, d/2]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[-1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def rope_half(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate the pairs (i, i + d/2) of the last axis by
    `position * theta^(-2i/d)`: the half-rotation layout
    (`x * cos + rotate_half(x) * sin`). x `[B, S, heads, d]`, positions
    `[B, S]`; the angles are fp32."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, :, None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    lo, hi = x32[..., :d // 2], x32[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                           axis=-1).astype(x.dtype)


def normal_init(std: float):
    return nn.initializers.normal(stddev=std)


class Weights(nn.Module):
    """Base of the LM modules: parameters live in fp32 (the masters the
    optimizer updates) and `w(...)` hands a copy in the compute dtype."""

    dtype: Any = jnp.float32
    init_std: float = 0.02

    def w(self, name: str, shape, init=None) -> jax.Array:
        init = init or normal_init(self.init_std)
        return self.param(name, init, shape, jnp.float32).astype(self.dtype)


class SwiGLU(Weights):
    """W_down(silu(W_gate x) * W_up x)."""

    width: int = 0

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        d = x.shape[-1]
        gate = x @ self.w("w_gate", (d, self.width))
        up = x @ self.w("w_up", (d, self.width))
        return (jax.nn.silu(gate) * up) @ self.w("w_down", (self.width, d))
