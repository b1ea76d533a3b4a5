"""What every decoder layer shares: RMSNorm, the rotary embedding on
interleaved pairs and on half-rotated ones, the SwiGLU MLP, the MLP of
two matrices and the fp32-master parameter."""

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


class Weights(nn.Module):
    """Base of the LM modules: parameters live in fp32 (the masters the
    optimizer updates) and `w(...)` hands a copy in the compute dtype."""

    dtype: Any = jnp.float32
    init_std: float = 0.02

    def w(self, name: str, shape) -> jax.Array:
        return self.param(name, nn.initializers.normal(self.init_std), shape,
                          jnp.float32).astype(self.dtype)


# rows of one block of a SwiGLU whose `[rows, width]` intermediates pass
# `_WHOLE_BYTES` each: `head_loss`'s block
_ROW_BLOCK = 8192
_WHOLE_BYTES = 512 * 1024 * 1024


class SwiGLU(Weights):
    """W_down(silu(W_gate x) * W_up x).

    Gate, up and their product are `[rows, width]` each, and the
    backward holds their three cotangents beside them. Where one of them
    passes `_WHOLE_BYTES` (32,768 rows of 11,008 in bf16 are 688 MiB, six
    of them 4.3 GB; 32,768 rows of 6,144 are 384 MiB and stay whole) and
    the rows are whole blocks of `_ROW_BLOCK`, the rows are walked a
    block at a time, each a `jax.checkpoint`: a block's intermediates
    exist for that block only, forward and backward. Chosen from the
    shapes; the result is the same."""

    width: int = 0

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        d = x.shape[-1]
        n = x.size // d
        if (n * self.width * x.dtype.itemsize <= _WHOLE_BYTES
                or n % _ROW_BLOCK):
            gate = x @ self.w("w_gate", (d, self.width))
            up = x @ self.w("w_up", (d, self.width))
            return (jax.nn.silu(gate) * up) @ self.w("w_down",
                                                     (self.width, d))
        w_gate, w_up = (self.w(name, (d, self.width))
                        for name in ("w_gate", "w_up"))
        w_down = self.w("w_down", (self.width, d))

        @jax.checkpoint
        def rows(h):
            return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down

        return jax.lax.map(rows, x.reshape(-1, _ROW_BLOCK, d)
                           ).reshape(x.shape)


class ActMLP(Weights):
    """W_down act(W_up x): an MLP of two matrices, no gate."""

    width: int = 0
    act: Any = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        d = x.shape[-1]
        return self.act(x @ self.w("w_up", (d, self.width))) @ self.w(
            "w_down", (self.width, d))
