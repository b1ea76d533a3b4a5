"""Short causal depthwise convolutions of a packed row, within
documents. Two mixers use them, with one mask and one way of shifting
(`_taps_sum`): the doubly gated one of an `Lfm2MoeConfig` between its
two projections (models/lm/attention.py `ShortConv`; the equations
below), and Mamba-2's ahead of its scan, `silu(conv_L(x) + bias)` with
no gate (`causal_conv`, models/lm/attention.py `Mamba2`).

Row positions n = 0..T-1 with document ids d(n) (pad is 0); B, C, z the
thirds of the in-projection, `[.., T, H]`; taps k `[H, L]`, depthwise,
no bias, k[:, L-1] on the position itself:

    a_n = B_n * z_n
    c_n = sum_{j=0..L-1} k[:, j] * a_{n-(L-1)+j} * [n-(L-1)+j >= 0 and
                                                    d(n-(L-1)+j) = d(n)]
    out_n = C_n * c_n

A tap that would read another document, or before the row, reads zero:
a document's outputs are what it gives alone, wherever it lies in the
row. With k[:, L-1] = 1 and the other taps 0, out = C * B * z.

Plain XLA: `a` shifted down the row by 1..L-1 positions (a pad in front,
the tail cut), each shift under the mask of the document ids shifted
alike, multiplied by its tap and summed in fp32. The shifts, the masks
and both gates are elementwise, so the compiler can make one pass of
them that reads three `[T, H]` arrays and writes one; the backward is
the same shifts the other way, by autodiff.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _shifted(x: jax.Array, by: int, fill) -> jax.Array:
    """x `[B, T, ...]` moved `by` positions down the row: entry n is
    x[n - by], and `fill` where that is before the row."""
    pad = [(0, 0)] * x.ndim
    pad[1] = (by, 0)
    return jnp.pad(x, pad, constant_values=fill)[:, :x.shape[1]]


def _taps_sum(a: jax.Array, taps: jax.Array,
              segment_ids: jax.Array) -> jax.Array:
    """c of the module docstring from a `[B, T, H]` fp32 and taps
    `[H, L]` fp32: the taps' sum under the document mask."""
    length = taps.shape[1]
    total = a * taps[:, length - 1]
    for back in range(1, length):
        same = _shifted(segment_ids, back, -1) == segment_ids
        total = total + jnp.where(same[..., None], _shifted(a, back, 0.0),
                                  0.0) * taps[:, length - 1 - back]
    return total


def gated_short_conv(b: jax.Array, c: jax.Array, z: jax.Array,
                     taps: jax.Array, segment_ids: jax.Array) -> jax.Array:
    """b, c, z `[B, T, H]`, taps `[H, L]`, segment_ids `[B, T]` ->
    `[B, T, H]` in b's dtype (module docstring). The products and the
    sum over the taps are fp32."""
    taps = taps.astype(jnp.float32)
    total = _taps_sum(b.astype(jnp.float32) * z.astype(jnp.float32), taps,
                      segment_ids)
    return (c.astype(jnp.float32) * total).astype(b.dtype)


def causal_conv(x: jax.Array, taps: jax.Array, bias: jax.Array,
                segment_ids: jax.Array) -> jax.Array:
    """silu(c + bias), c the taps' sum of x `[B, T, H]` under the same
    mask (taps `[H, L]`, bias `[H]`): `[B, T, H]` in x's dtype, the sum,
    the bias and the SiLU in fp32."""
    total = _taps_sum(x.astype(jnp.float32), taps.astype(jnp.float32),
                      segment_ids)
    return jax.nn.silu(total + bias.astype(jnp.float32)).astype(x.dtype)


def taps_masked(segment_ids: jax.Array, length: int) -> jax.Array:
    """The taps of the non-pad positions of `segment_ids` `[B, T]` that a
    document's start or the row's start zeroes, of one layer: int32."""
    real = segment_ids > 0
    return sum(
        (jnp.sum(real & (_shifted(segment_ids, back, -1) != segment_ids),
                 dtype=jnp.int32) for back in range(1, length)),
        start=jnp.zeros((), jnp.int32))
