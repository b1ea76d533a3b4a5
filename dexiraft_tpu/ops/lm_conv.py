"""The doubly gated short causal convolution of a packed row, within
documents: the `conv` mixer of an `Lfm2MoeConfig` between its two
projections (models/lm/attention.py `ShortConv`).

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


def gated_short_conv(b: jax.Array, c: jax.Array, z: jax.Array,
                     taps: jax.Array, segment_ids: jax.Array) -> jax.Array:
    """b, c, z `[B, T, H]`, taps `[H, L]`, segment_ids `[B, T]` ->
    `[B, T, H]` in b's dtype (module docstring). The products and the
    sum over the taps are fp32."""
    length = taps.shape[1]
    taps = taps.astype(jnp.float32)
    a = b.astype(jnp.float32) * z.astype(jnp.float32)
    total = a * taps[:, length - 1]
    for back in range(1, length):
        same = _shifted(segment_ids, back, -1) == segment_ids
        total = total + jnp.where(same[..., None], _shifted(a, back, 0.0),
                                  0.0) * taps[:, length - 1 - back]
    return (c.astype(jnp.float32) * total).astype(b.dtype)


def taps_masked(segment_ids: jax.Array, length: int) -> jax.Array:
    """The taps of the non-pad positions of `segment_ids` `[B, T]` that a
    document's start or the row's start zeroes, of one layer: int32."""
    real = segment_ids > 0
    return sum(
        (jnp.sum(real & (_shifted(segment_ids, back, -1) != segment_ids),
                 dtype=jnp.int32) for back in range(1, length)),
        start=jnp.zeros((), jnp.int32))
