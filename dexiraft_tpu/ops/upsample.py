"""Convex-combination flow upsampling (the learned 8x upsampler).

Reference: core/raft.py:87-98 — a 9-way softmax over 3x3 neighborhoods of
the coarse flow, predicted per 8x8 output sub-pixel. The reference uses
F.unfold; here the 3x3 patch extraction is nine shifted slices of a padded
array (XLA fuses these into one loop) and the combination is an einsum.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("upsample_flow_convex")
def upsample_flow_convex(flow: jax.Array, mask: jax.Array) -> jax.Array:
    """Upsample (B, H, W, 2) flow to (B, 8H, 8W, 2) by convex combination.

    mask: (B, H, W, 576) raw logits from the update block's mask head,
    laid out as 9 * (8*8) — kernel-position-major like the reference's
    ``mask.view(N, 1, 9, 8, 8, H, W)`` (core/raft.py:90), softmaxed over
    the 9 taps. Flow vectors are scaled by 8 (coarse pixels -> fine pixels).
    """
    b, h, w, _ = flow.shape
    m = mask.reshape(b, h, w, 9, 8, 8)
    m = jax.nn.softmax(m, axis=3)

    fp = jnp.pad(8.0 * flow, ((0, 0), (1, 1), (1, 1), (0, 0)))
    # Row-major 3x3 taps, matching F.unfold's kernel ordering (dy, then dx).
    patches = jnp.stack(
        [fp[:, dy : dy + h, dx : dx + w, :] for dy in range(3) for dx in range(3)],
        axis=3,
    )  # (B, H, W, 9, 2)

    up = jnp.einsum("bhwkij,bhwkc->bhwijc", m, patches)  # (B, H, W, 8, 8, 2)
    return up.transpose(0, 1, 3, 2, 4, 5).reshape(b, 8 * h, 8 * w, 2)
