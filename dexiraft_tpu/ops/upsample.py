"""Convex-combination flow upsampling (the learned 8x upsampler).

Reference: core/raft.py:87-98 — a 9-way softmax over 3x3 neighborhoods of
the coarse flow, predicted per 8x8 output sub-pixel. The reference uses
F.unfold; here the 3x3 patch extraction is nine shifted slices of a padded
array and the combination nine multiply-adds between whole slabs.

Layout (PERF.md section 6, PR 29). A TPU tiles an array's two minor
dimensions into (8, 128) registers, so nothing made here has the 9 taps,
an 8 of the sub-pixel grid or the 2 flow components as a minor dimension:

- **lanes carry the output's own width axis, ``8w + j``** (496 at the
  chairs crop, 1024 at Sintel), and the sublanes the sub-pixel row ``i``:
  a mask slab is ``(B, H, 8, 8W)``, which is the output ``(B, 8H, 8W)``
  with its rows split, so the result needs no shuffle at all;
- the 9 taps and the 2 components are *leading* axes: the softmax is
  eight maxima, nine exponentials and eight additions between slabs, the
  combination nine multiply-adds of a slab with a flow plane;
- the one shuffle that cannot be avoided (``8w + j`` mixes a spatial
  index with a mask channel) is paid once, by the mask, in the mask's own
  dtype: its minor pair is swapped (``w`` onto the lanes), ``(j, w)``
  merges, and a 0/1 permutation matrix on the MXU reorders the lanes to
  ``(w, j)``. A flow plane reaches its 8 lanes a pixel the same way (a 0/1
  repeat matrix), so the backward of both is a matmul too and no
  ``(..., W, 8)`` array exists in either direction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dexiraft_tpu.ops.grid import as_planes

_EXACT = jax.lax.Precision.HIGHEST  # fp32 through a 0/1 matrix: no rounding


def _mask_rows(mask: jax.Array) -> jax.Array:
    """(B, H, W, 9*8*8) logits -> (9, B, H, 8, 8W): tap k, rows i, lanes
    8w + j, in the mask's dtype (a permutation: exact in any dtype)."""
    b, h, w, _ = mask.shape
    mt = jnp.swapaxes(mask, -1, -2).reshape(b, h, 9, 8, 8 * w)  # lanes (j, w)
    lane = jnp.arange(8 * w)
    to_wj = ((lane % w) * 8 + lane // w)[:, None] == lane[None, :]
    return jnp.einsum(
        "bhkiy,yx->kbhix", mt, to_wj.astype(mask.dtype),
        precision=_EXACT if mask.dtype == jnp.float32 else None,
        preferred_element_type=mask.dtype)


def _repeat_lanes(planes: jax.Array) -> jax.Array:
    """(..., W') -> (..., 8W'): each value on 8 adjacent lanes."""
    wp = planes.shape[-1]
    rep = jnp.arange(wp)[:, None] == jnp.arange(8 * wp)[None, :] // 8
    return jnp.einsum("...w,wx->...x", planes, rep.astype(planes.dtype),
                      precision=_EXACT)


def convex_combine(flow_padded: jax.Array, mask: jax.Array) -> jax.Array:
    """The upsampler behind its border: ``flow_padded`` is (B, H+2, W+2, 2),
    8 x the coarse flow with the one-pixel border already in place (zeros
    in `upsample_flow_convex`, the neighbours' rows in `parallel/halo.py`).
    """
    b, h, w, _ = mask.shape
    m = _mask_rows(mask).astype(jnp.float32)  # (9, B, H, 8, 8W)
    planes = _repeat_lanes(jnp.moveaxis(flow_padded.astype(jnp.float32), -1, 0))

    top = m[0]
    for k in range(1, 9):
        top = jnp.maximum(top, m[k])
    top = jax.lax.stop_gradient(top)  # softmax is invariant to the shift
    total = weighted = 0.0
    # Row-major 3x3 taps, matching F.unfold's kernel ordering (dy, then dx).
    for k in range(9):
        dy, dx = divmod(k, 3)
        e = jnp.exp(m[k] - top)  # (B, H, 8, 8W)
        patch = planes[:, :, dy:dy + h, 8 * dx:8 * (dx + w)]  # (2, B, H, 8W)
        total = total + e
        weighted = weighted + e * patch[:, :, :, None, :]
    up = weighted / total  # (2, B, H, 8, 8W): rows 8h + i, lanes 8w + j
    out = jnp.moveaxis(up.reshape(2, b, 8 * h, 8 * w), 0, -1)
    # The contract's minor axis is the 2 components. Physically the result
    # stays (B, 2, 8H, 8W), which is what it was computed as: left to
    # itself the chip's compiler gave the scan's stacked predictions the
    # 2 as their lane axis (64x padding, 8.4 GB at the chairs crop).
    return as_planes(out)


@jax.named_scope("upsample_flow_convex")
def upsample_flow_convex(flow: jax.Array, mask: jax.Array) -> jax.Array:
    """Upsample (B, H, W, 2) flow to (B, 8H, 8W, 2) by convex combination.

    mask: (B, H, W, 576) raw logits from the update block's mask head,
    laid out as 9 * (8*8) — kernel-position-major like the reference's
    ``mask.view(N, 1, 9, 8, 8, H, W)`` (core/raft.py:90), softmaxed over
    the 9 taps. Flow vectors are scaled by 8 (coarse pixels -> fine pixels).
    The mask may come in the mask head's dtype: it is relaid in that dtype
    and the softmax and the combination are fp32.
    """
    return convex_combine(
        jnp.pad(8.0 * flow, ((0, 0), (1, 1), (1, 1), (0, 0))), mask)
