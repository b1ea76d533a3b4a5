"""Coordinate grids and bilinear sampling.

TPU-native equivalents of the reference tensor utilities
(reference: core/utils/utils.py:57-82): ``coords_grid``, ``bilinear_sampler``
(same semantics as torch ``grid_sample(align_corners=True,
padding_mode='zeros')`` driven in pixel coordinates), and ``upflow8``.

All images are NHWC; coordinate channels are ordered (x, y) like the
reference's flow convention (core/utils/utils.py:74-77).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint


def as_planes(field: jax.Array) -> jax.Array:
    """Hold a (B, H, W, 2) field — coordinates, a flow — physically as
    its two (H, W) planes, (B, 2, H, W); logically nothing changes. A TPU
    tiles an array's two minor dimensions into (8, 128) registers: with
    the 2 components as the lane axis the array is 64 times its size in
    tiles, and every copy and elementwise pass over it pays for that.
    Left to itself the chip's compiler gives a loop's carry or a scan's
    stacked output that form, whatever the producer computed in, even
    one that is logically (2, B, H, W) (PERF.md section 6, PR 29, PR 36)."""
    return with_layout_constraint(field, Layout(major_to_minor=(0, 3, 1, 2)))


def coords_grid(batch: int, ht: int, wd: int, dtype=jnp.float32) -> jax.Array:
    """Pixel-center coordinate grid, shape (batch, ht, wd, 2), channels (x, y).

    Mirrors reference core/utils/utils.py:74-77 (which stacks meshgrid
    reversed so channel 0 is x/width, channel 1 is y/height).
    """
    x = jnp.arange(wd, dtype=dtype)
    y = jnp.arange(ht, dtype=dtype)
    xx, yy = jnp.meshgrid(x, y)  # both (ht, wd)
    grid = jnp.stack([xx, yy], axis=-1)
    return jnp.broadcast_to(grid[None], (batch, ht, wd, 2))


def bilinear_sampler(img: jax.Array, coords: jax.Array) -> jax.Array:
    """Bilinearly sample ``img`` at real-valued pixel ``coords``.

    img:    (N, H, W, C)
    coords: (N, h, w, 2) with channels (x, y) in *pixel* units — (0, 0) is
            the center of the top-left pixel, (W-1, H-1) of the bottom-right.
    returns (N, h, w, C)

    Semantics match ``F.grid_sample(..., align_corners=True,
    padding_mode='zeros')`` as wrapped by the reference
    (core/utils/utils.py:57-71): out-of-range corners contribute zero.
    """
    H, W = img.shape[1], img.shape[2]
    x = coords[..., 0]
    y = coords[..., 1]

    x0f = jnp.floor(x)
    y0f = jnp.floor(y)
    x0 = x0f.astype(jnp.int32)
    y0 = y0f.astype(jnp.int32)
    x1 = x0 + 1
    y1 = y0 + 1

    wx1 = (x - x0f).astype(img.dtype)
    wy1 = (y - y0f).astype(img.dtype)
    wx0 = 1.0 - wx1
    wy0 = 1.0 - wy1

    n = img.shape[0]
    bidx = jnp.arange(n, dtype=jnp.int32)[:, None, None]

    def corner(yi, xi, w):
        valid = (xi >= 0) & (xi < W) & (yi >= 0) & (yi < H)
        xc = jnp.clip(xi, 0, W - 1)
        yc = jnp.clip(yi, 0, H - 1)
        vals = img[bidx, yc, xc]  # (N, h, w, C)
        return vals * (w * valid.astype(img.dtype))[..., None]

    out = (
        corner(y0, x0, wy0 * wx0)
        + corner(y0, x1, wy0 * wx1)
        + corner(y1, x0, wy1 * wx0)
        + corner(y1, x1, wy1 * wx1)
    )
    return out


def _resize_matrix(n_in: int, n_out: int, dtype) -> jax.Array:
    """Static 1-D align_corners interpolation matrix (n_out, n_in).

    Output pixel o samples input coordinate o*(n_in-1)/(n_out-1); linear
    interpolation is the triangular hat kernel relu(1 - |p - t|).
    """
    t = (jnp.linspace(0.0, n_in - 1.0, n_out, dtype=jnp.float32)
         if n_out > 1 else jnp.zeros((1,), jnp.float32))
    pos = jnp.arange(n_in, dtype=jnp.float32)
    return jnp.maximum(0.0, 1.0 - jnp.abs(pos[None, :] - t[:, None])).astype(dtype)


def resize_bilinear_align_corners(img: jax.Array, ht: int, wd: int) -> jax.Array:
    """Bilinear resize with align_corners=True semantics (torch interpolate).

    ``jax.image.resize`` uses half-pixel centers, so we interpolate
    explicitly — and since the target grid is REGULAR, the resize is
    separable into two dense matmuls against static hat matrices (MXU
    work; per-pixel gather sampling is ~2 orders slower on TPU).
    """
    h, w = img.shape[1], img.shape[2]
    ry = _resize_matrix(h, ht, img.dtype)  # (ht, h)
    rx = _resize_matrix(w, wd, img.dtype)  # (wd, w)
    # HIGHEST precision: TPU matmul at DEFAULT truncates operands to
    # bf16, which the elementwise sampler this replaces never did
    out = jnp.einsum("oy,nyxc->noxc", ry, img,
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32).astype(img.dtype)
    return jnp.einsum("px,noxc->nopc", rx, out,
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32).astype(img.dtype)


def upflow8(flow: jax.Array) -> jax.Array:
    """8x bilinear upsample of a flow field, scaling the vectors by 8.

    Reference: core/utils/utils.py:80-82. flow is (N, H, W, 2).
    """
    h, w = flow.shape[1], flow.shape[2]
    return 8.0 * resize_bilinear_align_corners(flow, 8 * h, 8 * w)
