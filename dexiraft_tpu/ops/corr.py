"""All-pairs 4D correlation volume: build, pyramid, windowed lookup.

TPU-native re-design of the reference centerpiece (core/corr.py:12-60):
the volume is one big batched matmul (MXU-friendly), the pyramid is
slice+reshape-mean 2x2 average pooling (NOT lax.reduce_window — see
avg_pool_2x2), and the per-iteration lookup gathers a (2r+1)^2 bilinear
window per pixel per level.

Layouts: feature maps are (B, H, W, D); the flattened volume is
(B*H*W, H_l, W_l, 1) per level — same flattening the reference uses so the
lookup is a plain batched 2D sample.

This module is the materialized path; the memory-efficient on-demand
equivalent of the reference's alt_cuda_corr CUDA kernel
(alt_cuda_corr/correlation_kernel.cu) is a separate op
(see dexiraft_tpu.ops.local_corr once built).
"""

from __future__ import annotations

from typing import List, Optional

import flax.struct
import jax
import jax.numpy as jnp

from dexiraft_tpu.ops.quant import store_corr


@flax.struct.dataclass
class CorrPyramid:
    """Correlation pyramid + lookup geometry.

    A pytree whose leaves are only the level arrays (and the per-level
    quantization scales, when present); the geometry ints are static aux
    data, so instances are safe to pass through jit boundaries and
    lax.scan carries without tracer leakage into shape arithmetic.
    """

    levels: tuple  # tuple of (B*H*W, H_l, W_l, 1) arrays (fp32/bf16/int8)
    batch: int = flax.struct.field(pytree_node=False)
    ht: int = flax.struct.field(pytree_node=False)
    wd: int = flax.struct.field(pytree_node=False)
    radius: int = flax.struct.field(pytree_node=False)
    # per-level fp32 scalar dequantization scales for int8 storage; None
    # for the scale-free dtypes (ops/quant.py). A pytree leaf tuple.
    scales: Optional[tuple] = None

    def __call__(self, coords: jax.Array) -> jax.Array:
        return corr_lookup(self, coords)


def all_pairs_correlation(fmap1: jax.Array, fmap2: jax.Array) -> jax.Array:
    """corr[b, i, j, k, l] = <fmap1[b,i,j,:], fmap2[b,k,l,:]> / sqrt(D).

    fmap1, fmap2: (B, H, W, D). Returns (B*H*W, H, W, 1) in float32 —
    the flattened layout the pyramid/lookup consume.
    Reference: core/corr.py:52-60 (matmul + /sqrt(dim)), fp32 like
    core/raft.py:139-142.
    """
    b, h, w, d = fmap1.shape
    h2, w2 = fmap2.shape[1:3]  # may differ from (h, w) when the query
    # axis is sharded (context parallelism, parallel/context.py)
    f1 = fmap1.reshape(b, h * w, d).astype(jnp.float32)
    f2 = fmap2.reshape(b, h2 * w2, d).astype(jnp.float32)
    corr = jnp.einsum("bnd,bmd->bnm", f1, f2, preferred_element_type=jnp.float32)
    corr = corr / jnp.sqrt(jnp.float32(d))
    return corr.reshape(b * h * w, h2, w2, 1)


def avg_pool_2x2(x: jax.Array) -> jax.Array:
    """2x2 stride-2 average pool over the spatial dims of (N, H, W, C).

    VALID padding so odd trailing rows/cols are dropped — exactly
    torch.nn.functional.avg_pool2d(x, 2, stride=2) (core/corr.py:26).

    Implemented as slice + reshape + mean rather than lax.reduce_window:
    identical numerics, cleanly differentiable in reverse mode (reduce_window
    linearization is unsupported on some backends), and XLA lowers it to the
    same windowed reduction.
    """
    n, h, w, c = x.shape
    h2, w2 = h // 2, w // 2
    x = x[:, : 2 * h2, : 2 * w2, :]
    x = x.reshape(n, h2, 2, w2, 2, c)
    return x.mean(axis=(2, 4))


@jax.named_scope("build_corr_pyramid")
def build_corr_pyramid(
    fmap1: jax.Array, fmap2: jax.Array, num_levels: int = 4, radius: int = 4,
    dtype: str = "fp32",
) -> CorrPyramid:
    """Materialize the all-pairs volume and its average-pool pyramid.

    Reference: core/corr.py:13-27. Level i has shape
    (B*H*W, H >> i, W >> i, 1) (floor division via VALID pooling).

    The reference pools the VOLUME; correlation is linear in fmap2, so
    avg-pooling the volume's target dims equals correlating against the
    avg-pooled fmap2 — mathematically identical (mean of dots = dot of
    mean), but each level is then a direct MXU matmul instead of strided
    2x2 pooling passes over the ~200 MB level-0 volume, which on TPU cost
    more than the matmul itself.

    ``dtype`` is the STORAGE precision of the pyramid ("fp32", "bf16",
    "int8" — ops/quant.py): correlation is always computed fp32, then
    each level is stored low-precision (per-level scale for int8) and
    dequantized inside the lookup's matmuls. This halves/quarters the
    HBM bytes every refinement iteration streams — the loop's bandwidth
    term (docs/perf.md "Correlation memory & precision").
    """
    b, h, w, _ = fmap1.shape
    f2 = fmap2
    levels: List[jax.Array] = []
    scales: List[Optional[jax.Array]] = []
    for _ in range(num_levels):
        lvl, scale = store_corr(all_pairs_correlation(fmap1, f2), dtype)
        levels.append(lvl)
        scales.append(scale)
        f2 = avg_pool_2x2(f2.astype(jnp.float32))
    return CorrPyramid(
        levels=tuple(levels), batch=b, ht=h, wd=w, radius=radius,
        scales=tuple(scales) if dtype == "int8" else None)


def _window_delta(radius: int, dtype=jnp.float32) -> jax.Array:
    """(2r+1, 2r+1, 2) offset lattice, channels (x-offset, y-offset).

    Matches the reference's ordering EXACTLY (core/corr.py:37-43): it
    stacks meshgrid(dy, dx) onto (x, y) centroids, so the x offset varies
    along window axis 0 and the y offset along axis 1 (a transposed
    window). Bit-compatibility here is what lets reference-trained
    checkpoints load via interop.torch_convert — the motion encoder's
    first conv consumes these 324 channels in this order.
    """
    d = jnp.arange(-radius, radius + 1, dtype=dtype)
    di, dj = jnp.meshgrid(d, d, indexing="ij")  # di varies along axis 0
    return jnp.stack([di, dj], axis=-1)  # (x + di, y + dj)


def _axis_interp_matrix(center: jax.Array, radius: int, size: int,
                        offset=0) -> jax.Array:
    """Per-pixel 1-D bilinear selection matrix A (N, 2r+1, size).

    Row j interpolates the axis at coordinate t = c_n + (j - radius);
    linear interpolation between floor(t) and floor(t)+1 is exactly the
    triangular hat kernel, so A[n, j, p] = relu(1 - |p - t|) — one fused
    elementwise expression, and out-of-range taps have empty support,
    reproducing the zero padding of bilinear_sampler /
    F.grid_sample(zeros). d/dc matches grid_sample's coordinate gradient
    almost everywhere.

    ``offset`` shifts the axis positions: column p represents global
    coordinate offset + p (used by ring context parallelism, where each
    chip holds a row BLOCK of the target axis).
    """
    t = center[:, None] + jnp.arange(-radius, radius + 1,
                                     dtype=jnp.float32)  # (N, win)
    pos = offset + jnp.arange(size, dtype=jnp.float32)[None, None, :]
    return jnp.maximum(0.0, 1.0 - jnp.abs(pos - t[..., None]))


def interp_window(vol: jax.Array, centers: jax.Array, radius: int,
                  scale: Optional[jax.Array] = None) -> jax.Array:
    """Bilinear (2r+1)^2 window of each volume slab around its center.

    vol (N, Hl, Wl), centers (N, 2) in level pixels -> (N, (2r+1)^2).

    ``vol`` may be stored below fp32 (bf16/int8 pyramid, ops/quant.py):
    the upcast happens inside the einsum's operand read (XLA fuses the
    convert into the matmul, so the fp32 values never round-trip HBM),
    and ``scale`` — the int8 dequantization factor — multiplies the
    window afterwards, which is exact because the whole lookup is linear
    in the volume.

    TPU formulation: the taps sit at INTEGER offsets from one real-valued
    center per slab, so every tap shares the slab's fractional part and
    the 2-D bilinear interpolation separates into per-axis 1-D stencils.
    The whole windowed gather then collapses into batched matmuls
    against per-pixel one-hot interpolation matrices,

        window[n] = A_x[n] · vol[n]ᵀ · A_y[n]ᵀ   — MXU work, no gather,

    which XLA schedules as streaming passes over the volume (HBM-bandwidth
    bound) instead of the scalar-gather HLO that advanced indexing lowers
    to. Expressed as ONE three-operand einsum so XLA picks the
    contraction path itself (scripts/lookup_ab.py --variant 2 compares
    the hand-split pairs; not measured on today's code).

    The window axis order matches _window_delta: x offset on the SLOW
    axis — the reference's transposed window (core/corr.py:37-43).
    """
    win = 2 * radius + 1
    hl, wl = vol.shape[1], vol.shape[2]
    ax = _axis_interp_matrix(centers[:, 0], radius, wl)  # (N, win, Wl)
    ay = _axis_interp_matrix(centers[:, 1], radius, hl)  # (N, win, Hl)
    # upcast in the operand read (fuses into the matmul; TPU's default
    # matmul precision truncates fp32 inputs to bf16 internally anyway —
    # lookup_ab3's finding — so the storage dtype only changes HBM bytes)
    window = jnp.einsum("nby,nyx,nax->nab", ay, vol.astype(jnp.float32), ax,
                        preferred_element_type=jnp.float32)
    if scale is not None:
        window = window * scale
    return window.reshape(vol.shape[0], win * win)


@jax.named_scope("corr_lookup")
def corr_lookup(pyramid: CorrPyramid, coords: jax.Array) -> jax.Array:
    """Sample a (2r+1)^2 window around ``coords / 2^i`` at every level.

    coords: (B, H, W, 2) current correspondence estimates in level-0 pixels.
    Returns (B, H, W, num_levels * (2r+1)^2) float32 correlation features.
    Reference: core/corr.py:29-50; windowing via interp_window.
    """
    r = pyramid.radius
    b, h, w = pyramid.batch, pyramid.ht, pyramid.wd
    win = 2 * r + 1

    flat = coords.reshape(b * h * w, 2).astype(jnp.float32)
    out = []
    for i, corr in enumerate(pyramid.levels):
        scale = pyramid.scales[i] if pyramid.scales is not None else None
        window = interp_window(corr[..., 0], flat / (2.0**i), r, scale=scale)
        out.append(window.reshape(b, h, w, win * win))
    return jnp.concatenate(out, axis=-1).astype(jnp.float32)
