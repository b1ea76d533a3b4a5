"""Memory-efficient local correlation — the alt_cuda_corr equivalent.

The reference's CUDA kernel (alt_cuda_corr/correlation_kernel.cu:19-119)
computes, per query pixel, dot products of fmap1 against an integer
lattice of fmap2 rows around floor(coords) and scatter-accumulates the 4
bilinear corner weights into a (2r+1)^2 window. O(HW * (2r+2)^2) memory
instead of the materialized volume's O((HW)^2) (SURVEY.md §2.2).

TPU-native reformulation — flash-attention-style, all MXU matmuls:
per chunk of query rows, the partial all-pairs block
vol = f1_chunk · f2ᵀ (ops.corr.all_pairs_correlation) is materialized,
windowed with the separable hat-matrix matmuls of
ops.corr.interp_window, and discarded. Transient memory is
O(chunk · W · H2 · W2) per level (`row_chunk` bounds it; lax.map keeps
chunks sequential), never the full volume, and there are zero gather
HLOs. The block keeps the reference's flattening, one (H2, W2) slab per
query, which the STORED pyramid left in PR 32 (ops/corr.py): on the chip
the slab form's minor pairs pad (12.97 ms a four-level lookup at
N = 45,632 against 3.63 with the queries on the lanes; my chip run,
PR 32). No benchmark cell runs this path (`auto` is the flash kernel on
a TPU, training takes allpairs), so it has no chip time of its own.

Like the reference's AlternateCorrBlock (core/corr.py:63-91), the pyramid
pools FMAP2 (not the correlation volume) — since build_corr_pyramid now
exploits the same linearity, the two paths agree to reassociation noise.
Out-of-frame lattice points contribute zero, matching bilinear_sampler's
zero padding.

Gradients flow to fmap1/fmap2 through the matmuls; coords get zero
gradient (stop_gradient), replicating the CUDA backward's never-written
coords_grad (correlation_kernel.cu:307). The reference's Python wrapper
has NO autograd at all (core/corr.py:86 calls the op directly) — ours is
trainable, a strict capability superset.
"""

from __future__ import annotations

from typing import List, Optional

import flax.struct
import jax
import jax.numpy as jnp

from dexiraft_tpu.ops.corr import avg_pool_2x2
from dexiraft_tpu.ops.quant import store_corr


def local_corr_level(
    fmap1: jax.Array,
    fmap2: jax.Array,
    coords: jax.Array,
    radius: int,
    row_chunk: Optional[int] = None,
) -> jax.Array:
    """Windowed correlation of fmap1 against fmap2 around coords.

    fmap1: (B, H, W, C) query features (level-0 resolution)
    fmap2: (B, H2, W2, C) target features at this pyramid level
    coords: (B, H, W, 2) sample centers in LEVEL pixels (x, y)
    Returns (B, H, W, (2r+1)^2) float32.

    Flash-attention-style formulation: per query-row chunk, the partial
    all-pairs block vol = f1_chunk · f2ᵀ (MXU matmul) is materialized,
    windowed via the separable hat-matrix matmuls of
    ops.corr.interp_window, and discarded — O(chunk·H2·W2) transient memory,
    never the full O((HW)²) volume, and zero gather HLOs.
    """
    b, h, w, c = fmap1.shape
    coords = jax.lax.stop_gradient(coords)

    if row_chunk is not None and row_chunk < h:
        pad = (-h) % row_chunk
        f1 = jnp.pad(fmap1, ((0, 0), (0, pad), (0, 0), (0, 0)))
        co = jnp.pad(coords, ((0, 0), (0, pad), (0, 0), (0, 0)))
        n_chunks = (h + pad) // row_chunk
        f1 = f1.reshape(b, n_chunks, row_chunk, w, c).swapaxes(0, 1)
        co = co.reshape(b, n_chunks, row_chunk, w, 2).swapaxes(0, 1)
        out = jax.lax.map(
            lambda args: _local_corr_dense(args[0], fmap2, args[1], radius),
            (f1, co),
        )  # (n_chunks, B, row_chunk, W, win^2)
        out = out.swapaxes(0, 1).reshape(b, h + pad, w, -1)
        return out[:, :h]
    return _local_corr_dense(fmap1, fmap2, coords, radius)


def _local_corr_dense(
    fmap1: jax.Array, fmap2: jax.Array, coords: jax.Array, radius: int
) -> jax.Array:
    from dexiraft_tpu.ops.corr import all_pairs_correlation, interp_window

    b, h, w, _ = fmap1.shape
    win = 2 * radius + 1
    # partial all-pairs block for these queries (fp32 accumulate, MXU)
    vol = all_pairs_correlation(fmap1, fmap2)  # (B*H*W, H2, W2, 1)
    flat = coords.reshape(b * h * w, 2).astype(jnp.float32)
    window = interp_window(vol[..., 0], flat, radius)
    return window.reshape(b, h, w, win * win)


@flax.struct.dataclass
class LocalCorr:
    """On-demand correlation pyramid: same lookup interface as CorrPyramid.

    Holds fmap1 and the avg-pooled fmap2 pyramid (core/corr.py:64-72);
    correlation is computed per lookup instead of materialized. Under
    kernel="flash" both are held in the form the kernel reads
    (ops/pallas_corr.py pad_flash_operands), made once in
    build_local_corr: a lookup hands the kernel only its coordinates.
    """

    # kernel="xla": (B, H, W, C) fp32. kernel="flash": (B, Np, C) fp32,
    # the queries flattened and zero-padded to a pixel-block multiple
    fmap1: jax.Array
    # one (B, H>>i, W>>i, C) level per pyramid index, in the storage
    # dtype; under kernel="flash" stored x-major, (B, W>>i, rows, C) with
    # the rows zero-padded to the kernel's row-block multiple (a
    # degenerate 0-row tail level stays empty)
    fmap2_pyramid: tuple
    batch: int = flax.struct.field(pytree_node=False)
    ht: int = flax.struct.field(pytree_node=False)
    wd: int = flax.struct.field(pytree_node=False)
    radius: int = flax.struct.field(pytree_node=False)
    # the true (H>>i, W>>i) of every level, whatever fmap2_pyramid pads
    level_shapes: tuple = flax.struct.field(pytree_node=False)
    row_chunk: Optional[int] = flax.struct.field(pytree_node=False, default=None)
    # lookup implementation: "xla" (local_corr_level matmuls) or "flash"
    # (blocked HBM-streaming kernel — ops/pallas_corr.py)
    kernel: str = flax.struct.field(pytree_node=False, default="xla")
    # per-level fp32 scalar dequantization scales for int8-stored fmap2
    # levels (ops/quant.py); None for fp32/bf16. Correlation is linear in
    # fmap2, so corr(f1, s*q) = s * corr(f1, q): the scale multiplies the
    # looked-up window AFTER the kernel — the quantized level is what
    # streams from HBM, and no dequantized copy is ever materialized.
    scales: Optional[tuple] = None

    def level_scale(self, i: int) -> Optional[jax.Array]:
        return self.scales[i] if self.scales is not None else None

    def __call__(self, coords: jax.Array) -> jax.Array:
        """coords (B, H, W, 2) in level-0 pixels -> (B, H, W, L*(2r+1)^2)."""
        out: List[jax.Array] = []
        for i, f2 in enumerate(self.fmap2_pyramid):
            coords_i = coords / (2.0 ** i)
            if self.kernel == "flash":
                from dexiraft_tpu.ops.pallas_corr import flash_local_corr_level

                # interpret=None defers to the kernel module's
                # DEXIRAFT_PALLAS_INTERPRET env knob, which makes these
                # whole-model paths exercisable off-chip
                # (tests/test_zzzflashcorr.py)
                corr = flash_local_corr_level(
                    self.fmap1, f2, coords_i, self.radius,
                    self.level_shapes[i], None, self.row_chunk)
            else:
                corr = local_corr_level(
                    self.fmap1, f2, coords_i, self.radius, self.row_chunk)
            scale = self.level_scale(i)
            if scale is not None:
                corr = corr * scale
            out.append(corr)
        return jnp.concatenate(out, axis=-1).astype(jnp.float32)

    def fused_step(self, coords: jax.Array, weight: jax.Array,
                   bias: jax.Array) -> jax.Array:
        """The lookup and the motion encoder's 1x1 corr conv as one
        kernel (kernel="flash" only; RAFTConfig refuses fused_update
        elsewhere): coords as above, weight (L*(2r+1)^2, F) with int8
        level scales already folded in, bias (F,) -> (B, H, W, F)."""
        from dexiraft_tpu.ops.pallas_corr import flash_fused_step

        return flash_fused_step(self.fmap1, self.fmap2_pyramid, coords,
                                weight, bias, self.radius, self.level_shapes,
                                None, self.row_chunk)


def build_local_corr(
    fmap1: jax.Array,
    fmap2: jax.Array,
    num_levels: int = 4,
    radius: int = 4,
    row_chunk: Optional[int] = None,
    dtype: str = "fp32",
    kernel: str = "xla",
) -> LocalCorr:
    """Build the pooled-fmap2 pyramid (no volume materialization).

    ``dtype`` sets the STORAGE precision of the fmap2 pyramid (the tensor
    every on-demand lookup streams; fmap1 stays fp32 — it is read once
    per pixel block, not once per lattice point). Pooling runs fp32; each
    level is then stored bf16/int8 with a per-level scale (ops/quant.py)
    and the lookup dequantizes in-register.

    ``kernel`` picks the lookup implementation ("xla" | "flash"); "flash"
    stores the operands as its kernel reads them, laid out here, once.
    """
    if kernel not in ("xla", "flash"):
        raise ValueError(f"unknown local-corr kernel {kernel!r}; "
                         "expected 'xla' or 'flash'")
    b, h, w, _ = fmap1.shape
    f1 = fmap1.astype(jnp.float32)
    pooled = [fmap2.astype(jnp.float32)]
    for _ in range(num_levels - 1):
        pooled.append(avg_pool_2x2(pooled[-1]))
    stored = [store_corr(lvl, dtype) for lvl in pooled]
    levels = tuple(s[0] for s in stored)
    level_shapes = tuple(tuple(lvl.shape[1:3]) for lvl in levels)
    if kernel == "flash":
        from dexiraft_tpu.ops.pallas_corr import pad_flash_operands

        f1, levels = pad_flash_operands(f1, levels)
    return LocalCorr(
        fmap1=f1, fmap2_pyramid=levels,
        batch=b, ht=h, wd=w, radius=radius, level_shapes=level_shapes,
        row_chunk=row_chunk, kernel=kernel,
        scales=(tuple(s[1] for s in stored) if dtype == "int8" else None))
