"""Pallas TPU kernel for the local correlation lookup.

The tpu-native twin of alt_cuda_corr/correlation_kernel.cu:19-119, in the
gather formulation (SURVEY.md §2.2): the CUDA kernel stages fmap tiles
through __shared__ memory and scatter-accumulates bilinear corner weights;
here the (zero-padded) fmap2 level lives in VMEM, each grid step owns a
block of P query pixels, and per pixel we

  1. dynamic-slice the (2r+2, 2r+2, C) integer patch around floor(coords)
     (VMEM load driven by SMEM-resident scalar indices),
  2. dot against the pixel's fmap1 row on the VPU (fp32 accumulate),
  3. mask out-of-frame lattice points (zero-padding semantics of
     bilinear_sampler / F.grid_sample(zeros)),

then blend the 4 bilinear corners vectorized over the whole block.

Index preparation happens in XLA: coords are clipped to [-r-1, size+r]
(out-of-range windows are provably all-zero there because the clip bounds
are integers, so the +1 corner weight vanishes at the boundary), and fmap2
is zero-padded by 2r+2 so every clipped window is a legal static-size
slice.

Gradients: forward-only kernel wrapped in jax.custom_vjp; the VJP
recomputes through the XLA gather formulation (local_corr_level), giving
fmap gradients and zero coords gradient — the CUDA backward's semantics
(correlation_kernel.cu:307) without a second hand-written kernel.

Three kernel generations live here, newest last:
  * the per-pixel slice kernels (corr_impl="pallas"): gather-shaped
    per-query dynamic slices, whole padded fmap2 levels staged in VMEM;
  * the fused per-pixel step (pallas_fused_step): the same lattice
    machinery plus the motion encoder's 1x1 corr conv in-kernel, with a
    VMEM-budget split path at large fp32 geometries;
  * the flash-blocked kernels (corr_impl="flash" —
    flash_local_corr_level / flash_fused_step): fmap2 stays in HBM and
    is row-block-streamed per fmap1 pixel block, the partial correlation
    is a block x blockᵀ MXU matmul windowed in-register by the hat
    matrices, and there is no budget split at any geometry. See the
    "Flash-blocked kernel" section below.

On the chip only the flash generation is supported. Mosaic refuses both
per-pixel generations at the v5 440x1024 shapes ("cannot statically
prove that index in dimension 2 is a multiple of 8": the (k, k, C)
window load starts at an arbitrary sublane), so on a TPU backend they
raise config.PALLAS_TPU_REFUSAL; they stay for interpret-mode parity
and as the reference the flash tests compare with.
tests/test_chip_compile.py compiles what stays reachable for v5e.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dexiraft_tpu.config import PALLAS_TPU_REFUSAL
from dexiraft_tpu.ops.local_corr import local_corr_level

# queries per grid step; read through _pixel_block() so tuning
# (DEXIRAFT_PALLAS_PIXEL_BLOCK) needs no code edit. Resolved at trace
# time — rebuild the jit to change it.
_PIXEL_BLOCK = 256


def _pixel_block() -> int:
    # the batched variant stages (P, k, k, C) fp32 patches in VMEM
    # (~100 KiB per pixel at C=256, r=4), so its default block must be
    # much smaller than the loop kernel's
    default = 32 if _variant() == "batched" else _PIXEL_BLOCK
    # clamp: a bad flag must fail soft, not as a ZeroDivisionError deep
    # inside jit tracing
    return max(1, int(os.environ.get("DEXIRAFT_PALLAS_PIXEL_BLOCK",
                                     default)))


def _interpret_default() -> bool:
    # DEXIRAFT_PALLAS_INTERPRET=1 runs the kernel in interpreter mode
    # (trace-time switch) — lets the whole-model kernel paths run
    # off-chip (tests/test_local_corr.py). On a TPU backend the variable
    # being set is an error, not a mode: the interpreter would stand in
    # for the kernel at orders of magnitude less speed, silently.
    interpret = os.environ.get("DEXIRAFT_PALLAS_INTERPRET", "0") == "1"
    if interpret and jax.default_backend() == "tpu":
        raise RuntimeError(
            "DEXIRAFT_PALLAS_INTERPRET=1 on a TPU backend: Pallas kernels "
            "must compile for the chip, not run in the interpreter — "
            "unset it")
    return interpret


def _refuse_per_pixel_on_tpu(interpret: bool) -> None:
    """The per-pixel kernels (corr_impl="pallas", fused or not) do not
    compile for the chip: fail with Mosaic's reason at trace time, on
    every path, instead of mid-compile."""
    if not interpret and jax.default_backend() == "tpu":
        raise NotImplementedError(PALLAS_TPU_REFUSAL)


def _variant() -> str:
    # "loop": the original per-pixel slice+reduce kernel.
    # "batched": per-pixel work reduced to a pure patch COPY into a
    # (P, k, k, C) scratch, then ONE vectorized multiply-reduce over the
    # whole block — the shape the VPU pipelines well (the per-pixel
    # (k,k,C) reduce of "loop" is latency-bound, VERDICT r4 weak-6).
    # Costs P*k*k*C*4 B of extra VMEM, so "batched" wants a SMALLER
    # pixel block (default 32 vs 256). Trace-time switch.
    v = os.environ.get("DEXIRAFT_PALLAS_VARIANT", "loop")
    return v if v in ("loop", "batched") else "loop"


def _blend_corners_val(lattice, frac_ref):
    """Bilinear-blend the (P, k, k) integer-lattice dots into a
    (P, win*win) window value, x offset on the slow axis (the reference
    channel order — ops.corr)."""
    p_block, k, _ = lattice.shape
    win = k - 1
    fx = frac_ref[0, :, 0].reshape(p_block, 1, 1)
    fy = frac_ref[0, :, 1].reshape(p_block, 1, 1)
    tl = lattice[:, 0:win, 0:win]
    tr = lattice[:, 0:win, 1:win + 1]
    bl = lattice[:, 1:win + 1, 0:win]
    br = lattice[:, 1:win + 1, 1:win + 1]
    out = ((1 - fy) * (1 - fx) * tl + (1 - fy) * fx * tr
           + fy * (1 - fx) * bl + fy * fx * br)
    return out.swapaxes(1, 2).reshape(p_block, win * win)


def _blend_corners(lattice, frac_ref, out_ref):
    out_ref[0] = _blend_corners_val(lattice, frac_ref)


def _corr_kernel_batched(sx_ref, sy_ref, f1_ref, f2_ref, frac_ref,
                         sxv_ref, syv_ref, out_ref, patches_ref,
                         *, radius: int, h2: int, w2: int):
    r = radius
    k = 2 * r + 2
    p_block = f1_ref.shape[1]
    c = f1_ref.shape[2]
    inv_sqrt_c = 1.0 / (c ** 0.5)

    # phase 1: pure data movement — stage every pixel's (k, k, C) patch
    # into the block scratch; no per-pixel compute on the critical path
    def body(p, _):
        sx = sx_ref[0, p]
        sy = sy_ref[0, p]
        patches_ref[pl.ds(p, 1)] = (
            f2_ref[0, pl.ds(sy, k), pl.ds(sx, k), :].astype(jnp.float32)[None])
        return 0

    jax.lax.fori_loop(0, p_block, body, 0)

    # phase 2: ONE vectorized multiply-reduce over the whole block
    patches = patches_ref[:].astype(jnp.float32)          # (P, k, k, C)
    f1 = f1_ref[0].astype(jnp.float32)                    # (P, C)
    dots = jnp.sum(patches * f1[:, None, None, :], axis=3)  # (P, k, k)

    # vectorized out-of-frame mask: true lattice origin per pixel is
    # (sx - (r + 2), sy - (r + 2)) — see the loop kernel's derivation
    sxv = sxv_ref[0]                                      # (P,) int32
    syv = syv_ref[0]
    gx = (jax.lax.broadcasted_iota(jnp.int32, (p_block, k, k), 2)
          + (sxv - 2 - 2 * r)[:, None, None])
    gy = (jax.lax.broadcasted_iota(jnp.int32, (p_block, k, k), 1)
          + (syv - 2 - 2 * r)[:, None, None])
    valid = (gx >= 0) & (gx < w2) & (gy >= 0) & (gy < h2)
    dots = jnp.where(valid, dots * inv_sqrt_c, 0.0)
    _blend_corners(dots, frac_ref, out_ref)


def _fill_lattice_dots(sx_ref, sy_ref, f1_ref, f2_ref, lattice_ref,
                       *, radius: int, h2: int, w2: int):
    """Per-pixel slice+dot+mask loop shared by the per-level loop kernel
    and the fused kernel: stage each pixel's (k, k) integer-lattice dots
    (fp32 accumulate, storage dtype upcast in-register) into lattice_ref.

    Masking: lattice points outside the ORIGINAL (unpadded) frame read
    zero; slice starts were clipped into the padded frame, so the true
    lattice origin is recomputed as x0 = sx - (r + 2), y0 = sy - (r + 2).
    """
    r = radius
    k = 2 * r + 2
    p_block = f1_ref.shape[1]
    c = f1_ref.shape[2]
    inv_sqrt_c = 1.0 / (c ** 0.5)

    def body(p, _):
        sx = sx_ref[0, p]
        sy = sy_ref[0, p]
        patch = f2_ref[0, pl.ds(sy, k), pl.ds(sx, k), :]  # (k, k, C)
        f1p = f1_ref[0, p, :]  # (C,)
        dots = jnp.sum(
            patch.astype(jnp.float32) * f1p.astype(jnp.float32)[None, None, :],
            axis=2,
        )  # (k, k)
        gx = jax.lax.broadcasted_iota(jnp.int32, (k, k), 1) + (sx - 2 - 2 * r)
        gy = jax.lax.broadcasted_iota(jnp.int32, (k, k), 0) + (sy - 2 - 2 * r)
        valid = ((gx >= 0) & (gx < w2) & (gy >= 0) & (gy < h2))
        dots = jnp.where(valid, dots * inv_sqrt_c, 0.0)
        lattice_ref[p, :] = dots.reshape(k * k)
        return 0

    jax.lax.fori_loop(0, p_block, body, 0)


def _corr_kernel(sx_ref, sy_ref, f1_ref, f2_ref, frac_ref, out_ref,
                 lattice_ref, *, radius: int, h2: int, w2: int):
    k = 2 * radius + 2
    p_block = f1_ref.shape[1]
    _fill_lattice_dots(sx_ref, sy_ref, f1_ref, f2_ref, lattice_ref,
                       radius=radius, h2=h2, w2=w2)
    _blend_corners(lattice_ref[:].reshape(p_block, k, k), frac_ref, out_ref)


def _pallas_forward(fmap1: jax.Array, fmap2: jax.Array, coords: jax.Array,
                    radius: int, interpret=None) -> jax.Array:
    if interpret is None:
        interpret = _interpret_default()
    _refuse_per_pixel_on_tpu(interpret)
    b, h, w, c = fmap1.shape
    h2, w2 = fmap2.shape[1:3]
    r = radius
    k = 2 * r + 2
    win = 2 * r + 1
    pad = k  # 2r+2 zeros on every side

    # ---- XLA-side index prep (shared with the fused kernel; slice
    # start in the padded frame is x0 - r + pad = x0 + r + 2, in range
    # [1, w2 + 2r + 2] given the clip — always a legal k-slice) ----
    sx, sy, frac = _index_prep(coords, h2, w2, r)

    # pad in the STORAGE dtype (fp32/bf16/int8 — ops/quant.py): the
    # quantized bytes are what stream HBM->VMEM; the kernel upcasts each
    # patch in-register (patch.astype(f32) in the dot)
    f2p = jnp.pad(fmap2, ((0, 0), (pad, pad), (pad, pad), (0, 0)))

    # flatten pixels, pad to the block size
    pixel_block = _pixel_block()
    n = h * w
    n_pad = (-n) % pixel_block
    np_tot = n + n_pad
    flat = lambda a, d: jnp.pad(a.reshape(b, n, *a.shape[3:]),
                                ((0, 0), (0, n_pad)) + ((0, 0),) * d)
    f1_flat = flat(fmap1.astype(jnp.float32), 1)
    sx_flat = flat(sx, 0)  # padded pixels read slice start 0 — harmless
    sy_flat = flat(sy, 0)
    frac_flat = flat(frac, 1)

    grid = (b, np_tot // pixel_block)
    smem_spec = pl.BlockSpec((1, pixel_block), lambda bi, ti: (bi, ti),
                             memory_space=pltpu.SMEM)
    vmem_vec_spec = pl.BlockSpec((1, pixel_block), lambda bi, ti: (bi, ti),
                                 memory_space=pltpu.VMEM)
    f1_spec = pl.BlockSpec((1, pixel_block, c), lambda bi, ti: (bi, ti, 0),
                           memory_space=pltpu.VMEM)
    f2_spec = pl.BlockSpec((1, h2 + 2 * pad, w2 + 2 * pad, c),
                           lambda bi, ti: (bi, 0, 0, 0),
                           memory_space=pltpu.VMEM)
    frac_spec = pl.BlockSpec((1, pixel_block, 2), lambda bi, ti: (bi, ti, 0),
                             memory_space=pltpu.VMEM)
    out_specs = pl.BlockSpec((1, pixel_block, win * win),
                             lambda bi, ti: (bi, ti, 0),
                             memory_space=pltpu.VMEM)
    out_shape = jax.ShapeDtypeStruct((b, np_tot, win * win), jnp.float32)

    if _variant() == "batched":
        kernel = functools.partial(_corr_kernel_batched, radius=r,
                                   h2=h2, w2=w2)
        out = pl.pallas_call(
            kernel,
            grid=grid,
            # slice starts twice: SMEM scalars drive the dynamic patch
            # slices, VMEM vectors feed the vectorized lattice mask
            in_specs=[smem_spec, smem_spec, f1_spec, f2_spec, frac_spec,
                      vmem_vec_spec, vmem_vec_spec],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((pixel_block, k, k, c), jnp.float32)],
            interpret=interpret,
            name="pallas_corr_batched",
        )(sx_flat, sy_flat, f1_flat, f2p, frac_flat, sx_flat, sy_flat)
    else:
        kernel = functools.partial(_corr_kernel, radius=r, h2=h2, w2=w2)
        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[smem_spec, smem_spec, f1_spec, f2_spec, frac_spec],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((pixel_block, k * k), jnp.float32)],
            interpret=interpret,
            name="pallas_corr",
        )(sx_flat, sy_flat, f1_flat, f2p, frac_flat)

    return out[:, :n].reshape(b, h, w, win * win)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def pallas_local_corr_level(fmap1, fmap2, coords, radius: int,
                            interpret=None, row_chunk=8):
    """(B,H,W,C) x (B,H2,W2,C) x (B,H,W,2 level coords) -> (B,H,W,(2r+1)^2).

    interpret=None defers to DEXIRAFT_PALLAS_INTERPRET (off-chip debug
    switch, resolved at trace time). row_chunk only affects the backward
    recompute (the forward kernel is already pixel-blocked); pass the
    model's corr_row_chunk so the VJP's transient patch buffer honors
    the same bound.
    """
    return _pallas_forward(fmap1, fmap2, coords, radius, interpret)


def _fwd(fmap1, fmap2, coords, radius, interpret, row_chunk):
    return (_pallas_forward(fmap1, fmap2, coords, radius, interpret),
            (fmap1, fmap2, coords))


def _bwd(radius, interpret, row_chunk, res, g):
    fmap1, fmap2, coords = res
    # row-chunked recompute: bounds the backward's transient patch buffer
    # the same way the forward XLA path does
    _, vjp = jax.vjp(
        lambda f1, f2: local_corr_level(f1, f2, coords, radius,
                                        row_chunk=row_chunk),
        fmap1, fmap2)
    g1, g2 = vjp(g)
    return g1, g2, jnp.zeros_like(coords)


pallas_local_corr_level.defvjp(_fwd, _bwd)


# ---------------------------------------------------------------------------
# Fused refinement-step kernel: 4-level lookup + motion-encoder entry
# ---------------------------------------------------------------------------
#
# The per-level kernel above still writes each level's (B, H, W, win^2)
# window to HBM, where XLA's motion encoder reads the concatenated
# (B, H, W, L*win^2) tensor back for its 1x1 corr conv — two full HBM
# round-trips of the widest activation in the refinement loop. The fused
# kernel does the whole chain in ONE pallas_call per iteration: every
# pyramid level's window is computed while the pixel block's patches are
# VMEM-resident and immediately contracted against that level's slice of
# the motion encoder's 1x1 conv weight (an MXU matmul), so only the
# (B, H, W, F) conv OUTPUT ever touches HBM. F=256 vs L*win^2=324 plus
# the per-level intermediates: the loop's widest tensors never leave
# VMEM. Division of labor for the linear factors: the kernel applies
# 1/sqrt(C) itself (inside _fill_lattice_dots, same as the per-level
# kernel — do NOT fold it into the weights too); the caller folds ONLY
# the per-level int8 dequantization scales into the weight slices
# (models/update.py FusedCorrEncoder). The kernel reads the pyramid in
# its storage dtype (fp32/bf16/int8) and upcasts in-register.


def _fused_kernel(*refs, radius: int, num_levels: int, level_shapes: tuple):
    """refs: f1, w, b, then [sx, sy, frac, f2p] per level, out, lattice.

    Per level: the per-pixel patch slice+dot of _corr_kernel, the corner
    blend, then window @ w_level accumulated into the block's (P, F)
    output — all while resident in VMEM.
    """
    f1_ref, w_ref, b_ref = refs[0], refs[1], refs[2]
    lvl_refs = refs[3:3 + 4 * num_levels]
    out_ref, lattice_ref = refs[3 + 4 * num_levels], refs[4 + 4 * num_levels]

    r = radius
    k = 2 * r + 2
    win = 2 * r + 1
    p_block = f1_ref.shape[1]

    acc = jnp.broadcast_to(b_ref[0].astype(jnp.float32),
                           (p_block, b_ref.shape[1]))
    for lvl in range(num_levels):
        sx_ref, sy_ref, frac_ref, f2_ref = lvl_refs[4 * lvl:4 * lvl + 4]
        h2, w2 = level_shapes[lvl]
        # same per-pixel slice+dot+mask as the per-level loop kernel
        # (shared helper — ONE copy of the lattice-origin arithmetic)
        _fill_lattice_dots(sx_ref, sy_ref, f1_ref, f2_ref, lattice_ref,
                           radius=r, h2=h2, w2=w2)
        window = _blend_corners_val(
            lattice_ref[:].reshape(p_block, k, k), frac_ref)  # (P, win^2)
        w_lvl = w_ref[pl.ds(lvl * win * win, win * win), :]
        acc = acc + jnp.dot(window, w_lvl.astype(jnp.float32),
                            preferred_element_type=jnp.float32)
    out_ref[0] = acc


def _index_prep(coords: jax.Array, h2: int, w2: int, radius: int):
    """XLA-side index prep for one level (the same clip/floor/frac as
    _pallas_forward, at this level's geometry)."""
    r = radius
    x = jnp.clip(coords[..., 0].astype(jnp.float32),
                 -(r + 1.0), w2 - 1 + r + 1.0)
    y = jnp.clip(coords[..., 1].astype(jnp.float32),
                 -(r + 1.0), h2 - 1 + r + 1.0)
    x0 = jnp.floor(x)
    y0 = jnp.floor(y)
    frac = jnp.stack([x - x0, y - y0], axis=-1)
    sx = x0.astype(jnp.int32) + (r + 2)
    sy = y0.astype(jnp.int32) + (r + 2)
    return sx, sy, frac


# combined VMEM budget for the padded fmap2 levels a single fused call
# may stage (bytes). ~16 MiB/core total minus the f1/weight/out/lattice
# blocks and double-buffering headroom. At the 440x1024 eval geometry the
# four padded fp32 levels need ~18 MB — over budget — so the fp32 fused
# path splits into per-level fused calls (each holds ONE level, the
# footprint the per-level kernel already proves fits); bf16 (~9 MB) and
# int8 (~4.5 MB) stay single-call, which is the configuration the fused
# kernel exists for. The env override is parsed ONCE at module load
# (tests override the module constant, not the environment).
_FUSED_LEVELS_VMEM_DEFAULT = 12 * 1024 * 1024


def _parse_positive_int_env(name: str, default: int) -> int:
    """Parse an integer-bytes env override once, at module load, with an
    actionable refusal instead of a bare ValueError from int()."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        value = int(raw)
    except ValueError:
        raise ValueError(
            f"{name}={raw!r} is not an integer; set a byte count "
            f"(e.g. {default} = {default // 2**20} MiB) or unset it"
        ) from None
    if value <= 0:
        raise ValueError(
            f"{name}={raw!r} must be a positive byte count; the VMEM "
            f"budget bounds the fmap2 levels one fused call stages "
            f"(default {default})")
    return value


_FUSED_LEVELS_VMEM_BYTES = _parse_positive_int_env(
    "DEXIRAFT_FUSED_LEVELS_VMEM_BYTES", _FUSED_LEVELS_VMEM_DEFAULT)


def _fused_levels_budget() -> int:
    return _FUSED_LEVELS_VMEM_BYTES


def _fused_forward(fmap1: jax.Array, fmap2_levels: tuple, coords: jax.Array,
                   weight: jax.Array, bias: jax.Array, radius: int,
                   interpret=None) -> jax.Array:
    if interpret is None:
        interpret = _interpret_default()
    _refuse_per_pixel_on_tpu(interpret)
    b, h, w, c = fmap1.shape
    r = radius
    k = 2 * r + 2
    win = 2 * r + 1
    pad = k
    num_levels = len(fmap2_levels)
    feat = weight.shape[1]
    level_shapes = tuple(f2.shape[1:3] for f2 in fmap2_levels)

    if num_levels > 1:
        staged = sum((h2 + 2 * pad) * (w2 + 2 * pad) * c * f2.dtype.itemsize
                     for (h2, w2), f2 in zip(level_shapes, fmap2_levels))
        if staged > _fused_levels_budget():
            # over the VMEM budget (fp32 pyramid at large geometry):
            # one fused lookup+conv call PER level — each stages a single
            # level, still contracting its window against the weight
            # slice in-kernel, and the (B, H, W, win^2) per-level corr
            # features still never materialize; only L partial (B,H,W,F)
            # products are summed in XLA. Exactly linear, so identical
            # to the single-call result up to summation order.
            ww = win * win
            out = None
            zero_bias = jnp.zeros_like(bias)
            for lvl in range(num_levels):
                o = _fused_forward(
                    fmap1, (fmap2_levels[lvl],), coords / (2.0 ** lvl),
                    weight[lvl * ww:(lvl + 1) * ww], zero_bias, radius,
                    interpret)
                out = o if out is None else out + o
            return out + bias.astype(jnp.float32)

    # the fused kernel has the loop kernel's VMEM shape (one (P, k*k)
    # lattice scratch), so it shares the loop default — not the batched
    # variant's small block
    pixel_block = max(1, int(os.environ.get("DEXIRAFT_PALLAS_PIXEL_BLOCK",
                                            _PIXEL_BLOCK)))
    n = h * w
    n_pad = (-n) % pixel_block
    np_tot = n + n_pad
    flat = lambda a, d: jnp.pad(a.reshape(b, n, *a.shape[3:]),
                                ((0, 0), (0, n_pad)) + ((0, 0),) * d)

    f1_flat = flat(fmap1.astype(jnp.float32), 1)

    grid = (b, np_tot // pixel_block)
    smem_spec = pl.BlockSpec((1, pixel_block), lambda bi, ti: (bi, ti),
                             memory_space=pltpu.SMEM)
    frac_spec = pl.BlockSpec((1, pixel_block, 2), lambda bi, ti: (bi, ti, 0),
                             memory_space=pltpu.VMEM)
    f1_spec = pl.BlockSpec((1, pixel_block, c), lambda bi, ti: (bi, ti, 0),
                           memory_space=pltpu.VMEM)
    w_spec = pl.BlockSpec((num_levels * win * win, feat),
                          lambda bi, ti: (0, 0), memory_space=pltpu.VMEM)
    b_spec = pl.BlockSpec((1, feat), lambda bi, ti: (0, 0),
                          memory_space=pltpu.VMEM)

    inputs = [f1_flat, weight.astype(jnp.float32),
              bias.reshape(1, feat).astype(jnp.float32)]
    in_specs = [f1_spec, w_spec, b_spec]
    for lvl, f2 in enumerate(fmap2_levels):
        h2, w2 = level_shapes[lvl]
        sx, sy, frac = _index_prep(coords / (2.0 ** lvl), h2, w2, r)
        # pad each level in its STORAGE dtype — the quantized bytes are
        # what stream HBM->VMEM
        f2p = jnp.pad(f2, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
        inputs += [flat(sx, 0), flat(sy, 0), flat(frac, 1), f2p]
        in_specs += [
            smem_spec, smem_spec, frac_spec,
            pl.BlockSpec((1, h2 + 2 * pad, w2 + 2 * pad, c),
                         lambda bi, ti: (bi, 0, 0, 0),
                         memory_space=pltpu.VMEM),
        ]

    kernel = functools.partial(_fused_kernel, radius=r,
                               num_levels=num_levels,
                               level_shapes=level_shapes)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, pixel_block, feat),
                               lambda bi, ti: (bi, ti, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, np_tot, feat), jnp.float32),
        scratch_shapes=[pltpu.VMEM((pixel_block, k * k), jnp.float32)],
        interpret=interpret,
        name="pallas_fused_step",
    )(*inputs)
    return out[:, :n].reshape(b, h, w, feat)


def fused_reference(fmap1, fmap2_levels, coords, weight, bias, radius,
                    row_chunk=None):
    """The unfused XLA formulation of the fused kernel — per-level
    local_corr_level windows concatenated, then the 1x1 conv as a plain
    contraction. The parity/gradient reference AND the backward-pass
    recompute target of pallas_fused_step (the same split as
    pallas_local_corr_level's VJP: hand-written forward kernel, XLA
    matmul backward).

    ``weight`` is (L*win^2, F) with any per-level dequantization scales
    already folded in (the caller's job — FusedCorrEncoder); levels may
    be stored bf16/int8, upcast here exactly as the kernel upcasts.
    """
    b, h, w, _ = fmap1.shape
    outs = []
    for lvl, f2 in enumerate(fmap2_levels):
        outs.append(local_corr_level(
            fmap1, f2.astype(jnp.float32), coords / (2.0 ** lvl), radius,
            row_chunk=row_chunk))
    corr = jnp.concatenate(outs, axis=-1)  # (B, H, W, L*win^2)
    return (jnp.einsum("bhwc,cf->bhwf", corr, weight.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
            + bias.astype(jnp.float32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def pallas_fused_step(fmap1, fmap2_levels, coords, weight, bias,
                      radius: int, interpret=None, row_chunk=8):
    """Fused lookup+update-entry: (B,H,W,C) x L levels x level-0 coords x
    (L*(2r+1)^2, F) weight x (F,) bias -> (B,H,W,F).

    One Pallas call per refinement iteration: the full multi-level window
    lookup feeds the motion encoder's 1x1 corr conv while each pixel
    block's patches are VMEM-resident (see module comment). interpret=None
    defers to DEXIRAFT_PALLAS_INTERPRET; row_chunk bounds the backward
    recompute's transient buffer like the per-level kernel's VJP.

    Gradients flow to fmap1, float-dtype fmap2 levels, weight, and bias
    by recomputing through fused_reference; coords get zero gradient
    (the CUDA-kernel semantics shared by every corr path). int8-stored
    levels are non-differentiable by construction (their float0
    cotangent falls out of jax.vjp) — the model layer refuses to train
    int8 pyramids rather than training with dead fmap2 gradients.
    """
    return _fused_forward(fmap1, tuple(fmap2_levels), coords, weight, bias,
                          radius, interpret)


def _fused_fwd(fmap1, fmap2_levels, coords, weight, bias, radius, interpret,
               row_chunk):
    out = _fused_forward(fmap1, tuple(fmap2_levels), coords, weight, bias,
                         radius, interpret)
    return out, (fmap1, tuple(fmap2_levels), coords, weight, bias)


def _fused_bwd(radius, interpret, row_chunk, res, g):
    fmap1, fmap2_levels, coords, weight, bias = res
    _, vjp = jax.vjp(
        lambda f1, f2s, w_, b_: fused_reference(
            f1, f2s, coords, w_, b_, radius, row_chunk=row_chunk),
        fmap1, fmap2_levels, weight, bias)
    g1, g2s, gw, gb = vjp(g)
    return g1, g2s, jnp.zeros_like(coords), gw, gb


pallas_fused_step.defvjp(_fused_fwd, _fused_bwd)


# ---------------------------------------------------------------------------
# Flash-blocked kernel: the materialized-volume killer (ISSUE 12)
# ---------------------------------------------------------------------------
#
# The per-pixel kernels above are gather-shaped (one (k, k, C) dynamic
# slice + VPU reduce per query) and must stage whole padded fmap2 levels
# in VMEM, which is why _fused_forward splits into per-level calls when
# the fp32 pyramid blows the budget. The flash-blocked kernel is the
# flash-attention translation of alt_cuda_corr instead: fmap2 levels
# STAY IN HBM (memory_space=ANY); per fmap1 pixel block the kernel DMAs
# VMEM-sized row blocks of each level, computes the partial all-pairs
# correlation as ONE block x blockᵀ MXU matmul (the exact formulation
# ops/local_corr.py proves correct in XLA), windows it in-register with
# the separable triangular hat matrices of ops.corr._axis_interp_matrix
# (bilinear blend + out-of-frame zeroing in one expression — no corner
# blending, no coordinate clipping), and accumulates. Row blocks whose
# rows cannot intersect any query window in the block (hat support is
# empty outside [ty - r - 1, ty + r + 1]) are never copied, so HBM
# traffic tracks the windows actually needed, not H2 x W2: each level's
# visited blocks are one contiguous range, known from the block's
# coords before the grid step's first matmul.
#
# The visited blocks of all levels stream through TWO VMEM slots, one
# DMA semaphore each (ISSUE 25): before computing on a visit the kernel
# starts the copy of the next one into the other slot — the level's
# next block or, on a level's last, the first block of the next level
# that has any — and only then waits for its own. The copy runs behind
# the matmuls of the visit before it; the first copy of a grid step is
# the only one waited for with nothing to compute. Visits and their
# order are those of a plain loop over each level's range, so the sums
# are too.
#
# To read what Mosaic made of it without a chip, compile the kernel for
# a described topology (tests/test_chip_compile.py's recipe) under
#   LIBTPU_INIT_ARGS="--xla_jf_dump_to=<dir> --xla_jf_dump_llo_text=true"
# and open <dir>/*-flash_fused_step.1-*-final_bundles.txt: one line a
# VLIW bundle, loop bodies between `LB:` marks, the copies as
# `dma.hbm_to_vmem` / `dma.done.wait`. (The dumper aborts on a missing
# report template after that file is written.) Give the levels the
# batch the model gives them (32 and up): at batch 1 the compiler keeps
# the whole level in VMEM and the "copy" is 256 vector loads and stores.
#
# Consequences: VMEM use is O(pixel_block) at ANY geometry (no budget
# split path), HBM holds only the fmaps (never a volume, never padded
# per-level copies — levels are padded only to a row-block multiple),
# and there is ONE kernel per refinement iteration. The fused variant
# additionally contracts each level's window against the motion
# encoder's weight slice in-kernel (same contract as _fused_kernel: the
# kernel applies 1/sqrt(C) itself, the caller folds only int8 scales
# into the weights); the unfused variant writes the (P, L*win^2) window
# features — the flash lookup for corr_impl="flash" without
# fused_update.

# queries per flash grid step / fmap2 rows per DMA block. Trace-time
# env knobs like DEXIRAFT_PALLAS_PIXEL_BLOCK; the defaults bound the
# resident set to ~5 MB at C=256 and W2=128 (f1 block 256 KB + the two
# (8, W2, C) row-block slots, 1 MiB each at fp32 + the (P, rows*W2)
# dots transient).
_FLASH_PIXEL_BLOCK = 256
_FLASH_ROWS = 8
_LANES = 128


def _flash_pixel_block() -> int:
    return max(1, int(os.environ.get("DEXIRAFT_FLASH_PIXEL_BLOCK",
                                     _FLASH_PIXEL_BLOCK)))


def _flash_rows() -> int:
    return max(1, int(os.environ.get("DEXIRAFT_FLASH_ROWS", _FLASH_ROWS)))


def _hat(taps_center, length, offset, radius, p_block):
    """(P,) centers -> (P, 2r+1, length) triangular hat weights for axis
    positions offset..offset+length-1 — the in-kernel twin of
    ops.corr._axis_interp_matrix(center, radius, length, offset):
    A[p, j, q] = relu(1 - |(offset + q) - (center_p + j - r)|). Out-of-
    range taps have empty support, reproducing bilinear_sampler's zero
    padding; zero-padded rows/cols get weights but multiply zeros."""
    win = 2 * radius + 1
    # Mosaic's iota is integer-only: build the indices in int32 and cast
    shape = (p_block, win, length)
    pos = offset + jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    tap = jax.lax.broadcasted_iota(jnp.int32, shape, 1) - radius
    return jnp.maximum(
        0.0, 1.0 - jnp.abs((pos - tap).astype(jnp.float32)
                           - taps_center[:, None, None]))


def _flash_kernel(*refs, radius: int, level_ids: tuple, level_shapes: tuple,
                  num_levels_total: int, rows: int, fused: bool):
    """refs: f1, coords, [w, b], f2 level refs (ANY/HBM), out, then
    scratch: the two f2 row-block slots, window accumulator, [out
    accumulator], one DMA semaphore a slot.

    ``level_ids`` are the ORIGINAL pyramid indices of the staged levels
    (degenerate 0-row tail levels are filtered out on the XLA side —
    their windows are identically zero); ``num_levels_total`` sizes the
    unfused output / weight slicing in original-pyramid channels."""
    n_lvls = len(level_ids)
    if fused:
        f1_ref, coords_ref, w_ref, b_ref = refs[:4]
        lvl_refs = refs[4:4 + n_lvls]
        out_ref = refs[4 + n_lvls]
        f2blk_ref, win_ref, acc_ref, sem = refs[5 + n_lvls:]
    else:
        f1_ref, coords_ref = refs[:2]
        lvl_refs = refs[2:2 + n_lvls]
        out_ref = refs[2 + n_lvls]
        f2blk_ref, win_ref, sem = refs[3 + n_lvls:]

    r = radius
    win = 2 * r + 1
    p_block = f1_ref.shape[1]
    c = f1_ref.shape[2]
    bi = pl.program_id(0)

    # fold the 1/sqrt(C) normalization into the query block once — every
    # dots matmul below then carries it (linear), same division of labor
    # as the per-pixel kernels (the caller never folds it into weights)
    f1 = f1_ref[0].astype(jnp.float32) * (1.0 / (c ** 0.5))
    if fused:
        acc_ref[...] = jnp.broadcast_to(b_ref[0].astype(jnp.float32),
                                        (p_block, b_ref.shape[1]))
    elif n_lvls < num_levels_total:
        # filtered degenerate levels own output channels nobody writes —
        # zero the whole block once so they read as the zero windows
        # they are
        out_ref[0] = jnp.zeros(
            (p_block, num_levels_total * win * win), jnp.float32)

    # The visited row blocks of every level, before the first copy. Hat
    # support of tap t is (t-1, t+1) and taps span [ty-r, ty+r]: a row
    # block outside [min ty - r - 1, max ty + r + 1] cannot contribute.
    # Block i holds rows [i*rows, i*rows + rows - 1], so the visited
    # ones are ceil((t_lo - rows + 1) / rows) .. floor(t_hi / rows),
    # clipped to the level: an empty range where every window lies
    # outside it. floor/ceil are monotone, so they are taken on the
    # vector and the reduction yields the integer.
    tys, first, end = [], [], []
    for f2_ref, lvl in zip(lvl_refs, level_ids):
        n_blocks = f2_ref.shape[1] // rows
        ty = coords_ref[0, :, 1].astype(jnp.float32) * (1.0 / (2.0 ** lvl))
        lo = jnp.min(jnp.ceil((ty - (r + 1) - (rows - 1)) / rows))
        hi = jnp.max(jnp.floor((ty + (r + 1)) / rows))
        tys.append(ty)
        first.append(jnp.clip(lo.astype(jnp.int32), 0, n_blocks))
        end.append(jnp.clip(hi.astype(jnp.int32) + 1, 0, n_blocks))

    def copy(i, blk_i, slot):
        w2 = level_shapes[i][1]
        return pltpu.make_async_copy(
            lvl_refs[i].at[bi, pl.ds(blk_i * rows, rows)],
            f2blk_ref.at[slot, :, :w2, :], sem.at[slot])

    def start_first_visit(from_i, slot):
        """Start the copy of the first visited block of the first level
        from ``from_i`` on that has any; none where none has."""
        none_yet = True
        for j in range(from_i, n_lvls):
            has = first[j] < end[j]
            pl.when(none_yet & has)(
                lambda j=j: copy(j, first[j], slot).start())
            none_yet = none_yet & ~has

    # the pipeline of the section comment above: a visit computes from
    # ``slot`` while its successor's copy fills the other; every copy
    # that is started is waited for by its own visit
    start_first_visit(0, 0)
    slot = jnp.int32(0)

    for i, (lvl, (h2, w2)) in enumerate(zip(level_ids, level_shapes)):
        inv = 1.0 / (2.0 ** lvl)
        tx = coords_ref[0, :, 0].astype(jnp.float32) * inv  # (P,)
        ty = tys[i]
        # x hats cover the whole level width (a row of queries spans it);
        # y hats are built per row block inside the loop
        ax = _hat(tx, w2, 0, r, p_block)  # (P, win, w2)
        win_ref[...] = jnp.zeros_like(win_ref)

        def body(blk_i, slot, i=i, ax=ax, ty=ty, w2=w2):
            row0 = blk_i * rows
            pl.when(blk_i + 1 < end[i])(
                lambda: copy(i, blk_i + 1, 1 - slot).start())
            if i + 1 < n_lvls:  # the last level hands nothing on
                pl.when(blk_i + 1 == end[i])(
                    lambda: start_first_visit(i + 1, 1 - slot))
            copy(i, blk_i, slot).wait()
            blk = (f2blk_ref[slot, :, :w2, :]
                   .reshape(rows * w2, c).astype(jnp.float32))
            # partial all-pairs block: (P, C) x (rows*w2, C)ᵀ on the
            # MXU — the local_corr formulation, never materialized
            # beyond this row block
            dots = jax.lax.dot_general(
                f1, blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dots = dots.reshape(p_block, rows, w2)
            ay = _hat(ty, rows, row0, r, p_block)  # (P, win, rows)
            rows_c = jax.lax.dot_general(  # (P, win_y, w2)
                ay, dots, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            wp = jax.lax.dot_general(  # (P, win_x, win_y) — x slow,
                ax, rows_c, (((2,), (2,)), ((0,), (0,))),  # ops.corr
                preferred_element_type=jnp.float32)  # channel order
            win_ref[...] += wp.reshape(p_block, win * win)
            return 1 - slot

        slot = jax.lax.fori_loop(first[i], end[i], body, slot)

        if fused:
            w_lvl = w_ref[pl.ds(lvl * win * win, win * win), :]
            acc_ref[...] += jnp.dot(win_ref[...], w_lvl.astype(jnp.float32),
                                    preferred_element_type=jnp.float32)
        else:
            out_ref[0, :, lvl * win * win:(lvl + 1) * win * win] = win_ref[...]
    if fused:
        out_ref[0] = acc_ref[...]


def _flash_forward(fmap1: jax.Array, fmap2_levels: tuple, coords: jax.Array,
                   weight, bias, radius: int, interpret=None) -> jax.Array:
    """Shared XLA-side prep for the fused (weight/bias given) and lookup
    (weight=bias=None) flash kernels. fmap2 levels are padded only to a
    row-block multiple (zero rows read as out-of-frame) and enter the
    kernel in HBM; everything else is pixel-blocked into VMEM."""
    if interpret is None:
        interpret = _interpret_default()
    b, h, w, c = fmap1.shape
    r = radius
    win = 2 * r + 1
    num_levels = len(fmap2_levels)
    fused = weight is not None
    rows = _flash_rows()
    pixel_block = _flash_pixel_block()

    # degenerate 0-row/0-col tail levels (a 1x1 level pools to nothing)
    # never enter the kernel: their windows are identically zero, and a
    # zero-size operand cannot flow through pallas_call
    level_ids = tuple(i for i, f2 in enumerate(fmap2_levels)
                      if f2.shape[1] > 0 and f2.shape[2] > 0)
    if not level_ids:
        # every staged level is degenerate (single-level call on a
        # pooled-away tail): the window features are identically zero,
        # so the fused output is just the broadcast bias
        if fused:
            return jnp.broadcast_to(bias.astype(jnp.float32),
                                    (b, h, w, weight.shape[1]))
        return jnp.zeros((b, h, w, num_levels * win * win), jnp.float32)
    kept = [fmap2_levels[i] for i in level_ids]

    # pad each level's rows to the DMA block size and its columns to the
    # lane width, in the STORAGE dtype (fp32/bf16/int8 — the quantized
    # bytes are what stream HBM->VMEM). Zero rows/columns read as
    # out-of-frame. The column pad is what Mosaic needs: the kernel
    # splits the (P, rows*w2) dots into (P, rows, w2), which it only
    # lays out when w2 is a whole number of 128-lane tiles
    f2p = [jnp.pad(f2, ((0, 0), (0, (-f2.shape[1]) % rows),
                        (0, (-f2.shape[2]) % _LANES), (0, 0)))
           for f2 in kept]
    level_shapes = tuple(f2.shape[1:3] for f2 in f2p)
    w2_max = max(s[1] for s in level_shapes)

    n = h * w
    n_pad = (-n) % pixel_block
    np_tot = n + n_pad
    flat = lambda a: jnp.pad(  # noqa: E731
        a.reshape(b, n, a.shape[3]), ((0, 0), (0, n_pad), (0, 0)))
    f1_flat = flat(fmap1.astype(jnp.float32))
    # padded tail queries carry coords 0 — they force row block 0 of each
    # level to be fetched, compute a real window, and are sliced away
    co_flat = flat(coords.astype(jnp.float32))

    grid = (b, np_tot // pixel_block)
    f1_spec = pl.BlockSpec((1, pixel_block, c), lambda bi, ti: (bi, ti, 0),
                           memory_space=pltpu.VMEM)
    co_spec = pl.BlockSpec((1, pixel_block, 2), lambda bi, ti: (bi, ti, 0),
                           memory_space=pltpu.VMEM)
    inputs = [f1_flat, co_flat]
    in_specs = [f1_spec, co_spec]
    if fused:
        feat = weight.shape[1]
        inputs += [weight.astype(jnp.float32),
                   bias.reshape(1, feat).astype(jnp.float32)]
        in_specs += [
            pl.BlockSpec((num_levels * win * win, feat),
                         lambda bi, ti: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, feat), lambda bi, ti: (0, 0),
                         memory_space=pltpu.VMEM),
        ]
        out_ch = feat
    else:
        out_ch = num_levels * win * win
    # the fmap2 levels: full arrays, HBM-resident — the kernel DMAs row
    # blocks on demand
    inputs += f2p
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * len(f2p)

    scratch = [pltpu.VMEM((2, rows, w2_max, c), f2p[0].dtype),
               pltpu.VMEM((pixel_block, win * win), jnp.float32)]
    if fused:
        scratch.append(pltpu.VMEM((pixel_block, out_ch), jnp.float32))
    scratch.append(pltpu.SemaphoreType.DMA((2,)))

    kernel = functools.partial(_flash_kernel, radius=r,
                               level_ids=level_ids,
                               level_shapes=level_shapes,
                               num_levels_total=num_levels,
                               rows=rows, fused=fused)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, pixel_block, out_ch),
                               lambda bi, ti: (bi, ti, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, np_tot, out_ch), jnp.float32),
        scratch_shapes=scratch,
        interpret=interpret,
        # the kernel's name in the compiled HLO and the device trace
        # (unnamed it takes whatever Flax scope is open: %Conv_0.6)
        name="flash_fused_step" if fused else "flash_corr",
    )(*inputs)
    return out[:, :n].reshape(b, h, w, out_ch)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_local_corr_level(fmap1, fmap2, coords, radius: int,
                           interpret=None, row_chunk=8):
    """Flash-blocked single-level lookup: same signature/semantics as
    pallas_local_corr_level (coords in LEVEL pixels, zero coords grad,
    VJP recomputes through local_corr_level) but fmap2 stays in HBM and
    the window is built from blocked MXU matmuls, not per-pixel slices."""
    return _flash_forward(fmap1, (fmap2,), coords, None, None, radius,
                          interpret)


def _flash_level_fwd(fmap1, fmap2, coords, radius, interpret, row_chunk):
    return (_flash_forward(fmap1, (fmap2,), coords, None, None, radius,
                           interpret),
            (fmap1, fmap2, coords))


flash_local_corr_level.defvjp(_flash_level_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def flash_fused_step(fmap1, fmap2_levels, coords, weight, bias,
                     radius: int, interpret=None, row_chunk=8):
    """Flash-blocked fused lookup+update-entry — pallas_fused_step's
    signature and custom-VJP contract (recompute through fused_reference,
    zero coords grad, int8 levels -> float0), ONE kernel per refinement
    iteration at ANY geometry: only the fmaps live in HBM, the window
    features and per-level intermediates never leave VMEM, and there is
    no VMEM-budget split path (levels are row-block-streamed, not staged
    whole)."""
    return _flash_forward(fmap1, tuple(fmap2_levels), coords, weight, bias,
                          radius, interpret)


def _flash_fused_fwd(fmap1, fmap2_levels, coords, weight, bias, radius,
                     interpret, row_chunk):
    out = _flash_forward(fmap1, tuple(fmap2_levels), coords, weight, bias,
                         radius, interpret)
    return out, (fmap1, tuple(fmap2_levels), coords, weight, bias)


flash_fused_step.defvjp(_flash_fused_fwd, _fused_bwd)
