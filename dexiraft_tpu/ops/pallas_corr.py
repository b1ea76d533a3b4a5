"""Pallas TPU kernel for the local correlation lookup.

The tpu-native twin of alt_cuda_corr/correlation_kernel.cu:19-119
(SURVEY.md §2.2), as its flash-attention translation: the CUDA kernel
stages fmap tiles through __shared__ memory and scatter-accumulates
bilinear corner weights per query; here fmap2 levels STAY IN HBM
(memory_space=ANY); per fmap1 pixel block the kernel DMAs VMEM-sized row
blocks of each level, computes the partial all-pairs correlation as ONE
block x blockᵀ MXU matmul (the exact formulation ops/local_corr.py
proves correct in XLA), windows it in-register with the separable
triangular hat matrices of ops.corr._axis_interp_matrix (bilinear blend
+ out-of-frame zeroing in one expression — no corner blending, no
coordinate clipping), and accumulates. Row blocks whose rows cannot
intersect any query window in the block (hat support is empty outside
[ty - r - 1, ty + r + 1]) are never copied, so HBM traffic tracks the
windows actually needed, not H2 x W2: each level's visited blocks are
one contiguous range, known from the block's coords before the grid
step's first matmul.

Consequences: VMEM use is O(pixel_block) at ANY geometry, HBM holds only
the fmaps (never a volume — levels are padded only to a row-block and
lane multiple, once, where the pyramid is built: pad_flash_operands),
and there is ONE kernel per refinement iteration. Two
entry points share it (corr_impl="flash", through ops/local_corr.py's
LocalCorr): flash_fused_step contracts each level's window against the
motion encoder's 1x1 conv weight slice in-kernel, so only the
(B, H, W, F) conv OUTPUT touches HBM, not the wider (B, H, W, L*win^2)
window features (the kernel applies 1/sqrt(C) itself — do NOT fold it
into the weights too; the caller folds only int8 dequantization scales
into the weight slices, models/update.py FusedCorrEncoder);
flash_local_corr_level writes one level's window features — the lookup
without fused_update. Levels are read in their storage dtype
(fp32/bf16/int8) and upcast in-register.

Gradients: forward-only kernel wrapped in jax.custom_vjp; the VJPs
slice the padded operands back to their true extents and recompute
through the XLA formulation (local_corr_level / fused_reference): fmap
gradients (zero in the padding) and zero coords gradient, the CUDA
backward's semantics (correlation_kernel.cu:307).

tests/test_chip_compile.py compiles both entry points for v5e. (An
earlier generation, one dynamic (k, k, C) window slice per query, was
refused by Mosaic and is gone: docs/perf.md, "tried and rejected".)
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dexiraft_tpu.ops.local_corr import local_corr_level


def _interpret_default() -> bool:
    # DEXIRAFT_PALLAS_INTERPRET=1 runs the kernel in interpreter mode
    # (trace-time switch) — lets the whole-model kernel paths run
    # off-chip. On a TPU backend the variable being set is an error, not
    # a mode: the interpreter would stand in for the kernel at orders of
    # magnitude less speed, silently.
    interpret = os.environ.get("DEXIRAFT_PALLAS_INTERPRET", "0") == "1"
    if interpret and jax.default_backend() == "tpu":
        raise RuntimeError(
            "DEXIRAFT_PALLAS_INTERPRET=1 on a TPU backend: Pallas kernels "
            "must compile for the chip, not run in the interpreter — "
            "unset it")
    return interpret


def fused_reference(fmap1, fmap2_levels, coords, weight, bias, radius,
                    row_chunk=None):
    """The unfused XLA formulation of the fused kernel — per-level
    local_corr_level windows concatenated, then the 1x1 conv as a plain
    contraction. The parity/gradient reference AND the backward-pass
    recompute target of flash_fused_step.

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


def _unpad_f1(f1, hw):
    """The kernel's (B, Np, C) queries back as the (B, H, W, C) fmap."""
    h, w = hw
    return f1[:, :h * w].reshape(f1.shape[0], h, w, f1.shape[2])


def _level_bwd_xla(radius, level_shape, interpret, row_chunk, res, g):
    """flash_local_corr_level's VJP: recompute through local_corr_level
    on the operands' true extents (the slices sit inside the function
    jax.vjp walks, so the cotangents come back in the padded form)."""
    f1, level, coords = res
    h2, w2 = level_shape
    # row-chunked recompute: bounds the backward's transient patch buffer
    # the same way the forward XLA path does
    _, vjp = jax.vjp(
        lambda f1_, lv: local_corr_level(
            _unpad_f1(f1_, coords.shape[1:3]), lv[:, :h2, :w2], coords,
            radius, row_chunk=row_chunk),
        f1, level)
    g1, g2 = vjp(g)
    return g1, g2, jnp.zeros_like(coords)


def _fused_bwd(radius, level_shapes, interpret, row_chunk, res, g):
    """flash_fused_step's VJP: recompute through fused_reference, sliced
    as in _level_bwd_xla."""
    f1, levels, coords, weight, bias = res
    _, vjp = jax.vjp(
        lambda f1_, lvs, w_, b_: fused_reference(
            _unpad_f1(f1_, coords.shape[1:3]),
            tuple(lv[:, :h2, :w2] for lv, (h2, w2) in zip(lvs, level_shapes)),
            coords, w_, b_, radius, row_chunk=row_chunk),
        f1, levels, weight, bias)
    g1, g2s, gw, gb = vjp(g)
    return g1, g2s, jnp.zeros_like(coords), gw, gb


# ---------------------------------------------------------------------------
# The kernel (ISSUE 12; the two-slot pipeline, ISSUE 25)
# ---------------------------------------------------------------------------
#
# The visited blocks of all levels stream through TWO VMEM slots, one
# DMA semaphore each: before computing on a visit the kernel starts the
# copy of the next one into the other slot — the level's next block or,
# on a level's last, the first block of the next level that has any —
# and only then waits for its own. The copy runs behind the matmuls of
# the visit before it; the first copy of a grid step is the only one
# waited for with nothing to compute. Visits and their order are those
# of a plain loop over each level's range, so the sums are too.
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

# queries per grid step / fmap2 rows per DMA block, read at trace time
# (tests set toy tiles on the module attribute): a resident set of ~5 MB
# at C=256 and W2=128 (f1 block 256 KB + the two (8, W2, C) row-block
# slots, 1 MiB each at fp32 + the (P, rows*W2) dots transient; the
# (2, P) coordinate block is one 8 KB tile pair).
_FLASH_PIXEL_BLOCK = 256
_FLASH_ROWS = 8
_LANES = 128


def pad_flash_operands(fmap1: jax.Array, fmap2_levels) -> tuple:
    """The kernel's operands in the form it reads, made ONCE where the
    pyramid is built (ops/local_corr.py build_local_corr) and not once a
    lookup: a refinement loop hands the kernel nothing but coordinates.

    fmap1 (B, H, W, C) -> fp32 (B, Np, C): the queries flattened and
    zero-padded to a pixel-block multiple. Each level -> its rows padded
    to the DMA block size and its columns to the lane width, in the
    STORAGE dtype (fp32/bf16/int8 — the quantized bytes are what stream
    HBM->VMEM); zero rows/columns read as out-of-frame. The column pad is
    what Mosaic needs: the kernel splits the (P, rows*w2) dots into
    (P, rows, w2), which it only lays out when w2 is a whole number of
    128-lane tiles. A degenerate 0-row/0-col tail level (a 1x1 level
    pools to nothing) stays as it is: it never enters the kernel.
    Returns (f1, levels); the VJPs slice them back to the levels' true
    extents, which the caller keeps (LocalCorr.level_shapes)."""
    b, h, w, c = fmap1.shape
    f1 = jnp.pad(fmap1.astype(jnp.float32).reshape(b, h * w, c),
                 ((0, 0), (0, (-h * w) % _FLASH_PIXEL_BLOCK), (0, 0)))
    levels = tuple(
        jnp.pad(f2, ((0, 0), (0, (-f2.shape[1]) % _FLASH_ROWS),
                     (0, (-f2.shape[2]) % _LANES), (0, 0)))
        if f2.shape[1] and f2.shape[2] else f2 for f2 in fmap2_levels)
    return f1, levels


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

    ``level_shapes`` are the staged levels' PADDED extents (the refs');
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
    # the (2, P) coordinate block, queries on the lanes, turned into
    # per-query columns once a grid step
    cols = coords_ref[0].astype(jnp.float32).T  # (P, 2)
    cx, cy = cols[:, 0], cols[:, 1]

    # fold the 1/sqrt(C) normalization into the query block once — every
    # dots matmul below then carries it (linear; the caller never folds
    # it into weights)
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
        ty = cy * (1.0 / (2.0 ** lvl))
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
        tx = cx * inv  # (P,)
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


def _flash_forward(f1: jax.Array, levels: tuple, coords: jax.Array,
                   weight, bias, radius: int, interpret=None) -> jax.Array:
    """Shared XLA-side call of the fused (weight/bias given) and lookup
    (weight=bias=None) flash kernels on operands pad_flash_operands made:
    nothing is padded here but the coordinates, which reach the kernel as
    (B, 2, Np) planes, the queries on the lanes. The levels enter the
    kernel in HBM; everything else is pixel-blocked into VMEM."""
    if interpret is None:
        interpret = _interpret_default()
    b, h, w, _ = coords.shape
    np_tot, c = f1.shape[1:]
    r = radius
    win = 2 * r + 1
    num_levels = len(levels)
    fused = weight is not None
    rows = _FLASH_ROWS
    pixel_block = _FLASH_PIXEL_BLOCK

    # degenerate 0-row/0-col tail levels never enter the kernel: their
    # windows are identically zero, and a zero-size operand cannot flow
    # through pallas_call
    level_ids = tuple(i for i, f2 in enumerate(levels)
                      if f2.shape[1] > 0 and f2.shape[2] > 0)
    if not level_ids:
        # every staged level is degenerate (single-level call on a
        # pooled-away tail): the window features are identically zero,
        # so the fused output is just the broadcast bias
        if fused:
            return jnp.broadcast_to(bias.astype(jnp.float32),
                                    (b, h, w, weight.shape[1]))
        return jnp.zeros((b, h, w, num_levels * win * win), jnp.float32)
    f2p = [levels[i] for i in level_ids]
    padded_shapes = tuple(f2.shape[1:3] for f2 in f2p)
    w2_max = max(s[1] for s in padded_shapes)

    n = h * w
    # padded tail queries carry coords 0 — they force row block 0 of each
    # level to be fetched, compute a real window, and are sliced away
    co = jnp.pad(
        jnp.moveaxis(coords.astype(jnp.float32), 3, 1).reshape(b, 2, n),
        ((0, 0), (0, 0), (0, np_tot - n)))

    grid = (b, np_tot // pixel_block)
    f1_spec = pl.BlockSpec((1, pixel_block, c), lambda bi, ti: (bi, ti, 0),
                           memory_space=pltpu.VMEM)
    co_spec = pl.BlockSpec((1, 2, pixel_block), lambda bi, ti: (bi, 0, ti),
                           memory_space=pltpu.VMEM)
    inputs = [f1, co]
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
                               level_shapes=padded_shapes,
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_local_corr_level(f1, level, coords, radius: int, level_shape,
                           interpret=None, row_chunk=8):
    """(B,Np,C) queries x one (B,H2p,W2p,C) level, both as
    pad_flash_operands made them (``level_shape`` the level's true
    extent), x (B,H,W,2 level coords) -> (B,H,W,(2r+1)^2):
    local_corr_level's semantics (coords in LEVEL pixels, zero coords
    grad; the VJP recomputes through it). interpret=None defers to
    DEXIRAFT_PALLAS_INTERPRET (off-chip debug switch, resolved at trace
    time). row_chunk only bounds the backward recompute's transient
    buffer: pass the model's corr_row_chunk."""
    return _flash_forward(f1, (level,), coords, None, None, radius, interpret)


def _flash_level_fwd(f1, level, coords, radius, level_shape, interpret,
                     row_chunk):
    return (_flash_forward(f1, (level,), coords, None, None, radius,
                           interpret),
            (f1, level, coords))


flash_local_corr_level.defvjp(_flash_level_fwd, _level_bwd_xla)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def flash_fused_step(f1, levels, coords, weight, bias, radius: int,
                     level_shapes, interpret=None, row_chunk=8):
    """Fused lookup+update-entry: (B,Np,C) queries x L levels, as
    pad_flash_operands made them (``level_shapes`` their true extents),
    x (B,H,W,2) level-0 coords x (L*(2r+1)^2, F) weight x (F,) bias ->
    (B,H,W,F), one kernel per refinement iteration.

    Gradients flow to the queries, float-dtype levels, weight and bias
    by recomputing through fused_reference; coords get zero gradient.
    int8-stored levels are non-differentiable by construction (their
    float0 cotangent falls out of jax.vjp) — the model layer refuses to
    train int8 pyramids rather than training with dead fmap2 gradients."""
    return _flash_forward(f1, tuple(levels), coords, weight, bias, radius,
                          interpret)


def _flash_fused_fwd(f1, levels, coords, weight, bias, radius, level_shapes,
                     interpret, row_chunk):
    out = _flash_forward(f1, tuple(levels), coords, weight, bias, radius,
                         interpret)
    return out, (f1, tuple(levels), coords, weight, bias)


flash_fused_step.defvjp(_flash_fused_fwd, _fused_bwd)
