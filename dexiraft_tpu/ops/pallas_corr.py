"""Pallas TPU kernel for the local correlation lookup.

The tpu-native twin of alt_cuda_corr/correlation_kernel.cu:19-119
(SURVEY.md §2.2), as its flash-attention translation: the CUDA kernel
stages fmap tiles through __shared__ memory and scatter-accumulates
bilinear corner weights per query; here fmap2 levels STAY IN HBM
(memory_space=ANY); per fmap1 pixel block the kernel DMAs VMEM-sized row
blocks of each level and computes the partial all-pairs correlation as
ONE block x blockᵀ MXU matmul (the formulation ops/local_corr.py proves
correct in XLA) with the QUERIES ON THE LANES: blk · f1ᵀ, (positions, C)
x (P, C)ᵀ -> (W2, rows, P), position x of the block a whole register
group, its rows on the sublanes. The window is taken from that form
without leaving it (ISSUE 39): along x each query's 2r+2 consecutive
positions by selects between registers under the binary digits of the
window's start (ops/corr.py's log-step shifter, _shift_in; what lies
outside the level reads a zero fill) and one lerp with the query's one
fraction; along y the triangular hat of the block's rows, multiplied in
on the vector unit into an accumulator that keeps the rows apart, summed
over them once a level. No per-query matmul, no relayout, no corner
blending, no coordinate clipping. Row blocks whose rows cannot
intersect any query window in the block (hat support is empty outside
[ty - r - 1, ty + r + 1]) are never copied, so HBM traffic tracks the
windows actually needed, not H2 x W2: each level's visited blocks are
one contiguous range, known from the block's coords before the grid
step's first matmul.

Consequences: VMEM use is O(pixel_block x level width) at ANY geometry,
HBM holds only the fmaps (never a volume — a level is stored x-major
with its rows padded to a row-block multiple, once, where the pyramid is
built: pad_flash_operands), and there is ONE kernel per refinement
iteration. Two entry points share it (corr_impl="flash", through
ops/local_corr.py's LocalCorr): flash_fused_step contracts each level's
window against the motion encoder's 1x1 conv weight slice in-kernel, so
only the (B, H, W, F) conv OUTPUT touches HBM, not the wider
(B, H, W, L*win^2) window features (the kernel applies 1/sqrt(C) itself
— do NOT fold it into the weights too; the caller folds only int8
dequantization scales into the weight slices, models/update.py
FusedCorrEncoder); flash_local_corr_level writes one level's window
features — the lookup without fused_update. Levels are read in their
storage dtype (fp32/bf16/int8) and upcast in-register.

Gradients: forward-only kernel wrapped in jax.custom_vjp; the VJPs take
the stored operands back to their true extents and recompute through the
XLA formulation (local_corr_level / fused_reference): fmap gradients
(zero in the padding) and zero coords gradient, the CUDA backward's
semantics (correlation_kernel.cu:307).

tests/test_chip_compile.py compiles both entry points for v5e. (Two
earlier generations are gone, docs/perf.md "tried and rejected": one
dynamic (k, k, C) window slice per query, refused by Mosaic; and the
window as two per-query hat matmuls on a (P, rows, W2) relayout of the
product, 2.8x this form's time.)
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dexiraft_tpu.ops.corr import (_digits, _lerp, _padded_length, _shift_in,
                                   _window_geometry)
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


def _unpad_level(level, shape):
    """A level as pad_flash_operands stores it, (B, W2, H2p, C), back as
    the (B, H2, W2, C) fmap; the empty tail level was never changed."""
    if not level.size:
        return level
    return jnp.swapaxes(level, 1, 2)[:, :shape[0]]


def _level_bwd_xla(radius, level_shape, interpret, row_chunk, res, g):
    """flash_local_corr_level's VJP: recompute through local_corr_level
    on the operands' true extents (the way back sits inside the function
    jax.vjp walks, so the cotangents come back in the stored form)."""
    f1, level, coords = res
    # row-chunked recompute: bounds the backward's transient patch buffer
    # the same way the forward XLA path does
    _, vjp = jax.vjp(
        lambda f1_, lv: local_corr_level(
            _unpad_f1(f1_, coords.shape[1:3]), _unpad_level(lv, level_shape),
            coords, radius, row_chunk=row_chunk),
        f1, level)
    g1, g2 = vjp(g)
    return g1, g2, jnp.zeros_like(coords)


def _fused_bwd(radius, level_shapes, interpret, row_chunk, res, g):
    """flash_fused_step's VJP: recompute through fused_reference, the
    operands taken back as in _level_bwd_xla."""
    f1, levels, coords, weight, bias = res
    _, vjp = jax.vjp(
        lambda f1_, lvs, w_, b_: fused_reference(
            _unpad_f1(f1_, coords.shape[1:3]),
            tuple(map(_unpad_level, lvs, level_shapes)),
            coords, w_, b_, radius, row_chunk=row_chunk),
        f1, levels, weight, bias)
    g1, g2s, gw, gb = vjp(g)
    return g1, g2s, jnp.zeros_like(coords), gw, gb


# ---------------------------------------------------------------------------
# The kernel (ISSUE 12; the two-slot pipeline, ISSUE 25; the windowing
# with the queries on the lanes, ISSUE 39)
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
# A visit, at P = 512 queries and 8 rows (a position = four registers):
# the product writes (W2, 8, P) into the zero-filled x axis (n = 2r+2
# zeros in front, zeros behind as far as the shifter's first stage
# reads: _padded_length(n, W2) positions); one select a stage and
# position the lower digits still reach (W2 = 128: 327 positions over 8
# stages, 1,308 register selects; 64, 32, 16: 760, 468, 304), one lerp
# (n - 1 positions), then 9 y hats of (8, P) and 81 multiply-adds into
# the (81, 8, P) taps. Once a level a strided read sums the 8 rows and
# the (81, P) window meets its weight slice on the MXU, transposed there
# ((81, P)ᵀ x (81, F) -> (P, F)). Resident at C = 256, fp32: the two
# slots 2 x W2 x 8 KB, the x axis 265 x 16 KB (any W2 from 119 to 246),
# taps 1.27 MiB, the output's sum 0.5 — scratch of 7.91 MiB at 440x1024
# (W2 = 128) and 9.66 at 1088x1920 (W2 = 240) — and the queries',
# weights' and output's blocks double-buffered; the compile passes under
# a vmem_limit_bytes of 12 and 13 MiB and is refused under 11 and 12
# (compile, PR 39), so the default limit of 16 stands. From 247 columns
# on (frames wider than 1968) the x axis is 521 positions and the
# default limit refuses the kernel, as it refused the hat form's.
#
# To read what Mosaic made of it without a chip, compile the kernel for
# a described topology (tests/test_chip_compile.py's recipe) under
#   LIBTPU_INIT_ARGS="--xla_jf_dump_to=<dir> --xla_jf_dump_llo_text=true"
# and open <dir>/*-flash_fused_step.1-*-final_bundles.txt: one line a
# VLIW bundle, loop bodies between `LB:` marks (the four visit loops are
# the lines marked `>>`), the copies as `dma.hbm_to_vmem` /
# `dma.done.wait`. (The dumper aborts on a missing report template after
# that file is written.) Give the levels the batch the model gives them
# (32 and up): at batch 1 the compiler keeps the whole level in VMEM and
# the "copy" is 256 vector loads and stores. Compiled alone for v5e at
# batch 32 (compile, PR 39): a level-0 visit is 2,331 bundles for 512
# queries, levels 1-3 1,437, 951, 712; 4,681 a grid step outside the
# loops (at P = 256: 1,235, 768, 526, 398 and 2,725; the hat form at
# its 256: 3,433, 3,420, 3,406, 3,393 and 4,393).

# queries per grid step / fmap2 rows per DMA block, read at trace time
# (tests set toy tiles on the module attribute). The rows are the
# sublanes of every register the windowing touches, so 8. 512 queries
# are four query rows at 440x1024: a row block is fetched for four of
# them, not two, and the copies, which set the pace at 256 (alone on the
# chip 8.70 ms of copies, 7.25 of arithmetic, 10.84 together), fall
# behind the arithmetic (5.61, 6.51, 7.96; my chip run, PR 39).
_FLASH_PIXEL_BLOCK = 512
_FLASH_ROWS = 8


def pad_flash_operands(fmap1: jax.Array, fmap2_levels) -> tuple:
    """The kernel's operands in the form it reads, made ONCE where the
    pyramid is built (ops/local_corr.py build_local_corr) and not once a
    lookup: a refinement loop hands the kernel nothing but coordinates.

    fmap1 (B, H, W, C) -> fp32 (B, Np, C): the queries flattened and
    zero-padded to a pixel-block multiple. Each level (B, H2, W2, C) ->
    (B, W2, H2p, C) in the STORAGE dtype (fp32/bf16/int8 — the quantized
    bytes are what stream HBM->VMEM): x a major axis, the rows
    zero-padded to the DMA block size and second-minor, so that a row
    block lands in VMEM as (W2, rows, C) and its product with the
    queries as (W2, rows, P) — a position x a whole register group, no
    relayout. x takes no pad: a zero row reads as out-of-frame, and what
    lies outside a level's width the kernel's own zero fill supplies. A
    degenerate 0-row/0-col tail level (a 1x1 level pools to nothing)
    stays as it is: it never enters the kernel. Returns (f1, levels);
    the VJPs undo the form (_unpad_level) back to the levels' true
    extents, which the caller keeps (LocalCorr.level_shapes)."""
    b, h, w, c = fmap1.shape
    f1 = jnp.pad(fmap1.astype(jnp.float32).reshape(b, h * w, c),
                 ((0, 0), (0, (-h * w) % _FLASH_PIXEL_BLOCK), (0, 0)))
    levels = tuple(
        jnp.swapaxes(jnp.pad(f2, ((0, 0), (0, (-f2.shape[1]) % _FLASH_ROWS),
                                  (0, 0), (0, 0))), 1, 2)
        if f2.shape[1] and f2.shape[2] else f2 for f2 in fmap2_levels)
    return f1, levels


def _flash_kernel(*refs, radius: int, level_ids: tuple, num_levels_total: int,
                  rows: int, fused: bool):
    """refs: f1, coords, [w, b], f2 level refs (ANY/HBM), out, then
    scratch: the two f2 row-block slots, the zero-filled x axis, the
    window accumulator, [out accumulator], one DMA semaphore a slot.

    ``level_ids`` are the ORIGINAL pyramid indices of the staged levels
    (degenerate 0-row tail levels are filtered out on the XLA side —
    their windows are identically zero); ``num_levels_total`` sizes the
    unfused output / weight slicing in original-pyramid channels."""
    n_lvls = len(level_ids)
    if fused:
        f1_ref, coords_ref, w_ref, b_ref = refs[:4]
        lvl_refs = refs[4:4 + n_lvls]
        out_ref = refs[4 + n_lvls]
        f2blk_ref, pad_ref, win_ref, acc_ref, sem = refs[5 + n_lvls:]
    else:
        f1_ref, coords_ref = refs[:2]
        lvl_refs = refs[2:2 + n_lvls]
        out_ref = refs[2 + n_lvls]
        f2blk_ref, pad_ref, win_ref, sem = refs[3 + n_lvls:]

    r = radius
    win = 2 * r + 1
    n = win + 1  # a window's consecutive positions along an axis
    p_block = f1_ref.shape[1]
    c = f1_ref.shape[2]
    bi = pl.program_id(0)
    # the (2, P) coordinate block: the queries are on the lanes here and
    # stay there down to the window
    co = coords_ref[0].astype(jnp.float32)
    cx, cy = co[0:1], co[1:2]  # (1, P)

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
            (num_levels_total * win * win, p_block), jnp.float32)

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
        n_blocks = f2_ref.shape[2] // rows
        ty = cy * (1.0 / (2.0 ** lvl))
        lo = jnp.min(jnp.ceil((ty - (r + 1) - (rows - 1)) / rows))
        hi = jnp.max(jnp.floor((ty + (r + 1)) / rows))
        tys.append(ty)
        first.append(jnp.clip(lo.astype(jnp.int32), 0, n_blocks))
        end.append(jnp.clip(hi.astype(jnp.int32) + 1, 0, n_blocks))

    def copy(i, blk_i, slot):
        w2 = lvl_refs[i].shape[1]
        return pltpu.make_async_copy(
            lvl_refs[i].at[bi, :, pl.ds(blk_i * rows, rows)],
            f2blk_ref.at[slot, :w2], sem.at[slot])

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
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (rows, p_block), 0)
    tap_y = (jax.lax.broadcasted_iota(jnp.int32, (win, rows, p_block), 0)
             - r).astype(jnp.float32)
    lanes = win_ref.shape[2]
    # the zeros in front of every level's x axis; those behind it are
    # written a level, where the level's width ends
    pad_ref[0:n] = jnp.zeros((n, rows, p_block), jnp.float32)

    for i, lvl in enumerate(level_ids):
        w2 = lvl_refs[i].shape[1]
        ty = tys[i]
        # x: where each query's window starts on the zero-filled axis,
        # and the one fraction its taps share; the same for all the rows
        # of a block, so spread over the sublanes once a level
        start, frac = _window_geometry(cx * (1.0 / (2.0 ** lvl)), r, w2)
        digits = [d[None] for d in _digits(
            jnp.broadcast_to(start, (rows, p_block)), w2 + n)]
        frac = jnp.broadcast_to(frac, (rows, p_block))[None]
        filled = _padded_length(n, w2)
        pad_ref[n + w2:filled] = jnp.zeros(
            (filled - n - w2, rows, p_block), jnp.float32)
        win_ref[...] = jnp.zeros_like(win_ref)

        def body(blk_i, slot, i=i, ty=ty, w2=w2, digits=digits, frac=frac,
                 filled=filled):
            pl.when(blk_i + 1 < end[i])(
                lambda: copy(i, blk_i + 1, 1 - slot).start())
            if i + 1 < n_lvls:  # the last level hands nothing on
                pl.when(blk_i + 1 == end[i])(
                    lambda: start_first_visit(i + 1, 1 - slot))
            copy(i, blk_i, slot).wait()
            blk = (f2blk_ref[slot, :w2].astype(jnp.float32)
                   .reshape(w2 * rows, c))
            # partial all-pairs block the other way round: (w2*rows, C)
            # x (P, C)ᵀ on the MXU, the queries the stationary operand —
            # the local_corr formulation, never materialized beyond this
            # row block. Position x of the result is a (rows, P)
            # register group.
            dots = jax.lax.dot_general(
                blk, f1, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            pad_ref[n:n + w2] = dots.reshape(w2, rows, p_block)
            # x: the window's n positions by selects between registers,
            # neighbours weighted by one lerp (ops/corr.py's shifter)
            taps = _lerp(_shift_in(pad_ref[0:filled], digits, n, 0), frac, 0)
            # y: triangular hat weights of the block's rows, on the
            # vector unit; the rows stay apart on the sublanes
            ay = jnp.maximum(0.0, 1.0 - jnp.abs(  # (win_y, rows, P)
                (blk_i * rows + row_iota).astype(jnp.float32) - ty - tap_y))
            for jx in range(win):  # x slow: ops.corr channel order
                both = (ay * taps[jx]).reshape(win * rows, p_block)
                at = pl.ds(jx * win * rows, win * rows)
                for k in range(win_ref.shape[0]):
                    win_ref[k, at, :] += both[:, k * lanes:(k + 1) * lanes]
            return 1 - slot

        slot = jax.lax.fori_loop(first[i], end[i], body, slot)

        # the block's rows summed, once a level: a strided read puts tap
        # e of sublane s on row e
        window = jnp.concatenate(
            [sum(win_ref[k, pl.ds(s, win * win, stride=rows), :]
                 for s in range(rows)) for k in range(win_ref.shape[0])],
            axis=1)  # (81, P)
        if fused:
            w_lvl = w_ref[pl.ds(lvl * win * win, win * win), :]
            acc_ref[...] += jax.lax.dot_general(  # (81, P)ᵀ x (81, F)
                window, w_lvl.astype(jnp.float32), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            out_ref[0, lvl * win * win:(lvl + 1) * win * win, :] = window
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
    w2_max = max(f2.shape[1] for f2 in f2p)

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

    # the two row-block slots, x major; the zero-filled x axis the
    # shifter reads, as long as the widest level's first stage reaches;
    # the window taps, a block's rows apart
    scratch = [pltpu.VMEM((2, w2_max, rows, c), f2p[0].dtype),
               pltpu.VMEM((_padded_length(win + 1, w2_max), rows, pixel_block),
                          jnp.float32),
               # (a strided read wants a register's 128 lanes last)
               pltpu.VMEM((pl.cdiv(pixel_block, 128), win * win * rows,
                           min(pixel_block, 128)), jnp.float32)]
    if fused:
        scratch.append(pltpu.VMEM((pixel_block, out_ch), jnp.float32))
    scratch.append(pltpu.SemaphoreType.DMA((2,)))

    kernel = functools.partial(_flash_kernel, radius=r,
                               level_ids=level_ids,
                               num_levels_total=num_levels,
                               rows=rows, fused=fused)
    if fused:  # (P, F) blocks, as the consumer reads them
        out_shape, out_block = (b, np_tot, out_ch), (1, pixel_block, out_ch)
        out_index = lambda bi, ti: (bi, ti, 0)  # noqa: E731
    else:  # the lookup's windows leave as they are held, taps x queries
        out_shape, out_block = (b, out_ch, np_tot), (1, out_ch, pixel_block)
        out_index = lambda bi, ti: (bi, 0, ti)  # noqa: E731
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(out_block, out_index, memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        scratch_shapes=scratch,
        interpret=interpret,
        # the kernel's name in the compiled HLO and the device trace
        # (unnamed it takes whatever Flax scope is open: %Conv_0.6)
        name="flash_fused_step" if fused else "flash_corr",
    )(*inputs)
    if not fused:
        out = jnp.swapaxes(out, 1, 2)
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
