"""The all-pairs lookup's window alignment as Pallas kernels.

ops/corr.py `_axis_window` takes each query's 2r+2 consecutive positions
out of one target axis of a stored level and weights neighbours by one
lerp. On a level `(B, S1, S2, Q)` the chip keeps the queries on the lanes
and the batch on the sublanes, so a target axis is a MAJOR axis: position
p of the axis is a whole `(8, lanes)` register, "the positions from
start on" is a choice among registers under a per-(b, q) mask, and a
shift by `start` is a log-step shifter, one select a stage. Written in
plain XLA every static slice of a select chain is fetched from HBM on its
own (level 0's x axis: 304 columns moved for 62 stored; my chip run, PR
34); here a block of the level is fetched once into VMEM, the stages run
between registers, and only the 2r+1 lerped taps go back.

Three kernels. `align_axis` (forward) and `place_axis` are mirror images:
the second puts a cotangent's taps back, zero elsewhere, in the form of
what the first read. `place_axis_sum` is `place_axis` summed over a stack
of lookups: the stack's index is the grid's last axis and the output
block ignores it, so a block of the level's gradient is zeroed, added to
in VMEM once a lookup and written to HBM once (ops/corr.py `place_once`
hands it a training loop's window cotangents after the backward loop).
The calls take the arrays as `(T, S1, S2, B, Q)`, T lookups each under
its own masks (1 for a single lookup): with the default layout
`(S1, S2, B, Q)` is the order the chip stores a level in, so the
transposes around a call are bitcasts.

Across chips a call runs shard by shard (`_per_chip`): the partitioner
cannot split a kernel, so the call says itself which axes it may be split
over, the batch and the queries.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dexiraft_tpu.ops.corr import (_digits, _lerp, _lerp_transposed,
                                   _padded_length, _shift_in, _shift_out)
from dexiraft_tpu.parallel.layout import LAYOUT

# A block: 256 queries (two registers a position) and as many lines as 4 MiB
# of the level hold. 128-512 lanes and 2-8 MiB read within 2 % (my chip run,
# PR 34); a raised `vmem_limit_bytes` cost 12 %: the compiler keeps the
# lookup's small results in VMEM too, and the kernel's reserve comes out of it.
_LANES = 256
_BLOCK_BYTES = 4 << 20


def _line(ref, axis, i):
    return ref[i] if axis == 1 else ref[:, i]


def _put_line(ref, axis, i, val):
    if axis == 1:
        ref[i] = val
    else:
        ref[:, i] = val


def _align_kernel(start_ref, frac_ref, vol_ref, out_ref, pad_ref, *, n, size,
                  axis):
    """out[j] = (1 - f) * pad[start + j] + f * pad[start + j + 1] for the
    2r+1 taps j, along ``axis`` of the block; pad is the axis with n zeros
    in front and zeros behind (``pad_ref``, VMEM). One iteration a line of
    the other leading axis: ops/corr.py's shifter on a (positions, B, Q)
    array, where a position is a leading index, a shift a slice and a
    stage one select between registers."""
    frac = frac_ref[...][None]
    digits = [d[None] for d in _digits(start_ref[...], size + n)]
    fill = pad_ref.shape[0] - n - size
    pad_ref[0:n] = jnp.zeros((n,) + pad_ref.shape[1:], jnp.float32)
    pad_ref[n + size:] = jnp.zeros((fill,) + pad_ref.shape[1:], jnp.float32)

    def line(i, carry):
        # a bf16 or int8 level is upcast here, as it is read
        pad_ref[n:n + size] = _line(vol_ref, axis, i).astype(jnp.float32)
        taps = _shift_in(pad_ref[...], digits, n, 0)
        _put_line(out_ref, axis, i, _lerp(taps, frac, 0))
        return carry

    jax.lax.fori_loop(0, vol_ref.shape[1 - axis], line, 0)


def _place_kernel(start_ref, frac_ref, g_ref, out_ref, *, n, size, axis,
                  add=False):
    """_align_kernel's transpose: the cotangent's taps through the lerp,
    then to positions start .. start + n - 1 of the padded axis; what
    lands on [n, n + size) is the line, zero elsewhere. ``add``: on top
    of what the output block holds."""
    frac = frac_ref[...][None]
    digits = [d[None] for d in _digits(start_ref[...], size + n)]

    def line(i, carry):
        d_taps = _lerp_transposed(_line(g_ref, axis, i), frac, 0)
        placed = _shift_out(d_taps, digits, n + size, 0)[n:n + size]
        if add:
            placed = placed + _line(out_ref, axis, i)
        _put_line(out_ref, axis, i, placed)
        return carry

    jax.lax.fori_loop(0, g_ref.shape[1 - axis], line, 0)


def _place_sum_kernel(start_ref, frac_ref, g_ref, out_ref, **geometry):
    """_place_kernel summed over the grid's last axis, the stack's: the
    output block stays in VMEM while that axis runs, zeroed at its first
    step and added to at every one, in fp32."""
    @pl.when(pl.program_id(3) == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32)

    _place_kernel(start_ref, frac_ref, g_ref, out_ref, add=True, **geometry)


# the names the trace's `tpu_custom_call` events carry
_NAMES = {_align_kernel: "corr_window_align",
          _place_kernel: "corr_window_place",
          _place_sum_kernel: "corr_window_place_sum"}


@functools.partial(jax.jit, static_argnames=(
    "kernel", "out_len", "n", "size", "axis", "interpret"))
def _call(start, frac, x, *, kernel, out_len, n, size, axis, interpret):
    """x (T, S1, S2, B, Q) with ``axis`` (0 or 1 of S1, S2) the target axis
    -> the same with ``out_len`` on it; _place_sum_kernel: summed over T,
    (S1, S2, B, Q). Grid: batch tiles of 8 x query blocks x blocks of lines
    x the stack; a block holds the whole target axis of one lookup. Jitted
    for its cache: a train step traces its scan body several times over and
    calls the same kernel forward and recomputed, at ~30 ms a trace of the
    kernel."""
    b, q = x.shape[3:]
    lines = x.shape[2 - axis]
    summed = kernel is _place_sum_kernel
    # a batch tile is the storage dtype's sublane tile: 8, 16 (bf16), 32 (int8)
    bt = min(b, 8 * 4 // x.dtype.itemsize)
    tq = _LANES if q > _LANES else q
    widest = max(size, out_len)
    tl = max(1, min(lines, _BLOCK_BYTES // (widest * bt * tq * 4)))

    def block(length, stacked=True):
        shape = (tl, length) if axis == 1 else (length, tl)
        at = ((lambda i, j, k: (k, 0, i, j)) if axis == 1
              else (lambda i, j, k: (0, k, i, j)))
        # not stacked: the same block at every step of the stack
        lead = (None,) if stacked else ()
        return pl.BlockSpec(
            lead + shape + (bt, tq),
            lambda i, j, k, t: ((t,) if stacked else ()) + at(i, j, k))

    per_query = pl.BlockSpec((None, bt, tq), lambda i, j, k, t: (t, i, j))
    out_shape = list(x.shape)
    out_shape[1 + axis] = out_len
    # the forward's zero-filled axis: as far as its first stage reads
    scratch = ([pltpu.VMEM((_padded_length(n, size), bt, tq), jnp.float32)]
               if kernel is _align_kernel else [])
    return pl.pallas_call(
        functools.partial(kernel, n=n, size=size, axis=axis),
        grid=(pl.cdiv(b, bt), pl.cdiv(q, tq), pl.cdiv(lines, tl), x.shape[0]),
        in_specs=[per_query, per_query, block(x.shape[1 + axis])],
        out_specs=block(out_len, stacked=not summed),
        out_shape=jax.ShapeDtypeStruct(
            tuple(out_shape[1:] if summed else out_shape), jnp.float32),
        scratch_shapes=scratch,
        name=_NAMES[kernel],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel",
            "arbitrary" if summed else "parallel")),
        interpret=interpret,
    )(start, frac, x)


def _per_chip(kernel, start, frac, x, out_len, n, size, axis, interpret,
              mesh=None):
    """The call, shard by shard where the arrays live on a mesh.

    The partitioner cannot split a kernel, and left alone it would gather
    a batch-sharded level onto every chip. The types carry the mesh (jax
    0.9), so where the step is partitioned automatically the call is
    wrapped in a `shard_map` over the axes the layout gives the batch and
    the queries (parallel/layout.py `corr_window`); every line, batch row
    and query is aligned on its own, so a shard needs nothing of another.
    Inside a `shard_map` (parallel/halo.py) the arrays are local already.
    ``mesh``: the caller's word where ``x`` is a cotangent, whose type may
    have lost it (a zero probe's, made where no mesh is in sight).
    """
    if mesh is None:
        mesh = jax.typeof(x).sharding.mesh
    specs = LAYOUT.corr_window(mesh, x.shape)
    call = functools.partial(_call, kernel=kernel, out_len=out_len, n=n,
                             size=size, axis=axis, interpret=interpret)
    if specs is None:
        return call(start, frac, x)
    whole, per_query = specs
    out = whole
    if kernel is _place_sum_kernel:  # the stack is whole on every chip
        out = LAYOUT.corr_window(mesh, x.shape[1:])[0]
    return jax.shard_map(call, mesh=mesh,
                         in_specs=(per_query, per_query, whole),
                         out_specs=out, check_vma=False)(start, frac, x)


# a stack of levels as the chip stores a level, and back: bitcasts there
_TO_KERNEL, _FROM_KERNEL = (0, 2, 3, 1, 4), (0, 3, 1, 2, 4)
_SUM_FROM_KERNEL = (2, 0, 1, 3)


def align_axis(vol, start, frac, n, axis, interpret=False):
    """vol (B, S1, S2, Q) as stored, start (B, Q) int32 in [0, S + n], frac
    (B, Q) -> float32 with n - 1 on ``axis`` (1 or 2): the lerped taps of
    the window whose first position is start - n, zero outside the axis."""
    out = _per_chip(_align_kernel, start[None], frac[None],
                    jnp.transpose(vol[None], _TO_KERNEL),
                    n - 1, n, vol.shape[axis], axis - 1, interpret)
    return jnp.transpose(out, _FROM_KERNEL)[0]


def place_axis(g, start, frac, n, size, axis, interpret=False, mesh=None):
    """align_axis' transpose in vol: g (.., n - 1, ..) -> (.., size, ..).
    Over a stack of lookups too, g (T, B, S1, S2, Q) with start and frac
    (T, B, Q): T x B rows, each placed under its own masks. ``mesh``: the
    level's, where g's type may not carry it (_per_chip)."""
    if g.ndim == 4:
        return place_axis(g[None], start[None], frac[None], n, size, axis,
                          interpret, mesh)[0]
    out = _per_chip(_place_kernel, start, frac, jnp.transpose(g, _TO_KERNEL),
                    size, n, size, axis - 1, interpret, mesh)
    return jnp.transpose(out, _FROM_KERNEL)


def place_axis_sum(g, start, frac, n, size, axis, interpret=False, mesh=None):
    """sum_t place_axis(g[t], start[t], frac[t]): g (T, B, S1, S2, Q) ->
    (B, S1, S2, Q) with ``size`` on ``axis`` (1 or 2), summed in fp32, each
    block of it written once."""
    out = _per_chip(_place_sum_kernel, start, frac,
                    jnp.transpose(g, _TO_KERNEL), size, n, size, axis - 1,
                    interpret, mesh)
    return jnp.transpose(out, _SUM_FROM_KERNEL)
