"""Rows to and from their tokens: the expert layer's row move and its
transpose.

`gather_rows(x, tokens, lo)` is `x[tokens]`, `[R, D]` from `[T, D]`;
`segment_add(rows, tokens, weights, lo, T)` adds `weights[r] * rows[r]`
into token `tokens[r]` of a `[T, D]` fp32 sum. Each is the other's
transpose (without and with the weights), and a `custom_vjp` says so:
the gather's backward is the segment sum, the segment sum's backward is
the gather (times the weights for the rows, the row dot for the
weights). Both gathers stay XLA's; the segment sum is `segment_sum`
below, and is not XLA's scatter-add, which walks rows that may share a
token one after another (9 ms for 32,768 rows of 2,560 on a v5e, 37
GB/s).

What the rows' order gives. The expert layer sorts its (token, choice)
slots by held expert with a stable sort, so inside an expert's run the
tokens ascend and are distinct. For a block of `block_tokens` tokens and
one expert the rows that belong to the block are then one contiguous
range of the sorted order, and the table `lo [held, blocks + 1]` says
where each starts (`lo[e, b] .. lo[e, b + 1]`, positions in `rows`;
models/lm/moe.py makes it from the routing, beside the sort). Rows in
no range (past the held slots) are never read.

`segment_sum` on a TPU is one Pallas kernel: a grid step owns a
`[block_tokens, D tile]` tile of the result, zeroes it in VMEM, and for
each expert fetches its range in windows of 128 rows from the aligned
start (one window nearly always: `windows`), the next expert's first
window in flight meanwhile. A window is placed by a 0/1 matrix on the
MXU (`[block_tokens, 128]`, row r to token `tokens[r]`, masked to the
range: exact in bf16, fp32 sums); since an expert gives a token at most
one row, the weight is applied afterwards in fp32, a token's weight
read off the same matrix. The tile is written once. Elsewhere the plain
`.at[].add` over the same live rows, which the tests hold the kernel
to in interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

WINDOW = 128  # rows a fetch: the MXU's depth, a lane row of the ids
_ALIGN = 16   # a window starts on a bf16 tile of rows
_BLOCK_TOKENS = 256
# a step's tile, its fp32 sum and three windows stay under this, inside
# the 16 MiB a kernel is given unasked
_VMEM_BUDGET = 10 * 1024 * 1024


def block_tokens(n_tokens: int) -> int:
    """Tokens a block of the table `lo`: the largest power of two up to
    `_BLOCK_TOKENS` that divides `n_tokens` (all of them where that is
    under a tile of 8)."""
    bt = _BLOCK_TOKENS
    while n_tokens % bt:
        bt //= 2
    return bt if bt >= 8 else n_tokens


def windows(lo, hi):
    """Windows the kernel fetches for the range `lo .. hi` (arrays or
    the kernel's own scalars): those of `WINDOW` rows from the aligned
    start that reach `hi`, none for an empty range."""
    first = lo // _ALIGN * _ALIGN
    return jnp.where(hi > lo, (hi - first + WINDOW - 1) // WINDOW, 0)


def _tile(d: int, bt: int, out_dtype) -> Optional[int]:
    """Columns a grid step holds: the most of `d` in whole lane tiles
    that fit the budget (the result's tile twice over, an fp32 sum
    beside it where the result is not fp32, three windows), None where
    `d` is no whole number of them."""
    out_dtype = jnp.dtype(out_dtype)
    tile_bytes = 2 * out_dtype.itemsize + (0 if out_dtype == jnp.float32
                                           else 4)
    for parts in range(1, d // 128 + 1):
        dt = d // parts
        if d % parts or dt % 128:
            continue
        if bt * dt * tile_bytes + 3 * WINDOW * dt * 2 <= _VMEM_BUDGET:
            return dt
    return None


def _kernel(lo_ref, tok_ref, *refs, bt, nb, held, n_rows, weighted):
    w_ref = refs[0] if weighted else None
    rows_ref, out_ref, *acc, buf, sem = refs[weighted:]
    # the sum is kept in the result's own tile where that is fp32, in a
    # scratch tile cast on the way out elsewhere
    acc_ref = acc[0] if acc else out_ref
    b, j = pl.program_id(0), pl.program_id(1)
    dt = out_ref.shape[1]
    n_live = lo_ref[held * (nb + 1) - 1]  # where the last range ends

    def bounds(e):
        at = e * (nb + 1) + b
        return lo_ref[at], lo_ref[at + 1]

    def span(e, k):
        """Window `k` of expert `e`'s range: where it starts in the
        order, and where its fetch does (inside `rows`)."""
        start = bounds(e)[0] // _ALIGN * _ALIGN + k * WINDOW
        return start, pl.multiple_of(
            jnp.minimum(start, n_rows - WINDOW), _ALIGN)

    def fetch(e, k, slot):
        return pltpu.make_async_copy(
            rows_ref.at[pl.ds(span(e, k)[1], WINDOW), pl.ds(j * dt, dt)],
            buf.at[slot], sem.at[slot])

    def lanes(ref, at):
        """`ref`'s 128 entries from row position `at` on, as a lane row:
        the ids lie 128 a row, so two rows shifted into one."""
        row, off = at // WINDOW, at % WINDOW
        shift = (WINDOW - off) % WINDOW
        a = pltpu.roll(ref[pl.ds(row, 1), :], shift, 1)
        c = pltpu.roll(ref[pl.ds(row + 1, 1), :], shift, 1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, WINDOW), 1)
        return jnp.where(lane + off < WINDOW, a, c)

    def place(e, k, slot):
        lo, hi = bounds(e)
        start, at = span(e, k)

        # rows past the last range hold anything (NaN too), and 0 x NaN
        # is no zero: the few windows that reach them are cleared there
        @pl.when(at + WINDOW > n_live)
        def _():
            below = at + jax.lax.broadcasted_iota(
                jnp.int32, (WINDOW, 1), 0) < n_live
            buf[slot] = jnp.where(below, buf[slot], jnp.zeros((), buf.dtype))

        row = at + jax.lax.broadcasted_iota(jnp.int32, (1, WINDOW), 1)
        live = ((row >= jnp.maximum(lo, start))
                & (row < jnp.minimum(hi, start + WINDOW)))
        token = lanes(tok_ref, at) - b * bt
        hit = live & (token == jax.lax.broadcasted_iota(
            jnp.int32, (bt, WINDOW), 0))
        placed = jnp.dot(hit.astype(buf.dtype), buf[slot],
                         preferred_element_type=jnp.float32)
        if weighted:
            placed = placed * jnp.sum(
                jnp.where(hit, lanes(w_ref, at), 0.0), axis=1, keepdims=True)
        acc_ref[...] += placed

    acc_ref[...] = jnp.zeros_like(acc_ref)

    def count(e):
        return windows(*bounds(e))

    @pl.when(count(0) > 0)
    def _():
        fetch(0, 0, 0).start()

    def expert(e, carry):
        n = count(e)

        @pl.when((e + 1 < held) & (count(jnp.minimum(e + 1, held - 1)) > 0))
        def _():
            fetch(e + 1, 0, (e + 1) % 2).start()

        @pl.when(n > 0)
        def _():
            fetch(e, 0, e % 2).wait()
            place(e, 0, e % 2)

        def further(k, carry):
            copy = fetch(e, k, 2)
            copy.start()
            copy.wait()
            place(e, k, 2)
            return carry

        return jax.lax.fori_loop(1, n, further, carry)

    jax.lax.fori_loop(0, held, expert, 0)
    if acc_ref is not out_ref:
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_tokens", "out_dtype",
                                             "interpret"))
def kernel_segment_sum(rows, tokens, lo, n_tokens, weights=None,
                       out_dtype=jnp.float32, interpret=False):
    """`segment_sum` as the Pallas kernel (the module's text); the table
    has `n_tokens // block_tokens(n_tokens) + 1` columns."""
    n_rows, d = rows.shape
    held = lo.shape[0]
    nb = lo.shape[1] - 1
    bt = n_tokens // nb
    out_dtype = jnp.dtype(out_dtype)
    dt = _tile(d, bt, out_dtype)
    # whole windows, and a row of ids past the last for `lanes`
    pad = -n_rows % WINDOW
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    n_rows += pad

    def by_lanes(a):
        return jnp.pad(a, (0, pad + WINDOW)).reshape(-1, WINDOW)

    ids = [by_lanes(tokens.astype(jnp.int32))]
    if weights is not None:
        ids.append(by_lanes(weights.astype(jnp.float32)))
    whole = pl.BlockSpec(ids[0].shape, lambda b, j, lo: (0, 0))
    in_place = out_dtype == jnp.float32
    return pl.pallas_call(
        functools.partial(_kernel, bt=bt, nb=nb, held=held, n_rows=n_rows,
                          weighted=weights is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nb, d // dt),
            in_specs=[whole] * len(ids) + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((bt, dt), lambda b, j, lo: (b, j)),
            scratch_shapes=(
                ([] if in_place else [pltpu.VMEM((bt, dt), jnp.float32)])
                + [pltpu.VMEM((3, WINDOW, dt), rows.dtype),
                   pltpu.SemaphoreType.DMA((3,))])),
        out_shape=jax.ShapeDtypeStruct((n_tokens, d), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="rows_segment_sum",  # in the compiled HLO and the device trace
    )(lo.reshape(-1).astype(jnp.int32), *ids, rows)


def xla_segment_sum(rows, tokens, lo, n_tokens, weights=None,
                    out_dtype=jnp.float32):
    """`segment_sum` in plain XLA: a scatter-add of the rows the table
    names (those before its last entry)."""
    live = (jnp.arange(rows.shape[0]) < lo[-1, -1])[:, None]
    rows = rows.astype(jnp.float32)
    if weights is not None:
        rows = rows * weights[:, None]
    zeros = jnp.zeros((n_tokens, rows.shape[1]), jnp.float32)
    return zeros.at[tokens].add(jnp.where(live, rows, 0.0)).astype(out_dtype)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def segment_sum(rows, tokens, lo, n_tokens, weights=None,
                out_dtype=jnp.float32):
    """`[n_tokens, D]`: token t's sum of `weights[r] * rows[r]` (fp32
    products and sums, cast at the end) over the rows r with
    `tokens[r] == t` that lie in a range of `lo` (the module's text)."""
    bt = n_tokens // (lo.shape[1] - 1)
    if (_on_tpu() and bt % 8 == 0
            and _tile(rows.shape[1], bt, out_dtype)):
        return kernel_segment_sum(rows, tokens, lo, n_tokens, weights,
                                  out_dtype)
    return xla_segment_sum(rows, tokens, lo, n_tokens, weights, out_dtype)


def gather_rows(x, tokens, lo):
    """`x[tokens]`; the table is the backward's."""
    return _gather(x.shape[0], x, tokens, lo)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gather(n_tokens, x, tokens, lo):
    return x[tokens]


def _gather_fwd(n_tokens, x, tokens, lo):
    return x[tokens], (tokens, lo)


def _gather_bwd(n_tokens, res, g):
    tokens, lo = res
    return segment_sum(g, tokens, lo, n_tokens, out_dtype=g.dtype), None, None


_gather.defvjp(_gather_fwd, _gather_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def segment_add(rows, tokens, weights, lo, n_tokens):
    """`[n_tokens, D]` fp32: `weights[r] * rows[r]` summed into token
    `tokens[r]`, over the rows in a range of `lo`."""
    return segment_sum(rows, tokens, lo, n_tokens, weights)


def _add_fwd(rows, tokens, weights, lo, n_tokens):
    return (segment_sum(rows, tokens, lo, n_tokens, weights),
            (rows, tokens, weights))


def _add_bwd(n_tokens, res, dy):
    rows, tokens, weights = res
    g = dy[tokens]
    return ((g * weights[:, None]).astype(rows.dtype), None,
            jnp.sum(g * rows.astype(jnp.float32), axis=-1), None)


segment_add.defvjp(_add_fwd, _add_bwd)
