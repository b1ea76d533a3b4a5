"""Causal attention within packed documents, over all earlier keys or
over a sliding window of them, with keys and values that several query
heads may share.

A row of a packed batch holds several documents; a token attends to the
tokens of its own document that do not come after it and, where the
caller gives a `window`, that lie fewer than `window` positions back:

    visible(i, j):  same document,  j <= i,  i - j < window

At 8192 positions the whole `[heads, S, S]` score matrix is 1 GiB a head
in fp32, at 32,768 it is 4 GiB, so it is never made. `q` has `H` heads,
`k` and `v` have `H_kv` with `H % H_kv == 0`: query head `h` reads
key/value head `h // (H // H_kv)`. `document_attention` takes one of two
paths, chosen from what it can observe (the backend and the shapes), not
by a flag:

**On a TPU, whole lane-aligned blocks: a Pallas flash kernel**
(`flash_document_attention`). Grid `(rows x groups of up to 4 query
heads, or one whole group of up to 8 that no smaller number divides,
query blocks, key blocks)`, key blocks innermost. A `(bq, bk)`
tile of scores lives in VMEM in fp32 and nowhere else; the running
maximum, the running sum and the output accumulator are fp32 VMEM
scratch. The backward is two more kernels (`dq`; `dk` and `dv`) that
recompute a tile's scores from `q`, `k` and the row's log-sum-exp, the
forward's only residual beside its output. A grid step loads one K/V
block for the query heads of its group that share it (it is never copied
to the query heads), and `dk`/`dv` sum over them in the step's scratch.

A packed row's documents are contiguous, so the key blocks a query block
needs are a range with two bounds (`block_table`; `last_q` is its
transpose for `dk`/`dv`). Its first block is the later of two: the block
that holds the first token of the document of the query block's first
row, and the block that holds the first key inside the window of that
row, `max(0, i*bq - window + 1) // bk`. Its last block is the diagonal.
The table is made in XLA from `segment_ids` and the window, handed to
the kernels by scalar prefetch, and does two things: the key block's
index map is clamped into the range, so a grid step outside it fetches
nothing, and the step's compute is under `pl.when`. Inside the range, a
tile below the diagonal whose rows and keys all lie in one document and
wholly inside the window (`full_kv`, `full_q`) skips the mask.
`block_pair_counts` counts the visited pairs from the same table. With
no window and `H_kv == H` the table and the kernels are what they were
before either existed.

**Elsewhere (the CPU of the tests, shapes that are not whole blocks):
plain XLA by blocks of query rows**, the oracle of the kernel's tests.
Query block `i` (rows `[i*block, (i+1)*block)`) meets keys
`[0, (i+1)*block)`, or from the block of the window's first key: the
causal mask's upper blocks and the blocks before the window are left
out, the blocks between that hold no visible pair are computed and
masked. Each block is a `jax.checkpoint`. Keys and values are repeated
to the query heads.

Either path also gives, asked with `return_lse`, the log of each row's
sum of `exp(score)` beside the normalised output, differentiable like it:
what a caller needs to carry the same softmax on over further keys
(ops/lm_eva.py). `d lse / d s = p`, so its cotangent adds `dlse * p` to
`dS`: the backward kernels are handed `delta - dlse` and do not change.

Both: scores, the mask and the softmax statistics are fp32 whatever the
inputs are; the probabilities are cast to `v`'s dtype for the second
matmul; pad positions share id 0 and see each other.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MASKED = -1e30  # not -inf: a row always holds its own diagonal
_LANES = 128
# the kernel's blocks of query rows and of keys: of 512, 1024 and their
# mixes the fastest on the cell's documents, 13.4 ms a layer against
# 13.9-14.3 (my chip run, PR 27; CHANGES.md has every reading)
_BLOCK_Q = 512
_BLOCK_K = 512
_NT = (((1,), (1,)), ((), ()))  # a @ b.T


# ---- the XLA path ----------------------------------------------------------


def _block(q, k, v, seg_q, seg_k, first_row, first_key, scale, window,
           with_lse=False):
    """q [G, Q, D], k [G, K, D], v [G, K, Dv], G = batch x heads; seg_q
    [G, Q], seg_k [G, K]. The block's first query is row `first_row` of
    the sequence, its first key row `first_key`. `with_lse`: also the
    rows' log-sum-exp `[G, Q]`, fp32."""
    s = jnp.einsum("gqd,gkd->gqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    rows = (first_row + jnp.arange(q.shape[1]))[:, None]
    keys = (first_key + jnp.arange(k.shape[1]))[None, :]
    near = rows >= keys
    if window is not None:
        near &= rows - keys < window
    mask = near[None] & (seg_q[:, :, None] == seg_k[:, None, :])
    s = jnp.where(mask, s, _MASKED)
    # the softmax written out, its row maximum behind a barrier: fused
    # with the subtraction, the chip's compiler turns the maximum into a
    # reduce-window as wide as the row (8192 keys: 47 ms a block where
    # the matmuls take one; my chip run, PR 26)
    top = jax.lax.optimization_barrier(
        jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True)))
    e = jnp.exp(s - top)
    total = jnp.sum(e, axis=-1, keepdims=True)
    out = jnp.einsum("gqk,gkd->gqd", (e / total).astype(v.dtype), v)
    if with_lse:
        return out, (top + jnp.log(total))[..., 0]
    return out


def _fold(x):
    """[B, S, H, D] -> [B*H, S, D]: heads beside the batch, in front: the
    layout the chip's compiler makes plain batched matmuls of."""
    b, seq, heads, d = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b * heads, seq, d)


def _unfold(x, b):
    g, seq, d = x.shape
    return jnp.swapaxes(x.reshape(b, g // b, seq, d), 1, 2)


def xla_document_attention(q, k, v, segment_ids, *, scale: float,
                           block: int, window: Optional[int] = None,
                           return_lse: bool = False):
    """`document_attention`'s XLA path (module docstring)."""
    b, seq, heads, _ = q.shape
    block = min(block, seq)
    if seq % block:
        raise ValueError(f"{seq} positions are not whole blocks of {block}")
    group = _group(heads, k.shape[2])
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    q, k, v = _fold(q), _fold(k), _fold(v)
    seg = jnp.repeat(segment_ids, heads, axis=0)
    run = jax.checkpoint(_block, static_argnums=(5, 6, 7, 8, 9))
    out = []
    for first in range(0, seq, block):
        end = first + block
        lo = (0 if window is None
              else max(0, first - window + 1) // block * block)
        out.append(run(q[:, first:end], k[:, lo:end], v[:, lo:end],
                       seg[:, first:end], seg[:, lo:end], first, lo, scale,
                       window, return_lse))
    if not return_lse:
        return _unfold(jnp.concatenate(out, axis=1), b)
    return (_unfold(jnp.concatenate([o for o, _ in out], axis=1), b),
            _unfold_rows(jnp.concatenate([l for _, l in out], axis=1), b))


def _unfold_rows(x, b):
    """A row statistic `[B*H, S]` -> `[B, S, H]`."""
    g, seq = x.shape
    return jnp.swapaxes(x.reshape(b, g // b, seq), 1, 2)


def _group(heads: int, kv_heads: int) -> int:
    """Query heads that share one key/value head."""
    if kv_heads < 1 or heads % kv_heads:
        raise ValueError(f"{heads} query heads do not divide over "
                         f"{kv_heads} key/value heads")
    return heads // kv_heads


# ---- which key blocks a query block needs ----------------------------------


def kernel_blocks(seq: int, d_qk: int, d_v: int) -> Optional[Tuple[int, int]]:
    """(bq, bk) if the kernel takes these shapes: whole blocks, head
    widths a multiple of 64. None otherwise."""
    bq, bk = min(_BLOCK_Q, seq), min(_BLOCK_K, seq)
    if (seq % bq or seq % bk or bq % _LANES or bk % _LANES
            or d_qk % 64 or d_v % 64):
        return None
    return bq, bk


class BlockTable(NamedTuple):
    """int32, a packed row's blocks: for query block `i` the key blocks
    `first_kv[b, i] .. (i*bq + bq - 1) // bk` hold every key its rows
    attend to, and of them `full_kv[b, i] ..` up to the last block that
    ends at or before the query block's first row need no mask. For key
    block `j` the query blocks `j*bk // bq .. last_q[b, j]`, no mask from
    the first block that starts at or after the key block's end up to
    `full_q[b, j]`. Each bound is the tighter of the document's and the
    window's."""
    first_kv: jax.Array  # [B, S // bq]
    full_kv: jax.Array   # [B, S // bq]
    last_q: jax.Array    # [B, S // bk]
    full_q: jax.Array    # [B, S // bk]


def block_table(segment_ids: jax.Array, bq: int, bk: int,
                window: Optional[int] = None) -> BlockTable:
    """From `segment_ids` `[B, S]`, whose documents are contiguous (a
    document is a run of one id: data/tokens.py packs them so), and the
    window, if there is one."""
    seq = segment_ids.shape[1]
    at = jnp.arange(seq, dtype=jnp.int32)
    differs = segment_ids[:, 1:] != segment_ids[:, :-1]
    edge = jnp.ones_like(segment_ids[:, :1], bool)
    # a position's document runs from `start` to `end`, both inside it
    start = jax.lax.cummax(
        jnp.where(jnp.concatenate([edge, differs], 1), at, 0), axis=1)
    end = jax.lax.cummin(
        jnp.where(jnp.concatenate([differs, edge], 1), at, seq - 1),
        axis=1, reverse=True)
    table = BlockTable(
        first_kv=start[:, ::bq] // bk,
        full_kv=-(-start[:, bq - 1::bq] // bk),
        last_q=end[:, bk - 1::bk] // bq,
        full_q=(end[:, ::bk] + 1) // bq - 1)
    if window is None:
        return table
    # a query block's rows are i*bq .. i*bq + bq - 1, a key block's keys
    # j*bk .. j*bk + bk - 1. The first row's window starts the range; a
    # tile lies wholly inside the window if its last row still sees its
    # first key: i*bq + bq - 1 - j*bk < window
    q0 = at[:seq // bq] * bq
    k0 = at[:seq // bk] * bk
    return BlockTable(
        first_kv=jnp.maximum(table.first_kv,
                             jnp.maximum(q0 - window + 1, 0) // bk),
        full_kv=jnp.maximum(table.full_kv,
                            -(-jnp.maximum(q0 + bq - window, 0) // bk)),
        last_q=jnp.minimum(table.last_q, (k0 + bk + window - 2) // bq),
        full_q=jnp.minimum(table.full_q, (k0 + window) // bq - 1))


def _diagonal(i, bq: int, bk: int):
    """The last key block that query block `i` reaches."""
    return (i * bq + bq - 1) // bk


def block_pair_counts(segment_ids: jax.Array, bq: int, bk: int,
                      window: Optional[int] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """(visited, causal), summed over the rows: the (query block, key
    block) pairs the kernel's grid computes, and those of the causal
    triangle."""
    first = block_table(segment_ids, bq, bk, window).first_kv
    diag = _diagonal(jnp.arange(first.shape[1], dtype=jnp.int32), bq, bk)
    return (jnp.sum(diag[None] - first + 1),
            jnp.sum(diag + 1) * first.shape[0])


# ---- the kernels -----------------------------------------------------------
#
# A grid step holds one tile's blocks for `hb` query heads of one row: the
# heads share the row's table and its mask, and a step's fixed cost (0.24
# us, beside 1.7 us of work a head and 512 x 512 tile; my chip run, PR 27)
# is paid once for them. Query-side refs are `[hb, rows, width]`, key-side
# refs `[hb // rep, rows, width]` where `rep` query heads of the step share
# a key/value head (1: every head has its own); a loop walks the query
# heads.


def _mask(seg_col, seg_row, a_first, b_first, shape, a_is_query, window):
    """`[A, B]` bool: the pair is of one document, the key does not come
    after the query and, under a window, lies fewer than `window`
    positions before it. seg_col `[A, 128]` (the ids of the tile's rows
    down the sublanes, the same in every lane), seg_row `[1, B]`;
    a_first, b_first: the tile's first positions."""
    a_at = a_first + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    b_at = b_first + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    near = a_at >= b_at if a_is_query else a_at <= b_at
    if window is not None:
        near &= (a_at - b_at if a_is_query else b_at - a_at) < window
    return near & (_wide(seg_col, shape[1]) == seg_row)


def _scores(a, b, mask, scale):
    """fp32 `a b^T * scale` of `a` `[A, D]` and `b` `[B, D]`, `_MASKED`
    outside `mask` if there is one."""
    s = jax.lax.dot_general(a, b, _NT,
                            preferred_element_type=jnp.float32) * scale
    return s if mask is None else jnp.where(mask, s, _MASKED)


def _wide(col, n: int):
    """A `[..., rows, 128]` column, the same in every lane, as
    `[..., rows, n]`."""
    if n == _LANES:
        return col
    reps = (1,) * (col.ndim - 1) + (-(-n // _LANES),)
    return jnp.tile(col, reps)[..., :n]


def _shared(h, rep: int):
    """The step's key/value head that its query head `h` reads."""
    return h if rep == 1 else h // rep


def _each_head(hb: int, tile, visited, full, make_mask):
    """`tile(h, mask)` for the step's heads, under `pl.when`: without a
    mask where the tile is `full`, with the row's on the range's other
    tiles."""
    def heads(mask):
        jax.lax.fori_loop(0, hb, lambda h, _: tile(h, mask), None)

    pl.when(visited & full)(lambda: heads(None))
    pl.when(visited & jnp.logical_not(full))(lambda: heads(make_mask()))


def _key_blocks_of(first_ref, full_ref, segq_ref, segk_ref, steps_a_row, bq,
                   bk, window):
    """`_each_head`'s (visited, full, make_mask) for the kernels whose
    grid is (g, query block i, key block j)."""
    g, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    at = (g // steps_a_row) * pl.num_programs(1) + i
    return ((j >= first_ref[at]) & (j <= _diagonal(i, bq, bk)),
            (j >= full_ref[at]) & (j * bk + bk - 1 <= i * bq),
            lambda: _mask(segq_ref[...], segk_ref[...], i * bq, j * bk,
                          (bq, bk), True, window))


def _fwd_kernel(first_ref, full_ref, q_ref, k_ref, v_ref, segq_ref, segk_ref,
                o_ref, lse_ref, m_ref, l_ref, acc_ref, *, steps_a_row, scale,
                bq, bk, window, rep):
    j = pl.program_id(2)
    d_v = acc_ref.shape[-1]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(h, mask):
        hk = _shared(h, rep)
        s = _scores(q_ref[h], k_ref[hk], mask, scale)
        m_prev = m_ref[h]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a row whose keys in this tile are all masked adds exp(0) = 1 a
        # key here; the diagonal tile, visited last, has a key of its
        # own, a maximum above _MASKED, and alpha = 0 wipes what was added
        p = jnp.exp(s - _wide(m_next, bk))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[h] = m_next
        acc_ref[h] = _wide(alpha, d_v) * acc_ref[h] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[hk],
            preferred_element_type=jnp.float32)

    _each_head(q_ref.shape[0], tile, *_key_blocks_of(
        first_ref, full_ref, segq_ref, segk_ref, steps_a_row, bq, bk,
        window))

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / _wide(l, d_v)).astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l)


def _dq_kernel(first_ref, full_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, segq_ref, segk_ref, dq_ref, acc_ref, *,
               steps_a_row, scale, bq, bk, window, rep):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(h, mask):
        hk = _shared(h, rep)
        k = k_ref[hk]
        p = jnp.exp(_scores(q_ref[h], k, mask, scale)
                    - _wide(lse_ref[h], bk))
        dp = jax.lax.dot_general(do_ref[h], v_ref[hk], _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - _wide(delta_ref[h], bk))
        acc_ref[h] += jnp.dot(ds.astype(k.dtype), k,
                              preferred_element_type=jnp.float32)

    _each_head(q_ref.shape[0], tile, *_key_blocks_of(
        first_ref, full_ref, segq_ref, segk_ref, steps_a_row, bq, bk,
        window))

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(last_ref, full_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, segq_ref, segk_ref, dk_ref, dv_ref, dk_acc,
                dv_acc, *, steps_a_row, scale, bq, bk, window, rep):
    """The transposed tile: keys down the sublanes, queries along the
    lanes, so the row statistics are rows and no matmul transposes. The
    `rep` query heads that share a key/value head add into its one
    accumulator."""
    g, j, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    at = (g // steps_a_row) * pl.num_programs(1) + j

    @pl.when(i == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def tile(h, mask):
        hk = _shared(h, rep)
        q, do = q_ref[h], do_ref[h]
        p = jnp.exp(_scores(k_ref[hk], q, mask, scale) - lse_ref[h])
        dv_acc[hk] += jnp.dot(p.astype(do.dtype), do,
                              preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_ref[hk], do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[h])
        dk_acc[hk] += jnp.dot(ds.astype(q.dtype), q,
                              preferred_element_type=jnp.float32)

    _each_head(q_ref.shape[0], tile,
               (i >= (j * bk) // bq) & (i <= last_ref[at]),
               (i * bq >= j * bk + bk - 1) & (i <= full_ref[at]),
               lambda: _mask(segk_ref[...], segq_ref[...], j * bk, i * bq,
                             (bk, bq), False, window))

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


# ---- their calls -----------------------------------------------------------

_HEADS_A_STEP = 4  # at most; VMEM holds their blocks twice over
# a group that no such number divides is held whole up to this many heads
# (7 heads' q, o, do blocks of 512 x 128 bf16 are 0.9 MB each, their fp32
# statistics and accumulators 1.8 MB each: inside `_VMEM_LIMIT`)
_GROUP_A_STEP = 8
# of a v5e's 128 MiB: the backward's blocks for 4 heads pass the 16 MiB a
# kernel is given unasked
_VMEM_LIMIT = 64 * 1024 * 1024


class _Static(NamedTuple):
    heads: int  # query heads of a row: q is [rows x heads, S, D]
    scale: float
    bq: int
    bk: int
    interpret: bool
    kv_heads: Optional[int] = None  # of a row; None: as many as `heads`
    window: Optional[int] = None

    @property
    def group(self) -> int:
        """Query heads that share one key/value head."""
        return _group(self.heads, self.kv_heads or self.heads)

    @property
    def hb(self) -> int:
        """Query heads a grid step holds: the largest divisor of `heads`
        up to `_HEADS_A_STEP` that holds whole groups or lies inside
        one; where only 1 is (a group of 7), the whole group, up to
        `_GROUP_A_STEP`: one K/V fetch, one mask and one step's fixed
        cost for its heads, and `dk`/`dv` summed in the step's scratch.
        At 7 heads on 1 of 128 over rows of 16,384 that is 7.9 against
        13.3 ms a window layer's forward and backward and 10.7 against
        16.0 a full layer's (my chip run, PR 45)."""
        few = max(n for n in range(1, _HEADS_A_STEP + 1)
                  if self.heads % n == 0
                  and (n % self.group == 0 or self.group % n == 0))
        if few == 1 and 1 < self.group <= _GROUP_A_STEP:
            return self.group
        return few

    @property
    def rep(self) -> int:
        """Query heads of a step that share a key/value head."""
        return min(self.group, self.hb)

    @property
    def hkv(self) -> int:
        """Key/value heads a grid step holds."""
        return self.hb // self.rep

    def kv_step(self, g):
        """Grid step `g`'s block along the folded key/value heads, in
        blocks of `hkv`."""
        return g if self.hb % self.group == 0 else g * self.hb // self.group


def _call(kernel, st: _Static, name, grid, prefetch, in_specs, inputs,
          out_specs, out_shape, scratch):
    return pl.pallas_call(
        functools.partial(kernel, steps_a_row=st.heads // st.hb,
                          scale=st.scale, bq=st.bq, bk=st.bk,
                          window=st.window, rep=st.rep),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=st.interpret,
        name=name,  # in the compiled HLO and the device trace
    )(*prefetch, *inputs)


def _query_major_specs(st: _Static, nq, d_qk, d_v):
    """Block specs of the kernels whose grid is (g, query block i, key
    block j), g over rows x groups of `hb` heads: q-side blocks follow
    `i`, key-side blocks follow `j` clamped into the range the table
    gives."""
    hb, hkv, bq, bk = st.hb, st.hkv, st.bq, st.bk
    steps_a_row = st.heads // hb

    def kv(g, i, j, first_ref, full_ref):
        return jnp.clip(j, first_ref[(g // steps_a_row) * nq + i],
                        _diagonal(i, bq, bk))

    spec = pl.BlockSpec
    return dict(
        q=spec((hb, bq, d_qk), lambda g, i, j, *_: (g, i, 0)),
        k=spec((hkv, bk, d_qk),
               lambda g, i, j, *t: (st.kv_step(g), kv(g, i, j, *t), 0)),
        v=spec((hkv, bk, d_v),
               lambda g, i, j, *t: (st.kv_step(g), kv(g, i, j, *t), 0)),
        o=spec((hb, bq, d_v), lambda g, i, j, *_: (g, i, 0)),
        stat=spec((hb, bq, _LANES), lambda g, i, j, *_: (g, i, 0)),
        seg_q=spec((None, bq, _LANES),
                   lambda g, i, j, *_: (g // steps_a_row, i, 0)),
        seg_k=spec((None, 1, bk),
                   lambda g, i, j, *t: (g // steps_a_row, 0,
                                        kv(g, i, j, *t))))


def _lanes(x):
    """[..., S] -> [..., S, 128]: a column the kernel reads down the
    sublanes, the same in every lane."""
    return jnp.broadcast_to(x[..., None], x.shape + (_LANES,))


@functools.partial(jax.jit, static_argnums=0)
def _forward(st: _Static, q, k, v, segment_ids, table: BlockTable):
    g, seq, d_qk = q.shape
    d_v = v.shape[-1]
    hb = st.hb
    nq, nk = seq // st.bq, seq // st.bk
    sp = _query_major_specs(st, nq, d_qk, d_v)
    o, lse = _call(
        _fwd_kernel, st, "lm_attention_fwd", (g // hb, nq, nk),
        (table.first_kv.reshape(-1), table.full_kv.reshape(-1)),
        [sp["q"], sp["k"], sp["v"], sp["seg_q"], sp["seg_k"]],
        (q, k, v, _lanes(segment_ids), segment_ids[:, None, :]),
        [sp["o"], sp["stat"]],
        [jax.ShapeDtypeStruct((g, seq, d_v), v.dtype),
         jax.ShapeDtypeStruct((g, seq, _LANES), jnp.float32)],
        [pltpu.VMEM((hb, st.bq, _LANES), jnp.float32),
         pltpu.VMEM((hb, st.bq, _LANES), jnp.float32),
         pltpu.VMEM((hb, st.bq, d_v), jnp.float32)])
    return o, lse[..., 0]


@functools.partial(jax.jit, static_argnums=0)
def _backward(st: _Static, q, k, v, segment_ids, table: BlockTable, o, lse,
              do, dlse=None):
    """`dlse`: the cotangent of the row log-sum-exp where that is an
    output too. d lse / d s = p, so it adds `dlse * p` to `dS = p * (dP -
    delta)`: the kernels are handed `delta - dlse` and are as they were."""
    g, seq, d_qk = q.shape
    d_v = v.shape[-1]
    hb, bq, bk = st.hb, st.bq, st.bk
    steps_a_row = st.heads // hb
    nq, nk = seq // bq, seq // bk
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse
    seg_col, seg_row = _lanes(segment_ids), segment_ids[:, None, :]

    sp = _query_major_specs(st, nq, d_qk, d_v)
    dq = _call(
        _dq_kernel, st, "lm_attention_dq", (g // hb, nq, nk),
        (table.first_kv.reshape(-1), table.full_kv.reshape(-1)),
        [sp["q"], sp["k"], sp["v"], sp["o"], sp["stat"], sp["stat"],
         sp["seg_q"], sp["seg_k"]],
        (q, k, v, do, _lanes(lse), _lanes(delta), seg_col, seg_row),
        sp["q"], jax.ShapeDtypeStruct(q.shape, q.dtype),
        [pltpu.VMEM((hb, bq, d_qk), jnp.float32)])

    def qi(g_, j, i, last_ref, full_ref):
        return jnp.clip(i, (j * bk) // bq,
                        last_ref[(g_ // steps_a_row) * nk + j])

    spec = pl.BlockSpec
    hkv = st.hkv
    key_side = lambda d: spec(
        (hkv, bk, d), lambda g_, j, i, *_: (st.kv_step(g_), j, 0))
    # a step writes the sums over its own query heads: one block a step
    key_out = lambda d: spec((hkv, bk, d), lambda g_, j, i, *_: (g_, j, 0))
    steps = g // hb
    query_side = lambda d: spec(
        (hb, bq, d), lambda g_, j, i, *t: (g_, qi(g_, j, i, *t), 0))
    row = lambda: spec((hb, 1, bq),
                       lambda g_, j, i, *t: (g_, 0, qi(g_, j, i, *t)))
    dk, dv = _call(
        _dkv_kernel, st, "lm_attention_dkv", (g // hb, nk, nq),
        (table.last_q.reshape(-1), table.full_q.reshape(-1)),
        [query_side(d_qk), key_side(d_qk), key_side(d_v), query_side(d_v),
         row(), row(),
         spec((None, 1, bq),
              lambda g_, j, i, *t: (g_ // steps_a_row, 0, qi(g_, j, i, *t))),
         spec((None, bk, _LANES),
              lambda g_, j, i, *_: (g_ // steps_a_row, j, 0))],
        (q, k, v, do, lse[:, None, :], delta[:, None, :], seg_row, seg_col),
        [key_out(d_qk), key_out(d_v)],
        [jax.ShapeDtypeStruct((steps * hkv, seq, d_qk), k.dtype),
         jax.ShapeDtypeStruct((steps * hkv, seq, d_v), v.dtype)],
        [pltpu.VMEM((hkv, bk, d_qk), jnp.float32),
         pltpu.VMEM((hkv, bk, d_v), jnp.float32)])
    if st.group > hb:
        # a key/value head's query heads span `group // hb` steps, which
        # lie side by side: their sums are added here
        dk, dv = (jnp.sum(x.reshape(k.shape[0], st.group // hb, seq, -1)
                          .astype(jnp.float32), axis=1).astype(x.dtype)
                  for x in (dk, dv))
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(st: _Static, q, k, v, segment_ids, table: BlockTable):
    return _forward(st, q, k, v, segment_ids, table)[0]


def _flash_fwd(st, q, k, v, segment_ids, table):
    o, lse = _forward(st, q, k, v, segment_ids, table)
    return o, (q, k, v, segment_ids, table, o, lse)


def _flash_bwd(st, res, do):
    q, k, v, segment_ids, table, o, lse = res
    dq, dk, dv = _backward(st, q, k, v, segment_ids, table, o, lse, do)
    return dq, dk, dv, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_lse(st: _Static, q, k, v, segment_ids, table: BlockTable):
    """`_flash` with the row log-sum-exp `[G, S]` as a second output,
    for a caller that goes on with the softmax over further keys."""
    return _forward(st, q, k, v, segment_ids, table)


def _flash_lse_fwd(st, q, k, v, segment_ids, table):
    o, lse = _forward(st, q, k, v, segment_ids, table)
    return (o, lse), (q, k, v, segment_ids, table, o, lse)


def _flash_lse_bwd(st, res, cotangents):
    q, k, v, segment_ids, table, o, lse = res
    do, dlse = cotangents
    dq, dk, dv = _backward(st, q, k, v, segment_ids, table, o, lse, do, dlse)
    return dq, dk, dv, None, None


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_document_attention(q, k, v, segment_ids, *, scale: float,
                             window: Optional[int] = None,
                             interpret: bool = False,
                             return_lse: bool = False):
    """`document_attention`'s kernel path (module docstring), for shapes
    `kernel_blocks` takes. `interpret=True` runs the kernels in Pallas's
    interpreter: the tests' way to them without a chip."""
    b, seq, heads, d_qk = q.shape
    blocks = kernel_blocks(seq, d_qk, v.shape[-1])
    if blocks is None:
        raise ValueError(f"no kernel for {seq} positions of widths {d_qk} "
                         f"and {v.shape[-1]}")
    st = _Static(heads, float(scale), *blocks, interpret, k.shape[2], window)
    args = (st, _fold(q), _fold(k), _fold(v), segment_ids,
            block_table(segment_ids, *blocks, window))
    if not return_lse:
        return _unfold(_flash(*args), b)
    out, lse = _flash_lse(*args)
    return _unfold(out, b), _unfold_rows(lse, b)


def document_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                       segment_ids: jax.Array, *, scale: float,
                       block: int, window: Optional[int] = None,
                       return_lse: bool = False):
    """softmax(q k^T * scale) v over the earlier tokens of the same
    document, all of them or those fewer than `window` positions back.
    q `[B, S, H, D]`, k `[B, S, H_kv, D]`, v `[B, S, H_kv, Dv]` with
    `H % H_kv == 0` (query head h reads key/value head h // (H // H_kv)),
    segment_ids `[B, S]` (pad positions share id 0 and see each other:
    their output is never read). Returns `[B, S, H, Dv]` in v's dtype.
    `block` is the XLA path's block of query rows; the kernel's blocks
    are its own. `return_lse`: also the log of each row's sum of
    `exp(score)` over its visible keys, `[B, S, H]` fp32, through which
    the gradient flows too: what a caller needs to go on with the same
    softmax over further keys (ops/lm_eva.py)."""
    if window is not None and window < 1:
        raise ValueError(f"a window of {window} positions holds no key")
    if (jax.default_backend() == "tpu"
            and kernel_blocks(q.shape[1], q.shape[-1], v.shape[-1])):
        return flash_document_attention(q, k, v, segment_ids, scale=scale,
                                        window=window, return_lse=return_lse)
    return xla_document_attention(q, k, v, segment_ids, scale=scale,
                                  block=block, window=window,
                                  return_lse=return_lse)
