"""EVA chunked linear attention over packed rows: exact softmax attention
inside the query's own window, a learned summary of every chunk before
that window, both under ONE softmax.

A row of `T` positions is cut, by the row's own positions, into windows of
`window` positions and chunks of `chunk` (`window % chunk == 0`). With
`d(n)` the document of position `n` (0 is pad), `w(n) = n // window`:

    chunk c, head h:  D(c) = the document of the chunk's last non-pad position
                      a_m  = softmax over the chunk's m with d(m) = D(c) of scale * (k_m . phi_h)
                      kk_c = sum_m a_m k_m + mu_h;   vv_c = sum_m a_m v_m
    query n:          L(n) = { m : d(m) = d(n), w(m) = w(n), m <= n }     exact keys
                      R(n) = { c : D(c) = d(n), w(c) < w(n) }             summaries
                      o_n  = softmax over L(n) and R(n) together of (scale * q_n . k_m | scale * q_n . kk_c)
                             applied to (v_m | vv_c)

A chunk that holds the end of one document and the start of the next is a
summary of the later one only; the earlier one never looks back at it
(its queries all lie at or before it). So a document's outputs are what
it gives alone at the same row offset with every other position pad.

`eva_attention` is four parts, each under its own `jax.named_scope`:

  * `lm/eva/pool`: the summaries, elementwise in fp32 (`pool`).
  * `lm/eva/local/kernel`: the exact part is `ops/lm_attention.py`'s
    `document_attention` on ids that separate document AND window
    (`local_ids`): on a TPU its Pallas flash kernel, whose block table
    then keeps the grid inside the window's own blocks, elsewhere its
    XLA blocks. It is asked for the rows' log-sum-exp beside its
    normalised output, and the gradient flows through both.
  * `lm/eva/remote`: plain XLA. Window `w`'s queries meet the summaries
    of the chunks of windows `0 .. w - 1`, a static prefix, masked to the
    query's document: `T / window - 1` batched products of
    `[heads, window, D] x [heads, D, w * window / chunk]`, each a
    `jax.checkpoint` (`remote`). At most `T / chunk - window / chunk`
    summaries a query: 1,920 at 32,768 / 2,048 / 16.
  * `lm/eva/merge`: the two parts weighted by their log-sum-exps (`merge`).

Scores, masks and softmax statistics are fp32 whatever the inputs are.
A row of one window has no summaries: the result is `document_attention`'s.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from dexiraft_tpu.ops.lm_attention import (_MASKED, _fold, _unfold,
                                           _unfold_rows, document_attention)


def whole_rows(seq: int, window: int, chunk: int) -> int:
    """The row's length padded to whole chunks and, past one window, to
    whole windows."""
    unit = window if seq > window else chunk
    return -(-seq // unit) * unit


def _pad_rows(x: jax.Array, seq: int) -> jax.Array:
    """Axis 1 padded with zeros (pad positions, id 0) to `seq`."""
    extra = seq - x.shape[1]
    if not extra:
        return x
    return jnp.pad(x, [(0, 0), (0, extra)] + [(0, 0)] * (x.ndim - 2))


def local_ids(segment_ids: jax.Array, window: int) -> jax.Array:
    """`[B, T]` ids that are equal where document and window are: what
    the exact part's `document_attention` is given for its documents.
    Runs of one id are contiguous, as a packed row's documents are."""
    seq = segment_ids.shape[1]
    return (segment_ids * (-(-seq // window))
            + jnp.arange(seq, dtype=segment_ids.dtype) // window)


def chunk_documents(segment_ids: jax.Array, chunk: int) -> jax.Array:
    """`[B, T / chunk]`: D(c), the document of each chunk's last non-pad
    position; 0 for a chunk of pad."""
    b, seq = segment_ids.shape
    seg = segment_ids.reshape(b, seq // chunk, chunk)
    last = jnp.max(jnp.where(seg > 0, jnp.arange(chunk), 0), axis=-1)
    return jnp.take_along_axis(seg, last[..., None], axis=-1)[..., 0]


def pool(k: jax.Array, v: jax.Array, phi: jax.Array, mu: jax.Array,
         segment_ids: jax.Array, *, chunk: int, scale: float
         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """k, v `[B, T, H, D]`, phi, mu `[H, D]` -> (kk, vv `[B, T / chunk,
    H, D]` in k's and v's dtypes, D(c) `[B, T / chunk]`)."""
    b, seq, heads, d = k.shape
    n = seq // chunk
    docs = chunk_documents(segment_ids, chunk)
    member = segment_ids.reshape(b, n, chunk) == docs[..., None]
    k32 = k.astype(jnp.float32).reshape(b, n, chunk, heads, d)
    v32 = v.astype(jnp.float32).reshape(b, n, chunk, heads, d)
    logit = jnp.sum(k32 * phi.astype(jnp.float32), axis=-1) * scale
    a = jax.nn.softmax(jnp.where(member[..., None], logit, _MASKED), axis=2)
    kk = jnp.sum(a[..., None] * k32, axis=2) + mu.astype(jnp.float32)
    vv = jnp.sum(a[..., None] * v32, axis=2)
    return kk.astype(k.dtype), vv.astype(v.dtype), docs


def _window_over_summaries(q, kk, vv, seg_q, docs, scale):
    """One window's queries q `[G, W, D]` over the summaries kk, vv
    `[G, N, D]` of the chunks before it, G = batch x heads; seg_q
    `[G, W]`, docs `[G, N]`. -> (o `[G, W, D]` in vv's dtype, lse
    `[G, W]` fp32). A query with no summary of its document gets
    `lse = _MASKED` (and the mean of `vv`, which `merge` weighs by 0)."""
    s = jnp.einsum("gqd,gnd->gqn", q, kk,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(seg_q[:, :, None] == docs[:, None, :], s, _MASKED)
    # the row maximum behind a barrier, as `lm_attention._block` has it
    top = jax.lax.optimization_barrier(
        jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True)))
    e = jnp.exp(s - top)
    total = jnp.sum(e, axis=-1, keepdims=True)
    o = jnp.einsum("gqn,gnd->gqd", (e / total).astype(vv.dtype), vv)
    return o, (top + jnp.log(total))[..., 0]


def remote(q: jax.Array, kk: jax.Array, vv: jax.Array,
           segment_ids: jax.Array, docs: jax.Array, *, window: int,
           chunk: int, scale: float) -> Tuple[jax.Array, jax.Array]:
    """Every query over the summaries of its document's chunks in the
    windows before its own. q `[B, T, H, D]`, kk, vv `[B, T / chunk, H,
    D]`, docs `[B, T / chunk]` -> (o `[B, T, H, D]`, lse `[B, T, H]`
    fp32; window 0 and every query without a summary: `_MASKED`)."""
    b, seq, heads, d = q.shape
    q, kk, vv = _fold(q), _fold(kk), _fold(vv)
    seg = jnp.repeat(segment_ids, heads, axis=0)
    docs = jnp.repeat(docs, heads, axis=0)
    per = window // chunk
    run = jax.checkpoint(_window_over_summaries, static_argnums=(5,))
    outs = [jnp.zeros((b * heads, window, d), vv.dtype)]
    lses = [jnp.full((b * heads, window), _MASKED, jnp.float32)]
    for w in range(1, seq // window):
        rows = slice(w * window, (w + 1) * window)
        o, lse = run(q[:, rows], kk[:, :w * per], vv[:, :w * per],
                     seg[:, rows], docs[:, :w * per], scale)
        outs.append(o)
        lses.append(lse)
    return (_unfold(jnp.concatenate(outs, axis=1), b),
            _unfold_rows(jnp.concatenate(lses, axis=1), b))


def merge(o_l: jax.Array, lse_l: jax.Array, o_r: jax.Array,
          lse_r: jax.Array) -> jax.Array:
    """Two softmaxes over disjoint key sets as the one over their union:
    each part's normalised output `[B, T, H, D]` weighted by its share
    `exp(lse) / (exp(lse_l) + exp(lse_r))` of the whole sum. fp32 inside,
    `o_l`'s dtype out."""
    top = jax.lax.stop_gradient(jnp.maximum(lse_l, lse_r))
    w_l, w_r = jnp.exp(lse_l - top), jnp.exp(lse_r - top)
    out = (w_l[..., None] * o_l.astype(jnp.float32)
           + w_r[..., None] * o_r.astype(jnp.float32))
    return (out / (w_l + w_r)[..., None]).astype(o_l.dtype)


def eva_attention(q: jax.Array, k: jax.Array, v: jax.Array, phi: jax.Array,
                  mu: jax.Array, segment_ids: jax.Array, *, window: int,
                  chunk: int, scale: float, block: int) -> jax.Array:
    """The module docstring's `o`. q, k, v `[B, T, H, D]` (rotary
    embedding applied), phi, mu `[H, D]`, segment_ids `[B, T]` ->
    `[B, T, H, D]` in v's dtype. `block` is `document_attention`'s."""
    if window % chunk:
        raise ValueError(f"a window of {window} positions is not whole "
                         f"chunks of {chunk}")
    seq = q.shape[1]
    whole = whole_rows(seq, window, chunk)
    q, k, v, segment_ids = (_pad_rows(x, whole)
                            for x in (q, k, v, segment_ids))
    with jax.named_scope("lm/eva/local/kernel"):
        # within one id no key lies a window back: `window` tells the
        # XLA path which key blocks to leave out and leaves the kernel's
        # table as the ids alone make it
        exact = document_attention(
            q, k, v, local_ids(segment_ids, window), scale=scale, block=block,
            window=window, return_lse=whole > window)
    if whole <= window:
        return exact[:, :seq]
    o_l, lse_l = exact
    with jax.named_scope("lm/eva/pool"):
        kk, vv, docs = pool(k, v, phi, mu, segment_ids, chunk=chunk,
                            scale=scale)
    with jax.named_scope("lm/eva/remote"):
        o_r, lse_r = remote(q, kk, vv, segment_ids, docs, window=window,
                            chunk=chunk, scale=scale)
    with jax.named_scope("lm/eva/merge"):
        return merge(o_l, lse_l, o_r, lse_r)[:, :seq]


def pair_counts(segment_ids: jax.Array, *, window: int, chunk: int
                ) -> Tuple[jax.Array, jax.Array]:
    """(local, remote), int32, summed over the rows: the (query, key)
    pairs of every real query's L(n) and the (query, summary) pairs of
    its R(n), exactly, from the ids alone. A query's exact keys run from
    the later of its document's and its window's first position to
    itself. Its summaries are a run of chunks that ends with the last
    chunk before its window: documents are contiguous, so if any chunk
    before the window is its document's, that one is."""
    whole = whole_rows(segment_ids.shape[1], window, chunk)
    seg = _pad_rows(segment_ids, whole)
    b = seg.shape[0]

    def run_position(ids):
        """Each entry's distance from the first of its run of one id."""
        at = jnp.arange(ids.shape[1], dtype=jnp.int32)
        edge = jnp.concatenate([jnp.ones((b, 1), bool),
                                ids[:, 1:] != ids[:, :-1]], axis=1)
        return at - jax.lax.cummax(jnp.where(edge, at, 0), axis=1)

    real = seg > 0
    local = jnp.sum(jnp.where(real, run_position(local_ids(seg, window)) + 1,
                              0))
    if whole <= window:
        return local, jnp.zeros((), jnp.int32)
    docs = chunk_documents(seg, chunk)
    per = window // chunk
    before = slice(per - 1, None, per)  # the last chunk of every window
    doc_before = docs[:, before][:, :-1]                     # [B, NW - 1]
    run_before = (run_position(docs) + 1)[:, before][:, :-1]
    later = seg.reshape(b, -1, window)[:, 1:]                # [B, NW - 1, W]
    queries = jnp.sum((later == doc_before[..., None]) & (later > 0),
                      axis=-1)
    return local, jnp.sum(queries * run_before)
