"""Causal attention within packed documents, by blocks of query rows.

A row of a packed batch holds several documents; a token attends to the
tokens of its own document that do not come after it. At 8192 positions
the whole `[heads, S, S]` score matrix is 1 GiB a head in fp32, so it is
never made: query block `i` (rows `[i*block, (i+1)*block)`) meets keys
`[0, (i+1)*block)` only, which also leaves out the causal mask's upper
blocks (36 of 64 block pairs at 8 blocks). Each block is a
`jax.checkpoint`: the backward recomputes its scores and keeps none.

Plain XLA on purpose: two matmuls and a softmax a block. The blocks
below the diagonal that hold no pair of one document are still computed
and masked; skipping them takes a kernel that reads the segment table
(ROADMAP.md).

Scores, the mask and the softmax are fp32 whatever the inputs are; the
probabilities are cast to `v`'s dtype for the second matmul.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_MASKED = -1e30  # not -inf: a row always holds its own diagonal


def _block(q, k, v, seg_q, seg_k, first_row, scale):
    """q [G, Q, D], k [G, K, D], v [G, K, Dv], G = batch x heads; seg_q
    [G, Q], seg_k [G, K]. The block's first query is row `first_row` of
    the sequence, the keys start at row 0."""
    s = jnp.einsum("gqd,gkd->gqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    rows = first_row + jnp.arange(q.shape[1])
    mask = ((rows[:, None] >= jnp.arange(k.shape[1])[None, :])[None]
            & (seg_q[:, :, None] == seg_k[:, None, :]))
    s = jnp.where(mask, s, _MASKED)
    # the softmax written out, its row maximum behind a barrier: fused
    # with the subtraction, the chip's compiler turns the maximum into a
    # reduce-window as wide as the row (8192 keys: 47 ms a block where
    # the matmuls take one; my chip run, PR 26)
    top = jax.lax.optimization_barrier(
        jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True)))
    e = jnp.exp(s - top)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    return jnp.einsum("gqk,gkd->gqd", p.astype(v.dtype), v)


def document_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                       segment_ids: jax.Array, *, scale: float,
                       block: int) -> jax.Array:
    """softmax(q k^T * scale) v over the earlier tokens of the same
    document. q, k `[B, S, H, D]`, v `[B, S, H, Dv]`, segment_ids
    `[B, S]` (pad positions share id 0 and see each other: their output
    is never read). Returns `[B, S, H, Dv]` in v's dtype."""
    b, seq, heads, _ = q.shape
    block = min(block, seq)
    if seq % block:
        raise ValueError(f"{seq} positions are not whole blocks of {block}")
    # heads beside the batch, in front: the layout the chip's compiler
    # makes plain batched matmuls of
    fold = lambda x: jnp.swapaxes(x, 1, 2).reshape(b * heads, seq, -1)
    q, k, v = fold(q), fold(k), fold(v)
    seg = jnp.repeat(segment_ids, heads, axis=0)
    run = jax.checkpoint(_block, static_argnums=(5, 6))
    out = []
    for first in range(0, seq, block):
        end = first + block
        out.append(run(q[:, first:end], k[:, :end], v[:, :end],
                       seg[:, first:end], seg[:, :end], first, scale))
    out = jnp.concatenate(out, axis=1).reshape(b, heads, seq, -1)
    return jnp.swapaxes(out, 1, 2)
