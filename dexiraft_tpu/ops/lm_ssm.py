"""The selective state-space scan of Mamba-2 over a packed row, within
documents: the `mamba2` mixer's core (models/lm/attention.py `Mamba2`)
behind its convolution and ahead of its gated norm.

Row positions t = 0..T-1 with document ids d(t) (pad is 0); per head h
of size P with a state `[P, N]`, reading the B/C group `h // (H / G)`:

    a_t = exp(dt_t A)                     A < 0 a head, dt_t > 0
    r_t = 0 where t is its document's first token (d(t) != d(t-1), and
          t = 0), 1 elsewhere
    h_t = r_t a_t h_{t-1} + dt_t x_t (x) B_t            h_{-1} = 0
    y_t = h_t C_t + D x_t

The reset makes a document's outputs what it gives alone, wherever it
lies in the row: nothing of another document's state reaches it.

One chunked form for every size (chunks of Q positions; a row that is
not whole chunks is padded to them at its end and the result cut).
With `n_t` the running count of document starts over the row (two
positions lie in one document exactly where no start lies between them,
`n_s = n_t`), `l_t = dt_t A`, and L its segment-aware running sum
inside a chunk, `L_t = sum_{s <= t, n_s = n_t} l_s` (it restarts where a
document does, so nothing of a neighbour's steps is in a document's
decays, not even as a rounding):

    inside a chunk   y_t += sum_{s <= t, n_s = n_t} (C_t . B_s)
                            exp(L_t - L_s) dt_s x_s
    a chunk's state  S_c  = sum_{s, n_s = n_last} exp(L_last - L_s)
                            dt_s x_s (x) B_s
    between chunks   H_{c+1} = [n_last(c) = n_last(c-1)] exp(L_last(c)) H_c
                               + S_c                          H_0 = 0
    from before      y_t += [n_t = n_last(c-1)] exp(L_t) C_t . H_c

The pair products of a chunk (`C B^T` `[Q, Q]` a group, the masked decay
matrix times `dt x`) and the states are matrix products on the MXU with
bf16 operands where the inputs are bf16; `dt`, `l`, their running sums,
the decays, the chunk states and the recurrence between chunks are fp32.
The recurrence is a `lax.scan` over the T / Q chunk states of a row and
head. A row without a boundary and one full of them run the same
program: the reset is part of the decay, no flag, no second path.

Plain XLA, differentiated by autodiff. `doc_counts` says how many resets
and how many chunks with one a batch gives a layer.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from dexiraft_tpu.ops.lm_conv import _shifted


def doc_starts(segment_ids: jax.Array) -> jax.Array:
    """`[B, T]` bool: t is its document's first token (or the row's, or
    the pad's)."""
    return _shifted(segment_ids, 1, -1) != segment_ids


def ssm_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, d: jax.Array, segment_ids: jax.Array,
             chunk: int) -> jax.Array:
    """x `[B, T, H, P]`, dt `[B, T, H]` fp32 (after the softplus), a
    `[H]` fp32 (A, negative), b and c `[B, T, G, N]` (head h reads group
    `h // (H // G)`), d `[H]` fp32, segment_ids `[B, T]` -> y
    `[B, T, H, P]` in x's dtype (module docstring)."""
    t_given = x.shape[1]
    if t_given % chunk:
        to = [(0, 0), (0, -t_given % chunk)]
        x, dt, b, c = (jnp.pad(v, to + [(0, 0)] * (v.ndim - 2))
                       for v in (x, dt, b, c))
        segment_ids = jnp.pad(segment_ids, to)
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    nc, q = t // chunk, chunk
    f32 = jnp.float32

    def chunks(v):
        return v.reshape((bsz, nc, q) + v.shape[2:])

    count = jnp.cumsum(doc_starts(segment_ids), axis=1, dtype=jnp.int32)
    count = chunks(count)                                   # [B, C, Q]
    last = count[:, :, -1]                                  # [B, C]
    before = _shifted(last, 1, -1)       # n at the chunk before's last token
    dt = chunks(dt.astype(f32))                             # [B, C, Q, H]
    # one document and not later: [B, C, Q(t), Q(s)]
    same = (count[:, :, :, None] == count[:, :, None, :]) & jnp.tril(
        jnp.ones((q, q), bool))
    cum = jnp.einsum("bcts,bcsh->bcth", same.astype(f32), dt * a,
                     precision=jax.lax.Precision.HIGHEST)   # L, <= 0
    xdt = (chunks(x).astype(f32) * dt[..., None]).astype(x.dtype)
    xdt = xdt.reshape(bsz, nc, q, g, h // g, p)
    bq, cq = chunks(b), chunks(c)                           # [B, C, Q, G, N]

    # inside a chunk: [B, C, H, Q(t), Q(s)]
    gap = (jnp.swapaxes(cum, 2, 3)[..., :, None]
           - jnp.swapaxes(cum, 2, 3)[..., None, :])
    decay = jnp.exp(jnp.where(same[:, :, None], gap, -jnp.inf))
    pairs = jnp.einsum("bctgn,bcsgn->bcgts", cq, bq,
                       preferred_element_type=f32)
    weights = (pairs[:, :, :, None]
               * decay.reshape(bsz, nc, g, h // g, q, q)).astype(x.dtype)
    y = jnp.einsum("bcgkts,bcsgkp->bctgkp", weights, xdt,
                   preferred_element_type=f32)

    # a chunk's own state, and the chunks' recurrence
    to_last = jnp.exp(jnp.where((count == last[:, :, None])[..., None],
                                cum[:, :, -1:] - cum, -jnp.inf))
    decayed = (xdt.astype(f32) * to_last.reshape(
        bsz, nc, q, g, h // g, 1)).astype(x.dtype)
    states = jnp.einsum("bcsgkp,bcsgn->bcgkpn", decayed, bq,
                        preferred_element_type=f32)
    keep = jnp.where((last == before)[..., None], jnp.exp(cum[:, :, -1]),
                     0.0).reshape(bsz, nc, g, h // g, 1, 1)

    def step(carry, xs):
        keep_c, state_c = xs
        return keep_c * carry + state_c, carry

    _, entering = jax.lax.scan(
        step, jnp.zeros((bsz, g, h // g, p, n), f32),
        (jnp.moveaxis(keep, 1, 0), jnp.moveaxis(states, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                 # H_c
    reach = jnp.where((count == before[:, :, None])[..., None],
                      jnp.exp(cum), 0.0)                    # [B, C, Q, H]
    y = y + jnp.einsum(
        "bctgn,bcgkpn->bctgkp", cq.astype(f32), entering,
        preferred_element_type=f32,
        precision=jax.lax.Precision.HIGHEST) * reach.reshape(
            bsz, nc, q, g, h // g, 1)
    y = y.reshape(bsz, t, h, p) + x.astype(f32) * d[:, None]
    return y.astype(x.dtype)[:, :t_given]


def doc_counts(segment_ids: jax.Array, chunk: int
               ) -> Tuple[jax.Array, jax.Array]:
    """(resets applied to real tokens, chunks that hold one) of
    `segment_ids` `[B, T]`, of one layer: int32 each."""
    starts = doc_starts(segment_ids) & (segment_ids > 0)
    starts = jnp.pad(starts, [(0, 0), (0, -starts.shape[1] % chunk)])
    per_chunk = jnp.any(starts.reshape(starts.shape[0], -1, chunk), axis=-1)
    return (jnp.sum(starts, dtype=jnp.int32),
            jnp.sum(per_chunk, dtype=jnp.int32))
