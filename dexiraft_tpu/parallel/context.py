"""Sequence/context parallelism for the correlation volume.

The all-pairs volume is quadratic in pixels exactly like attention in
tokens — (H/8*W/8)^2 entries (SURVEY.md §5). For frames too large for one
chip's HBM, shard the QUERY axis (the volume's first HW dimension) across
a 'seq' mesh axis: each chip builds and looks up only its row-block of
the volume against the replicated target features — flash-attention-style
row parallelism with zero per-iteration communication (the only
collective is the all-gather of fmap2, inserted once by the partitioner).

Two complementary mechanisms:
  * context_parallel_corr — explicit shard_map over a (data, seq) mesh;
    used when you want manual control (and it documents the math).
  * spatial input shardings (parallel.mesh.spatial_sharding) — GSPMD
    auto-partitioning of the full train step: annotate batch images with
    P('data', 'seq') over H and XLA partitions the encoders (halo
    exchanges), the volume matmul, and the lookup automatically.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh

from dexiraft_tpu.ops.corr import build_corr_pyramid, corr_lookup
from dexiraft_tpu.parallel.layout import LAYOUT, SEQ_AXIS


def context_parallel_corr(
    fmap1: jax.Array,
    fmap2: jax.Array,
    coords: jax.Array,
    mesh: Mesh,
    num_levels: int = 4,
    radius: int = 4,
) -> jax.Array:
    """Row-sharded all-pairs correlation lookup.

    fmap1, fmap2: (B, H, W, D); coords: (B, H, W, 2) in level-0 pixels.
    fmap1/coords shard over H on the 'seq' axis; fmap2 replicates (it is
    the target space every query row needs). Each shard materializes its
    (B * H_loc * W, H, W) volume slice and samples it — the full volume
    never exists on any single chip.

    Returns (B, H, W, num_levels * (2r+1)^2), sharded like the inputs.
    """
    if not LAYOUT.has_seq(mesh):
        raise ValueError(f"mesh has no '{SEQ_AXIS}' axis: {mesh.axis_names}")
    q_spec = LAYOUT.corr_query_rows()

    @partial(shard_map, mesh=mesh,
             in_specs=(q_spec, LAYOUT.replicated(), q_spec),
             out_specs=q_spec)
    def _lookup(f1_loc, f2_full, coords_loc):
        pyr = build_corr_pyramid(f1_loc, f2_full, num_levels, radius)
        return corr_lookup(pyr, coords_loc)

    return _lookup(fmap1, fmap2, coords)


def ring_corr_lookup(
    fmap1: jax.Array,
    fmap2: jax.Array,
    coords: jax.Array,
    mesh: Mesh,
    num_levels: int = 4,
    radius: int = 4,
) -> jax.Array:
    """Ring context-parallel correlation lookup — the ring-attention analog.

    Both QUERY and TARGET rows shard over the 'seq' axis. Per level, each
    chip's target-feature block rotates around the ring (lax.ppermute over
    ICI, exactly ring attention's rotating KV blocks); at each of the
    n_seq steps a chip correlates its queries against the visiting block
    (partial volume matmul) and accumulates that block's window
    contribution through a row-offset hat stencil. The hat supports
    partition across blocks, so the accumulated rows equal the unsharded
    lookup exactly.

    vs. context_parallel_corr (replicated fmap2, per-chip volume slice
    B·H_loc·W × H·W): peak transient here is B·H_loc·W × H_loc·W — the
    quadratic object shrinks with the SQUARE of the ring size, and no
    all-gather of fmap2 is needed. Comm per lookup = the fmap2 pyramid
    once around the ring (~1.33·H·W·C/n_seq per hop).

    Requires H % (n_seq · 2^(num_levels-1)) == 0 so the VALID 2x2 pooling
    of row blocks composes to the global pooling (no window straddles a
    block boundary).

    Returns (B, H, W, num_levels * (2r+1)^2), sharded like the inputs.
    """
    if not LAYOUT.has_seq(mesh):
        raise ValueError(f"mesh has no '{SEQ_AXIS}' axis: {mesh.axis_names}")
    n_seq = mesh.shape[SEQ_AXIS]
    h = fmap1.shape[1]
    if h % n_seq != 0 or (h // n_seq) % (2 ** (num_levels - 1)) != 0:
        raise ValueError(
            f"H={h} must be divisible by n_seq={n_seq} with blocks "
            f"divisible by 2^{num_levels - 1} for pooling alignment")
    q_spec = LAYOUT.corr_query_rows()
    fwd = [(i, (i + 1) % n_seq) for i in range(n_seq)]

    from dexiraft_tpu.ops.corr import (
        _axis_interp_matrix,
        all_pairs_correlation,
        avg_pool_2x2,
    )

    @partial(shard_map, mesh=mesh,
             in_specs=(q_spec, q_spec, q_spec), out_specs=q_spec)
    def _lookup(f1_loc, f2_loc, coords_loc):
        b, h_loc, w = f1_loc.shape[:3]
        n = b * h_loc * w
        idx = jax.lax.axis_index(SEQ_AXIS)
        flat = coords_loc.reshape(n, 2).astype(jnp.float32)
        win = 2 * radius + 1

        out = []
        f2_l = f2_loc.astype(jnp.float32)
        for lvl in range(num_levels):
            h_blk, wl = f2_l.shape[1], f2_l.shape[2]
            centers = flat / (2.0 ** lvl)
            ax = _axis_interp_matrix(centers[:, 0], radius, wl)

            # static unroll: n_seq - 1 ppermute hops (the last visiting
            # block needs no onward rotation)
            rows = jnp.zeros((n, win, wl), jnp.float32)
            blk = f2_l
            for s in range(n_seq):
                src = jax.lax.rem(idx - s + n_seq, n_seq)
                vol = all_pairs_correlation(f1_loc, blk)[..., 0]
                ay = _axis_interp_matrix(centers[:, 1], radius, h_blk,
                                         offset=(src * h_blk).astype(
                                             jnp.float32))
                rows = rows + jnp.einsum("nby,nyx->nbx", ay, vol,
                                         preferred_element_type=jnp.float32)
                if s < n_seq - 1:
                    blk = jax.lax.ppermute(blk, SEQ_AXIS, fwd)

            window = jnp.einsum("nax,nbx->nab", ax, rows,
                                preferred_element_type=jnp.float32)
            out.append(window.reshape(b, h_loc, w, win * win))
            f2_l = avg_pool_2x2(f2_l)
        return jnp.concatenate(out, axis=-1)

    return _lookup(fmap1, fmap2, coords)
