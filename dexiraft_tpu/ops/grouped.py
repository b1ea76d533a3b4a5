"""Grouped matrix product: rows sorted by group, one weight a group.

`rows [M, K]` holds the rows of group 0, then group 1, ...;
`group_sizes [G]` says how many each has (data-dependent: the expert
layer's routing decides them every step) and `weights [G, K, N]` gives
each group its matrix. Rows past `sum(group_sizes)` belong to no group
and their output is unspecified: the caller masks them.

`jax.lax.ragged_dot`. XLA:TPU lowers it to a tiled Mosaic kernel of its
own (`ragged-dot-none`, 512-row tiles, a tile table computed from
`group_sizes` on the device), so work follows the rows that are there,
not `M`; its two gradients are ragged products too. The cell's
`lm_moe_experts_roofline_pct` reads its share of the chip's peak
(benchmarks/lm_counts.py has the FLOPs and bytes).
"""

from __future__ import annotations

import jax


def grouped_matmul(rows: jax.Array, weights: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    return jax.lax.ragged_dot(rows, weights, group_sizes,
                              preferred_element_type=rows.dtype)
