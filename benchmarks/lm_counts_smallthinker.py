"""FLOPs and bytes of the SmallThinker cell (`SmallThinkerConfig`),
counted from shapes: `lm_counts_afmoe.py`'s account for the fifth
architecture.

What differs from `lm_counts_afmoe`: the projections have no gate
(query, key, value, output), no layer is dense, no expert is shared, and
every layer has a router of `moe_num_primary_experts` outputs. The
attention pairs are of the same two kinds, counted on the host from the
rows, exactly (`lm_counts.pairs_in_document`,
`lm_counts_afmoe.pairs_in_window` at `sliding_window_size`; a pair's
FLOPs are `lm_counts_afmoe.per_pair_forward`'s): a layer
whose `sliding_window_layout` entry is 1 is a `window` layer, the others
`full`. A routed slot is the three products of a gated MLP of
`moe_ffn_hidden_size`, ReLU or SiLU alike. tests/test_zz_lm_counts.py
holds the parts to `flops.py`'s walk of the plain reference.

No kernel is new, so no roofline function is: the grouped products'
share is `lm_counts.grouped_roofline_seconds` on this width, the
attention kernels' `lm_counts_afmoe.attention_roofline_seconds` on 7
query heads and 1 key/value head: the same work however many heads a
grid step of the kernel holds.
"""

from __future__ import annotations

from typing import Dict

from benchmarks.lm_counts import pairs_in_document, per_slot_forward
from benchmarks.lm_counts_afmoe import pairs_in_window, per_pair_forward


def layers_by_kind(cfg) -> Dict[str, int]:
    window = sum(cfg.sliding_window_layout)
    return {"window": window, "full": len(cfg.sliding_window_layout) - window}


def per_token_forward(cfg) -> Dict[str, float]:
    """Forward FLOPs a real token, by part, summed over the layers held:
    every matrix product whose size does not depend on routing or on the
    documents."""
    d, hd = cfg.hidden_size, cfg.head_dim
    heads, kv_heads = cfg.heads_held[1], cfg.kv_heads_held[1]
    layers = cfg.num_hidden_layers
    projections = 2 * d * hd * (2 * heads         # W_q, W_o
                                + 2 * kv_heads)   # W_k, W_v
    return {
        "projections": layers * projections,
        "router": layers * 2 * d * cfg.moe_num_primary_experts,
        "head": 2 * d * cfg.vocab_size,
    }


def pairs_by_kind(cfg, segment_ids) -> Dict[str, float]:
    """The pairs ONE layer of each kind needs for rows `[B, S]`, keyed as
    `layers_by_kind` is."""
    import numpy as np

    rows = np.asarray(segment_ids)
    return {"full": float(sum(pairs_in_document(r) for r in rows)),
            "window": float(sum(pairs_in_window(r, cfg.sliding_window_size)
                                for r in rows))}


def step_flops(cfg, tokens_real: float, slots_held: float,
               pairs: Dict[str, float]) -> Dict[str, float]:
    """FLOPs one step needs (forward + backward = 3x forward), by part.
    `slots_held` is the step's sum over the expert layers; `pairs` is
    `pairs_by_kind` of the batch."""
    kinds = layers_by_kind(cfg)
    parts = {k: 3 * v * tokens_real for k, v in per_token_forward(cfg).items()}
    parts["attention"] = 3 * per_pair_forward(cfg) * sum(
        kinds[k] * pairs[k] for k in kinds)
    parts["routed"] = 3 * per_slot_forward(cfg) * slots_held
    parts["total"] = sum(parts.values())
    return parts
