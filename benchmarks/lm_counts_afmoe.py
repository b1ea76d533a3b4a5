"""FLOPs and bytes of the AFMoE cells (`AfmoeConfig`: Trinity), counted
from shapes: `lm_counts.py`'s account for the second architecture.

What differs from `lm_counts`: the projections (query, gate, key, value,
output; keys and values for the key/value heads held), and the attention
pairs, which are of two kinds. A full layer needs every (query, key)
pair of one document with the key not after the query
(`lm_counts.pairs_in_document`); a sliding layer needs those of them
fewer than `sliding_window` positions apart (`pairs_in_window`). Both
are counted on the host from the rows, exactly: never from the kernel's
block pairs, which also hold the pairs a tile computes and masks. The
dense, shared, routed, router and head parts are `lm_counts`'s formulas
on this configuration's widths. tests/test_zz_lm_counts.py holds the
dense parts and the pairs to `flops.py`'s walk of the plain reference.

`attention_roofline_seconds` is the attention kernels' least time, call
by call as `lm_counts.grouped_calls` counts the grouped products'.
"""

from __future__ import annotations

from typing import Dict

from benchmarks import lm_counts
from benchmarks.lm_counts import pairs_in_document, per_slot_forward  # noqa: F401


def layers_by_kind(cfg) -> Dict[str, int]:
    window = sum(k == "sliding_attention" for k in cfg.layer_types)
    return {"window": window, "full": len(cfg.layer_types) - window}


def per_token_forward(cfg) -> Dict[str, float]:
    """Forward FLOPs a real token, by part, summed over the layers held:
    every matrix product whose size does not depend on routing or on the
    documents."""
    d, hd = cfg.hidden_size, cfg.head_dim
    heads, kv_heads = cfg.heads_held[1], cfg.kv_heads_held[1]
    layers = cfg.num_hidden_layers
    dense_layers = min(cfg.num_dense_layers, layers)
    moe_layers = layers - dense_layers
    projections = 2 * d * hd * (3 * heads         # W_q, W_g, W_o
                                + 2 * kv_heads)   # W_k, W_v
    return {
        "projections": layers * projections,
        "dense_mlp": dense_layers * 3 * 2 * d * cfg.intermediate_size,
        "shared": moe_layers * 3 * 2 * d * (cfg.num_shared_experts
                                            * cfg.moe_intermediate_size),
        "router": moe_layers * 2 * d * cfg.num_experts,
        "head": 2 * d * cfg.vocab_size,
    }


def per_pair_forward(cfg) -> float:
    """Forward FLOPs of one (query, key) pair of one layer over the held
    query heads: a score and a weighted value, each over `head_dim`."""
    return cfg.heads_held[1] * 2 * (cfg.head_dim + cfg.head_dim)


def pairs_in_window(segment_ids, window: int) -> int:
    """(query, key) pairs of one row with the key in the query's
    document, not after it and fewer than `window` positions before it:
    a document's token t (from 0) sees min(t + 1, window) keys. numpy."""
    import numpy as np

    seg = np.asarray(segment_ids)
    _, counts = np.unique(seg[seg > 0], return_counts=True)
    short = np.minimum(counts, window)
    return int(np.sum(short * (short + 1) // 2 + (counts - short) * window))


def pairs_by_kind(cfg, segment_ids) -> Dict[str, float]:
    """The pairs ONE layer of each kind needs for rows `[B, S]`, keyed as
    `layers_by_kind` is."""
    import numpy as np

    rows = np.asarray(segment_ids)
    return {"full": float(sum(pairs_in_document(r) for r in rows)),
            "window": float(sum(pairs_in_window(r, cfg.sliding_window)
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


def attention_calls(remat: bool) -> Dict[str, int]:
    """Matrix products a (pair, query head) passes through in each
    kernel call of a step: 2 forward (scores, weighted values), the same
    again when the layer is recomputed, 3 in `dq` (scores, dP, dQ), 4 in
    `dk`/`dv` (scores, dV, dP, dK)."""
    calls = {"forward": 2, "dq": 3, "dkv": 4}
    if remat:
        calls["forward_recomputed"] = 2
    return calls


def attention_roofline_seconds(pairs: float, layers: int, tokens: float,
                               heads: int, kv_heads: int, head_dim: int,
                               remat: bool, peaks: dict) -> Dict[str, float]:
    """The least time the chip could take for a step's attention kernels
    over `layers` layers that each need `pairs` (query, key) pairs:
    call by call the larger of FLOPs over the bf16 peak and bytes over
    the HBM bandwidth, summed. A product of a pair and head is
    2 x head_dim FLOP; a call moves q, o or its gradient, and do once
    (`heads` wide) and k, v or their gradients once (`kv_heads` wide),
    bf16, for the `tokens` positions of the batch."""
    per_call_bytes = tokens * head_dim * 2 * (3 * heads + 2 * kv_heads)
    flops = bytes_ = seconds = 0.0
    for products in attention_calls(remat).values():
        call_flops = layers * pairs * heads * products * 2 * head_dim
        call_bytes = layers * per_call_bytes
        flops += call_flops
        bytes_ += call_bytes
        seconds += max(call_flops / peaks["bf16_flops_per_s"],
                       call_bytes / peaks["hbm_bytes_per_s"])
    return {"flops": flops, "bytes": bytes_, "seconds": seconds}
