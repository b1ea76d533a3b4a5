"""FLOPs and bytes of the LFM2 cells (`Lfm2MoeConfig`), counted from
shapes: `lm_counts.py`'s account for the fourth architecture.

A layer's mixer is one of two kinds. A `conv` layer is two projections
(W_in, hidden -> 3 x hidden; W_out, hidden -> hidden: 4 x hidden^2
multiply-adds a token) around the gate, which is elementwise: B * z,
`conv_L_cache` taps and C *, 2 L + 1 operations a channel. A `full`
(attention) layer is afmoe's without the gate: W_q and W_o over the
held query heads, W_k and W_v over the key/value heads they read, and
the (query, key) pairs of one document with the key not after the query
(`lm_counts.pairs_in_document`), each a score and a weighted value over
`head_dim` a held query head. The dense, routed and router parts are
`lm_counts`'s formulas on this configuration's widths; there is no
shared expert; the head is the embedding (tied), and its product is
counted once, as the head's.

tests/test_zz_lm_counts.py holds the pairs to a brute-force count and
the dense parts to `flops.py`'s walk of the plain reference. The taps a
document's start masks are the program's to count (`conv_taps_masked`
in the step's metrics, ops/lm_conv.py `taps_masked`); nothing here
counts them again.

`conv_roofline_seconds` is the least time the chip could take for a
step's convolution mixers, whatever computes them. The projections: 4
hidden^2 multiply-adds a token forward; a recomputed layer runs them
forward, forward again, and two backward products each, so 16 hidden^2
multiply-adds = 32 hidden^2 FLOP a token and layer (12 and 24 without
recomputation), at the bf16 peak. Their operands are far under that in
bytes (a `[T, hidden]` activation once a product). The gate: every
`[T, hidden]` bf16 array once a pass at the HBM bandwidth: forward
reads B, C, z and writes one (4 arrays), the same recomputed, backward
reads those three and the output's gradient and writes three gradients
(7 arrays): 15 arrays, 11 without recomputation. The two are summed:
they are separate operations, one bound by the matrix unit and one by
memory.
"""

from __future__ import annotations

from typing import Dict

from benchmarks.lm_counts import pairs_in_document, per_slot_forward  # noqa: F401
from benchmarks.lm_counts_afmoe import attention_roofline_seconds  # noqa: F401


def layers_by_kind(cfg) -> Dict[str, int]:
    conv = sum(k == "conv" for k in cfg.layer_types)
    return {"conv": conv, "full": len(cfg.layer_types) - conv}


def per_token_forward(cfg) -> Dict[str, float]:
    """Forward FLOPs a real token, by part, summed over the layers held:
    everything whose size does not depend on routing or on the
    documents."""
    d, hd = cfg.hidden_size, cfg.head_dim
    heads, kv_heads = cfg.heads_held[1], cfg.kv_heads_held[1]
    kinds = layers_by_kind(cfg)
    dense_layers = min(cfg.num_dense_layers, cfg.num_hidden_layers)
    moe_layers = cfg.num_hidden_layers - dense_layers
    return {
        "conv_projections": kinds["conv"] * 2 * 4 * d * d,  # W_in, W_out
        "conv_gate": kinds["conv"] * d * (2 * cfg.conv_L_cache + 1),
        "attention_projections": kinds["full"] * 2 * d * hd * (
            2 * heads + 2 * kv_heads),          # W_q, W_o; W_k, W_v
        "dense_mlp": dense_layers * 3 * 2 * d * cfg.intermediate_size,
        "router": moe_layers * 2 * d * cfg.num_experts,
        "head": 2 * d * cfg.vocab_size,
    }


def per_pair_forward(cfg) -> float:
    """Forward FLOPs of one (query, key) pair of one attention layer over
    the held query heads: a score and a weighted value, each over
    `head_dim`."""
    return cfg.heads_held[1] * 2 * (cfg.head_dim + cfg.head_dim)


def pairs_by_kind(cfg, segment_ids) -> Dict[str, float]:
    """What ONE layer of each kind needs of rows `[B, S]`: `full`, an
    attention layer's (query, key) pairs. A convolution layer needs
    nothing that depends on the documents but its mask."""
    import numpy as np

    return {"full": float(sum(pairs_in_document(r)
                              for r in np.asarray(segment_ids)))}


def step_flops(cfg, tokens_real: float, slots_held: float,
               pairs: Dict[str, float]) -> Dict[str, float]:
    """FLOPs one step needs (forward + backward = 3x forward), by part.
    `slots_held` is the step's sum over the expert layers; `pairs` is
    `pairs_by_kind` of the batch."""
    parts = {k: 3 * v * tokens_real for k, v in per_token_forward(cfg).items()}
    parts["attention"] = (3 * per_pair_forward(cfg)
                          * layers_by_kind(cfg)["full"] * pairs["full"])
    parts["routed"] = 3 * per_slot_forward(cfg) * slots_held
    parts["total"] = sum(parts.values())
    return parts


def conv_calls(remat: bool) -> Dict[str, int]:
    """(matrix products a projection passes through in a step, `[T,
    hidden]` arrays the gate moves in a step): module docstring."""
    return {"products": 4 if remat else 3, "gate_arrays": 15 if remat else 11}


def conv_roofline_seconds(tokens: float, hidden: int, layers: int,
                          remat: bool, peaks: dict) -> Dict[str, float]:
    """The least time the chip could take for a step's `layers`
    convolution mixers over `tokens` positions (module docstring): the
    projections' FLOPs over the bf16 peak plus the gate's bytes over the
    HBM bandwidth."""
    calls = conv_calls(remat)
    flops = layers * tokens * calls["products"] * 2 * 4 * hidden * hidden
    bytes_ = layers * tokens * calls["gate_arrays"] * hidden * 2
    return {"flops": flops, "bytes": bytes_,
            "seconds": (flops / peaks["bf16_flops_per_s"]
                        + bytes_ / peaks["hbm_bytes_per_s"])}
