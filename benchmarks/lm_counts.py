"""FLOPs and bytes of the language-model cells, counted from shapes.

`flops.py` walks a jaxpr and knows `dot_general` and convolutions; it
does not know `ragged_dot`, and the work of a dropless expert layer
follows the routing, which no shape states. So the count here is
analytic, from the configuration held (`LMConfig`) and three numbers of
the measured window: real (non-pad) tokens a step, slots routed to held
experts a step, and the (query, key) pairs a step that lie in one
document with the key not after the query.

What the algorithm needs, recomputation never counted: a step is the
forward and the backward, and the backward of a matrix product is two
products of the forward's size, so a step is 3x the forward. Pad
positions need nothing. tests/test_zz_lm_counts.py holds the dense parts
to `flops.py`'s walk of the plain reference and the routed part to
slots x 3 x 2 x hidden x width by hand.

The grouped products' roofline share counts what the kernel is asked to
do, call by call, so the forward recomputed under `remat` is a call like
any other there (`grouped_calls`).
"""

from __future__ import annotations

from typing import Dict


def per_token_forward(cfg) -> Dict[str, float]:
    """Forward FLOPs a real token, by part, summed over the layers held:
    every matrix product whose size does not depend on routing or on the
    documents."""
    d = cfg.hidden_size
    heads = cfg.heads_held[1]
    qk, dv = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.v_head_dim
    layers = cfg.num_hidden_layers
    dense_layers = min(cfg.first_k_dense_replace, layers)
    moe_layers = layers - dense_layers
    projections = 2 * (d * heads * qk                      # W_q
                       + d * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
                       + cfg.kv_lora_rank * heads * (cfg.qk_nope_head_dim + dv)
                       + heads * dv * d)                   # W_o
    return {
        "projections": layers * projections,
        "dense_mlp": dense_layers * 3 * 2 * d * cfg.intermediate_size,
        "shared": moe_layers * 3 * 2 * d * (cfg.n_shared_experts
                                            * cfg.moe_intermediate_size),
        "router": moe_layers * 2 * d * cfg.n_routed_experts,
        "head": 2 * d * cfg.vocab_size,
    }


def per_pair_forward(cfg) -> float:
    """Forward FLOPs of one (query, key) pair over the held heads and
    all layers: a score (q.k over nope + rope) and a weighted value."""
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    return cfg.num_hidden_layers * cfg.heads_held[1] * 2 * (qk + cfg.v_head_dim)


def per_slot_forward(cfg) -> float:
    """Forward FLOPs of one slot (a token at one held expert): the three
    products of a SwiGLU of `moe_intermediate_size`."""
    return 3 * 2 * cfg.hidden_size * cfg.moe_intermediate_size


def step_flops(cfg, tokens_real: float, slots_held: float,
               pairs_in_document: float) -> Dict[str, float]:
    """FLOPs one step needs (forward + backward = 3x forward), by part;
    `slots_held` is the step's sum over the expert layers, as the
    program's `moe_slots_held` counter is, `pairs_in_document` the sum
    over the batch's rows."""
    parts = {k: 3 * v * tokens_real for k, v in per_token_forward(cfg).items()}
    parts["attention"] = 3 * per_pair_forward(cfg) * pairs_in_document
    parts["routed"] = 3 * per_slot_forward(cfg) * slots_held
    parts["total"] = sum(parts.values())
    return parts


def pairs_in_document(segment_ids) -> int:
    """(query, key) pairs of one row with the key in the query's document
    and not after it: sum over documents of n (n + 1) / 2. numpy."""
    import numpy as np

    seg = np.asarray(segment_ids)
    _, counts = np.unique(seg[seg > 0], return_counts=True)
    return int(np.sum(counts * (counts + 1) // 2))


def grouped_calls(remat: bool) -> int:
    """Grouped products one slot passes through in a step: 3 forward,
    3 more when the layer is recomputed, 6 backward (each product's two
    gradients)."""
    return 3 + (3 if remat else 0) + 6


def grouped_roofline_seconds(hidden: int, width: int, experts: int,
                             slots_held: float, experts_layers: int,
                             remat: bool, peaks: dict) -> Dict[str, float]:
    """The least time the chip could take for a step's grouped products,
    the larger of FLOPs over the bf16 peak and bytes over the HBM
    bandwidth. `slots_held` is the step's sum over the `experts_layers`
    expert layers, each holding `experts` experts of `hidden` x `width`.
    Bytes: each call reads its rows and writes its result once (bf16)
    and reads each held expert's matrix once."""
    calls = grouped_calls(remat)
    flops = slots_held * calls * 2 * hidden * width
    row_bytes = slots_held * calls * (hidden + width) * 2
    weight_bytes = experts_layers * experts * calls * hidden * width * 2
    bytes_ = row_bytes + weight_bytes
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": bytes_, "seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
