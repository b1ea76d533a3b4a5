"""FLOPs and bytes of the Nemotron-H cell (`NemotronHConfig`), counted
from shapes: `lm_counts_lfm2.py`'s account for the sixth architecture.

A layer is one block of three kinds. A `mamba` layer is two projections
(W_in, hidden -> 2 H P + 2 G N + H; W_out, H P -> hidden), the
convolution's `conv_kernel` taps a channel of [x; B; C], and the
recurrence, which needs 4 P N FLOP a token and head whatever computes
it: the state's update `h += (dt x) (x) B` and its reading `h C`, a
multiply-add an entry each (the decay's one multiply an entry is left
out, as elementwise work is everywhere here but in the convolution). A
`full` layer is grouped-query attention's four projections over the
held heads and the (query, key) pairs of one document with the key not
after the query (`lm_counts.pairs_in_document`), each a score and a
weighted value over `head_dim` a held query head. An `experts` layer is
the router on the hidden width, the two latent projections, the shared
expert's two products over the columns held, and a routed slot's TWO
products of `moe_latent_size` x `moe_intermediate_size`: no gate
product, and not the hidden width. The head is untied.
tests/test_zz_lm_counts.py holds the dense parts to `flops.py`'s walk
of the plain reference.

`scan_roofline_seconds` is the least time the chip could take for a
step's recurrences, whatever computes them: the 4 P N FLOP a real token
and head at the bf16 peak against one read of x, B, C (bf16) and dt
(fp32) and one write of y (bf16) at the HBM bandwidth, the larger of
the two, a pass; a recomputed layer makes four passes (forward, forward
again, and a backward that is two), three without recomputation. z is
read by the gate and the norm behind the scan (`lm/ssm/gate_norm`), not
by what `lm/ssm/scan` holds, and is left out. The chunked form's pair
products, its decay matrices and its chunk states are its own choice
and are not credited, so the share cannot pass 100 %.

`grouped_roofline_seconds` is `lm_counts.grouped_roofline_seconds` for
experts of two matrices at the latent width: 2 grouped products a slot
forward, 2 more recomputed, 4 backward.

Neither of the two has a reader under `layer_metrics/` yet: the heads,
groups and widths they take are no counter the runner carries, and a
reader that looked them up in one cell's file would credit every later
cell with this one's (PERF.md section 7). PERF.md's shares are these
functions over a traced run's scope times, by hand.
"""

from __future__ import annotations

from typing import Dict

from benchmarks.lm_counts import pairs_in_document

KINDS = {"M": "mamba", "*": "full", "E": "experts"}


def layers_by_kind(cfg) -> Dict[str, int]:
    return {kind: cfg.hybrid_override_pattern.count(letter)
            for letter, kind in KINDS.items()}


def per_token_forward(cfg) -> Dict[str, float]:
    """Forward FLOPs a real token, by part, summed over the layers held:
    everything whose size does not depend on routing or on the
    documents."""
    d, hd = cfg.hidden_size, cfg.head_dim
    heads, kv_heads = cfg.heads_held[1], cfg.kv_heads_held[1]
    ssm_heads, groups = cfg.ssm_heads_held[1], cfg.ssm_groups_held[1]
    inner, bc = ssm_heads * cfg.mamba_head_dim, groups * cfg.ssm_state_size
    kinds = layers_by_kind(cfg)
    return {
        "ssm_projections": kinds["mamba"] * 2 * d * (
            2 * inner + 2 * bc + ssm_heads    # W_in
            + inner),                         # W_out
        "ssm_conv": kinds["mamba"] * 2 * cfg.conv_kernel * (inner + 2 * bc),
        "ssm_scan": kinds["mamba"] * ssm_heads * scan_flops_a_head(cfg),
        "attention_projections": kinds["full"] * 2 * d * hd * (
            2 * heads + 2 * kv_heads),        # W_q, W_o; W_k, W_v
        "router": kinds["experts"] * 2 * d * cfg.n_routed_experts,
        "latent": kinds["experts"] * 2 * 2 * d * cfg.moe_latent_size,
        "shared": kinds["experts"] * 2 * 2 * d * cfg.shared_width,
        "head": 2 * d * cfg.vocab_size,
    }


def scan_flops_a_head(cfg) -> float:
    """Forward FLOPs of the recurrence, a token and head."""
    return 4 * cfg.mamba_head_dim * cfg.ssm_state_size


def per_pair_forward(cfg) -> float:
    """Forward FLOPs of one (query, key) pair of one attention layer over
    the held query heads: a score and a weighted value."""
    return cfg.heads_held[1] * 2 * (cfg.head_dim + cfg.head_dim)


def per_slot_forward(cfg) -> float:
    """Forward FLOPs of one slot (a token at one held expert): the two
    products of an ungated MLP at the latent width."""
    return 2 * 2 * cfg.moe_latent_size * cfg.moe_intermediate_size


def pairs_by_kind(cfg, segment_ids) -> Dict[str, float]:
    """What ONE layer of each kind needs of rows `[B, S]`: `full`, the
    attention layer's (query, key) pairs. A Mamba-2 or an expert layer
    needs nothing that depends on the documents but its mask."""
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


def scan_roofline_seconds(tokens: float, heads: int, head_dim: int,
                          state: int, groups: int, layers: int, remat: bool,
                          peaks: dict) -> Dict[str, float]:
    """The least time the chip could take for the recurrences of a
    step's `layers` Mamba-2 layers over `tokens` real positions (module
    docstring)."""
    passes = 4 if remat else 3
    flops = layers * tokens * passes * heads * 4 * head_dim * state
    a_token = (2 * heads * head_dim * 2    # x read, y written: bf16
               + 2 * groups * state * 2    # B, C: bf16
               + heads * 4)                # dt: fp32
    bytes_ = layers * tokens * passes * a_token
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": bytes_, "seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}


def grouped_calls(remat: bool) -> int:
    """Grouped products one slot passes through in a step: 2 forward, 2
    more when the layer is recomputed, 4 backward."""
    return 2 + (2 if remat else 0) + 4


def grouped_roofline_seconds(latent: int, width: int, experts: int,
                             slots_held: float, experts_layers: int,
                             remat: bool, peaks: dict) -> Dict[str, float]:
    """The least time the chip could take for a step's grouped products:
    `slots_held` is the step's sum over the `experts_layers` expert
    layers, each holding `experts` experts of `latent` x `width`. Bytes:
    each call reads its rows and writes its result once (bf16) and reads
    each held expert's matrix once."""
    calls = grouped_calls(remat)
    flops = slots_held * calls * 2 * latent * width
    bytes_ = (slots_held * calls * (latent + width) * 2
              + experts_layers * experts * calls * latent * width * 2)
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": bytes_, "seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
