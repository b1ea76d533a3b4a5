"""The mixer's share of the chip's roofline: the least time the chip
could take for the pairs a step needs
(benchmarks/lm_counts_eva.py `attention_roofline_seconds`: 11 products a
pair and head under recomputation, from the exact (query, key) pairs
inside window and document and the exact (query, summary) pairs of the
traced steps' own rows, never block pairs or the static prefixes the
program multiplies; bytes of q, k, v, o once a call; against peaks.json)
over the time under `lm/eva/local/kernel`, `lm/eva/pool`,
`lm/eva/remote` and `lm/eva/merge`. The time also holds the table, the
head folds, the pooling, the merge and what a tile or a prefix computes
and masks, so the share errs low and cannot pass 100 %.
"""

from benchmarks import lm_counts_eva
from benchmarks.layer_metrics.lm_moe_device_ms import scope_ms


def read(obs):
    c = obs.counters
    ms = scope_ms(obs, "lm/eva/local/kernel", "lm/eva/pool", "lm/eva/remote",
                  "lm/eva/merge")
    if not ms or obs.peaks is None or "traced_pairs_local" not in c:
        return None
    least = lm_counts_eva.attention_roofline_seconds(
        c["traced_pairs_local"] + c["traced_pairs_remote"],
        c["attn_layers_local"], c["batch"] * c["seq_len"],
        c["attn_heads_held"], c["attn_head_dim"], bool(c["remat"]),
        obs.peaks)["seconds"]
    return least * 1e3 / ms * 100
