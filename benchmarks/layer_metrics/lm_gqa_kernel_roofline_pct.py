"""The attention kernels' share of the chip's roofline: the least time
the chip could take for the calls a step makes
(benchmarks/lm_counts_afmoe.py `attention_roofline_seconds`: 11 products
a pair and query head under recomputation, from the exact in-window,
in-document pairs of the traced steps' own rows, never block pairs;
bytes of q, k, v, o, do once a call; against peaks.json) over the time
under the two kernel scopes. The time also holds the table, the head
folds and the tiles a block computes and masks, so the share errs low
and cannot pass 100 %.
"""

from benchmarks import lm_counts_afmoe
from benchmarks.layer_metrics.lm_moe_device_ms import scope_ms


def read(obs):
    c = obs.counters
    ms = scope_ms(obs, "lm/gqa/window/kernel", "lm/gqa/full/kernel")
    if not ms or obs.peaks is None or "traced_pairs_window" not in c:
        return None
    least = sum(lm_counts_afmoe.attention_roofline_seconds(
        c["traced_pairs_" + kind], c["attn_layers_" + kind],
        c["batch"] * c["seq_len"], c["attn_heads_held"],
        c["attn_kv_heads_held"], c["attn_head_dim"], bool(c["remat"]),
        obs.peaks)["seconds"] for kind in ("window", "full"))
    return least * 1e3 / ms * 100
