"""The full attention layers' kernels' share of the chip's roofline, for
a stack whose attention layers are all of the `full` kind: the least
time the chip could take for the calls a step makes
(benchmarks/lm_counts_lfm2.py `attention_roofline_seconds`, afmoe's: 11
products a pair and query head under recomputation, from the exact
in-document pairs of the traced steps' own rows, never block pairs;
bytes of q, k, v, o, do once a call; against peaks.json) over the time
under `lm/gqa/full/kernel`. The time also holds the table, the head
folds and the tiles a block computes and masks, so the share errs low
and cannot pass 100 % (`lm_gqa_kernel_roofline_pct` is the same over
both kinds of layer, for a stack that has a `window` kind).
"""

from benchmarks import lm_counts_lfm2
from benchmarks.layer_metrics.lm_moe_device_ms import scope_ms


def read(obs):
    c = obs.counters
    ms = scope_ms(obs, "lm/gqa/full/kernel")
    if not ms or obs.peaks is None or "traced_pairs_full" not in c:
        return None
    least = lm_counts_lfm2.attention_roofline_seconds(
        c["traced_pairs_full"], c["attn_layers_full"],
        c["batch"] * c["seq_len"], c["attn_heads_held"],
        c["attn_kv_heads_held"], c["attn_head_dim"], bool(c["remat"]),
        obs.peaks)["seconds"]
    return least * 1e3 / ms * 100
