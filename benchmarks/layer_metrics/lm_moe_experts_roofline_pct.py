"""The grouped products' share of the chip's roofline: the least time the
chip could take for the calls a step makes (benchmarks/lm_counts.py
`grouped_roofline_seconds`: FLOPs and bytes of 12 grouped products a
slot under recomputation, from the slots the traced steps really routed
to held experts, against min(bf16 peak, intensity x HBM bandwidth) of
peaks.json) over `lm_moe_experts_device_ms`. The time also holds the
SwiGLU's elementwise ops and the masks, so the share errs low.
"""

from benchmarks import lm_counts
from benchmarks.layer_metrics.lm_moe_experts_device_ms import read as device_ms


def read(obs):
    c = obs.counters
    ms = device_ms(obs)
    if not ms or obs.peaks is None or "traced_slots_held" not in c:
        return None
    least = lm_counts.grouped_roofline_seconds(
        c["hidden_size"], c["moe_intermediate_size"], c["experts_held"],
        c["traced_slots_held"], c["experts_layers"], bool(c["remat"]),
        obs.peaks)
    return least["seconds"] * 1e3 / ms * 100
