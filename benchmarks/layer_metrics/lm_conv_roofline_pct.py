"""The convolution mixers' share of the chip's roofline: the least time
the chip could take for what a step's convolution layers need
(benchmarks/lm_counts_lfm2.py `conv_roofline_seconds`: the two
projections' 32 hidden^2 FLOP a real token and layer under
recomputation at the bf16 peak, plus the gate's fifteen `[tokens,
hidden]` bf16 arrays at the HBM bandwidth; against peaks.json) over the
time under `lm/conv/`. The needed work, whatever computes it: the time
also holds the pad positions, the slices of the in-projection's output
and whatever the gate's passes read twice, so the share errs low and
cannot pass 100 %.
"""

from benchmarks import lm_counts_lfm2
from benchmarks.layer_metrics.lm_moe_device_ms import scope_ms


def read(obs):
    c = obs.counters
    ms = scope_ms(obs, "lm/conv/")
    if not ms or obs.peaks is None or "attn_layers_conv" not in c:
        return None
    least = lm_counts_lfm2.conv_roofline_seconds(
        c["tokens_real"], c["hidden_size"], c["attn_layers_conv"],
        bool(c["remat"]), obs.peaks)["seconds"]
    return least * 1e3 / ms * 100
