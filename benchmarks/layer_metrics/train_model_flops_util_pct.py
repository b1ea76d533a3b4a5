"""Model FLOP utilisation: FLOPs the algorithm needs per unit of work
(benchmarks/flops.py on the plain form: allpairs, no remat; recomputed
work not counted) x units per second of the measured window, over
chips x the bf16 peak of peaks.json. An end-to-end utilisation, not a
kernel's roofline share.

Train cells, from steps a second; `eval_model_flops_util_pct` is the eval cells' reading,
from pairs a second.
"""


def read(obs):
    c = obs.counters
    if obs.peaks is None or "flops_per_unit" not in c:
        return None
    steps_per_s = obs.end_to_end["train_samples_per_s"] / c["batch"]
    return (c["flops_per_unit"] * steps_per_s
            / (obs.chips * obs.peaks["bf16_flops_per_s"]) * 100)
