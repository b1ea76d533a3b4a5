"""Device time a step spends under `lm/gqa/window/kernel`: the document
attention of the sliding-window layers (`ops/lm_attention.py`: the three
Pallas calls with the table, the head folds and the backward's row sums
around them), summed over those layers; forward, recomputation and
backward (`lm_moe_device_ms.scope_ms`).
"""

from benchmarks.layer_metrics.lm_moe_device_ms import scope_ms


def read(obs):
    return scope_ms(obs, "lm/gqa/window/kernel")
