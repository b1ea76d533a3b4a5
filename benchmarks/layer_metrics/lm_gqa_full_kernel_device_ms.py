"""Device time a step spends under `lm/gqa/full/kernel`: the document
attention of the full layers, over all earlier keys of a document
(`lm_gqa_window_kernel_device_ms` for the other kind).
"""

from benchmarks.layer_metrics.lm_moe_device_ms import scope_ms


def read(obs):
    return scope_ms(obs, "lm/gqa/full/kernel")
