"""Device time a step spends under `optimizer` (train/step.py): the
gradient norm, the clip and AdamW over the fp32 masters, a pass over
16 bytes a parameter (`lm_moe_device_ms.scope_ms`).
"""

from benchmarks.layer_metrics.lm_moe_device_ms import PREFIX, scope_ms


def read(obs):
    return scope_ms(obs, PREFIX + "optimizer")
