"""Device time a step spends under `lm/head_loss`: the output head and
the cross-entropy, a row of the batch at a time, forward, recomputation
and backward (`lm_moe_device_ms.scope_ms`).
"""

from benchmarks.layer_metrics.lm_moe_device_ms import scope_ms


def read(obs):
    return scope_ms(obs, "lm/head_loss")
