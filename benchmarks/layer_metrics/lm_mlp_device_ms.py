"""Device time a step spends under `lm/mlp`: the dense layers' SwiGLU,
forward, recomputation and backward over the layers that have one
(`lm_moe_device_ms.scope_ms`). In an all-dense stack it sets the pace.
"""

from benchmarks.layer_metrics.lm_moe_device_ms import scope_ms


def read(obs):
    return scope_ms(obs, "lm/mlp")
