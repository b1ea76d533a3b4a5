"""Device time a step spends under `lm/moe/experts`: the grouped products
of the held experts' SwiGLUs (`ops/grouped.py`, XLA's `ragged-dot`
kernel) with the activation and the masks between them; forward,
recomputation and backward (`lm_moe_device_ms.scope_ms`).
"""

from benchmarks.layer_metrics.lm_moe_device_ms import scope_ms


def read(obs):
    return scope_ms(obs, "lm/moe/experts")
