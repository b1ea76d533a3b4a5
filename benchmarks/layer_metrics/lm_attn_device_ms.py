"""Device time a step spends under `lm/mla`: the latent attention's
projections, rotary embedding and the blockwise document attention
(`ops/lm_attention.py`, plain XLA); forward, recomputation and backward
over the layers held (`lm_moe_device_ms.scope_ms`).
"""

from benchmarks.layer_metrics.lm_moe_device_ms import scope_ms


def read(obs):
    return scope_ms(obs, "lm/mla")
