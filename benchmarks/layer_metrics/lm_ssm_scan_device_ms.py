"""Device time a step spends under `lm/ssm/scan`: the softplus and the
decays, the pair products inside the chunks, the chunks' states and
their recurrence, and the D skip; forward, recomputation and backward
over the Mamba-2 layers held (`lm_moe_device_ms.scope_ms`). A program
without the scope, or a run without a device trace, reads as nothing.
"""

from benchmarks.layer_metrics.lm_moe_device_ms import scope_ms


def read(obs):
    return scope_ms(obs, "lm/ssm/scan")
