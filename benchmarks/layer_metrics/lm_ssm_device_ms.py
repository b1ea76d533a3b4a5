"""Device time a step spends under `lm/ssm/`: the Mamba-2 mixers'
in-projection (`lm/ssm/in`), their convolution (`lm/ssm/conv`), the scan
(`lm/ssm/scan`), the gate and the grouped norm (`lm/ssm/gate_norm`) and
the out-projection (`lm/ssm/out`); forward, recomputation and backward
over the Mamba-2 layers held (`lm_moe_device_ms.scope_ms`). A program
without the scopes, or a run without a device trace, reads as nothing.
"""

from benchmarks.layer_metrics.lm_moe_device_ms import scope_ms


def read(obs):
    return scope_ms(obs, "lm/ssm/")
