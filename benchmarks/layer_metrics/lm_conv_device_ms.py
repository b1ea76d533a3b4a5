"""Device time a step spends under `lm/conv/`: the convolution mixers'
in-projection (`lm/conv/in`), their gates and taps (`lm/conv/gate`) and
their out-projection (`lm/conv/out`); forward, recomputation and backward
over the convolution layers held (`lm_moe_device_ms.scope_ms`). A program
without the scopes reads as nothing.
"""

from benchmarks.layer_metrics.lm_moe_device_ms import scope_ms


def read(obs):
    return scope_ms(obs, "lm/conv/")
