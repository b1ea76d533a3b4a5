"""Device time a step spends under `lm/moe/combine`: the experts'
weighted rows added back to their tokens and, in the backward, the
gather of the output's cotangent by row with the weights' row dots; over
the expert layers held (`lm_moe_device_ms.scope_ms`). A program without
the scope, or a run without a device trace, reads as nothing.
"""

from benchmarks.layer_metrics.lm_moe_device_ms import scope_ms


def read(obs):
    return scope_ms(obs, "lm/moe/combine")
