"""Device time a step spends under `lm/moe/dispatch`: the sort of the
(token, choice) slots by held expert, the table of their ranges, the
gather of each chunk's rows and, in the backward, the rows' cotangents
added back to their tokens; forward, recomputation and backward over the
expert layers held (`lm_moe_device_ms.scope_ms`). A program without the
scope, or a run without a device trace, reads as nothing.
"""

from benchmarks.layer_metrics.lm_moe_device_ms import scope_ms


def read(obs):
    return scope_ms(obs, "lm/moe/dispatch")
