"""Device time a step spends under `lm/moe/router`: the router's product
over all experts in fp32, the choice of the top few and their weights;
forward, recomputation and backward over the expert layers held
(`lm_moe_device_ms.scope_ms`). Where the router reads the layer's input
it runs ahead of the mixer, and this is what that placement has to
hide. A program without the scope, or a run without a device trace,
reads as nothing.
"""

from benchmarks.layer_metrics.lm_moe_device_ms import scope_ms


def read(obs):
    return scope_ms(obs, "lm/moe/router")
