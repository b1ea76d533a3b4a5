"""Device time a step spends under `lm/moe/latent`: the two projections
around the routed path of an expert layer with a latent space, `hidden
-> latent` ahead of the dispatch and `latent -> hidden` after the
combine; forward, recomputation and backward over the expert layers
held (`lm_moe_device_ms.scope_ms`; `lm_moe_device_ms` holds it too). A
program without the scope, or a run without a device trace, reads as
nothing.
"""

from benchmarks.layer_metrics.lm_moe_device_ms import scope_ms


def read(obs):
    return scope_ms(obs, "lm/moe/latent")
