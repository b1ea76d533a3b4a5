"""Device time a step spends under `lm/gqa/`: the gated grouped-query
attention's projections, gate, QK-norm and rotary embedding
(`lm/gqa/proj`) and the document attention's kernels of both layer kinds
(`lm/gqa/window/kernel`, `lm/gqa/full/kernel`); forward, recomputation
and backward over the layers held (`lm_moe_device_ms.scope_ms`). A
program without the scopes reads as nothing.
"""

from benchmarks.layer_metrics.lm_moe_device_ms import scope_ms


def read(obs):
    return scope_ms(obs, "lm/gqa/")
