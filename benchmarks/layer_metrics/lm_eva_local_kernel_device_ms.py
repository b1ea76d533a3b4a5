"""Device time a step spends under `lm/eva/local/kernel`: the exact
attention inside the query's own window and document
(`ops/lm_attention.py` on ids that separate document and window: the
three Pallas calls with the table, the head folds and the backward's row
sums around them), summed over the layers; forward, recomputation and
backward (`lm_moe_device_ms.scope_ms`).
"""

from benchmarks.layer_metrics.lm_moe_device_ms import scope_ms


def read(obs):
    return scope_ms(obs, "lm/eva/local/kernel")
