"""Device time a step spends under `lm/eva/`: EvaByte's mixer whole.
Projections, rotary embedding and W_o (`lm/eva/proj`), the chunk
summaries (`lm/eva/pool`), the exact attention inside the window
(`lm/eva/local/kernel`), the attention over summaries (`lm/eva/remote`)
and the merge of the two (`lm/eva/merge`); forward, recomputation and
backward over the layers held (`lm_moe_device_ms.scope_ms`). A program
without the scopes reads as nothing.
"""

from benchmarks.layer_metrics.lm_moe_device_ms import scope_ms


def read(obs):
    return scope_ms(obs, "lm/eva/")
