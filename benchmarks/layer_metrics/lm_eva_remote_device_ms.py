"""Device time a step spends on what EVA adds to windowed attention:
the chunk summaries (`lm/eva/pool`), every window's queries over the
summaries of the windows before it (`lm/eva/remote`) and the merge of
that softmax with the window's own (`lm/eva/merge`); forward,
recomputation and backward over the layers held
(`lm_moe_device_ms.scope_ms`).
"""

from benchmarks.layer_metrics.lm_moe_device_ms import scope_ms


def read(obs):
    return scope_ms(obs, "lm/eva/pool", "lm/eva/remote", "lm/eva/merge")
