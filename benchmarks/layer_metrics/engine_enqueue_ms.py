"""Host time in the `eval_fn` call of one batch until it returns its
futures (span `engine:enqueue`; a fresh signature's call goes to
`InferenceEngine.compile_s` and is not in here). The third part of
`engine_dispatch_ms`. Mean over the measured window's batches
(`engine_assemble_ms.window_mean_ms`).
"""

from benchmarks.layer_metrics.engine_assemble_ms import window_mean_ms


def read(obs):
    return window_mean_ms("engine:enqueue", obs.counters.get("engine_batches"))
