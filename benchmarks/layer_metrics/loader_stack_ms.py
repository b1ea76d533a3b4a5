"""Time the `Loader`'s consumer takes to make one batch of its samples:
the backfill and `_stack`, an `np.stack` per key on the consuming thread
(span `loader:stack`). Part of `prefetch_stall_ms`, and serial with the
step's dispatch. Mean over the measured window's batches
(`loader_wait_ms.window_mean_ms`).
"""

from benchmarks.layer_metrics.loader_wait_ms import window_mean_ms


def read(obs):
    return window_mean_ms("loader:stack", obs)
