"""Host time in the `put` of one batch onto the chips until it returns
(`DevicePrefetcher._pull`, span `prefetch:put`): the enqueue of the
transfer with the step's input shardings, which `prefetch_stall_ms`
leaves out. `PrefetchStats.reset()` at the window's first dispatch
resets `prefetch:`; mean over the measured window's batches
(`engine_assemble_ms.window_mean_ms`).
"""

from benchmarks.layer_metrics.engine_assemble_ms import window_mean_ms


def read(obs):
    return window_mean_ms("prefetch:put", obs.counters.get("prefetch_batches"))
