"""Time one worker thread takes to decode and augment one sample
(`Loader._decode`, span `loader:decode`; process workers report
nothing). A counter only: worker threads' lines are not kept in the
trace. Times `train_samples_per_s` / 1000 it is the worker threads
really running beside the step.

The MEDIAN over the decodes of the measured window's batches
(`loader_wait_ms.window_durations`, `batch` decodes a batch: the one
process of a cell decodes the global batch). The loader runs ahead of
its consumer by its queue, so the window's first batches were decoded
during set-up, some against the warm step's Python tracing, which
holds the interpreter lock: those take ten times as long, nobody waits
for them, and they pull a mean up by half. Workers finish out of
order, so the cut is good to a batch or so at either end.
"""

import statistics

from benchmarks.layer_metrics.loader_wait_ms import window_durations


def read(obs):
    kept = window_durations("loader:decode", obs,
                            per_batch=obs.counters.get("batch"))
    return None if kept is None else statistics.median(kept) * 1e3
