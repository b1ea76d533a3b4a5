"""Host time in the host-to-device put of one batch: `_assemble_fi` and
`self.put` until it returns (span `engine:put`); the transfer itself is
asynchronous, so this is what the enqueue of two 173 MB frames costs the
dispatching thread. The second part of `engine_dispatch_ms`. Mean over
the measured window's batches (`engine_assemble_ms.window_mean_ms`).
"""

from benchmarks.layer_metrics.engine_assemble_ms import window_mean_ms


def read(obs):
    return window_mean_ms("engine:put", obs.counters.get("engine_batches"))
