"""Host time in the device-to-host copy of one batch's results, after the
device has finished them: `device_get` of `flow_low` and `flow_up` (span
`engine:copy_out`). With `engine_device_wait_ms` it makes up
`ServeStats.fetch_s`. Mean over the measured window's batches
(`engine_assemble_ms.window_mean_ms`).
"""

from benchmarks.layer_metrics.engine_assemble_ms import window_mean_ms


def read(obs):
    return window_mean_ms("engine:copy_out", obs.counters.get("engine_batches"))
