"""Time the fetching thread waits for the device to finish a batch before
it copies it out: `block_until_ready` on the ticket's outputs (span
`engine:wait`). The host's slack: while it is above zero the device sets
the pace, and a device-side gain shows end to end only as far as this
reaches. Higher is better. Mean over the measured window's batches
(`engine_assemble_ms.window_mean_ms`).
"""

from benchmarks.layer_metrics.engine_assemble_ms import window_mean_ms


def read(obs):
    return window_mean_ms("engine:wait", obs.counters.get("engine_batches"))
