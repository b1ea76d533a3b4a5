"""What the engine's one serial thread does per batch when it is not
waiting for the device: assemble + put + enqueue + copy_out + deliver
(unpad the rows) + caller (the time `stream`'s generator stays suspended
at its yields: the consumer's work, on the same thread). When it reaches
`eval_step_device_ms` the host sets the pace. Pulling the next batch's
items from the caller's iterator is not under a span and not in here.
Mean over the measured window's batches
(`engine_assemble_ms.window_mean_ms`).
"""

from benchmarks.layer_metrics.engine_assemble_ms import window_mean_ms

SPANS = ("engine:assemble", "engine:put", "engine:enqueue",
         "engine:copy_out", "engine:deliver", "engine:caller")


def read(obs):
    parts = [window_mean_ms(name, obs.counters.get("engine_batches"))
             for name in SPANS]
    return None if None in parts else sum(parts)
