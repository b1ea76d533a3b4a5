"""Host time to dispatch one batch: pad, stack, put, enqueue.

`ServeStats.dispatch_s / batches` over the measured window
(serve/engine.py, data/padder.py). Hidden behind the device while the
in-flight window is full; exposed as device idle when it is not.
"""


def read(obs):
    c = obs.counters
    if not c.get("engine_batches"):
        return None
    return c["engine_dispatch_s"] / c["engine_batches"] * 1e3
