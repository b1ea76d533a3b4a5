"""Time a step waited for the host input pipeline, per batch.

`PrefetchStats.stall_s / batches` over the measured window: the time
`DevicePrefetcher` spent blocked in `next()` of the `Loader`'s stream
(data/prefetch.py). Above a tenth of a step the host sets the pace.
"""


def read(obs):
    c = obs.counters
    if not c.get("prefetch_batches"):
        return None
    return c["prefetch_stall_s"] / c["prefetch_batches"] * 1e3
