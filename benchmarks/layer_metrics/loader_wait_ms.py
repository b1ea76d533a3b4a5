"""Time the `Loader`'s consumer waits for one batch's samples: the
feeder's queue, then each sample's decode future (`Loader.batches`, span
`loader:wait`). Part of `prefetch_stall_ms`; near zero when the workers
keep ahead.

The loader's stream starts before the measured window (warm fill, warm
steps: its first batches wait for workers that have only just started)
and runs on into the traced tail. Every batch it yields is one
`prefetch:host_next` of the `DevicePrefetcher`, whose spans
`PrefetchStats.reset()` resets at the window's first dispatch: so the
loader's last `count("prefetch:host_next")` batches (of
`count("loader:stack")` in all) belong to the window and the tail, and
the first `prefetch_batches` of those to the window
(`window_durations`). `window_mean_ms` is the mean over them: the same
batches `prefetch_stall_ms` covers, profiler off. The alignment is by count, so it holds for one `Loader`
stream behind one prefetcher in the process (every cell today) that
dropped no batch; where the counts of `loader:wait` and `loader:stack`
differ, and on a program without the span table or a span nothing
entered, the readers give nothing.
"""


def window_durations(name, obs, per_batch=1):
    """`name`'s durations for the window's batches, `per_batch` of them
    a batch (one wait and one stack; a decode for every sample)."""
    try:
        from dexiraft_tpu.profiling import snapshot
    except ImportError:
        return None
    spans = snapshot("")
    rec, waits, batches, pulls = (spans.get(k) for k in (
        name, "loader:wait", "loader:stack", "prefetch:host_next"))
    units = obs.counters.get("prefetch_batches")
    if not units or not per_batch or None in (rec, waits, batches, pulls):
        return None
    # a batch the Loader dropped waited and was never stacked: the
    # windows can then not be aligned by count
    if waits["count"] != batches["count"]:
        return None
    first = (batches["count"] - pulls["count"]) * per_batch
    n = int(units * per_batch)
    kept = rec["durations"]
    # count == len(kept): none has dropped out of the bounded record
    if first < 0 or len(kept) < first + n or rec["count"] != len(kept):
        return None
    return kept[first:first + n]


def window_mean_ms(name, obs):
    kept = window_durations(name, obs)
    return None if kept is None else sum(kept) / len(kept) * 1e3


def read(obs):
    return window_mean_ms("loader:wait", obs)
