"""Device-side double-buffered batch prefetch.

`data.loader.Loader` already decodes AHEAD of the training loop into
host RAM (threaded/process pool). This layer removes the remaining
synchronous hop: the host→device transfer. `jax.device_put` is
asynchronous — it enqueues a DMA and returns immediately — so keeping
`depth` puts in flight means batch N+1 (and N+2, ...) is streaming onto
the chips with the step's OWN input shardings while step N computes.
The train step then starts without waiting on PCIe/DCN: its arguments
are already resident (the classic double-buffering pattern; depth=2 is
one buffer computing + one filling).

Stall accounting: `stall_s` is the time spent inside `next()` of the
HOST iterator after warm-fill (span `prefetch:host_next`), exposed to
the chips or not — the number `scripts/train_bench.py` reports as
`prefetch_stall`. For a `Loader` it holds the consumer-side work of
every batch (`loader:wait` for the decode futures, `loader:stack`), so
it is above zero when no chip ever waits: with `depth` batches in
flight it is hidden behind the step, and whether the chips starve is
read from the device trace's idle share, not from here. The device_put
only enqueues; it has a span of its own (`prefetch:put`) and is not in
`stall_s`.

Donation interplay: the jitted step donates only its STATE argument
(donate_argnums=0), never the batch, so a prefetched batch that is
still queued for a future step is never invalidated by the current one.
"""

from __future__ import annotations

import collections
from typing import Any, Callable, Iterable, Iterator, Optional

from dexiraft_tpu.profiling import reset as reset_spans, span

# parallel.mesh (and with it jax) is imported lazily: data/__init__ must
# stay importable without jax so the Loader's SPAWNED process workers
# don't pay a jax init just to decode numpy batches


class PrefetchStats:
    """Host-iterator time accounting for a DevicePrefetcher."""

    def __init__(self) -> None:
        self.reset()

    def reset(self) -> None:
        """Zero the counters (warm_fill_s included) and the prefetcher's
        spans (`prefetch:*`) — e.g. to exclude a bench's warmup steps
        from the steady-state record."""
        reset_spans("prefetch:")
        self.batches = 0  # batches yielded (after warm-fill)
        self.stall_s = 0.0  # time inside the HOST iterator's next()
        self.stalls = 0  # yields on which the host made us wait
        self.warm_fill_s = 0.0  # initial fill (excluded from stall_s)

    @property
    def stall_per_batch_s(self) -> float:
        return self.stall_s / self.batches if self.batches else 0.0

    def summary(self, pipeline_stats=None) -> str:
        base = (f"{self.batches} batches, prefetch stall "
                f"{self.stall_s * 1e3:.1f} ms total "
                f"({self.stall_per_batch_s * 1e3:.3f} ms/batch, "
                f"{self.stalls} stalled yields; warm fill "
                f"{self.warm_fill_s * 1e3:.1f} ms)")
        if pipeline_stats is not None and pipeline_stats.faults:
            base += f"; {pipeline_stats.summary()}"
        return base


class DevicePrefetcher:
    """Iterate device-resident batches, keeping `depth` transfers in flight.

    put: host batch -> on-device batch (e.g. parallel.mesh.batch_putter
    result — device_put with the train step's input shardings). depth=2
    is double buffering; depth=0 degrades to a synchronous put-per-yield
    (useful as the parity baseline in tests).
    """

    def __init__(
        self,
        iterable: Iterable[Any],
        put: Optional[Callable[[Any], Any]] = None,
        *,
        depth: int = 2,
        pipeline_stats=None,
    ):
        if depth < 0:
            raise ValueError(f"depth must be >= 0, got {depth}")
        if put is None:
            from dexiraft_tpu.parallel.layout import batch_putter

            put = batch_putter(None)
        self.put = put
        self.depth = depth
        self.stats = PrefetchStats()
        # fault counters of the HOST pipeline feeding this prefetcher (a
        # Loader's PipelineStats) — passed explicitly when the iterable
        # is a bare generator (loader.batches(...)), else picked off a
        # Loader's .stats; surfaced by summary() so the end-of-run
        # prefetch line also reports pipeline degradation
        if pipeline_stats is None:
            pipeline_stats = getattr(iterable, "stats", None)
        self.pipeline_stats = (pipeline_stats
                               if hasattr(pipeline_stats, "faults") else None)
        self._it = iter(iterable)
        self._buf: "collections.deque" = collections.deque()
        self._warm = False
        self._exhausted = False

    # a host next() faster than this is "the batch was already decoded
    # and waiting" — only waits above it count as a stalled yield (the
    # call itself always costs some microseconds)
    STALL_EPS_S = 1e-3

    def _pull(self) -> bool:
        """Enqueue one more host batch's transfer; False when exhausted.
        The put only ENQUEUES (async dispatch) — the host-iterator next()
        is the only blocking part, and that is what stall_s times."""
        if self._exhausted:
            return False
        with span("prefetch:host_next") as host_next:
            try:
                batch = next(self._it)
            except StopIteration:
                self._exhausted = True
        if self._exhausted:
            # the call that finds the end is in the span and, as
            # before, not in stall_s
            return False
        dt = host_next.seconds
        if self._warm:
            self.stats.stall_s += dt
            if dt > self.STALL_EPS_S:
                self.stats.stalls += 1
        else:
            self.stats.warm_fill_s += dt
        with span("prefetch:put"):
            self._buf.append(self.put(batch))
        return True

    def __iter__(self) -> Iterator[Any]:
        return self

    def __next__(self) -> Any:
        if not self._warm:
            # warm fill: depth+1 so the first yield already leaves
            # `depth` batches in flight behind it
            for _ in range(self.depth + 1):
                self._pull()
            self._warm = True
        else:
            self._pull()
        if not self._buf:
            raise StopIteration
        self.stats.batches += 1
        return self._buf.popleft()

    def summary(self) -> str:
        """One line: prefetch stall accounting + any pipeline faults."""
        return self.stats.summary(self.pipeline_stats)

    def close(self) -> None:
        """Close the underlying host iterator (e.g. a Loader generator,
        whose feeder thread and worker pool stop on close) and drop the
        buffered device batches so their device memory can be freed."""
        close = getattr(self._it, "close", None)
        if close is not None:
            close()
        self._buf.clear()
        self._exhausted = True


def prefetch_to_device(
    iterable: Iterable[Any],
    mesh=None,
    *,
    depth: int = 2,
    pipeline_stats=None,
) -> DevicePrefetcher:
    """Convenience wrapper: prefetch with the train step's input layout
    for `mesh` (parallel.layout.batch_putter; plain device_put when None)."""
    from dexiraft_tpu.parallel.layout import batch_putter

    return DevicePrefetcher(iterable, batch_putter(mesh), depth=depth,
                            pipeline_stats=pipeline_stats)
