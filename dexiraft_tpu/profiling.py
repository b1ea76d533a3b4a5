"""Profiling / tracing (SURVEY.md §5 — absent in the reference, where the
only timing is DexiNed's per-image time.time() deltas, main.py:133-147).

Tools:
  * trace(log_dir): context manager around jax.profiler for a window of
    steps — inspect with TensorBoard's profile plugin or Perfetto.
  * span(name) / add / reset / snapshot: the program's host spans — one
    process-wide table of seconds, counts and single durations per
    name, each span also a TraceAnnotation on the device trace's clock.
    The engine, the loader, the prefetcher and the compile listener
    write it; PERF.md section 3 names every span and what reads it.
  * enable_persistent_cache(): persistent XLA compilation cache —
    repeat launches of the same program skip the multi-minute compile.
  * device_banner(label): the one line every entry point prints about
    the device, the versions and the cache it runs with.
  * ThroughputReport: steps/s, pixel-iters/s (the tokens/s analog for
    this workload), and MFU from counted FLOPs — the record format
    scripts/train_bench.py emits per config.
  * ServeStats: dispatch/fetch/in-flight accounting for the serving
    engine (dexiraft_tpu.serve) — the record scripts/serve_bench.py
    emits per config.
"""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
from typing import Dict, Iterator, Optional

# jax is imported inside the functions that touch it: this module
# sits on the serve package's import path, and the serve CLI's parser /
# --workers pool parent must stay jax-free (seconds of import on a TPU
# host for a process that never runs the model)

# persistent-cache location when JAX_COMPILATION_CACHE_DIR is unset: a
# fixed absolute path inside the checkout. The path is part of the
# cache's key, and train/serve/the tests chdir — a path relative to the
# working directory would key the same program differently per directory
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable_persistent_cache() -> str:
    """Turn on XLA's persistent compilation cache; returns its directory.

    The ONE owner of the cache location. Where JAX_COMPILATION_CACHE_DIR
    is set the cache is placed from outside: JAX reads the variable
    itself and no directory is set in code. Unset, the directory is
    DEFAULT_CACHE_DIR. Either way the thresholds are zeroed so even
    sub-second compiles cache — this repo's jitted steps are exactly the
    artifacts worth keeping. Safe to call more than once.
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir


def device_banner(label: str, **extra) -> dict:
    """Print (and return) one line naming what this process runs on:
    platform, device kind and count as JAX reports them, the
    JAX/jaxlib/libtpu versions and the compile-cache directory in use
    (None = no persistent cache). Every entry point prints it before
    its first compile, so no log can be read as a device run when it
    was not one; chip_smoke.py parses it. ``extra`` rides along (what
    --corr_impl auto resolved to, which decoder serves the loader)."""
    import importlib.metadata
    import json

    import jax
    import jaxlib

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices()), "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "libtpu": libtpu,
            "compile_cache_dir": jax.config.jax_compilation_cache_dir,
            **extra}
    print(f"[{label}] device: {json.dumps(info)}", flush=True)
    return info


class ThroughputReport:
    """Training-throughput record: steps/s, pixel-iters/s, MFU.

    pixel-iters/s = batch * H * W * iters * steps/s — the tokens/s
    analog for iterative-refinement optical flow (each refinement
    iteration touches every pixel once, like a decode step touches
    every position). MFU = counted_flops / step_time / chip_peak, with
    both inputs named in the record (docs/perf.md "MFU accounting").
    """

    def __init__(self, *, batch: int, height: int, width: int, iters: int):
        self.batch = batch
        self.height = height
        self.width = width
        self.iters = iters

    def fields(self, step_s: float, flops: Optional[int] = None,
               peak_flops: Optional[float] = None) -> dict:
        out = {
            "step_ms": round(step_s * 1e3, 2),
            "steps_per_sec": round(1.0 / step_s, 3),
            "pixel_iters_per_sec": round(
                self.batch * self.height * self.width * self.iters / step_s),
        }
        if flops:
            out["step_flops"] = int(flops)
            out["tflops_per_sec"] = round(flops / step_s / 1e12, 2)
            if peak_flops:
                out["mfu"] = round(flops / step_s / peak_flops, 4)
                out["chip_peak_bf16_flops"] = int(peak_flops)
        return out


class ServeStats:
    """Honest dispatch/fetch accounting for the throughput-mode inference
    engine (dexiraft_tpu.serve.InferenceEngine).

    The engine's dispatch is asynchronous: eval_fn() enqueues device work
    and returns array FUTURES; the only host-blocking operation is the
    np.asarray fetch when a ticket leaves the in-flight window. So:

      * fetch_s       — wall time the host spent inside fetches: the
                        wait for the device (`engine:wait`, the host's
                        slack) plus the device-to-host copy
                        (`engine:copy_out`)
      * dispatch_s    — host-side assemble/put/enqueue time (never
                        blocks on device compute): `engine:assemble` +
                        `engine:put` + `engine:enqueue`
      * batch_latency — per-batch dispatch→fetch-complete wall time;
                        p50/p99 come from these samples
      * peak_inflight — max dispatched-unfetched batches observed
      * pad_frames    — tail filler items (dispatched for shape
                        stability, masked out of results)

    The latency sample window is BOUNDED (maxlen, default 4096 batches):
    a long-lived server accumulating every batch latency forever would
    grow without bound between /stats scrapes, and percentiles over the
    recent window are what an SLO dashboard wants anyway.
    """

    def __init__(self, maxlen: int = 4096) -> None:
        self.maxlen = maxlen
        self.reset()

    def reset(self) -> None:
        """Zero the counters, and with them the engine's spans in the
        process-wide table (`engine:*`, which split dispatch_s and
        fetch_s): the two accounts cover the same batches."""
        reset("engine:")
        self.batches = 0
        self.frames = 0          # real frame pairs yielded
        self.pad_frames = 0      # partial-batch tail filler (masked out)
        self.dispatch_s = 0.0
        self.fetch_s = 0.0
        self.fetches = 0
        self.peak_inflight = 0
        # warm-start carry transfer accounting (the PR 6 round-trip the
        # device-resident handoff removes): H2D = host flow_init rows
        # ridden up with a dispatch, D2H = flow_low bytes fetched to
        # host for the carry. Both stay 0 on the device-carry path —
        # scripts/video_bench.py pins the before/after.
        self.carry_h2d_bytes = 0
        self.carry_d2h_bytes = 0
        self.batch_latency_s: "collections.deque" = collections.deque(
            maxlen=self.maxlen)
        # adaptive-iteration accounting (engine adaptive mode): per-ITEM
        # samples of how many refinement updates each real (non-pad)
        # frame pair actually applied, and its last pre-freeze flow-delta
        # norm — the convergence evidence the /stats adaptive block and
        # the serve_bench frontier record serialize. Bounded like the
        # latency window, and 0-length on fixed-iteration engines.
        self.iters_used: "collections.deque" = collections.deque(
            maxlen=self.maxlen)
        self.final_delta: "collections.deque" = collections.deque(
            maxlen=self.maxlen)

    def latency_ms(self, p: float) -> float:
        import numpy as np

        if not self.batch_latency_s:
            return 0.0
        return float(np.percentile(self.batch_latency_s, p)) * 1e3

    def iters_used_pctl(self, p: float) -> float:
        import numpy as np

        if not self.iters_used:
            return 0.0
        return float(np.percentile(self.iters_used, p))

    def iters_used_mean(self) -> float:
        if not self.iters_used:
            return 0.0
        return sum(self.iters_used) / len(self.iters_used)

    def final_delta_pctl(self, p: float) -> float:
        import numpy as np

        if not self.final_delta:
            return 0.0
        return float(np.percentile(self.final_delta, p))

    def summary(self) -> str:
        return (f"{self.batches} batches / {self.frames} frame pairs "
                f"(+{self.pad_frames} tail pad), peak in-flight "
                f"{self.peak_inflight}, fetch-blocked "
                f"{self.fetch_s * 1e3:.1f} ms total, batch latency "
                f"p50 {self.latency_ms(50):.1f} / "
                f"p99 {self.latency_ms(99):.1f} ms")


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """Capture a device+host profiler trace into log_dir."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# ---- host spans ---------------------------------------------------------------

# single durations kept per span name, as ServeStats.batch_latency_s keeps
SPAN_WINDOW = 4096


class _SpanRecord:
    __slots__ = ("seconds", "count", "durations")

    def __init__(self) -> None:
        self.seconds = 0.0
        self.count = 0
        self.durations: "collections.deque" = collections.deque(
            maxlen=SPAN_WINDOW)


# process-wide and always on, like ServeStats: a handful of entries per
# batch or step, plus one per sample a loader thread decodes. One plain
# leaf lock (nothing is called while it is held): loader workers share a
# name, and an engine may dispatch from a scheduler or handler thread.
# A name is a layer's, not an object's: two engines, prefetchers or
# Loader streams in one process write the same records, and a reset by
# one (ServeStats/PrefetchStats.reset, a new Loader stream) restarts the
# account of both. The stats objects' own fields stay per object.
_spans: Dict[str, _SpanRecord] = {}
_spans_lock = threading.Lock()


def add(name: str, seconds: float) -> None:
    """One more duration under `name`: what `span` does on exit, for a
    duration measured elsewhere (a sum over a batch's rows, what one
    jitted program's compile-path events came to)."""
    with _spans_lock:
        rec = _spans.get(name)
        if rec is None:
            rec = _spans[name] = _SpanRecord()
        rec.seconds += seconds
        rec.count += 1
        rec.durations.append(seconds)


def reset(prefix: str = "") -> None:
    """Forget every name that starts with `prefix` (a layer's spans share
    one: "engine:", "loader:", "prefetch:", "jax:")."""
    with _spans_lock:
        for name in [n for n in _spans if n.startswith(prefix)]:
            del _spans[name]


def snapshot(prefix: str = "") -> Dict[str, dict]:
    """`{name: {"seconds", "count", "durations"}}` of the names that
    start with `prefix`, since their last reset. `durations` is the last
    SPAN_WINDOW single durations in order: while `count` equals its
    length it is all of them, so a reader can take the first n alone."""
    with _spans_lock:
        return {name: {"seconds": rec.seconds, "count": rec.count,
                       "durations": list(rec.durations)}
                for name, rec in _spans.items() if name.startswith(prefix)}


class span:
    """`with span(name):` — a host span of the program.

    A `jax.profiler.TraceAnnotation` for the time inside (on the host
    plane of a profiler trace, on the device events' clock; nanoseconds
    when no profiler session is active), and on exit one duration on
    `time.perf_counter` added to the table under `name`. The duration
    stays on the object as `.seconds`, so a caller that also keeps a
    total of its own (ServeStats, PrefetchStats) feeds it from the same
    clock reads. The annotation is made on entry, not when the object
    is: a TraceAnnotation starts when it is constructed.
    """

    __slots__ = ("name", "seconds", "_t0", "_annotation")

    def __init__(self, name: str) -> None:
        self.name = name
        self.seconds = 0.0

    def __enter__(self) -> "span":
        import jax

        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        add(self.name, self.seconds)
        return False
