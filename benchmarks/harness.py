"""What run.py and the runners share: the manifest, lookup of a cell's
files by name, host spans, the profiler window and the device report.

Nothing here names a cell, a configuration, a traffic mix or a metric:
each is found through its name in BENCHMARK.json as a file of its own
(configs/<config>.json, traffic/<mix>.json, runners/<kind>.py,
layer_metrics/<metric>.py), so a later PR adds files and one manifest
entry and edits nothing.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import os.path as osp
import shutil
import time
from typing import Callable, Dict, Iterator, List, Optional

BENCH_DIR = osp.dirname(osp.abspath(__file__))
REPO = osp.dirname(BENCH_DIR)
# data trees and profiler output; listed in .gitignore
WORK_DIR = osp.join(REPO, ".bench_work")


class BenchError(Exception):
    """The run cannot produce a result; run.py exits non-zero without one."""


# ---- manifest and files found by name --------------------------------------


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(repo: str = REPO) -> dict:
    return _read_json(osp.join(repo, "BENCHMARK.json"))


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = ({**out[k], **v} if isinstance(v, dict)
                  and isinstance(out.get(k), dict) else v)
    return out


@dataclasses.dataclass
class Cell:
    """One entry of `workloads` with its configuration and traffic files."""

    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    manifest: dict

    def metrics(self, group: str) -> List[dict]:
        """This cell's metrics of `end_to_end` or `per_layer`."""
        return [m for m in self.manifest[group]
                if self.name in m.get("workloads", [self.name])]


def load_cell(name: str, repo: str = REPO, rehearsal: bool = False) -> Cell:
    manifest = load_manifest(repo)
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; it names "
                         f"{[w['name'] for w in manifest['workloads']]}")
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == entry["config"])
    config = _read_json(osp.join(repo, cfg_entry["file"]))
    bench = osp.join(repo, manifest["paths"][0])
    traffic = _read_json(osp.join(bench, "traffic", entry["traffic"] + ".json"))
    if rehearsal:
        traffic = _merge(traffic, traffic.get("rehearsal", {}))
    return Cell(name, entry["chips"], entry["config"], entry["traffic"],
                config, traffic, manifest)


def load_module(path: str):
    """A Python file by path: runner and metric files are found by the
    name in the manifest, not imported by a name fixed in code."""
    if not osp.exists(path):
        raise BenchError(f"no such file: {osp.relpath(path, REPO)}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + osp.splitext(osp.basename(path))[0].replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_runner(kind: str, bench_dir: str = BENCH_DIR):
    return load_module(osp.join(bench_dir, "runners", kind + ".py"))


def load_metric(name: str, bench_dir: str = BENCH_DIR):
    return load_module(osp.join(bench_dir, "layer_metrics", name + ".py"))


def load_peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    peaks = _read_json(osp.join(bench_dir, "peaks.json"))
    if device_kind not in peaks:
        raise BenchError(f"no peaks on record for device_kind "
                         f"{device_kind!r} (known: {sorted(peaks)}): add it "
                         "to peaks.json with its source")
    return peaks[device_kind]


# ---- the model configuration a cell runs -----------------------------------


def build_config(config: dict, flags: dict, platform: str):
    """The cell's `RAFTConfig`: the configuration file's constructor with
    the traffic file's flags. `corr_impl: "auto"` resolves as the eval and
    serve CLIs resolve it (flash + fused on a TPU)."""
    import dexiraft_tpu.config as cfglib

    flags = dict(flags)
    if flags.get("corr_impl") == "auto":
        impl, fused = cfglib.resolve_corr_impl("auto", platform)
        flags.update(corr_impl=impl, fused_update=fused)
    return getattr(cfglib, config["constructor"])(**flags)


# ---- spans, counters, the result of a run ----------------------------------


class Spans:
    """Host spans by name, in seconds on `time.perf_counter`."""

    def __init__(self) -> None:
        self.seconds: Dict[str, float] = {}

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.seconds[name] = (self.seconds.get(name, 0.0)
                                  + time.perf_counter() - t0)


@dataclasses.dataclass
class Context:
    """What run.py hands a runner."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    devices: list                 # the jax devices this cell uses
    spans: Spans
    log: Callable[[str], None]

    @property
    def platform(self) -> str:
        return self.devices[0].platform

    def work_dir(self, *parts: str) -> str:
        path = osp.join(WORK_DIR, self.cell.traffic_name, *parts)
        os.makedirs(path, exist_ok=True)
        return path


@dataclasses.dataclass
class Outcome:
    """What a runner gives back. `window_start` is the `perf_counter`
    reading at the window's first dispatch (set-up ends there). `trace` is
    `trace_reduce.summarize`'s result for a traced run."""

    attempted: int
    failed: int
    correct: bool
    end_to_end: Dict[str, float]
    window_start: float
    counters: Dict[str, float]
    trace: Optional[dict] = None


@dataclasses.dataclass
class Observation:
    """What a layer metric's `read` is given."""

    spans: Dict[str, float]
    counters: Dict[str, float]
    end_to_end: Dict[str, float]
    trace: Optional[dict]
    peaks: Optional[dict]
    chips: int
    memory_peak_bytes: int


# ---- window pacing ----------------------------------------------------------


class Pacer:
    """When to stop offering work so that a closed loop ends near
    `seconds`: whole units only (a batch, a step), never a unit cut by
    the clock. `more()` projects the end of what is already dispatched
    from the mean time of the units finished so far."""

    def __init__(self, seconds: float, first_guess_s: float, least: int = 3):
        self.seconds = seconds
        self.guess = first_guess_s
        self.least = least
        self.start = time.perf_counter()
        self.dispatched = 0
        self.finished = 0
        self.last_finish = self.start

    def note_dispatch(self) -> None:
        self.dispatched += 1

    def note_finish(self) -> None:
        self.finished += 1
        self.last_finish = time.perf_counter()

    def more(self) -> bool:
        if self.dispatched < self.least:
            return True
        unit = self.elapsed / self.finished if self.finished else self.guess
        return self.dispatched * unit < self.seconds

    @property
    def elapsed(self) -> float:
        """First dispatch to last finish."""
        return self.last_finish - self.start


# ---- the profiler window ------------------------------------------------------


class TraceWindow:
    """A `jax.profiler` trace with the runner's own host spans in it.
    `span(name)` is a `TraceAnnotation` named `bench:<name>`; the span
    named `window` bounds what `trace_reduce.summarize` reads. `stop()`
    reduces the trace and deletes it: the trace itself is not kept."""

    def __init__(self, ctx: Context):
        self.dir = osp.join(ctx.work_dir(), "trace")
        self.rehearsal = ctx.rehearsal
        self.active = False
        self._keep = os.environ.get("BENCH_KEEP_TRACE")  # dump for hand reading

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # Python frames: large, not read
        options.host_tracer_level = 2     # keeps TraceAnnotation spans
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.active = True

    def span(self, name: str):
        import jax

        if not self.active:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation("bench:" + name)

    def stop(self) -> Optional[dict]:
        import jax

        from benchmarks import trace_reduce

        jax.profiler.stop_trace()
        self.active = False
        path = trace_reduce.find_xplane(self.dir)
        trace = trace_reduce.load_xplane(path)
        if self._keep:
            os.makedirs(self._keep, exist_ok=True)
            with open(osp.join(self._keep, "describe.json"), "w") as f:
                json.dump(trace_reduce.describe(path), f, indent=1)
            with open(osp.join(self._keep, "trace.json"), "w") as f:
                json.dump(trace_reduce.trim(trace, min_dur_ns=20_000), f)
        shutil.rmtree(self.dir, ignore_errors=True)
        if not trace["devices"]:
            if self.rehearsal:  # the CPU backend writes no device plane
                return None
            raise BenchError("the trace holds no device plane with an "
                             f"{trace_reduce.OPS_LINE!r} line")
        return trace_reduce.summarize(trace)


# ---- the device, as JAX reports it ---------------------------------------------


def memory_peaks(devices) -> Dict[str, int]:
    """Peak device memory on the fullest chip. The TPU runtime keeps two
    counters: `peak_bytes_in_use` for buffers (weights, batches, results)
    and `peak_bytes_reserved` for the scratch a running program reserves
    (XLA's temporaries: 7.2 GB for the v5 eval batch, 11.8 GB for the v5
    train step, where buffers are 1.2 GB; my chip runs, PR 22). A program
    runs with its arguments resident, so the chip holds both at once:
    `reported` is the sum of the two peaks, capped at the chip's limit.
    The two peaks need not coincide, so the sum is an upper bound; each
    run logs both beside it, and a traced run the compiler's own figure
    for the step (`compiled_memory`), which the sum should stay near."""
    out = {"peak_bytes_in_use": 0, "peak_bytes_reserved": 0, "reported": 0}
    for d in devices:
        stats = d.memory_stats() or {}
        in_use = int(stats.get("peak_bytes_in_use", 0))
        reserved = int(stats.get("peak_bytes_reserved", 0))
        both = min(in_use + reserved,
                   int(stats.get("bytes_limit", in_use + reserved)))
        if both >= out["reported"]:
            out = {"peak_bytes_in_use": in_use,
                   "peak_bytes_reserved": reserved, "reported": both}
    return out


def compiled_memory(lowered) -> Dict[str, int]:
    """The compiler's account of a lowered step, per device: temporaries
    and arguments. Compiling what already ran is a cache load."""
    ma = lowered.compile().memory_analysis()
    return {"compiled_temp_bytes": int(ma.temp_size_in_bytes),
            "compiled_argument_bytes": int(ma.argument_size_in_bytes)}


def cache_entries() -> Optional[int]:
    import jax

    d = jax.config.jax_compilation_cache_dir
    return len(os.listdir(d)) if d and osp.isdir(d) else None
