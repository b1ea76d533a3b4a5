"""From a profiler trace to numbers: the one reduction every PR uses.

`load_xplane` turns the `.xplane.pb` the JAX profiler writes into a small
plain structure (`{"devices": [{"name", "ops": [[label, start_ns, dur_ns],
...]}], "host": [[name, start_ns, dur_ns], ...]}`); `summarize` reduces
that structure. The split is what lets `tests/` check the reduction on a
recorded trace kept as JSON, with no profiler and no chip.

What the TPU trace of this program looks like (looked at by hand on the
chip, PR 22; `describe` prints it): one plane per chip, `/device:TPU:<n>`.
Its `XLA Ops` line holds one event per executed HLO op, named by the
op's whole HLO text and carrying no framework metadata (no flax module
path), so what an op is can only be read from its name, opcode and
shape: `label` keeps those three. A `while` op is an event of its own
that spans its body's events, so the refinement loop is read from the
`while` events. A Pallas kernel is a `custom-call` whose target is
`tpu_custom_call`. Asynchronous ops (copies, slices, collectives that
overlap compute) are on the `Async XLA Ops` line; of those only the
collectives are read. The `Steps` and `XLA Modules` lines hold one event
per executed program. The runner's own `jax.profiler.TraceAnnotation`
spans (`bench:*`) are on the host plane, on the line of the Python
thread that made them, on the same clock; that line's other events
(`PjitFunction(step)`, `np.asarray(jax.Array)`, ...) say what the host
was doing where the runner has no span of its own.
"""

from __future__ import annotations

import collections
import glob
import os.path as osp
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"
MIN_GAP_NS = 100_000  # idle under 0.1 ms in all is not worth a line

_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast")
_HLO = re.compile(r"^(\S+) = \(*([a-z0-9]+\[[0-9,]*\]).*?\s([a-z][a-z0-9\-]*)\(")


def label(hlo_text: str) -> str:
    """`<name> <opcode> <result type>` of an op's HLO text (of a tuple,
    its first element's). A custom call into a Pallas kernel gets the
    opcode `tpu_custom_call`."""
    m = _HLO.match(hlo_text)
    if m is None:
        return hlo_text[:100]
    name, shape, opcode = m.groups()
    if opcode == "custom-call" and "tpu_custom_call" in hlo_text:
        opcode = "tpu_custom_call"
    return f"{name} {opcode} {shape}"[:100]


def _opcode(lbl: str) -> str:
    parts = lbl.split(" ")
    return parts[1] if len(parts) > 1 else ""


def is_container(lbl: str) -> bool:
    return _opcode(lbl) in ("while", "conditional", "call")


def is_loop(lbl: str) -> bool:
    return _opcode(lbl) == "while"


def is_collective(lbl: str) -> bool:
    """By the op's own name or opcode, never by its operands' names."""
    return bool(_COLLECTIVE.search(" ".join(lbl.split(" ")[:2])))


def is_pallas(lbl: str) -> bool:
    return _opcode(lbl) == "tpu_custom_call"


# ---- reading the profiler's file -----------------------------------------


def find_xplane(trace_dir: str) -> str:
    """The newest `.xplane.pb` under a `jax.profiler.start_trace` directory."""
    paths = sorted(glob.glob(osp.join(trace_dir, "plugins", "profile", "*",
                                      "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str) -> dict:
    """The plain structure of the module docstring, times in integer
    nanoseconds from the earliest event kept."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [[label(ev.name), int(ev.start_ns),
                             int(ev.duration_ns)] for ev in line.events]
                elif line.name == ASYNC_LINE:
                    ops += [[lbl, int(ev.start_ns), int(ev.duration_ns)]
                            for ev in line.events
                            for lbl in [label(ev.name)] if is_collective(lbl)]
            if ops:
                devices.append({"name": plane.name, "ops": ops})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                          for ev in line.events]
                # the runner's own thread: its spans and what JAX did there
                if any(e[0].startswith(HOST_PREFIX) for e in events):
                    host += events
    starts = [e[1] for d in devices for e in d["ops"]] + [e[1] for e in host]
    t0 = min(starts) if starts else 0
    for ev in host:
        ev[1] -= t0
    for d in devices:
        for ev in d["ops"]:
            ev[1] -= t0
        d["ops"].sort(key=lambda e: e[1])
    devices.sort(key=lambda d: d["name"])
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def describe(path: str, top: int = 25) -> dict:
    """What a trace file holds, for reading by hand before trusting
    `load_xplane`: every plane and line with its event count, the stat
    names of its first event and its longest event names."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            total: Dict[str, float] = collections.defaultdict(float)
            count: Dict[str, int] = collections.defaultdict(int)
            first_stats = None
            n = 0
            for ev in line.events:
                n += 1
                total[ev.name] += ev.duration_ns
                count[ev.name] += 1
                if first_stats is None:
                    first_stats = {str(k): str(v)[:200] for k, v in ev.stats}
            names = sorted(total, key=total.get, reverse=True)[:top]
            lines.append({"line": line.name, "events": n,
                          "first_stats": first_stats,
                          "top": [[k, count[k], total[k] / 1e6]
                                  for k in names]})
        out.append({"plane": plane.name, "lines": lines})
    return {"planes": out}


# ---- interval arithmetic ---------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of the given half-open intervals."""
    out: List[List[int]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(disjoint: Sequence[Interval]) -> int:
    return sum(b - a for a, b in disjoint)


def subtract(cover: Sequence[Interval], holes: Sequence[Interval]) -> List[Interval]:
    """`cover` minus `holes`; both sorted and disjoint."""
    out: List[Interval] = []
    j = 0
    for a, b in cover:
        while j < len(holes) and holes[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(holes) and holes[k][0] < b:
            if holes[k][0] > cur:
                out.append((cur, holes[k][0]))
            cur = max(cur, holes[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def _clip(events: Iterable[Sequence], window: Interval) -> List[Tuple[str, int, int]]:
    w0, w1 = window
    out = []
    for name, start, dur in events:
        a, b = max(start, w0), min(start + dur, w1)
        if b > a:
            out.append((name, a, b))
    return out


# ---- the reduction ---------------------------------------------------------


def window_of(trace: dict) -> Interval:
    """The traced window: the runner's `bench:window` span, which is on
    the device events' clock; without one, the span of the device events."""
    for name, start, dur in trace["host"]:
        if name == WINDOW_SPAN:
            return (start, start + dur)
    ends = [(e[1], e[1] + e[2]) for d in trace["devices"] for e in d["ops"]]
    if not ends:
        raise ValueError("trace holds no device event")
    return (min(a for a, _ in ends), max(b for _, b in ends))


def summarize(trace: dict, top: int = 10) -> dict:
    """Seconds per chip (the mean over the chips in the trace) inside the
    traced window:

      window_s              length of the window
      busy_s                union of every device op's interval
      loop_s                union of the `while` ops (they span their bodies)
      pallas_s              union of the Pallas custom calls
      collective_s          union of the collective ops
      collective_exposed_s  the part of that with no other leaf op running
      device_ops            [[name, seconds]] leaf ops that took most time
      idle_gaps             [[host span, seconds]] idle time of the first
                            chip, by the runner's host span that overlaps
                            each gap most ("(no host span)" if none)
    """
    window = window_of(trace)
    if not trace["devices"]:
        raise ValueError("trace holds no device plane with an "
                         f"{OPS_LINE!r} line")
    n = len(trace["devices"])
    acc = collections.defaultdict(float)
    by_name: Dict[str, float] = collections.defaultdict(float)
    first_gaps: List[Interval] = []
    for i, dev in enumerate(trace["devices"]):
        ops = _clip(dev["ops"], window)
        busy = union((a, b) for _, a, b in ops)
        coll = union((a, b) for nm, a, b in ops if is_collective(nm))
        leaf_compute = union((a, b) for nm, a, b in ops
                             if not is_container(nm) and not is_collective(nm))
        acc["busy_s"] += length(busy)
        acc["loop_s"] += length(union((a, b) for nm, a, b in ops if is_loop(nm)))
        acc["pallas_s"] += length(union((a, b) for nm, a, b in ops
                                        if is_pallas(nm)))
        acc["collective_s"] += length(coll)
        acc["collective_exposed_s"] += length(subtract(coll, leaf_compute))
        for nm, a, b in ops:
            if not is_container(nm):
                by_name[nm] += (b - a)
        if i == 0:
            first_gaps = subtract([window], busy)
    out = {k: v / n / 1e9 for k, v in acc.items()}
    for k in ("busy_s", "loop_s", "pallas_s", "collective_s",
              "collective_exposed_s"):
        out.setdefault(k, 0.0)
    out["window_s"] = (window[1] - window[0]) / 1e9
    out["chips"] = n
    names = sorted(by_name, key=by_name.get, reverse=True)[:top]
    out["device_ops"] = [[nm, by_name[nm] / n / 1e9] for nm in names]
    out["idle_gaps"] = _attribute_gaps(first_gaps, trace["host"], top)
    return out


def _attribute_gaps(gaps: Sequence[Interval], host: Sequence[Sequence],
                    top: int) -> List[List]:
    """Each gap goes to the host event that overlaps it most; of equals,
    to the shortest (the most specific)."""
    spans = sorted(((nm, s, s + d) for nm, s, d in host if nm != WINDOW_SPAN),
                   key=lambda e: e[2] - e[1])
    total: Dict[str, float] = collections.defaultdict(float)
    for a, b in gaps:
        best, best_len = "(no host span)", 0
        for nm, s, e in spans:
            ov = min(b, e) - max(a, s)
            if ov > best_len:
                best, best_len = nm, ov
        total[best] += b - a
    names = sorted((nm for nm in total if total[nm] >= MIN_GAP_NS),
                   key=total.get, reverse=True)[:top]
    return [[nm, total[nm] / 1e9] for nm in names]


def trim(trace: dict, window: Optional[Interval] = None,
         min_dur_ns: int = 0) -> dict:
    """A smaller trace of the same form: events clipped to `window` and
    leaf events shorter than `min_dur_ns` dropped. How the recorded trace
    under tests/ was cut from a chip run."""
    window = window or window_of(trace)
    keep = lambda nm, a, b: (b - a >= min_dur_ns or is_container(nm)
                             or is_collective(nm) or is_pallas(nm))
    return {
        "devices": [{"name": d["name"],
                     "ops": [[nm, a, b - a] for nm, a, b in _clip(d["ops"], window)
                             if keep(nm, a, b)]}
                    for d in trace["devices"]],
        "host": [[nm, a, b - a] for nm, a, b in _clip(trace["host"], window)],
    }
