"""Runtime guards: the dynamic half of the jaxlint story.

jaxlint (the static half) catches the footguns visible in source text;
this module catches the two that only exist at run time:

- **steady-state recompiles** — a shape/dtype drift after warmup silently
  retraces the step and erases the throughput the benches measured. The
  process-wide `compile_count()` counter (fed by jax.monitoring's
  ``/jax/core/compile/backend_compile_duration`` event — one firing per
  executable built or read from the persistent cache, none for a call
  that finds its executable in memory) makes "compile count must stay
  flat after warmup" an assertable property.
- **implicit host<->device transfers** — a ``float()``/``np.asarray()``
  on the wrong value syncs the pipeline every step.
  ``jax.transfer_guard("disallow")`` turns those into errors while the
  sanctioned explicit spellings (``jax.device_put``/``jax.device_get``)
  pass.

``strict_mode()`` arms both and RAISES on violation — wired behind
``--strict`` in train_cli/eval_cli and always-on for the steady-state
window of serve_bench/train_bench. ``RecompileWatch`` observes without
raising — it powers the one-line drift warning non-strict runs emit.

Monitoring listeners cannot be unregistered (jax.monitoring has no
per-listener removal), so ONE module-level pair of listeners (begins
and ends) is installed, once, when this module is imported (an entry
point's set-up is mostly over before it makes its first watch, and the
`jax:` spans below are that set-up); entering/leaving strict_mode
snapshots its compile counter.

The same listeners keep what set-up is made of: seconds of JAX's own
compile-path events in the process-wide span table
(`profiling.snapshot("jax:")`): `jax:trace` (Python tracing to a jaxpr),
`jax:lower` (jaxpr to an MLIR module), `jax:backend_compile` (XLA's
compile) and `jax:cache_load` (reading a persistent-cache entry). JAX
fires a begin (a scalar event) and an end (a duration event) for the
first three, and the events nest: every inner `jit` logs a trace of its
own inside the outer's, and in jax 0.9 `backend_compile_duration` is
logged around `compile_or_get_cached`, so on a cache hit it fires and
holds the whole cache read. The listeners follow them on a per-thread
stack, so the seconds are SELF times (an event's seconds leave out the
events that ran inside it) and the four add up to no more than the wall
time they were spent in; `jax:backend_compile` is net of
`jax:cache_load`: near zero on a warm cache.

The bottom frame of the stack is the program a user's call started: its
`fun_name` with JAX's wrapper taken off (`jit(step)` -> `step`) is the
ROOT. When a root frame closes, each phase's self seconds inside it are
booked once under the phase and once more under `jax:<phase>/<root>`,
and a root compile that read no cache entry adds one count under
`jax:uncached/<root>`. So a record's count is a count of root frames
(`jax:lower`'s: the programs built), and the per-root records add up to
the phase totals. `jax_at_warm()` is the copy of the table
`RecompileWatch.mark_warm()` last put aside (what set-up cost, without
what compiles afterwards) and `setup_report()` the same copy as rows by
root. The drift count keeps counting the raw compile event, and keeps
the root each compile was for: the drift warning names them.
"""

from __future__ import annotations

import collections
import contextlib
import re
import sys
import threading
from typing import Iterator, Optional

import jax

from dexiraft_tpu import profiling
from dexiraft_tpu.analysis.locks import OrderedLock

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
# jax.monitoring event -> span name in profiling's table
_DURATION_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax:trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax:lower",
    _COMPILE_EVENT: "jax:backend_compile",
    _CACHE_EVENT: "jax:cache_load",
}
_WRAPPED = re.compile(r"^\w+\((.*)\)$")   # jit(step), pmap(step)

_lock = OrderedLock("analysis.guards.listener")
_installed = False
_count = 0
# (compile number, root) of the last compiles, for the drift warning
_compiled: "collections.deque" = collections.deque(
    maxlen=profiling.SPAN_WINDOW)
_at_warm: dict = {}
_tls = threading.local()


class RecompileBudgetExceeded(RuntimeError):
    """Raised when a strict_mode region compiles past its pinned budget."""


class _Frame:
    """An event of this thread that has begun and not ended."""

    __slots__ = ("event", "fun_name", "inside", "read_cache")

    def __init__(self, event: str, fun_name: str) -> None:
        self.event = event
        self.fun_name = fun_name
        self.inside = 0.0        # seconds of the events that ended in it
        self.read_cache = False


def _root_name(fun_name: str) -> str:
    m = _WRAPPED.match(fun_name)
    return (m.group(1) if m else fun_name) or "?"


def _on_begin(event: str, _start: float, fun_name: str = "", **_kw) -> None:
    if event in _DURATION_SPANS:
        _tls.__dict__.setdefault("stack", []).append(_Frame(event, fun_name))


def _on_end(event: str, duration: float, fun_name: str = "", **_kw) -> None:
    global _count
    phase = _DURATION_SPANS.get(event)
    if phase is None:
        return
    state = _tls.__dict__
    stack = state.setdefault("stack", [])
    # self seconds by phase inside the root frame that is open
    booked = state.setdefault("booked", {})
    # the frame this end closes: the nearest open one of its event and
    # name. The frames above it are begins whose end JAX skipped: they
    # go, and what ended inside them ended inside this one. An end that
    # has no begin (the cache read always; a begin from before the
    # listeners) is a leaf of whatever frame is open.
    at = next((i for i in range(len(stack) - 1, -1, -1)
               if stack[i].event == event
               and stack[i].fun_name == fun_name), None)
    if at is None:
        frame = _Frame(event, fun_name)
    else:
        frame = stack[at]
        for lost in stack[at + 1:]:
            frame.inside += lost.inside
            frame.read_cache |= lost.read_cache
        del stack[at:]
    root = _root_name(stack[0].fun_name if stack else fun_name)
    if event == _COMPILE_EVENT:
        _count += 1
        _compiled.append((_count, root))
    booked[phase] = (booked.get(phase, 0.0)
                     + max(duration - frame.inside, 0.0))
    if stack:
        stack[-1].inside += duration
        stack[-1].read_cache |= event == _CACHE_EVENT
        return
    for name, seconds in booked.items():
        profiling.add(name, seconds)
        profiling.add(f"{name}/{root}", seconds)
    booked.clear()
    if event == _COMPILE_EVENT and not frame.read_cache:
        profiling.add(f"jax:uncached/{root}", duration)


def _ensure_listener() -> None:
    global _installed
    with _lock:
        if not _installed:
            jax.monitoring.register_scalar_listener(_on_begin)
            jax.monitoring.register_event_duration_secs_listener(_on_end)
            _installed = True


_ensure_listener()


def jax_at_warm() -> dict:
    """`profiling.snapshot("jax:")` as the last `mark_warm()` of any
    watch saw it ({} before the first): a run's set-up, when warm is
    marked where the timed window starts and not again."""
    return _at_warm


def setup_report(floor_s: float = 1.0) -> list:
    """`jax_at_warm()` as rows by root, largest first: `root`, the four
    phases' `seconds` and their sum, `programs` (root lowerings) and
    `uncached` (root compiles that read no cache entry). Roots under
    `floor_s` seconds are folded into one last row, `other`."""
    phases = {name: name[len("jax:"):] + "_s"
              for name in _DURATION_SPANS.values()}
    rows: dict = {}
    for name, rec in _at_warm.items():
        kind, _, root = name.partition("/")
        if not root:
            continue
        row = rows.setdefault(root, {
            "root": root, "seconds": 0.0, **dict.fromkeys(phases.values(), 0.0),
            "programs": 0, "uncached": 0})
        if kind in phases:
            row[phases[kind]] += rec["seconds"]
            row["seconds"] += rec["seconds"]
            if kind == "jax:lower":
                row["programs"] += rec["count"]
        elif kind == "jax:uncached":
            row["uncached"] += rec["count"]
    kept = sorted((r for r in rows.values() if r["seconds"] >= floor_s),
                  key=lambda r: -r["seconds"])
    small = [r for r in rows.values() if r["seconds"] < floor_s]
    if small:
        other = {key: sum(r[key] for r in small) for key in small[0]
                 if key != "root"}
        kept.append({"root": "other", **other})
    return kept


def setup_line() -> str:
    """`setup_report()` on one line, for an entry point to print when
    its set-up is over."""
    rows = setup_report()
    parts = [
        f"{r['root']} {r['seconds']:.1f} s (trace {r['trace_s']:.1f}, lower "
        f"{r['lower_s']:.1f}, compile {r['backend_compile_s']:.1f}, cache "
        f"read {r['cache_load_s']:.1f}; {r['programs']} program(s), "
        f"{r['uncached']} uncached)" for r in rows]
    total = sum(r["seconds"] for r in rows)
    return (f"[setup] jax spent {total:.1f} s before warm: "
            + ("; ".join(parts) or "nothing traced or compiled"))


def _roots_between(lo: int, hi: int) -> list:
    """The roots of compiles lo+1 .. hi, "?" for one the window lost."""
    if hi <= lo:  # the steady state: a check a batch, nothing compiled
        return []
    seen = dict(tuple(_compiled))
    return [seen.get(n, "?") for n in range(lo + 1, hi + 1)]


def _named(roots: list) -> str:
    """`step x2, loss x1`, most compiled first."""
    return ", ".join(f"{root} x{n}" for root, n in
                     collections.Counter(roots).most_common())


def compile_count() -> int:
    """Backend compiles observed in this process so far (monotone).

    Counts actual XLA backend compiles — executable-cache hits and
    persistent-cache deserializations do not fire the event twice for
    the same executable, so a flat count across a window means XLA
    re-used executables for every dispatch in it.
    """
    _ensure_listener()
    return _count


class RecompileWatch:
    """Observe-only recompile sentinel for non-strict runs.

    Usage::

        watch = RecompileWatch("train")
        ... warmup (compiles expected) ...
        watch.mark_warm()
        ... steady state ...
        watch.warn_if_drifted()   # one line on stderr, once, if any
                                  # post-warmup compile happened

    ``mark_warm()`` may be called repeatedly (e.g. once per new bucket
    the caller *expects* to compile); drift is measured from the last
    call.
    """

    def __init__(self, label: str = "run", budget: int = 0):
        self.label = label
        self.budget = budget
        _ensure_listener()
        self._warm_at: Optional[int] = None
        # roots of the drift from before the last sanctioned window
        self._before: list = []
        self._warned = False
        # open sanctioned() windows (possibly on OTHER threads): the
        # compile counter is process-global, so a check() racing an
        # in-progress expected compile would read it as drift before
        # the window's exit shifts the baseline
        self._slock = OrderedLock("analysis.guards.watch")
        self._sanctioned_depth = 0
        self._win_base = 0   # compile_count at the 0->1 depth transition

    def mark_warm(self) -> None:
        # read AND write under the window lock: engines call this from
        # dispatcher and handler threads, and a count read before the
        # lock can go stale against a concurrent sanctioned() exit's
        # re-baseline — writing the stale count would re-expose the
        # window's own compiles as drift. watch -> listener (via
        # compile_count) is the declared LOCK_ORDER direction.
        global _at_warm
        with self._slock:
            self._warm_at = compile_count()
            self._before = []
        _at_warm = profiling.snapshot("jax:")

    @property
    def drift(self) -> int:
        """Compiles since mark_warm() (0 before it is called)."""
        return len(self.recompiled())

    def recompiled(self) -> list:
        """The root (the jitted function a call started, as
        `setup_report` names it) of each compile `drift` counts."""
        if self._warm_at is None:
            return []
        return self._before + _roots_between(self._warm_at, compile_count())

    def check(self, budget: Optional[int] = None) -> None:
        """Raise :class:`RecompileBudgetExceeded` when drift exceeds the
        budget (defaults to the watch's own). The strict-mode teeth; the
        observe-only path uses :meth:`warn_if_drifted` instead."""
        budget = self.budget if budget is None else budget
        with self._slock:
            if self._sanctioned_depth > 0:
                # a sanctioned window is open (engines share one watch
                # across threads: a cold streaming bucket compiling in
                # a handler thread must not fail the pair dispatcher's
                # concurrent check, and vice versa) — its exit shifts
                # the baseline past its compiles; the next check has
                # teeth again
                return
            # read drift under the same lock as the depth check: a
            # window opening (or exiting) in between would hand us a
            # count that includes its sanctioned compiles
            roots = self.recompiled()
        if len(roots) > budget:
            raise RecompileBudgetExceeded(
                f"[guards] {self.label}: {len(roots)} backend compile(s) "
                f"in a strict region with budget {budget}: {_named(roots)} "
                f"— steady state retraced (shape/dtype drift); "
                f"docs/static_analysis.md has the playbook")

    @contextlib.contextmanager
    def sanctioned(self) -> Iterator[None]:
        """Absorb the compiles of a sanctioned window — the compile-side
        twin of ``jax.transfer_guard("allow")`` around planned host I/O.

        The baseline shifts by exactly the window's compile count, so
        drift observed OUTSIDE the window still counts: a checkpoint
        save's one-time per-shape device copies (the fsdp per-shard
        snapshot) pass, a train-step retrace before or after does not.
        No-op before ``mark_warm()``. Thread-aware: while any window is
        open, concurrent :meth:`check`/:meth:`warn_if_drifted` calls
        (the other engine's dispatch on its own thread) defer rather
        than read the in-progress expected compile as drift.
        OVERLAPPING windows (both engines compiling fresh buckets at
        once) merge into one span: the baseline snapshots at the 0->1
        depth transition and shifts once at 1->0, so a compile landing
        inside two open windows is absorbed once, not twice (a double
        shift would drive drift negative and silently extend the
        blind spot past the windows' exit).

        Known blind spot, accepted: the compile counter is
        process-GLOBAL, so another thread's genuine drift landing inside
        an open window is absorbed with it (``mark_warm()`` has the same
        property — it baselines past everything). Attribution would need
        per-thread counts the jax.monitoring listener does not expose;
        windows are short (cold-bucket compiles), and steady-state drift
        recurs, so the next post-window check catches a real leak."""
        with self._slock:
            if self._sanctioned_depth == 0:
                self._win_base = compile_count()
            self._sanctioned_depth += 1
        try:
            yield
        finally:
            with self._slock:
                self._sanctioned_depth -= 1
                if self._sanctioned_depth == 0 and self._warm_at is not None:
                    # the drift from before the window stays, by
                    # name; the baseline lands ON the current count. A
                    # mark_warm() issued while the window was open is at
                    # or past its base and leaves nothing before it
                    # (never a negative drift, which would mask real
                    # retraces)
                    self._before += _roots_between(self._warm_at,
                                                   self._win_base)
                    self._warm_at = compile_count()

    def warn_if_drifted(self, file=None) -> bool:
        """One-line, once-only warning when post-warmup compiles exist.

        Returns True if drift was (ever) reported — callers embedding
        this in a loop get the cadence for free.
        """
        report = False
        with self._slock:
            if self._sanctioned_depth > 0 or self._warned:
                return self._warned
            # drift is read INSIDE the lock, after the depth check: a
            # sanctioned window exiting between an early read and the
            # check would leave a stale pre-rebaseline count here — a
            # bogus warning that latches _warned and silences every
            # future real one. (watch -> listener nesting via
            # compile_count() is the declared LOCK_ORDER direction.)
            roots = self.recompiled()
            if roots:
                # claim the once-only slot under the lock (two engine
                # threads drifting together must not both print); the
                # print itself happens after release — I/O under a lock
                # is the JL023 shape this module now lints against
                self._warned = True
                report = True
        if report:
            print(f"[guards] {self.label}: {len(roots)} recompile(s) after "
                  f"warmup: {_named(roots)} — shape/dtype drift is erasing "
                  f"throughput; rerun with --strict to fail fast "
                  f"(docs/static_analysis.md)",
                  file=file or sys.stderr)
        return self._warned


@contextlib.contextmanager
def strict_mode(compile_budget: int = 0,
                transfer: str = "disallow",
                label: str = "strict") -> Iterator[RecompileWatch]:
    """Arm transfer_guard + the recompile sentinel for a region.

    Inside the region:
      - implicit host<->device transfers raise immediately (jax's own
        transfer_guard error names the offending aval); explicit
        ``jax.device_put``/``jax.device_get`` still pass,
      - backend compiles are counted; leaving the region (or calling
        ``check()`` on the yielded watch) raises
        :class:`RecompileBudgetExceeded` if more than ``compile_budget``
        happened.

    ``compile_budget=0`` is the steady-state contract: run warmup
    *before* entering. A warmup-inclusive region should pass its known
    compile count (e.g. one per serve bucket).

    ``transfer`` is any jax transfer-guard level ("allow", "log",
    "disallow"); "log" is the diagnose-without-failing mode.

    The yielded object is a :class:`RecompileWatch` pre-marked at entry,
    so ``watch.drift`` is live inside the region, ``watch.check()`` can
    assert mid-region (e.g. per bench rep), and ``watch.mark_warm()``
    can absorb an *expected* compile (a planned new bucket) without
    widening the budget for the unplanned ones.
    """
    watch = RecompileWatch(label, budget=compile_budget)
    watch.mark_warm()
    with jax.transfer_guard(transfer):
        yield watch
    watch.check()
