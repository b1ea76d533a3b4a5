"""Runtime guards: the dynamic half of the jaxlint story.

jaxlint (the static half) catches the footguns visible in source text;
this module catches the two that only exist at run time:

- **steady-state recompiles** — a shape/dtype drift after warmup silently
  retraces the step and erases the throughput the benches measured. The
  process-wide `compile_count()` counter (fed by jax.monitoring's
  ``/jax/core/compile/backend_compile_duration`` event — one firing per
  backend compile, cache hits excluded) makes "compile count must stay
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
per-listener removal), so ONE module-level listener is installed,
once, when this module is imported (an entry point's set-up is mostly
over before it makes its first watch, and the `jax:` spans below are
that set-up); entering/leaving strict_mode snapshots its compile
counter.

The same listener keeps what set-up is made of: seconds and count of
JAX's own compile-path events in the process-wide span table
(`profiling.snapshot("jax:")`): `jax:trace` (Python tracing to a jaxpr),
`jax:lower` (jaxpr to an MLIR module), `jax:backend_compile` (XLA's
compile) and `jax:cache_load` (reading a persistent-cache entry). The
seconds are SELF times, so the four add up to no more than the wall
time they were spent in: JAX's events nest (every inner `jit` logs a
trace of its own inside the outer's, and in jax 0.9
`backend_compile_duration` is logged around `compile_or_get_cached`,
so on a cache hit it fires and holds the whole cache read), and an
event's seconds here leave out the events that ran inside it. So
`jax:backend_compile` is net of `jax:cache_load`: near zero on a warm
cache. The drift count keeps counting the raw compile event.
`jax_at_warm()` is the copy of those totals `RecompileWatch.mark_warm()`
last put aside: what set-up cost, without what compiles afterwards.
"""

from __future__ import annotations

import collections
import contextlib
import sys
import threading
import time
from typing import Iterator, Optional

import jax

from dexiraft_tpu import profiling
from dexiraft_tpu.analysis.locks import OrderedLock

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# jax.monitoring event -> span name in profiling's table
_DURATION_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax:trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax:lower",
    _COMPILE_EVENT: "jax:backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax:cache_load",
}

_lock = OrderedLock("analysis.guards.listener")
_installed = False
_count = 0
_at_warm: dict = {}
_tls = threading.local()


class RecompileBudgetExceeded(RuntimeError):
    """Raised when a strict_mode region compiles past its pinned budget."""


def _listener(event: str, duration: float, **_kw) -> None:
    global _count
    if event == _COMPILE_EVENT:
        _count += 1
    name = _DURATION_SPANS.get(event)
    if name is not None:
        profiling.add(name, _self_seconds(duration))


def _self_seconds(duration: float) -> float:
    """`duration` of the event that just ended on this thread, less the
    events that ran inside it. The listener is called as an event ends,
    so the event began `duration` ago; the events already seen whose
    midpoint is later than that were inside it (midpoints, because this
    clock read is microseconds after the one JAX ended the event with:
    a sibling that ended just before this event began must not count,
    and an error is at most the length of such a sibling). They were
    each already made net of their own inside, so only the outermost of
    them remain. Own reads are on the monotonic clock; only lengths
    come from JAX."""
    now = time.perf_counter()
    start = now - duration
    seen = _tls.__dict__.setdefault(
        "seen", collections.deque(maxlen=profiling.SPAN_WINDOW))
    inside = 0.0
    while seen and seen[-1][0] > start:
        inside += seen.pop()[1]
    seen.append((now - duration / 2, duration))
    return max(duration - inside, 0.0)


def _ensure_listener() -> None:
    global _installed
    with _lock:
        if not _installed:
            jax.monitoring.register_event_duration_secs_listener(_listener)
            _installed = True


_ensure_listener()


def jax_at_warm() -> dict:
    """`profiling.snapshot("jax:")` as the last `mark_warm()` of any
    watch saw it ({} before the first): a run's set-up, when warm is
    marked where the timed window starts and not again."""
    return _at_warm


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
        _at_warm = profiling.snapshot("jax:")

    @property
    def drift(self) -> int:
        """Compiles since mark_warm() (0 before it is called)."""
        if self._warm_at is None:
            return 0
        return compile_count() - self._warm_at

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
            d = self.drift
        if d > budget:
            raise RecompileBudgetExceeded(
                f"[guards] {self.label}: {d} backend compile(s) "
                f"in a strict region with budget {budget} — steady state "
                f"retraced (shape/dtype drift). Enable jax.log_compiles() "
                f"to see what; docs/static_analysis.md has the playbook")

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
                    now = compile_count()
                    # the min-cap keeps a mark_warm() issued while the
                    # window was open from compounding with the shift:
                    # the baseline may land ON the current count, never
                    # past it (negative drift would mask real retraces)
                    self._warm_at = min(self._warm_at
                                        + (now - self._win_base), now)

    def warn_if_drifted(self, file=None) -> bool:
        """One-line, once-only warning when post-warmup compiles exist.

        Returns True if drift was (ever) reported — callers embedding
        this in a loop get the cadence for free.
        """
        report = False
        with self._slock:
            if self._sanctioned_depth > 0:
                return self._warned
            # drift is read INSIDE the lock, after the depth check: a
            # sanctioned window exiting between an early read and the
            # check would leave a stale pre-rebaseline count here — a
            # bogus warning that latches _warned and silences every
            # future real one. (watch -> listener nesting via
            # compile_count() is the declared LOCK_ORDER direction.)
            d = self.drift
            if d > 0 and not self._warned:
                # claim the once-only slot under the lock (two engine
                # threads drifting together must not both print); the
                # print itself happens after release — I/O under a lock
                # is the JL023 shape this module now lints against
                self._warned = True
                report = True
        if report:
            print(f"[guards] {self.label}: {d} recompile(s) after warmup "
                  f"— shape/dtype drift is erasing throughput; rerun "
                  f"with --strict to fail fast (docs/static_analysis.md)",
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
