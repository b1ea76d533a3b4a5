"""Set-up JAX spent on the step programs: tracing, lowering, compiling
and cache reading (the four `setup_jax_*` / `setup_*` phases) booked to
the root `step`, the name of what `make_train_step` / `make_eval_step`
make. In a train cell that is the step; in an eval cell the cell's
forward and the check's plain forward, which both come from
`make_eval_step`.

`analysis.guards` follows JAX's begin and end events on a stack and
books each root frame's self seconds once under the phase (`jax:trace`,
which `setup_jax_trace_s` reads) and once under `jax:<phase>/<root>`,
the root being the jitted function a call started. So this metric and
`setup_other_programs_s` add up to the four phases' sum. `by_root` reads
the copy `RecompileWatch.mark_warm()` put aside at the window's first
dispatch, as `setup_jax_trace_s.at_warm_s` does; a program that keeps no
records by root (the parent commit), or a runner that never marks warm,
reads as nothing.
"""

PHASES = ("jax:trace", "jax:lower", "jax:backend_compile", "jax:cache_load")
STEP = "step"


def by_root():
    """`{root: {"jax:<kind>": record}}` of the at-warm copy, the kinds
    being PHASES and `jax:uncached`; None without records by root."""
    from dexiraft_tpu.analysis import guards

    at_warm = getattr(guards, "jax_at_warm", lambda: None)()
    roots = {}
    for name, rec in (at_warm or {}).items():
        kind, _, root = name.partition("/")
        if root:
            roots.setdefault(root, {})[kind] = rec
    return roots or None


def phase_seconds(records):
    return sum(rec["seconds"] for kind, rec in records.items()
               if kind in PHASES)


def read(obs):
    roots = by_root()
    if roots is None:
        return None
    return phase_seconds(roots.get(STEP, {}))
