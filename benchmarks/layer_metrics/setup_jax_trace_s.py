"""Set-up spent tracing Python to jaxprs: JAX's `jaxpr_trace_duration`
events as `analysis.guards` keeps them (`jax:trace`), paid on every run,
cache hit or not.

The four `setup_jax_*` / `setup_*` phases are self times (an event's
seconds leave out the events that ran inside it: nested `jit` traces,
the cache read inside `backend_compile_duration`), so they add up to no
more than the wall time they were spent in. `at_warm_s` reads the copy
`RecompileWatch.mark_warm()` put aside at the window's first dispatch:
set-up only, without the traced run's lowerings after the window. A
phase no event fell into is zero seconds (no cache read on a cold
cache); a program without the copy, or a runner that never marks warm,
reads as nothing.
"""


def at_warm_s(name):
    from dexiraft_tpu.analysis import guards

    at_warm = getattr(guards, "jax_at_warm", lambda: None)()
    if not at_warm:
        return None
    return at_warm.get(name, {"seconds": 0.0})["seconds"]


def read(obs):
    return at_warm_s("jax:trace")
