"""Set-up spent in XLA's compiler: JAX's `backend_compile_duration`
events NET of the persistent-cache read inside them (`jax:backend_compile`;
in jax 0.9 the event is logged around `compile_or_get_cached`, so on a
cache hit it fires and holds the read, which is `setup_cache_load_s`).
Under a second on a warm cache; most of `first_setup_s` less `setup_s`
on a cold one. Self time, at the window's first dispatch
(`setup_jax_trace_s.at_warm_s`).
"""

from benchmarks.layer_metrics.setup_jax_trace_s import at_warm_s


def read(obs):
    return at_warm_s("jax:backend_compile")
