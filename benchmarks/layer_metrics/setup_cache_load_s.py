"""Set-up spent reading executables from the persistent compilation
cache: JAX's `cache_retrieval_time_sec` events (`jax:cache_load`). Absent
on a run that hit nothing. At the window's first dispatch
(`setup_jax_trace_s.at_warm_s`).
"""

from benchmarks.layer_metrics.setup_jax_trace_s import at_warm_s


def read(obs):
    return at_warm_s("jax:cache_load")
