"""Set-up spent lowering jaxprs to MLIR modules: JAX's
`jaxpr_to_mlir_module_duration` events (`jax:lower`), paid on every run,
cache hit or not. Self time, at the window's first dispatch
(`setup_jax_trace_s.at_warm_s`).
"""

from benchmarks.layer_metrics.setup_jax_trace_s import at_warm_s


def read(obs):
    return at_warm_s("jax:lower")
