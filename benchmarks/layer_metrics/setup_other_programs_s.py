"""Set-up JAX spent on every program but the step's: the same four
phases as `setup_step_programs_s`, under every other root. The jitted
`init`, the flow check's plain `loss`, the language check's reference
(`reference_layer`, `reference_layer_vjp`, ... and what it dispatches
op by op), eager conversions. With `setup_step_programs_s` it adds up
to `setup_jax_trace_s + setup_jax_lower_s + setup_backend_compile_s +
setup_cache_load_s`.
"""

from benchmarks.layer_metrics.setup_step_programs_s import (STEP, by_root,
                                                            phase_seconds)


def read(obs):
    roots = by_root()
    if roots is None:
        return None
    return sum(phase_seconds(records) for root, records in roots.items()
               if root != STEP)
