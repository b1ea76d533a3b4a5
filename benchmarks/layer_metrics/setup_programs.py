"""Programs set-up builds: root lowerings before warm, one for every
jitted function (or eager operation) a call of the program's started and
JAX lowered, whether its executable was then compiled or read from the
cache. A handful in a flow cell (the step, `init`, the check); a hundred
where the check's reference runs op by op. At the window's first
dispatch (`setup_step_programs_s.by_root`).
"""

from benchmarks.layer_metrics.setup_step_programs_s import by_root


def read(obs):
    roots = by_root()
    if roots is None:
        return None
    return sum(records["jax:lower"]["count"] for records in roots.values()
               if "jax:lower" in records)
