"""Programs set-up compiled that the persistent cache did not hold: root
compiles before warm with no cache read inside them (`jax:uncached/<root>`).
Every one on a cold cache; on a warm cache the ones the cache never
keeps, each costing its whole compile on every run. Zero, not nothing,
where every compile read its entry. At the window's first dispatch
(`setup_step_programs_s.by_root`).
"""

from benchmarks.layer_metrics.setup_step_programs_s import by_root


def read(obs):
    roots = by_root()
    if roots is None:
        return None
    return sum(records["jax:uncached"]["count"] for records in roots.values()
               if "jax:uncached" in records)
