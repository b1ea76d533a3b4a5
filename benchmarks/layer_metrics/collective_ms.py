"""Device time in collective ops per step (gradient all-reduce,
cross-replica BatchNorm moments; parallel/layout.py decides the mesh):
union of the collective events of the traced window, mean over chips.
"""


def read(obs):
    if obs.trace is None:
        return None
    return obs.trace["collective_s"] / obs.counters["traced_units"] * 1e3
