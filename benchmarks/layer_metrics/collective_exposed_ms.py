"""The part of `collective_ms` during which no other op ran on that chip:
communication the schedule did not hide behind compute.
"""


def read(obs):
    if obs.trace is None:
        return None
    return (obs.trace["collective_exposed_s"]
            / obs.counters["traced_units"] * 1e3)
