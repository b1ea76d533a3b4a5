"""Device time of one batch (eval) or one step (train): the union of the
device-op intervals in the traced window over the units traced, mean
over the chips.

`train_step_device_ms` is this reading in the train cells.
"""


def read(obs):
    if obs.trace is None:
        return None
    return obs.trace["busy_s"] / obs.counters["traced_units"] * 1e3
