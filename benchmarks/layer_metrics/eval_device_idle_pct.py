"""Share of the traced window in which no op ran on the device, mean over
the chips. The `breakdown`'s `idle_gaps` says what the host was doing.

`train_device_idle_pct` is this reading in the train cells.
"""


def read(obs):
    if obs.trace is None:
        return None
    return (1.0 - obs.trace["busy_s"] / obs.trace["window_s"]) * 100
