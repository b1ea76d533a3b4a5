"""Backend compiles inside the measured window; 0 is the contract.

`analysis.guards.RecompileWatch.drift` from just before the first timed
dispatch to after the last fetch.

`train_window_compiles` is this reading in the train cells.
"""


def read(obs):
    return obs.counters.get("window_compiles")
