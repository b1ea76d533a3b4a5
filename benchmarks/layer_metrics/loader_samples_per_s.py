"""The host's ceiling: samples a second the cell's own `Loader` gives when
drained alone for a few seconds during set-up, the device idle.

A host measurement (decode + augmentation on this machine's cores), not
a device metric; `train_samples_per_s` cannot exceed it.
"""


def read(obs):
    return obs.counters.get("loader_samples_per_s")
