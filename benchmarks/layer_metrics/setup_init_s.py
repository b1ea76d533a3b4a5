"""Set-up spent on data tree or frame pool from the seed, and the state on the device from the seed in one jitted init.

The runner's own span `init`, host clock. Part of `setup_s`.
"""


def read(obs):
    return obs.spans.get("init")
