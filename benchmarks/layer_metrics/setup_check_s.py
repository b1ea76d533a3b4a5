"""Set-up spent on the correctness check: the plain reference program traced, compiled or loaded, and run.

The runner's own span `check`, host clock. Part of `setup_s`.
"""


def read(obs):
    return obs.spans.get("check")
