"""Set-up spent on the first call of each shape this cell uses (compile, or load from the persistent cache, with the Python tracing and lowering either way) and the few calls that settle the loop.

The runner's own span `warm`, host clock. Part of `setup_s`.
"""


def read(obs):
    return obs.spans.get("warm")
