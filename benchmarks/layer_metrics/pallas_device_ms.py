"""Device time in the Pallas correlation kernel per pair: the `custom-call`
events of the traced window (ops/pallas_corr.py; `corr_impl=auto` is
flash + fused on a TPU).

Eval cells only. The train cells run `allpairs` and hold no Pallas
call: that is the bypass.
"""


def read(obs):
    if obs.trace is None:
        return None
    c = obs.counters
    return obs.trace["pallas_s"] / (c["traced_units"] * c["batch"]) * 1e3
