"""Imbalance of the routing over the held experts: the fullest held
expert's slots over the mean (the program's `moe_load_max` /
`moe_load_mean`, the fullest over the expert layers, mean over the
measured window's steps). 1.0 is perfect balance; the grouped products'
work follows the sum, so imbalance costs tile padding only.
"""


def read(obs):
    c = obs.counters
    if not c.get("moe_load_mean"):
        return None
    return c["moe_load_max"] / c["moe_load_mean"]
