"""Device busy time outside the `while` ops.

Eval: per pair; DexiNed, the encoders, the pyramid build and the final
upsample. Train: per step; the same forward parts, their backward
(the frozen DexiNed has none) and the optimizer update.

Eval cells, per pair; `train_prelude_device_ms` is the train cells' reading,
per step.
"""


def read(obs):
    if obs.trace is None:
        return None
    c = obs.counters
    return ((obs.trace["busy_s"] - obs.trace["loop_s"])
            / (c["traced_units"] * c["batch"]) * 1e3)
