"""Device busy time outside the `while` ops.

Eval: per pair; DexiNed, the encoders, the pyramid build and the final
upsample. Train: per step; the same forward parts, their backward
(the frozen DexiNed has none) and the optimizer update.

Train cells, per step; `eval_prelude_device_ms` is the eval cells' reading,
per pair.
"""


def read(obs):
    if obs.trace is None:
        return None
    return ((obs.trace["busy_s"] - obs.trace["loop_s"])
            / obs.counters["traced_units"] * 1e3)
