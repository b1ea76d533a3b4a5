"""Host time to assemble one batch for dispatch: padders, pad, tail fill,
`np.stack` of both frames (`InferenceEngine._dispatch`, span
`engine:assemble`). The first part of `engine_dispatch_ms`.

The program's span table (`dexiraft_tpu.profiling.snapshot`) keeps each
span's single durations since its last reset, and `ServeStats.reset()`
at the window's first dispatch resets `engine:`; a traced run then goes
on into the traced tail. `window_mean_ms` is the mean of the first
`units` durations: the measured window's batches, profiler off, the
same batches `engine_dispatch_ms` covers. A program without the table,
or a span nothing entered, reads as nothing.
"""


def window_mean_ms(name, units):
    try:
        from dexiraft_tpu.profiling import snapshot
    except ImportError:
        return None
    rec = snapshot(name).get(name)
    # count == len(durations): none has dropped out of the bounded record
    if (not units or rec is None or len(rec["durations"]) < units
            or rec["count"] != len(rec["durations"])):
        return None
    return sum(rec["durations"][:int(units)]) / units * 1e3


def read(obs):
    return window_mean_ms("engine:assemble", obs.counters.get("engine_batches"))
