"""Device time of one refinement iteration: time under the `while` ops of
the traced window (a `while` event spans its body's ops).

Eval: per pair and iteration (over batches x batch x iterations), so
cells of different batch compare. Train: per step and iteration (over
steps x iterations); the forward scan, the backward scan and the
forward recomputed under remat are all `while` ops and all counted.

Eval cells, per pair and iteration; `train_loop_device_ms_per_iter` is the train cells' reading,
per step and iteration.
"""


def read(obs):
    if obs.trace is None:
        return None
    c = obs.counters
    return obs.trace["loop_s"] / (c["traced_units"] * c["batch"] * c["iters"]) * 1e3
