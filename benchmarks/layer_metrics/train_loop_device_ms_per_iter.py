"""Device time of one refinement iteration: time under the `while` ops of
the traced window (a `while` event spans its body's ops).

Eval: per pair and iteration (over batches x batch x iterations), so
cells of different batch compare. Train: per step and iteration (over
steps x iterations); the forward scan, the backward scan and the
forward recomputed under remat are all `while` ops and all counted.

Train cells, per step and iteration; `eval_loop_device_ms_per_iter` is the eval cells' reading,
per pair and iteration.
"""


def read(obs):
    if obs.trace is None:
        return None
    c = obs.counters
    return obs.trace["loop_s"] / (c["traced_units"] * c["iters"]) * 1e3
