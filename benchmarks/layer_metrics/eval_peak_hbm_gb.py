"""Peak device memory on the fullest chip: the line's `memory_peak_bytes`,
which is `memory_stats()["peak_bytes_in_use"]` (buffers: weights,
batches, results) plus `["peak_bytes_reserved"]` (the scratch a running
program reserves), capped at the chip's limit. The two peaks need not
coincide, so it is an upper bound; `harness.memory_peaks` says why it
is the sum, and each run logs both counters (a traced run also the
compiler's temporaries and arguments for the step, which the sum
should stay near).

`train_peak_hbm_gb` is this reading in the train cells.
"""


def read(obs):
    if not obs.memory_peak_bytes:
        return None
    return obs.memory_peak_bytes / 1e9
