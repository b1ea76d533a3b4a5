"""Of the rows the expert layers' segment sums fetch, in whole windows
from each range's aligned start, the share that are rows of a range (the
program's `moe_rows_live` / `moe_rows_covered`, summed over the expert
layers, mean over the measured window's steps): what is left is padding
the kernel reads and places as zeros, which a window or a token block of
the wrong size shows as. A program without the counters, or a runner
that does not carry them, reads as nothing.
"""


def read(obs):
    c = obs.counters
    if not c.get("moe_rows_covered"):
        return None
    return 100.0 * c["moe_rows_live"] / c["moe_rows_covered"]
