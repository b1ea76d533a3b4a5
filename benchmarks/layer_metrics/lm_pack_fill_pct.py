"""Share of a step's positions that hold a token: the program's
`tokens_real` counter (non-pad positions, mean over the measured
window's steps) over rows x `seq_len`. Pad positions run through every
layer and the head and count for nothing.
"""


def read(obs):
    c = obs.counters
    if not c.get("tokens_real") or not c.get("seq_len"):
        return None
    return c["tokens_real"] / (c["batch"] * c["seq_len"]) * 100
