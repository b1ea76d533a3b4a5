"""What the window saves the kernel: the (query block, key block) pairs
its grid computes in a sliding-window layer over those of a full layer,
from the program's `attn_block_pairs_visited_window` and
`attn_block_pairs_visited_full` counters (each summed over the layers of
its kind; mean over the measured window's steps). About 30 % on the
cell's documents if blocks before the window are skipped, 100 % if they
are computed and masked.
"""


def read(obs):
    c = obs.counters
    if not c.get("attn_block_pairs_visited_full") or not c.get(
            "attn_layers_window"):
        return None
    window = c["attn_block_pairs_visited_window"] / c["attn_layers_window"]
    full = c["attn_block_pairs_visited_full"] / c["attn_layers_full"]
    return window / full * 100
