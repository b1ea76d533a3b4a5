"""What the window saves the exact part's kernel: the (query block, key
block) pairs its grid computes over those of the row's causal triangle,
from the program's `attn_block_pairs_visited_local` and
`attn_block_pairs_causal` counters (both summed over the layers; mean
over the measured window's steps). A window is four blocks of 512, so
about 2.5 of a query block's 32.5 causal blocks on average: near 8 % if
the blocks outside the window are skipped, 100 % if they are computed
and masked.
"""


def read(obs):
    c = obs.counters
    if not c.get("attn_block_pairs_visited_local") or not c.get(
            "attn_block_pairs_causal"):
        return None
    return (c["attn_block_pairs_visited_local"]
            / c["attn_block_pairs_causal"] * 100)
