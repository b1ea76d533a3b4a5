"""Seeded token documents for the language-model cells: the same seed
gives the same file.

Lengths are log-normal (`median`, `sigma` of the log), clipped to
`[shortest, longest]`; token ids are uniform over the vocabulary held.
Written once per key under the cell's work directory as the `.npz`
`dexiraft_tpu.data.tokens.PackedTokens` reads; a file of another key is
replaced, so the directory never grows.
"""

from __future__ import annotations

import json
import os
import os.path as osp

import numpy as np


def token_file(root: str, seed: int, documents: int, vocab_size: int,
               median: float, sigma: float, shortest: int,
               longest: int) -> str:
    from dexiraft_tpu.data.tokens import write_token_file

    key = {"seed": seed, "documents": documents, "vocab_size": vocab_size,
           "median": median, "sigma": sigma, "shortest": shortest,
           "longest": longest}
    path = osp.join(root, "documents.npz")
    marker = osp.join(root, "documents.json")
    if osp.exists(marker) and osp.exists(path):
        with open(marker) as f:
            if json.load(f) == key:
                return path
    for p in (marker, path):
        if osp.exists(p):
            os.remove(p)
    rng = np.random.default_rng(seed)
    lengths = np.clip(np.exp(rng.normal(np.log(median), sigma, documents)),
                      shortest, longest).astype(np.int32)
    tokens = rng.integers(0, vocab_size, int(lengths.sum()), dtype=np.int32)
    write_token_file(path, tokens, lengths)
    with open(marker, "w") as f:  # last: a half-written file has no marker
        json.dump(key, f)
    return path
