"""Packed token rows for the language model, behind the same `Loader`.

A token file is an `.npz` of `tokens` (int32, every document end to end)
and `lengths` (int32, one entry a document). `PackedTokens` packs the
documents first-fit into rows of `seq_len` without splitting one (a
document goes into the first row that still has room for it, a new row
when none has; one longer than a row is cut to a row), and pads each
row's end. A sample is one row:

    tokens       [seq_len] int32   the documents' tokens, then 0
    positions    [seq_len] int32   restart at 0 in every document
    segment_ids  [seq_len] int32   1, 2, ... per document; 0 for pad

Attention and the loss stay inside a segment (models/lm). The packing is
a function of the file alone; which row a step sees is the `Loader`'s
seeded shuffle, as for image pairs. A row is put together from the file
when it is sampled (`loader:decode`), not held packed in memory.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def write_token_file(path: str, tokens: np.ndarray, lengths: np.ndarray) -> None:
    lengths = np.asarray(lengths, np.int32)
    tokens = np.asarray(tokens, np.int32)
    if int(lengths.sum()) != tokens.shape[0]:
        raise ValueError(f"{tokens.shape[0]} tokens for documents of "
                         f"{int(lengths.sum())} in all")
    with open(path, "wb") as f:  # np.savez appends .npz to a bare name
        np.savez(f, tokens=tokens, lengths=lengths)


def first_fit(lengths: np.ndarray, seq_len: int) -> List[List[int]]:
    """Rows of document indices: each document, in order, into the first
    row with room for it."""
    rows: List[List[int]] = []
    free = np.zeros(0, np.int64)
    for doc, n in enumerate(np.minimum(lengths, seq_len)):
        fits = np.flatnonzero(free >= n)
        if fits.size:
            row = int(fits[0])
        else:
            row = len(rows)
            rows.append([])
            free = np.append(free, seq_len)
        rows[row].append(doc)
        free[row] -= n
    return rows


class PackedTokens:
    """The dataset `Loader` wants: `len()` rows, `sample(index, rng)`."""

    def __init__(self, path: str, seq_len: int):
        with np.load(path) as f:
            self.tokens = f["tokens"]
            lengths = f["lengths"]
        self.seq_len = int(seq_len)
        self.starts = np.concatenate([[0], np.cumsum(lengths)[:-1]])
        self.lengths = np.minimum(lengths, self.seq_len)
        self.rows = first_fit(lengths, self.seq_len)

    def __len__(self) -> int:
        return len(self.rows)

    @property
    def fill(self) -> float:
        """Share of the rows' positions that hold a token."""
        return float(self.lengths.sum()) / (len(self.rows) * self.seq_len)

    def sample(self, index: int, rng=None) -> Dict[str, np.ndarray]:
        out = {k: np.zeros(self.seq_len, np.int32)
               for k in ("tokens", "positions", "segment_ids")}
        at = 0
        for seg, doc in enumerate(self.rows[index], start=1):
            n = int(self.lengths[doc])
            lo = int(self.starts[doc])
            out["tokens"][at:at + n] = self.tokens[lo:lo + n]
            out["positions"][at:at + n] = np.arange(n)
            out["segment_ids"][at:at + n] = seg
            at += n
        return out
