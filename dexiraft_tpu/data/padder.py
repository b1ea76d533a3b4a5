"""Eval-time padding to stride-8 shapes (core/utils/utils.py:7-24).

'sintel' mode centers the pad; other modes (kitti/HD1K) pad width
centered + all height at the bottom — replicate-edge padding in both,
like F.pad(mode='replicate').

`target=` generalizes the reference contract for the serving engine's
shape buckets (dexiraft_tpu.serve): instead of the next stride multiple,
pad out to an arbitrary (stride-aligned, >= input) bucket shape with the
same replicate-edge placement rules, and unpad per item on the way out.
target=None is bit-for-bit the reference behavior.

`seq=` aligns HEIGHT for halo compute sharding (parallel/halo.py):
each of the mesh's n_seq devices owns a contiguous block of feature
rows, so the padded height must divide by stride*seq — the effective
height alignment becomes stride*seq while width keeps plain stride.
seq=1 (default) is the unchanged single-slab contract.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np


class InputPadder:
    def __init__(self, shape: Sequence[int], mode: str = "sintel", stride: int = 8,
                 target: Optional[Tuple[int, int]] = None, seq: int = 1):
        self.ht, self.wd = int(shape[-3]), int(shape[-2])  # NHWC
        if seq < 1:
            raise ValueError(f"seq must be >= 1, got {seq}")
        h_align = stride * seq  # rows split into seq slabs of whole
        # stride-blocks each; width never shards, so it keeps stride
        if target is None:
            pad_ht = (((self.ht // h_align) + 1) * h_align - self.ht) \
                % h_align
            pad_wd = (((self.wd // stride) + 1) * stride - self.wd) % stride
        else:
            tht, twd = int(target[0]), int(target[1])
            if tht < self.ht or twd < self.wd:
                raise ValueError(
                    f"pad target {tht}x{twd} smaller than input "
                    f"{self.ht}x{self.wd}")
            if tht % stride or twd % stride:
                raise ValueError(
                    f"pad target {tht}x{twd} not stride-{stride} aligned")
            if tht % h_align:
                raise ValueError(
                    f"pad target height {tht} not divisible by "
                    f"stride*seq = {stride}*{seq} = {h_align} — pick a "
                    f"bucket height that splits into {seq} whole-stride "
                    "row slabs")
            pad_ht, pad_wd = tht - self.ht, twd - self.wd
        if mode == "sintel":
            # [left, right, top, bottom]
            self._pad = [pad_wd // 2, pad_wd - pad_wd // 2,
                         pad_ht // 2, pad_ht - pad_ht // 2]
        else:
            self._pad = [pad_wd // 2, pad_wd - pad_wd // 2, 0, pad_ht]

    @property
    def padded_shape(self) -> Tuple[int, int]:
        l, r, t, b = self._pad
        return (self.ht + t + b, self.wd + l + r)

    def pad(self, *inputs: np.ndarray) -> Tuple[np.ndarray, ...]:
        l, r, t, b = self._pad
        width = [(0, 0)] * (inputs[0].ndim - 3) + [(t, b), (l, r), (0, 0)]
        return tuple(np.pad(x, width, mode="edge") for x in inputs)

    def pad_into(self, out: np.ndarray, x: np.ndarray) -> None:
        """Write `x` edge-padded into `out` (..., padded H, padded W, C)
        in one pass over its bytes: the assignment casts to `out.dtype`
        as it places the frame, then the edges are replicated in place
        (columns of the frame's own rows first, then whole rows, so a
        corner holds the corner pixel). Bit for bit
        `pad(np.asarray(x, out.dtype))`, with no array of the padded
        size beside `out` — the serving engines fill their batch
        buffers with it."""
        l, r, t, b = self._pad
        if tuple(out.shape[-3:-1]) != self.padded_shape:
            raise ValueError(
                f"pad_into: out is {out.shape[-3]}x{out.shape[-2]}, the "
                f"padded shape is {self.padded_shape[0]}x"
                f"{self.padded_shape[1]}")
        y1, x1 = t + self.ht, l + self.wd  # the frame's far corner in `out`
        rows = out[..., t:y1, :, :]
        rows[..., l:x1, :] = x
        if l:
            rows[..., :l, :] = rows[..., l:l + 1, :]
        if r:
            rows[..., x1:, :] = rows[..., x1 - 1:x1, :]
        if t:
            out[..., :t, :, :] = out[..., t:t + 1, :, :]
        if b:
            out[..., y1:, :, :] = out[..., y1 - 1:y1, :, :]

    def unpad(self, x: np.ndarray) -> np.ndarray:
        l, r, t, b = self._pad
        ht, wd = x.shape[-3], x.shape[-2]
        return x[..., t:ht - b or None, l:wd - r or None, :]
