"""Seeded synthetic inputs: the same seed gives the same bytes.

Copied from chip_smoke.py (`_texture`, `write_data`), which stays the
smoke's own; listed in PERF.md for the PR that removes the duplicate.
"""

from __future__ import annotations

import json
import os
import os.path as osp
import shutil
from typing import Dict, List, Sequence

import numpy as np


def texture(rng: np.random.Generator, h: int, w: int) -> np.ndarray:
    """A smooth random uint8 texture, so the encoders see structure."""
    coarse = rng.uniform(0, 255, (h // 16 + 2, w // 16 + 2, 3))
    img = np.kron(coarse, np.ones((16, 16, 1)))[:h, :w]
    img += rng.normal(0, 6, img.shape)
    return np.clip(img, 0, 255).astype(np.uint8)


def _pair(rng: np.random.Generator, h: int, w: int):
    """(frame 1, frame 2, (dx, dy)): frame 2 is frame 1 rolled by a small
    seeded integer shift, which is the pair's flow."""
    dx, dy = (int(v) for v in rng.integers(-6, 7, 2))
    img1 = texture(rng, h, w)
    return img1, np.roll(img1, (dy, dx), (0, 1)), (dx, dy)


def frame_pairs(seed: int, n: int, hw: Sequence[int]) -> List[Dict[str, np.ndarray]]:
    """n frame pairs of size hw, in memory."""
    rng = np.random.default_rng(seed)
    pairs = []
    for _ in range(n):
        img1, img2, _ = _pair(rng, *hw)
        pairs.append({"image1": img1, "image2": img2})
    return pairs


def chairs_tree(root: str, seed: int, n_pairs: int, hw: Sequence[int]) -> str:
    """A FlyingChairs_release tree under <root>/data, written once per
    (seed, n_pairs, hw) and reused by later runs with the same key; a
    tree of another key is replaced, so the directory never grows.
    Returns the directory DEXIRAFT_DATA_DIR should name."""
    from PIL import Image

    from dexiraft_tpu.data.flow_io import write_flo

    key = {"seed": seed, "n_pairs": n_pairs, "hw": list(hw)}
    data = osp.join(root, "data")
    marker = osp.join(data, "tree.json")
    if osp.exists(marker):
        with open(marker) as f:
            if json.load(f) == key:
                return data
    shutil.rmtree(data, ignore_errors=True)
    chairs = osp.join(data, "FlyingChairs_release", "data")
    os.makedirs(chairs)
    rng = np.random.default_rng(seed)
    h, w = hw
    for i in range(n_pairs):
        img1, img2, shift = _pair(rng, h, w)
        Image.fromarray(img1).save(osp.join(chairs, f"{i:05d}_img1.ppm"))
        Image.fromarray(img2).save(osp.join(chairs, f"{i:05d}_img2.ppm"))
        write_flo(osp.join(chairs, f"{i:05d}_flow.flo"),
                  np.broadcast_to(np.float32(shift), (h, w, 2)))
    with open(osp.join(chairs, "..", "chairs_split.txt"), "w") as f:
        f.write("\n".join(["1"] * n_pairs))
    with open(marker, "w") as f:  # last: a half-written tree has no marker
        json.dump(key, f)
    return data
