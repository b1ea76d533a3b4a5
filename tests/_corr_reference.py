"""What the tests/test_corr*.py files compare the all-pairs correlation
against, each once: the reference CorrBlock (core/corr.py:12-60)
re-implemented in torch, an oracle in jax that shares no code with
ops/corr.py, and the probe coordinates both are read at.
"""

import functools

import numpy as np
import pytest

from _models import as_one_program
from dexiraft_tpu import ops

build_corr_pyramid = as_one_program(ops.build_corr_pyramid)

# without torch the files that import this module are skipped whole
torch = pytest.importorskip("torch")
import torch.nn.functional as F  # noqa: E402


class TorchCorrBlock:
    """Reference CorrBlock (core/corr.py) including its transposed window
    ordering (meshgrid(dy, dx) stacked onto (x, y) centroids,
    core/corr.py:37-43) — our implementation matches it bit-for-bit so
    reference-trained checkpoints load (see ops/corr.py:_window_delta and
    tests/test_torch_interop.py for the real-reference check)."""

    def __init__(self, fmap1, fmap2, num_levels=4, radius=4):
        self.num_levels = num_levels
        self.radius = radius
        b, dim, h, w = fmap1.shape
        f1 = fmap1.view(b, dim, h * w)
        f2 = fmap2.view(b, dim, h * w)
        corr = torch.matmul(f1.transpose(1, 2), f2) / (dim**0.5)
        corr = corr.view(b * h * w, 1, h, w)
        self.batch, self.h, self.w = b, h, w
        self.pyramid = [corr]
        for _ in range(num_levels - 1):
            corr = F.avg_pool2d(corr, 2, stride=2)
            self.pyramid.append(corr)

    def __call__(self, coords):  # coords (B, 2, H, W), channels (x, y)
        r = self.radius
        coords = coords.permute(0, 2, 3, 1)
        b, h, w, _ = coords.shape
        out = []
        for i, corr in enumerate(self.pyramid):
            d = torch.linspace(-r, r, 2 * r + 1)
            di, dj = torch.meshgrid(d, d, indexing="ij")
            # reference ordering: axis-0 offset added to x, axis-1 to y
            delta = torch.stack([di, dj], dim=-1)
            centroid = coords.reshape(b * h * w, 1, 1, 2) / 2**i
            coords_lvl = centroid + delta.view(1, 2 * r + 1, 2 * r + 1, 2)

            H, W = corr.shape[-2:]
            xg, yg = coords_lvl.split([1, 1], dim=-1)
            xg = 2 * xg / (W - 1) - 1
            yg = 2 * yg / (H - 1) - 1
            sampled = F.grid_sample(
                corr, torch.cat([xg, yg], dim=-1), align_corners=True
            )
            out.append(sampled.view(b, h, w, -1))
        return torch.cat(out, dim=-1)


# --- the stored pyramid against an oracle that shares no code with ops/corr.py


def _oracle_volumes(f1, f2, num_levels):
    """The reference's way round (core/corr.py:13-27): one all-pairs
    product in true fp32, one slab per query, and the VOLUME is pooled
    (ops/corr.py pools fmap2 and multiplies once per level)."""
    import jax
    import jax.numpy as jnp

    b, h, w, d = f1.shape
    corr = jnp.einsum("bijd,bkld->bijkl", f1, f2,
                      precision=jax.lax.Precision.HIGHEST) / np.sqrt(d)
    vols = [corr.reshape(b * h * w, h, w)]
    for _ in range(num_levels - 1):
        v = vols[-1]
        n, hh, ww = v.shape
        v = v[:, :hh // 2 * 2, :ww // 2 * 2]
        vols.append(v.reshape(n, hh // 2, 2, ww // 2, 2).mean((2, 4)))
    return vols


def _oracle_lookup(vols, coords, radius):
    """Naive bilinear sampling, tap by tap: floor, the four neighbours
    gathered, zero outside the frame (F.grid_sample's zeros padding with
    absolute coordinates). x offset on the slow window axis."""
    import jax.numpy as jnp

    b, h, w, _ = coords.shape
    flat = coords.reshape(-1, 2)
    d = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    win = 2 * radius + 1
    out = []
    for i, v in enumerate(vols):
        n, hl, wl = v.shape
        if hl == 0 or wl == 0:  # a level pooled away: nothing inside
            out.append(jnp.zeros((b, h, w, win * win), jnp.float32))
            continue
        x = jnp.broadcast_to(flat[:, 0, None, None] / 2**i + d[:, None],
                             (n, win, win))
        y = jnp.broadcast_to(flat[:, 1, None, None] / 2**i + d[None, :],
                             (n, win, win))
        x0, y0 = jnp.floor(x), jnp.floor(y)
        fx, fy = x - x0, y - y0

        def tap(yi, xi):
            inside = (yi >= 0) & (yi < hl) & (xi >= 0) & (xi < wl)
            val = v[jnp.arange(n)[:, None, None],
                    jnp.clip(yi, 0, hl - 1).astype(jnp.int32),
                    jnp.clip(xi, 0, wl - 1).astype(jnp.int32)]
            return jnp.where(inside, val, 0.0)

        window = ((1 - fy) * (1 - fx) * tap(y0, x0)
                  + (1 - fy) * fx * tap(y0, x0 + 1)
                  + fy * (1 - fx) * tap(y0 + 1, x0)
                  + fy * fx * tap(y0 + 1, x0 + 1))
        out.append(window.reshape(b, h, w, win * win))
    return jnp.concatenate(out, axis=-1)


@functools.lru_cache(maxsize=None)
def _oracle_programs(radius):
    """(lookup from the feature maps, lookup from stored volumes, the
    gradient of the first under a weight) of the oracle, jitted once a
    radius: the cases of one shape (a dtype and a path each) share the
    compiled programs."""
    import jax
    import jax.numpy as jnp

    def lookup(f1, f2, coords):
        return _oracle_lookup(_oracle_volumes(f1, f2, 4), coords, radius)

    def stored(vols, coords):
        return _oracle_lookup(vols, coords, radius)

    def weighted(f1, f2, coords, weight):
        return jnp.sum(lookup(f1, f2, coords) * weight)

    return jax.jit(lookup), jax.jit(stored), jax.jit(jax.grad(weighted, (0, 1)))


def _probe_coords(rng, b, h, w):
    """Centres inside the frame, exactly on its border pixels, between
    the last pixel and the frame's edge, and wholly outside (every tap of
    every level misses)."""
    coords = np.stack(np.meshgrid(np.arange(w), np.arange(h)), -1)[None]
    coords = coords.repeat(b, 0).astype(np.float32)
    coords += rng.uniform(-3, 3, coords.shape).astype(np.float32)
    flat = coords.reshape(-1, 2)
    special = np.array([
        [0, 0], [w - 1, h - 1], [w - 1, 0], [0, h - 1],        # border pixels
        [w - 0.5, h - 0.5], [-0.5, -0.25], [w - 1 + 4, 2.0],   # fading out
        [-1000, -1000], [w + 900, h + 700], [3.0, -500],       # outside
        [2.0, 3.0], [1.5, 2.5]], np.float32)                   # integer, half
    assert len(flat) >= 2 * len(special)
    flat[np.arange(len(special)) * (len(flat) // len(special))] = special
    return flat.reshape(b, h, w, 2)
