"""The stored all-pairs pyramid's lookup and its gradient against the
oracle of tests/_corr_reference.py, at every stored dtype, plain and
through the Pallas kernels interpreted. A file of its own so that xdist's
`loadfile` hands these 56 cases and the rest of tests/test_corr.py to
different workers.
"""

import numpy as np
import pytest

from _corr_reference import (_oracle_programs, _oracle_volumes, _probe_coords,
                             build_corr_pyramid)
from _models import as_one_program
from dexiraft_tpu.ops import corr_lookup


def _boundary_centres(size, radius, levels):
    """Where aligning a window can break and weighting the axis cannot:
    at every level, each whole position from below -(2r+2) - r (the
    window's first position clamps there) to above size + r (it clamps at
    size), so every fine shift, every coarse stride and both clamps, with
    an epsilon either side of it, exactly on it, and half a pixel on."""
    n = 2 * radius + 2
    out = []
    for i in range(levels):
        whole = np.arange(-n - radius - 2, (size >> i) + radius + 3)
        for off in (-2.0**-10, 0.0, 2.0**-10, 0.5):
            out.append((whole + off) * 2.0**i)
    return np.concatenate(out).astype(np.float32)


def _boundary_coords(rng, h, w, radius, levels):
    """(b, h, w, 2): every boundary centre of the x axis beside one of the
    y axis (each list shuffled, the shorter cycled); b is what holds them."""
    xs = rng.permutation(_boundary_centres(w, radius, levels))
    ys = rng.permutation(_boundary_centres(h, radius, levels))
    count = max(len(xs), len(ys))
    b = -(-count // (h * w))
    idx = np.arange(b * h * w)
    coords = np.stack([xs[idx % len(xs)], ys[idx % len(ys)]], -1)
    return coords.reshape(b, h, w, 2)


# level extents either side of the window (2r+2 = 10) and of each stage
# boundary (the coarse block is 17 wide, its stride 8), as height and as width
_EDGE_SHAPES = [(9, 25), (10, 24), (16, 18), (17, 17), (18, 16), (24, 10),
                (25, 9)]
_ORACLE_CASES = (
    [pytest.param(shape, dt, 4, "plain", id=f"{shape[1]}x{shape[2]}-{dt}")
     for shape in [(1, 5, 7), (2, 6, 9), (1, 46, 62)]
     for dt in ("fp32", "bf16", "int8")]
    + [pytest.param((0, h, w), dt, 4, "plain", id=f"edges-{h}x{w}-{dt}")
       for h, w in _EDGE_SHAPES for dt in ("fp32", "bf16", "int8")]
    + [pytest.param((0, h, w), dt, r, "plain", id=f"edges-{h}x{w}-r{r}-{dt}")
       for h, w in [(16, 18), (9, 25)] for r in (3, 2)
       for dt in ("fp32", "bf16", "int8")]
    # the same through the Pallas kernels, interpreted (ops/pallas_window.py)
    + [pytest.param(shape, "fp32", 4, "kernel",
                    id=f"kernel-{shape[1]}x{shape[2]}")
       for shape in [(1, 5, 7), (2, 6, 9), (1, 46, 62)]]
    + [pytest.param((0, h, w), "fp32", 4, "kernel", id=f"kernel-edges-{h}x{w}")
       for h, w in _EDGE_SHAPES]
    + [pytest.param((0, 16, 18), dt, r, "kernel",
                    id=f"kernel-edges-16x18-r{r}-{dt}")
       for r, dt in [(3, "fp32"), (2, "fp32"), (3, "bf16"), (3, "int8")]])


@pytest.mark.parametrize("shape,corr_dtype,radius,path", _ORACLE_CASES)
def test_stored_pyramid_lookup_and_grad_match_oracle(shape, corr_dtype, radius,
                                                     path, monkeypatch):
    """4 levels: (1, 46, 62) is the chairs crop's 46x62, 23x31, 11x15,
    5x7; (2, 6, 9) ends in the 1x2 level and a 0x1 one, (1, 5, 7) in 1x1
    and 0x0. The `edges` cases (batch 0: as many as hold the centres) put
    level extents and centres where a window ALIGNED by selects can break
    (_boundary_centres), at radius 4, 3 and 2: the stage widths follow
    the radius. `kernel`: the Pallas kernels a TPU runs, interpreted.
    fp32: lookup and jax.grad with respect to BOTH feature maps against
    the oracle. bf16/int8: the lookup against the oracle on the STORED
    values (the lookup itself adds no rounding), and for bf16 the
    gradient, whose cotangent passes through the bf16 cast (int8's round
    has none: models/raft.py refuses to train with it)."""
    import jax
    import jax.numpy as jnp

    from dexiraft_tpu.ops import corr as corr_mod

    monkeypatch.setattr(corr_mod, "_kernel_interpret",
                        lambda: True if path == "kernel" else None)
    b, h, w = shape
    d = 16
    win2 = (2 * radius + 1) ** 2
    rng = np.random.RandomState(b * 100 + h)
    if b:
        coords = _probe_coords(rng, b, h, w)
    else:
        coords = _boundary_coords(rng, h, w, radius, 4)
        b = coords.shape[0]
    coords = jnp.asarray(coords)
    f1 = jnp.asarray(rng.randn(b, h, w, d).astype(np.float32))
    f2 = jnp.asarray(rng.randn(b, h, w, d).astype(np.float32))
    weight = jnp.asarray(rng.randn(b, h, w, 4 * win2).astype(np.float32))

    @jax.jit
    def ours(f1, f2):
        pyr = build_corr_pyramid(f1, f2, num_levels=4, radius=radius,
                                 dtype=corr_dtype)
        return corr_lookup(pyr, coords)

    oracle, oracle_stored, grad_oracle = _oracle_programs(radius)

    # one pyramid for the lookup and for the stored values read below: a
    # second build may round a product at a bf16 boundary the other way
    pyr = build_corr_pyramid(f1, f2, num_levels=4, radius=radius,
                             dtype=corr_dtype)
    assert pyr.level_shapes == tuple((h >> i, w >> i) for i in range(4))
    got = np.asarray(jax.jit(corr_lookup)(pyr, coords))
    assert got.shape == (b, h, w, 4 * win2) and got.dtype == np.float32

    if corr_dtype == "fp32":
        want = np.asarray(oracle(f1, f2, coords))
    else:  # the stored values, relaid to the oracle's one slab per query
        stored = []
        for i, (lvl, (hl, wl)) in enumerate(zip(pyr.levels, pyr.level_shapes)):
            v = np.asarray(lvl).astype(np.float32)
            if pyr.scales is not None:
                v = v * np.float32(pyr.scales[i])
            stored.append(jnp.asarray(
                np.moveaxis(v, -1, 1).reshape(b * h * w, hl, wl)))
        want = np.asarray(oracle_stored(stored, coords))
        # and the stored values are the oracle's, rounded once
        full = np.asarray(as_one_program(_oracle_volumes)(f1, f2, 1)[0])
        step = {"bf16": 2.0**-8 * np.abs(full).max(),
                "int8": np.abs(full).max() / 127 * 0.51}[corr_dtype]
        assert np.abs(np.asarray(stored[0]) - full).max() <= step + 1e-5
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-5)
    assert np.abs(want).max() > 0.1  # the probe reads something

    if corr_dtype == "int8":
        return
    grad = jax.jit(jax.grad(
        lambda a, c: jnp.sum(ours(a, c) * weight), (0, 1)))
    for g, want_g in zip(grad(f1, f2), grad_oracle(f1, f2, coords, weight)):
        g, want_g = np.asarray(g), np.asarray(want_g)
        scale = np.abs(want_g).max()
        assert scale > 0.1
        # fp32: sums of up to 4 x 81 x H*W products in another order;
        # bf16: each level's cotangent is rounded to bf16 on its way back
        tol = 1e-5 if corr_dtype == "fp32" else 2.0**-7
        np.testing.assert_allclose(g, want_g, rtol=0, atol=tol * scale)
