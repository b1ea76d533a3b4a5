"""Parity tests for the all-pairs correlation volume against the reference
CorrBlock semantics (core/corr.py:12-60), re-implemented here in torch.
"""

import functools

import numpy as np
import pytest

from _models import as_one_program
from dexiraft_tpu import ops
from dexiraft_tpu.ops import corr_lookup

build_corr_pyramid = as_one_program(ops.build_corr_pyramid)

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


@pytest.mark.parametrize("radius,num_levels", [(4, 4), (3, 4), (2, 2)])
def test_corr_pyramid_and_lookup_match_torch(radius, num_levels):
    rng = np.random.RandomState(0)
    # keep every pyramid level >= 2 in both dims: torch's grid normalization
    # divides by (size-1) and NaNs out on singleton levels
    B, H, W, D = 2, 16, 24, 8
    f1 = rng.randn(B, H, W, D).astype(np.float32)
    f2 = rng.randn(B, H, W, D).astype(np.float32)
    coords = (
        np.stack(np.meshgrid(np.arange(W), np.arange(H)), axis=-1)[None]
        .repeat(B, axis=0)
        .astype(np.float32)
    )
    coords += rng.uniform(-2, 2, coords.shape).astype(np.float32)

    pyr = build_corr_pyramid(f1, f2, num_levels=num_levels, radius=radius)
    ours = np.asarray(corr_lookup(pyr, coords))

    tb = TorchCorrBlock(
        torch.from_numpy(f1.transpose(0, 3, 1, 2)),
        torch.from_numpy(f2.transpose(0, 3, 1, 2)),
        num_levels=num_levels,
        radius=radius,
    )
    ref = tb(torch.from_numpy(coords.transpose(0, 3, 1, 2))).numpy()

    assert ours.shape == (B, H, W, num_levels * (2 * radius + 1) ** 2)
    np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-4)


def test_pyramid_shapes_floor_division():
    # odd spatial dims must floor like avg_pool2d (e.g. Sintel 55x128 at 1/8)
    rng = np.random.RandomState(1)
    f = rng.randn(1, 55, 13, 4).astype(np.float32)
    pyr = build_corr_pyramid(f, f, num_levels=4, radius=4)
    assert pyr.level_shapes == ((55, 13), (27, 6), (13, 3), (6, 1))
    # the stored form: target rows and columns major, the queries last
    assert [lvl.shape for lvl in pyr.levels] == [
        (1, hl, wl, 55 * 13) for hl, wl in pyr.level_shapes]


def test_lookup_finite_at_one_pixel_levels():
    """A pyramid level that collapses to a single row/col must still
    produce finite lookups. The reference's bilinear_sampler normalizes
    grid coords by (dim-1) (core/utils/utils.py:63-66), so a 1-pixel
    level divides by zero and floods the update block with nan (observed
    in tests/test_eval_stack_parity.py at 104x136 inputs). Our one-hot
    interpolation matmul uses absolute coords and stays finite at every
    size — small-image inference just works."""
    from dexiraft_tpu.ops import coords_grid

    rng = np.random.RandomState(3)
    f = rng.randn(1, 13, 17, 8).astype(np.float32)  # 104x136 at 1/8
    pyr = build_corr_pyramid(f, f, num_levels=4, radius=4)
    assert pyr.level_shapes[-1] == (1, 2)  # degenerate level hit
    out = corr_lookup(pyr, coords_grid(1, 13, 17))
    assert np.isfinite(np.asarray(out)).all()


def test_corr_pyramid_is_jit_safe_pytree():
    """Geometry ints are static aux data — jit/scan must not trace them."""
    import jax

    from dexiraft_tpu.ops import coords_grid

    rng = np.random.RandomState(2)
    f = rng.randn(1, 16, 16, 8).astype(np.float32)
    pyr = build_corr_pyramid(f, f)
    out = jax.jit(corr_lookup)(pyr, coords_grid(1, 16, 16))
    assert out.shape == (1, 16, 16, 324)


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


def _tap_hats(center, radius, size):
    """The form the lookup had until PR 34, as a second oracle: the axis
    weighted by dense hats, hats[b, j, p, q] = relu(1 - |p - (c + j - r)|)."""
    import jax.numpy as jnp

    t = center[:, None, None, :] + jnp.arange(
        -radius, radius + 1, dtype=jnp.float32)[:, None, None]
    pos = jnp.arange(size, dtype=jnp.float32)[:, None]
    return jnp.maximum(0.0, 1.0 - jnp.abs(pos - t))


def _hat_lookup(pyr, coords):
    import jax.numpy as jnp

    r, b, q = pyr.radius, pyr.batch, pyr.ht * pyr.wd
    flat = coords.reshape(b, q, 2).astype(jnp.float32)
    out = []
    for i, vol in enumerate(pyr.levels):
        hl, wl = vol.shape[1:3]
        ax = _tap_hats(flat[..., 0] / 2.0**i, r, wl)
        ay = _tap_hats(flat[..., 1] / 2.0**i, r, hl)
        rows = jnp.sum(ax[:, :, None] * vol.astype(jnp.float32)[:, None], 3)
        out.append(jnp.sum(ay[:, None] * rows[:, :, None], 3).reshape(b, -1, q))
    out = jnp.concatenate(out, axis=1)
    return jnp.swapaxes(out, 1, 2).reshape(b, pyr.ht, pyr.wd, -1)


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


@pytest.mark.parametrize("path", ["plain", "kernel"])
@pytest.mark.parametrize("shape,radius", [((2, 6, 9), 4), ((1, 16, 18), 3),
                                          ((1, 46, 62), 4)])
def test_lookup_matches_the_hat_form_and_its_coordinate_gradient(
        shape, radius, path, monkeypatch):
    """Against the dense-hat form the lookup had until PR 34 (a second
    oracle, now the tests' own): the window, and d/d coords, which the hats
    give as the slope of the interpolation and the aligned form has to
    state itself (floor has no gradient). The model stops that gradient
    (models/raft.py), so nothing else reads it."""
    import jax
    import jax.numpy as jnp

    from dexiraft_tpu.ops import corr as corr_mod

    monkeypatch.setattr(corr_mod, "_kernel_interpret",
                        lambda: True if path == "kernel" else None)
    b, h, w = shape
    rng = np.random.RandomState(h)
    f1 = jnp.asarray(rng.randn(b, h, w, 16).astype(np.float32))
    f2 = jnp.asarray(rng.randn(b, h, w, 16).astype(np.float32))
    pyr = build_corr_pyramid(f1, f2, num_levels=4, radius=radius)
    # random centres: the slope is not defined ON a whole position
    coords = jnp.asarray(_probe_coords(rng, b, h, w)
                         + rng.uniform(0.05, 0.45, (b, h, w, 2))
                         .astype(np.float32))
    weight = jnp.asarray(rng.randn(
        b, h, w, 4 * (2 * radius + 1) ** 2).astype(np.float32))

    got = jax.jit(corr_lookup)(pyr, coords)
    want = jax.jit(_hat_lookup)(pyr, coords)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=2e-5)
    g = jax.jit(jax.grad(lambda c: jnp.sum(corr_lookup(pyr, c) * weight)))
    g_hat = jax.jit(jax.grad(lambda c: jnp.sum(_hat_lookup(pyr, c) * weight)))
    want_g = np.asarray(g_hat(coords))
    assert np.abs(want_g).max() > 0.1
    np.testing.assert_allclose(np.asarray(g(coords)), want_g, rtol=0,
                               atol=2e-5 * np.abs(want_g).max())


def test_kernel_path_runs_shard_by_shard_on_a_data_mesh(monkeypatch):
    """The partitioner cannot split a Pallas call: alone it would gather a
    batch-sharded level onto every chip (`v5-train-chairs-dp4`). The call
    wraps itself in a shard_map over the axes the layout names
    (ops/pallas_window.py `_per_chip`): same values and gradients as the
    plain form on one device, and no all-gather in the program."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from dexiraft_tpu.ops import corr as corr_mod
    from dexiraft_tpu.parallel.layout import LAYOUT

    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs 4 (virtual) devices")
    b, h, w, d = 4, 8, 12, 16
    rng = np.random.RandomState(5)
    f1 = jnp.asarray(rng.randn(b, h, w, d).astype(np.float32))
    f2 = jnp.asarray(rng.randn(b, h, w, d).astype(np.float32))
    coords = jnp.asarray(_probe_coords(rng, b, h, w))
    weight = jnp.asarray(rng.randn(b, h, w, 3 * 49).astype(np.float32))

    def loss(f1, f2, coords):
        pyr = build_corr_pyramid(f1, f2, num_levels=3, radius=3)
        return jnp.sum(corr_lookup(pyr, coords) * weight)

    grad = jax.grad(loss, (0, 1))
    monkeypatch.setattr(corr_mod, "_kernel_interpret", lambda: None)
    want = jax.jit(grad)(f1, f2, coords)

    monkeypatch.setattr(corr_mod, "_kernel_interpret", lambda: True)
    mesh = Mesh(np.array(devices[:4]), (LAYOUT.data_axis,))
    data = NamedSharding(mesh, LAYOUT.batch())
    fn = jax.jit(grad, in_shardings=(data,) * 3, out_shardings=(data,) * 2)
    args = [jax.device_put(a, data) for a in (f1, f2, coords)]
    for g, want_g in zip(fn(*args), want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(want_g),
                                   rtol=0, atol=1e-5 * np.abs(want_g).max())
    text = fn.lower(*args).compile().as_text()
    assert "all-gather" not in text


# ---- the levels' gradient placed once a loop (ops/corr.py place_once) -----

@pytest.mark.parametrize("dtype", ["fp32", "bf16"])
@pytest.mark.parametrize("stack", [1, 3])
@pytest.mark.parametrize("lines,size", [(5, 20), (3, 7)],
                         ids=["longer", "shorter"])
def test_summing_place_kernel_matches_the_placements_summed(lines, size, stack,
                                                            dtype):
    """`place_axis_sum` (interpreted) against sum_t `place_axis(g_t, ...)`
    and against the plain form, along x as `place_once` calls it: an axis
    longer than the window's 10 positions and level 3's 7 columns, one
    lookup and three, a batch of 18 (tiles of 8 and, in bf16, 16: the last
    one is partial), starts from wholly before the axis to wholly after."""
    import jax
    import jax.numpy as jnp

    from dexiraft_tpu.ops import corr as corr_mod
    from dexiraft_tpu.ops.pallas_window import place_axis, place_axis_sum

    radius, b, q = 4, 18, 40
    n = 2 * radius + 2
    rng = np.random.RandomState(size + stack)
    g = jnp.asarray(rng.randn(stack, b, lines, n - 1, q).astype(np.float32))
    g = g.astype({"fp32": jnp.float32, "bf16": jnp.bfloat16}[dtype])
    centre = jnp.asarray(rng.uniform(-n - 2, size + n + 2, (stack, b, q))
                         .astype(np.float32))
    start, frac = corr_mod._window_geometry(centre, radius, size)

    got = jax.jit(lambda g: place_axis_sum(g, start, frac, n, size, 2, True))(g)
    assert got.shape == (b, lines, size, q) and got.dtype == jnp.float32
    one = jax.jit(lambda g, s, f: place_axis(g, s, f, n, size, 2, True))
    want = sum(np.asarray(one(g[t], start[t], frac[t]), np.float64)
               for t in range(stack))
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=1e-5)
    plain = sum(np.asarray(corr_mod._axis_placed(
        g[t].astype(jnp.float32), start[t], frac[t], n, size, 2), np.float64)
        for t in range(stack))
    np.testing.assert_allclose(np.asarray(got), plain, rtol=0, atol=1e-5)


def _scanned_lookups(pyr, probe, coords, weight, scale):
    """Three lookups under `jax.checkpoint` whose coordinates move with
    what the one before read, as a loop for `place_once`."""
    import jax
    import jax.numpy as jnp

    from dexiraft_tpu.ops.corr import lookup_centres

    def body(shift, probe):
        at = coords + shift
        out = jax.checkpoint(lambda p, c, z: p(c, z))(pyr, at, probe)
        step = 0.3 * jax.lax.stop_gradient(jnp.mean(out))
        return shift + step, (jnp.sum(out * weight) * scale,
                              lookup_centres(at))

    return jax.lax.scan(body, jnp.float32(0), probe, length=3)[1]


@pytest.mark.parametrize("path,corr_dtype", [
    ("plain", "fp32"), ("plain", "bf16"), ("kernel", "fp32")])
def test_place_once_matches_the_gradient_of_the_loop_as_written(
        path, corr_dtype, monkeypatch):
    """The wrapper against `jax.grad` of the same loop unwrapped (each
    backward iteration places its level's gradient whole and the scan sums
    them): both feature maps' gradients within 1e-5 (the order of an fp32
    sum over the iterations differs), the value and the other argument's
    gradient equal. A bf16 pyramid's gradient is summed in fp32 and cast
    once; the loop as written rounds every iteration's to bf16."""
    import jax
    import jax.numpy as jnp

    from dexiraft_tpu.ops import corr as corr_mod
    from dexiraft_tpu.ops.corr import place_once

    monkeypatch.setattr(corr_mod, "_kernel_interpret",
                        lambda: True if path == "kernel" else None)
    b, h, w, d = 2, 8, 10, 16
    rng = np.random.RandomState(3)
    f1 = jnp.asarray(rng.randn(b, h, w, d).astype(np.float32))
    f2 = jnp.asarray(rng.randn(b, h, w, d).astype(np.float32))
    coords = jnp.asarray(_probe_coords(rng, b, h, w))
    weight = jnp.asarray(rng.randn(b, h, w, 2 * 81).astype(np.float32))

    def loss(f1, f2, scale, wrapped):
        pyr = build_corr_pyramid(f1, f2, num_levels=2, radius=4,
                                 dtype=corr_dtype)
        if wrapped:
            out = place_once(_scanned_lookups, pyr, coords, weight, scale,
                             iters=3)
        else:
            out = _scanned_lookups(pyr, None, coords, weight, scale)[0]
        return jnp.sum(out)

    scale = jnp.float32(1.5)
    results = [jax.jit(jax.value_and_grad(
        lambda a, c, s: loss(a, c, s, wrapped), (0, 1, 2)))(f1, f2, scale)
        for wrapped in (True, False)]
    (value, grads), (want_value, want_grads) = results
    assert value == want_value
    assert grads[2] == want_grads[2] and abs(float(grads[2])) > 1.0
    tol = 1e-5 if corr_dtype == "fp32" else 2.0**-7
    for g, want in zip(grads[:2], want_grads[:2]):
        scale = np.abs(np.asarray(want)).max()
        assert scale > 0.1
        np.testing.assert_allclose(np.asarray(g), np.asarray(want), rtol=0,
                                   atol=tol * scale)
    # no gradient taken: the loop as written
    assert jax.jit(lambda a, c: loss(a, c, scale, True))(f1, f2) == \
        jax.jit(lambda a, c: loss(a, c, scale, False))(f1, f2)


def test_place_once_leaves_other_pyramids_to_the_loop():
    """An int8 pyramid has no tangent space and a pyramid of another type
    no levels to place: the loop runs as written, its probe None."""
    import jax.numpy as jnp

    from dexiraft_tpu.ops.corr import place_once

    seen = []

    def loop(pyr, probe, x):
        seen.append(probe)
        return 2.0 * x, None

    f = jnp.ones((1, 4, 4, 8), jnp.float32)
    for pyr in (build_corr_pyramid(f, f, num_levels=2, radius=2, dtype="int8"),
                {"levels": (f,)}):
        assert float(place_once(loop, pyr, jnp.float32(2.0), iters=3)) == 4.0
    assert seen == [None, None]


_REFINE_CASES = [("v1", {}), ("v1", {"remat": True}),
                 ("v3", {"small": True, "remat_lookup": True}),
                 ("v5", {}), ("v5", {"remat": True})]


@pytest.mark.parametrize("variant,flags", _REFINE_CASES, ids=[
    "-".join([v] + [f"{k}={f[k]}" for k in f]) for v, f in _REFINE_CASES])
def test_raft_refinement_places_the_levels_gradient_once(variant, flags,
                                                         monkeypatch):
    """RAFT's scanned refinement (mode="step": the pyramid build and the
    loop, from given features) in train mode, against the same model with
    `place_once` taken out, which is the path the scan took before: the
    levels differentiated inside the loop through `corr_lookup`'s own rule.
    The predictions are equal, the gradients of the parameters and of the
    features (the pyramid's operands) within fp32 rounding, and the
    wrapper is entered only on the train path: a test_mode trace never
    reaches it. v3 with the small update block stands for the variants no
    cell runs (v2 and v4 scan as v1 does)."""
    import jax
    import jax.numpy as jnp

    from dexiraft_tpu import config
    from dexiraft_tpu.models import raft as raft_mod

    cfg = getattr(config, f"raft_{variant}")(
        **({"embed_dexined": True} if variant == "v3" else {}), **flags)
    model = raft_mod.RAFT(cfg)
    b, h, w, iters = 2, 8, 10, 2
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 12))

    def features():
        f = {"fmap": jax.random.normal(next(keys), (b, h, w, cfg.fnet_dim)),
             "ctx": jax.random.normal(
                 next(keys), (b, h, w, cfg.hidden_dim + cfg.context_dim))}
        if cfg.has_edge_stream:
            f["efmap"] = jax.random.normal(next(keys), f["fmap"].shape)
            f["ectx"] = jax.random.normal(next(keys), f["ctx"].shape)
        return f

    f1, f2 = features(), features()
    target = jax.random.normal(next(keys), (iters, b, 8 * h, 8 * w, 2))
    variables = jax.jit(lambda: model.init(
        jax.random.PRNGKey(0), None, mode="step", features1=f1, features2=f2,
        iters=1))()

    def loss(variables, f1, f2):
        preds = model.apply(variables, None, mode="step", features1=f1,
                            features2=f2, iters=iters, train=True)
        return jnp.sum(jnp.abs(preds - target)) / (64 * h * w), preds

    entered = []
    real = raft_mod.place_once

    def counted(*args, **kwargs):
        entered.append(kwargs["iters"])
        return real(*args, **kwargs)

    grad = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)
    monkeypatch.setattr(raft_mod, "place_once", counted)
    (value, preds), grads = jax.jit(grad)(variables, f1, f2)
    assert entered == [iters]
    jax.eval_shape(lambda v: model.apply(
        v, None, mode="step", features1=f1, features2=f2, iters=iters,
        test_mode=True), variables)
    assert entered == [iters]

    monkeypatch.setattr(
        raft_mod, "place_once",
        lambda loop, pyr, *args, iters: loop(pyr, None, *args)[0])
    (want_value, want_preds), want_grads = jax.jit(grad)(variables, f1, f2)

    assert value == want_value
    np.testing.assert_array_equal(np.asarray(preds), np.asarray(want_preds))
    leaves, want_leaves = jax.tree.leaves(grads), jax.tree.leaves(want_grads)
    assert len(leaves) == len(want_leaves) > 10
    for tree, want_tree in zip(grads, want_grads):  # parameters, features
        top = max(float(jnp.abs(x).max()) for x in jax.tree.leaves(want_tree))
        assert top > 1e-4
        for got, want in zip(jax.tree.leaves(tree),
                             jax.tree.leaves(want_tree)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=0, atol=2e-5 * top)


def test_raft_train_path_wraps_every_kernel_call_on_a_data_mesh(monkeypatch):
    """`v5-train-chairs-dp4`'s condition at toy size: the model's gradient
    traced with a batch split over four devices. A kernel call reads its
    mesh from its operand's type, and the stack of window cotangents, the
    cotangent of zeros made where no mesh is in sight, carries none:
    `place_once` hands the level's mesh on. Every Pallas call of the traced
    gradient, the two a level after the loop among them, sits inside a
    `shard_map` (left bare, the chip's compiler refuses it)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding

    from dexiraft_tpu.config import raft_v1
    from dexiraft_tpu.models.raft import RAFT
    from dexiraft_tpu.ops import corr as corr_mod
    from dexiraft_tpu.parallel import layout

    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs 4 (virtual) devices")
    monkeypatch.setattr(corr_mod, "_kernel_interpret", lambda: True)
    cfg = raft_v1(small=True, remat=True)
    model = RAFT(cfg)
    b, h, w, iters = 4, 8, 10, 2
    mesh = Mesh(np.array(devices[:4]), (layout.LAYOUT.data_axis,))
    data = NamedSharding(mesh, layout.LAYOUT.batch())

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=data)

    f = {"fmap": sds(b, h, w, cfg.fnet_dim),
         "ctx": sds(b, h, w, cfg.hidden_dim + cfg.context_dim)}
    variables = jax.eval_shape(lambda f: model.init(
        jax.random.PRNGKey(0), None, mode="step", features1=f, features2=f,
        iters=1), jax.tree.map(lambda x: jnp.zeros(x.shape), f))
    variables = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=layout.replicated_sharding(mesh)),
        variables)

    def loss(variables, f1, f2):
        return jnp.sum(model.apply(variables, None, mode="step", features1=f1,
                                   features2=f2, iters=iters, train=True))

    calls = []

    def walk(jaxpr, wrapped):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                calls.append((eqn.params["name"], wrapped))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, wrapped or eqn.primitive.name == "shard_map")

    walk(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(variables, f, f).jaxpr,
         False)
    names = {name for name, _ in calls}
    assert {"corr_window_align", "corr_window_place",
            "corr_window_place_sum"} <= names, names
    bare = [name for name, wrapped in calls if not wrapped]
    assert not bare, bare
