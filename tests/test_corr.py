"""Parity tests for the all-pairs correlation volume against the reference
CorrBlock semantics (core/corr.py:12-60), re-implemented in torch
(tests/_corr_reference.py). The oracle cases are in
tests/test_corr_oracle.py and `place_once` in
tests/test_corr_place_once.py.
"""

import numpy as np
import pytest

from _corr_reference import (TorchCorrBlock, _probe_coords,
                             build_corr_pyramid, torch)
from dexiraft_tpu.ops import corr_lookup


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

