"""Pallas local-corr kernel vs the XLA gather formulation.

Runs in interpreter mode so parity holds on the CPU test mesh; the same
kernel compiles for TPU (exercised by bench/eval on hardware).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dexiraft_tpu.ops.local_corr import local_corr_level
from dexiraft_tpu.ops.pallas_corr import pallas_local_corr_level


@pytest.fixture(autouse=True, params=["loop", "batched"])
def _kernel_variant(request, monkeypatch):
    """Every parity/grad case runs against BOTH kernel shapes (the
    per-pixel loop and the staged-patches batched reduce) — the variant
    is a trace-time env switch, ops/pallas_corr.py:_variant."""
    monkeypatch.setenv("DEXIRAFT_PALLAS_VARIANT", request.param)
    return request.param


def _setup(key, b=1, h=8, w=16, c=128, noise=3.0):
    k1, k2, k3 = jax.random.split(key, 3)
    f1 = jax.random.normal(k1, (b, h, w, c), jnp.float32)
    f2 = jax.random.normal(k2, (b, h, w, c), jnp.float32)
    ys, xs = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                          jnp.arange(w, dtype=jnp.float32), indexing="ij")
    base = jnp.stack([xs, ys], axis=-1)[None].repeat(b, 0)
    coords = base + jax.random.uniform(k3, (b, h, w, 2), jnp.float32,
                                       -noise, noise)
    return f1, f2, coords


@pytest.mark.parametrize("radius", [3, 4])
def test_parity_with_xla_gather(radius):
    f1, f2, coords = _setup(jax.random.PRNGKey(0))
    ref = local_corr_level(f1, f2, coords, radius)
    out = pallas_local_corr_level(f1, f2, coords, radius, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_boundary_windows_match():
    """Centers right at the frame edge exercise the clip+mask path."""
    f1, f2, _ = _setup(jax.random.PRNGKey(1))
    b, h, w, _ = f1.shape
    coords = jnp.stack(
        [jnp.full((b, h, w), -0.4), jnp.full((b, h, w), float(h) - 0.6)],
        axis=-1)
    ref = local_corr_level(f1, f2, coords, 4)
    out = pallas_local_corr_level(f1, f2, coords, 4, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_far_out_of_frame_zero():
    f1, f2, _ = _setup(jax.random.PRNGKey(2))
    b, h, w, _ = f1.shape
    for val in (-500.0, 500.0):
        coords = jnp.full((b, h, w, 2), val)
        out = pallas_local_corr_level(f1, f2, coords, 4, True)
        np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)


def test_nonsquare_level_shapes():
    """fmap2 at a coarser pyramid level than the query grid."""
    key = jax.random.PRNGKey(3)
    k1, k2, k3 = jax.random.split(key, 3)
    b, h, w, c = 1, 8, 8, 128
    f1 = jax.random.normal(k1, (b, h, w, c), jnp.float32)
    f2 = jax.random.normal(k2, (b, h // 2, w // 2, c), jnp.float32)
    coords = jax.random.uniform(k3, (b, h, w, 2), jnp.float32, 0.0, 4.0)
    ref = local_corr_level(f1, f2, coords, 3)
    out = pallas_local_corr_level(f1, f2, coords, 3, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_custom_vjp_grads():
    f1, f2, coords = _setup(jax.random.PRNGKey(4), h=4, w=8, c=128)

    def loss_pallas(a, b_, c_):
        return jnp.sum(pallas_local_corr_level(a, b_, c_, 2, True) ** 2)

    def loss_ref(a, b_, c_):
        return jnp.sum(local_corr_level(a, b_, c_, 2) ** 2)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(f1, f2, coords)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(f1, f2, coords)
    np.testing.assert_allclose(np.asarray(gp[0]), np.asarray(gr[0]),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(gp[1]), np.asarray(gr[1]),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(gp[2]), 0.0)


def test_pixel_block_override_identical(monkeypatch):
    # the tuning knob (DEXIRAFT_PALLAS_PIXEL_BLOCK) must only change
    # the grid partition, never the values
    monkeypatch.delenv("DEXIRAFT_PALLAS_PIXEL_BLOCK", raising=False)
    f1, f2, coords = _setup(jax.random.PRNGKey(2))
    ref = pallas_local_corr_level(f1, f2, coords, 4, True)
    monkeypatch.setenv("DEXIRAFT_PALLAS_PIXEL_BLOCK", "64")
    out = pallas_local_corr_level(f1, f2, coords, 4, True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
