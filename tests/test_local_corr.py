"""Local (on-demand) correlation vs the materialized all-pairs path.

At level 0 the two formulations compute the same quantity, so they must
agree to float tolerance for arbitrary fractional coords. Higher levels
legitimately differ (pooled correlation vs pooled fmap2 — the same
approximation the reference's AlternateCorrBlock makes, core/corr.py:63-91).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dexiraft_tpu.ops.corr import build_corr_pyramid
from dexiraft_tpu.ops.local_corr import build_local_corr, local_corr_level


def _fmaps(key, b=2, h=12, w=16, c=32):
    k1, k2 = jax.random.split(key)
    f1 = jax.random.normal(k1, (b, h, w, c), jnp.float32)
    f2 = jax.random.normal(k2, (b, h, w, c), jnp.float32)
    return f1, f2


def _coords(key, b, h, w, lo=-2.0, hi=2.0):
    ys, xs = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                          jnp.arange(w, dtype=jnp.float32), indexing="ij")
    base = jnp.stack([xs, ys], axis=-1)[None].repeat(b, 0)
    return base + jax.random.uniform(key, (b, h, w, 2), jnp.float32, lo, hi)


class TestLevel0Parity:
    @pytest.mark.parametrize("radius", [3, 4])
    def test_matches_allpairs(self, radius):
        f1, f2 = _fmaps(jax.random.PRNGKey(0))
        b, h, w, _ = f1.shape
        coords = _coords(jax.random.PRNGKey(1), b, h, w)

        allpairs = build_corr_pyramid(f1, f2, num_levels=1, radius=radius)
        local = build_local_corr(f1, f2, num_levels=1, radius=radius)
        np.testing.assert_allclose(
            np.asarray(allpairs(coords)), np.asarray(local(coords)),
            rtol=1e-4, atol=1e-4)

    def test_far_out_of_frame_is_zero(self):
        f1, f2 = _fmaps(jax.random.PRNGKey(2))
        b, h, w, _ = f1.shape
        coords = jnp.full((b, h, w, 2), 1000.0)
        out = local_corr_level(f1, f2, coords, radius=4)
        np.testing.assert_allclose(np.asarray(out), 0.0)

    def test_row_chunking_equivalent(self):
        f1, f2 = _fmaps(jax.random.PRNGKey(3), h=13)  # odd H: chunk padding
        b, h, w, _ = f1.shape
        coords = _coords(jax.random.PRNGKey(4), b, h, w)
        full = local_corr_level(f1, f2, coords, radius=4)
        chunked = local_corr_level(f1, f2, coords, radius=4, row_chunk=4)
        np.testing.assert_allclose(np.asarray(full), np.asarray(chunked),
                                   rtol=1e-5, atol=1e-5)


class TestPyramid:
    def test_multilevel_shapes(self):
        f1, f2 = _fmaps(jax.random.PRNGKey(5), h=16, w=16)
        b, h, w, _ = f1.shape
        coords = _coords(jax.random.PRNGKey(6), b, h, w)
        local = build_local_corr(f1, f2, num_levels=4, radius=4)
        out = local(coords)
        assert out.shape == (b, h, w, 4 * 81)
        assert out.dtype == jnp.float32

    def test_integer_coords_match_direct_dot(self):
        """At integer coords with zero offset the (r, r) window center is
        exactly <f1[p], f2[p]> / sqrt(C)."""
        f1, f2 = _fmaps(jax.random.PRNGKey(7))
        b, h, w, c = f1.shape
        ys, xs = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                              jnp.arange(w, dtype=jnp.float32), indexing="ij")
        coords = jnp.stack([xs, ys], axis=-1)[None].repeat(b, 0)
        r = 4
        out = local_corr_level(f1, f2, coords, radius=r)
        center = out.reshape(b, h, w, 2 * r + 1, 2 * r + 1)[:, :, :, r, r]
        expect = jnp.einsum("bhwc,bhwc->bhw", f1, f2) / jnp.sqrt(jnp.float32(c))
        np.testing.assert_allclose(np.asarray(center), np.asarray(expect),
                                   rtol=1e-4, atol=1e-4)


class TestGradients:
    def test_grads_flow_to_fmaps_not_coords(self):
        f1, f2 = _fmaps(jax.random.PRNGKey(8), b=1, h=6, w=6, c=8)
        coords = _coords(jax.random.PRNGKey(9), 1, 6, 6)

        def loss(f1_, f2_, coords_):
            return jnp.sum(local_corr_level(f1_, f2_, coords_, radius=2) ** 2)

        g1, g2, gc = jax.grad(loss, argnums=(0, 1, 2))(f1, f2, coords)
        assert float(jnp.abs(g1).max()) > 0
        assert float(jnp.abs(g2).max()) > 0
        np.testing.assert_allclose(np.asarray(gc), 0.0)  # CUDA-kernel semantics


class TestRAFTIntegration:
    def test_raft_local_forward(self):
        from _models import init_raft, jit_apply
        from dexiraft_tpu.config import raft_v1

        model, variables = init_raft(raft_v1(small=True, corr_impl="local"))
        rng = jax.random.PRNGKey(1)
        im1 = jax.random.uniform(rng, (1, 64, 64, 3), jnp.float32, 0, 255)
        preds = jit_apply(model)(variables, im1, im1, iters=2)
        assert preds.shape == (2, 1, 64, 64, 2)
        assert np.isfinite(np.asarray(preds)).all()
