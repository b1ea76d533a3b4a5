"""Context parallelism over the correlation volume, on the 8-device CPU
mesh: shard_map row-sharded lookup parity, and the GSPMD spatially-sharded
train step matching the 1-D data-parallel step numerically."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dexiraft_tpu.config import TrainConfig, raft_v1
from dexiraft_tpu.ops.corr import build_corr_pyramid, corr_lookup
from dexiraft_tpu.ops.grid import coords_grid
from dexiraft_tpu.parallel.context import (
    context_parallel_corr,
    ring_corr_lookup,
)
from dexiraft_tpu.parallel.mesh import (
    make_mesh,
    make_mesh_2d,
    shard_batch,
    shard_batch_spatial,
)
from dexiraft_tpu.train.state import create_state
from dexiraft_tpu.train.step import make_train_step


def _fmaps(key, b=2, h=16, w=16, c=32):
    k1, k2, k3 = jax.random.split(key, 3)
    f1 = jax.random.normal(k1, (b, h, w, c), jnp.float32)
    f2 = jax.random.normal(k2, (b, h, w, c), jnp.float32)
    coords = coords_grid(b, h, w) + jax.random.uniform(
        k3, (b, h, w, 2), jnp.float32, -2.0, 2.0)
    return f1, f2, coords


class TestContextParallelCorr:
    def test_matches_unsharded(self):
        f1, f2, coords = _fmaps(jax.random.PRNGKey(0))
        mesh = make_mesh_2d(2, 4)
        out = jax.jit(lambda a, b, c: context_parallel_corr(
            a, b, c, mesh, num_levels=2, radius=3))(f1, f2, coords)
        pyr = build_corr_pyramid(f1, f2, num_levels=2, radius=3)
        ref = corr_lookup(pyr, coords)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_under_jit_with_sharded_inputs(self):
        f1, f2, coords = _fmaps(jax.random.PRNGKey(1))
        mesh = make_mesh_2d(1, 8)
        fn = jax.jit(lambda a, b, c: context_parallel_corr(
            a, b, c, mesh, num_levels=2, radius=3))
        out = fn(f1, f2, coords)
        pyr = build_corr_pyramid(f1, f2, num_levels=2, radius=3)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(corr_lookup(pyr, coords)),
                                   rtol=1e-4, atol=1e-4)


class TestRingCorrLookup:
    def test_matches_unsharded(self):
        """Ring-rotated target blocks (the ring-attention analog) must
        reproduce the unsharded lookup exactly: hat-stencil supports
        partition across blocks."""
        f1, f2, coords = _fmaps(jax.random.PRNGKey(2))
        mesh = make_mesh_2d(2, 4)  # H=16 over 4 ring chips -> blocks of 4
        out = jax.jit(lambda a, b, c: ring_corr_lookup(
            a, b, c, mesh, num_levels=3, radius=3))(f1, f2, coords)
        pyr = build_corr_pyramid(f1, f2, num_levels=3, radius=3)
        ref = corr_lookup(pyr, coords)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_full_ring_under_jit(self):
        f1, f2, coords = _fmaps(jax.random.PRNGKey(3), h=32)
        mesh = make_mesh_2d(1, 8)  # blocks of 4 rows over an 8-ring
        fn = jax.jit(lambda a, b, c: ring_corr_lookup(
            a, b, c, mesh, num_levels=2, radius=4))
        out = fn(f1, f2, coords)
        pyr = build_corr_pyramid(f1, f2, num_levels=2, radius=4)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(corr_lookup(pyr, coords)),
                                   rtol=1e-4, atol=1e-4)

    def test_alignment_guard(self):
        import pytest

        f1, f2, coords = _fmaps(jax.random.PRNGKey(4), h=12)
        mesh = make_mesh_2d(2, 4)  # blocks of 3 rows: not 2^2-aligned
        with pytest.raises(ValueError, match="divisible"):
            ring_corr_lookup(f1, f2, coords, mesh, num_levels=3, radius=3)


class TestSpatiallyShardedTrainStep:
    @pytest.mark.skipif(
        jax.default_backend() == "cpu",
        reason="GSPMD miscompiles spatially-partitioned convolutions on the "
               "CPU backend: the fence train step on a mesh with a 'seq' "
               "axis computes a wrong loss (same class as the feature-dim "
               "conv miscompile in docs/perf.md; see docs/parallel.md). "
               "compute_sharding='halo' sidesteps GSPMD conv partitioning "
               "entirely and is parity-pinned in tests/test_zzzhalo.py.")
    def test_2d_mesh_matches_1d(self):
        cfg = raft_v1(small=True)
        tc = TrainConfig(name="cp", num_steps=10, batch_size=4,
                         image_size=(64, 64), iters=2)
        rng = np.random.default_rng(0)
        batch = {
            "image1": rng.uniform(0, 255, (4, 64, 64, 3)).astype(np.float32),
            "image2": rng.uniform(0, 255, (4, 64, 64, 3)).astype(np.float32),
            "flow": rng.normal(0, 1, (4, 64, 64, 2)).astype(np.float32),
            "valid": np.ones((4, 64, 64), np.float32),
        }

        losses = {}
        for name, mesh, shard in [
            ("dp", make_mesh(jax.devices()[:4]), shard_batch),
            ("dp_sp", make_mesh_2d(4, 2), shard_batch_spatial),
        ]:
            state = create_state(jax.random.PRNGKey(0), cfg, tc)
            step = make_train_step(cfg, tc, mesh=mesh)
            with mesh:
                state, metrics = step(state, shard(batch, mesh))
                losses[name] = float(metrics["loss"])
                assert np.isfinite(losses[name])

        # GSPMD partitioning must not change the math
        np.testing.assert_allclose(losses["dp_sp"], losses["dp"],
                                   rtol=2e-4, atol=2e-4)


class TestSpatiallyShardedEval:
    @pytest.mark.slow
    def test_sharded_eval_matches_unsharded(self):
        """Long-context inference: the test-mode forward with inputs
        sharded over a (data, seq) mesh — batch over 'data', image rows
        over 'seq', so each chip holds a row-block of the quadratic
        volume — must reproduce the unsharded flow exactly (jit
        propagates input shardings; make_eval_step docstring)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dexiraft_tpu.train.step import make_eval_step

        cfg = raft_v1(small=True)
        tc = TrainConfig(name="spe", num_steps=1, batch_size=2,
                         image_size=(64, 64), iters=2)
        state = create_state(jax.random.PRNGKey(0), cfg, tc)
        step = make_eval_step(cfg, iters=2)

        rng = np.random.default_rng(5)
        im1 = jnp.asarray(rng.uniform(0, 255, (2, 64, 64, 3)), jnp.float32)
        im2 = jnp.asarray(rng.uniform(0, 255, (2, 64, 64, 3)), jnp.float32)

        low_ref, up_ref = step(state.variables, im1, im2)

        mesh = make_mesh_2d(2, 2)
        sp = NamedSharding(mesh, P("data", "seq", None, None))
        with mesh:
            low_sh, up_sh = step(state.variables,
                                 jax.device_put(im1, sp),
                                 jax.device_put(im2, sp))
        np.testing.assert_allclose(np.asarray(up_sh), np.asarray(up_ref),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(low_sh), np.asarray(low_ref),
                                   rtol=2e-4, atol=2e-4)
