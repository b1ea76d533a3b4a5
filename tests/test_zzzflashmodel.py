"""The whole model on the flash kernel (interpret mode), on the same
parameters as the unfused and the all-pairs paths: v1 forward, fused and
unfused, mixed precision, the gradients, and v5 as the eval cells run it.
The kernel alone: tests/test_zzzflashcorr.py, of which this was a part
until PR 43 (a file is one xdist worker's under `--dist loadfile`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _models import init_raft, jit_apply, raft_shapes
from dexiraft_tpu.ops import pallas_corr


@pytest.fixture(autouse=True)
def _small_flash_blocks(monkeypatch):
    """As in tests/test_zzzflashcorr.py: tiny fixtures want tiny blocks."""
    monkeypatch.setattr(pallas_corr, "_FLASH_PIXEL_BLOCK", 16)
    monkeypatch.setattr(pallas_corr, "_FLASH_ROWS", 2)


def _assert_flash_fused_matches_allpairs(make, mixed, variables, im1, im2):
    """The whole model as the eval cells run it (flash + fused, which
    `auto` resolves to on a TPU) against allpairs on the same
    parameters."""
    from dexiraft_tpu.models.raft import RAFT

    def flow(**corr):
        cfg = make(small=True, mixed_precision=mixed, **corr)
        return jit_apply(RAFT(cfg))(variables, im1, im2, iters=2)

    ref = flow()
    out = flow(corr_impl="flash", fused_update=True)
    scale = float(jnp.abs(ref).max())
    assert out.shape == ref.shape and scale > 1.0  # px: a flow to compare
    # fp32: reassociation noise. bf16 compute: a last-bit difference in a
    # window feature can round an activation the other way (measured
    # 0.6-0.8 % of the largest flow after two iterations)
    tol = 0.02 * scale if mixed else 1e-4
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=tol)


class TestFlashModel:
    """Whole-model flash vs the unfused path, SAME parameters — the
    checkpoint-interchange contract of FusedCorrEncoder extends to the
    flash kernel unchanged."""

    @pytest.fixture(scope="class")
    def fixture(self):
        from dexiraft_tpu.config import raft_v1

        im1 = jax.random.uniform(jax.random.PRNGKey(1), (1, 32, 32, 3),
                                 jnp.float32, 0, 255)
        im2 = jax.random.uniform(jax.random.PRNGKey(2), (1, 32, 32, 3),
                                 jnp.float32, 0, 255)
        model, variables = init_raft(raft_v1(small=True, corr_impl="local"),
                                     32, 32)
        ref = jit_apply(model)(variables, im1, im2, iters=2)
        return im1, im2, variables, ref

    def test_param_tree_identical(self, fixture, monkeypatch):
        from dexiraft_tpu.config import raft_v1

        monkeypatch.setenv("DEXIRAFT_PALLAS_INTERPRET", "1")
        _, _, variables, _ = fixture
        cfg_f = raft_v1(small=True, corr_impl="flash", fused_update=True)
        v_f = raft_shapes(cfg_f, 32, 32)
        assert (jax.tree_util.tree_structure(v_f)
                == jax.tree_util.tree_structure(variables))
        assert (jax.tree_util.tree_map(lambda x: x.shape, v_f)
                == jax.tree_util.tree_map(lambda x: x.shape, variables))

    def test_flash_fused_matches_unfused_same_params(self, fixture,
                                                     monkeypatch):
        from dexiraft_tpu.config import raft_v1
        from dexiraft_tpu.models.raft import RAFT

        monkeypatch.setenv("DEXIRAFT_PALLAS_INTERPRET", "1")
        im1, im2, variables, ref = fixture
        cfg_f = raft_v1(small=True, corr_impl="flash", fused_update=True)
        out = jit_apply(RAFT(cfg_f))(variables, im1, im2, iters=2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_flash_unfused_lookup_matches(self, fixture, monkeypatch):
        from dexiraft_tpu.config import raft_v1
        from dexiraft_tpu.models.raft import RAFT

        monkeypatch.setenv("DEXIRAFT_PALLAS_INTERPRET", "1")
        im1, im2, variables, ref = fixture
        cfg_u = raft_v1(small=True, corr_impl="flash")
        out = jit_apply(RAFT(cfg_u))(variables, im1, im2, iters=2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_flash_fused_matches_allpairs_mixed_precision(self, fixture,
                                                          monkeypatch):
        from dexiraft_tpu.config import raft_v1

        monkeypatch.setenv("DEXIRAFT_PALLAS_INTERPRET", "1")
        im1, im2, variables, _ = fixture
        _assert_flash_fused_matches_allpairs(raft_v1, True, variables,
                                             im1, im2)

    def test_flash_trains(self, fixture, monkeypatch):
        """flash is trainable (what licenses train_cli --corr_impl
        flash): whole-model param grads through the scanned fused step
        match the unfused path's grads — the VJP recomputes through
        fused_reference, so this is the same backward graph."""
        from dexiraft_tpu.config import raft_v1
        from dexiraft_tpu.models.raft import RAFT

        monkeypatch.setenv("DEXIRAFT_PALLAS_INTERPRET", "1")
        im1, im2, variables, _ = fixture

        def loss(cfg):
            def f(params):
                out = RAFT(cfg).apply(
                    {**variables, "params": params}, im1, im2, iters=1,
                    train=False)
                return jnp.mean(out ** 2)
            return f

        g_flash = jax.jit(jax.grad(loss(raft_v1(
            small=True, corr_impl="flash", fused_update=True))))(
            variables["params"])
        g_ref = jax.jit(jax.grad(loss(raft_v1(
            small=True, corr_impl="local"))))(variables["params"])
        flat_f = jax.tree_util.tree_leaves(g_flash)
        flat_r = jax.tree_util.tree_leaves(g_ref)
        for a, b in zip(flat_f, flat_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-3)
        # and they are not trivially zero
        assert max(float(jnp.abs(a).max()) for a in flat_f) > 0


class TestEvalCellModelV5:
    """v5 small: the dual stream's 2B-batch pyramid through the fused
    step, behind the embedded DexiNed."""

    @pytest.fixture(scope="class")
    def fixture(self):
        from dexiraft_tpu.config import raft_v5

        im1 = jax.random.uniform(jax.random.PRNGKey(1), (1, 32, 32, 3),
                                 jnp.float32, 0, 255)
        im2 = jax.random.uniform(jax.random.PRNGKey(2), (1, 32, 32, 3),
                                 jnp.float32, 0, 255)
        _, variables = init_raft(raft_v5(small=True), 32, 32)
        return im1, im2, variables

    @pytest.mark.parametrize("mixed", [False, True])
    def test_flash_fused_matches_allpairs(self, fixture, mixed, monkeypatch):
        from dexiraft_tpu.config import raft_v5

        monkeypatch.setenv("DEXIRAFT_PALLAS_INTERPRET", "1")
        im1, im2, variables = fixture
        _assert_flash_fused_matches_allpairs(raft_v5, mixed, variables,
                                             im1, im2)
