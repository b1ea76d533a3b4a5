"""Training layer: schedule parity vs torch, step convergence, DP sharding,
checkpoint round-trip and curriculum partial restore."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dexiraft_tpu.config import RAFTConfig, TrainConfig, raft_v1
from dexiraft_tpu.parallel import make_mesh, shard_batch
from dexiraft_tpu.train import create_state, make_train_step, onecycle_lr
from dexiraft_tpu.train.state import param_count

SMALL = raft_v1(small=True)
TC = TrainConfig(num_steps=200, batch_size=2, iters=2, image_size=(64, 64), lr=1e-4)


@functools.lru_cache(maxsize=None)
def plain_step():
    """SMALL's step under TC, compiled once for the tests that take it."""
    return make_train_step(SMALL, TC)


@functools.lru_cache(maxsize=None)
def _kept_state(tc):
    return create_state(jax.random.key(0), SMALL, tc)


def fresh_state(tc=TC):
    """`create_state(key(0), SMALL, tc)`, one init a configuration: a
    copy, since a step donates the state it is given."""
    return jax.tree.map(jnp.copy, _kept_state(tc))


def synthetic_batch(rng, batch=2, size=(64, 64)):
    """Pair of frames related by a constant 2px shift, so flow is learnable."""
    h, w = size
    base = rng.uniform(0, 255, (batch, h + 8, w + 8, 3)).astype(np.float32)
    img1 = base[:, 4 : 4 + h, 4 : 4 + w]
    img2 = base[:, 4 : 4 + h, 2 : 2 + w]  # shift x by +2
    flow = np.zeros((batch, h, w, 2), np.float32)
    flow[..., 0] = 2.0
    valid = np.ones((batch, h, w), np.float32)
    return {
        "image1": jnp.asarray(img1),
        "image2": jnp.asarray(img2),
        "flow": jnp.asarray(flow),
        "valid": jnp.asarray(valid),
    }


class TestOneCycle:
    def test_matches_torch_onecycle_linear(self):
        torch = pytest.importorskip("torch")
        total, max_lr = 1000, 4e-4
        p = torch.nn.Parameter(torch.zeros(1))
        opt = torch.optim.AdamW([p], lr=max_lr)
        sched = torch.optim.lr_scheduler.OneCycleLR(
            opt, max_lr, total_steps=total, pct_start=0.05,
            cycle_momentum=False, anneal_strategy="linear",
        )
        ours = onecycle_lr(max_lr, total)
        torch_lrs = []
        for _ in range(total):
            torch_lrs.append(opt.param_groups[0]["lr"])
            opt.step()
            sched.step()
        got = np.array([float(ours(s)) for s in range(total)])
        np.testing.assert_allclose(got, np.array(torch_lrs), rtol=1e-5, atol=1e-10)

    def test_clamps_past_total(self):
        s = onecycle_lr(1e-3, 100)
        assert float(s(150)) == pytest.approx(float(s(99)))


class TestTrainStep:
    def test_loss_decreases(self):
        state = fresh_state()
        step = plain_step()
        batch = synthetic_batch(np.random.default_rng(0))
        losses = []
        for _ in range(8):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]
        assert int(state.step) == 8

    def test_metrics_keys_and_lr(self):
        state = fresh_state()
        step = plain_step()
        _, metrics = step(state, synthetic_batch(np.random.default_rng(1)))
        for k in ("epe", "1px", "3px", "5px", "loss", "lr"):
            assert k in metrics
        assert float(metrics["lr"]) == pytest.approx(float(onecycle_lr(TC.lr, TC.num_steps + 100)(0)))

    def test_param_count_nonzero(self):
        state = fresh_state()
        assert param_count(state.params) > 900_000  # small RAFT ~1M params


class TestShardedStep:
    def test_dp_mesh_matches_single_device(self):
        mesh = make_mesh()
        assert mesh.devices.size == 8, "conftest must provide 8 virtual devices"
        tc = TrainConfig(num_steps=200, batch_size=8, iters=2, image_size=(64, 64), lr=1e-4)
        batch = synthetic_batch(np.random.default_rng(2), batch=8)

        state_a = fresh_state(tc)
        step_single = make_train_step(SMALL, tc)
        state_a, m_single = step_single(state_a, batch)

        state_b = fresh_state(tc)
        step_dp = make_train_step(SMALL, tc, mesh=mesh)
        state_b, m_dp = step_dp(state_b, shard_batch(batch, mesh))

        assert np.isfinite(float(m_dp["loss"]))
        np.testing.assert_allclose(
            float(m_dp["loss"]), float(m_single["loss"]), rtol=1e-4
        )
        # parameters after one step agree (grad allreduce == full-batch grad)
        la = jax.tree.leaves(state_a.params)
        lb = jax.tree.leaves(state_b.params)
        for a, b in zip(la, lb):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-3, atol=3e-5)


class TestCheckpoint:
    def test_roundtrip_and_partial_restore(self, tmp_path):
        from dexiraft_tpu.train.checkpoint import (
            restore_checkpoint,
            restore_params_into,
            save_checkpoint,
        )

        state = fresh_state()
        step = plain_step()
        state, _ = step(state, synthetic_batch(np.random.default_rng(3)))
        save_checkpoint(str(tmp_path / "ck"), state)

        template = create_state(jax.random.key(1), SMALL, TC)
        restored = restore_checkpoint(str(tmp_path / "ck"), template)
        assert int(restored.step) == 1
        for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(restored.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        # strict=False semantics: graft into a DIFFERENT architecture
        big = RAFTConfig(variant="raft", small=False)
        fresh = create_state(jax.random.key(2), big, TC)
        merged, skipped = restore_params_into(fresh.params, restored.params)
        assert len(skipped) > 0  # architectures differ
        assert jax.tree_util.tree_structure(merged) == jax.tree_util.tree_structure(fresh.params)


class TestStateFiniteSignal:
    """The checkpoint gate's poison detector (train.step.all_finite):
    value_and_grad computes the loss from PRE-update params, so a step
    whose UPDATE introduces non-finite values passes a loss-only guard
    while the checkpoint would save the poisoned post-update state.
    state_finite is computed on the new state inside the step."""

    def test_healthy_step_reports_finite(self):
        state = fresh_state()
        step = plain_step()
        _, metrics = step(state, synthetic_batch(np.random.default_rng(0)))
        assert "state_finite" in metrics
        assert bool(metrics["state_finite"])

    def test_poisoned_update_flags_despite_finite_loss(self):
        """Inf in the optimizer's moments: the loss (pre-update params)
        stays finite, but the update poisons params — exactly the blind
        spot a loss-only guard has."""
        state = fresh_state()
        step = plain_step()
        state, _ = step(state, synthetic_batch(np.random.default_rng(0)))

        poisoned_opt = jax.tree.map(
            lambda x: (jnp.full_like(x, jnp.inf)
                       if jnp.issubdtype(x.dtype, jnp.inexact) else x),
            state.opt_state)
        state = state.replace(opt_state=poisoned_opt)
        new_state, metrics = step(state,
                                  synthetic_batch(np.random.default_rng(1)))
        assert np.isfinite(float(metrics["loss"]))  # pre-update loss: fine
        assert not bool(metrics["state_finite"])    # post-update: poisoned
        # and the poison is real, not a false alarm
        leaves = jax.tree.leaves(new_state.params)
        assert not all(np.isfinite(np.asarray(l)).all() for l in leaves)

    def test_all_finite_ignores_integer_leaves(self):
        from dexiraft_tpu.train.step import all_finite

        tree = {"count": jnp.int32(3), "x": jnp.ones((2, 2))}
        assert bool(all_finite(tree))
        tree["x"] = tree["x"].at[0, 0].set(jnp.nan)
        assert not bool(all_finite(tree))


class TestEdgeSumFusion:
    def test_step_runs_and_differs_from_plain(self):
        """alt/train_1.py:173-176 capability: per-iter predictions of the
        image pair and the edge-image pair are summed before the loss."""
        import dataclasses

        tc = dataclasses.replace(TC, edge_sum_fusion=True)
        rng = np.random.default_rng(0)
        batch = synthetic_batch(rng)
        batch["edges1"] = batch["image1"] * 0.5
        batch["edges2"] = batch["image2"] * 0.5

        state = fresh_state(tc)
        step = make_train_step(SMALL, tc)
        state2, m = step(state, batch)
        assert np.isfinite(float(m["loss"]))

        _, m_plain = plain_step()(fresh_state(), {
            k: v for k, v in batch.items() if not k.startswith("edges")})
        # summed fusion must actually change the loss
        assert abs(float(m["loss"]) - float(m_plain["loss"])) > 1e-6

    def test_missing_edges_raises(self):
        import dataclasses

        tc = dataclasses.replace(TC, edge_sum_fusion=True)
        state = fresh_state(tc)
        step = make_train_step(SMALL, tc)
        with pytest.raises(ValueError, match="edge_sum_fusion"):
            step(state, synthetic_batch(np.random.default_rng(1)))
