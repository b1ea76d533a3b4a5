"""The seam between a model family and the train step (train/family.py),
from both sides.

RAFT's side: at the small size, one `make_train_step(cfg, tc)` step gives
the loss, the gradients' norm and the new state of `jax.value_and_grad`
over `RAFT.apply` + `sequence_loss` followed by `tx.update`, written out
here: the seam moved code and no arithmetic. The language model's side:
the same step trains it, and nothing of it is imported unless it runs.
"""

import dataclasses
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dexiraft_tpu.config import TrainConfig, raft_v1
from dexiraft_tpu.models.raft import RAFT
from dexiraft_tpu.ops.losses import sequence_loss
from dexiraft_tpu.train.state import create_state, make_optimizer_from
from dexiraft_tpu.train.step import make_train_step

from _lm_common import packed_batch, toy


def _raft_batch(b=2, h=64, w=96, seed=0):
    rng = np.random.default_rng(seed)
    return {"image1": jnp.asarray(rng.uniform(0, 255, (b, h, w, 3)), jnp.float32),
            "image2": jnp.asarray(rng.uniform(0, 255, (b, h, w, 3)), jnp.float32),
            "flow": jnp.asarray(rng.normal(0, 2, (b, h, w, 2)), jnp.float32),
            "valid": jnp.ones((b, h, w), jnp.float32)}


# (loss, share of elements off by 1e-6). fp32: the same
# arithmetic fused another way. bf16: two separately compiled programs
# round their bf16 activations at different fusion boundaries
@pytest.mark.parametrize("precision,loss_tol,off_share", [
    ("fp32", 1e-6, 1e-3), ("bf16", 2e-3, 5e-2)])
def test_raft_step_is_value_and_grad_then_tx_update_written_out(
        precision, loss_tol, off_share):
    cfg = raft_v1(small=True)
    tc = TrainConfig(batch_size=2, iters=2, num_steps=100, lr=1e-3,
                     precision=precision)
    state = create_state(jax.random.PRNGKey(3), cfg, tc)
    batch = _raft_batch()
    # the step donates its state: the hand-written side goes first
    model = RAFT(dataclasses.replace(cfg, mixed_precision=precision == "bf16"))
    tx = make_optimizer_from(tc)
    _, _, dropout_rng = jax.random.split(state.rng, 3)

    def loss_fn(params):
        flows, mutated = model.apply(
            {"params": params, "batch_stats": state.batch_stats},
            batch["image1"], batch["image2"], iters=tc.iters, train=True,
            freeze_bn=tc.freeze_bn, mutable=["batch_stats"],
            rngs={"dropout": dropout_rng})
        return sequence_loss(flows.astype(jnp.float32), batch["flow"],
                             batch["valid"], tc.gamma)[0]

    want_loss, grads = jax.jit(jax.value_and_grad(loss_fn))(state.params)
    updates, want_opt = jax.jit(tx.update)(grads, state.opt_state,
                                           state.params)
    want_params = jax.tree.map(lambda p, u: p + u, state.params, updates)
    want_params, want_opt = jax.device_get((want_params, want_opt))

    new_state, metrics = make_train_step(cfg, tc)(state, batch)
    assert float(metrics["loss"]) == pytest.approx(float(want_loss),
                                                   rel=loss_tol)
    assert "grad_norm" not in metrics  # RAFT reads nothing of its gradients
    assert int(new_state.step) == 1

    def tree_rel(got, want):
        g = np.concatenate([np.ravel(x) for x in jax.tree.leaves(got)])
        w = np.concatenate([np.ravel(x) for x in jax.tree.leaves(want)])
        return float(np.linalg.norm(g.astype(np.float64) - w)
                     / np.linalg.norm(w.astype(np.float64)))

    # the new parameters, element by element. The first AdamW step is
    # sign-like (+-lr0 = 4e-5 an element), so the few elements whose
    # gradient is rounding noise (a bias in front of a norm) may flip
    # with the fusion: at most 0.1 % of the tree may differ by 1e-6
    got = np.concatenate([np.ravel(x) for x in
                          jax.tree.leaves(jax.device_get(new_state.params))])
    want = np.concatenate([np.ravel(x) for x in jax.tree.leaves(want_params)])
    assert np.mean(np.abs(got - want) > 1e-6) < off_share
    assert np.max(np.abs(got - want)) < 2.1 * 4e-5
    # mu, nu: fp32 sums in another order
    assert tree_rel(new_state.opt_state, want_opt) < 200 * loss_tol


def test_the_same_step_trains_the_language_model():
    cfg = toy(experts_held=(0, 4), heads_held=(0, 2))
    tc = TrainConfig(batch_size=2, num_steps=50, lr=3e-3, precision="bf16",
                     remat="per_iter")
    state = create_state(jax.random.PRNGKey(0), cfg, tc)
    assert all(p.dtype == jnp.float32 for p in jax.tree.leaves(state.params))
    step = make_train_step(cfg, tc)
    batch = packed_batch(cfg)
    losses = []
    for _ in range(8):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.05, losses  # it learns the batch
    for key in ("moe_slots_held", "moe_load_max", "moe_load_mean",
                "moe_dropped_slots", "tokens_real", "grad_norm",
                "state_finite"):
        assert key in metrics, key
    assert int(metrics["moe_dropped_slots"]) == 0
    assert bool(metrics["state_finite"])
    # b is carried, fp32, and stays at zero
    for b in jax.tree.leaves(state.batch_stats):
        assert b.dtype == jnp.float32 and float(jnp.abs(b).max()) == 0.0


def test_accumulation_and_mesh_paths_take_token_batches():
    from dexiraft_tpu.parallel import layout

    cfg = toy(experts_held=(0, 4), heads_held=(0, 2))
    tc = TrainConfig(batch_size=4, num_steps=50, accum_steps=2)
    mesh = layout.make_train_mesh(2, devices=jax.devices()[:2])
    state = layout.shard_state(create_state(jax.random.PRNGKey(0), cfg, tc),
                               mesh)
    batch = layout.batch_putter(mesh)(
        jax.device_get(packed_batch(cfg, rows=4)))
    with mesh:
        state, metrics = make_train_step(cfg, tc, mesh=mesh)(state, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_the_language_model_refuses_what_is_not_its_own():
    cfg = toy()
    with pytest.raises(ValueError, match="dots_saveable"):
        make_train_step(cfg, TrainConfig(remat="dots_saveable"))
    with pytest.raises(ValueError, match="add_noise"):
        make_train_step(cfg, TrainConfig(add_noise=True))
    with pytest.raises(ValueError, match="halo"):
        make_train_step(cfg, TrainConfig(), compute_sharding="halo")


def test_nothing_of_the_language_model_is_imported_by_a_raft_path():
    code = (
        "import sys, dexiraft_tpu, dexiraft_tpu.train_cli, "
        "dexiraft_tpu.eval_cli, dexiraft_tpu.serve.engine\n"
        "from dexiraft_tpu.config import raft_v1, TrainConfig\n"
        "from dexiraft_tpu.train.step import make_train_step\n"
        "make_train_step(raft_v1(small=True), TrainConfig())\n"
        "bad = [m for m in sys.modules if m.startswith('dexiraft_tpu.models.lm')"
        " or m in ('dexiraft_tpu.ops.lm_attention', 'dexiraft_tpu.ops.grouped',"
        " 'dexiraft_tpu.data.tokens', 'dexiraft_tpu.interop.lm_reference')]\n"
        "assert not bad, bad\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=300)
