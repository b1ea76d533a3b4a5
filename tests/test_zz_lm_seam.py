"""The seam between a model family and the train step (train/family.py),
from both sides.

RAFT's side: at the small size, one `make_train_step(cfg, tc)` step gives
the loss, the gradients' norm and the new state of `jax.value_and_grad`
over `RAFT.apply` + `sequence_loss` followed by `tx.update`, written out
here: the seam moved code and no arithmetic. The language model's side:
the same step trains it, and nothing of it is imported unless it runs.
Behind the seam the stack reads what a configuration answers
(`config.DecoderConfig`) and never its class: an architecture defined in
this file alone trains, and the sources are held to it.
"""

import ast
import dataclasses
import os
import os.path as osp
import subprocess
import sys
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dexiraft_tpu.config import (LM_VARIANTS, DecoderConfig, TrainConfig,
                                 raft_v1)
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


@dataclasses.dataclass(frozen=True)
class _HybridConfig(DecoderConfig):
    """A decoder no published class is: convolution layers and windowed
    grouped-query attention layers in one stack, a dense layer and then
    experts without a shared one, the head tied to the embedding."""

    vocab_size: int = 256
    hidden_size: int = 64
    num_hidden_layers: int = 4
    first_k_dense_replace: int = 1
    intermediate_size: int = 96
    moe_intermediate_size: int = 32
    n_routed_experts: int = 8
    num_experts_per_tok: int = 2
    head_dim: int = 8
    sliding_window: int = 24
    conv_L_cache: int = 3
    rope_theta: float = 1e4
    rms_norm_eps: float = 1e-5
    init_std: float = 0.02
    seq_len: int = 128
    heads_held: Tuple[int, int] = (0, 8)
    kv_heads_held: Tuple[int, int] = (0, 4)
    experts_held: Tuple[int, int] = (0, 8)
    mixed_precision: bool = False
    remat: bool = False
    attn_block: int = 32
    moe_chunk: Optional[int] = 64

    model_type = "hybrid"
    tie_embedding = True

    def mixer(self, i):
        return "gqa" if i % 2 else "conv"

    def layer_window(self, i):
        return self.sliding_window if i % 2 else None


def test_an_architecture_of_this_file_alone_trains_a_step():
    from dexiraft_tpu.models.lm import moe
    from dexiraft_tpu.models.lm.model import COUNTERS
    from dexiraft_tpu.train.family import family_of

    cfg = _HybridConfig()
    tc = TrainConfig(batch_size=2, num_steps=50, lr=3e-3, precision="bf16",
                     remat="per_iter")
    assert type(family_of(cfg, tc)).__name__ == "LMFamily"
    state = create_state(jax.random.PRNGKey(0), cfg, tc)
    assert "head" not in state.params
    layers = [state.params[f"layers_{i}"] for i in range(4)]
    assert [sorted(set(p) & {"attn", "conv", "mlp", "moe"}) for p in layers] \
        == [["conv", "mlp"], ["attn", "moe"], ["conv", "moe"], ["attn", "moe"]]
    assert "wg" not in layers[1]["attn"] and "shared" not in layers[1]["moe"]
    _, metrics = make_train_step(cfg, tc)(state, packed_batch(cfg))
    assert np.isfinite(float(metrics["loss"]))
    assert bool(metrics["state_finite"])
    # the counters its kinds declare for such layers, and no other
    assert set(metrics) & set(COUNTERS) == set(moe.COUNTERS) | {
        "conv_taps_masked", "attn_block_pairs_visited_window",
        "attn_block_pairs_causal"}
    assert int(metrics["moe_dropped_slots"]) == 0
    # rows of 128 are one block; two rows, two windowed layers
    assert int(metrics["attn_block_pairs_visited_window"]) == 2 * 2
    # per row and layer: documents start at 0, 50 and 90, two taps back
    assert int(metrics["conv_taps_masked"]) == 2 * 2 * 3 * (1 + 2)


@pytest.mark.parametrize("variant", sorted(LM_VARIANTS))
def test_every_variant_answers_the_whole_vocabulary(variant):
    from dexiraft_tpu.interop.lm_reference import _ARCHS
    from dexiraft_tpu.models.lm.attention import MIXERS

    cfg = LM_VARIANTS[variant]()
    assert isinstance(cfg, DecoderConfig)
    for name in (n for n in vars(DecoderConfig) if not n.startswith("_")):
        answer = getattr(cfg, name)
        if callable(answer):
            answer = [answer(i) for i in range(cfg.num_hidden_layers)]
        assert answer is not None, name
    assert cfg.model_type in _ARCHS
    assert {cfg.mixer(i) for i in range(cfg.num_hidden_layers)} <= set(MIXERS)
    assert set(getattr(cfg, "layer_types", ())) <= set(cfg.layer_kinds)
    assert cfg.first_k_dense_replace <= cfg.num_hidden_layers
    assert cfg.qk_head_dim > 0 and cfg.v_head_dim > 0
    held = cfg.experts_held[1]
    assert (held > 0) == (cfg.first_k_dense_replace < cfg.num_hidden_layers)
    assert held <= cfg.n_routed_experts


def test_the_shared_modules_read_answers_and_never_a_class():
    """models/lm and train hold no `isinstance` of a configuration class
    but the two the seam picks by, and probe no configuration for a
    field by name."""
    root = osp.join(osp.dirname(osp.dirname(osp.abspath(__file__))),
                    "dexiraft_tpu")
    found = []
    for folder in ("models/lm", "train"):
        for path in sorted(osp.join(root, folder, f) for f in
                           os.listdir(osp.join(root, folder))
                           if f.endswith(".py")):
            with open(path) as f:
                tree = ast.parse(f.read())
            classes = {a.asname or a.name for node in ast.walk(tree)
                       if isinstance(node, ast.ImportFrom)
                       and node.module == "dexiraft_tpu.config"
                       for a in node.names} - {"DecoderConfig", "RAFTConfig"}
            for node in ast.walk(tree):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Name)):
                    continue
                where = f"{osp.relpath(path, root)}:{node.lineno}"
                if node.func.id == "isinstance" and classes & {
                        n.id for n in ast.walk(node.args[1])
                        if isinstance(n, ast.Name)}:
                    found.append(where)
                if (node.func.id in ("getattr", "hasattr")
                        and ast.unparse(node.args[0]).endswith("cfg")
                        and isinstance(node.args[1], ast.Constant)):
                    found.append(where)
    assert not found, found
