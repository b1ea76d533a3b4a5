"""The language models against their plain reference
(dexiraft_tpu/interop/lm_reference.py) at the toy size on the CPU, on
seeded random weights: logits, loss and every gradient leaf, for every
architecture (`_lm_common.ARCHS`; an lfm2 stack's `embed` leaf is the
tied sum of the gather's gradient and the head's).

Tolerances. Under the fp32 policy both sides are float32 arithmetic of
the same mathematics in another order (blocks of attention against full
matrices, sorted grouped products against every expert on every token,
layers recomputed): 2e-5 relative in the 2-norm is 20x the 1e-6 seen.
Under the bf16 policy activations and the weights' copies carry 8 bits
of mantissa (2^-8 = 0.4 % an operation) through three layers of width
64, and a rounding that flips one of a token's two experts moves a
leaf of a few thousand entries by whole percents: seen 0.04-0.21 a leaf
and 2e-4 on the loss; the limits are 0.35 and 2e-3. The trinity toy
carries them through five layers, each of which norms what it adds
(gains and a router read 0.26-0.35): its limit is 0.45. The evabyte toy
has no router to flip: its leaves read 0.006-0.049 (a phi of 16
entries the largest) and its loss 1.5e-4; its limit is 0.15. The lfm2
toy has four expert layers behind its dense one: its routers read
0.25-0.43 over three seeds (a token's second expert of 16 flipped) and
every other leaf under 0.32; its limit is 0.55. A bf16 run that
dropped a term (a missing head, expert or rope half) is off by 0.5-1.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dexiraft_tpu.interop import lm_reference as ref
from dexiraft_tpu.models.lm import LM

from _lm_common import (ARCHS, SHARES, packed_batch,
                        reference_loss_and_grads, rel, seeded, toy)
from _models import as_one_program


@functools.lru_cache(maxsize=None)
def _fp32(arch):
    """The system's and the reference's loss and gradients, once an
    architecture a process."""
    cfg = toy(arch, **SHARES[arch])
    family, params, stats = seeded(cfg, remat="per_iter")
    batch = packed_batch(cfg)
    (loss, (metrics, _)), grads = jax.jit(jax.value_and_grad(
        family.loss_fn, has_aux=True))(params, stats, batch,
                                       jax.random.PRNGKey(0))
    ref_loss, ref_grads = reference_loss_and_grads(params, batch, cfg)
    return dict(arch=arch, cfg=cfg, params=params, stats=stats,
                batch=batch, loss=loss,
                metrics=metrics, grads=grads, ref_loss=ref_loss,
                ref_grads=ref_grads)


@pytest.fixture
def fp32(request):
    return _fp32(request.param)


def _leaf_names(arch):
    """Every parameter's path, from shapes alone (this runs at
    collection, in every worker)."""
    from dexiraft_tpu.config import TrainConfig
    from dexiraft_tpu.train.family import family_of

    family = family_of(toy(arch, **SHARES[arch]), TrainConfig())
    params, _ = jax.eval_shape(family.init, jax.random.PRNGKey(0))
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(params)[0]]


def test_logits_match_the_reference(fp32):
    cfg = fp32["cfg"]
    b = fp32["batch"]
    got, _ = jax.jit(lambda v, b: LM(cfg).apply(
        v, b["tokens"], b["positions"], b["segment_ids"], logits=True))(
        {"params": fp32["params"], "batch_stats": fp32["stats"]}, b)
    want = as_one_program(ref.logits)(fp32["params"], b, cfg)
    real = np.asarray(b["segment_ids"]) > 0
    assert rel(np.asarray(got)[real], np.asarray(want)[real]) < 2e-5


def test_loss_matches_the_reference(fp32):
    assert abs(float(fp32["loss"]) - float(fp32["ref_loss"])) < 2e-5 * float(
        fp32["ref_loss"])
    assert int(fp32["metrics"]["moe_dropped_slots"]) == 0
    assert int(fp32["metrics"]["tokens_real"]) == 2 * 120


def pytest_generate_tests(metafunc):
    """`fp32` once an architecture; a leaf's test under the
    architecture that has the leaf."""
    if metafunc.function is test_gradient_leaf_matches_the_reference:
        metafunc.parametrize(
            "fp32,name", [(arch, n) for arch in ARCHS
                          for n in _leaf_names(arch)], indirect=["fp32"])
    elif "fp32" in metafunc.fixturenames:
        metafunc.parametrize("fp32", list(ARCHS), indirect=True)


def test_gradient_leaf_matches_the_reference(fp32, name):
    got = {jax.tree_util.keystr(p): g for p, g in
           jax.tree_util.tree_flatten_with_path(fp32["grads"])[0]}
    want = {jax.tree_util.keystr(p): g for p, g in
            jax.tree_util.tree_flatten_with_path(fp32["ref_grads"])[0]}
    assert float(jnp.linalg.norm(want[name])) > 0, "a dead leaf tests nothing"
    assert rel(got[name], want[name]) < 2e-5


@pytest.mark.parametrize("block", [None, 32])
def test_blocked_walk_equals_jax_grad_of_the_loss(fp32, block):
    """A sequence and a layer at a time, and (the afmoe layers and the
    head) 32 of a row's 128 query rows at a time."""
    loss, grads = ref.blocked_loss_and_grads(fp32["params"], fp32["batch"],
                                             fp32["cfg"], block=block)
    assert abs(float(loss) - float(fp32["ref_loss"])) < 1e-6 * float(loss)
    for got, want in zip(jax.tree.leaves(grads),
                         jax.tree.leaves(fp32["ref_grads"])):
        assert rel(got, want) < 5e-6


@pytest.mark.parametrize("arch", list(ARCHS))
def test_bf16_policy_stays_near_the_reference(arch):
    cfg = toy(arch, **SHARES[arch])
    family, params, stats = seeded(cfg, precision="bf16", remat="per_iter")
    batch = packed_batch(cfg)
    (loss, _), grads = jax.jit(jax.value_and_grad(
        family.loss_fn, has_aux=True))(params, stats, batch,
                                       jax.random.PRNGKey(0))
    ref_loss, ref_grads = reference_loss_and_grads(params, batch, cfg)
    assert abs(float(loss) - float(ref_loss)) < 2e-3 * float(ref_loss)
    worst = max(rel(g, r) for g, r in zip(jax.tree.leaves(grads),
                                          jax.tree.leaves(ref_grads)))
    # not fp32 by accident, not broken
    assert 1e-4 < worst < {"kanana2": 0.35, "trinity": 0.45,
                           "evabyte": 0.15, "lfm2": 0.55,
                           "smallthinker": 0.55}[arch], worst
    assert all(g.dtype == jnp.float32 for g in jax.tree.leaves(grads))


def test_reference_runs_in_the_precision_below(fp32):
    """The benchmark's second reading (`dtype=bfloat16`: weights, router,
    softmax, norms and loss in bf16) runs, and is the same model: within
    1 % of the fp32 loss, where a wrong term is off by far more. How far
    apart the two precisions are is read on the chip (PERF.md)."""
    low, grads = ref.blocked_loss_and_grads(
        fp32["params"], fp32["batch"], fp32["cfg"], dtype=jnp.bfloat16)
    assert abs(float(low) - float(fp32["ref_loss"])) < 0.01 * float(
        fp32["ref_loss"])
    assert all(g.dtype == jnp.bfloat16 for g in jax.tree.leaves(grads))


@pytest.mark.parametrize("arch,wrong", [
    ("trinity", dict(embed_scale=1.0)),
    ("trinity", dict(layer_rope=lambda self, i: True)),
    ("lfm2", dict(tie_embedding=False))])
def test_a_wrong_answer_of_the_configuration_fails_the_comparison(arch, wrong):
    """The reference goes by `model_type` and the published keys, not by
    what `config.DecoderConfig` answers the stack: a configuration that
    answers wrongly moves the system and leaves the reference where it
    was, by 10x the loss's limit at the least (the full layer's rotary
    embedding alone: 7e-4)."""
    right = toy(arch)
    cfg = type("Wrong", (type(right),), wrong)(**{
        f.name: getattr(right, f.name) for f in dataclasses.fields(right)})
    family, params, stats = seeded(cfg)
    batch = packed_batch(cfg)
    loss, _ = jax.jit(family.loss_fn)(params, stats, batch,
                                      jax.random.PRNGKey(0))
    want = as_one_program(ref.loss)(params, batch, cfg)
    assert abs(float(loss) - float(want)) > 2e-4 * float(want)
