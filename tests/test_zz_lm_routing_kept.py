"""The routing is kept across a layer's checkpoint (models/lm/moe.py
`ROUTING`, models/lm/model.py's `nn.remat` line): at toy sizes on the
CPU in fp32, for one configuration of each routing kind (sigmoid scores
normalised, a softmax over the chosen, sigmoid scores ahead of a latent
space), the loss and every gradient leaf equal, bit for bit, those of
the same step under a checkpoint that keeps nothing; the score taken
after the selection is the selection of the scores, bit for bit, ties or
none; and the lowered gradient holds one forward product of the router,
one top-k, one sort and one `weights[order]` an expert layer where the
checkpoint that keeps nothing holds two of each.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dexiraft_tpu.config import TrainConfig, nemotron_h_toy
from dexiraft_tpu.models.lm import moe
from dexiraft_tpu.train.family import family_of

from _lm_common import SHARES, packed_batch, routing_ops, seeded, toy

KINDS = {
    "sigmoid": lambda: toy("kanana2", **SHARES["kanana2"]),
    "softmax": lambda: toy("smallthinker", **SHARES["smallthinker"]),
    "latent": lambda: nemotron_h_toy(
        ssm_heads_held=(2, 4), heads_held=(4, 4), experts_held=(2, 6),
        shared_columns_held=(12, 24))}


@functools.lru_cache(maxsize=None)
def _step(kind, kept):
    """(loss, gradients, lowered text) of the toy's step with every
    layer recomputed, the routing kept or, the parent's form, nothing."""
    cfg = KINDS[kind]()
    family, params, stats = seeded(cfg, remat="per_iter")
    args = (params, stats, packed_batch(cfg), jax.random.PRNGKey(0))
    step = jax.jit(jax.value_and_grad(family.loss_fn, has_aux=True))
    with pytest.MonkeyPatch.context() as patch:
        if not kept:
            patch.setattr(
                jax.checkpoint_policies, "save_only_these_names",
                lambda *names: jax.checkpoint_policies.nothing_saveable)
        text = step.lower(*args).as_text()
        (loss, _), grads = step(*args)
    return loss, _flat(grads), text


def _flat(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def _leaves():
    """(kind, leaf) for every parameter of every kind, from shapes."""
    return [(kind, leaf) for kind, cfg in KINDS.items() for leaf in _flat(
        jax.eval_shape(family_of(cfg(), TrainConfig()).init,
                       jax.random.PRNGKey(0))[0])]


@pytest.mark.parametrize("kind", KINDS)
def test_the_loss_is_the_one_of_a_checkpoint_that_keeps_nothing(kind):
    assert np.array_equal(_step(kind, True)[0], _step(kind, False)[0])


@pytest.mark.parametrize("kind,leaf", _leaves())
def test_gradient_leaf_is_the_one_of_a_checkpoint_that_keeps_nothing(
        kind, leaf):
    got, want = _step(kind, True)[1][leaf], _step(kind, False)[1][leaf]
    assert got.dtype == jnp.float32 and float(jnp.linalg.norm(want)) > 0
    assert np.array_equal(got, want)


@pytest.mark.parametrize("kind", KINDS)
def test_the_backward_routes_no_second_time(kind):
    """The router's forward product and the two of its transpose, one
    top-k, one sort of the slots and one gather of their weights an
    expert layer; the forward product and the other three twice under a
    checkpoint that keeps nothing, which the same count has to see."""
    cfg = KINDS[kind]()
    layers = sum(1 for i in range(cfg.num_hidden_layers)
                 if "ffn" in cfg.layer_parts(i)
                 and i >= cfg.first_k_dense_replace)
    assert layers >= 2
    count = lambda kept: routing_ops(  # noqa: E731
        _step(kind, kept)[2], 2 * cfg.seq_len, cfg.n_routed_experts,
        cfg.num_experts_per_tok)
    once = dict.fromkeys(("top_k", "sorts", "weight_gathers"), layers)
    assert count(True) == dict(once, products=3 * layers)
    assert count(False) == dict({k: 2 * n for k, n in once.items()},
                                products=4 * layers)


def _parent_route(logits, bias, top_k, scale, normalise, eps):
    """`route` as it stood: the scores of every expert, then the chosen
    ones' entries."""
    scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalise:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + eps)
    return chosen, weights * scale


@pytest.mark.parametrize("normalise", [True, False])
@pytest.mark.parametrize("ties", [False, True])
def test_the_score_after_the_selection_is_the_selected_score(ties,
                                                             normalise):
    """`sigmoid(take(logits))` is `take(sigmoid(logits))` bit for bit,
    each under jit, and with it `route`'s ids and weights are the
    parent's, operation by operation: random fp32 logits over six orders
    of magnitude, and rows in which every score stands twice, a pair of
    them on the edge of the selection. (Compiled as one program the
    normalised weights may differ in the last bit: XLA rewrites
    `(1 / (1 + e)) / s` into `1 / ((1 + e) * s)` where the sigmoid now
    stands next to the division.)"""
    rng = np.random.default_rng(5)
    logits = rng.normal(size=(512, 64)) * 10.0 ** rng.integers(
        -3, 3, (512, 1))
    bias = rng.normal(size=64) * 0.01
    if ties:
        logits[:, 32:], bias[32:] = logits[:, :32], bias[:32]
    logits, bias = (jnp.asarray(a, jnp.float32) for a in (logits, bias))
    args = (logits, bias, 7, 2.5, normalise, 1e-20)
    chosen, weights = moe.route(*args)
    want_chosen, want = _parent_route(*args)
    assert np.array_equal(chosen, want_chosen)
    assert np.array_equal(weights, want)
    take = lambda a: jnp.take_along_axis(a, chosen, axis=-1)  # noqa: E731
    assert np.array_equal(jax.jit(lambda a: jax.nn.sigmoid(take(a)))(logits),
                          jax.jit(lambda a: take(jax.nn.sigmoid(a)))(logits))
    top = jax.lax.top_k(jax.nn.sigmoid(logits) + bias, 8)[0]
    assert bool(jnp.any(top[:, 6] == top[:, 7])) == ties


@pytest.mark.parametrize("ties", [False, True])
def test_the_softmax_routing_scores_the_logits_the_top_k_returns(ties):
    """`route_softmax` takes the chosen logits with the kept ids: they
    are `lax.top_k`'s own values, bit for bit."""
    rng = np.random.default_rng(6)
    logits = rng.normal(size=(512, 64))
    if ties:
        logits[:, 32:] = logits[:, :32]
    logits = jnp.asarray(logits, jnp.float32)
    chosen, weights = jax.jit(lambda a: moe.route_softmax(a, 6, 1.0))(logits)
    top, want_chosen = jax.lax.top_k(logits, 6)
    assert np.array_equal(chosen, want_chosen)
    assert np.array_equal(weights, jax.jit(
        lambda t: jax.nn.softmax(t, axis=-1))(top))
