"""Packing: documents go whole into rows, a token attends to nothing
outside its document, positions restart, pad and cross-document targets
count for nothing; and dropless routing with every token forced onto one
held expert."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dexiraft_tpu.data.loader import Loader
from dexiraft_tpu.data.tokens import PackedTokens, first_fit, write_token_file
from dexiraft_tpu.models.lm import LM, next_token_targets

from _lm_common import (packed_batch, reference_loss_and_grads, rel, seeded,
                        toy)


@pytest.fixture(scope="module")
def token_file(tmp_path_factory):
    rng = np.random.default_rng(3)
    lengths = np.clip(np.exp(rng.normal(np.log(40), 1.0, 300)).astype(int),
                      4, 200)  # some longer than a row of 128
    path = str(tmp_path_factory.mktemp("tokens") / "docs.npz")
    write_token_file(path, rng.integers(1, 256, lengths.sum()), lengths)
    return path, lengths


def test_first_fit_places_every_document_whole_in_the_first_row_with_room():
    lengths = np.array([70, 70, 50, 58, 8, 128, 200])
    rows = first_fit(lengths, 128)
    assert rows == [[0, 2, 4], [1, 3], [5], [6]]
    assert sorted(d for r in rows for d in r) == list(range(len(lengths)))


def test_rows_hold_whole_documents_with_positions_restarting(token_file):
    path, lengths = token_file
    ds = PackedTokens(path, 128)
    with np.load(path) as f:
        tokens = f["tokens"]
    starts = np.concatenate([[0], np.cumsum(lengths)[:-1]])
    seen = 0
    for i in range(len(ds)):
        row = ds.sample(i)
        assert all(v.dtype == np.int32 and v.shape == (128,)
                   for v in row.values())
        seg = row["segment_ids"]
        real = int((seg > 0).sum())
        assert (seg[:real] > 0).all() and (seg[real:] == 0).all()
        for k, doc in enumerate(ds.rows[i], start=1):
            at = np.flatnonzero(seg == k)
            n = min(int(lengths[doc]), 128)
            assert len(at) == n and (np.diff(at) == 1).all()
            assert (row["positions"][at] == np.arange(n)).all()
            assert (row["tokens"][at] == tokens[starts[doc]:starts[doc] + n]).all()
            seen += 1
    assert seen == len(lengths)
    assert 0.8 < ds.fill <= 1.0


def test_packed_rows_pass_through_the_loader_as_they_are(token_file):
    path, _ = token_file
    ds = PackedTokens(path, 128)
    it = Loader(ds, 4, seed=5, num_workers=2).batches()
    try:
        batch = next(it)
    finally:
        it.close()
    assert set(batch) == {"tokens", "positions", "segment_ids"}
    assert all(v.shape == (4, 128) and v.dtype == np.int32
               for v in batch.values())


def test_targets_stay_inside_a_document_and_leave_pad_out():
    tokens = jnp.asarray([[5, 6, 7, 8, 9, 0, 0, 0]])
    seg = jnp.asarray([[1, 1, 1, 2, 2, 0, 0, 0]])
    targets, weight = next_token_targets(tokens, seg)
    assert weight.tolist() == [[1, 1, 0, 1, 0, 0, 0, 0]]
    assert targets[0, :2].tolist() == [6, 7] and int(targets[0, 3]) == 9


def test_a_token_attends_to_nothing_outside_its_document():
    cfg = toy(experts_held=(0, 16), heads_held=(0, 8))
    _, params, stats = seeded(cfg)
    batch = packed_batch(cfg, rows=1)
    model = LM(cfg)

    @jax.jit
    def logits(tokens):
        return model.apply({"params": params, "batch_stats": stats}, tokens,
                           batch["positions"], batch["segment_ids"],
                           logits=True)[0]

    base = logits(batch["tokens"])
    seg = np.asarray(batch["segment_ids"][0])
    second = np.flatnonzero(seg == 2)
    changed = batch["tokens"].at[0, second].set(
        (batch["tokens"][0, second] + 1) % cfg.vocab_size)
    moved = logits(changed)
    others = np.flatnonzero((seg == 1) | (seg == 3))
    # documents 1 and 3 do not see document 2's tokens; document 2 does
    assert float(jnp.max(jnp.abs(moved[0, others] - base[0, others]))) == 0.0
    assert float(jnp.max(jnp.abs(moved[0, second] - base[0, second]))) > 1e-3


def test_a_document_reads_the_same_wherever_it_lies_in_the_row():
    """Positions restart: document 2 alone at the row's start gives the
    logits it gives behind document 1."""
    cfg = toy(experts_held=(0, 16), heads_held=(0, 8))
    _, params, stats = seeded(cfg)
    batch = packed_batch(cfg, rows=1)
    model = LM(cfg)
    run = jax.jit(lambda b: model.apply(
        {"params": params, "batch_stats": stats}, b["tokens"], b["positions"],
        b["segment_ids"], logits=True)[0])
    base = run(batch)
    alone = {k: jnp.zeros_like(v) for k, v in batch.items()}
    for k in batch:
        alone[k] = alone[k].at[0, :40].set(batch[k][0, 50:90])
    got = run(alone)
    assert rel(got[0, :40], base[0, 50:90]) < 2e-5


def test_dropless_with_every_token_forced_onto_one_held_expert():
    """b = +10 on held expert 5: every token's top 2 then holds it, so
    one expert takes T slots (4 dispatch chunks of 64 at T = 256, where
    balance would give it 32). Nothing is dropped and the output is the
    reference's under the same b."""
    cfg = toy(experts_held=(4, 2), heads_held=(0, 8))
    family, params, stats = seeded(cfg)
    bias = jnp.zeros((cfg.n_routed_experts,)).at[5].set(10.0)
    stats = jax.tree.map(lambda b: bias, stats)
    batch = packed_batch(cfg)
    loss, (metrics, _) = jax.jit(family.loss_fn)(params, stats, batch,
                                                 jax.random.PRNGKey(0))
    tokens = batch["tokens"].size
    assert int(metrics["moe_load_max"]) == tokens
    assert int(metrics["moe_slots_held"]) >= 2 * tokens  # two expert layers
    assert int(metrics["moe_dropped_slots"]) == 0
    want, want_grads = reference_loss_and_grads(params, batch, cfg, bias=bias)
    assert abs(float(loss) - float(want)) < 2e-5 * float(want)
    grads = jax.jit(jax.grad(lambda p: family.loss_fn(
        p, stats, batch, jax.random.PRNGKey(0))[0]))(params)
    for path in (("layers_1", "moe", "experts", "w_down"),
                 ("layers_2", "moe", "experts", "router")):
        g, w = grads, want_grads
        for k in path:
            g, w = g[k], w[k]
        assert rel(g, w) < 2e-5


@pytest.mark.parametrize("slots,held,experts,rows", [
    (4 * 8192 * 6, 16, 128, 32_768),   # kanana2-train-pack8k: as PR 26 set it
    (32_768 * 8, 16, 128, 49_152),     # trinity-train-pack32k
    (8192 * 6, 16, 128, 8192),         # one row of kanana2's
    (32_768 * 8, 128, 128, 32_768 * 8),  # every expert here: every slot
    (256 * 2, 2, 16, 256 * 2)])        # a toy: one chunk
def test_the_dispatch_chunk_clears_the_expected_load_by_a_third(
        slots, held, experts, rows):
    """No field, flag or traffic file picks the chunk: the layer sizes it
    from its shapes, and for the benchmark's two cells that is the size
    each was measured at."""
    from dexiraft_tpu.config import kanana2, trinity_mini
    from dexiraft_tpu.models.lm.moe import dispatch_chunk

    got = dispatch_chunk(slots, held, experts)
    assert got == rows
    assert got == slots or (got % 8192 == 0
                            and 3 * got * experts >= 4 * slots * held
                            and 3 * (got - 8192) * experts < 4 * slots * held)
    assert kanana2().moe_chunk is None and trinity_mini().moe_chunk is None


@pytest.mark.parametrize("block", [32, 64, 128, 96])
def test_head_loss_by_blocks_of_positions_equals_the_whole(block):
    """The head and the loss walk blocks of positions; the sum and the
    gradients of the activations and of the head are those of one pass
    over `[B, S, vocab]` logits. 96 is no divisor of the row: a row at a
    time."""
    import jax

    from dexiraft_tpu.models.lm.model import head_loss

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 128, 16)), jnp.float32)
    head = jnp.asarray(rng.normal(size=(16, 40)), jnp.float32)
    targets = jnp.asarray(rng.integers(0, 40, (2, 128)), jnp.int32)
    weight = jnp.asarray(rng.integers(0, 2, (2, 128)), jnp.float32)

    def whole(x, head):
        logp = jax.nn.log_softmax(x @ head, axis=-1)
        picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return -jnp.sum(picked * weight)

    want = jax.value_and_grad(whole, argnums=(0, 1))(x, head)
    got = jax.value_and_grad(
        lambda x, head: head_loss(x, head, targets, weight, block),
        argnums=(0, 1))(x, head)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
