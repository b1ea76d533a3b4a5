"""How the expert layer's rows come back to their tokens (ops/rows.py;
models/lm/moe.py, "How rows come back"): the blocked segment sum's
Pallas kernel in interpret mode against the plain scatter-add, at toy
sizes of the four sparse cells' routing geometries; its two
`custom_vjp`s against autodiff of the plain forms; and through the
expert layer itself, where the table comes from `plan` and the counters
from the same table. Mosaic sees the kernel at the cells' real shapes in
tests/test_chip_compile.py.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dexiraft_tpu.config import (kanana2_toy, lfm2_8b_a1b_toy,
                                 smallthinker_21b_toy, trinity_mini_toy)
from dexiraft_tpu.models.lm import moe
from dexiraft_tpu.ops import rows

from _lm_common import rel
from _models import init_module

# (the toy configuration with its published names for the two counts,
# chosen experts a token, experts, held here) as the four cells have them
GEOMETRIES = {
    "kanana2": ((kanana2_toy, "num_experts_per_tok", "n_routed_experts"),
                6, 128, 16),
    "trinity": ((trinity_mini_toy, "num_experts_per_tok", "num_experts"),
                8, 128, 16),
    "lfm2": ((lfm2_8b_a1b_toy, "num_experts_per_tok", "num_experts"),
             4, 32, 8),
    "smallthinker": ((smallthinker_21b_toy, "moe_num_active_primary_experts",
                      "moe_num_primary_experts"), 6, 64, 16)}
TOKENS, HIDDEN, CHUNK = 512, 128, 640  # two token blocks; no whole windows
FIRST = 2  # the first expert held


def _chosen(case, top_k, experts, held, seed=0):
    """`[TOKENS, top_k]` expert ids. `random`: a token's experts distinct
    and uniform, so a token has rows at several held experts; `empty`:
    one held expert chosen by nobody; `one`: every token on the first
    held expert and on no other (a range of 256 rows, longer than a
    window, across the chunks' ends); `none`: nothing held."""
    rng = np.random.default_rng(seed)
    chosen = np.argsort(rng.random((TOKENS, experts)), axis=1)[:, :top_k]
    away = np.array([e for e in range(experts)
                     if not FIRST <= e < FIRST + held])
    if case == "empty":
        chosen = np.where(chosen == FIRST + 1, away[0], chosen)
    elif case in ("one", "none"):
        chosen = np.broadcast_to(away[:top_k], (TOKENS, top_k)).copy()
        if case == "one":
            chosen[:, top_k // 2] = FIRST
    return chosen.astype(np.int32)


def _table(chosen, held):
    """The sorted slots' tokens and the table `lo` over the whole order,
    by hand."""
    top_k = chosen.shape[1]
    local = chosen.reshape(-1) - FIRST
    key = np.where((local >= 0) & (local < held), local, held)
    order = np.argsort(key, kind="stable")
    bt = rows.block_tokens(TOKENS)
    by_block = (key.reshape(TOKENS // bt, bt * top_k, 1)
                == np.arange(held)).sum(1)
    counts = by_block.sum(0)
    starts = np.cumsum(counts) - counts
    lo = starts[:, None] + np.concatenate(
        [np.zeros((1, held), int), np.cumsum(by_block, 0)]).T
    return (order // top_k).astype(np.int32), lo.astype(np.int32)


def _chunks(tokens, lo):
    """Each chunk's tokens and table, as `run_chunk` cuts them."""
    for c in range(-(-len(tokens) // CHUNK)):
        t = np.zeros(CHUNK, np.int32)
        part = tokens[c * CHUNK:(c + 1) * CHUNK]
        t[:len(part)] = part
        yield t, np.clip(lo, c * CHUNK, (c + 1) * CHUNK) - c * CHUNK


def _added(tokens, values):
    out = np.zeros((TOKENS, HIDDEN), np.float32)
    np.add.at(out, tokens, values)
    return out


_interpreted = functools.partial(rows.kernel_segment_sum, interpret=True)


@pytest.fixture
def on_the_kernel(monkeypatch):
    """`segment_sum` takes the path it takes on a TPU, interpreted."""
    monkeypatch.setattr(rows, "_on_tpu", lambda: True)
    monkeypatch.setattr(rows, "kernel_segment_sum", _interpreted)


@pytest.mark.parametrize("case", ["random", "empty", "one", "none"])
@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_kernel_adds_the_rows_of_every_range_and_no_other(geometry, case):
    """Chunk by chunk, with the weights in fp32 and without them in
    bf16: the rows of the ranges summed by token, rows past the held
    slots (NaN here) never in the sum."""
    _, top_k, experts, held = GEOMETRIES[geometry]
    tokens, lo = _table(_chosen(case, top_k, experts, held), held)
    n_held = int(lo[-1, -1])
    rng = np.random.default_rng(1)
    got_w = got_p = want_w = want_p = 0.0
    for c, (t, lo_c) in enumerate(_chunks(tokens, lo)):
        live = c * CHUNK + np.arange(CHUNK) < n_held
        r = rng.standard_normal((CHUNK, HIDDEN)).astype(np.float32)
        r = np.asarray(jnp.asarray(r, jnp.bfloat16).astype(jnp.float32))
        w = rng.uniform(0.05, 1.0, CHUNK).astype(np.float32)
        with_nan = jnp.asarray(np.where(live[:, None], r, np.nan),
                               jnp.bfloat16)
        got_w += _interpreted(with_nan, t, lo_c, TOKENS, w)
        got_p += _interpreted(with_nan, t, lo_c, TOKENS, None,
                              jnp.bfloat16).astype(jnp.float32)
        want_w += _added(t[live], (r * w[:, None])[live])
        want_p += _added(t[live], r[live])
    assert np.all(np.isfinite(np.asarray(got_w)))
    assert np.abs(np.asarray(got_w) - want_w).max() < 1e-5
    # a token's sum of up to `top_k` bf16 rows, rounded to bf16 a chunk
    assert np.abs(np.asarray(got_p) - want_p).max() < 0.05
    if case == "none":
        assert n_held == 0 and not np.any(np.asarray(got_w))


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_plain_form_reads_the_same_rows(geometry):
    _, top_k, experts, held = GEOMETRIES[geometry]
    tokens, lo = _table(_chosen("random", top_k, experts, held), held)
    # the chunk the held slots end in
    t, lo_c = list(_chunks(tokens, lo))[int(lo[-1, -1]) // CHUNK]
    n_live = int(lo_c[-1, -1])
    assert 0 < n_live < CHUNK
    r = np.random.default_rng(2).standard_normal((CHUNK, HIDDEN))
    r[n_live:] = np.nan
    w = np.linspace(0.1, 1.0, CHUNK, dtype=np.float32)
    plain = rows.xla_segment_sum(jnp.asarray(r, jnp.bfloat16), t, lo_c,
                                 TOKENS, w)
    kernel = _interpreted(jnp.asarray(r, jnp.bfloat16), t, lo_c, TOKENS, w)
    assert np.all(np.isfinite(np.asarray(plain)))
    assert rel(kernel, plain) < 1e-6


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_both_moves_are_each_others_transpose(geometry, on_the_kernel):
    """`gather_rows`' backward and `segment_add`'s, the kernel inside,
    against autodiff of the plain forms over the live rows."""
    _, top_k, experts, held = GEOMETRIES[geometry]
    tokens, lo = _table(_chosen("random", top_k, experts, held), held)
    t, lo_c = next(_chunks(tokens, lo))
    live = (np.arange(CHUNK) < lo_c[-1, -1])[:, None]
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(keys[0], (TOKENS, HIDDEN))
    out = jax.random.normal(keys[1], (CHUNK, HIDDEN)).astype(jnp.bfloat16)
    w = jax.random.uniform(keys[2], (CHUNK,), minval=0.05)
    dy = jax.random.normal(keys[3], (TOKENS, HIDDEN))

    def taken(x):
        return jnp.where(live, rows.gather_rows(x, t, lo_c), 0.0)

    def taken_plainly(x):
        return jnp.where(live, x[t], 0.0)

    g = jax.random.normal(keys[3], (CHUNK, HIDDEN))
    got, pull = jax.vjp(taken, x)
    want, pull_plainly = jax.vjp(taken_plainly, x)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert rel(pull(g)[0], pull_plainly(g)[0]) < 1e-6

    def added_plainly(out, w):
        return jnp.zeros((TOKENS, HIDDEN)).at[t].add(
            jnp.where(live, out.astype(jnp.float32) * w[:, None], 0.0))

    got, pull = jax.vjp(
        lambda out, w: rows.segment_add(out, t, w, lo_c, TOKENS), out, w)
    want, pull_plainly = jax.vjp(added_plainly, out, w)
    assert rel(got, want) < 1e-6
    (d_out, d_w), (want_out, want_w) = pull(dy), pull_plainly(dy)
    assert d_out.dtype == out.dtype and d_w.dtype == w.dtype
    assert rel(jnp.where(live, d_out, 0).astype(jnp.float32),
               want_out.astype(jnp.float32)) < 1e-6
    assert rel(jnp.where(live[:, 0], d_w, 0), want_w) < 1e-5


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_expert_layer_on_the_kernel_is_the_expert_layer(geometry,
                                                        monkeypatch):
    """`MoE` at the geometry's routing with several chunks, the output
    and every gradient on the kernel path against the plain path; the
    table `plan` makes is the table by hand; no slot dropped, and the
    rows of the ranges are the held slots, each once."""
    (make, chosen_key, experts_key), top_k, experts, held = (
        GEOMETRIES[geometry])
    cfg = make(hidden_size=HIDDEN, experts_held=(FIRST, held), moe_chunk=256,
               **{chosen_key: top_k, experts_key: experts})
    x = jax.random.normal(jax.random.PRNGKey(4), (TOKENS, HIDDEN))
    module = moe.MoE(cfg=cfg, init_std=0.2, dtype=jnp.bfloat16)
    variables = init_module(module, x)
    dy = jax.random.normal(jax.random.PRNGKey(5), x.shape)

    def run(variables, x):
        def out(p, x):
            y, counters = module.apply({**variables, "params": p},
                                       x.astype(jnp.bfloat16))
            return jnp.sum(y.astype(jnp.float32) * dy), counters
        (_, counters), grads = jax.value_and_grad(
            out, argnums=(0, 1), has_aux=True)(variables["params"], x)
        return counters, grads

    plain_counters, plain = jax.jit(run)(variables, x)
    monkeypatch.setattr(rows, "_on_tpu", lambda: True)
    monkeypatch.setattr(rows, "kernel_segment_sum", _interpreted)
    counters, grads = jax.jit(run)(variables, x)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(plain)):
        assert rel(got, want) < 2e-2  # bf16 rows, another order of sums
    assert {k: int(v) for k, v in counters.items() if "load" not in k} == {
        k: int(v) for k, v in plain_counters.items() if "load" not in k}
    n_held = int(counters["moe_slots_held"])
    assert 0 < n_held and int(counters["moe_dropped_slots"]) == 0
    assert int(counters["moe_rows_live"]) == n_held
    covered = int(counters["moe_rows_covered"])
    assert covered % rows.WINDOW == 0 and covered >= n_held

    plan = jax.jit(lambda v, x: module.apply(v, x, method="plan"))(
        variables, x.astype(jnp.bfloat16))
    local = np.asarray(plan.slot_token).reshape(-1)[:TOKENS * top_k]
    tokens, lo = _table(_chosen_of(module, variables, x, cfg), held)
    assert np.array_equal(np.asarray(plan.block_lo), lo)
    assert np.array_equal(local, tokens)


def _chosen_of(module, variables, x, cfg):
    """The experts the layer's own router picks, `[TOKENS, top_k]`."""
    e = variables["params"]["experts"]
    logits = jnp.matmul(x.astype(jnp.bfloat16).astype(jnp.float32),
                        e["router"], precision=jax.lax.Precision.HIGHEST)
    if cfg.route_score == "softmax":
        return np.asarray(moe.route_softmax(
            logits, cfg.num_experts_per_tok, 1.0)[0])
    bias = variables["batch_stats"]["experts"]["e_score_correction_bias"]
    return np.asarray(moe.route(logits, bias, cfg.num_experts_per_tok, 1.0,
                                False, 0.0)[0])
