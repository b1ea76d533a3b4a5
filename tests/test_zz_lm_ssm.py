"""The chunked state-space scan (ops/lm_ssm.py) against the recurrence
it stands for, written here token by token in numpy's order of
operations, at toy sizes on the CPU, each under jit: forward and every
gradient at chunk sizes that do and do not divide the documents and the
row, document starts at a chunk's first, middle and last position, a
document of several chunks, pad; the reset bit for bit (a document's
outputs do not depend on what its neighbours hold); Mamba-2's
convolution with its bias the same; the mixer's two counters against
brute force.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dexiraft_tpu.config import nemotron_h_toy
from dexiraft_tpu.models.lm.attention import Mamba2
from dexiraft_tpu.ops.lm_conv import causal_conv
from dexiraft_tpu.ops.lm_ssm import doc_counts, ssm_scan

from _lm_common import rel
from _models import init_module

H, P, G, N = 4, 8, 2, 16


def _row(lengths, total):
    """Segment ids `[1, total]`: documents of `lengths`, then pad."""
    seg = np.zeros((1, total), np.int32)
    at = 0
    for i, n in enumerate(lengths, start=1):
        seg[0, at:at + n] = i
        at += n
    assert at <= total
    return jnp.asarray(seg)


def _inputs(t, seed=0, batch=1):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        x=jax.random.normal(keys[0], (batch, t, H, P)),
        dt=jax.nn.softplus(jax.random.normal(keys[1], (batch, t, H)) - 1.0),
        a=-jnp.exp(jax.random.normal(keys[2], (H,))),
        b=jax.random.normal(keys[3], (batch, t, G, N)),
        c=jax.random.normal(keys[4], (batch, t, G, N)),
        d=jax.random.normal(keys[5], (H,)))


def recurrence(x, dt, a, b, c, d, segment_ids):
    """h_t = r_t exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t; y_t = h_t C_t +
    D x_t: a `lax.scan` over the positions of one row."""
    per = H // G
    seg = segment_ids[0]
    carried = (seg == jnp.roll(seg, 1)).at[0].set(False)

    def token(h, xs):
        x_t, dt_t, b_t, c_t, r_t = xs
        b_t, c_t = (jnp.repeat(v, per, axis=0) for v in (b_t, c_t))
        h = (jnp.where(r_t, jnp.exp(dt_t * a), 0.0)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return h, jnp.einsum("hpn,hn->hp", h, c_t) + d[:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N)),
                        (x[0], dt[0], b[0], c[0], carried))
    return y[None]


# (documents, row, chunk): starts at 0, 50, 90, pad from 120
CASES = {
    "chunk_divides_documents": ((50, 40, 30), 128, 10),
    "chunk_divides_nothing": ((50, 40, 30), 128, 16),
    "one_chunk_a_row": ((50, 40, 30), 128, 128),
    "row_not_whole_chunks": ((50, 40, 30), 128, 48),
    # chunk 16: starts at 16 (a chunk's first), 40 (its middle), 63 (its last)
    "start_first_middle_last": ((16, 24, 23, 33), 96, 16),
    "a_document_of_many_chunks": ((100, 20), 128, 8),
    "no_boundary_no_pad": ((128,), 128, 16),
    "every_token_a_document": ((1,) * 24, 32, 8),
}


@pytest.mark.parametrize("case", list(CASES))
def test_chunked_scan_is_the_recurrence_forward_and_every_gradient(case):
    lengths, total, chunk = CASES[case]
    seg, inp = _row(lengths, total), _inputs(total)
    w = jax.random.normal(jax.random.PRNGKey(7), inp["x"].shape)
    w = w * (seg > 0)[..., None, None]  # what pad computes feeds nothing

    def total_of(f):
        def run(inp):
            y = f(**inp)
            return jnp.sum(y * w), y
        return jax.jit(jax.value_and_grad(run, has_aux=True))

    (_, y), g = total_of(
        lambda **kw: ssm_scan(segment_ids=seg, chunk=chunk, **kw))(inp)
    (_, want), want_g = total_of(
        lambda **kw: recurrence(segment_ids=seg, **kw))(inp)
    real = np.asarray(seg[0] > 0)
    assert rel(np.asarray(y)[0, real], np.asarray(want)[0, real]) < 2e-5
    assert set(g) == {"x", "dt", "a", "b", "c", "d"}
    for name in g:
        assert rel(g[name], want_g[name]) < 5e-5, name


@pytest.mark.parametrize("chunk", [8, 16, 48])
def test_a_documents_outputs_do_not_depend_on_its_neighbours_bit_for_bit(
        chunk):
    """The middle document's outputs with its neighbours as drawn, with
    other neighbours, and alone in the row (the rest pad) are the same
    bits: the reset zeroes what crosses a start, it does not shrink it."""
    seg = _row((37, 45, 30), 128)
    inp, other = _inputs(128), _inputs(128, seed=5)
    mine = np.asarray(seg[0] == 2)
    scan = jax.jit(lambda seg, **kw: ssm_scan(segment_ids=seg, chunk=chunk,
                                              **kw))

    def elsewhere(name):
        if inp[name].ndim < 3:
            return inp[name]
        keep = jnp.asarray(mine).reshape((1, -1) + (1,) * (inp[name].ndim - 2))
        return jnp.where(keep, inp[name], 30.0 * other[name])

    base = np.asarray(scan(seg, **inp))[0, mine]
    swapped = np.asarray(scan(seg, **{k: elsewhere(k) for k in inp}))[0, mine]
    alone = np.asarray(scan(jnp.where(seg == 2, 2, 0), **inp))[0, mine]
    assert np.array_equal(base, swapped)
    assert np.array_equal(base, alone)
    # and they do depend on the document's own first token
    moved = dict(inp, x=inp["x"].at[0, 37].add(1.0))
    assert not np.array_equal(base, np.asarray(scan(seg, **moved))[0, mine])


def test_the_state_is_carried_from_chunk_to_chunk():
    """With B, C, x constant and no decay to speak of, y_t counts the
    document's tokens so far: a state cut at chunk starts could not."""
    t, chunk = 64, 8
    seg = _row((40, 24), t)
    ones = lambda *s: jnp.ones(s)  # noqa: E731
    y = jax.jit(lambda: ssm_scan(
        ones(1, t, H, P), ones(1, t, H), jnp.full((H,), -1e-9),
        ones(1, t, G, N) / N, ones(1, t, G, N), jnp.zeros((H,)), seg,
        chunk))()
    want = np.concatenate([np.arange(1, 41), np.arange(1, 25)])
    assert np.allclose(np.asarray(y)[0, :, 0, 0], want, rtol=1e-5)


@pytest.mark.parametrize("bias", [False, True])
def test_causal_conv_is_the_looked_up_taps_within_documents(bias):
    """silu(sum_j k[:, j] x[n - 3 + j] [same document] + b), each source
    position looked up; and bit for bit what the document gives alone."""
    seg = _row((50, 40, 30), 128)
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(keys[0], (1, 128, 12))
    taps = jax.random.normal(keys[1], (12, 4))
    b = jax.random.normal(keys[2], (12,)) if bias else jnp.zeros((12,))
    got = np.asarray(jax.jit(causal_conv)(x, taps, b, seg))
    ids, xs = np.asarray(seg[0]), np.asarray(x[0], np.float64)
    want = np.zeros_like(xs)
    for n_ in range(128):
        for j in range(4):
            m = n_ - 3 + j
            if m >= 0 and ids[m] == ids[n_]:
                want[n_] += np.asarray(taps)[:, j] * xs[m]
    want = want + np.asarray(b)
    want = want / (1 + np.exp(-want))
    assert rel(got[0], want) < 1e-6
    mine = ids == 2
    alone = np.asarray(jax.jit(causal_conv)(
        x, taps, b, jnp.where(seg == 2, 2, 0)))
    assert np.array_equal(got[0, mine], alone[0, mine])


def test_the_mixers_outputs_of_a_document_are_its_own_bit_for_bit():
    """Through `Mamba2` (projections, convolution, scan, gate, grouped
    norm): other tokens in the neighbours, same bits in the document."""
    cfg = nemotron_h_toy(ssm_heads_held=(2, 4))
    seg = _row((37, 45, 30), 128)
    module = Mamba2(cfg=cfg, init_std=0.3)
    u = jax.random.normal(jax.random.PRNGKey(1), (1, 128, 64))
    variables = init_module(module, u, seg, seg)
    assert set(variables["params"]) == {
        "in_proj", "taps", "conv_bias", "dt_bias", "A_log", "D", "norm",
        "out_proj"}
    a_log = np.asarray(variables["params"]["A_log"])
    assert np.allclose(a_log, np.log(1.0 + (2 + np.arange(4)) % 16))
    run = jax.jit(lambda u, seg: module.apply(variables, u, seg, seg))
    mine = np.asarray(seg[0] == 2)
    other = 10.0 * jax.random.normal(jax.random.PRNGKey(4), u.shape)
    base = np.asarray(run(u, seg))[0, mine]
    swapped = run(jnp.where(jnp.asarray(mine)[None, :, None], u, other), seg)
    assert np.array_equal(base, np.asarray(swapped)[0, mine])
    assert np.array_equal(
        base, np.asarray(run(u, jnp.where(seg == 2, 2, 0)))[0, mine])


@pytest.mark.parametrize("lengths,total,chunk", [
    ((50, 40, 30), 128, 16), ((16, 24, 23, 33), 96, 16), ((128,), 128, 16),
    ((1,) * 24, 32, 8), ((50, 40, 30), 128, 48)])
def test_counters_are_the_resets_and_the_chunks_that_hold_one(
        lengths, total, chunk):
    seg = _row(lengths, total)
    seg = jnp.concatenate([seg, seg[:, ::-1] * 0 + seg], axis=0)  # two rows
    starts, chunks = jax.jit(lambda s: doc_counts(s, chunk))(seg)
    ids = np.asarray(seg)
    want_starts, holding = 0, set()
    for r, row in enumerate(ids):
        for n_, d in enumerate(row):
            if d > 0 and (n_ == 0 or row[n_ - 1] != d):
                want_starts += 1
                holding.add((r, n_ // chunk))
    assert int(starts) == want_starts == 2 * len(lengths)
    assert int(chunks) == len(holding)
    cfg = nemotron_h_toy(chunk_size=chunk if total % chunk == 0 else 16)
    if total % chunk == 0:
        got = jax.jit(lambda s: Mamba2.counters(cfg, s, {None: 3}))(seg)
        assert {k: int(v) for k, v in got.items()} == {
            "ssm_doc_starts": 3 * want_starts,
            "ssm_chunks_reset": 3 * len(holding)}
