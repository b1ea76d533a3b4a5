"""The packed-document attention's Pallas kernels (ops/lm_attention.py) in
Pallas's interpreter on the CPU: against the XLA path of the same file,
which the CPU tests of the model run, and against the plain reference's
attention (interop/lm_reference.py); forward and the gradients of `q`,
`k`, `v`. And the block table alone, against a count of the pairs.

Sizes: 512 positions in blocks of 128 (the module's constants are the
chip's, 512; the tests set them), so a row has 4 x 4 block pairs, at the
cell's head widths, 192 and 128. Interpret-mode parity says nothing
about Mosaic: tests/test_chip_compile.py compiles the same kernels at
the cell's shapes.

Tolerances, relative in the 2-norm. fp32 inputs: both paths are float32
arithmetic of one mathematics in another order (a running maximum
against a row's, one division at the end against one a key), on the
CPU's exact fp32 matmuls: 1e-5 is 25x the 2e-7 to 4e-7 seen. bf16
inputs: both round the probabilities to 8 bits for the second matmul,
the XLA path after the division, the kernel before it, and the XLA
path's backward rounds dP where the kernel keeps fp32: 2e-2 is 5x the
2.9e-3 to 3.9e-3 seen, and a dropped block or a wrong mask is off by 0.1
to 1.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _models import init_module
from dexiraft_tpu.ops import lm_attention as la

S, HEADS, D_QK, D_V = 512, 2, 192, 128
TOL = {"fp32": 1e-5, "bf16": 2e-2}


def _segments(*lengths):
    """One row: documents of these lengths, ids 1, 2, ...; pad (id 0)
    to S."""
    seg = np.zeros(S, np.int32)
    at = 0
    for i, n in enumerate(lengths, start=1):
        seg[at:at + n] = i
        at += n
    assert at <= S
    return seg


LAYOUTS = {
    # a row that is one document: the whole triangle, no tile skipped
    "one_document": [_segments(S)],
    # documents that start and end inside a block ([0, 40), [40, 100)),
    # across block edges ([100, 300)), on an edge ([300, 384)), and a
    # short padded tail
    "inside_and_across": [_segments(40, 60, 200, 84, 86)],
    # pad longer than a block: whole blocks of id 0, which see each other
    "padded_tail": [_segments(200)],
    # documents that are whole blocks, and two rows with their own tables
    "block_aligned_two_rows": [_segments(128, 256, 128),
                               _segments(300, 150)],
}


@pytest.fixture
def blocks(monkeypatch, request):
    bq, bk = getattr(request, "param", (128, 128))
    monkeypatch.setattr(la, "_BLOCK_Q", bq)
    monkeypatch.setattr(la, "_BLOCK_K", bk)
    return bq, bk


def _inputs(rows, dtype, d_qk=D_QK, d_v=D_V, seed=0):
    rng = np.random.default_rng(seed)
    arr = lambda *shape: jnp.asarray(  # noqa: E731
        rng.normal(size=shape), jnp.float32).astype(dtype)
    return (arr(rows, S, HEADS, d_qk), arr(rows, S, HEADS, d_qk),
            arr(rows, S, HEADS, d_v),
            jnp.asarray(rng.normal(size=(rows, S, HEADS, d_v)), jnp.float32))


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _out_and_grads(fn, q, k, v, w):
    return jax.jit(lambda *a: (fn(*a),) + jax.grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
        argnums=(0, 1, 2))(*a))(q, k, v)


def _assert_kernel_matches_the_xla_path(layout, dtype):
    seg = jnp.asarray(np.stack(LAYOUTS[layout]))
    q, k, v, w = _inputs(seg.shape[0],
                         jnp.float32 if dtype == "fp32" else jnp.bfloat16)
    scale = D_QK ** -0.5
    got = _out_and_grads(lambda *a: la.flash_document_attention(
        *a, seg, scale=scale, interpret=True), q, k, v, w)
    want = _out_and_grads(lambda *a: la.xla_document_attention(
        *a, seg, scale=scale, block=128), q, k, v, w)
    # pad rows' outputs are never read, but both paths define them alike
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert _rel(a, b) < TOL[dtype], (name, _rel(a, b))


@pytest.mark.parametrize("dtype", ["fp32", "bf16"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_kernel_matches_the_xla_path(blocks, layout, dtype):
    _assert_kernel_matches_the_xla_path(layout, dtype)


@pytest.mark.parametrize("blocks", [(256, 128), (128, 256)], indirect=True)
def test_kernel_with_unequal_blocks(blocks):
    """Query and key blocks of different sizes: the diagonal and both
    ranges are computed in positions, not in block indices."""
    _assert_kernel_matches_the_xla_path("inside_and_across", "fp32")


def test_latent_attention_on_the_kernel_matches_the_reference(
        blocks, monkeypatch):
    """The module that calls the kernel, heads and rotary embedding
    included, against the plain reference's attention: the output and
    the gradients of the input and of every weight, fp32."""
    import dexiraft_tpu.models.lm.attention as attention
    from dexiraft_tpu.config import kanana2_toy
    from dexiraft_tpu.interop import lm_reference as ref

    monkeypatch.setattr(
        attention, "document_attention",
        lambda q, k, v, seg, *, scale, block: la.flash_document_attention(
            q, k, v, seg, scale=scale, interpret=True))
    cfg = kanana2_toy(qk_nope_head_dim=128, qk_rope_head_dim=64,
                      v_head_dim=128, seq_len=S, attn_block=128,
                      num_attention_heads=HEADS)
    seg_row = LAYOUTS["inside_and_across"][0]
    seg = jnp.asarray(seg_row[None])
    starts = np.maximum.accumulate(np.where(
        np.r_[True, seg_row[1:] != seg_row[:-1]], np.arange(S), 0))
    pos = jnp.asarray((np.arange(S) - starts)[None], jnp.int32)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(1, S, cfg.hidden_size)), jnp.float32)
    w = jnp.asarray(rng.normal(size=x.shape), jnp.float32)
    module = attention.LatentAttention(cfg=cfg, dtype=jnp.float32,
                                       init_std=0.2)
    params = init_module(module, x, pos, seg)["params"]

    def ours(p, x):
        return jnp.sum(module.apply({"params": p}, x, pos, seg) * w)

    def plain(p, x):
        with jax.default_matmul_precision("highest"):
            return jnp.sum(ref.attention(p, x[0], pos[0], seg[0], cfg,
                                         HEADS) * w[0])

    got = jax.jit(jax.value_and_grad(ours, argnums=(0, 1)))(params, x)
    want = jax.jit(jax.value_and_grad(plain, argnums=(0, 1)))(params, x)
    assert abs(float(got[0]) - float(want[0])) < 1e-4 * abs(float(want[0]))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got[1])[0],
                            jax.tree.leaves(want[1])):
        assert _rel(a, b) < 1e-4, (jax.tree_util.keystr(path), _rel(a, b))


@pytest.mark.parametrize("kind", ["sliding", "full"])
def test_gated_attention_on_the_kernel_matches_the_reference(
        blocks, monkeypatch, kind):
    """The second mixer on the kernel (4 query heads over 1 key/value
    head, the cell's share; gate, QK-norm, rotary embedding on the
    sliding layer only) against the plain reference's: the output and
    the gradients of the input and of every weight, fp32."""
    import dexiraft_tpu.models.lm.attention as attention
    from dexiraft_tpu.config import trinity_mini_toy
    from dexiraft_tpu.interop import lm_reference as ref

    monkeypatch.setattr(
        attention, "document_attention",
        lambda q, k, v, seg, *, scale, block, window:
        la.flash_document_attention(q, k, v, seg, scale=scale, window=window,
                                    interpret=True))
    cfg = trinity_mini_toy(head_dim=128, seq_len=S, attn_block=128,
                           num_attention_heads=4, num_key_value_heads=1,
                           sliding_window=200)
    window = cfg.sliding_window if kind == "sliding" else None
    seg_row = LAYOUTS["inside_and_across"][0]
    seg = jnp.asarray(seg_row[None])
    starts = np.maximum.accumulate(np.where(
        np.r_[True, seg_row[1:] != seg_row[:-1]], np.arange(S), 0))
    pos = jnp.asarray((np.arange(S) - starts)[None], jnp.int32)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(1, S, cfg.hidden_size)), jnp.float32)
    w = jnp.asarray(rng.normal(size=x.shape), jnp.float32)
    module = attention.GatedAttention(cfg=cfg, window=window,
                                      rope=window is not None,
                                      dtype=jnp.float32, init_std=0.2)
    params = init_module(module, x, pos, seg)["params"]

    def ours(p, x):
        return jnp.sum(module.apply({"params": p}, x, pos, seg) * w)

    def plain(p, x):
        with jax.default_matmul_precision("highest"):
            return jnp.sum(ref.gated_attention(p, x[0], pos[0], seg[0], cfg,
                                               4, 1, window) * w[0])

    got = jax.jit(jax.value_and_grad(ours, argnums=(0, 1)))(params, x)
    want = jax.jit(jax.value_and_grad(plain, argnums=(0, 1)))(params, x)
    assert abs(float(got[0]) - float(want[0])) < 1e-4 * abs(float(want[0]))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got[1])[0],
                            jax.tree.leaves(want[1])):
        assert _rel(a, b) < 1e-4, (jax.tree_util.keystr(path), _rel(a, b))


# ---- a window, and keys and values that query heads share ------------------

WINDOWS = {"none": None, "under_a_block": 48, "not_a_multiple_of_a_block": 200,
           "over_the_longest_document": 400}
GQA_HEADS, GQA_D = 4, 128


def _visible(seg_row, window):
    """[S, S] bool, the three terms written out: query r may attend to
    key c."""
    t = np.arange(S)
    back = t[:, None] - t[None, :]
    near = back >= 0 if window is None else (back >= 0) & (back < window)
    return near & (seg_row[:, None] == seg_row[None, :])


def _dense_oracle(q, k, v, seg, scale, window):
    """softmax over the visible keys of the whole `[S, S]` score matrix,
    keys and values repeated to the query heads; no blocks, no table."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    mask = jnp.asarray(np.stack([_visible(r, window) for r in seg]))
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _gqa_inputs(rows, heads, kv_heads, seed=3):
    rng = np.random.default_rng(seed)
    arr = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    return (arr(rows, S, heads, GQA_D), arr(rows, S, kv_heads, GQA_D),
            arr(rows, S, kv_heads, GQA_D), arr(rows, S, heads, GQA_D))


def _assert_matches_the_dense_oracle(path, window, heads, kv_heads,
                                     layout="inside_and_across"):
    seg_np = np.stack(LAYOUTS[layout])
    seg = jnp.asarray(seg_np)
    q, k, v, w = _gqa_inputs(len(seg_np), heads, kv_heads)
    scale = GQA_D ** -0.5
    if path == "kernel":
        fn = lambda *a: la.flash_document_attention(  # noqa: E731
            *a, seg, scale=scale, window=window, interpret=True)
    else:
        fn = lambda *a: la.document_attention(  # noqa: E731
            *a, seg, scale=scale, block=128, window=window)
    got = _out_and_grads(fn, q, k, v, w)
    want = _out_and_grads(
        lambda *a: _dense_oracle(*a, seg_np, scale, window), q, k, v, w)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape, name
        assert _rel(a, b) < TOL["fp32"], (name, _rel(a, b))


@pytest.mark.parametrize("kv_heads", [1, GQA_HEADS])
@pytest.mark.parametrize("window", list(WINDOWS))
@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_window_and_shared_heads_match_the_dense_oracle(blocks, path, window,
                                                        kv_heads):
    """Both paths against the whole masked score matrix: out, dq, and
    dk, dv summed over the query heads that share a key/value head. The
    longest document of the layout is 200 positions."""
    _assert_matches_the_dense_oracle(path, WINDOWS[window], GQA_HEADS,
                                     kv_heads)


@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (8, 1), (2, 1)])
def test_kernel_steps_hold_whole_groups_or_lie_inside_one(blocks, heads,
                                                          kv_heads):
    """2 key/value heads a step; one head's 8 query heads over two steps
    whose dk, dv are added outside the kernel; a step of 2 heads."""
    st = la._Static(heads, 1.0, 128, 128, True, kv_heads, None)
    assert (st.hb, st.rep, st.hkv) == {(4, 2): (4, 2, 2), (8, 1): (4, 4, 1),
                                       (2, 1): (2, 2, 1)}[heads, kv_heads]
    _assert_matches_the_dense_oracle("kernel", 200, heads, kv_heads,
                                     layout="block_aligned_two_rows")


@pytest.mark.parametrize("window", [None, 200])
@pytest.mark.parametrize("heads,kv_heads", [(7, 1), (14, 2)])
def test_groups_of_seven_match_the_dense_oracle(blocks, heads, kv_heads,
                                                window):
    """SmallThinker's share: a key/value head serves 7 query heads, which
    is no power of two and no divisor's multiple up to `_HEADS_A_STEP`.
    Forward, dq, and dk, dv summed over the 7 heads, with and without a
    window, on two rows: with the whole group a grid step (what the
    rule picks: the faster on the chip, PERF.md section 6), and with one
    head a step, dk and dv of the 7 steps summed outside the kernel."""
    st = la._Static(heads, 1.0, 128, 128, True, kv_heads, window)
    assert (st.group, st.hb, st.rep, st.hkv) == (7, 7, 7, 1)
    _assert_matches_the_dense_oracle("kernel", window, heads, kv_heads,
                                     layout="block_aligned_two_rows")


@pytest.mark.parametrize("window", [None, 200])
def test_a_group_of_seven_over_seven_steps_matches_the_dense_oracle(
        blocks, monkeypatch, window):
    monkeypatch.setattr(la, "_GROUP_A_STEP", 1)  # the rule before PR 45
    st = la._Static(7, 1.0, 128, 128, True, 1, window)
    assert (st.hb, st.rep, st.hkv) == (1, 1, 1) and st.group > st.hb
    _assert_matches_the_dense_oracle("kernel", window, 7, 1,
                                     layout="block_aligned_two_rows")


@pytest.mark.parametrize("heads,kv_heads,want", [
    (4, 4, (4, 1, 4)), (4, 1, (4, 4, 1)), (8, 2, (4, 4, 1)),
    (8, 8, (4, 1, 4)), (32, 32, (4, 1, 4)), (2, 1, (2, 2, 1)),
    (8, 1, (4, 4, 1)), (7, 1, (7, 7, 1)), (14, 2, (7, 7, 1)),
    (5, 1, (5, 5, 1)), (9, 1, (3, 3, 1)), (11, 1, (1, 1, 1))])
def test_heads_a_step_of_the_accepted_cells_stand(heads, kv_heads, want):
    """(hb, rep, hkv) for the (query, key/value) heads the benchmark's
    cells run: kanana's 4 on 4, Trinity's 4 on 1, LFM2's 8 on 2,
    EvaByte's 8 on 8 (and the whole EvaByte's 32): what they were before
    a group of 7 came; then the tests' 2 on 1 and 8 on 1, SmallThinker's
    share and its toy, and what the rule gives other odd groups (a group
    past `_GROUP_A_STEP` stays a head a step)."""
    st = la._Static(heads, 1.0, 512, 512, False, kv_heads, None)
    assert (st.hb, st.rep, st.hkv) == want


def test_heads_that_do_not_divide_are_refused():
    q, k, v, _ = _gqa_inputs(1, 4, 3)
    with pytest.raises(ValueError, match="do not divide"):
        la.document_attention(q, k, v, jnp.ones((1, S), jnp.int32),
                              scale=1.0, block=128)


# ---- the table alone -------------------------------------------------------


def _allowed(seg_row):
    """[S, S] bool: query r may attend to key c."""
    t = np.arange(S)
    return (t[:, None] >= t[None, :]) & (seg_row[:, None] == seg_row[None, :])


def _by_blocks(allowed, bq, bk, how):
    """[S // bq, S // bk]: `how` (any, all) over each block pair."""
    return how(allowed.reshape(S // bq, bq, S // bk, bk), axis=(1, 3))


def _table_of_pr27(seg, bq, bk):
    """`block_table` as it was before the window, in numpy: what
    `window=None` has to give array for array."""
    at = np.arange(S)
    differs = seg[:, 1:] != seg[:, :-1]
    edge = np.ones_like(seg[:, :1], bool)
    start = np.maximum.accumulate(
        np.where(np.concatenate([edge, differs], 1), at, 0), axis=1)
    end = np.minimum.accumulate(
        np.where(np.concatenate([differs, edge], 1), at, S - 1)[:, ::-1],
        axis=1)[:, ::-1]
    return la.BlockTable(
        first_kv=start[:, ::bq] // bk, full_kv=-(-start[:, bq - 1::bq] // bk),
        last_q=end[:, bk - 1::bk] // bq, full_q=(end[:, ::bk] + 1) // bq - 1)


@pytest.mark.parametrize("window", [None, 1, 48, 128, 200, 400, 4 * S])
@pytest.mark.parametrize("bq,bk", [(128, 128), (256, 128), (128, 256),
                                   (64, 64)])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_the_table_under_a_window(layout, bq, bk, window):
    """No window: yesterday's table, array for array. A window: the
    visited range holds every block pair with a visible pair (and is a
    range, so it may hold a block between two that do), the tiles that
    skip the mask are exactly those with no masked pair, and a window
    longer than the row changes nothing."""
    seg = np.stack(LAYOUTS[layout])
    table = jax.tree.map(np.asarray,
                         la.block_table(jnp.asarray(seg), bq, bk, window))
    plain = _table_of_pr27(seg, bq, bk)
    if window is None or window >= S:
        for got, want in zip(table, plain):
            np.testing.assert_array_equal(got, want)
        return
    nq, nk = S // bq, S // bk
    i, j = np.arange(nq)[:, None], np.arange(nk)[None, :]
    diag = (i * bq + bq - 1) // bk
    below = j * bk + bk - 1 <= i * bq
    visited_total = 0
    for b, row in enumerate(seg):
        visible = _visible(row, window)
        holds_a_pair = _by_blocks(visible, bq, bk, np.any)
        all_pairs = _by_blocks(visible, bq, bk, np.all)
        by_query = (j >= table.first_kv[b][:, None]) & (j <= diag)
        by_key = (i >= (j * bk) // bq) & (i <= table.last_q[b][None, :])
        # every needed pair is inside both ranges, and both are the same
        # set of tiles: no block the window or the document has left
        # behind is visited
        assert not (holds_a_pair & ~by_query).any()
        np.testing.assert_array_equal(by_query, by_key)
        first_needed = np.where(holds_a_pair.any(1), holds_a_pair.argmax(1),
                                nk)
        np.testing.assert_array_equal(table.first_kv[b], first_needed)
        np.testing.assert_array_equal(
            (j >= table.full_kv[b][:, None]) & below, all_pairs)
        np.testing.assert_array_equal(
            (i <= table.full_q[b][None, :]) & below, all_pairs)
        visited_total += int(by_query.sum())
    visited, causal = la.block_pair_counts(jnp.asarray(seg), bq, bk, window)
    assert int(visited) == visited_total
    assert int(visited) <= int(la.block_pair_counts(jnp.asarray(seg), bq,
                                                    bk)[0])
    assert int(causal) == len(seg) * int((diag + 1).sum())


@pytest.mark.parametrize("bq,bk", [(128, 128), (256, 128), (128, 256),
                                   (64, 64)])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_the_table_visits_exactly_the_blocks_that_hold_a_pair(layout, bq, bk):
    seg = np.stack(LAYOUTS[layout])
    table = jax.tree.map(np.asarray, la.block_table(jnp.asarray(seg), bq, bk))
    nq, nk = S // bq, S // bk
    i, j = np.arange(nq)[:, None], np.arange(nk)[None, :]
    diag = (i * bq + bq - 1) // bk
    below = j * bk + bk - 1 <= i * bq  # every key at or before every query
    visited_total = 0
    for b, row in enumerate(seg):
        allowed = _allowed(row)
        holds_a_pair = _by_blocks(allowed, bq, bk, np.any)
        all_pairs = _by_blocks(allowed, bq, bk, np.all)
        # the forward's and dq's range: key blocks of a query block
        by_query = (j >= table.first_kv[b][:, None]) & (j <= diag)
        np.testing.assert_array_equal(by_query, holds_a_pair)
        # dk/dv's range, the transpose: query blocks of a key block
        by_key = (i >= (j * bk) // bq) & (i <= table.last_q[b][None, :])
        np.testing.assert_array_equal(by_key, holds_a_pair)
        # the tiles that skip the mask are those with no masked pair
        np.testing.assert_array_equal(
            (j >= table.full_kv[b][:, None]) & below, all_pairs)
        np.testing.assert_array_equal(
            (i <= table.full_q[b][None, :]) & below, all_pairs)
        visited_total += int(holds_a_pair.sum())
    visited, causal = la.block_pair_counts(jnp.asarray(seg), bq, bk)
    assert int(visited) == visited_total
    assert int(causal) == len(seg) * int((diag + 1).sum())
    if layout == "one_document":
        assert int(visited) == int(causal)
    elif bq == bk:  # every other layout leaves a block pair out
        assert int(visited) < int(causal)


def test_the_step_carries_the_counters():
    """The model's metrics hold both counters, from the kernel's table
    where the kernel takes the shapes and one block a row where it does
    not (the toy widths)."""
    from dexiraft_tpu.config import kanana2_toy
    from dexiraft_tpu.models.lm.model import COUNTERS, _attention_counters

    assert {"attn_block_pairs_visited",
            "attn_block_pairs_causal"} <= set(COUNTERS)
    seg = jnp.asarray(np.stack(LAYOUTS["block_aligned_two_rows"]))
    toy = _attention_counters(kanana2_toy(seq_len=S), seg)
    assert {k: int(v) for k, v in toy.items()} == {
        "attn_block_pairs_visited": 2, "attn_block_pairs_causal": 2}
    wide = _attention_counters(
        kanana2_toy(seq_len=S, qk_nope_head_dim=128, qk_rope_head_dim=64,
                    v_head_dim=128), seg)
    # S = 512 is one block of the chip's 512
    assert int(wide["attn_block_pairs_causal"]) == 2


def test_the_step_carries_the_counters_of_both_kinds_of_layer():
    """An `AfmoeConfig`: the sliding layers' visited pairs under the
    window and the full layers' without, each summed over its layers."""
    from dexiraft_tpu.config import trinity_mini_toy
    from dexiraft_tpu.models.lm.model import COUNTERS, _attention_counters

    seg = jnp.asarray(np.stack(LAYOUTS["one_document"]))
    # widths the kernel takes, a row of four of the chip's blocks
    cfg = trinity_mini_toy(head_dim=128, seq_len=4 * S, sliding_window=8)
    got = _attention_counters(cfg, jnp.concatenate([seg] * 4, axis=1))
    assert set(got) <= set(COUNTERS)
    # a full layer sees the 10 block pairs of the triangle; a sliding
    # layer its diagonal and, for the 7 keys before a block's first row,
    # the block before it: 4 + 3
    assert {k: int(v) for k, v in got.items()} == {
        "attn_block_pairs_visited_window": 4 * 7,
        "attn_block_pairs_visited_full": 10, "attn_block_pairs_causal": 10}
    one = _attention_counters(trinity_mini_toy(seq_len=S), seg)
    assert int(one["attn_block_pairs_visited_window"]) == 4  # toy widths


@pytest.mark.parametrize("seq,d_qk,d_v,want", [
    (8192, 192, 128, (512, 512)),   # the cell
    (512, 192, 128, (512, 512)),
    (256, 128, 128, (256, 256)),    # a short row is one block
    (8192 + 512, 192, 128, (512, 512)),
    (8192 + 128, 192, 128, None),   # not whole blocks
    (128, 12, 8, None),             # the toy widths
    (8, 192, 128, None),            # `init`'s dummy row
    (192, 192, 128, None),          # not a multiple of the lane width
])
def test_kernel_blocks_takes_whole_lane_aligned_shapes(seq, d_qk, d_v, want):
    assert la.kernel_blocks(seq, d_qk, d_v) == want


def test_the_cpu_takes_the_xla_path():
    """Selection is by the backend: here the XLA path, whatever the
    shapes, so the tier-1 numbers of the model's tests are today's."""
    seg = jnp.asarray(np.stack(LAYOUTS["padded_tail"]))
    q, k, v, _ = _inputs(1, jnp.float32)
    text = jax.jit(lambda q, k, v: la.document_attention(
        q, k, v, seg, scale=1.0, block=128)).lower(q, k, v).as_text()
    assert "pallas" not in text and "custom_call" not in text
