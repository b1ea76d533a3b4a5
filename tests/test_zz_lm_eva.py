"""EvaByte's mixer (ops/lm_eva.py, models/lm/attention.py `EvaAttention`)
on the CPU: against the plain reference (interop/lm_reference.py
`eva_attention`), on its XLA path and on the exact part's Pallas kernel
in interpret mode; the row log-sum-exp that `document_attention` gives
beside its output, and its gradient; the two limits in which EVA is
`document_attention`; the reference's defining sentence; the multi-byte
targets; the program's pair counters against a brute-force count.

Sizes: 512 positions in kernel blocks of 128 (the module's constants are
the chip's, 512; the tests set them), 2 heads of 64. Documents of 41,
59, 201, 83 and 87 positions and 41 of pad: they start at 41, 100, 301
and 384, inside a chunk of 2, 4 or 16 (41, 301) and inside a window of
8, 32 or 128 (41, 100, 301), and one runs over several windows of
either size. Window 8 with chunk 2 takes the row's first 256 positions
(32 windows: every window's product is a program of its own).

Tolerances, relative in the 2-norm, fp32 on the CPU's exact matmuls: the
module against the reference 1e-4, as the other mixers' kernel tests
have it (seen 2e-7 to 3e-6: one mathematics in another order, a running
maximum against a row's, two softmaxes merged against one); a dropped
summary, a wrong chunk document or a missing window is off by 0.01 to 1.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dexiraft_tpu.config import evabyte_toy
from dexiraft_tpu.interop import lm_reference as ref
from dexiraft_tpu.models.lm.attention import EvaAttention
from dexiraft_tpu.models.lm.model import next_token_targets
from dexiraft_tpu.ops import lm_attention as la
from dexiraft_tpu.ops import lm_eva

from _lm_common import brute_force_eva_pairs, rel
from _models import init_module

S, HEADS, HD = 512, 2, 64
DOCS = (41, 59, 201, 83, 87)
# window, chunk, positions of the row
SIZES = {"window8_chunk2": (8, 2, 256), "window32_chunk4": (32, 4, S),
         "window128_chunk16": (128, 16, S)}


def _row(lengths=DOCS, seq=S):
    """(segment_ids, positions) of one row: documents of these lengths,
    ids 1, 2, ..., then pad."""
    seg, pos = np.zeros(seq, np.int32), np.zeros(seq, np.int32)
    at = 0
    for i, n in enumerate(lengths, start=1):
        seg[at:at + n], pos[at:at + n] = i, np.arange(n)
        at += n
    assert at <= seq
    return seg, pos


@pytest.fixture
def kernel_blocks_of_128(monkeypatch):
    monkeypatch.setattr(la, "_BLOCK_Q", 128)
    monkeypatch.setattr(la, "_BLOCK_K", 128)


def _on_the_kernel(monkeypatch):
    """The exact part on the Pallas kernel in interpret mode, whatever
    the backend."""
    monkeypatch.setattr(
        lm_eva, "document_attention",
        lambda q, k, v, seg, *, scale, block, window=None, return_lse=False:
        la.flash_document_attention(q, k, v, seg, scale=scale, window=window,
                                    interpret=True, return_lse=return_lse))


def _cfg(window, chunk, seq=S, **kw):
    return evabyte_toy(hidden_size=HEADS * HD, num_attention_heads=HEADS,
                       num_key_value_heads=HEADS, window_size=window,
                       chunk_size=chunk, seq_len=seq, attn_block=128, **kw)


def _cut(row, seq):
    """The row's first `seq` positions."""
    return tuple(a[:seq] for a in row)


@pytest.mark.parametrize("sizes", list(SIZES))
@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_mixer_matches_the_reference(path, sizes, kernel_blocks_of_128,
                                     monkeypatch):
    """The module, projections and rotary embedding included: the output
    and the gradients of the input and of every weight (phi and mu_k
    among them), two rows with their own documents."""
    if path == "kernel":
        _on_the_kernel(monkeypatch)
    cfg = _cfg(*SIZES[sizes])
    rows = [_cut(r, cfg.seq_len) for r in (_row(), _row((107, 7, 150, 200)))]
    seg = jnp.asarray(np.stack([r[0] for r in rows]))
    pos = jnp.asarray(np.stack([r[1] for r in rows]))
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, cfg.seq_len, cfg.hidden_size)),
                    jnp.float32)
    w = jnp.asarray(rng.normal(size=x.shape), jnp.float32)
    module = EvaAttention(cfg=cfg, dtype=jnp.float32, init_std=0.2)
    params = init_module(module, x, pos, seg)["params"]
    real = (seg > 0)[..., None]

    def ours(p, x):
        return jnp.sum(jnp.where(real, module.apply({"params": p}, x, pos,
                                                    seg) * w, 0.0))

    def plain(p, x):
        with jax.default_matmul_precision("highest"):
            return sum(jnp.sum(jnp.where(real[b], ref.eva_attention(
                p, x[b], pos[b], seg[b], cfg, HEADS) * w[b], 0.0))
                for b in range(2))

    got = jax.jit(jax.value_and_grad(ours, argnums=(0, 1)))(params, x)
    want = jax.jit(jax.value_and_grad(plain, argnums=(0, 1)))(params, x)
    assert abs(float(got[0]) - float(want[0])) < 1e-4 * abs(float(want[0]))
    assert set(got[1][0]) == {"wq", "wk", "wv", "wo", "phi", "mu_k"}
    for (p, a), b in zip(jax.tree_util.tree_flatten_with_path(got[1])[0],
                         jax.tree.leaves(want[1])):
        assert float(jnp.linalg.norm(b)) > 0, jax.tree_util.keystr(p)
        assert rel(a, b) < 1e-4, (jax.tree_util.keystr(p), rel(a, b))


def _qkv(seed=0, rows=1, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    arr = lambda *shape: jnp.asarray(rng.normal(size=shape),  # noqa: E731
                                     jnp.float32).astype(dtype)
    return (arr(rows, S, HEADS, HD), arr(rows, S, HEADS, HD),
            arr(rows, S, HEADS, HD))


def _dense(q, k, v, seg, scale, window=None):
    """(out, lse) of one row by the whole score matrix."""
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale
    t = jnp.arange(q.shape[0])
    back = t[:, None] - t[None, :]
    visible = (back >= 0) & (seg[:, None] == seg[None, :])
    if window is not None:
        visible &= back < window
    s = jnp.where(visible[None], s, -jnp.inf)
    return (jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v),
            jax.nn.logsumexp(s, axis=-1).T)


@pytest.mark.parametrize("window", [None, 48])
@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_the_log_sum_exp_is_an_output_with_its_own_gradient(
        path, window, kernel_blocks_of_128):
    """`return_lse=True`: the output as without it, the rows' log-sum-exp
    beside it, and the gradient of a loss that reads both."""
    seg = jnp.asarray(_row()[0][None])
    q, k, v = _qkv()
    rng = np.random.default_rng(5)
    w = jnp.asarray(rng.normal(size=q.shape), jnp.float32)
    u = jnp.asarray(rng.normal(size=q.shape[:3]), jnp.float32)
    run = (functools.partial(la.flash_document_attention, interpret=True)
           if path == "kernel"
           else functools.partial(la.xla_document_attention, block=128))
    scale = HD ** -0.5

    def ours(q, k, v):
        out, lse = run(q, k, v, seg, scale=scale, window=window,
                       return_lse=True)
        return jnp.sum(out * w) + jnp.sum(lse * u), (out, lse)

    def plain(q, k, v):
        out, lse = _dense(q[0], k[0], v[0], seg[0], scale, window)
        return jnp.sum(out * w[0]) + jnp.sum(lse * u[0]), (out, lse)

    (_, (out, lse)), grads = jax.jit(jax.value_and_grad(
        ours, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    (_, (want_out, want_lse)), want = jax.jit(jax.value_and_grad(
        plain, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    assert lse.shape == (1, S, HEADS) and lse.dtype == jnp.float32
    assert rel(out[0], want_out) < 1e-5 and rel(lse[0], want_lse) < 1e-5
    for name, a, b in zip("qkv", grads, want):
        assert rel(a, b) < 1e-5, (name, rel(a, b))
    # and the output alone is what it is without the second output
    alone = run(q, k, v, seg, scale=scale, window=window)
    assert rel(alone, out) < 1e-6


def _eva(q, k, v, phi, mu, seg, window, chunk):
    return lm_eva.eva_attention(q, k, v, phi, mu, seg, window=window,
                                chunk=chunk, scale=HD ** -0.5, block=128)


@pytest.mark.parametrize("limit", ["one_window", "chunks_of_one"])
def test_the_two_limits_are_document_attention(limit):
    """A window as long as the row has no summaries: the causal softmax
    within documents, exactly. Chunks of one position with mu = 0 are the
    keys themselves: the earlier windows' keys come back through the
    summaries, and the whole is that softmax again."""
    seg = jnp.asarray(np.stack([_row()[0], _row((300, 7, 150))[0]]))
    q, k, v = _qkv(3, rows=2)
    rng = np.random.default_rng(4)
    phi = jnp.asarray(rng.normal(size=(HEADS, HD)), jnp.float32)
    mu = jnp.asarray(rng.normal(size=(HEADS, HD)), jnp.float32)
    w = jnp.asarray(rng.normal(size=q.shape), jnp.float32)
    real = (seg > 0)[..., None, None]
    window, chunk, mu = ((S, 16, mu) if limit == "one_window"
                         else (32, 1, jnp.zeros_like(mu)))

    def ours(q, k, v):
        return jnp.sum(jnp.where(real, _eva(q, k, v, phi, mu, seg, window,
                                            chunk) * w, 0.0))

    def theirs(q, k, v):
        return jnp.sum(jnp.where(real, la.document_attention(
            q, k, v, seg, scale=HD ** -0.5, block=128) * w, 0.0))

    got = jax.jit(jax.value_and_grad(ours, argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.value_and_grad(theirs, argnums=(0, 1, 2)))(q, k, v)
    tol = 1e-6 if limit == "one_window" else 1e-5
    assert abs(float(got[0]) - float(want[0])) < tol * abs(float(want[0]))
    for a, b in zip(got[1], want[1]):
        assert rel(a, b) < tol


@pytest.mark.parametrize("sizes", ["window32_chunk4", "window128_chunk16"])
def test_a_document_reads_what_it_reads_alone_at_the_same_offset(sizes):
    """The reference's definition: a document's outputs are what the
    layer gives that document alone at the same row offset with every
    other position pad. For the reference and for the program."""
    cfg = _cfg(*SIZES[sizes])
    seg, pos = _cut(_row(), cfg.seq_len)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(cfg.seq_len, cfg.hidden_size)),
                    jnp.float32)
    module = EvaAttention(cfg=cfg, dtype=jnp.float32, init_std=0.2)
    params = init_module(module, x[None], pos[None], seg[None])["params"]
    plain = jax.jit(lambda pos, seg: ref.eva_attention(params, x, pos, seg,
                                                       cfg, HEADS))
    ours = jax.jit(lambda pos, seg: module.apply(
        {"params": params}, x[None], pos[None], seg[None])[0])
    packed_ref = plain(jnp.asarray(pos), jnp.asarray(seg))
    packed = ours(jnp.asarray(pos), jnp.asarray(seg))
    for doc in range(1, int(seg.max()) + 1):
        mine = seg == doc
        alone = (jnp.asarray(np.where(mine, pos, 0)),
                 jnp.asarray(np.where(mine, seg, 0)))
        assert rel(packed_ref[mine], plain(*alone)[mine]) < 1e-5, doc
        assert rel(packed[mine], ours(*alone)[mine]) < 1e-5, doc


@pytest.mark.parametrize("ahead", [1, 3, 8])
def test_multi_byte_targets_never_cross_a_document_or_the_rows_end(ahead):
    seg, _ = _row((5, 1, 9, 4), seq=24)
    tokens = np.arange(100, 124, dtype=np.int32)
    targets, weight = next_token_targets(jnp.asarray(tokens[None]),
                                         jnp.asarray(seg[None]), ahead)
    if ahead == 1:
        targets, weight = targets[..., None], weight[..., None]
    assert targets.shape == weight.shape == (1, 24, ahead)
    total = 0
    for n in range(24):
        for j in range(ahead):
            m = n + 1 + j
            valid = m < 24 and seg[n] > 0 and seg[m] == seg[n]
            assert bool(weight[0, n, j]) == valid, (n, j)
            if valid:
                assert int(targets[0, n, j]) == tokens[m]
                total += 1
    assert int(weight.sum()) == total
    ref_targets, ref_valid = ref._targets(jnp.asarray(tokens),
                                          jnp.asarray(seg), ahead)
    assert np.array_equal(np.asarray(ref_valid), np.asarray(weight[0]) > 0)
    assert np.array_equal(np.asarray(ref_targets)[np.asarray(ref_valid)],
                          np.asarray(targets[0])[np.asarray(weight[0]) > 0])


@pytest.mark.parametrize("window,chunk", [(8, 2), (32, 4), (16, 16), (64, 1)])
def test_the_steps_pair_counters_equal_a_brute_force_count(window, chunk):
    rows = [_row((41, 59, 20), seq=128)[0], _row((3, 1, 100), seq=128)[0],
            _row((128,), seq=128)[0], _row((), seq=128)[0]]
    for seg in rows:
        got = lm_eva.pair_counts(jnp.asarray(seg[None]), window=window,
                                 chunk=chunk)
        want = brute_force_eva_pairs(seg, window, chunk)
        assert tuple(int(x) for x in got) == (want["local"], want["remote"])
    both = lm_eva.pair_counts(jnp.asarray(np.stack(rows)), window=window,
                              chunk=chunk)
    each = [brute_force_eva_pairs(s, window, chunk) for s in rows]
    assert tuple(int(x) for x in both) == (sum(p["local"] for p in each),
                                           sum(p["remote"] for p in each))


def test_the_step_carries_the_mixers_counters():
    """Block pairs from the table the exact part's kernel is handed (ids
    that separate document and window), and the pairs the batch needs,
    each summed over the layers; no expert layer: no slot held, none
    dropped."""
    from dexiraft_tpu.models.lm.model import COUNTERS, _attention_counters

    seg = jnp.asarray(np.tile(_row((2048,), seq=2048)[0], (1, 1)))
    # widths the kernel takes: a row of four of the chip's blocks, one
    # document, windows of two blocks
    cfg = evabyte_toy(hidden_size=8 * 128, seq_len=2048, window_size=1024,
                      chunk_size=16, num_hidden_layers=3)
    got = {k: int(v) for k, v in _attention_counters(cfg, seg).items()}
    assert set(got) <= set(COUNTERS)
    # a window's two blocks: 3 block pairs; two windows; the triangle 10
    assert got["attn_block_pairs_visited_local"] == 3 * 2 * 3
    assert got["attn_block_pairs_causal"] == 3 * 10
    assert got["eva_pairs_local"] == 3 * 2 * (1024 * 1025 // 2)
    assert got["eva_pairs_remote"] == 3 * 1024 * 64
    toy = _attention_counters(evabyte_toy(), jnp.asarray(_row(
        (50, 40, 30), seq=128)[0][None]))
    assert int(toy["attn_block_pairs_visited_local"]) == 4  # toy widths


def test_rows_that_are_not_whole_chunks_are_padded(monkeypatch):
    """`init`'s dummy row of 8 positions under chunks of 16, and a row of
    one and a half windows: pad to whole chunks and windows, outputs of
    the row's own positions."""
    rng = np.random.default_rng(0)
    for seq, window, chunk in ((8, 2048, 16), (48, 32, 4)):
        arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
        q, k, v = (arr(1, seq, HEADS, 8) for _ in range(3))
        phi, mu = arr(HEADS, 8), arr(HEADS, 8)
        seg = jnp.ones((1, seq), jnp.int32)
        out = lm_eva.eva_attention(q, k, v, phi, mu, seg, window=window,
                                   chunk=chunk, scale=0.3, block=128)
        assert out.shape == q.shape and bool(jnp.all(jnp.isfinite(out)))
        whole = lm_eva.whole_rows(seq, window, chunk)
        pad = lambda x: jnp.pad(x, [(0, 0), (0, whole - seq)]  # noqa: E731
                                + [(0, 0)] * (x.ndim - 2))
        want = lm_eva.eva_attention(pad(q), pad(k), pad(v), phi, mu, pad(seg),
                                    window=window, chunk=chunk, scale=0.3,
                                    block=128)[:, :seq]
        assert rel(out, want) < 1e-6
