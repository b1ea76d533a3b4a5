"""The fourth architecture's own mechanisms (`Lfm2MoeConfig`) on the CPU,
each against the plain reference (interop/lm_reference.py) or a count
written out: the doubly gated short convolution within documents
(ops/lm_conv.py), a document alone against the document in a packed row
through the whole toy stack, the attention mixer without a gate and with
the rotary embedding at 4 query heads a key/value head (XLA path, and
the kernel in Pallas's interpreter at heads of 64), the router's
`+ 1e-6` without a shared expert, the tied head, and the step's
`conv_taps_masked` counter.

Tolerances, relative in the 2-norm, fp32: both sides are float32
arithmetic of one mathematics in another order (a shifted copy under a
mask against a looked-up source position; blocks of attention against
full matrices): 2e-5 is 20x the 1e-6 seen; the kernel in interpret mode
at 1e-4 as tests/test_zz_lm_attention_kernel.py holds the other mixers.
"""

import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dexiraft_tpu.config import ROUTE_EPS
from dexiraft_tpu.interop import lm_reference as ref
from dexiraft_tpu.models.lm import LM
from dexiraft_tpu.models.lm import attention
from dexiraft_tpu.models.lm.moe import MoE, route
from dexiraft_tpu.ops import lm_attention as la
from dexiraft_tpu.ops.lm_conv import gated_short_conv, taps_masked

from _lm_common import packed_batch, rel, seeded, toy
from _models import init_module, jit_apply

H, L, T = 16, 3, 24


@functools.lru_cache(maxsize=None)
def _toy_state():
    """(cfg, family, params, batch_stats) of the toy, once a process."""
    cfg = toy("lfm2")
    return (cfg,) + seeded(cfg)


@functools.lru_cache(maxsize=None)
def _toy_step():
    """(batch of one row, ((loss, (metrics, stats)), gradients)) of the
    toy's loss: compiled once for the tests that read it."""
    cfg, family, params, stats = _toy_state()
    batch = packed_batch(cfg, rows=1)
    return batch, jax.jit(jax.value_and_grad(family.loss_fn, has_aux=True))(
        params, stats, batch, jax.random.PRNGKey(0))


@functools.lru_cache(maxsize=None)
def _toy_logits():
    """The toy stack's logits of a batch, compiled once."""
    cfg, _, params, stats = _toy_state()
    return jax.jit(lambda b: LM(cfg).apply(
        {"params": params, "batch_stats": stats}, b["tokens"],
        b["positions"], b["segment_ids"], logits=True)[0])


def _row(*lengths, total=T):
    """One row: documents of these lengths, ids 1, 2, ...; pad to
    `total`."""
    seg = np.zeros(total, np.int32)
    at = 0
    for i, n in enumerate(lengths, start=1):
        seg[at:at + n] = i
        at += n
    assert at <= total
    return seg


# documents 1, 2 and L tokens long starting at every offset of the row:
# a longer document in front is stepped through the lengths 0..8, so
# each short one starts at each of nine offsets, beside its neighbours
ROWS = {f"{n}_tokens_at_{at}": _row(*([at] if at else []), n, 5, n, n)
        for n in (1, 2, L) for at in range(9)}
ROWS["one_document"] = _row(T)
ROWS["all_pad"] = _row()


def _conv_inputs(seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    arr = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)  # noqa: E731
    return arr(1, T, H), arr(1, T, H), arr(1, T, H), arr(H, L)


def _reference_gate(b, c, z, taps, seg):
    """The reference's own lines for the gate alone: `ref.short_conv`
    given [B, C, z] side by side as its input, an identity W_in that
    hands each third on as it is, and an identity W_out."""
    x = jnp.concatenate([b[0], c[0], z[0]], axis=-1)               # [T, 3H]
    p = {"w_in": jnp.eye(3 * H, dtype=x.dtype),
         "w_out": jnp.eye(H, dtype=x.dtype), "taps": taps}

    class Cfg:
        conv_L_cache = L

    with jax.default_matmul_precision("highest"):
        return ref.short_conv(p, x, seg, Cfg)[None]


def _weighted(conv):
    """(out, its gradients by b, c, z, taps under the weights w) of a
    form of the gate: one compiled program for every row of documents."""
    def run(b, c, z, taps, seg, w):
        return conv(b, c, z, taps, seg), jax.grad(
            lambda *a: jnp.sum(conv(*a, seg) * w), argnums=(0, 1, 2, 3))(
                b, c, z, taps)
    return jax.jit(run)


_OURS = _weighted(gated_short_conv)
_PLAIN = _weighted(lambda b, c, z, taps, seg: _reference_gate(
    b, c, z, taps, seg[0]))


@pytest.mark.parametrize("row", list(ROWS))
def test_gated_short_conv_matches_the_reference_forward_and_backward(row):
    seg = jnp.asarray(ROWS[row][None])
    w = jnp.asarray(np.random.default_rng(1).normal(size=(1, T, H)),
                    jnp.float32)
    real = ROWS[row] > 0
    (got, ours), (want, plain) = (f(*_conv_inputs(), seg, w)
                                  for f in (_OURS, _PLAIN))
    got, want = np.asarray(got)[0], np.asarray(want)[0]
    if real.any():
        assert rel(got[real], want[real]) < 2e-5
    # pad positions (id 0) are a document to both, never read by one
    assert np.allclose(got, want, atol=1e-5)
    for name, a, g in zip("bczk", ours, plain):
        assert rel(a, g) < 2e-5, name


def test_the_one_tap_limit_is_three_products_a_position():
    """k[:, L-1] = 1 and the other taps 0: out = C * B * z, whatever the
    documents."""
    b, c, z, _ = _conv_inputs()
    taps = jnp.zeros((H, L)).at[:, L - 1].set(1.0)
    for seg in (ROWS["one_document"], ROWS["2_tokens_at_3"]):
        got = gated_short_conv(b, c, z, taps, jnp.asarray(seg[None]))
        assert rel(got, c * b * z) < 1e-6


def test_a_tap_never_reads_another_document_or_before_the_row():
    """Everything outside one document changed: its outputs stay, bit for
    bit; and the first tokens of the row read nothing before them."""
    seg = jnp.asarray(_row(4, 7, 6)[None])
    b, c, z, taps = _conv_inputs()
    inside = (np.asarray(seg[0]) == 2)[None, :, None]
    other = _conv_inputs(seed=9)
    mix = lambda a, o: jnp.where(inside, a, o)  # noqa: E731
    got = gated_short_conv(b, c, z, taps, seg)
    moved = gated_short_conv(mix(b, other[0]), mix(c, other[1]),
                             mix(z, other[2]), taps, seg)
    assert np.array_equal(np.asarray(got)[0, 4:11], np.asarray(moved)[0, 4:11])
    assert not np.allclose(np.asarray(got)[0, :4], np.asarray(moved)[0, :4])
    a = np.asarray(b * z)[0]
    k = np.asarray(taps)
    assert np.allclose(np.asarray(got)[0, 0], np.asarray(c)[0, 0] * k[:, 2]
                       * a[0], rtol=1e-5)
    assert np.allclose(np.asarray(got)[0, 1], np.asarray(c)[0, 1] * (
        k[:, 2] * a[1] + k[:, 1] * a[0]), rtol=1e-5)


def test_bf16_inputs_give_bf16_from_fp32_sums():
    seg = jnp.asarray(ROWS["3_tokens_at_2"][None])
    b, c, z, taps = _conv_inputs(dtype=jnp.bfloat16)
    got = gated_short_conv(b, c, z, taps, seg)
    want = gated_short_conv(*(a.astype(jnp.float32) for a in (b, c, z, taps)),
                            seg)
    assert got.dtype == jnp.bfloat16
    assert rel(got.astype(jnp.float32), want) < 2 ** -8


def _brute_force_taps_masked(seg_rows, length):
    masked = 0
    for seg in seg_rows:
        for n, d in enumerate(seg):
            if d == 0:
                continue
            masked += sum(1 for back in range(1, length)
                          if n - back < 0 or seg[n - back] != d)
    return masked


@pytest.mark.parametrize("length", [1, 2, 3, 5])
def test_taps_masked_equals_a_brute_force_count(length):
    rows = np.stack([ROWS["1_tokens_at_0"], ROWS["2_tokens_at_5"],
                     ROWS["3_tokens_at_8"], ROWS["one_document"],
                     ROWS["all_pad"]])
    assert int(taps_masked(jnp.asarray(rows), length)) == (
        _brute_force_taps_masked(rows.tolist(), length))


def test_the_step_carries_the_convolutions_counter():
    """`conv_taps_masked`: a layer's count times the four convolution
    layers of the toy, beside the attention layer's table."""
    cfg = _toy_state()[0]
    batch, ((_, (metrics, _)), _) = _toy_step()
    a_layer = _brute_force_taps_masked(
        np.asarray(batch["segment_ids"]).tolist(), cfg.conv_L_cache)
    assert a_layer == 3 * 3  # documents x L (L - 1) / 2
    assert int(metrics["conv_taps_masked"]) == 4 * a_layer
    assert int(metrics["attn_block_pairs_visited_full"]) >= 1
    assert "attn_block_pairs_visited_window" not in metrics
    assert int(metrics["moe_dropped_slots"]) == 0


@pytest.mark.parametrize("doc", [1, 2, 3])
def test_a_document_alone_equals_the_document_in_a_packed_row(doc):
    """The reference's definition, held on the program: through the
    whole toy stack (convolution and attention mixers, dense and expert
    layers, the tied head) a document's logits are what the same stack
    gives that document at the same row offset with every other position
    pad."""
    cfg, _, params, _ = _toy_state()
    batch = packed_batch(cfg, rows=1)
    seg = np.asarray(batch["segment_ids"])
    alone = {k: jnp.where(seg == doc, v, 0) for k, v in batch.items()}
    run = _toy_logits()
    packed, single = np.asarray(run(batch)), np.asarray(run(alone))
    assert rel(packed[seg == doc], single[seg == doc]) < 2e-5
    # and what the reference gives the document by itself, from offset 0
    at = np.flatnonzero(seg[0] == doc)
    cut = {k: v[:, at[0]:at[-1] + 1] for k, v in batch.items()}
    by_itself = jax.jit(lambda b: ref.logits(params, b, cfg))(cut)
    assert rel(packed[0, at], by_itself[0]) < 2e-5


def _mixer_case(cfg, seq):
    seg_row = np.zeros(seq, np.int32)
    for i, (a, b) in enumerate(((0, 40), (40, 100), (100, 300), (300, 384)),
                               start=1):
        seg_row[a:min(b, seq)] = i
    seg = jnp.asarray(seg_row[None])
    starts = np.maximum.accumulate(np.where(
        np.r_[True, seg_row[1:] != seg_row[:-1]], np.arange(seq), 0))
    pos = jnp.asarray((np.arange(seq) - starts)[None], jnp.int32)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(1, seq, cfg.hidden_size)), jnp.float32)
    w = jnp.asarray(rng.normal(size=x.shape), jnp.float32)
    return seg, pos, x, w


def _assert_attention_mixer_matches(cfg, tol):
    seg, pos, x, w = _mixer_case(cfg, cfg.seq_len)
    module = attention.mixer_of(cfg, 1, dtype=jnp.float32, init_std=0.2)
    assert isinstance(module, attention.GatedAttention)
    params = init_module(module, x, pos, seg)["params"]
    assert set(params) == {"wq", "wk", "wv", "wo", "q_norm", "k_norm"}
    heads, kv_heads = cfg.heads_held[1], cfg.kv_heads_held[1]

    def ours(p, x):
        return jnp.sum(module.apply({"params": p}, x, pos, seg) * w)

    def plain(p, x):
        with jax.default_matmul_precision("highest"):
            return jnp.sum(ref.gated_attention(
                p, x[0], pos[0], seg[0], cfg, heads, kv_heads, None,
                gate=False, rope=True) * w[0])

    got = jax.jit(jax.value_and_grad(ours, argnums=(0, 1)))(params, x)
    want = jax.jit(jax.value_and_grad(plain, argnums=(0, 1)))(params, x)
    assert abs(float(got[0]) - float(want[0])) < tol * abs(float(want[0]))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got[1])[0],
                            jax.tree.leaves(want[1])):
        assert rel(a, b) < tol, (jax.tree_util.keystr(path), rel(a, b))


def test_attention_mixer_matches_the_reference_on_the_xla_path():
    """Heads of 8, 4 query heads a key/value head, the share's one
    group: no gate, QK-norm, the rotary embedding on the full layer."""
    _assert_attention_mixer_matches(
        toy("lfm2", heads_held=(4, 4), seq_len=128), 2e-5)


def test_attention_mixer_matches_the_reference_on_the_kernel(monkeypatch):
    """The same at the cell's head width, 64, and group, 4 (8 query
    heads over 2 key/value heads), on the flash kernel in interpret
    mode: a width no other configuration runs it at."""
    monkeypatch.setattr(la, "_BLOCK_Q", 128)
    monkeypatch.setattr(la, "_BLOCK_K", 128)
    monkeypatch.setattr(
        attention, "document_attention",
        lambda q, k, v, seg, *, scale, block, window:
        la.flash_document_attention(q, k, v, seg, scale=scale, window=window,
                                    interpret=True))
    cfg = toy("lfm2", hidden_size=512, num_attention_heads=8,
              num_key_value_heads=2, seq_len=384, attn_block=128)
    assert cfg.head_dim == 64 and la.kernel_blocks(384, 64, 64) == (128, 128)
    _assert_attention_mixer_matches(cfg, 1e-4)


@pytest.mark.parametrize("normalise", [True, False])
def test_the_router_adds_its_epsilon_to_the_chosen_scores_sum(normalise):
    """`route_eps` 1e-6 against the reference's `routing`, and not
    1e-20: scores small enough that the two differ in the third digit."""
    cfg = toy("lfm2", norm_topk_prob=normalise)
    rng = np.random.default_rng(3)
    # logits near -15: sigmoid scores near 3e-7, sums of 2 under 1e-6
    x = jnp.asarray(rng.normal(size=(32, cfg.hidden_size)), jnp.float32)
    x = x.at[:, 0].set(1.0)
    router = jnp.asarray(rng.normal(size=(cfg.hidden_size, cfg.num_experts))
                         * 0.05, jnp.float32).at[0].add(-15.0)
    logits = x @ router
    zero = jnp.zeros((cfg.num_experts,))
    chosen, got = route(logits, zero, cfg.num_experts_per_tok,
                        cfg.routed_scaling_factor, normalise, cfg.route_eps)
    with jax.default_matmul_precision("highest"):
        want_chosen, want = ref.routing({"router": router}, x, cfg)
    assert np.array_equal(np.asarray(chosen), np.asarray(want_chosen))
    assert rel(got, want) < 1e-5
    if normalise:
        assert 0.1 < float(jnp.max(jnp.sum(got, axis=-1))) < 0.9
        untouched = route(logits, zero, cfg.num_experts_per_tok, 1.0, True,
                          ROUTE_EPS)[1]
        assert rel(untouched, want) > 0.1


def test_an_expert_layer_without_a_shared_expert_matches_the_reference():
    cfg = toy("lfm2", experts_held=(2, 4))
    assert cfg.n_shared_experts == 0
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 96, cfg.hidden_size))
    module = MoE(cfg=cfg, init_std=0.1)
    variables = init_module(module, x)
    assert set(variables["params"]) == {"experts"}  # nothing built for it
    (got, counters), _ = jit_apply(module)(variables, x,
                                           mutable=("batch_stats",))
    with jax.default_matmul_precision("highest"):
        want = ref.moe(variables["params"], x[0], cfg, cfg.experts_held)
    assert rel(got[0], want) < 2e-5
    assert int(counters["moe_dropped_slots"]) == 0


def test_the_head_is_the_embedding_and_an_untied_one_fails():
    """No `head` parameter; the logits are E . norm(x); the `embed`
    gradient is the gather's and the head's summed. The same stack with
    the head a parameter of its own, holding the same numbers, gives the
    same loss and splits that gradient in two: either part alone, which
    is what an untied program writes under `embed`, fails the limit."""
    cfg, _, params, _ = _toy_state()
    assert "head" not in params and cfg.tie_embedding
    batch, ((loss, _), grads) = _toy_step()

    # the reference goes by its own row, not by `cfg.tie_embedding`
    untied = ref._ARCHS[cfg.model_type]._replace(tied=False)
    with mock.patch.dict(ref._ARCHS, {cfg.model_type: untied}):
        loss_u, parts = jax.jit(lambda p: ref.loss_and_grads(p, batch, cfg))(
            dict(params, head=params["embed"].T))
    assert abs(float(loss_u) - float(loss)) < 2e-5 * float(loss)
    assert rel(grads["embed"], parts["embed"] + parts["head"].T) < 2e-5
    assert rel(grads["embed"], parts["embed"]) > 0.1
    assert rel(grads["embed"], parts["head"].T) > 0.1
