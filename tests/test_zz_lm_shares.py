"""The shares test: the parts of a layer's output that all 8 chips of
the stated deployment give (heads 8 ways, routed experts 8 ways), with
what every chip computes alike (the shared experts) counted once, add up
to what the uncut reference gives for the whole layer. For both
architectures: kanana2's latent attention first, then trinity's gated
grouped-query attention, where two chips hold copies of one key/value
head, then evabyte's chunked linear attention over 4 chips, whose dense
layer every chip holds whole, then lfm2's two kinds of layer over 4
chips, whose convolution mixer every chip holds whole, then
smallthinker's two kinds of layer over 4 chips, each a whole group of 7
query heads, the routing made on the layer's input.

Each share runs the SYSTEM's modules (models/lm) on its slice of the
whole model's weights; the whole is the plain reference holding every
head and expert. fp32, so 2e-5 relative (seen 1e-6): float32 sums in
another order.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dexiraft_tpu.interop import lm_reference as ref
from dexiraft_tpu.models.lm.attention import LatentAttention
from dexiraft_tpu.models.lm.layers import SwiGLU
from dexiraft_tpu.models.lm.moe import RoutedExperts

from _lm_common import packed_batch, rel, seeded, toy

SHARES = 8


@pytest.fixture(scope="module")
def whole():
    cfg = toy()
    _, params, stats = seeded(cfg)
    batch = packed_batch(cfg, rows=1)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, cfg.seq_len,
                                                  cfg.hidden_size))
    return cfg, params, batch, x


def _share_cfg(i):
    return toy(heads_held=(i, 1), experts_held=(2 * i, 2))


def _attention_part(i, whole):
    cfg, params, batch, x = whole
    share = _share_cfg(i)
    p = ref.take_share(params, cfg, share.heads_held, share.experts_held)
    return LatentAttention(cfg=share).apply(
        {"params": p["layers_1"]["attn"]}, x, batch["positions"],
        batch["segment_ids"])[0]


def _routed_part(i, whole):
    cfg, params, batch, x = whole
    share = _share_cfg(i)
    p = ref.take_share(params, cfg, share.heads_held, share.experts_held)
    out, counters = RoutedExperts(cfg=share).apply(
        {"params": p["layers_1"]["moe"]["experts"]}, x[0],
        mutable=["batch_stats"])[0]
    assert int(counters["moe_dropped_slots"]) == 0
    return out, counters


@pytest.mark.parametrize("i", range(SHARES))
def test_a_share_equals_the_reference_given_that_share(i, whole):
    cfg, params, batch, x = whole
    share = _share_cfg(i)
    p = ref.take_share(params, cfg, share.heads_held, share.experts_held)
    lp = p["layers_1"]
    want_attn = ref.attention(lp["attn"], x[0], batch["positions"][0],
                              batch["segment_ids"][0], cfg, heads=1)
    assert rel(_attention_part(i, whole), want_attn) < 2e-5
    want_moe = ref.moe(lp["moe"], x[0], cfg, share.experts_held)
    routed, _ = _routed_part(i, whole)
    shared = SwiGLU(width=cfg.n_shared_experts * cfg.moe_intermediate_size
                    ).apply({"params": lp["moe"]["shared"]}, x[0])
    assert rel(routed + shared, want_moe) < 2e-5


def test_attention_parts_of_all_shares_add_up_to_the_whole(whole):
    cfg, params, batch, x = whole
    total = sum(_attention_part(i, whole) for i in range(SHARES))
    want = ref.attention(params["layers_1"]["attn"], x[0],
                         batch["positions"][0], batch["segment_ids"][0], cfg,
                         heads=cfg.num_attention_heads)
    assert rel(total, want) < 2e-5


def test_routed_parts_add_up_with_the_shared_experts_counted_once(whole):
    cfg, params, batch, x = whole
    parts = [_routed_part(i, whole) for i in range(SHARES)]
    shared = SwiGLU(width=cfg.n_shared_experts * cfg.moe_intermediate_size
                    ).apply({"params": params["layers_1"]["moe"]["shared"]},
                            x[0])
    total = sum(out for out, _ in parts) + shared
    want = ref.moe(params["layers_1"]["moe"], x[0], cfg,
                   (0, cfg.n_routed_experts))
    assert rel(total, want) < 2e-5
    # every slot of every token lands on exactly one chip
    slots = sum(int(c["moe_slots_held"]) for _, c in parts)
    assert slots == cfg.seq_len * cfg.num_experts_per_tok


def test_layer_outputs_of_the_shares_add_up_to_the_whole_layer(whole):
    """x + sum of the attention parts = h; h + routed parts + shared once
    = the uncut reference's layer output."""
    cfg, params, batch, x = whole
    lp = params["layers_1"]
    want = ref.layer(lp, x[0], batch["positions"][0],
                     batch["segment_ids"][0], cfg, dense=False)
    normed = ref._rms_norm(x[0], lp["attn_norm"], cfg.rms_norm_eps)
    h = x[0]
    routed = 0.0
    for i in range(SHARES):
        share = _share_cfg(i)
        p = ref.take_share(params, cfg, share.heads_held, share.experts_held)
        h = h + LatentAttention(cfg=share).apply(
            {"params": p["layers_1"]["attn"]}, normed[None],
            batch["positions"], batch["segment_ids"])[0]
    ffn_in = ref._rms_norm(h, lp["ffn_norm"], cfg.rms_norm_eps)
    for i in range(SHARES):
        share = _share_cfg(i)
        p = ref.take_share(params, cfg, share.heads_held, share.experts_held)
        routed = routed + RoutedExperts(cfg=share).apply(
            {"params": p["layers_1"]["moe"]["experts"]}, ffn_in,
            mutable=["batch_stats"])[0][0]
    shared = SwiGLU(width=cfg.n_shared_experts * cfg.moe_intermediate_size
                    ).apply({"params": lp["moe"]["shared"]}, ffn_in)
    assert rel(h + routed + shared, want) < 2e-5


# ---- the second architecture: gated grouped-query attention ---------------
#
# 8 query heads over 4 key/value heads: share i holds query head i and a
# copy of key/value head i // 2, which share i ^ 1 holds too, and experts
# 2i, 2i + 1. Layer 1 is a sliding expert layer, layer 4 the full layer.

AFMOE_LAYERS = {"sliding": "layers_1", "full": "layers_4"}


@pytest.fixture(scope="module")
def afmoe_whole():
    cfg = toy("trinity")
    _, params, _ = seeded(cfg)
    batch = packed_batch(cfg, rows=1)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, cfg.seq_len,
                                                  cfg.hidden_size))
    return cfg, params, batch, x


def _afmoe_share(i, whole):
    """(the share's configuration, its parameter tree)."""
    cfg, params, _, _ = whole
    share = toy("trinity", heads_held=(i, 1), experts_held=(2 * i, 2))
    assert share.kv_heads_held == (i // 2, 1)
    return share, ref.take_share(params, cfg, share.heads_held,
                                 share.experts_held, share.kv_heads_held)


def _gated_part(i, whole, layer, x):
    from dexiraft_tpu.models.lm.attention import mixer_of

    _, _, batch, _ = whole
    share, p = _afmoe_share(i, whole)
    index = int(layer.split("_")[1])
    return mixer_of(share, index).apply(
        {"params": p[layer]["attn"]}, x, batch["positions"],
        batch["segment_ids"])[0]


def _afmoe_routed_part(i, whole, layer, rows):
    share, p = _afmoe_share(i, whole)
    out, counters = RoutedExperts(cfg=share).apply(
        {"params": p[layer]["moe"]["experts"]}, rows,
        mutable=["batch_stats"])[0]
    assert int(counters["moe_dropped_slots"]) == 0
    return out, counters


@pytest.mark.parametrize("kind", list(AFMOE_LAYERS))
@pytest.mark.parametrize("i", range(SHARES))
def test_a_gated_share_equals_the_reference_given_that_share(i, kind,
                                                             afmoe_whole):
    cfg, _, batch, x = afmoe_whole
    layer = AFMOE_LAYERS[kind]
    share, p = _afmoe_share(i, afmoe_whole)
    index = int(layer.split("_")[1])
    want = ref.gated_attention(
        p[layer]["attn"], x[0], batch["positions"][0],
        batch["segment_ids"][0], cfg, 1, 1, cfg.layer_window(index))
    assert rel(_gated_part(i, afmoe_whole, layer, x), want) < 2e-5
    want_moe = ref.moe(p[layer]["moe"], x[0], cfg, share.experts_held)
    routed, _ = _afmoe_routed_part(i, afmoe_whole, layer, x[0])
    shared = SwiGLU(width=cfg.moe_intermediate_size).apply(
        {"params": p[layer]["moe"]["shared"]}, x[0])
    assert rel(routed + shared, want_moe) < 2e-5


@pytest.mark.parametrize("kind", list(AFMOE_LAYERS))
def test_gated_attention_parts_of_all_shares_add_up_to_the_whole(
        kind, afmoe_whole):
    """A key/value head's two copies each serve their own query head:
    the eight parts are the uncut attention's eight heads."""
    cfg, params, batch, x = afmoe_whole
    layer = AFMOE_LAYERS[kind]
    total = sum(_gated_part(i, afmoe_whole, layer, x) for i in range(SHARES))
    want = ref.gated_attention(
        params[layer]["attn"], x[0], batch["positions"][0],
        batch["segment_ids"][0], cfg, cfg.num_attention_heads,
        cfg.num_key_value_heads, cfg.layer_window(int(layer.split("_")[1])))
    assert rel(total, want) < 2e-5


@pytest.mark.parametrize("kind", list(AFMOE_LAYERS))
def test_afmoe_layer_outputs_of_the_shares_add_up_to_the_whole_layer(
        kind, afmoe_whole):
    """x + N2(sum of the attention parts) = h; h + N4(routed parts +
    the shared expert once) = the uncut reference's layer output, for a
    sliding expert layer and the full layer: the norms of what a half
    adds come after the sum over the chips."""
    cfg, params, batch, x = afmoe_whole
    layer = AFMOE_LAYERS[kind]
    lp = params[layer]
    eps = cfg.rms_norm_eps
    want = ref.afmoe_layer(lp, x[0], batch["positions"][0],
                           batch["segment_ids"][0], cfg,
                           int(layer.split("_")[1]))
    normed = ref._rms_norm(x[0], lp["attn_norm"], eps)
    attn = sum(_gated_part(i, afmoe_whole, layer, normed[None])
               for i in range(SHARES))
    h = x[0] + ref._rms_norm(attn, lp["attn_post_norm"], eps)
    ffn_in = ref._rms_norm(h, lp["ffn_norm"], eps)
    parts = [_afmoe_routed_part(i, afmoe_whole, layer, ffn_in)
             for i in range(SHARES)]
    shared = SwiGLU(width=cfg.moe_intermediate_size).apply(
        {"params": lp["moe"]["shared"]}, ffn_in)
    ffn = sum(out for out, _ in parts) + shared
    got = h + ref._rms_norm(ffn, lp["ffn_post_norm"], eps)
    assert rel(got, want) < 2e-5
    # every slot of every token lands on exactly one chip
    assert sum(int(c["moe_slots_held"]) for _, c in parts) == (
        cfg.seq_len * cfg.num_experts_per_tok)


# ---- the third architecture: EVA chunked linear attention ------------------
#
# One of 4 chips that share each layer: 8 heads, 2 a share, with their own
# rows of phi and mu_k (a head's summaries are its own). The SwiGLU is
# whole on every chip and counted once.

EVA_SHARES = 4


@pytest.fixture(scope="module")
def eva_whole():
    cfg = toy("evabyte")
    _, params, _ = seeded(cfg)
    batch = packed_batch(cfg, rows=1)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, cfg.seq_len,
                                                  cfg.hidden_size))
    return cfg, params, batch, x


def _eva_share(i, whole):
    cfg, params, _, _ = whole
    share = toy("evabyte", heads_held=(2 * i, 2))
    return share, ref.take_share(params, cfg, share.heads_held)


def _eva_part(i, whole, x):
    from dexiraft_tpu.models.lm.attention import mixer_of

    _, _, batch, _ = whole
    share, p = _eva_share(i, whole)
    return mixer_of(share, 1).apply(
        {"params": p["layers_1"]["attn"]}, x, batch["positions"],
        batch["segment_ids"])[0]


@pytest.mark.parametrize("i", range(EVA_SHARES))
def test_an_eva_share_equals_the_reference_given_that_share(i, eva_whole):
    cfg, _, batch, x = eva_whole
    share, p = _eva_share(i, eva_whole)
    assert p["layers_1"]["attn"]["phi"].shape == (2, cfg.head_dim)
    want = ref.eva_attention(p["layers_1"]["attn"], x[0],
                             batch["positions"][0], batch["segment_ids"][0],
                             cfg, 2)
    assert rel(_eva_part(i, eva_whole, x), want) < 2e-5


def test_eva_attention_parts_of_all_shares_add_up_to_the_whole(eva_whole):
    cfg, params, batch, x = eva_whole
    total = sum(_eva_part(i, eva_whole, x) for i in range(EVA_SHARES))
    want = ref.eva_attention(params["layers_1"]["attn"], x[0],
                             batch["positions"][0], batch["segment_ids"][0],
                             cfg, cfg.num_attention_heads)
    assert rel(total, want) < 2e-5


def test_eva_layer_outputs_of_the_shares_add_up_to_the_whole_layer(eva_whole):
    """x + the four shares' attention parts = h; h + the SwiGLU, which
    every chip holds whole, counted once = the uncut reference's layer
    output (the norms' gains are 1 + g)."""
    cfg, params, batch, x = eva_whole
    lp = params["layers_1"]
    want = ref.eva_layer(lp, x[0], batch["positions"][0],
                         batch["segment_ids"][0], cfg)
    normed = ref._rms_norm(x[0], 1.0 + lp["attn_norm"], cfg.rms_norm_eps)
    h = x[0] + sum(_eva_part(i, eva_whole, normed[None])
                   for i in range(EVA_SHARES))
    ffn_in = ref._rms_norm(h, 1.0 + lp["ffn_norm"], cfg.rms_norm_eps)
    mlp = SwiGLU(width=cfg.intermediate_size).apply({"params": lp["mlp"]},
                                                    ffn_in)
    assert rel(h + mlp, want) < 2e-5
    # the whole model's tree cut to a share is the share's own tree
    share, p = _eva_share(1, eva_whole)
    from dexiraft_tpu.config import TrainConfig
    from dexiraft_tpu.train.family import family_of
    shapes, _ = jax.eval_shape(family_of(share, TrainConfig()).init,
                               jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, p) == jax.tree.map(
        lambda a: a.shape, shapes)


# ---- the fourth architecture: convolution and attention mixers -------------
#
# One of 4 chips that share each layer, as the cell's deployment: 16 query
# heads of 8 over 4 key/value heads, so share i holds the whole group of
# key/value head i (no copies) and experts 4i..4i+3. Three layers: after
# the dense one, layer 1 is the attention expert layer and layer 2 a
# convolution expert layer, whose mixer
# has no heads: every chip holds it whole and it is counted once. No
# shared expert.

LFM2_SHARES = 4
LFM2_LAYERS = {"attention": "layers_1", "conv": "layers_2"}
_LFM2_SIZE = dict(hidden_size=128, num_attention_heads=16,
                  num_key_value_heads=4, num_hidden_layers=3,
                  layer_types=("conv", "full_attention", "conv"))


@pytest.fixture(scope="module")
def lfm2_whole():
    cfg = toy("lfm2", **_LFM2_SIZE)
    _, params, _ = seeded(cfg)
    batch = packed_batch(cfg, rows=1)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, cfg.seq_len,
                                                  cfg.hidden_size))
    return cfg, params, batch, x


def _lfm2_share(i, whole):
    cfg, params, _, _ = whole
    share = toy("lfm2", heads_held=(4 * i, 4), experts_held=(4 * i, 4),
                **_LFM2_SIZE)
    assert share.kv_heads_held == (i, 1)
    return share, ref.take_share(params, cfg, share.heads_held,
                                 share.experts_held, share.kv_heads_held)


def _lfm2_mixer_part(i, whole, layer, x):
    from dexiraft_tpu.models.lm.attention import mixer_of

    _, _, batch, _ = whole
    share, p = _lfm2_share(i, whole)
    name = "conv" if layer == LFM2_LAYERS["conv"] else "attn"
    return mixer_of(share, int(layer.split("_")[1])).apply(
        {"params": p[layer][name]}, x, batch["positions"],
        batch["segment_ids"])[0]


def _lfm2_routed_part(i, whole, layer, rows):
    share, p = _lfm2_share(i, whole)
    out, counters = RoutedExperts(cfg=share).apply(
        {"params": p[layer]["moe"]["experts"]}, rows,
        mutable=["batch_stats"])[0]
    assert int(counters["moe_dropped_slots"]) == 0
    return out, counters


@pytest.mark.parametrize("i", range(LFM2_SHARES))
def test_an_lfm2_share_equals_the_reference_given_that_share(i, lfm2_whole):
    cfg, params, batch, x = lfm2_whole
    share, p = _lfm2_share(i, lfm2_whole)
    pos, seg = batch["positions"][0], batch["segment_ids"][0]
    want = ref.gated_attention(p["layers_1"]["attn"], x[0], pos, seg, cfg,
                               4, 1, None, gate=False, rope=True)
    assert rel(_lfm2_mixer_part(i, lfm2_whole, "layers_1", x), want) < 2e-5
    # the convolution mixer is the whole model's, whatever the share
    assert jax.tree.all(jax.tree.map(
        lambda a, b: a is b, p["layers_2"]["conv"],
        params["layers_2"]["conv"]))
    assert rel(_lfm2_mixer_part(i, lfm2_whole, "layers_2", x),
               ref.short_conv(params["layers_2"]["conv"], x[0], seg, cfg)
               ) < 2e-5
    assert "shared" not in p["layers_2"]["moe"]
    routed, _ = _lfm2_routed_part(i, lfm2_whole, "layers_2", x[0])
    assert rel(routed, ref.moe(p["layers_2"]["moe"], x[0], cfg,
                               share.experts_held)) < 2e-5


def test_lfm2_attention_parts_of_all_shares_add_up_to_the_whole(lfm2_whole):
    cfg, params, batch, x = lfm2_whole
    total = sum(_lfm2_mixer_part(i, lfm2_whole, "layers_1", x)
                for i in range(LFM2_SHARES))
    want = ref.gated_attention(
        params["layers_1"]["attn"], x[0], batch["positions"][0],
        batch["segment_ids"][0], cfg, cfg.num_attention_heads,
        cfg.num_key_value_heads, None, gate=False, rope=True)
    assert rel(total, want) < 2e-5


@pytest.mark.parametrize("kind", list(LFM2_LAYERS))
def test_lfm2_layer_outputs_of_the_shares_add_up_to_the_whole_layer(
        kind, lfm2_whole):
    """x + the mixer (the four attention parts summed, or the convolution
    counted once) = h; h + the four routed parts, and no shared expert =
    the uncut reference's layer output, for both kinds of expert layer."""
    cfg, params, batch, x = lfm2_whole
    layer = LFM2_LAYERS[kind]
    lp = params[layer]
    want = ref.lfm2_layer(lp, x[0], batch["positions"][0],
                          batch["segment_ids"][0], cfg,
                          int(layer.split("_")[1]))
    normed = ref._rms_norm(x[0], lp["attn_norm"], cfg.norm_eps)[None]
    if kind == "conv":
        h = x[0] + _lfm2_mixer_part(0, lfm2_whole, layer, normed)
    else:
        h = x[0] + sum(_lfm2_mixer_part(i, lfm2_whole, layer, normed)
                       for i in range(LFM2_SHARES))
    ffn_in = ref._rms_norm(h, lp["ffn_norm"], cfg.norm_eps)
    parts = [_lfm2_routed_part(i, lfm2_whole, layer, ffn_in)
             for i in range(LFM2_SHARES)]
    assert rel(h + sum(out for out, _ in parts), want) < 2e-5
    # every slot of every token lands on exactly one chip
    assert sum(int(c["moe_slots_held"]) for _, c in parts) == (
        cfg.seq_len * cfg.num_experts_per_tok)
    # the whole model's tree cut to a share is the share's own tree
    share, p = _lfm2_share(1, lfm2_whole)
    from dexiraft_tpu.config import TrainConfig
    from dexiraft_tpu.train.family import family_of
    shapes, _ = jax.eval_shape(family_of(share, TrainConfig()).init,
                               jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, p) == jax.tree.map(
        lambda a: a.shape, shapes)


# ---- the fifth architecture: groups of seven, the router ahead of attention
#
# One of 4 chips that share each layer, as the cell's deployment: 28 query
# heads of 8 over 4 key/value heads, so share i holds the whole group of 7
# of key/value head i (no copies) and experts 4i..4i+3 of 16. Layer 0 is
# the full layer (no positional embedding), layer 1 a sliding one (rotary
# embedding). The router reads the layer's input, the experts the norm of
# what follows attention; no shared expert.

ST_SHARES = 4
ST_LAYERS = {"full": "layers_0", "sliding": "layers_1"}
_ST_SIZE = dict(num_attention_heads=28, num_key_value_heads=4,
                moe_num_primary_experts=16, num_hidden_layers=2)


@pytest.fixture(scope="module")
def st_whole():
    cfg = toy("smallthinker", **_ST_SIZE)
    _, params, _ = seeded(cfg)
    batch = packed_batch(cfg, rows=1)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, cfg.seq_len,
                                                  cfg.hidden_size))
    return cfg, params, batch, x


def _st_share(i, whole):
    cfg, params, _, _ = whole
    share = toy("smallthinker", heads_held=(7 * i, 7),
                experts_held=(4 * i, 4), **_ST_SIZE)
    assert share.kv_heads_held == (i, 1)
    return share, ref.take_share(params, cfg, share.heads_held,
                                 share.experts_held, share.kv_heads_held)


def _st_attention_part(i, whole, layer, x):
    from dexiraft_tpu.models.lm.attention import mixer_of

    _, _, batch, _ = whole
    share, p = _st_share(i, whole)
    module = mixer_of(share, int(layer.split("_")[1]))
    return jax.jit(module.apply)({"params": p[layer]["attn"]}, x,
                                 batch["positions"], batch["segment_ids"])[0]


def _st_routed_part(i, whole, layer, x, u):
    """The share's experts on `u`, routed on `x`."""
    share, p = _st_share(i, whole)
    module = RoutedExperts(cfg=share)

    def run(v, x, u):
        return module.apply(v, u, module.apply(v, x, method="plan"))

    out, counters = jax.jit(run)({"params": p[layer]["moe"]["experts"]},
                                 x, u)
    assert int(counters["moe_dropped_slots"]) == 0
    return out, counters


@pytest.mark.parametrize("kind", list(ST_LAYERS))
def test_smallthinker_layer_outputs_of_the_shares_add_up_to_the_whole_layer(
        kind, st_whole):
    """x + the four attention parts = h; h + the four routed parts (each
    routed on x, fed N2(h)), and no shared expert = the uncut reference's
    layer output, for the full and for a sliding layer; each share's
    parts equal the reference given that share."""
    cfg, params, batch, x = st_whole
    layer = ST_LAYERS[kind]
    index = int(layer.split("_")[1])
    lp = params[layer]
    pos, seg = batch["positions"][0], batch["segment_ids"][0]
    want = ref.smallthinker_layer(lp, x[0], pos, seg, cfg, index)
    normed = ref._rms_norm(x[0], lp["attn_norm"], cfg.rms_norm_eps)[None]
    parts = [_st_attention_part(i, st_whole, layer, normed)
             for i in range(ST_SHARES)]
    for i, part in enumerate(parts):
        share, p = _st_share(i, st_whole)
        assert "q_norm" not in p[layer]["attn"]
        assert rel(part, ref.gated_attention(
            p[layer]["attn"], normed[0], pos, seg, cfg, 7, 1,
            cfg.layer_window(index), gate=False, rope=cfg.layer_rope(index),
            qk_norm=False)) < 2e-5
    h = x[0] + sum(parts)
    ffn_in = ref._rms_norm(h, lp["ffn_norm"], cfg.rms_norm_eps)
    routed = [_st_routed_part(i, st_whole, layer, x[0], ffn_in)
              for i in range(ST_SHARES)]
    for i, (out, _) in enumerate(routed):
        share, p = _st_share(i, st_whole)
        assert set(p[layer]["moe"]) == {"experts"}
        assert rel(out, ref.smallthinker_moe(
            p[layer]["moe"], x[0], ffn_in, cfg, share.experts_held)) < 2e-5
    assert rel(h + sum(out for out, _ in routed), want) < 2e-5
    # every slot of every token lands on exactly one chip
    assert sum(int(c["moe_slots_held"]) for _, c in routed) == (
        cfg.seq_len * cfg.moe_num_active_primary_experts)
    # the whole model's tree cut to a share is the share's own tree
    share, p = _st_share(1, st_whole)
    from dexiraft_tpu.config import TrainConfig
    from dexiraft_tpu.train.family import family_of
    shapes, _ = jax.eval_shape(family_of(share, TrainConfig()).init,
                               jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, p) == jax.tree.map(
        lambda a: a.shape, shapes)
