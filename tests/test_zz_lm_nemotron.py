"""What Nemotron-H added to the stack (docs/lm.md, "Nemotron-H's
equations"), at toy sizes on the CPU, each under jit: the whole model
against the plain reference's `nemotron_h` row on seeded weights (loss,
logits, every gradient leaf, so one of every layer kind), the expert
layer with two-matrix experts in a latent space against the plain path
(output, every leaf, every counter, several dispatch chunks), a layer of
one block, the controls' fields (the reference moves, the program does
not), the configuration's refusals, and the defaults that leave the
other five architectures as they were; and the test that ties the
share to the model (the shares' parts of a layer add up to the uncut
reference's layer).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dexiraft_tpu.config import (LM_VARIANTS, DecoderConfig,
                                 NemotronHConfig, TrainConfig, nemotron_h,
                                 nemotron_h_toy)
from dexiraft_tpu.interop import lm_reference as ref
from dexiraft_tpu.models.lm import moe
from dexiraft_tpu.models.lm.attention import mixer_of
from dexiraft_tpu.models.lm.model import COUNTERS, DecoderLayer
from dexiraft_tpu.train.family import family_of

from _lm_common import (packed_batch, reference_loss_and_grads, rel, seeded)
from _models import init_module

SHARE = dict(ssm_heads_held=(2, 4), heads_held=(4, 4), experts_held=(2, 6),
             shared_columns_held=(12, 24))


def _flat(tree):
    return {"/".join(str(getattr(k, "key", k)) for k in path): v
            for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.fixture(scope="module")
def model():
    cfg = nemotron_h_toy(**SHARE)
    family, params, stats = seeded(cfg)
    batch = packed_batch(cfg)
    (loss, (metrics, _)), grads = jax.jit(jax.value_and_grad(
        family.loss_fn, has_aux=True))(params, stats, batch,
                                       jax.random.PRNGKey(0))
    with jax.default_matmul_precision("highest"):
        want, want_grads = reference_loss_and_grads(params, batch, cfg)
    return cfg, family, params, stats, batch, (loss, metrics, grads), (
        want, want_grads)


def test_loss_and_counters_against_the_reference(model):
    cfg, _, _, _, batch, (loss, metrics, _), (want, _) = model
    assert abs(float(loss) - float(want)) < 2e-5 * abs(float(want))
    assert int(metrics["moe_dropped_slots"]) == 0
    # 2 rows x 3 documents, 2 Mamba-2 layers; starts at 0, 50, 90 fall in
    # chunks 0, 3, 5 of 16
    assert int(metrics["ssm_doc_starts"]) == 2 * 2 * 3
    assert int(metrics["ssm_chunks_reset"]) == 2 * 2 * 3
    assert {"ssm_doc_starts", "ssm_chunks_reset"} <= set(COUNTERS)
    assert "attn_block_pairs_visited_full" in metrics


def _leaves():
    cfg = nemotron_h_toy(**SHARE)
    shapes, _ = jax.eval_shape(family_of(cfg, TrainConfig()).init,
                               jax.random.PRNGKey(0))
    return sorted(_flat(shapes))


@pytest.mark.parametrize("leaf", _leaves())
def test_gradient_leaf_against_the_reference(leaf, model):
    *_, (_, _, grads), (_, want_grads) = model
    got, want = _flat(grads)[leaf], _flat(want_grads)[leaf]
    assert float(jnp.linalg.norm(want)) > 0, "a leaf nothing reaches"
    assert rel(got, want) < 5e-5


def test_the_tree_holds_one_norm_and_one_block_a_layer(model):
    cfg, _, params, stats, *_ = model
    kinds = {"M": {"attn_norm", "ssm"}, "*": {"attn_norm", "attn"},
             "E": {"ffn_norm", "moe"}}
    for i, letter in enumerate(cfg.hybrid_override_pattern):
        assert set(params[f"layers_{i}"]) == kinds[letter]
    expert = params["layers_1"]["moe"]
    assert set(expert) == {"experts", "shared", "latent_down", "latent_up"}
    assert set(expert["experts"]) == {"router", "w_up", "w_down"}
    assert expert["experts"]["w_up"].shape == (6, 32, 24)   # the latent width
    assert expert["experts"]["router"].shape == (64, 16)    # the hidden width
    assert expert["shared"]["w_up"].shape == (64, 24)       # the columns held
    assert set(params["layers_3"]["attn"]) == {"wq", "wk", "wv", "wo"}
    assert set(stats["layers_1"]["moe"]["experts"]) == {
        "e_score_correction_bias"}


def test_no_leaf_of_the_state_is_weakly_typed(model):
    """A weakly typed leaf (`jnp.log(1.0 + integers)`) comes back from
    the first step strongly typed, and the step then compiles a second
    time in every run: 75 s more of set-up at the cell's size and a
    second executable in the compile cache."""
    _, _, params, stats, *_ = model
    weak = [k for k, a in _flat({"params": params, "stats": stats}).items()
            if a.weak_type]
    assert not weak


def test_logits_against_the_reference(model):
    cfg, family, params, stats, batch, *_ = model
    got, _ = jax.jit(lambda p: family.model.apply(
        {"params": p, "batch_stats": stats}, batch["tokens"],
        batch["positions"], batch["segment_ids"], logits=True))(params)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: ref.logits(p, batch, cfg))(params)
    real = np.asarray(batch["segment_ids"]) > 0
    assert rel(np.asarray(got)[real], np.asarray(want)[real]) < 2e-5


def test_the_blocked_walk_is_the_gradient_of_the_loss(model):
    cfg, _, params, _, batch, _, (want, want_grads) = model
    with jax.default_matmul_precision("highest"):
        loss, grads = ref.blocked_loss_and_grads(params, batch, cfg,
                                                 block=32)
    assert abs(float(loss) - float(want)) < 1e-5 * abs(float(want))
    for k, g in _flat(grads).items():
        assert rel(g, _flat(want_grads)[k]) < 2e-5, k


@pytest.mark.parametrize("moe_chunk,stacked", [
    (16, None), (32, None), (32, 0), (64, None), (None, None)])
def test_two_matrix_experts_in_a_latent_space_against_the_plain_path(
        moe_chunk, stacked, monkeypatch):
    """`MoE` of the toy (16 experts top 3, 6 held, width 24, latent 32,
    24 shared columns) on rows of the hidden width: output, every
    parameter's gradient and the input's equal `nemotron_moe`'s; no slot
    dropped, the counters what the routing says, through one dispatch
    chunk and through several, the later chunks' branch around their
    checkpoint (the toy's copies are small) and, with no room for copies
    (`stacked` 0: the cell's size), inside it."""
    if stacked is not None:
        monkeypatch.setattr(moe, "_STACKED_BYTES", stacked)
    cfg = nemotron_h_toy(moe_chunk=moe_chunk, **SHARE)
    u = jax.random.normal(jax.random.PRNGKey(3), (96, cfg.hidden_size))
    module = moe.MoE(cfg=cfg, init_std=0.2)
    variables = init_module(module, u)
    w = jax.random.normal(jax.random.PRNGKey(9), u.shape)

    def mine(p, u):
        out, counters = module.apply(dict(variables, params=p), u)
        return jnp.sum(out * w), (out, counters)

    def theirs(p, u):
        out = ref.nemotron_moe(p, u, cfg, cfg.experts_held)
        return jnp.sum(out * w), out

    p = variables["params"]
    (_, (out, counters)), g = jax.jit(jax.value_and_grad(
        mine, argnums=(0, 1), has_aux=True))(p, u)
    with jax.default_matmul_precision("highest"):
        (_, want), want_g = jax.jit(jax.value_and_grad(
            theirs, argnums=(0, 1), has_aux=True))(p, u)
    assert rel(out, want) < 2e-5
    for k, a in _flat(g).items():
        assert rel(a, _flat(want_g)[k]) < 5e-5, k
    chosen, _ = ref.routing(p["experts"], u, cfg, None)
    held = (np.asarray(chosen) >= 2) & (np.asarray(chosen) < 8)
    loads = [int(np.sum(np.asarray(chosen) == e)) for e in range(2, 8)]
    assert {k: float(v) for k, v in counters.items()
            if k in ("moe_slots_held", "moe_load_max", "moe_load_mean",
                     "moe_dropped_slots", "moe_rows_live")} == {
        "moe_slots_held": held.sum(), "moe_load_max": max(loads),
        "moe_load_mean": pytest.approx(np.mean(loads)),
        "moe_dropped_slots": 0, "moe_rows_live": held.sum()}
    if moe_chunk in (16, 32):
        assert held.sum() > 64  # the load passed chunk 1: the scan ran


@pytest.mark.parametrize("stacked", [None, 0])
def test_every_token_on_one_held_expert_runs_every_chunk(stacked,
                                                         monkeypatch):
    """A router whose first held expert outscores everything: all 96 x 3
    slots' first choice lands there, the overflow takes them, none is
    dropped; with the later chunks' branch around and inside their
    checkpoint."""
    if stacked is not None:
        monkeypatch.setattr(moe, "_STACKED_BYTES", stacked)
    cfg = nemotron_h_toy(moe_chunk=16, **SHARE)
    u = jnp.abs(jax.random.normal(jax.random.PRNGKey(3),
                                  (96, cfg.hidden_size)))
    module = moe.MoE(cfg=cfg, init_std=0.2)
    variables = init_module(module, u)
    p = jax.tree.map(lambda a: a, variables["params"])
    p["experts"]["router"] = p["experts"]["router"].at[:, 2].set(5.0)
    out, counters = jax.jit(lambda p: module.apply(
        dict(variables, params=p), u))(p)
    with jax.default_matmul_precision("highest"):
        want = ref.nemotron_moe(p, u, cfg, cfg.experts_held)
    assert int(counters["moe_load_max"]) == 96
    assert int(counters["moe_dropped_slots"]) == 0
    assert rel(out, want) < 2e-5


# a control of the cell's check -> the fields the reference is given, and
# a layer of the toy's M E M * E whose block reads them
CONTROLS = {
    "no_state_carry": (dict(state_carry=False), 0),
    "state_across_documents": (dict(document_reset=False), 0),
    "norm_before_gate": (dict(gate_before_norm=False), 0),
    "rope_on_attention": (dict(attention_rope=True), 3),
    "router_reads_latent": (dict(router_reads_latent=True), 1),
    "relu_experts": (dict(mlp_hidden_act="relu"), 1),
    "gated_experts": (dict(gated_experts=True), 1),
    "no_D_skip": (dict(d_skip=False), 0),
    "without_layer_1": (dict(without_layer=1), 1),
}


def _program(family, params, stats, batch) -> str:
    return str(jax.make_jaxpr(family.loss_fn)(
        params, stats, batch, jax.random.PRNGKey(0)))


@pytest.fixture(scope="module")
def program(model):
    _, family, params, stats, batch, *_ = model
    return _program(family, params, stats, batch)


@pytest.mark.parametrize("control", list(CONTROLS))
def test_a_control_moves_the_reference_and_not_the_program(control, model,
                                                           program):
    """The fields a control sets are the reference's alone: the program
    traces to the same equations, letter for letter, and what the
    reference's layer adds to the stream moves, or a gradient of it
    (the state's carry shows in `A_log`'s and little elsewhere)."""
    cfg, _, params, stats, batch, *_ = model
    fields, index = CONTROLS[control]
    faulty = dataclasses.replace(cfg, **fields)
    assert _program(family_of(faulty, TrainConfig()), params, stats,
                    batch) == program
    x, w = jax.random.normal(jax.random.PRNGKey(7),
                             (2, cfg.seq_len, cfg.hidden_size))
    pos, seg = batch["positions"][0], batch["segment_ids"][0]

    def adds(c):
        def f(p):
            out = ref.nemotron_layer(p, x, pos, seg, c, index) - x
            return jnp.sum(out * w), out
        (_, out), grads = jax.jit(jax.value_and_grad(f, has_aux=True))(
            params[f"layers_{index}"])
        return dict(_flat(grads), out=out)

    with jax.default_matmul_precision("highest"):
        moved, want = adds(faulty), adds(cfg)
    assert max(rel(moved[k], a) for k, a in want.items()
               if float(jnp.linalg.norm(a)) > 0) > 0.05


def test_a_layer_of_one_block_adds_once_to_the_stream():
    """`DecoderLayer` of an `M` layer: x + Mixer(N(x)), one norm, no
    feed-forward part, no counters; of an `E` layer: x + Experts(N(x))."""
    cfg = nemotron_h_toy()
    batch = packed_batch(cfg, rows=1)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 128, 64))
    pos, seg = batch["positions"], batch["segment_ids"]
    for index, norm, tree in ((0, "attn_norm", "ssm"), (1, "ffn_norm", "moe")):
        layer = DecoderLayer(cfg=cfg, index=index, init_std=0.3)
        variables = init_module(layer, x, pos, seg)
        assert set(variables["params"]) == {norm, tree}
        out, counters = jax.jit(lambda v: layer.apply(v, x, pos, seg))(
            variables)
        assert bool(counters) == (tree == "moe")
        with jax.default_matmul_precision("highest"):
            want = ref.nemotron_layer(variables["params"], x[0], pos[0],
                                      seg[0], cfg, index)
        assert rel(out[0], want) < 2e-5


@pytest.mark.parametrize("fields,needle", [
    (dict(hybrid_override_pattern="MEM*"), "hybrid_override_pattern"),
    (dict(hybrid_override_pattern="MEMXE"), "hybrid_override_pattern"),
    (dict(num_hidden_layers=7, hybrid_override_pattern=None),
     "hybrid_override_pattern"),
    (dict(ssm_heads_held=(1, 2)), "splits a group"),
    (dict(ssm_heads_held=(0, 3)), "splits a group"),
    (dict(ssm_heads_held=(0, 4), ssm_groups_held=(1, 2)), "ssm_groups_held"),
    (dict(ssm_heads_held=(6, 4)), "ssm_heads_held"),
    (dict(shared_columns_held=(40, 24)), "shared_columns_held"),
    (dict(experts_held=(12, 8)), "experts_held"),
    (dict(heads_held=(6, 4)), "heads_held"),
    (dict(seq_len=120), "whole chunks"),
    (dict(mamba_num_heads=6), "groups"),
])
def test_the_configuration_refuses(fields, needle):
    with pytest.raises(ValueError, match=needle):
        nemotron_h_toy(**fields)


def test_the_published_configuration_and_its_answers():
    cfg = nemotron_h()
    pattern = cfg.hybrid_override_pattern
    assert (len(pattern), pattern.count("M"), pattern.count("E"),
            pattern.count("*")) == (88, 40, 40, 8)
    assert pattern[27:38] == "MEMEMEMEM*E"
    assert cfg.ssm_groups_held == (0, 8) and cfg.shared_width == 5376
    assert cfg.model_type == "nemotron_h" and cfg.model_type in ref._ARCHS
    assert (cfg.expert_gate, cfg.expert_act, cfg.moe_latent_size,
            cfg.first_k_dense_replace, cfg.qk_norm, cfg.rms_norm_eps) == (
        False, "relu2", 1024, 0, False, 1e-5)
    assert [cfg.layer_parts(i) for i in (0, 1, 7)] == [
        ("mixer",), ("ffn",), ("mixer",)]
    assert (cfg.mixer(0), cfg.mixer(7), cfg.layer_rope(7)) == (
        "mamba2", "gqa", False)
    assert LM_VARIANTS["nemotron-h"] is nemotron_h
    assert isinstance(LM_VARIANTS["nemotron-h-toy"](), NemotronHConfig)
    share = nemotron_h(
        num_hidden_layers=11, hybrid_override_pattern="MEMEMEMEM*E",
        ssm_heads_held=(0, 16), heads_held=(0, 4), kv_heads_held=(0, 1),
        experts_held=(0, 8), shared_columns_held=(0, 672), vocab_size=16_384)
    assert share.ssm_groups_held == (0, 1) and share.shared_width == 672
    assert moe.dispatch_chunk(32_768 * 22, 8, 512) == 16_384


def test_the_new_answers_default_to_what_the_older_configurations_are():
    base = DecoderConfig
    assert (base.expert_gate, base.moe_latent_size) == (True, 0)
    for name, make in LM_VARIANTS.items():
        cfg = make()
        if isinstance(cfg, NemotronHConfig):
            continue
        assert cfg.expert_gate and not cfg.moe_latent_size, name
        assert all(cfg.layer_parts(i) == ("mixer", "ffn")
                   for i in range(cfg.num_hidden_layers)), name
        if cfg.n_shared_experts:
            assert cfg.shared_width == (cfg.n_shared_experts
                                        * cfg.moe_intermediate_size), name


# ---- the share and the model (a late file of four long tests once; it
# rides in this one, which starts early: scripts/test_slowest.py) ----
#
# The test that ties Nemotron-H's share to the model: at a small size
# the parts of a layer's output that all 4 chips of a deployment give add
# up to what the uncut reference gives for the whole layer, for each of
# the three kinds of layer. Share i holds Mamba heads 2i, 2i+1 (one whole
# B/C group of the toy's 4, so the gated norm is the chip's own), query
# heads 2i, 2i+1 (shares 0, 1 and 2, 3 hold copies of one key/value
# head), experts 4i..4i+3 of 16 and columns 12i..12i+11 of the shared
# expert's 48; the router, the latent projections and the norms are whole
# on every chip and counted once. Each share runs the SYSTEM's modules
# (models/lm) on its slice of the whole model's weights
# (`lm_reference.take_share`). fp32, so 2e-5 relative.

SHARES = 4
LAYERS = {"mamba": 0, "experts": 1, "attention": 3}


@pytest.fixture(scope="module")
def whole():
    cfg = nemotron_h_toy()
    _, params, stats = seeded(cfg)
    batch = packed_batch(cfg, rows=1)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, cfg.seq_len,
                                                  cfg.hidden_size))
    return cfg, params, stats, batch, x


def _share(i, whole):
    cfg, params, *_ = whole
    share = nemotron_h_toy(ssm_heads_held=(2 * i, 2), heads_held=(2 * i, 2),
                           experts_held=(4 * i, 4),
                           shared_columns_held=(12 * i, 12))
    assert share.ssm_groups_held == (i, 1)
    assert share.kv_heads_held == (i // 2, 1)
    return share, ref.take_share(
        params, cfg, share.heads_held, share.experts_held,
        share.kv_heads_held, share.ssm_heads_held, share.shared_columns_held)


def _part(i, whole, kind, u):
    """Share i's block of layer `kind` on the normed input `u`."""
    _, _, stats, batch, _ = whole
    share, p = _share(i, whole)
    layer = f"layers_{LAYERS[kind]}"
    pos, seg = batch["positions"], batch["segment_ids"]
    if kind == "experts":
        out, counters = jax.jit(moe.MoE(cfg=share).apply)(
            {"params": p[layer]["moe"],
             "batch_stats": stats[layer]["moe"]}, u)
        assert int(counters["moe_dropped_slots"]) == 0
        return out[0], counters
    module = mixer_of(share, LAYERS[kind])
    return jax.jit(module.apply)({"params": p[layer][module.TREE]}, u,
                                 pos, seg)[0], None


@pytest.mark.parametrize("kind", list(LAYERS))
def test_layer_outputs_of_the_shares_add_up_to_the_whole_layer(kind, whole):
    cfg, params, _, batch, x = whole
    index = LAYERS[kind]
    lp = params[f"layers_{index}"]
    pos, seg = batch["positions"][0], batch["segment_ids"][0]
    with jax.default_matmul_precision("highest"):
        want = ref.nemotron_layer(lp, x[0], pos, seg, cfg, index)
    norm = "ffn_norm" if kind == "experts" else "attn_norm"
    u = ref._rms_norm(x[0], lp[norm], cfg.layer_norm_epsilon)[None]
    parts = [_part(i, whole, kind, u) for i in range(SHARES)]
    assert rel(x[0] + sum(out for out, _ in parts), want) < 2e-5
    # each share's part is the reference given that share
    for i, (out, _) in enumerate(parts):
        share, p = _share(i, whole)
        with jax.default_matmul_precision("highest"):
            alone = ref.nemotron_layer(p[f"layers_{index}"], x[0], pos, seg,
                                       share, index)
        assert rel(x[0] + out, alone) < 2e-5
    if kind == "experts":
        # every slot of every token lands on exactly one chip
        assert sum(int(c["moe_slots_held"]) for _, c in parts) == (
            cfg.seq_len * cfg.num_experts_per_tok)


def test_the_whole_models_tree_cut_to_a_share_is_the_shares_own_tree(whole):
    share, p = _share(1, whole)
    shapes, _ = jax.eval_shape(family_of(share, TrainConfig()).init,
                               jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, p) == jax.tree.map(
        lambda a: a.shape, shapes)
    # the held heads' own numbers: A_log is the model's head h, not 0..
    cfg, params, *_ = whole
    assert np.array_equal(np.asarray(p["layers_0"]["ssm"]["A_log"]),
                          np.asarray(params["layers_0"]["ssm"]["A_log"])[2:4])
