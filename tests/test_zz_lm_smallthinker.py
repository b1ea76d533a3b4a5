"""What SmallThinker added to the stack (docs/lm.md, "SmallThinker's
equations"), piece by piece at toy sizes on the CPU, each under jit:
the router's softmax over the chosen logits, the router on the layer's
input (its gradient reaches that tensor and not the experts'), the
ReLU-gated experts and their zeros, the attention module without
QK-norm, the configuration's refusals, and the defaults that leave the
other four architectures as they were. The whole stack against the
reference is tests/test_zz_lm_reference.py's (`_lm_common.ARCHS`).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dexiraft_tpu.config import (LM_VARIANTS, DecoderConfig,
                                 SmallThinkerConfig, smallthinker_21b,
                                 smallthinker_21b_toy)
from dexiraft_tpu.interop import lm_reference as ref
from dexiraft_tpu.models.lm import moe
from dexiraft_tpu.models.lm.attention import mixer_of
from dexiraft_tpu.models.lm.model import DecoderLayer

from _lm_common import packed_batch, rel
from _models import init_module


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_softmax_over_the_chosen_is_softmax_top_k_normalise(seed):
    """The program: the top 6 logits, a softmax over them. The published
    form as the reference writes it: a softmax over all 64, the top 6,
    divided by their sum."""
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(seed), (96, 64))
    chosen, got = jax.jit(lambda r: moe.route_softmax(r, 6, 1.0))(logits)
    top, want_chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), 6)
    assert np.array_equal(np.asarray(chosen), np.asarray(want_chosen))
    assert rel(got, top / jnp.sum(top, axis=-1, keepdims=True)) < 1e-6
    assert np.allclose(np.asarray(jnp.sum(got, axis=-1)), 1.0, atol=1e-6)


def _experts(cfg, seed=3):
    x, u = (jax.random.normal(jax.random.PRNGKey(seed + i),
                              (96, cfg.hidden_size)) for i in range(2))
    module = moe.MoE(cfg=cfg, init_std=0.2)
    return module, init_module(module, u), x, u


def test_the_routers_gradient_reaches_the_layers_input_and_not_the_experts():
    """out = Experts(u; routed on x). d out / d x flows through the
    router alone and d out / d u through the experts alone; both, the
    output and every parameter's gradient equal the reference's
    `smallthinker_moe(p, x, u)`."""
    cfg = smallthinker_21b_toy(experts_held=(2, 4))
    module, variables, x, u = _experts(cfg)
    assert set(variables) == {"params"}  # no bias buffer under a softmax
    w = jax.random.normal(jax.random.PRNGKey(9), u.shape)

    def mine(p, x, u):
        out, counters = module.apply(
            {"params": p}, u, module.apply({"params": p}, x, method="plan"))
        return jnp.sum(out * w), (out, counters)

    def theirs(p, x, u):
        out = ref.smallthinker_moe(p, x, u, cfg, cfg.experts_held)
        return jnp.sum(out * w), out

    p = variables["params"]
    (got, (out, counters)), g = jax.jit(jax.value_and_grad(
        mine, argnums=(0, 1, 2), has_aux=True))(p, x, u)
    with jax.default_matmul_precision("highest"):
        (want, ref_out), ref_g = jax.jit(jax.value_and_grad(
            theirs, argnums=(0, 1, 2), has_aux=True))(p, x, u)
    assert rel(out, ref_out) < 2e-5
    assert float(jnp.linalg.norm(g[1])) > 0  # the router's path into x
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(ref_g)):
        assert rel(a, b) < 2e-5
    assert int(counters["moe_dropped_slots"]) == 0
    # the check's control hands the reference another place for the
    # router: the program keeps the published one, the reference moves,
    # and what it gives is what the program gives routed on `u` itself
    after = dataclasses.replace(cfg, router_before_attention=False)
    assert after.router_reads == "layer"
    out2 = jax.jit(lambda p, u: module.apply({"params": p}, u)[0])(p, u)
    with jax.default_matmul_precision("highest"):
        want2 = ref.smallthinker_moe(p, x, u, after, after.experts_held)
    assert rel(out2, want2) < 2e-5 and rel(out2, ref_out) > 0.1


@dataclasses.dataclass(frozen=True)
class _RoutesOnTheExpertsInput(SmallThinkerConfig):
    router_reads = "ffn"


def test_a_layer_routes_on_its_input_ahead_of_the_mixer():
    """Through `DecoderLayer`: moving the tensor the experts read (the
    attention output's `wo` scaled) leaves the routing where it was; in
    a configuration whose router reads what its experts read, the same
    change moves it."""
    batch = packed_batch(smallthinker_21b_toy(), rows=1)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 128, 64))

    def slots(cfg, scale):
        layer = DecoderLayer(cfg=cfg, index=1, init_std=0.3)
        variables = init_module(layer, x, batch["positions"],
                                batch["segment_ids"])
        p = jax.tree.map(lambda a: a, variables["params"])
        p["attn"]["wo"] = p["attn"]["wo"] * scale
        _, counters = jax.jit(lambda p: layer.apply(
            {"params": p}, x, batch["positions"], batch["segment_ids"]))(p)
        return int(counters["moe_slots_held"]), int(counters["moe_load_max"])

    before = smallthinker_21b_toy(experts_held=(0, 3))
    assert slots(before, 1.0) == slots(before, 30.0)
    after = _RoutesOnTheExpertsInput(**dataclasses.asdict(before))
    assert slots(after, 1.0) != slots(after, 30.0)


def test_relu_experts_leave_half_of_the_gate_at_zero():
    """At random weights a ReLU gate is an exact zero on about half of
    the held slots' entries, which a SiLU's never is. By hand, on every
    token's two chosen experts: nothing in the step counts it (a sum
    that no change to the program could move: PERF.md, PR 45)."""
    cfg = smallthinker_21b_toy(experts_held=(0, 8))  # every slot held
    module, variables, x, u = _experts(cfg)
    _, counters = jax.jit(lambda v, u, x: module.apply(
        v, u, module.apply(v, x, method="plan")))(variables, u, x)
    assert int(counters["moe_slots_held"]) == 96 * 2
    assert set(counters) == set(moe.COUNTERS)
    e = variables["params"]["experts"]
    chosen, _ = moe.route_softmax(x @ e["router"], 2, 1.0)
    gates = jnp.einsum("td,tkdw->tkw", u, e["w_gate"][chosen])
    assert 0.4 < float(jnp.mean(moe.ACTS["relu"](gates) == 0)) < 0.6
    assert float(jnp.mean(moe.ACTS["silu"](gates) == 0)) == 0.0


@pytest.mark.parametrize("index", [0, 1])
def test_attention_without_qk_norm_matches_the_reference(index):
    """Layer 0: full, no positional embedding; layer 1: window 32, the
    rotary embedding. 7 query heads on key/value head 1, no `q_norm`,
    `k_norm` or `wg` parameters."""
    cfg = smallthinker_21b_toy(heads_held=(7, 7))
    batch = packed_batch(cfg, rows=1)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 128, 64))
    module = mixer_of(cfg, index, init_std=0.2)
    assert (module.window, module.rope) == ((None, False), (32, True))[index]
    variables = init_module(module, x, batch["positions"],
                            batch["segment_ids"])
    assert set(variables["params"]) == {"wq", "wk", "wv", "wo"}
    got = jax.jit(module.apply)(variables, x, batch["positions"],
                                batch["segment_ids"])
    with jax.default_matmul_precision("highest"):
        want = ref.gated_attention(
            variables["params"], x[0], batch["positions"][0],
            batch["segment_ids"][0], cfg, 7, 1, module.window, gate=False,
            rope=module.rope, qk_norm=False)
    real = np.asarray(batch["segment_ids"][0]) > 0
    assert rel(np.asarray(got[0])[real], np.asarray(want)[real]) < 2e-5


def test_the_published_configuration_and_its_refusals():
    cfg = smallthinker_21b()
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.head_dim,
            cfg.num_attention_heads, cfg.num_key_value_heads) == (
                52, 2560, 128, 28, 4)
    assert cfg.sliding_window_layout == cfg.rope_layout == (0, 1, 1, 1) * 13
    assert [cfg.layer_window(i) for i in range(5)] == [
        None, 4096, 4096, 4096, None]
    assert [cfg.layer_rope(i) for i in range(5)] == [
        False, True, True, True, False]
    assert (cfg.n_routed_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.n_shared_experts,
            cfg.first_k_dense_replace) == (64, 6, 768, 0, 0)
    assert (cfg.route_score, cfg.router_reads, cfg.expert_act, cfg.qk_norm,
            cfg.attention_gate, cfg.tie_embedding) == (
                "softmax", "layer", "relu", False, False, False)
    # the fields a control of the check changes are the reference's alone
    assert dataclasses.replace(
        cfg, moe_primary_router_apply_softmax=False, hidden_act="silu",
        router_before_attention=False).route_score == "softmax"
    assert (cfg.init_std, cfg.embed_init_std) == (0.02, 1.0)
    share = smallthinker_21b(heads_held=(14, 7), experts_held=(32, 16),
                             num_hidden_layers=4, vocab_size=37_984)
    assert share.kv_heads_held == (2, 1)
    assert share.rope_layout == (0, 1, 1, 1)
    for wrong, said in (
            (dict(heads_held=(0, 4)), "splits a group of 7"),
            (dict(heads_held=(3, 7)), "divide evenly"),
            (dict(num_hidden_layers=4, rope_layout=(0, 1, 1)), "rope_layout"),
            (dict(num_hidden_layers=4, sliding_window_layout=(0, 1, 2, 1)),
             "sliding_window_layout"),
            (dict(seq_len=1536), "attn_block"),
            (dict(experts_held=(60, 8)), "experts_held"),
            (dict(num_key_value_heads=5), "do not divide")):
        with pytest.raises(ValueError, match=said):
            smallthinker_21b(**wrong)


@pytest.mark.parametrize("variant", sorted(
    v for v in LM_VARIANTS
    if not v.startswith(("smallthinker", "nemotron"))))  # the four before it
def test_the_new_answers_leave_the_other_architectures_as_they_were(variant):
    cfg = LM_VARIANTS[variant]()
    assert (cfg.router_reads, cfg.route_score, cfg.expert_act, cfg.qk_norm,
            cfg.embed_init_std) == ("ffn", "sigmoid", "silu", True,
                                    cfg.init_std)
    assert (DecoderConfig.router_reads, DecoderConfig.route_score,
            DecoderConfig.expert_act, DecoderConfig.qk_norm) == (
                "ffn", "sigmoid", "silu", True)
    rope = [cfg.layer_rope(i) for i in range(cfg.num_hidden_layers)]
    if variant.startswith("trinity"):  # the rotary embedding under a window
        assert rope == [cfg.layer_window(i) is not None
                        for i in range(cfg.num_hidden_layers)]
    else:
        assert all(rope)
