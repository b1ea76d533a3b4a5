"""The benchmark's analytic counts for the language-model cell
(benchmarks/lm_counts.py) and its scope reducer (benchmarks/lm_scopes.py).

`flops.py` walks a jaxpr and knows `dot_general`; the cell's count is
analytic because the expert layer's work follows the routing. The dense
parts (projections, shared experts, dense layer, head, router) are held
to the walk of the plain reference at the toy size, whose other products
have closed forms of their own: full `S x S` score matrices and every
held expert on every token. The routed part is slots x 3 x 2 x hidden x
width by hand.
"""

import os.path as osp
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, osp.dirname(osp.dirname(osp.abspath(__file__))))

from benchmarks import (flops, lm_counts, lm_counts_afmoe,  # noqa: E402
                        lm_counts_eva, lm_counts_lfm2, lm_counts_nemotron,
                        lm_counts_smallthinker, lm_scopes)
from dexiraft_tpu.config import (evabyte, kanana2, lfm2_8b_a1b,  # noqa: E402
                                 nemotron_h, nemotron_h_toy,
                                 smallthinker_21b, trinity_mini)
from dexiraft_tpu.interop import lm_reference as ref  # noqa: E402

from _lm_common import (SHARES, brute_force_eva_pairs,  # noqa: E402
                        packed_batch, seeded, toy)


def test_dense_parts_equal_the_walk_of_the_reference():
    cfg = toy(experts_held=(2, 4), heads_held=(1, 2))
    _, params, _ = seeded(cfg)
    batch = packed_batch(cfg)
    rows, s = batch["tokens"].shape
    walked = flops.count(lambda p: ref.loss(p, batch, cfg), params)
    per_token = sum(lm_counts.per_token_forward(cfg).values())
    heads = cfg.heads_held[1]
    moe_layers = cfg.num_hidden_layers - cfg.first_k_dense_replace
    # what the reference computes beyond the dense parts, in closed form:
    # whole score matrices, and each held expert on every token
    scores = rows * cfg.num_hidden_layers * heads * s * s * 2 * (
        cfg.qk_nope_head_dim + cfg.qk_rope_head_dim + cfg.v_head_dim)
    experts = (rows * s * moe_layers * cfg.experts_held[1]
               * lm_counts.per_slot_forward(cfg))
    assert walked == per_token * rows * s + scores + experts


def test_routed_part_is_slots_times_three_products_by_hand():
    cfg = kanana2(num_hidden_layers=6, heads_held=(0, 4),
                  experts_held=(0, 16), vocab_size=16032)
    assert lm_counts.per_slot_forward(cfg) == 3 * 2 * 2048 * 768
    parts = lm_counts.step_flops(cfg, tokens_real=32768, slots_held=5 * 24576,
                                 pairs_in_document=4 * 14e6)
    assert parts["routed"] == 3 * (5 * 24576) * 3 * 2 * 2048 * 768
    assert parts["total"] == pytest.approx(sum(
        v for k, v in parts.items() if k != "total"))
    # the issue's arithmetic: an expert layer's routed + shared + router
    # outweigh its attention projections at the deployment's balance
    assert 3.0e13 < parts["total"] < 4.5e13


def test_pairs_in_document_counts_the_causal_pairs_of_each_document():
    seg = np.array([1, 1, 1, 2, 2, 0, 0])
    assert lm_counts.pairs_in_document(seg) == 6 + 3


def test_grouped_roofline_is_compute_bound_at_the_deployments_load():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    slots = 5 * 16 * 1536
    least = lm_counts.grouped_roofline_seconds(2048, 768, 16, slots, 5, True,
                                               peaks)
    assert lm_counts.grouped_calls(True) == 12
    assert least["flops"] == slots * 12 * 2 * 2048 * 768
    assert least["bound"] == "compute"
    assert least["flops"] / least["bytes"] == pytest.approx(409.6, rel=0.01)
    # a tenth of the rows an expert: the weights' bytes bound it
    few = lm_counts.grouped_roofline_seconds(2048, 768, 16, slots / 10, 5,
                                             True, peaks)
    assert few["bound"] == "memory"


COMPILED = """
HloModule jit_step
%fused_computation.1 (p: bf16[8,8]) -> bf16[8,8] {
  %multiply.9 = bf16[8,8]{1,0} multiply(%p, %p), metadata={op_name="jit(step)/jit(main)/lm/mla/mul"}
}
ENTRY %main {
  %fusion.1 = bf16[8,8]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jit(main)/transpose(jvp(lm/mla))/dot_general" source_file="x.py"}
  %ragged-dot.2 = bf16[8,8]{1,0} custom-call(%b), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jit(main)/checkpoint/lm/moe/experts/ragged_dot"}
  %fusion.3 = f32[8]{0} fusion(%c), kind=kInput, calls=%fc, metadata={op_name="jit(step)/jit(main)/optimizer/add"}
  %while.4 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(step)/jit(main)/lm/head_loss/while"}
  ROOT %copy.5 = f32[8]{0} copy(%fusion.3)
}
"""


def test_scopes_join_trace_events_with_the_compiled_text():
    names = lm_scopes.instruction_scopes(COMPILED)
    assert names["fusion.1"].endswith("transpose(jvp(lm/mla))/dot_general")
    assert "copy.5" not in names
    ms = 1_000_000
    ops = [["%fusion.1 fusion bf16[8,8]", 0, 2 * ms],
           ["%ragged-dot.2 tpu_custom_call bf16[8,8]", 2 * ms, 3 * ms],
           ["%fusion.3 fusion f32[8]", 5 * ms, 1 * ms],
           ["%while.4 while s32[]", 0, 9 * ms],      # a container: skipped
           ["%copy.5 copy f32[8]", 6 * ms, 1 * ms],  # no op_name
           ["%fusion.1 fusion bf16[8,8]", 20 * ms, 2 * ms]]  # outside
    got = lm_scopes.scope_seconds(
        [{"name": "/device:TPU:0", "ops": ops}], (0, 10 * ms), names,
        ("lm/moe/experts", "lm/mla", "optimizer"))
    assert got["lm/mla"] == pytest.approx(0.002)
    assert got["lm/moe/experts"] == pytest.approx(0.003)
    assert got["optimizer"] == pytest.approx(0.001)
    assert got["unattributed"] == pytest.approx(0.001)
    assert got["leaf_total"] == pytest.approx(0.007)
    assert got["unattributed_top"][0][0].startswith("%copy.5")


def test_new_layer_metrics_read_the_scope_counters_and_give_nothing_without():
    from benchmarks import harness

    counters = {"scope_s:lm/moe/experts": 0.2, "scope_s:lm/moe/router": 0.01,
                "scope_s:lm/moe/dispatch": 0.03, "scope_s:lm/moe/combine": 0.02,
                "scope_s:lm/moe/shared": 0.04, "scope_s:lm/mla": 0.15,
                "scope_s:lm/head_loss": 0.07, "scope_s:optimizer": 0.02,
                "traced_slots_held": 5 * 16 * 1536.0, "experts_layers": 5,
                "remat": 1.0, "hidden_size": 2048,
                "moe_intermediate_size": 768, "experts_held": 16,
                "moe_load_max": 1700.0, "moe_load_mean": 1536.0,
                "tokens_real": 32500.0, "batch": 4, "seq_len": 8192}

    def obs(c, trace):
        return harness.Observation(
            spans={}, counters=c, end_to_end={}, trace=trace,
            peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            chips=1, memory_peak_bytes=0)

    read = lambda name, o: harness.load_metric(name).read(o)
    full = obs(counters, {"busy_s": 1.0})
    assert read("lm_moe_device_ms", full) == pytest.approx(300.0)
    assert read("lm_moe_experts_device_ms", full) == pytest.approx(200.0)
    assert read("lm_attn_device_ms", full) == pytest.approx(150.0)
    assert read("lm_head_loss_device_ms", full) == pytest.approx(70.0)
    assert read("lm_optimizer_device_ms", full) == pytest.approx(20.0)
    share = read("lm_moe_experts_roofline_pct", full)
    assert share == pytest.approx(
        5 * 16 * 1536 * 12 * 2 * 2048 * 768 / 197e12 / 0.2 * 100)
    assert 0 < share <= 100
    assert read("lm_moe_load_max_over_mean", full) == pytest.approx(1700 / 1536)
    assert read("lm_pack_fill_pct", full) == pytest.approx(32500 / 32768 * 100)
    # a program without the scopes or counters (the parent): nothing, no raise
    bare = obs({}, {"busy_s": 1.0})
    for name in ("lm_moe_device_ms", "lm_moe_experts_device_ms",
                 "lm_attn_device_ms", "lm_head_loss_device_ms",
                 "lm_optimizer_device_ms", "lm_moe_experts_roofline_pct",
                 "lm_moe_load_max_over_mean", "lm_pack_fill_pct"):
        assert read(name, bare) is None


# ---- the second architecture's counts (benchmarks/lm_counts_afmoe.py) ------


def test_afmoe_dense_parts_equal_the_walk_of_the_reference():
    """The reference makes whole `[S, S]` score matrices in every layer
    of either kind (the window is a mask there) and applies each held
    expert to every token; the rest of its products are the analytic
    per-token parts."""
    cfg = toy("trinity", **SHARES["trinity"])
    _, params, _ = seeded(cfg)
    batch = packed_batch(cfg)
    rows, s = batch["tokens"].shape
    walked = flops.count(lambda p: ref.loss(p, batch, cfg), params)
    per_token = sum(lm_counts_afmoe.per_token_forward(cfg).values())
    moe_layers = cfg.num_hidden_layers - cfg.num_dense_layers
    scores = (rows * cfg.num_hidden_layers * s * s
              * lm_counts_afmoe.per_pair_forward(cfg))
    experts = (rows * s * moe_layers * cfg.experts_held[1]
               * lm_counts_afmoe.per_slot_forward(cfg))
    assert walked == per_token * rows * s + scores + experts


@pytest.mark.parametrize("window", [1, 2, 3, 50])
def test_pairs_in_window_counts_the_visible_pairs_of_each_document(window):
    seg = np.array([1, 1, 1, 1, 2, 2, 0, 0, 3])
    t = np.arange(len(seg))
    back = t[:, None] - t[None, :]
    visible = ((back >= 0) & (back < window) & (seg[:, None] == seg[None, :])
               & (seg[:, None] > 0))
    assert lm_counts_afmoe.pairs_in_window(seg, window) == visible.sum()
    if window >= 4:
        assert (lm_counts_afmoe.pairs_in_window(seg, window)
                == lm_counts.pairs_in_document(seg) == 10 + 3 + 1)
    cfg = toy("trinity", sliding_window=window)
    assert lm_counts_afmoe.pairs_by_kind(cfg, np.stack([seg, seg])) == {
        "full": 2 * 14.0, "window": 2.0 * visible.sum()}


def test_afmoe_step_flops_at_the_cells_share_by_hand():
    cfg = trinity_mini(
        num_hidden_layers=5, num_dense_layers=1, vocab_size=25024,
        layer_types=("sliding_attention",) * 4 + ("full_attention",),
        heads_held=(0, 4), experts_held=(0, 16))
    assert lm_counts_afmoe.layers_by_kind(cfg) == {"window": 4, "full": 1}
    assert lm_counts_afmoe.per_slot_forward(cfg) == 3 * 2 * 2048 * 1024
    # W_q, W_g, W_o of 4 heads and W_k, W_v of 1, each 2048 x 128 a head
    assert lm_counts_afmoe.per_token_forward(cfg)["projections"] == (
        5 * 2 * 2048 * 128 * (3 * 4 + 2 * 1))
    parts = lm_counts_afmoe.step_flops(
        cfg, tokens_real=32200, slots_held=4 * 32200,
        pairs={"full": 199e6, "window": 58.6e6})
    assert parts["attention"] == 3 * 4 * 2 * 256 * (199e6 + 4 * 58.6e6)
    assert parts["total"] == pytest.approx(sum(
        v for k, v in parts.items() if k != "total"))
    # the issue's arithmetic: 3.3e13 a step, the head 9.9e12 of it
    assert 3.0e13 < parts["total"] < 3.6e13
    assert parts["head"] == pytest.approx(9.9e12, rel=0.01)


def test_attention_roofline_counts_eleven_products_a_pair_and_head():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert sum(lm_counts_afmoe.attention_calls(True).values()) == 11
    assert sum(lm_counts_afmoe.attention_calls(False).values()) == 9
    least = lm_counts_afmoe.attention_roofline_seconds(
        58.6e6, 4, 32768, 4, 1, 128, True, peaks)
    assert least["flops"] == 4 * 58.6e6 * 4 * 11 * 2 * 128
    # each of the 4 calls moves q, o / dq, do for 4 heads and k, v for 1
    assert least["bytes"] == 4 * 4 * 32768 * 128 * 2 * (3 * 4 + 2)
    assert least["seconds"] == pytest.approx(least["flops"] / 197e12)
    # a handful of pairs a token: the calls' bytes bound them
    few = lm_counts_afmoe.attention_roofline_seconds(
        32768 * 8, 4, 32768, 4, 1, 128, True, peaks)
    assert few["seconds"] == pytest.approx(few["bytes"] / 819e9)


def test_gqa_layer_metrics_read_their_counters_and_give_nothing_without():
    from benchmarks import harness

    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    counters = {"scope_s:lm/gqa/proj": 0.030,
                "scope_s:lm/gqa/window/kernel": 0.040,
                "scope_s:lm/gqa/full/kernel": 0.025,
                "scope_s:lm/moe/experts": 0.2,
                "traced_pairs_window": 58.6e6, "traced_pairs_full": 199e6,
                "attn_layers_window": 4, "attn_layers_full": 1,
                "attn_heads_held": 4, "attn_kv_heads_held": 1,
                "attn_head_dim": 128, "remat": 1.0, "batch": 1,
                "seq_len": 32768,
                "attn_block_pairs_visited_window": 4 * 300.0,
                "attn_block_pairs_visited_full": 1000.0}

    def obs(c):
        return harness.Observation(
            spans={}, counters=c, end_to_end={}, trace={"busy_s": 1.0},
            peaks=peaks, chips=1, memory_peak_bytes=0)

    read = lambda name, o: harness.load_metric(name).read(o)  # noqa: E731
    full = obs(counters)
    assert read("lm_gqa_device_ms", full) == pytest.approx(95.0)
    assert read("lm_gqa_window_kernel_device_ms", full) == pytest.approx(40.0)
    assert read("lm_gqa_full_kernel_device_ms", full) == pytest.approx(25.0)
    least = (4 * 58.6e6 + 199e6) * 4 * 11 * 256 / 197e12
    share = read("lm_gqa_kernel_roofline_pct", full)
    assert share == pytest.approx(least / 0.065 * 100)
    assert 0 < share < 100
    assert read("lm_gqa_window_blocks_visited_pct", full) == pytest.approx(30.0)
    # the parent's program, or kanana's: nothing, and no raise
    for c in ({}, {"scope_s:lm/mla": 0.15, "batch": 4, "seq_len": 8192,
                   "attn_block_pairs_visited": 500.0}):
        for name in ("lm_gqa_device_ms", "lm_gqa_window_kernel_device_ms",
                     "lm_gqa_full_kernel_device_ms",
                     "lm_gqa_kernel_roofline_pct",
                     "lm_gqa_window_blocks_visited_pct"):
            assert read(name, obs(c)) is None, name


# ---- the third architecture's counts (benchmarks/lm_counts_eva.py) ---------


def test_eva_dense_parts_equal_the_walk_of_the_reference():
    """The reference makes, a head, one `[S, S + chunks]` row of scores
    and the `[chunks, S]` pooling matrix over every position (both kinds
    of pair are masks there); the rest of its products are the analytic
    per-token parts, the pooling's logit among them."""
    cfg = toy("evabyte", **SHARES["evabyte"])
    _, params, _ = seeded(cfg)
    batch = packed_batch(cfg)
    rows, s = batch["tokens"].shape
    walked = flops.count(lambda p: ref.loss(p, batch, cfg), params)
    parts = lm_counts_eva.per_token_forward(cfg)
    heads, hd = cfg.heads_held[1], cfg.head_dim
    chunks = s // cfg.chunk_size
    # the analytic pooling counts a token's own chunk: a logit and two
    # weighted sums; the reference multiplies whole matrices instead
    dense = sum(v for k, v in parts.items() if k != "pooling")
    layer = heads * (2 * s * hd                      # k . phi
                     + 2 * 2 * chunks * s * hd       # a @ k, a @ v
                     + 2 * 2 * s * (s + chunks) * hd)  # scores, values
    assert walked == dense * rows * s + rows * cfg.num_hidden_layers * layer
    assert parts["pooling"] == cfg.num_hidden_layers * heads * 3 * 2 * hd


@pytest.mark.parametrize("window,chunk", [(8, 2), (32, 4), (16, 16), (64, 1)])
@pytest.mark.parametrize("lengths", [(41, 59, 20), (3, 1, 100), (128,), (),
                                     (7, 1, 1, 1, 30, 88)])
def test_eva_pairs_equal_a_brute_force_count(lengths, window, chunk):
    seg = np.zeros(128, np.int64)
    at = 0
    for i, n in enumerate(lengths, start=1):
        seg[at:at + n] = i
        at += n
    want = brute_force_eva_pairs(seg, window, chunk)
    assert lm_counts_eva.pairs_in_row(seg, window, chunk) == want
    cfg = toy("evabyte", window_size=window, chunk_size=chunk)
    assert lm_counts_eva.pairs_by_kind(cfg, np.stack([seg, seg])) == {
        k: 2.0 * v for k, v in want.items()}
    # and what the step itself reports (ops/lm_eva.py `pair_counts`)
    from dexiraft_tpu.ops.lm_eva import pair_counts
    got = pair_counts(np.asarray(seg[None], np.int32), window=window,
                      chunk=chunk)
    assert {"local": int(got[0]), "remote": int(got[1])} == want


def test_eva_step_flops_at_the_cells_share_by_hand():
    cfg = evabyte(num_hidden_layers=4, heads_held=(0, 8))
    assert lm_counts_eva.layers_by_kind(cfg) == {"local": 4, "remote": 4}
    per_token = lm_counts_eva.per_token_forward(cfg)
    assert per_token["mlp"] == 4 * 3 * 2 * 4096 * 11008
    assert per_token["projections"] == 4 * 4 * 2 * 4096 * 1024
    assert per_token["head"] == 2 * 4096 * 8 * 320
    parts = lm_counts_eva.step_flops(
        cfg, tokens_real=32000, slots_held=0.0,
        pairs={"local": 30e6, "remote": 25e6})
    assert parts["attention"] == 3 * 8 * 2 * 256 * 4 * 55e6
    assert parts["total"] == pytest.approx(sum(
        v for k, v in parts.items() if k != "total"))
    # forward + backward without the recomputed pass: the SwiGLU 104 of
    # 123 TFLOP (the issue's 142 of 167 counts four passes, not three)
    assert parts["mlp"] == pytest.approx(1.039e14, rel=0.01)
    assert 1.20e14 < parts["total"] < 1.26e14


def test_eva_roofline_is_over_the_needed_pairs_of_both_kinds():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least = lm_counts_eva.attention_roofline_seconds(
        55e6, 4, 32768, 8, 128, True, peaks)
    assert least["flops"] == 4 * 55e6 * 8 * 11 * 2 * 128
    assert least["bytes"] == 4 * 4 * 32768 * 128 * 2 * 5 * 8
    assert least["seconds"] == pytest.approx(least["flops"] / 197e12)


def test_eva_layer_metrics_read_their_counters_and_give_nothing_without():
    from benchmarks import harness

    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    counters = {"scope_s:lm/eva/proj": 0.060,
                "scope_s:lm/eva/local/kernel": 0.030,
                "scope_s:lm/eva/remote": 0.050, "scope_s:lm/eva/pool": 0.004,
                "scope_s:lm/eva/merge": 0.006, "scope_s:lm/mlp": 1.0,
                "scope_s:lm/head_loss": 0.02,
                "traced_pairs_local": 30e6, "traced_pairs_remote": 25e6,
                "attn_layers_local": 4, "attn_layers_remote": 4,
                "attn_heads_held": 8, "attn_kv_heads_held": 8,
                "attn_head_dim": 128, "remat": 1.0, "batch": 1,
                "seq_len": 32768,
                "attn_block_pairs_visited_local": 4 * 160.0,
                "attn_block_pairs_causal": 4 * 2080.0}

    def obs(c):
        return harness.Observation(
            spans={}, counters=c, end_to_end={}, trace={"busy_s": 1.0},
            peaks=peaks, chips=1, memory_peak_bytes=0)

    read = lambda name, o: harness.load_metric(name).read(o)  # noqa: E731
    full = obs(counters)
    assert read("lm_eva_device_ms", full) == pytest.approx(150.0)
    assert read("lm_eva_local_kernel_device_ms", full) == pytest.approx(30.0)
    assert read("lm_eva_remote_device_ms", full) == pytest.approx(60.0)
    assert read("lm_mlp_device_ms", full) == pytest.approx(1000.0)
    least = 4 * 55e6 * 8 * 11 * 256 / 197e12
    share = read("lm_eva_roofline_pct", full)
    assert share == pytest.approx(least / 0.090 * 100)
    assert 0 < share < 100
    assert read("lm_eva_local_blocks_visited_pct", full) == pytest.approx(
        160 / 2080 * 100)
    # the parent's program, or another architecture's: nothing, no raise
    for c in ({}, {"scope_s:lm/gqa/proj": 0.03, "batch": 1, "seq_len": 32768,
                   "attn_block_pairs_visited_window": 500.0,
                   "attn_block_pairs_causal": 2080.0}):
        for name in ("lm_eva_device_ms", "lm_eva_local_kernel_device_ms",
                     "lm_eva_remote_device_ms", "lm_eva_roofline_pct",
                     "lm_eva_local_blocks_visited_pct", "lm_mlp_device_ms"):
            assert read(name, obs(c)) is None, name


# ---- the fourth architecture's counts (benchmarks/lm_counts_lfm2.py) -------


def test_lfm2_dense_parts_equal_the_walk_of_the_reference():
    """The reference makes whole `[S, S]` score matrices in its one
    attention layer and applies every held expert to every token; the
    gate is elementwise, which the walk does not count; the tied head is
    one product, counted once."""
    cfg = toy("lfm2", **SHARES["lfm2"])
    _, params, _ = seeded(cfg)
    batch = packed_batch(cfg)
    rows, s = batch["tokens"].shape
    walked = flops.count(lambda p: ref.loss(p, batch, cfg), params)
    parts = lm_counts_lfm2.per_token_forward(cfg)
    dense = sum(v for k, v in parts.items() if k != "conv_gate")
    kinds = lm_counts_lfm2.layers_by_kind(cfg)
    assert kinds == {"conv": 4, "full": 1}
    scores = rows * kinds["full"] * cfg.heads_held[1] * s * s * 2 * (
        2 * cfg.head_dim)
    experts = (rows * s * (cfg.num_hidden_layers - cfg.num_dense_layers)
               * cfg.experts_held[1] * lm_counts_lfm2.per_slot_forward(cfg))
    assert walked == dense * rows * s + scores + experts
    assert parts["conv_gate"] == 4 * cfg.hidden_size * 7  # 2 L + 1, L = 3


@pytest.mark.parametrize("length", [1, 2, 3, 4])
@pytest.mark.parametrize("lengths", [(41, 59, 20), (3, 1, 2, 100), (128,), (),
                                     (1,) * 128])
def test_lfm2_pairs_and_taps_equal_a_brute_force_count(lengths, length):
    """By the definitions, position by position: the benchmark's pairs,
    and the taps of the program's own counter (ops/lm_conv.py
    `taps_masked`), which nothing else counts."""
    import jax.numpy as jnp

    from dexiraft_tpu.ops.lm_conv import taps_masked

    seg = np.zeros(128, np.int32)
    at = 0
    for i, n in enumerate(lengths, start=1):
        seg[at:at + n] = i
        at += n
    masked = pairs = 0
    for n, d in enumerate(seg):
        if d == 0:
            continue
        pairs += sum(1 for m in range(n + 1) if seg[m] == d)
        masked += sum(1 for back in range(1, length)
                      if n - back < 0 or seg[n - back] != d)
    cfg = toy("lfm2", conv_L_cache=length)
    got = lm_counts_lfm2.pairs_by_kind(cfg, np.stack([seg, seg]))
    assert got == {"full": 2.0 * pairs}
    assert int(taps_masked(jnp.asarray(seg[None]), length)) == masked


def test_lfm2_step_flops_at_the_cells_share_by_hand():
    """The issue's arithmetic: 3.85e13 FLOP a step at an even balance."""
    cfg = lfm2_8b_a1b(
        num_hidden_layers=5, num_dense_layers=1,
        layer_types=("conv", "full_attention", "conv", "conv", "conv"),
        heads_held=(0, 8), experts_held=(0, 8), vocab_size=16384)
    per_token = lm_counts_lfm2.per_token_forward(cfg)
    assert per_token["conv_projections"] == 4 * 2 * 4 * 2048 * 2048
    assert per_token["attention_projections"] == 2 * 2048 * 64 * 20
    assert per_token["dense_mlp"] == 3 * 2 * 2048 * 7168
    assert per_token["router"] == 4 * 2 * 2048 * 32
    assert per_token["head"] == 2 * 2048 * 16384
    assert lm_counts_lfm2.per_slot_forward(cfg) == 3 * 2 * 2048 * 1792
    parts = lm_counts_lfm2.step_flops(
        cfg, tokens_real=32768, slots_held=4 * 32768,
        pairs={"full": 130e6})
    assert parts["attention"] == 3 * 8 * 2 * 128 * 130e6
    assert parts["conv_projections"] == pytest.approx(1.32e13, rel=0.01)
    assert parts["routed"] == pytest.approx(8.66e12, rel=0.01)
    assert parts["head"] == pytest.approx(6.6e12, rel=0.01)
    assert parts["total"] == pytest.approx(sum(
        v for k, v in parts.items() if k != "total"))
    assert parts["total"] == pytest.approx(3.85e13, rel=0.01)


def test_conv_roofline_is_four_products_and_fifteen_arrays_under_remat():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least = lm_counts_lfm2.conv_roofline_seconds(32768, 2048, 4, True, peaks)
    assert least["flops"] == 4 * 32768 * 32 * 2048 * 2048
    assert least["bytes"] == 4 * 32768 * 15 * 2048 * 2
    assert least["seconds"] == pytest.approx(
        least["flops"] / 197e12 + least["bytes"] / 819e9)
    assert least["seconds"] == pytest.approx(0.0992, rel=0.01)
    plain = lm_counts_lfm2.conv_roofline_seconds(32768, 2048, 4, False, peaks)
    assert (plain["flops"], plain["bytes"]) == (
        least["flops"] * 3 / 4, least["bytes"] * 11 / 15)
    assert (lm_counts_lfm2.attention_roofline_seconds
            is lm_counts_afmoe.attention_roofline_seconds)


def test_lfm2_layer_metrics_read_their_counters_and_give_nothing_without():
    from benchmarks import harness

    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    counters = {"scope_s:lm/conv/in": 0.090, "scope_s:lm/conv/gate": 0.030,
                "scope_s:lm/conv/out": 0.030,
                "scope_s:lm/gqa/proj": 0.010,
                "scope_s:lm/gqa/full/kernel": 0.008,
                "scope_s:lm/mlp": 0.06, "scope_s:lm/moe/experts": 0.1,
                "traced_pairs_full": 130e6,
                "attn_layers_conv": 4, "attn_layers_full": 1,
                "attn_heads_held": 8, "attn_kv_heads_held": 2,
                "attn_head_dim": 64, "hidden_size": 2048, "remat": 1.0,
                "batch": 1, "seq_len": 32768, "tokens_real": 32600.0}

    def obs(c):
        return harness.Observation(
            spans={}, counters=c, end_to_end={}, trace={"busy_s": 1.0},
            peaks=peaks, chips=1, memory_peak_bytes=0)

    read = lambda name, o: harness.load_metric(name).read(o)  # noqa: E731
    full = obs(counters)
    assert read("lm_conv_device_ms", full) == pytest.approx(150.0)
    assert read("lm_conv_gate_device_ms", full) == pytest.approx(30.0)
    assert read("lm_gqa_device_ms", full) == pytest.approx(18.0)
    assert read("lm_gqa_full_kernel_device_ms", full) == pytest.approx(8.0)
    least = lm_counts_lfm2.conv_roofline_seconds(32600.0, 2048, 4, True,
                                                 peaks)["seconds"]
    share = read("lm_conv_roofline_pct", full)
    assert share == pytest.approx(least / 0.150 * 100)
    assert 0 < share < 100
    kernel = lm_counts_afmoe.attention_roofline_seconds(
        130e6, 1, 32768, 8, 2, 64, True, peaks)["seconds"]
    assert kernel == pytest.approx(130e6 * 8 * 11 * 128 / 197e12)
    assert read("lm_gqa_full_kernel_roofline_pct", full) == pytest.approx(
        kernel / 0.008 * 100)
    # the parent's program, or another architecture's: nothing, no raise
    for c in ({}, {"scope_s:lm/eva/proj": 0.03, "batch": 1, "seq_len": 32768,
                   "traced_pairs_local": 3e7, "attn_layers_local": 4}):
        for name in ("lm_conv_device_ms", "lm_conv_gate_device_ms",
                     "lm_conv_roofline_pct",
                     "lm_gqa_full_kernel_roofline_pct"):
            assert read(name, obs(c)) is None, name


# ---- the fifth architecture's counts (benchmarks/lm_counts_smallthinker.py)


def test_smallthinker_dense_parts_equal_the_walk_of_the_reference():
    """The reference makes whole `[S, S]` score matrices in every layer
    of either kind (the window is a mask there), applies each held expert
    to every token, and its router is one product over all experts in
    every layer; no layer is dense, no expert shared, no projection a
    gate's."""
    cfg = toy("smallthinker", **SHARES["smallthinker"])
    _, params, _ = seeded(cfg)
    batch = packed_batch(cfg)
    rows, s = batch["tokens"].shape
    walked = flops.count(lambda p: ref.loss(p, batch, cfg), params)
    parts = lm_counts_smallthinker.per_token_forward(cfg)
    assert set(parts) == {"projections", "router", "head"}
    assert lm_counts_smallthinker.layers_by_kind(cfg) == {"window": 3,
                                                          "full": 1}
    scores = (rows * cfg.num_hidden_layers * s * s
              * lm_counts_smallthinker.per_pair_forward(cfg))
    experts = (rows * s * cfg.num_hidden_layers * cfg.experts_held[1]
               * lm_counts_smallthinker.per_slot_forward(cfg))
    assert walked == sum(parts.values()) * rows * s + scores + experts


def test_smallthinker_pairs_are_the_window_and_the_document():
    cfg = toy("smallthinker")
    seg = np.asarray(packed_batch(cfg, rows=1)["segment_ids"][0])
    at = np.arange(len(seg))
    back = at[:, None] - at[None, :]
    same = (seg[:, None] == seg[None, :]) & (seg[:, None] > 0) & (back >= 0)
    assert lm_counts_smallthinker.pairs_by_kind(cfg, np.stack([seg, seg])) == {
        "full": 2.0 * same.sum(),
        "window": 2.0 * (same & (back < cfg.sliding_window_size)).sum()}


def test_smallthinker_step_flops_at_the_cells_share_by_hand():
    """The issue's arithmetic: the head and loss over a quarter of the
    vocabulary are 63 % of the matrix FLOPs a token, 194 M against
    4 x 28.5 M in the layers at an even balance."""
    cfg = smallthinker_21b(
        num_hidden_layers=4, heads_held=(0, 7), experts_held=(0, 16),
        vocab_size=37_984)
    per_token = lm_counts_smallthinker.per_token_forward(cfg)
    assert per_token["projections"] == 4 * 2 * 2560 * 128 * (14 + 2)
    assert per_token["router"] == 4 * 2 * 2560 * 64
    assert per_token["head"] == 2 * 2560 * 37_984
    assert lm_counts_smallthinker.per_slot_forward(cfg) == 3 * 2 * 2560 * 768
    assert lm_counts_smallthinker.per_pair_forward(cfg) == 7 * 2 * 256
    # a token's 6 slots fall on the 16 held of 64 experts 1.5 times a layer
    a_layer = (per_token["projections"] + per_token["router"]) / 4 + 1.5 * (
        lm_counts_smallthinker.per_slot_forward(cfg))
    assert a_layer == pytest.approx(28.5e6, rel=0.01)
    assert per_token["head"] / (per_token["head"] + 4 * a_layer
                                ) == pytest.approx(0.63, abs=0.005)
    parts = lm_counts_smallthinker.step_flops(
        cfg, tokens_real=16384, slots_held=4 * 24576,
        pairs={"full": 134e6, "window": 58.7e6})
    assert parts["attention"] == 3 * 7 * 2 * 256 * (134e6 + 3 * 58.7e6)
    assert parts["routed"] == 3 * 3 * 2 * 2560 * 768 * 4 * 24576
    assert parts["total"] == pytest.approx(sum(
        v for k, v in parts.items() if k != "total"))
    # a 16,384-token document: the window layer's pairs and the full one's
    row = np.ones(16384, np.int32)
    assert lm_counts_smallthinker.pairs_by_kind(cfg, row[None]) == {
        "full": 16384 * 16385 / 2,
        "window": 4096 * 4097 / 2 + (16384 - 4096) * 4096}


def test_smallthinker_layer_metric_reads_its_scope_and_gives_nothing_without():
    from benchmarks import harness

    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    counters = {"scope_s:lm/moe/router": 0.004,
                "scope_s:lm/moe/dispatch": 0.040,
                "scope_s:lm/moe/experts": 0.070,
                "scope_s:lm/moe/combine": 0.030,
                "scope_s:lm/gqa/proj": 0.010,
                "scope_s:lm/gqa/window/kernel": 0.030,
                "scope_s:lm/gqa/full/kernel": 0.020,
                "moe_slots_held": 90000.0,
                "moe_intermediate_size": 768, "hidden_size": 2560,
                "experts_held": 16, "experts_layers": 4, "remat": 1.0,
                "traced_slots_held": 90000.0,
                "traced_pairs_window": 30e6, "traced_pairs_full": 50e6,
                "attn_layers_window": 3, "attn_layers_full": 1,
                "attn_heads_held": 7, "attn_kv_heads_held": 1,
                "attn_head_dim": 128, "batch": 1, "seq_len": 16384}

    def obs(c):
        return harness.Observation(
            spans={}, counters=c, end_to_end={}, trace={"busy_s": 1.0},
            peaks=peaks, chips=1, memory_peak_bytes=0)

    read = lambda name, o: harness.load_metric(name).read(o)  # noqa: E731
    full = obs(counters)
    assert read("lm_moe_router_device_ms", full) == pytest.approx(4.0)
    assert read("lm_moe_device_ms", full) == pytest.approx(144.0)
    # the accepted shares on this width and these heads: the same work
    # whatever `hb` implements it, and under 100 %
    kernel = sum(lm_counts_afmoe.attention_roofline_seconds(
        pairs, layers, 16384, 7, 1, 128, True, peaks)["seconds"]
        for pairs, layers in ((30e6, 3), (50e6, 1)))
    assert kernel == pytest.approx((3 * 30e6 + 50e6) * 7 * 11 * 256 / 197e12)
    assert read("lm_gqa_kernel_roofline_pct", full) == pytest.approx(
        kernel / 0.050 * 100)
    grouped = lm_counts.grouped_roofline_seconds(2560, 768, 16, 90000.0, 4,
                                                 True, peaks)
    assert grouped["bound"] == "compute"
    assert read("lm_moe_experts_roofline_pct", full) == pytest.approx(
        grouped["seconds"] / 0.070 * 100)
    assert 0 < read("lm_moe_experts_roofline_pct", full) < 100
    # the parent's program (no such scope), an untraced run: nothing,
    # and no raise
    for c in ({}, {"scope_s:lm/mla": 0.15, "batch": 4, "seq_len": 8192}):
        assert read("lm_moe_router_device_ms", obs(c)) is None
    untraced = obs(counters)
    untraced.trace = None
    assert read("lm_moe_router_device_ms", untraced) is None


# ---- Nemotron-H (benchmarks/lm_counts_nemotron.py) --------------------------

_NEMOTRON_SHARE = dict(ssm_heads_held=(2, 4), heads_held=(4, 4),
                       experts_held=(2, 6), shared_columns_held=(12, 24))


def test_nemotron_dense_parts_equal_the_walk_of_the_reference():
    """The reference makes a whole `[S, S]` score matrix in the attention
    layer and applies each held expert to every token; its recurrence's
    one matrix product a token is the state's reading `h C` (2 P N a
    head: the update `(dt x) (x) B` is elementwise there and half of the
    part), and its convolution is elementwise."""
    cfg = nemotron_h_toy(**_NEMOTRON_SHARE)
    _, params, _ = seeded(cfg)
    batch = packed_batch(cfg)
    rows, s = batch["tokens"].shape
    walked = flops.count(lambda p: ref.loss(p, batch, cfg), params)
    parts = lm_counts_nemotron.per_token_forward(cfg)
    assert set(parts) == {"ssm_projections", "ssm_conv", "ssm_scan",
                          "attention_projections", "router", "latent",
                          "shared", "head"}
    kinds = lm_counts_nemotron.layers_by_kind(cfg)
    assert kinds == {"mamba": 2, "full": 1, "experts": 2}
    assert parts["ssm_scan"] == 2 * 4 * 4 * 8 * 16
    assert parts["ssm_conv"] == 2 * 2 * 4 * (4 * 8 + 2 * 2 * 16)
    dense = sum(parts.values()) - parts["ssm_conv"] - parts["ssm_scan"] / 2
    scores = rows * s * s * lm_counts_nemotron.per_pair_forward(cfg)
    experts = (rows * s * kinds["experts"] * cfg.experts_held[1]
               * lm_counts_nemotron.per_slot_forward(cfg))
    assert walked == dense * rows * s + scores + experts


def test_nemotron_step_flops_at_the_cells_share_by_hand():
    cfg = nemotron_h(
        num_hidden_layers=11, hybrid_override_pattern="MEMEMEMEM*E",
        ssm_heads_held=(0, 16), heads_held=(0, 4), kv_heads_held=(0, 1),
        experts_held=(0, 8), shared_columns_held=(0, 672), vocab_size=16_384)
    per_token = lm_counts_nemotron.per_token_forward(cfg)
    assert per_token["ssm_projections"] == 5 * 2 * 4096 * (2320 + 1024)
    assert per_token["ssm_scan"] == 5 * 16 * 4 * 64 * 128
    assert per_token["attention_projections"] == 2 * 4096 * 128 * (8 + 2)
    assert per_token["router"] == 5 * 2 * 4096 * 512
    assert per_token["latent"] == 5 * 2 * 2 * 4096 * 1024
    assert per_token["shared"] == 5 * 2 * 2 * 4096 * 672
    assert per_token["head"] == 2 * 4096 * 16_384
    # two products a slot at the latent width: a ninth of what three at
    # the hidden width would be credited
    assert lm_counts_nemotron.per_slot_forward(cfg) == 2 * 2 * 1024 * 2688
    assert lm_counts.per_slot_forward(cfg) == 6 * lm_counts_nemotron.per_slot_forward(cfg)
    assert lm_counts_nemotron.per_pair_forward(cfg) == 4 * 2 * 256
    parts = lm_counts_nemotron.step_flops(
        cfg, tokens_real=32_000, slots_held=5 * 11_264, pairs={"full": 90e6})
    assert parts["routed"] == 3 * 2 * 2 * 1024 * 2688 * 5 * 11_264
    assert parts["attention"] == 3 * 4 * 2 * 256 * 90e6
    assert parts["total"] == pytest.approx(sum(
        v for k, v in parts.items() if k != "total"))
    row = np.repeat(np.arange(1, 9, dtype=np.int32), 4096)
    assert lm_counts_nemotron.pairs_by_kind(cfg, row[None]) == {
        "full": 8 * 4096 * 4097 / 2}
    assert lm_counts_nemotron.grouped_calls(True) == 8
    assert lm_counts_nemotron.grouped_calls(False) == 6


def test_nemotron_layer_metrics_read_their_scopes_and_stay_under_100():
    """The three new readers on a synthetic observation of the cell's
    size (the predicted times): device times are their scopes' sums; the
    counts' two least times over them are shares under 100 % (they have
    no reader: the runner carries no width of theirs), as the accepted
    attention reader's is; the parent's program (no such scope) and an
    untraced run read as nothing."""
    from benchmarks import harness

    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    counters = {"scope_s:lm/ssm/in": 0.060, "scope_s:lm/ssm/conv": 0.015,
                "scope_s:lm/ssm/scan": 0.150,
                "scope_s:lm/ssm/gate_norm": 0.010,
                "scope_s:lm/ssm/out": 0.030, "scope_s:lm/moe/latent": 0.045,
                "scope_s:lm/moe/router": 0.050,
                "scope_s:lm/moe/dispatch": 0.040,
                "scope_s:lm/moe/experts": 0.030,
                "scope_s:lm/moe/shared": 0.030,
                "scope_s:lm/moe/combine": 0.030,
                "scope_s:lm/gqa/proj": 0.008,
                "scope_s:lm/gqa/full/kernel": 0.020,
                "tokens_real": 32_000.0, "moe_slots_held": 56_000.0,
                "traced_slots_held": 56_000.0, "moe_intermediate_size": 2688,
                "hidden_size": 4096, "experts_held": 8, "experts_layers": 11,
                "remat": 1.0, "traced_pairs_full": 90e6,
                "attn_layers_mamba": 5, "attn_layers_full": 1,
                "attn_layers_experts": 5, "attn_heads_held": 4,
                "attn_kv_heads_held": 1, "attn_head_dim": 128, "batch": 1,
                "seq_len": 32_768}

    def obs(c):
        return harness.Observation(
            spans={}, counters=c, end_to_end={}, trace={"busy_s": 1.0},
            peaks=peaks, chips=1, memory_peak_bytes=0)

    read = lambda name, o: harness.load_metric(name).read(o)  # noqa: E731
    full = obs(counters)
    assert read("lm_ssm_device_ms", full) == pytest.approx(265.0)
    assert read("lm_ssm_scan_device_ms", full) == pytest.approx(150.0)
    assert read("lm_moe_latent_device_ms", full) == pytest.approx(45.0)
    assert read("lm_moe_device_ms", full) == pytest.approx(225.0)
    scan = lm_counts_nemotron.scan_roofline_seconds(
        32_000.0, 16, 64, 128, 1, 5, True, peaks)
    assert scan["flops"] == 5 * 32_000 * 4 * 16 * 4 * 64 * 128
    assert scan["bytes"] == 5 * 32_000 * 4 * (
        2 * 1024 * 2 + 2 * 128 * 2 + 16 * 4)
    assert scan["bound"] == "memory"
    grouped = lm_counts_nemotron.grouped_roofline_seconds(
        1024, 2688, 8, 56_000.0, 5, True, peaks)
    assert grouped["flops"] == 56_000 * 8 * 2 * 1024 * 2688
    assert grouped["bound"] == "compute"
    # the accepted reader of the attention layer's kernel reads this
    # cell's one `full` layer truly: 4 heads on 1 of 128
    kernel = lm_counts_lfm2.attention_roofline_seconds(
        90e6, 1, 32_768, 4, 1, 128, True, peaks)["seconds"]
    assert read("lm_gqa_full_kernel_roofline_pct", full) == pytest.approx(
        kernel / 0.020 * 100)
    for share in (scan["seconds"] / 0.150, grouped["seconds"] / 0.030,
                  read("lm_gqa_full_kernel_roofline_pct", full) / 100):
        assert 0 < share < 1
    # the reader fixed at 12 products a slot of the hidden width would
    # credit six times the work: why the cell is not in its list
    assert read("lm_moe_experts_roofline_pct", full) > 100
    for c in ({}, {"scope_s:lm/mla": 0.15, "batch": 4, "seq_len": 8192}):
        for name in ("lm_ssm_device_ms", "lm_ssm_scan_device_ms",
                     "lm_moe_latent_device_ms"):
            assert read(name, obs(c)) is None, name
    untraced = obs(counters)
    untraced.trace = None
    assert read("lm_ssm_scan_device_ms", untraced) is None
    assert read("lm_moe_latent_device_ms", untraced) is None
