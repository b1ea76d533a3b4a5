"""The language-model cells' rehearsals (`JAX_PLATFORMS=cpu`, exit 4),
their manifest entries and configuration files, and `train_cli`'s
refusals."""

import json
import os
import os.path as osp
import subprocess
import sys

import pytest

REPO = osp.dirname(osp.dirname(osp.abspath(__file__)))
CELL = "kanana2-train-pack8k"
EXIT_REHEARSAL = 4

# section 6 of the issue: the accepted metrics the runner feeds, and the
# eight it brings
FED = ["train_step_device_ms", "train_window_compiles",
       "train_model_flops_util_pct", "train_device_idle_pct",
       "train_peak_hbm_gb", "prefetch_stall_ms", "prefetch_put_ms",
       "loader_wait_ms", "loader_stack_ms", "loader_decode_ms",
       "loader_samples_per_s"]
NEW = ["lm_moe_device_ms", "lm_moe_experts_device_ms", "lm_attn_device_ms",
       "lm_head_loss_device_ms", "lm_optimizer_device_ms",
       "lm_moe_experts_roofline_pct", "lm_moe_load_max_over_mean",
       "lm_pack_fill_pct"]
SETUP = ["setup_init_s", "setup_warm_s", "setup_check_s", "setup_jax_trace_s",
         "setup_jax_lower_s", "setup_backend_compile_s", "setup_cache_load_s"]
# what a CPU run has no device trace, peak table or memory counter for
NEEDS_A_CHIP = {"train_step_device_ms", "train_device_idle_pct",
                "train_peak_hbm_gb", "train_model_flops_util_pct",
                "lm_moe_device_ms", "lm_moe_experts_device_ms",
                "lm_attn_device_ms", "lm_head_loss_device_ms",
                "lm_optimizer_device_ms", "lm_moe_experts_roofline_pct"}


# no cut may name a width (the builder's contract)
WIDTHS = {"hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_experts_per_tok", "n_shared_experts", "num_shared_experts",
          "head_dim", "sliding_window"}

# the second language cell (PR 31): what it shares with the first, what
# it brings, and what of the first's it leaves (`lm_attn_device_ms`
# reads `lm/mla`)
CELL2 = "trinity-train-pack32k"
NEW2 = ["lm_gqa_device_ms", "lm_gqa_window_kernel_device_ms",
        "lm_gqa_full_kernel_device_ms", "lm_gqa_kernel_roofline_pct",
        "lm_gqa_window_blocks_visited_pct"]
SHARED2 = [m for m in NEW if m != "lm_attn_device_ms"]
NEEDS_A_CHIP2 = NEEDS_A_CHIP | {
    "lm_gqa_device_ms", "lm_gqa_window_kernel_device_ms",
    "lm_gqa_full_kernel_device_ms", "lm_gqa_kernel_roofline_pct"}
# the third language cell (PR 33): a dense stack, so none of the expert
# layer's metrics; its own mixer's six
CELL3 = "evabyte-train-bytes32k"
NEW3 = ["lm_eva_device_ms", "lm_eva_local_kernel_device_ms",
        "lm_eva_remote_device_ms", "lm_eva_roofline_pct",
        "lm_eva_local_blocks_visited_pct", "lm_mlp_device_ms"]
SHARED3 = ["lm_head_loss_device_ms", "lm_optimizer_device_ms",
           "lm_pack_fill_pct"]
NEEDS_A_CHIP3 = (NEEDS_A_CHIP | set(NEW3)) - {
    "lm_eva_local_blocks_visited_pct"}
# the fourth language cell (PR 38): two kinds of mixer, an expert layer
# without a shared expert; what it shares with Trinity's (the attention
# layer is a `full` one under `lm/gqa/`), with EvaByte's (`lm_mlp`), its
# own four (the program's `conv_taps_masked` has no reader: the runner
# does not carry it, PERF.md section 7)
CELL4 = "lfm2-train-pack32k"
NEW4 = ["lm_conv_device_ms", "lm_conv_gate_device_ms", "lm_conv_roofline_pct",
        "lm_gqa_full_kernel_roofline_pct"]
SHARED4 = SHARED2 + ["lm_gqa_device_ms", "lm_gqa_full_kernel_device_ms",
                     "lm_mlp_device_ms"]
NEEDS_A_CHIP4 = NEEDS_A_CHIP2 | set(NEW4) | {"lm_mlp_device_ms"}
# the fifth language cell (PR 45): window and full layers as Trinity's,
# so every metric of Trinity's cell, and one of its own (the router's
# time ahead of attention)
CELL5 = "smallthinker-train-pack16k"
NEW5 = ["lm_moe_router_device_ms"]
SHARED5 = SHARED2 + NEW2
NEEDS_A_CHIP5 = NEEDS_A_CHIP2 | {"lm_moe_router_device_ms"}
# PR 47: the expert layer's two row moves timed apart in the four sparse
# cells, and the share of the segment sums' fetched rows that are rows of
# a range (the first cell's runner carries four counters by name and not
# this one: PERF.md section 7)
NEW47 = ["lm_moe_dispatch_device_ms", "lm_moe_combine_device_ms",
         "lm_moe_rows_live_pct"]
SPARSE = [CELL, CELL2, CELL4, CELL5]
# the sixth language cell (PR 48): layers of one block, a state-space
# mixer, an attention layer of the `full` kind as LFM2's, an expert layer
# with a latent space, so the expert layer's metrics but the one whose
# reader counts three products a slot at the hidden width; its own
# three device times (the two roofline shares of `lm_counts_nemotron`
# and the program's `ssm_doc_starts` / `ssm_chunks_reset` have no
# reader: the runner carries neither the widths nor the counters,
# PERF.md section 7)
CELL6 = "nemotron3-train-pack32k"
NEW6 = ["lm_ssm_device_ms", "lm_ssm_scan_device_ms",
        "lm_moe_latent_device_ms"]
SHARED6 = [m for m in SHARED2 if m != "lm_moe_experts_roofline_pct"] + [
    "lm_moe_router_device_ms", "lm_gqa_device_ms",
    "lm_gqa_full_kernel_device_ms", "lm_gqa_full_kernel_roofline_pct"
] + NEW47
NEEDS_A_CHIP6 = (NEEDS_A_CHIP | set(NEW6) | set(NEW47[:2]) | {
    "lm_moe_router_device_ms", "lm_gqa_device_ms",
    "lm_gqa_full_kernel_device_ms", "lm_gqa_full_kernel_roofline_pct"})
CELLS = {
    CELL: dict(config="kanana-2-30b-a3b-share8", traffic="train-pack8k",
               model="kanana-2-30b-a3b-instruct-2601", shares=8,
               assumes="e_score_correction_bias",
               reports=FED + NEW + NEW47[:2] + SETUP,
               needs_a_chip=NEEDS_A_CHIP | set(NEW47[:2])),
    CELL2: dict(config="trinity-mini-share8", traffic="train-pack32k",
                model="Trinity-Mini", shares=8, assumes="expert_bias",
                reports=FED + SHARED2 + NEW2 + NEW47 + SETUP,
                needs_a_chip=NEEDS_A_CHIP2 | set(NEW47[:2])),
    CELL3: dict(config="evabyte-6.5b-share4", traffic="train-bytes32k",
                model="EvaByte", shares=4, assumes="adaptive_mu_k",
                reports=FED + SHARED3 + NEW3 + SETUP,
                needs_a_chip=NEEDS_A_CHIP3),
    CELL4: dict(config="lfm2-8b-a1b-share4", traffic="train-pack32k-docs2k",
                model="LFM2-8B-A1B", shares=4, assumes="expert_bias",
                reports=FED + SHARED4 + NEW4 + NEW47 + SETUP,
                needs_a_chip=NEEDS_A_CHIP4 | set(NEW47[:2])),
    CELL5: dict(config="smallthinker-21b-a3b-share4",
                traffic="train-pack16k",
                model="SmallThinker-21BA3B-Instruct", shares=4,
                assumes="router_before_attention",
                reports=FED + SHARED5 + NEW5 + NEW47 + SETUP,
                needs_a_chip=NEEDS_A_CHIP5 | set(NEW47[:2])),
    CELL6: dict(config="nemotron-3-super-120b-a12b-share64",
                traffic="train-pack32k-docs4k",
                model="NVIDIA-Nemotron-3-Super-120B-A12B-BF16", shares=64,
                assumes="document reset",
                reports=FED + SHARED6 + NEW6 + SETUP,
                needs_a_chip=NEEDS_A_CHIP6),
}


@pytest.fixture(scope="module")
def manifest():
    with open(osp.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_manifest_names_the_cell_and_every_metric_of_section_6(manifest):
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kanana-2-30b-a3b-share8", "train-pack8k", 1)
    assert manifest["workloads"].index(cell) == 4  # later cells follow it
    by_name = {m["name"]: m for m in manifest["per_layer"] + manifest["end_to_end"]}
    for name in FED + NEW + ["train_samples_per_s"]:
        assert CELL in by_name[name]["workloads"], name
        assert osp.exists(osp.join(REPO, "benchmarks", "layer_metrics",
                                   name + ".py")) or name == "train_samples_per_s"
    for name in SETUP:  # no list: every cell that reports setup_s
        assert "workloads" not in by_name[name]
    for name in NEW:
        assert by_name[name]["workloads"][0] == CELL
        assert by_name[name]["moves"] == "train_samples_per_s"
    for name in ("train_loop_device_ms_per_iter", "train_prelude_device_ms"):
        assert CELL not in by_name[name]["workloads"]  # no refinement loop


def test_manifest_names_the_second_cell_and_what_it_reports(manifest):
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL2)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-mini-share8", "train-pack32k", 1)
    assert manifest["workloads"][5] == cell  # entries are added at the end
    by_name = {m["name"]: m
               for m in manifest["per_layer"] + manifest["end_to_end"]}
    for name in FED + SHARED2 + ["train_samples_per_s"]:
        cells = by_name[name]["workloads"]
        at = cells.index(CELL)
        assert cells[at:at + 2] == [CELL, CELL2], name
    assert by_name["lm_attn_device_ms"]["workloads"] == [CELL]
    for name in NEW2:
        assert by_name[name]["workloads"][0] == CELL2
        assert by_name[name]["moves"] == "train_samples_per_s"
        assert by_name[name]["layer"] == by_name["lm_attn_device_ms"]["layer"]
        assert osp.exists(osp.join(REPO, "benchmarks", "layer_metrics",
                                   name + ".py"))
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[names.index(NEW2[0]):][:5] == NEW2


def test_manifest_names_the_third_cell_and_what_it_reports(manifest):
    cell = manifest["workloads"][7]  # entries are added at the end
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL3, "evabyte-6.5b-share4", "train-bytes32k", 1)
    assert len(cell["why"]) <= 200
    assert manifest["configs"][4]["name"] == "evabyte-6.5b-share4"
    by_name = {m["name"]: m
               for m in manifest["per_layer"] + manifest["end_to_end"]}
    for name in FED + SHARED3 + ["train_samples_per_s"]:
        cells = by_name[name]["workloads"]
        assert cells[cells.index(CELL3) + 1] == CELL4, name  # it follows
    for name in NEW3:
        assert by_name[name]["workloads"][0] == CELL3
        assert by_name[name]["moves"] == "train_samples_per_s"
        assert by_name[name]["layer"] == by_name["lm_attn_device_ms"]["layer"]
        assert osp.exists(osp.join(REPO, "benchmarks", "layer_metrics",
                                   name + ".py"))
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[names.index(NEW3[0]):][:6] == NEW3
    for name, m in by_name.items():  # a dense stack has no expert layer
        if name.startswith(("lm_moe_", "lm_gqa_", "lm_attn_")):
            assert CELL3 not in m["workloads"], name


def test_manifest_names_the_fourth_cell_and_what_it_reports(manifest):
    cell = manifest["workloads"][8]  # entries are added at the end
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL4, "lfm2-8b-a1b-share4", "train-pack32k-docs2k", 1)
    assert len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert manifest["configs"][5]["name"] == "lfm2-8b-a1b-share4"
    by_name = {m["name"]: m
               for m in manifest["per_layer"] + manifest["end_to_end"]}
    for name in FED + SHARED4 + ["train_samples_per_s"]:
        cells = by_name[name]["workloads"]  # last, or later cells follow
        assert cells[cells.index(CELL4) + 1:] in (
            [], [CELL5], [CELL6], [CELL5, CELL6]), name
    for name in NEW4:
        assert by_name[name]["workloads"][0] == CELL4
        assert by_name[name]["workloads"][1:] in ([], [CELL6])
        assert by_name[name]["moves"] == "train_samples_per_s"
        assert by_name[name]["layer"] == by_name["lm_attn_device_ms"]["layer"]
        assert osp.exists(osp.join(REPO, "benchmarks", "layer_metrics",
                                   name + ".py"))
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[names.index(NEW4[0]):][:len(NEW4)] == NEW4
    # what reads a `window` kind, latent attention or EVA stays the others'
    for name, m in by_name.items():
        if (name.startswith(("lm_eva_", "lm_attn_", "lm_gqa_window_"))
                or name == "lm_gqa_kernel_roofline_pct"):
            assert CELL4 not in m["workloads"], name


def test_manifest_names_the_fifth_cell_and_what_it_reports(manifest):
    cell = manifest["workloads"][9]  # entries are added at the end
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL5, "smallthinker-21b-a3b-share4", "train-pack16k", 1)
    assert len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    entry = manifest["configs"][6]
    assert entry["name"] == "smallthinker-21b-a3b-share4"
    assert len(entry["why"]) <= 200
    by_name = {m["name"]: m
               for m in manifest["per_layer"] + manifest["end_to_end"]}
    # every list Trinity's cell is in, and no other
    for name, m in by_name.items():
        if name in NEW5:
            continue
        cells = m.get("workloads", [])
        assert (CELL5 in cells) == (CELL2 in cells), name
        if CELL5 in cells:  # last, or the sixth cell follows
            assert cells[cells.index(CELL5) + 1:] in ([], [CELL6]), name
    for name in NEW5:
        assert by_name[name]["workloads"] in ([CELL5], [CELL5, CELL6])
        assert by_name[name]["moves"] == "train_samples_per_s"
        assert by_name[name]["layer"] == by_name["lm_moe_device_ms"]["layer"]
        assert osp.exists(osp.join(REPO, "benchmarks", "layer_metrics",
                                   name + ".py"))
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[names.index(NEW47[0]) - 1] == NEW5[0]  # PR 47's follow
    assert by_name["lm_moe_router_device_ms"]["source"] == "device_trace"


def test_manifest_names_the_sixth_cell_and_what_it_reports(manifest):
    cell = manifest["workloads"][-1]  # entries are added at the end
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL6, "nemotron-3-super-120b-a12b-share64", "train-pack32k-docs4k",
        1)
    assert len(manifest["workloads"]) == 11 and len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    entry = manifest["configs"][-1]
    assert entry["name"] == "nemotron-3-super-120b-a12b-share64"
    assert len(entry["why"]) <= 200 and len(manifest["configs"]) == 8
    by_name = {m["name"]: m
               for m in manifest["per_layer"] + manifest["end_to_end"]}
    for name in FED + SHARED6 + ["train_samples_per_s"]:
        assert by_name[name]["workloads"][-1] == CELL6, name
    listed = {name for name, m in by_name.items()
              if CELL6 in m.get("workloads", [])}
    assert listed == set(FED + SHARED6 + NEW6 + ["train_samples_per_s"])
    # its reader counts 12 products a slot at the hidden width
    assert CELL6 not in by_name["lm_moe_experts_roofline_pct"]["workloads"]
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[-len(NEW6):] == NEW6
    for name in NEW6:
        m = by_name[name]
        assert m["workloads"] == [CELL6] and m["source"] == "device_trace"
        assert m["moves"] == "train_samples_per_s"
        assert m["layer"] == by_name[
            "lm_moe_device_ms" if "moe" in name else "lm_attn_device_ms"][
                "layer"]
        assert (m["unit"], m["better"]) == (
            ("%", "higher") if name.endswith("_pct") else ("ms", "lower"))
        assert osp.exists(osp.join(REPO, "benchmarks", "layer_metrics",
                                   name + ".py"))


def test_manifest_names_the_row_moves_metrics_in_the_sparse_cells(manifest):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[names.index(NEW47[0]):][:3] == NEW47
    for name in NEW47:
        m = by_name[name]
        assert m["layer"] == by_name["lm_moe_device_ms"]["layer"]
        assert m["moves"] == "train_samples_per_s"
        assert osp.exists(osp.join(REPO, "benchmarks", "layer_metrics",
                                   name + ".py"))
    for name in NEW47[:2]:
        assert by_name[name]["workloads"][:4] == SPARSE
        assert (by_name[name]["source"], by_name[name]["better"]) == (
            "device_trace", "lower")
    assert by_name["lm_moe_rows_live_pct"]["workloads"][:3] == SPARSE[1:]
    assert (by_name["lm_moe_rows_live_pct"]["source"],
            by_name["lm_moe_rows_live_pct"]["better"]) == (
        "program_counter", "higher")


@pytest.mark.parametrize("cell", list(CELLS))
def test_configuration_file_keeps_every_published_width(manifest, cell):
    entry = next(c for c in manifest["configs"]
                 if c["name"] == CELLS[cell]["config"])
    with open(osp.join(REPO, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == entry["reduced"]
    assert cfg["source"] == entry["source"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not osp.exists(catalog):
        pytest.skip("the catalog is not mounted here")
    with open(catalog) as f:
        row = next(json.loads(l) for l in f
                   if '"name": "%s"' % CELLS[cell]["model"] in l)
    assert entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] != value
            assert not key.endswith(("_dim", "_rank")) and key not in WIDTHS
        else:
            assert cfg[key] == value, key
    assert (cfg["deployment"]["chips_sharing_a_layer"]
            == CELLS[cell]["shares"])
    assert any(CELLS[cell]["assumes"] in a for a in cfg["assumed"])
    assert osp.exists(osp.join(REPO, cfg["plain_reference"].split(":")[0]))


@pytest.mark.parametrize("cell", list(CELLS))
def test_rehearsal_runs_the_cell_end_to_end_and_lists_what_it_would_report(
        cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed",
         "2147483659", "--seconds", "1", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == EXIT_REHEARSAL, proc.stderr[-2000:]
    line = next(l for l in proc.stdout.splitlines()
                if l.startswith("REHEARSAL "))
    out = json.loads(line[len("REHEARSAL "):])
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 3
    want = set(CELLS[cell]["reports"]) - CELLS[cell]["needs_a_chip"]
    assert want <= set(out["would_report"]), want - set(out["would_report"])
    counters = json.loads(next(
        l for l in proc.stdout.splitlines()
        if l.startswith("[bench] counters: "))[len("[bench] counters: "):])
    assert counters["window_compiles"] == 0
    assert counters["moe_dropped_slots"] == 0
    assert counters["flops_per_unit"] > 0
    assert "against their limits" in proc.stdout
    if cell in SPARSE[1:]:  # the held slots, each in a range once
        assert counters["moe_rows_live"] == counters["moe_slots_held"]
        assert counters["moe_rows_covered"] >= counters["moe_rows_live"]
    if cell == CELL2:  # both kinds of layer, counted apart
        assert counters["attn_block_pairs_visited_window"] > 0
        assert counters["attn_block_pairs_visited_full"] > 0
    if cell == CELL3:  # a dense stack; both kinds of pair a traced row needs
        assert counters["moe_slots_held"] == 0
        assert counters["attn_block_pairs_visited_local"] > 0
        assert counters["traced_pairs_local"] > 0
        assert counters["traced_pairs_remote"] > 0
        assert counters["attn_layers_local"] == 4
    if cell == CELL4:  # one attention layer's table beside four mixers
        assert counters["attn_block_pairs_visited_full"] > 0
        assert "attn_block_pairs_visited_window" not in counters
        assert (counters["attn_layers_conv"], counters["attn_layers_full"]
                ) == (4, 1)
        assert set(k for k in counters if k.startswith("traced_pairs_")
                   ) == {"traced_pairs_full"}
        assert counters["moe_slots_held"] > 0
    if cell == CELL6:  # three kinds of layer, each of one block
        assert (counters["attn_layers_mamba"], counters["attn_layers_full"],
                counters["attn_layers_experts"]) == (2, 1, 2)
        assert counters["attn_block_pairs_visited_full"] > 0
        assert set(k for k in counters if k.startswith("traced_pairs_")
                   ) == {"traced_pairs_full"}
        assert counters["moe_slots_held"] > 0
        assert counters["moe_rows_live"] == counters["moe_slots_held"]
        assert not any(k.startswith("ssm_") for k in counters)
    if cell == CELL5:  # both kinds of layer, a group of 7
        assert counters["attn_block_pairs_visited_window"] > 0
        assert counters["attn_block_pairs_visited_full"] > 0
        assert (counters["attn_layers_window"], counters["attn_layers_full"]
                ) == (3, 1)
        assert (counters["attn_heads_held"], counters["attn_kv_heads_held"]
                ) == (7, 1)
        assert counters["moe_slots_held"] > 0


def test_the_second_cells_configuration_states_its_cut_and_builds():
    """`parameters_held` is the program's own count, the share is the
    deployment's, and the runner builds the configuration from the file's
    `program` group alone."""
    import jax

    sys.path.insert(0, REPO)
    from benchmarks import harness
    from dexiraft_tpu.config import TrainConfig
    from dexiraft_tpu.train.family import family_of
    from dexiraft_tpu.train.state import param_count

    cell = harness.load_cell(CELL2)
    cfg, tc = harness.load_runner("lm_train_packed")._configs(cell, 0)
    held = cell.config["parameters_held"]
    params, _ = jax.eval_shape(family_of(cfg, TrainConfig()).init,
                               jax.random.PRNGKey(0))
    assert param_count(params) == held["total"] == 587_508_992
    assert held["state_bytes_at_16_a_parameter"] == 16 * held["total"]
    assert (cfg.heads_held, cfg.kv_heads_held, cfg.experts_held) == (
        (0, 4), (0, 1), (0, 16))
    assert (cfg.num_experts, cfg.num_attention_heads,
            cfg.num_key_value_heads) == (128, 32, 4)  # the router's width
    assert cfg.layer_types == ("sliding_attention",) * 4 + ("full_attention",)
    assert (cfg.seq_len, tc.batch_size, cfg.remat) == (32768, 1, True)
    # the cell times what the program selects: no tuned size of its own
    assert cfg.moe_chunk is None
    assert set(cell.traffic["model_flags"]) == {"seq_len", "remat"}


def test_the_third_cells_configuration_states_its_cut_and_builds():
    """`parameters_held` is the program's own count and the issue's
    arithmetic, the share is the deployment's, and the runner answers
    every read of an expert layer truthfully: there is none."""
    import dataclasses

    import jax

    sys.path.insert(0, REPO)
    from benchmarks import harness
    from dexiraft_tpu.config import EvaByteConfig, TrainConfig
    from dexiraft_tpu.train.family import family_of
    from dexiraft_tpu.train.state import param_count

    cell = harness.load_cell(CELL3)
    cfg, tc = harness.load_runner("lm_train_packed")._configs(cell, 0)
    assert isinstance(cfg, EvaByteConfig)
    held = cell.config["parameters_held"]
    params, _ = jax.eval_shape(family_of(cfg, TrainConfig()).init,
                               jax.random.PRNGKey(0))
    assert param_count(params) == held["total"] == 620_015_616
    assert held["total"] == 4 * held["layer"] + held[
        "embedding_head_and_final_norm"]
    assert held["layer"] == (held["attention_a_layer"]
                             + held["swiglu_a_layer"] + 2 * 4096)
    assert held["state_bytes_at_16_a_parameter"] == 16 * held["total"]
    assert (cfg.heads_held, cfg.num_attention_heads, cfg.num_hidden_layers
            ) == ((0, 8), 32, 4)
    assert (cfg.window_size, cfg.chunk_size, cfg.num_pred_heads,
            cfg.vocab_size, cfg.rope_theta, cfg.init_std) == (
                2048, 16, 8, 320, 100000, 0.01275)
    assert (cfg.seq_len, tc.batch_size, cfg.remat) == (32768, 1, True)
    assert set(cell.traffic["model_flags"]) == {"seq_len", "remat"}
    # what the runner reads of an expert layer
    assert (cfg.experts_held, cfg.n_routed_experts, cfg.moe_intermediate_size,
            cfg.first_k_dense_replace) == ((0, 0), 0, 0, 4)
    docs = cell.traffic["documents"]
    assert (docs["median"], docs["sigma"], docs["shortest"], docs["longest"],
            docs["count"]) == (6144, 1.2, 256, 32768, 512)
    assert (cell.traffic["batch"], cell.traffic["traced_steps"],
            cell.traffic["check"]["reference_block"]) == (1, 4, 2048)
    # controls: published keys the reference is given another value of
    controls = cell.traffic["check"]["controls"]
    assert {k for fault in controls.values() for k in fault} == {
        "window_size", "chunk_size", "rope_theta", "norm_add_unit_offset"}
    for fault in controls.values():
        assert dataclasses.replace(cfg, **fault) != cfg
    tol = cell.traffic["check"]["tolerances"]
    assert set(tol) == {"loss", "grad_norm"} | {
        "/".join(map(str, leaf)) for leaf in cell.traffic["check"]["leaves"]}


def test_the_fourth_cells_configuration_states_its_cut_and_builds():
    """`parameters_held` is the program's own count and the issue's
    arithmetic, the share is the deployment's, the traffic is the
    issue's letter for letter, and every read of the runner is met."""
    import dataclasses

    import jax

    sys.path.insert(0, REPO)
    from benchmarks import harness
    from dexiraft_tpu.config import Lfm2MoeConfig, TrainConfig
    from dexiraft_tpu.models.lm.moe import dispatch_chunk
    from dexiraft_tpu.train.family import family_of
    from dexiraft_tpu.train.state import param_count

    cell = harness.load_cell(CELL4)
    cfg, tc = harness.load_runner("lm_train_packed")._configs(cell, 0)
    assert isinstance(cfg, Lfm2MoeConfig)
    held = cell.config["parameters_held"]
    params, _ = jax.eval_shape(family_of(cfg, TrainConfig()).init,
                               jax.random.PRNGKey(0))
    assert param_count(params) == held["total"] == 499_955_840
    assert "head" not in params  # tied: one matrix
    assert held["total"] == (
        held["dense_layer"] + held["expert_layer_attention"]
        + 3 * held["expert_layer_conv"] + held["embedding_and_final_norm"])
    assert held["dense_layer"] == held["conv_mixer"] + 3 * 2048 * 7168 + 4096
    assert held["expert_layer_conv"] == (
        held["conv_mixer"] + held["experts_and_router_a_layer"] + 4096)
    assert held["expert_layer_attention"] == (
        held["attention_mixer"] + held["experts_and_router_a_layer"] + 4096)
    assert held["state_bytes_at_16_a_parameter"] == 16 * held["total"]
    assert (cfg.heads_held, cfg.kv_heads_held, cfg.experts_held) == (
        (0, 8), (0, 2), (0, 8))
    assert (cfg.num_experts, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim) == (32, 32, 8, 64)
    assert cfg.layer_types == ("conv", "full_attention", "conv", "conv",
                               "conv")
    assert (cfg.num_dense_layers, cfg.vocab_size, cfg.conv_L_cache,
            cfg.rope_theta, cfg.norm_eps) == (1, 16384, 3, 1e6, 1e-5)
    assert (cfg.seq_len, tc.batch_size, cfg.remat) == (32768, 1, True)
    assert cfg.moe_chunk is None
    assert dispatch_chunk(32768 * 4, 8, 32) == 49152
    assert set(cell.traffic["model_flags"]) == {"seq_len", "remat"}
    # what the runner reads of a configuration
    assert (cfg.n_routed_experts, cfg.first_k_dense_replace,
            cfg.moe_intermediate_size, cfg.qk_head_dim, cfg.hidden_size) == (
                32, 1, 1792, 64, 2048)
    docs = cell.traffic["documents"]
    assert (docs["median"], docs["sigma"], docs["shortest"], docs["longest"],
            docs["count"]) == (2048, 1.2, 64, 16384, 1024)
    assert (cell.traffic["batch"], cell.traffic["warm_steps"],
            cell.traffic["traced_steps"], cell.traffic["loader_drain_s"],
            cell.traffic["num_workers"], cell.traffic["prefetch_depth"],
            cell.traffic["lr"], cell.traffic["wdecay"],
            cell.traffic["check"]["reference_block"]) == (
                1, 3, 6, 3.0, 8, 2, 3e-4, 0.1, 2048)
    # controls: published keys the reference is given another value of
    controls = cell.traffic["check"]["controls"]
    assert {k: v for fault in controls.values() for k, v in fault.items()
            } == {"rope_theta": 10000.0, "norm_topk_prob": False,
                  "routed_scaling_factor": 2.0}
    for fault in controls.values():
        assert dataclasses.replace(cfg, **fault) != cfg
    leaves = ["/".join(map(str, leaf))
              for leaf in cell.traffic["check"]["leaves"]]
    assert leaves == ["layers_2/conv/w_in", "layers_2/conv/taps",
                      "layers_1/attn/wk", "layers_4/moe/experts/router",
                      "layers_2/moe/experts/w_down/0", "layers_3/conv/w_out",
                      "layers_0/mlp/w_down", "embed"]
    # every layer held carries a checked leaf: a layer the program lacked
    # would fail by its own
    assert {leaf.split("/")[0] for leaf in leaves} == {"embed"} | {
        f"layers_{i}" for i in range(cfg.num_hidden_layers)}
    assert set(cell.traffic["check"]["tolerances"]) == {
        "loss", "grad_norm"} | set(leaves)
    assert cell.config["program"]["scopes"].count("lm/moe/shared") == 0
    # the other three still build from their files
    for other in (CELL2, CELL3):
        harness.load_runner("lm_train_packed")._configs(
            harness.load_cell(other), 0)


def test_a_control_goes_through_the_checks_own_comparison():
    """The builder's other readings (`LM_CHECK_SECOND_READING`): each
    control is a fault the configuration can express, and its readings
    meet the limits the way the program's do."""
    import dataclasses

    sys.path.insert(0, REPO)
    from benchmarks import harness

    cell = harness.load_cell(CELL2)
    runner = harness.load_runner("lm_train_packed")
    cfg, _ = runner._configs(cell, 0)
    controls = cell.traffic["check"]["controls"]
    assert set(controls) == {"no_window", "no_route_scale", "no_route_norm",
                             "no_embedding_scale"}
    for fault in controls.values():
        faulty = dataclasses.replace(cfg, **fault)
        assert faulty != cfg
    assert dataclasses.replace(cfg, **controls["no_window"]).layer_window(
        0) == cfg.seq_len
    tol = {"loss": 1e-3, "wk": 0.1}
    lines = []
    for readings in ({"loss": 2e-3, "wk": 0.05}, {"loss": 2e-4, "wk": 0.05},
                     {"loss": float("nan"), "wk": 0.2}):
        runner._control(lines.append, "a fault", readings, tol)
    assert lines[0].endswith("FAILED by loss")
    assert "FAILED" not in lines[1] and "ok" in lines[1]
    assert lines[2].endswith("FAILED by loss, wk")
    assert [runner._within(r, tol) for r in (
        {"loss": 2e-3, "wk": 0.05}, {"loss": 2e-4, "wk": 0.05})] == [
            False, True]
    docs = cell.traffic["documents"]
    assert (docs["median"], docs["sigma"], docs["shortest"], docs["longest"],
            docs["count"]) == (8192, 1.0, 1024, 16384, 512)
    assert cell.config["deployment"]["chips_sharing_a_layer"] == 8


def test_the_fifth_cells_configuration_states_its_cut_and_builds():
    """`parameters_held` is the program's own count and the issue's
    arithmetic, the share is the deployment's, the traffic is the
    issue's letter for letter, and every control is a field of the
    configuration that the reference reads."""
    import dataclasses

    import jax

    sys.path.insert(0, REPO)
    from benchmarks import harness
    from dexiraft_tpu.config import TrainConfig
    from dexiraft_tpu.train.family import family_of
    from dexiraft_tpu.train.state import param_count

    cell = harness.load_cell(CELL5)
    cfg, tc = harness.load_runner("lm_train_packed")._configs(cell, 0)
    held = cell.config["parameters_held"]
    params, stats = jax.eval_shape(family_of(cfg, TrainConfig()).init,
                                   jax.random.PRNGKey(0))
    assert param_count(params) == held["total"] == 593_615_360
    assert not jax.tree.leaves(stats)  # no bias buffer under a softmax
    assert held["total"] == 4 * held["layer"] + held[
        "embedding_and_head"] + held["final_norm"]
    assert held["layer"] == (held["attention_a_layer"]
                             + held["experts_a_layer"]
                             + held["router_a_layer"] + held["norms_a_layer"])
    assert held["state_bytes_at_16_a_parameter"] == 16 * held["total"]
    assert "q_norm" not in params["layers_0"]["attn"]
    assert (cfg.heads_held, cfg.kv_heads_held, cfg.experts_held) == (
        (0, 7), (0, 1), (0, 16))
    assert (cfg.moe_num_primary_experts, cfg.num_attention_heads,
            cfg.num_key_value_heads) == (64, 28, 4)  # the router's width
    assert cfg.sliding_window_layout == cfg.rope_layout == (0, 1, 1, 1)
    assert (cfg.hidden_size, cfg.head_dim, cfg.moe_ffn_hidden_size,
            cfg.moe_num_active_primary_experts, cfg.sliding_window_size,
            cfg.rope_theta, cfg.rms_norm_eps, cfg.vocab_size) == (
                2560, 128, 768, 6, 4096, 1.5e6, 1e-6, 37_984)
    assert (cfg.seq_len, tc.batch_size, cfg.remat, tc.precision, tc.lr,
            tc.wdecay) == (16384, 1, True, "bf16", 3e-4, 0.1)
    assert cfg.moe_chunk is None  # what the program selects: 32,768 rows
    from dexiraft_tpu.models.lm.moe import dispatch_chunk
    assert dispatch_chunk(16384 * 6, 16, 64) == 32768
    assert set(cell.traffic["model_flags"]) == {"seq_len", "remat"}
    docs = cell.traffic["documents"]
    assert (docs["median"], docs["sigma"], docs["shortest"], docs["longest"],
            docs["count"]) == (6144, 1.0, 1024, 16384, 512)
    assert (cell.traffic["num_workers"], cell.traffic["prefetch_depth"],
            cell.traffic["warm_steps"], cell.traffic["traced_steps"],
            cell.traffic["check"]["reference_block"]) == (8, 2, 3, 6, 2048)
    assert cell.config["deployment"]["chips_sharing_a_layer"] == 4
    # controls: fields of the configuration the reference is given
    # another value of; each changes what the reference reads
    controls = cell.traffic["check"]["controls"]
    assert set(controls) == {"router_after_attention", "no_window",
                             "rope_on_full_layers", "silu_experts",
                             "sigmoid_router"}
    for fault in controls.values():
        assert dataclasses.replace(cfg, **fault) != cfg
    assert dataclasses.replace(cfg, **controls["no_window"]).layer_window(
        1) == cfg.seq_len
    assert dataclasses.replace(
        cfg, **controls["rope_on_full_layers"]).layer_rope(0)
    leaves = ["/".join(map(str, leaf))
              for leaf in cell.traffic["check"]["leaves"]]
    assert set(cell.traffic["check"]["tolerances"]) == {
        "loss", "grad_norm"} | set(leaves)
    assert any("router" in leaf for leaf in leaves)
    # a window layer's W_k and the full layer's
    assert {"layers_1/attn/wk", "layers_0/attn/wk"} <= set(leaves)
    assert [cfg.layer_window(i) for i in (0, 1)] == [None, 4096]


def test_the_sixth_cells_configuration_states_its_cut_and_builds():
    """`parameters_held` is the program's own count and the issue's
    arithmetic, the share is the deployment's, the traffic is the
    issue's, and every control is a field of the configuration that the
    reference reads."""
    import dataclasses

    import jax

    sys.path.insert(0, REPO)
    from benchmarks import harness, lm_counts_nemotron
    from dexiraft_tpu.config import TrainConfig, nemotron_h_toy
    from dexiraft_tpu.models.lm.moe import dispatch_chunk
    from dexiraft_tpu.train.family import family_of
    from dexiraft_tpu.train.state import param_count

    cell = harness.load_cell(CELL6)
    cfg, tc = harness.load_runner("lm_train_packed")._configs(cell, 0)
    held = cell.config["parameters_held"]
    params, stats = jax.eval_shape(family_of(cfg, TrainConfig()).init,
                                   jax.random.PRNGKey(0))
    assert param_count(params) == held["total"] == 508_187_120
    assert held["total"] == (5 * held["mamba_layer"] + held["attention_layer"]
                             + 5 * held["expert_layer"]
                             + held["embedding_and_head"]
                             + held["final_norm"])
    assert held["state_bytes_at_16_a_parameter"] == 16 * held["total"]
    count = lambda tree: sum(  # noqa: E731
        int(a.size) for a in jax.tree.leaves(tree))
    assert [count(params[f"layers_{i}"]) for i in (0, 9, 10)] == [
        held["mamba_layer"], held["attention_layer"], held["expert_layer"]]
    # the toy constructor's count by the same formulas at its widths
    toy, _ = harness.load_runner("lm_train_packed")._configs(
        harness.load_cell(CELL6, rehearsal=True), 0)
    toy_params, _ = jax.eval_shape(family_of(toy, TrainConfig()).init,
                                   jax.random.PRNGKey(0))
    d, inner, bc = 64, 4 * 8, 2 * 16
    mamba = (d + d * (2 * inner + 2 * bc + 4) + (inner + 2 * bc) * 5 + 12
             + inner + inner * d)
    attention = d + 2 * d * 32 + 2 * d * 8
    expert = d + d * 16 + 2 * d * 32 + 4 * 2 * 32 * 24 + 2 * d * 24
    assert param_count(toy_params) == (2 * mamba + attention + 2 * expert
                                       + 2 * 256 * d + d)
    assert toy == nemotron_h_toy(
        ssm_heads_held=(0, 4), heads_held=(0, 4), experts_held=(0, 4),
        shared_columns_held=(0, 24), seq_len=128, remat=True)
    assert (cfg.ssm_heads_held, cfg.ssm_groups_held, cfg.heads_held,
            cfg.kv_heads_held, cfg.experts_held, cfg.shared_columns_held
            ) == ((0, 16), (0, 1), (0, 4), (0, 1), (0, 8), (0, 672))
    assert cell.config["deployment"]["ssm_groups_held"] == [0, 1]
    assert (cfg.mamba_num_heads, cfg.n_groups, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.n_routed_experts) == (
                128, 8, 32, 2, 512)  # the whole counts
    assert (cfg.hidden_size, cfg.mamba_head_dim, cfg.ssm_state_size,
            cfg.conv_kernel, cfg.chunk_size, cfg.head_dim,
            cfg.moe_latent_size, cfg.moe_intermediate_size,
            cfg.moe_shared_expert_intermediate_size, cfg.num_experts_per_tok,
            cfg.routed_scaling_factor, cfg.vocab_size,
            cfg.hybrid_override_pattern) == (
                4096, 64, 128, 4, 128, 128, 1024, 2688, 5376, 22, 5, 16_384,
                "MEMEMEMEM*E")
    assert (cfg.seq_len, tc.batch_size, cfg.remat, tc.precision, tc.lr,
            tc.wdecay) == (32_768, 1, True, "bf16", 3e-4, 0.1)
    # the names the runner reads of a configuration
    assert (cfg.first_k_dense_replace, cfg.qk_head_dim) == (0, 128)
    assert lm_counts_nemotron.layers_by_kind(cfg) == {
        "mamba": 5, "full": 1, "experts": 5}
    assert cfg.moe_chunk is None  # what the program selects: 16,384 rows
    assert dispatch_chunk(32_768 * 22, 8, 512) == 16_384
    docs = cell.traffic["documents"]
    assert (docs["median"], docs["sigma"], docs["shortest"], docs["longest"],
            docs["count"]) == (4096, 1.0, 256, 16_384, 768)
    assert (cell.traffic["num_workers"], cell.traffic["prefetch_depth"],
            cell.traffic["warm_steps"], cell.traffic["traced_steps"],
            cell.traffic["check"]["reference_block"]) == (8, 2, 3, 4, 2048)
    assert (cell.config["deployment"]["chips_sharing_a_layer"],
            cell.config["deployment"]["pipeline_stages"]) == (64, 8)
    assert set(cell.config["program"]["scopes"]) >= {
        "lm/ssm/in", "lm/ssm/conv", "lm/ssm/scan", "lm/ssm/gate_norm",
        "lm/ssm/out", "lm/moe/latent"}
    controls = cell.traffic["check"]["controls"]
    assert set(controls) == {
        "no_state_carry", "state_across_documents", "norm_before_gate",
        "rope_on_attention", "router_reads_latent", "relu_experts",
        "gated_experts", "no_D_skip"} | {
            f"without_layer_{i}" for i in range(11)}
    # every held layer carries a leaf: one that adds nothing cannot pass
    assert {leaf[0] for leaf in cell.traffic["check"]["leaves"]} == {
        f"layers_{i}" for i in range(11)}
    for fault in controls.values():
        assert dataclasses.replace(cfg, **fault) != cfg
    leaves = ["/".join(map(str, leaf))
              for leaf in cell.traffic["check"]["leaves"]]
    assert set(cell.traffic["check"]["tolerances"]) == {
        "loss", "grad_norm"} | set(leaves)
    tree = {"/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]}
    for leaf in leaves:  # every layer kind carries one
        assert leaf.removesuffix("/0") in tree, leaf
    for needle in ("ssm/in_proj", "ssm/taps", "ssm/A_log", "ssm/dt_bias",
                   "ssm/out_proj", "attn/wk", "experts/router",
                   "latent_down", "experts/w_down", "shared/w_up"):
        assert any(needle in leaf for leaf in leaves), needle


def _train(*flags):
    return subprocess.run(
        [sys.executable, "-m", "dexiraft_tpu", "train", *flags], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)


@pytest.mark.parametrize("flags,named", [
    (["--iters", "3"], ["--iters"]),
    (["--corr_impl", "flash", "--edge_root", "/x"], ["--corr_impl", "--edge_root"]),
    (["--remat_lookup"], ["--remat_lookup"]),
    (["--stage", "chairs", "--small"], ["--stage", "--small"]),
])
def test_train_cli_refuses_raft_only_flags_for_the_language_model_by_name(
        flags, named):
    proc = _train("--variant", "kanana2", "--tokens", "none.npz", *flags)
    assert proc.returncode != 0
    for flag in named:
        assert flag in proc.stderr, proc.stderr[-500:]


def test_train_cli_refuses_the_language_models_flags_for_raft():
    proc = _train("--variant", "v1", "--stage", "chairs", "--experts_held",
                  "0", "16")
    assert proc.returncode != 0 and "--experts_held" in proc.stderr
    proc = _train("--variant", "v1")
    assert proc.returncode != 0 and "--stage is required" in proc.stderr


@pytest.mark.parametrize("variant,share", [
    ("kanana2-toy", ("--heads_held", "0", "2", "--experts_held", "0", "4")),
    # a dense sliding layer, an expert sliding layer, the full layer
    ("trinity-mini-toy", ("--heads_held", "2", "2", "--kv_heads_held", "1",
                          "1", "--layers", "3", "--dense_layers", "1",
                          "--layer_types", "sliding_attention",
                          "sliding_attention", "full_attention",
                          "--experts_held", "0", "4")),
    # two of four shares' heads, two layers
    ("evabyte-toy", ("--heads_held", "2", "4", "--layers", "2")),
    # a dense convolution layer and the attention expert layer; one
    # key/value head's whole group
    ("lfm2-8b-a1b-toy", ("--heads_held", "4", "4", "--kv_heads_held", "1",
                         "1", "--layers", "2", "--dense_layers", "1",
                         "--layer_types", "conv", "full_attention",
                         "--experts_held", "0", "4")),
    # the full layer and a sliding one; one key/value head's group of 7
    ("smallthinker-21b-toy", ("--heads_held", "7", "7", "--kv_heads_held",
                              "1", "1", "--layers", "2", "--experts_held",
                              "0", "4")),
    # M E M * E; one group of Mamba heads, a key/value head's second half,
    # a quarter of the experts and of the shared expert's columns
    ("nemotron-h-toy", ("--ssm_heads_held", "2", "2", "--heads_held", "2",
                        "2", "--experts_held", "4", "4",
                        "--shared_columns_held", "12", "12"))])
def test_train_cli_trains_the_toy_model_through_the_normal_path(
        tmp_path, variant, share):
    import numpy as np

    from dexiraft_tpu.data.tokens import write_token_file

    rng = np.random.default_rng(5)
    lengths = np.clip(np.exp(rng.normal(np.log(40), 1.0, 200)).astype(int),
                      4, 128)
    tokens = str(tmp_path / "toy.npz")
    write_token_file(tokens, rng.integers(0, 256, lengths.sum()), lengths)
    proc = _train("--variant", variant, "--tokens", tokens,
                  "--batch_size", "2", "--num_steps", "4", "--val_freq", "2",
                  "--sum_freq", "2", "--precision", "bf16", "--remat", *share,
                  "--num_workers", "2", "--output", str(tmp_path / "ck"),
                  "--log_dir", str(tmp_path / "runs"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "Done: 4 steps" in proc.stdout
    assert "packed rows of 128 positions" in proc.stdout
    with open(tmp_path / "runs" / variant / "metrics.jsonl") as f:
        last = [r for r in map(json.loads, f) if "loss" in r][-1]
    assert last["moe_dropped_slots"] == 0 and last["tokens_real"] > 0
    assert osp.isdir(tmp_path / "ck" / variant)


def test_train_cli_refuses_a_share_that_splits_a_key_value_head(tmp_path):
    proc = _train("--variant", "trinity-mini-toy", "--tokens", "x.npz",
                  "--heads_held", "1", "3")  # heads 1 | 2, 3 of two
    assert proc.returncode != 0 and "divide evenly" in proc.stderr
    proc = _train("--variant", "kanana2-toy", "--tokens", "x.npz",
                  "--kv_heads_held", "0", "1")
    assert proc.returncode != 0 and "--kv_heads_held" in proc.stderr


@pytest.mark.parametrize("flags,named", [
    (["--experts_held", "0", "4"], "--experts_held"),
    (["--dense_layers", "1"], "--dense_layers"),
    (["--layer_types", "full_attention"], "--layer_types"),
])
def test_train_cli_refuses_for_evabyte_what_a_dense_stack_has_not(flags,
                                                                  named):
    proc = _train("--variant", "evabyte-toy", "--tokens", "x.npz", *flags)
    assert proc.returncode != 0 and named in proc.stderr, proc.stderr[-500:]
