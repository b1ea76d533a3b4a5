"""The new cell's rehearsal (`JAX_PLATFORMS=cpu`, exit 4), its manifest
entries and its configuration file, and `train_cli`'s refusals."""

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
          "num_experts_per_tok", "n_shared_experts"}


@pytest.fixture(scope="module")
def manifest():
    with open(osp.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_manifest_names_the_cell_and_every_metric_of_section_6(manifest):
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kanana-2-30b-a3b-share8", "train-pack8k", 1)
    assert len(manifest["workloads"]) == 5
    by_name = {m["name"]: m for m in manifest["per_layer"] + manifest["end_to_end"]}
    for name in FED + NEW + ["train_samples_per_s"]:
        assert CELL in by_name[name]["workloads"], name
        assert osp.exists(osp.join(REPO, "benchmarks", "layer_metrics",
                                   name + ".py")) or name == "train_samples_per_s"
    for name in SETUP:  # no list: every cell that reports setup_s
        assert "workloads" not in by_name[name]
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_samples_per_s"
    for name in ("train_loop_device_ms_per_iter", "train_prelude_device_ms"):
        assert CELL not in by_name[name]["workloads"]  # no refinement loop


def test_configuration_file_keeps_every_published_width(manifest):
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "kanana-2-30b-a3b-share8")
    with open(osp.join(REPO, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == entry["reduced"]
    assert cfg["source"] == entry["source"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not osp.exists(catalog):
        pytest.skip("the catalog is not mounted here")
    with open(catalog) as f:
        row = next(json.loads(l) for l in f
                   if '"kanana-2-30b-a3b-instruct-2601"' in l)
    assert entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] != value
            assert not key.endswith(("_dim", "_rank")) and key not in WIDTHS
        else:
            assert cfg[key] == value, key
    assert cfg["deployment"]["chips_sharing_a_layer"] == 8
    assert any("e_score_correction_bias" in a for a in cfg["assumed"])
    assert osp.exists(osp.join(REPO, cfg["plain_reference"].split(":")[0]))


def test_rehearsal_runs_the_cell_end_to_end_and_lists_what_it_would_report():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "2147483659", "--seconds", "1", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == EXIT_REHEARSAL, proc.stderr[-2000:]
    line = next(l for l in proc.stdout.splitlines()
                if l.startswith("REHEARSAL "))
    out = json.loads(line[len("REHEARSAL "):])
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 3
    want = set(FED + NEW + SETUP) - NEEDS_A_CHIP
    assert want <= set(out["would_report"]), want - set(out["would_report"])
    counters = json.loads(next(
        l for l in proc.stdout.splitlines()
        if l.startswith("[bench] counters: "))[len("[bench] counters: "):])
    assert counters["window_compiles"] == 0
    assert counters["moe_dropped_slots"] == 0
    assert counters["flops_per_unit"] > 0
    assert "against their limits" in proc.stdout


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


def test_train_cli_trains_the_toy_model_through_the_normal_path(tmp_path):
    import numpy as np

    from dexiraft_tpu.data.tokens import write_token_file

    rng = np.random.default_rng(5)
    lengths = np.clip(np.exp(rng.normal(np.log(40), 1.0, 200)).astype(int),
                      4, 128)
    tokens = str(tmp_path / "toy.npz")
    write_token_file(tokens, rng.integers(0, 256, lengths.sum()), lengths)
    proc = _train("--variant", "kanana2-toy", "--tokens", tokens,
                  "--batch_size", "2", "--num_steps", "4", "--val_freq", "2",
                  "--sum_freq", "2", "--precision", "bf16", "--remat",
                  "--experts_held", "0", "4", "--heads_held", "0", "2",
                  "--num_workers", "2", "--output", str(tmp_path / "ck"),
                  "--log_dir", str(tmp_path / "runs"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "Done: 4 steps" in proc.stdout
    assert "packed rows of 128 positions" in proc.stdout
    with open(tmp_path / "runs" / "kanana2-toy" / "metrics.jsonl") as f:
        last = [r for r in map(json.loads, f) if "loss" in r][-1]
    assert last["moe_dropped_slots"] == 0 and last["tokens_real"] > 0
    assert osp.isdir(tmp_path / "ck" / "kanana2-toy")
