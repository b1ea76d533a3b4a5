"""A later PR adds and never edits: a cell, a configuration, a traffic
mix, a traffic kind and a per-layer metric, each as new files plus one
entry in BENCHMARK.json, in a temporary copy of the benchmark; then the
rehearsal runs the new cell there. Also: a checkout without the program
gives no result."""

import json
import os
import os.path as osp
import shutil
import subprocess
import sys

from benchmarks import harness

EXIT_REHEARSAL = 4

RUNNER = '''
"""Traffic kind `matmul_chain`: a stand-in kind for the extension test."""
import time
from benchmarks import harness


def run(ctx):
    import jax, jax.numpy as jnp
    n = ctx.cell.traffic["n"]
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jax.block_until_ready(f(jnp.ones((n, n)) * 1e-3))
    pacer = harness.Pacer(ctx.seconds, 0.01)
    while pacer.more():
        pacer.note_dispatch()
        x = jax.block_until_ready(f(x))
        pacer.note_finish()
    return harness.Outcome(
        attempted=pacer.dispatched, failed=0, correct=True,
        end_to_end={"chain_calls_per_s": pacer.finished / pacer.elapsed},
        window_start=pacer.start, counters={"calls": pacer.finished})
'''

METRIC = '''
"""Calls the window made."""


def read(obs):
    return obs.counters.get("calls")
'''


def _copy(tmp_path, with_program=True):
    shutil.copytree(harness.BENCH_DIR, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(osp.join(harness.REPO, "BENCHMARK.json"), tmp_path)
    if with_program:
        os.symlink(osp.join(harness.REPO, "dexiraft_tpu"),
                   tmp_path / "dexiraft_tpu")
    return tmp_path


def _run(root, workload, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(root / ".jax_cache"))
    return subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", workload,
         "--seed", "3", "--seconds", "0.2", "--trace", str(trace)],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def _rehearsal_line(proc):
    assert proc.returncode == EXIT_REHEARSAL, proc.stderr[-2000:]
    last = proc.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL "), last
    return json.loads(last[len("REHEARSAL "):])


def test_one_of_each_is_added_by_new_files_and_one_entry(tmp_path):
    root = _copy(tmp_path)
    before = {p: open(p, "rb").read() for p in
              (str(q) for q in (root / "benchmarks").rglob("*") if q.is_file())}
    bench = root / "benchmarks"
    (bench / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "source": "this test", "constructor": "raft_v1",
         "reduced": []}))
    (bench / "traffic" / "chain.json").write_text(json.dumps(
        {"kind": "matmul_chain", "n": 64}))
    (bench / "runners" / "matmul_chain.py").write_text(RUNNER)
    (bench / "layer_metrics" / "chain_calls.py").write_text(METRIC)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append(
        {"name": "toy", "source": "this test",
         "file": "benchmarks/configs/toy.json", "reduced": [], "why": "test"})
    manifest["workloads"].append(
        {"name": "toy-chain", "config": "toy", "traffic": "chain",
         "chips": 1, "why": "test"})
    manifest["end_to_end"].append(
        {"name": "chain_calls_per_s", "unit": "calls/s", "better": "higher",
         "bound": 0.03, "source": "host_clock", "workloads": ["toy-chain"]})
    manifest["per_layer"].append(
        {"name": "chain_calls", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "toy layer",
         "moves": "chain_calls_per_s", "workloads": ["toy-chain"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))

    timed = _rehearsal_line(_run(root, "toy-chain", 0))
    assert timed["correct"] and timed["attempted"] >= 3
    assert timed["would_report"] == ["chain_calls_per_s", "setup_s"]
    traced = _rehearsal_line(_run(root, "toy-chain", 1))
    # its own metric, and the set-up spans every cell has; nothing that
    # moves another kind's end-to-end metric
    assert "chain_calls" in traced["would_report"]
    assert not [m for m in traced["would_report"]
                if m.startswith(("eval_", "train_"))]
    # no file the benchmark already had was edited
    for path, content in before.items():
        assert open(path, "rb").read() == content, path


def test_an_existing_cell_rehearses_end_to_end(tmp_path):
    """v1-eval-sintel at the traffic file's rehearsal size: engine, check
    against the plain path, window, traced tail. No value is printed."""
    root = _copy(tmp_path)
    proc = _run(root, "v1-eval-sintel", 1)
    line = _rehearsal_line(proc)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert {"engine_dispatch_ms", "eval_window_compiles", "setup_init_s",
            "setup_warm_s", "setup_check_s"} <= set(line["would_report"])
    assert '"metrics"' not in proc.stdout


def test_no_program_or_no_accelerator_gives_no_result(tmp_path):
    root = _copy(tmp_path, with_program=False)
    proc = _run(root, "v1-eval-sintel", 0)
    assert proc.returncode not in (0, EXIT_REHEARSAL)
    assert "REHEARSAL" not in proc.stdout and '"metrics"' not in proc.stdout
    assert "not in this checkout" in proc.stderr


def test_unknown_workload_gives_no_result(tmp_path):
    root = _copy(tmp_path)
    proc = _run(root, "no-such-cell", 0)
    assert proc.returncode == 1 and proc.stdout.strip() == ""
