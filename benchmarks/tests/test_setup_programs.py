"""The set-up metrics that read the compile listener's records by root
(`analysis.guards.jax_at_warm`: `jax:<phase>/<root>`, `jax:uncached/<root>`)
and the runner's `import` span: each gives a number in the CPU rehearsal
of a train and an eval cell, the by-root seconds add up to the phases',
and each gives nothing, without raising, on a program that keeps no
records by root (the parent commit, on which the driver runs these files
too)."""

import functools
import json
import os
import subprocess
import sys

import pytest

from benchmarks import harness
from test_extend import EXIT_REHEARSAL, _copy

BY_ROOT = ("setup_step_programs_s", "setup_other_programs_s",
           "setup_programs", "setup_uncached_programs")
PHASES = ("setup_jax_trace_s", "setup_jax_lower_s", "setup_backend_compile_s",
          "setup_cache_load_s")
NEW = set(BY_ROOT) | {"setup_import_s"}
CELLS = ("v1-eval-sintel", "v5-train-chairs")

# the traced rehearsal, then what the readers of the at-warm copy give in
# that process: the REHEARSAL line carries names and never a CPU value
REHEARSE = """
import json, sys
from benchmarks import harness, run
rc = run.main(["--workload", sys.argv[1], "--seed", "3", "--seconds", "0.2",
               "--trace", "1"])
print("READ " + json.dumps({n: harness.load_metric(n).read(None)
                            for n in sys.argv[2:]}), flush=True)
sys.exit(rc)
"""


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """`cell` -> (the REHEARSAL line, the readers' values) of one traced
    rehearsal of it on a cold cache, run once for the tests that ask."""
    return functools.lru_cache(maxsize=None)(
        lambda cell: _rehearse(tmp_path_factory.mktemp(cell), cell))


def _rehearse(tmp_path, cell):
    root = _copy(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(root),
               JAX_COMPILATION_CACHE_DIR=str(root / ".jax_cache"))
    proc = subprocess.run(
        [sys.executable, "-c", REHEARSE, cell, *BY_ROOT, *PHASES],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == EXIT_REHEARSAL, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    line = next(l for l in lines if l.startswith("REHEARSAL "))
    read = next(l for l in lines if l.startswith("READ "))
    return (json.loads(line[len("REHEARSAL "):]),
            json.loads(read[len("READ "):]))


@pytest.mark.parametrize("cell", CELLS)
def test_each_new_reader_gives_a_number_in_the_cells_rehearsal(rehearsed,
                                                               cell):
    line, _ = rehearsed(cell)
    assert line["correct"]
    assert NEW <= set(line["would_report"])


@pytest.mark.parametrize("cell", CELLS)
def test_seconds_by_root_add_up_to_the_phases(rehearsed, cell):
    _, read = rehearsed(cell)
    assert all(isinstance(read[n], (int, float)) for n in BY_ROOT + PHASES)
    assert read["setup_step_programs_s"] > 0.0    # the step has its root
    assert read["setup_other_programs_s"] > 0.0   # `init` at the least
    assert (read["setup_step_programs_s"] + read["setup_other_programs_s"]
            == pytest.approx(sum(read[n] for n in PHASES), rel=1e-9))
    # a cold cache: the step, `init` and the rest were each built, and
    # each was a miss
    assert read["setup_programs"] >= 2
    assert 2 <= read["setup_uncached_programs"] <= read["setup_programs"]


def test_every_new_reader_is_in_the_manifest_under_its_layer():
    entries = {m["name"]: m for m in harness.load_manifest()["per_layer"]}
    for name in NEW:
        m = entries[name]
        assert (m["layer"], m["moves"], m["better"]) == (
            "entry points and set-up", "setup_s", "lower"), name
        assert "workloads" not in m, name      # every cell, as setup_s
        assert m["source"] == ("host_clock" if name == "setup_import_s"
                               else "program_counter"), name


def _obs(spans):
    return harness.Observation(spans=spans, counters={}, end_to_end={},
                               trace=None, peaks=None, chips=1,
                               memory_peak_bytes=0)


def _rec(seconds, count=1):
    return {"seconds": seconds, "count": count, "durations": [seconds]}


def test_readers_take_the_step_root_apart_from_the_others(monkeypatch):
    from dexiraft_tpu.analysis import guards

    table = {
        "jax:trace": _rec(4.0, 3), "jax:lower": _rec(1.0, 4),
        "jax:backend_compile": _rec(2.5, 4), "jax:cache_load": _rec(0.5, 1),
        "jax:trace/step": _rec(3.0), "jax:lower/step": _rec(0.5, 2),
        "jax:backend_compile/step": _rec(0.25, 2),
        "jax:cache_load/step": _rec(0.5),
        "jax:uncached/step": _rec(9.0),    # a compile's seconds: no phase
        "jax:trace/init": _rec(1.0, 2), "jax:lower/init": _rec(0.25),
        "jax:backend_compile/init": _rec(2.0), "jax:uncached/init": _rec(2.0),
        "jax:lower/add": _rec(0.25), "jax:backend_compile/add": _rec(0.25),
    }
    monkeypatch.setattr(guards, "jax_at_warm", lambda: table)
    got = {n: harness.load_metric(n).read(_obs({})) for n in BY_ROOT}
    assert got == {"setup_step_programs_s": 4.25,
                   "setup_other_programs_s": 3.75,
                   "setup_programs": 4, "setup_uncached_programs": 2}
    assert sum(table[p]["seconds"] for p in (
        "jax:trace", "jax:lower", "jax:backend_compile",
        "jax:cache_load")) == 8.0
    # every compile read its entry: zero, not nothing
    warm = {k: v for k, v in table.items() if "uncached" not in k}
    monkeypatch.setattr(guards, "jax_at_warm", lambda: warm)
    assert harness.load_metric("setup_uncached_programs").read(_obs({})) == 0
    assert harness.load_metric("setup_import_s").read(
        _obs({"import": 12.5, "init": 3.0})) == 12.5


def test_readers_give_nothing_on_a_program_without_the_records(monkeypatch):
    from dexiraft_tpu.analysis import guards

    # the parent's table: the four totals, nothing by root
    monkeypatch.setattr(guards, "jax_at_warm", lambda: {
        "jax:trace": _rec(4.0, 8519), "jax:lower": _rec(1.0, 5),
        "jax:backend_compile": _rec(2.5, 5)})
    for name in BY_ROOT:
        assert harness.load_metric(name).read(_obs({})) is None, name
    monkeypatch.setattr(guards, "jax_at_warm", dict)  # never marked warm
    for name in BY_ROOT:
        assert harness.load_metric(name).read(_obs({})) is None, name
    monkeypatch.delattr(guards, "jax_at_warm")
    for name in BY_ROOT:
        assert harness.load_metric(name).read(_obs({})) is None, name
    assert harness.load_metric("setup_import_s").read(_obs({})) is None
