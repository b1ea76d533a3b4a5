"""The per-layer metrics that read the program's span table
(`dexiraft_tpu.profiling.snapshot`, `analysis.guards.jax_at_warm`): each
gives a number in the CPU rehearsal of its cells, and nothing, without
raising, on a program that has no such table (the parent commit, on
which the driver runs these files too)."""

import pytest

from benchmarks import harness
from test_extend import _copy, _rehearsal_line, _run

ENGINE = {"engine_assemble_ms", "engine_put_ms", "engine_enqueue_ms",
          "engine_copy_out_ms", "engine_device_wait_ms",
          "engine_host_busy_ms"}
INPUT = {"loader_wait_ms", "loader_stack_ms", "loader_decode_ms",
         "prefetch_put_ms"}
SETUP = {"setup_jax_trace_s", "setup_jax_lower_s", "setup_backend_compile_s",
         "setup_cache_load_s"}


@pytest.mark.parametrize("cell,expected", [
    ("v1-eval-sintel", ENGINE | SETUP), ("v5-train-chairs", INPUT | SETUP)])
def test_each_reader_gives_a_number_in_its_cells_rehearsal(tmp_path, cell,
                                                           expected):
    line = _rehearsal_line(_run(_copy(tmp_path), cell, 1))
    assert line["correct"]
    assert expected <= set(line["would_report"])
    others = (ENGINE | INPUT | SETUP) - expected
    assert not others & set(line["would_report"])


def test_every_reader_is_in_the_manifest_under_its_layer():
    layers = {m["name"]: (m["layer"], m["moves"], m["source"])
              for m in harness.load_manifest()["per_layer"]}
    for names, layer, moves in (
            (ENGINE, "eval host", "eval_pairs_per_s"),
            (INPUT, "host input", "train_samples_per_s"),
            (SETUP, "entry points and set-up", "setup_s")):
        for name in names:
            assert layers[name] == (layer, moves, "program_counter"), name


def test_readers_give_nothing_on_a_program_without_the_table(monkeypatch):
    from dexiraft_tpu import profiling
    from dexiraft_tpu.analysis import guards

    monkeypatch.delattr(profiling, "snapshot")
    monkeypatch.delattr(guards, "jax_at_warm")
    obs = harness.Observation(
        spans={}, counters={"engine_batches": 7, "prefetch_batches": 15},
        end_to_end={}, trace=None, peaks=None, chips=1, memory_peak_bytes=0)
    for name in sorted(ENGINE | INPUT | SETUP):
        assert harness.load_metric(name).read(obs) is None, name


def test_window_readers_take_the_windows_batches_alone():
    """Since the reset: 3 window batches, then a traced tail of 2. The
    loader's stream began 4 batches before the reset."""
    from dexiraft_tpu import profiling

    for prefix in ("engine:", "prefetch:", "loader:"):
        profiling.reset(prefix)
    for s in (9.0, 9.0, 9.0, 9.0):
        profiling.add("loader:wait", s)
        profiling.add("loader:stack", s)
        for _ in range(2):  # batch 2: a decode for every sample
            profiling.add("loader:decode", s)
    for s in (0.001, 0.002, 0.003, 0.5, 0.5):
        profiling.add("engine:put", s)
        profiling.add("prefetch:put", s)
        profiling.add("prefetch:host_next", 2 * s)
        profiling.add("loader:wait", s)
        profiling.add("loader:stack", s)
        for _ in range(2):
            profiling.add("loader:decode", s)
    for _ in range(4):  # workers run ahead of the consumer
        profiling.add("loader:decode", 7.0)
    obs = harness.Observation(
        spans={}, counters={"engine_batches": 3, "prefetch_batches": 3,
                            "batch": 2},
        end_to_end={}, trace=None, peaks=None, chips=1, memory_peak_bytes=0)
    for name in ("engine_put_ms", "prefetch_put_ms", "loader_wait_ms",
                 "loader_stack_ms", "loader_decode_ms"):
        assert harness.load_metric(name).read(obs) == pytest.approx(2.0), name
    # fewer durations than the window's batches: nothing, not a guess
    obs.counters["engine_batches"] = 6
    assert harness.load_metric("engine_put_ms").read(obs) is None
    # a dropped batch waited and was never stacked: no alignment by count
    profiling.add("loader:wait", 0.5)
    for name in ("loader_wait_ms", "loader_stack_ms", "loader_decode_ms"):
        assert harness.load_metric(name).read(obs) is None, name
    for prefix in ("engine:", "prefetch:", "loader:"):
        profiling.reset(prefix)


def test_decode_reader_takes_the_median_of_the_windows_decodes():
    """The loader runs ahead: one of the window's samples was decoded
    against set-up's tracing and took a thousand times as long."""
    from dexiraft_tpu import profiling

    for prefix in ("prefetch:", "loader:"):
        profiling.reset(prefix)
    for _ in range(3):
        for name in ("loader:wait", "loader:stack", "prefetch:host_next"):
            profiling.add(name, 0.001)
    for s in (5.0, 0.002, 0.002, 0.002, 0.004, 0.002):
        profiling.add("loader:decode", s)
    obs = harness.Observation(
        spans={}, counters={"prefetch_batches": 3, "batch": 2},
        end_to_end={}, trace=None, peaks=None, chips=1, memory_peak_bytes=0)
    assert harness.load_metric("loader_decode_ms").read(obs) == pytest.approx(2.0)
    for prefix in ("prefetch:", "loader:"):
        profiling.reset(prefix)
