"""The manifest against the contract's limits, every cell's files found
by name, and throughput taken from whole batches."""

import json
import os.path as osp
import re

import pytest

from benchmarks import harness

MANIFEST = harness.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def test_manifest_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len(json.dumps(MANIFEST)) < 64 * 1024
    assert 2 <= len(MANIFEST["workloads"]) <= 24
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(MANIFEST["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert used == {c["name"] for c in MANIFEST["configs"]}
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        assert c["file"].startswith(MANIFEST["paths"][0] + "/")


def test_names_and_units_use_the_allowed_characters():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(set(names)) == len(names)
    for w in MANIFEST["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


def test_end_to_end_metrics_are_bounded_and_every_cell_has_them():
    by_name = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "workloads" not in by_name["setup_s"]
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for name in CELLS:
        cell = harness.load_cell(name)
        got = {m["name"] for m in cell.metrics("end_to_end")}
        assert "setup_s" in got and len(got) >= 2, name


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_resolve_by_name(name):
    cell = harness.load_cell(name)
    assert cell.config["name"] == cell.config_name
    assert cell.config["reduced"] == next(
        c["reduced"] for c in MANIFEST["configs"]
        if c["name"] == cell.config_name)
    runner = harness.load_runner(cell.traffic["kind"])
    assert callable(runner.run) and callable(runner.compile_for)
    e2e = {m["name"] for m in cell.metrics("end_to_end")}
    layers = cell.metrics("per_layer")
    assert layers, name
    for m in layers:
        # a per-layer metric is reported only where the metric it moves is
        assert m["moves"] in e2e, (name, m["name"])
        assert callable(harness.load_metric(m["name"]).read)


def test_every_metric_file_is_in_the_manifest():
    import glob

    files = {osp.basename(p)[:-3] for p in glob.glob(
        osp.join(harness.BENCH_DIR, "layer_metrics", "*.py"))}
    assert files == {m["name"] for m in MANIFEST["per_layer"]}


def test_rehearsal_overrides_merge_one_level_deep():
    real = harness.load_cell("v5-train-chairs")
    tiny = harness.load_cell("v5-train-chairs", rehearsal=True)
    assert real.traffic["image_size"] == [368, 496]
    assert tiny.traffic["image_size"] != [368, 496]
    # nested flags merge: the rehearsal adds `small`, keeps remat
    assert tiny.traffic["model_flags"]["small"] is True
    assert tiny.traffic["model_flags"]["remat"] is True
    assert "small" not in real.traffic["model_flags"]


def test_unknown_names_are_refused():
    with pytest.raises(harness.BenchError):
        harness.load_cell("no-such-cell")
    with pytest.raises(harness.BenchError):
        harness.load_runner("no_such_kind")
    with pytest.raises(harness.BenchError):
        harness.load_peaks("TPU v9 imaginary")
    assert harness.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def _closed_loop(pacer, clock, unit_s, fill_s=0.0, inflight=2):
    """Drive a pacer as a pipelined closed loop would: `inflight` units
    out, the device runs them in turn, the first starts after `fill_s`."""
    out = []
    device_free = clock["t"] + fill_s
    while pacer.more():
        if len(out) == inflight:        # window full: wait for the oldest
            clock["t"] = max(clock["t"], out.pop(0))
            pacer.note_finish()
        device_free = max(device_free, clock["t"]) + unit_s
        out.append(device_free)
        pacer.note_dispatch()
    for done in out:
        clock["t"] = done
        pacer.note_finish()


def test_throughput_comes_from_whole_batches(monkeypatch):
    """A closed loop of 2 s batches and a 7 s window: the pacer offers
    ceil(7 / 2) = 4 batches and the rate is 4 batches over the 8 s they
    took, not 3.5 batches cut by the clock."""
    clock = {"t": 100.0}
    monkeypatch.setattr(harness.time, "perf_counter", lambda: clock["t"])
    pacer = harness.Pacer(seconds=7.0, first_guess_s=2.0)
    _closed_loop(pacer, clock, unit_s=2.0)
    assert pacer.dispatched == pacer.finished == 4
    assert pacer.elapsed == pytest.approx(8.0)
    assert pacer.finished * 16 / pacer.elapsed == pytest.approx(8.0)


def test_pacer_runs_at_least_three_units():
    pacer = harness.Pacer(seconds=0.0, first_guess_s=10.0)
    n = 0
    while pacer.more():
        pacer.note_dispatch()
        n += 1
    assert n == 3


def test_ready_clock_ticks_once_per_batch_in_dispatch_order():
    """The eval runner's clock: one tick per dispatched batch, and
    `ticks()` waits until the watcher has seen everything put so far."""
    import numpy as np

    runner = harness.load_runner("eval_closed")
    clock = runner._ReadyClock()
    for _ in range(5):
        clock(np.zeros(3))
    ticks = clock.ticks()
    clock.close()
    assert len(ticks) == 5 and ticks == sorted(ticks)
    # rate from whole batches: the first tick starts the clock
    assert (len(ticks) - 1) * 32 / max(ticks[-1] - ticks[0], 1e-9) > 0


def test_ready_clock_hook_runs_per_tick_and_its_error_surfaces():
    """The traced tail's window is opened and closed from the watcher
    thread by tick count; a hook that raises must not hang `ticks()`."""
    import numpy as np

    runner = harness.load_runner("eval_closed")
    clock = runner._ReadyClock()
    seen = []
    clock.on_tick = seen.append
    for _ in range(3):
        clock(np.zeros(3))
    assert len(clock.ticks()) == 3 and seen == [1, 2, 3]

    def boom(n):
        raise RuntimeError("hook failed")

    clock.on_tick = boom
    clock(np.zeros(3))
    with pytest.raises(RuntimeError):
        clock.ticks()
    clock.close()
