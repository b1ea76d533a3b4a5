"""The trace reducer: interval arithmetic by hand on a built trace, and
the recorded trace of a chip run kept under data/."""

import glob
import json
import os.path as osp

import pytest

from benchmarks import trace_reduce as tr

MS = 1_000_000  # ns


def built_trace():
    """Two chips, a 100 ms window. Chip 0: a fusion 0-10, a `while` 10-60
    that spans two body ops, a Pallas call inside it, an all-reduce 60-80
    of which 70-80 is overlapped by a fusion, idle 80-90, a fusion 90-100.
    Chip 1: busy 0-50 only. One op lies outside the window."""
    chip0 = [
        ["%fusion.1 fusion f32[8]", 0, 10 * MS],
        ["%while.7 while s32[]", 10 * MS, 50 * MS],
        ["%fusion.2 fusion f32[8]", 10 * MS, 20 * MS],          # body, inside the while
        ["%Conv_0.3 tpu_custom_call f32[8]", 30 * MS, 25 * MS],     # body, Pallas
        ["%all-reduce.4 all-reduce f32[8]", 60 * MS, 20 * MS],
        ["%fusion.5 fusion f32[8]", 70 * MS, 10 * MS],          # hides half the all-reduce
        ["%fusion.6 fusion f32[8]", 90 * MS, 10 * MS],
        ["%fusion.9 fusion f32[8]", 150 * MS, 10 * MS],         # after the window
    ]
    chip1 = [["%fusion.1 fusion f32[8]", 0, 50 * MS]]
    host = [
        ["bench:window", 0, 100 * MS],
        ["bench:loader_wait", 78 * MS, 14 * MS],  # covers the idle gap
        ["bench:dispatch", 92 * MS, 2 * MS],
        ["PjitFunction(step)", 92 * MS, 1 * MS],  # JAX's own, same thread
    ]
    return {"devices": [{"name": "/device:TPU:0", "ops": chip0},
                        {"name": "/device:TPU:1", "ops": chip1}],
            "host": host}


def test_interval_arithmetic():
    assert tr.union([(5, 9), (0, 3), (2, 4), (9, 9)]) == [(0, 4), (5, 9)]
    assert tr.length([(0, 4), (5, 9)]) == 8
    assert tr.subtract([(0, 10), (20, 30)], [(2, 3), (8, 22), (29, 40)]) == \
        [(0, 2), (3, 8), (22, 29)]


def test_busy_idle_loop_pallas_and_collective_exposure():
    s = tr.summarize(built_trace())
    assert s["chips"] == 2
    assert s["window_s"] == pytest.approx(0.100)
    # chip 0 busy 0-80 and 90-100 = 90 ms, chip 1 50 ms: mean 70 ms
    assert s["busy_s"] == pytest.approx(0.070)
    # the while spans 10-60 on chip 0 only: mean 25 ms; its body's ops
    # are inside it and add nothing
    assert s["loop_s"] == pytest.approx(0.025)
    assert s["pallas_s"] == pytest.approx(0.0125)
    # all-reduce 60-80 on chip 0; 70-80 runs under fusion.5
    assert s["collective_s"] == pytest.approx(0.010)
    assert s["collective_exposed_s"] == pytest.approx(0.005)


def test_breakdown_names_leaf_ops_and_attributes_gaps():
    s = tr.summarize(built_trace())
    ops = dict(s["device_ops"])
    assert "%while.7 while s32[]" not in ops            # a container, not work
    assert ops["%Conv_0.3 tpu_custom_call f32[8]"] == pytest.approx(0.0125)
    assert ops["%fusion.1 fusion f32[8]"] == pytest.approx((0.010 + 0.050) / 2)
    # chip 0's only gap, 80-90, lies under the loader wait
    assert s["idle_gaps"] == [["bench:loader_wait", pytest.approx(0.010)]]


def test_labels_from_hlo_text():
    conv = ('%Conv_0.6 = f32[32,7168,256]{2,1,0:T(8,128)} custom-call(f32[32,'
            '7168,256]{2,1,0:T(8,128)} %pad.494, f32[32,7168,2]{2,1,0:T(8,128)'
            'S(1)} %custom-call.4), custom_call_target="tpu_custom_call"')
    assert tr.label(conv) == "%Conv_0.6 tpu_custom_call f32[32,7168,256]"
    assert tr.is_pallas(tr.label(conv))
    loop = ("%while.22 = (s32[]{:T(128)}, f32[256]{0:T(256)}) while((s32[]"
            "{:T(128)}, f32[256]{0:T(256)}) %tuple.1), condition=%c, body=%b")
    assert tr.label(loop) == "%while.22 while s32[]"
    assert tr.is_loop(tr.label(loop)) and tr.is_container(tr.label(loop))
    # an op is a collective by its own name or opcode, not its operands'
    fused = ("%fusion.9 = f32[8]{0:T(256)} fusion(f32[8]{0:T(256)} "
             "%all-reduce.3), kind=kLoop, calls=%fused_computation.9")
    assert not tr.is_collective(tr.label(fused))
    start = ("%all-reduce-start.2 = f32[64]{0:T(256)} all-reduce-start(f32[64]"
             "{0:T(256)} %fusion.1), replica_groups={{0,1,2,3}}")
    assert tr.is_collective(tr.label(start))
    assert tr.label("jit_step(123)") == "jit_step(123)"


def test_window_falls_back_to_the_device_events():
    trace = built_trace()
    trace["host"] = []
    assert tr.window_of(trace) == (0, 160 * MS)


def test_trim_keeps_the_summary_of_long_ops():
    trace = built_trace()
    small = tr.trim(trace, min_dur_ns=15 * MS)
    names = [e[0] for e in small["devices"][0]["ops"]]
    assert "%fusion.9 fusion f32[8]" not in names and "%fusion.1 fusion f32[8]" not in names
    assert "%while.7 while s32[]" in names and "%all-reduce.4 all-reduce f32[8]" in names
    assert tr.summarize(small)["loop_s"] == tr.summarize(trace)["loop_s"]


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        tr.summarize({"devices": [], "host": [["bench:window", 0, 10]]})


RECORDED = sorted(glob.glob(osp.join(osp.dirname(__file__), "data",
                                     "trace_*.json")))


@pytest.mark.parametrize("path", RECORDED, ids=[osp.basename(p) for p in RECORDED])
def test_recorded_chip_trace(path):
    """A trace cut from a chip run (harness.TraceWindow with
    BENCH_KEEP_TRACE, then trace_reduce.trim) with the numbers this
    reducer gave for it pinned beside it: a change to the reducer that
    moves a number shows here, with no chip."""
    with open(path) as f:
        rec = json.load(f)
    s = tr.summarize(rec["trace"])
    for key, want in rec["expect"].items():
        assert s[key] == pytest.approx(want, rel=1e-6), key
    assert s["busy_s"] <= s["window_s"]
    assert s["loop_s"] <= s["busy_s"]
    assert s["collective_exposed_s"] <= s["collective_s"]
