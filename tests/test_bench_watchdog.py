"""The bench harness must never hang its caller, and never hide a
failure.

A device fetch that never returns would leave bench.py silent forever,
so it runs the measurement in a child process under a stall watchdog.
These tests exercise the watchdog with a fake child that blocks forever
(BENCH_FAKE_HANG), at a short test-only stall threshold (BENCH_STALL_S).
The child's failure is the run's: there is no fallback platform.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _load_bench():
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_mod", BENCH)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


def test_record_schema_pinned():
    """The ONE JSON line the driver greps is schema-pinned: required keys
    (including this PR's corr_dtype/fused_update config naming), optional
    conditional keys, and tag-prefixed per-config diagnostics — anything
    else fails validate_record, so the record cannot drift silently."""
    bench = _load_bench()
    assert {"corr_dtype", "fused_update", "corr_impl",
            "dexined_upconv"} <= bench.BENCH_RECORD_KEYS
    rec = {k: None for k in bench.BENCH_RECORD_KEYS}
    rec.update(allpairs_forward_ms=1.0, flash_int8_iters_per_sec=2.0,
               local_transpose_forward_ms=3.0, mfu=0.5)
    bench.validate_record(rec)  # required + diag + optional: passes

    with pytest.raises(ValueError, match="missing"):
        bench.validate_record({k: None for k in
                               bench.BENCH_RECORD_KEYS - {"corr_dtype"}})
    bad = {k: None for k in bench.BENCH_RECORD_KEYS}
    bad["surprise_key"] = 1
    with pytest.raises(ValueError, match="unpinned"):
        bench.validate_record(bad)


def test_cpu_anchor_parse_keeps_freshest_per_geometry(tmp_path, monkeypatch):
    """The anchor script APPENDS on re-runs; the bench record carries one
    ratio per measured geometry, each the freshest for that geometry
    (ADVICE r3 + VERDICT r4 next-8). Malformed lines, key-missing lines,
    and legacy geometry-less records are skipped without losing good
    ones."""
    bench = _load_bench()

    log = tmp_path / "logs" / "torch_cpu_anchor.log"
    log.parent.mkdir()
    log.write_text(
        "# methodology note\n"
        '{"flax_over_torch": 1.18, "host": "loaded"}\n'  # legacy: no metric
        '{"broken json\n'
        '{"no_ratio_key": true}\n'
        '{"metric": "cpu_anchor_v5_forward@224x512x6it",'
        ' "flax_over_torch": 1.9, "host": "loaded"}\n'
        '{"metric": "cpu_anchor_v5_forward@224x512x6it",'
        ' "flax_over_torch": 2.06, "host": "idle"}\n'
        '{"metric": "cpu_anchor_v5_forward@440x1024x32it",'
        ' "flax_over_torch": 1.27}\n'
        '{"metric": "cpu_anchor_v5_trainstep@96x128x12it",'
        ' "flax_over_torch_train": 0.23}\n')
    # _cpu_anchor_fields resolves the log relative to its module's
    # __file__ — point that at tmp_path rather than patching the
    # process-global os.path.dirname
    monkeypatch.setattr(bench, "__file__", str(tmp_path / "bench.py"))
    fields = bench._cpu_anchor_fields()
    assert fields["cpu_anchor_flax_over_torch"] == {
        "224x512x6it": 2.06, "440x1024x32it": 1.27}
    assert fields["cpu_anchor_flax_over_torch_train"] == {
        "96x128x12it": 0.23}


def test_watchdog_kills_stalled_child():
    # the stall threshold must outlast interpreter startup, which can
    # take >10 s on a loaded host — the fake child prints one line as
    # soon as it is up, then blocks
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_FAKE_HANG="1",
               BENCH_STALL_S="40")
    r = subprocess.run([sys.executable, BENCH], env=env,
                       capture_output=True, timeout=180)
    # one stall cycle, no retry on another platform, exit code 8
    assert r.returncode == 8, r.stderr.decode()
    assert b"stalled" in r.stderr
    assert b"fake child hanging" in r.stderr


def test_sigterm_forwards_to_measurement_child():
    # an outer `timeout` signals only the parent; the parent must kill
    # the measurement child before dying or it would be orphaned still
    # holding the chip
    import glob
    import time

    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_FAKE_HANG="1",
               BENCH_STALL_S="600")
    p = subprocess.Popen([sys.executable, BENCH], env=env,
                         stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    try:
        deadline = time.time() + 120
        saw_child = False
        while time.time() < deadline:
            line = p.stderr.readline()
            if b"fake child hanging" in line:
                saw_child = True
                break
        assert saw_child, "fake child never started"
        p.terminate()
        assert p.wait(timeout=30) == 143  # 128 + SIGTERM
        time.sleep(1.0)
        # no orphaned bench.py process may remain
        orphans = []
        for cmd in glob.glob("/proc/[0-9]*/cmdline"):
            try:
                with open(cmd, "rb") as f:
                    argv = f.read().split(b"\0")
            except OSError:
                continue
            if any(a == BENCH.encode() for a in argv):
                orphans.append(cmd)
        assert not orphans, orphans
    finally:
        if p.poll() is None:
            p.kill()


def test_hard_cap_kills_overrunning_child():
    # even a child that is not silent long enough to trip the stall
    # check must die at the hard cap
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_FAKE_HANG="1",
               BENCH_STALL_S="600", BENCH_HARD_CAP_S="25")
    r = subprocess.run([sys.executable, BENCH], env=env,
                       capture_output=True, timeout=180)
    assert r.returncode == 8, r.stderr.decode()
    assert b"overran" in r.stderr
