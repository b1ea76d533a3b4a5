"""scripts/serve_bench.py: tiny-geometry CPU smoke with the JSON record
schema pinned, and the stall watchdog (the bench.py pattern — a hung
device fetch must never hang the caller; the parent kills a silent
child and exits 8).

Named to sort LAST in collection (tier-1 870 s budget convention, see
test_zpipeline_async.py).
"""

import json
import os
import os.path as osp
import subprocess
import sys

REPO = osp.dirname(osp.dirname(osp.abspath(__file__)))
BENCH = osp.join(REPO, "scripts", "serve_bench.py")


def test_cpu_smoke_record_schema_and_bucket_compiles():
    """One mixed-geometry stream, batch 1 vs 4: the record is
    self-describing (schema pinned here), every config compiles EXACTLY
    one executable per bucket, and the speed-up of the batched
    configuration is reported."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, BENCH, "--variant", "v1", "--small", "--iters", "2",
         # 12 frames = exact batch multiples per bucket: no tail-pad
         "--batch", "4", "--sizes", "40x56,44x60,62x70", "--frames", "12",
         "--bucket_multiple", "16", "--inflight", "2", "--no_compile_cache",
         "--cpu"],
        env=env, capture_output=True, timeout=420)
    assert r.returncode == 0, r.stderr.decode()
    line = [ln for ln in r.stdout.decode().splitlines()
            if ln.startswith('{"metric"')]
    assert line, r.stdout.decode()
    rec = json.loads(line[-1])

    # schema pin: the queue tooling greps these fields
    sys.path.insert(0, osp.dirname(BENCH))
    try:
        from serve_bench import CONFIG_KEYS, RECORD_KEYS
    finally:
        sys.path.pop(0)
    assert set(rec) == RECORD_KEYS, sorted(set(rec) ^ RECORD_KEYS)
    assert [c["batch_size"] for c in rec["configs"]] == [1, 4]
    for c in rec["configs"]:
        assert set(c) == CONFIG_KEYS, sorted(set(c) ^ CONFIG_KEYS)
        # 40x56/44x60 -> 48x64, 62x70 -> 64x80 at multiple=16
        assert c["bucket_count"] == 2
        assert c["compiles"] == c["bucket_count"]  # exactly one per bucket
        assert c["frame_pairs_per_sec"] > 0
    assert rec["platform"] == "cpu"
    # a ratio of two throughputs measured beside five other workers
    # failed whole runs and passed alone: it is asked to be a number
    assert rec["speedup_batched_over_b1"] > 0, rec


def test_watchdog_kills_stalled_child():
    # the fake child prints one line as soon as it is up (no jax
    # import on its path), then blocks forever; the stall threshold
    # only needs to outlast interpreter startup
    env = dict(os.environ, JAX_PLATFORMS="cpu", SERVE_BENCH_FAKE_HANG="1",
               SERVE_BENCH_STALL_S="20")
    r = subprocess.run([sys.executable, BENCH], env=env,
                       capture_output=True, timeout=180)
    assert r.returncode == 8, r.stderr.decode()
    assert b"stalled" in r.stderr
    assert b"fake child hanging" in r.stderr
