"""Chaos smoke: a short CPU run proving the recovery paths recover
(~4 min on a laptop-class CPU, dominated by the XLA compiles).

Injects the fault families the resilience layer claims to survive —
corrupt samples, decode-worker death, SIGTERM mid-run, a truncated
checkpoint, a hard kill DURING an async checkpoint flush, a dead
virtual host on a 2-process mesh, and a serve replica SIGKILLed behind
the fleet router — against the REAL loader, the REAL train CLI, the
real multiprocess runtime, and real serve processes, and exits nonzero
if any path fails to recover. Intended for CI and for a quick sanity check
after touching the train/data/resilience path:

    python scripts/chaos_smoke.py 2>&1 | tee logs/chaos_smoke.log

Phases:
  1 corrupt-sample   Loader + always-failing samples: batches keep
                     flowing, skips counted, shapes stable
  2 worker-death     process-pool worker os._exit()s: pool rebuilt,
                     batches bit-identical to a clean run
  3 sigterm-resume   train_cli with a real SIGTERM after step N:
                     emergency checkpoint + stream position, --resume,
                     final params BIT-EXACT vs an uninterrupted run
  4 truncated-ckpt   newest checkpoint file truncated: verified restore
                     falls back to the previous step
  5 kill-mid-flush   train_cli killed while an async checkpoint flush
                     is in flight (--chaos kill_mid_flush@N, a real
                     os._exit mid-serialize): the uncommitted step is
                     invisible, restore_verified lands on the prior
                     committed step, --resume completes the run
  6 multihost-kill   2-process virtual mesh, one host os._exit()s
                     mid-run: the survivor exits NONZERO (watchdog /
                     collective error) instead of hanging, and a
                     --resume pair agrees on one step and finishes
                     BIT-EXACT vs an uninterrupted reference pair
  7 router-failover  2 serve replicas behind the fleet router
                     (serve/router.py), one SIGKILLed under closed-loop
                     session load: ZERO accepted requests dropped (the
                     router's failover retry + the clients' connection
                     retry absorb the death), the breaker opens inside
                     the recovery bound, and the dead replica's
                     sessions remap (sticky misses, then warm again)
  8 shrink-and-continue  the SAME kill as phase 6 but under --elastic
                     (resilience.membership): the survivor re-forms a
                     solo membership epoch, restores the agreed step,
                     and FINISHES the run with exit 0 — its
                     reconfiguration recovery_s is pinned into the
                     record next to phase 6's exit-98 abort wall (the
                     cost elastic replaces), and the child's lock-order
                     runtime must report zero violations across the
                     reconfiguration

The last stdout line is a JSON record with per-phase recovery
wall-times (`[chaos] record {...}` — RECORD_KEYS pins the schema), so
recovery-latency regressions are visible run-over-run in the logs. The
record also carries the lock-order runtime's verdict (analysis/locks):
the kill-mid-flush, router-failover, and shrink-and-continue phases
assert — and pin into their record entries — ZERO lock-order
violations and ZERO deadlock cycles while their thread fabric was
under fire, so the concurrency gate holds under the exact chaos it
exists for, not just in unit tests. The shrink phase additionally
records its elastic `recovery_s` next to the multihost-kill phase's
exit-98 `abort_s` — the restart cost it replaces — and asserts it is
cheaper.
The smoke also runs `lint_gate.py --json` up front (the machine-
readable contract, no stdout scraping) and pins the static gate's
verdict alongside — one record answers both halves of the concurrency
story: the tree lints clean AND the runtime observed no violations.
"""

from __future__ import annotations

import json
import os
import os.path as osp
import subprocess
import sys
import tempfile
import time
import traceback

sys.path.insert(0, osp.dirname(osp.dirname(osp.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

# JSON-tail schema: per-phase {ok, wall_s} plus totals; the locks block
# is the lock-order runtime's verdict (analysis/locks.py) — the
# kill-mid-flush, router-failover, and shrink-and-continue phases
# additionally pin a per-phase snapshot proving ZERO order violations /
# deadlock cycles were observed while their thread fabric was under
# fire (the shrink phase's snapshot comes from the SURVIVOR CHILD —
# the process that ran the lease thread + flush executor + watchdog
# through a real reconfiguration)
RECORD_KEYS = ("phases", "failures", "total_s", "locks", "lint_gate",
               "collective_trace")
# every phase entry carries at least these keys ...
PHASE_KEYS = ("ok", "wall_s")
# ... and the concurrency-gate phases (kill-mid-flush,
# router-failover, shrink-and-continue) additionally merge this key —
# their per-phase lock-order snapshot; multihost-kill merges abort_s
# and shrink-and-continue merges {recovery_s, exit98_abort_s}, the
# before/after pair of the elastic-membership story
PHASE_LOCKS_KEY = "locks"


def _locks_verdict(phase: str) -> dict:
    """Assert the lock-order runtime saw no violations, and return the
    snapshot for the phase's record entry. In-process the smoke drives
    the REAL router/checkpoint thread fabric (handler threads, health
    loop, drain threads, the flush barrier), so a nonzero count here is
    a concurrency regression even when the phase's recovery contract
    still held."""
    from dexiraft_tpu.analysis import locks

    rec = locks.stats_record()
    assert rec["order_violations"] == 0, \
        f"{phase}: lock-order violations under fire: {rec['violations']}"
    assert rec["cycles"] == 0, \
        f"{phase}: deadlock cycles detected under fire: {rec['violations']}"
    return {"locks": {"order_violations": rec["order_violations"],
                      "cycles": rec["cycles"],
                      "contended": sum(v["contended"]
                                       for v in rec["by_lock"].values())}}


def _build_chairs_tree(tmp: str, n: int = 8) -> None:
    import imageio.v2 as imageio

    from dexiraft_tpu.data.flow_io import write_flo

    root = os.path.join(tmp, "FlyingChairs_release", "data")
    os.makedirs(root, exist_ok=True)
    rng = np.random.default_rng(0)
    for i in range(n):
        imageio.imwrite(f"{root}/{i:05d}_img1.ppm",
                        rng.integers(0, 256, (96, 128, 3), dtype=np.uint8))
        imageio.imwrite(f"{root}/{i:05d}_img2.ppm",
                        rng.integers(0, 256, (96, 128, 3), dtype=np.uint8))
        write_flo(f"{root}/{i:05d}_flow.flo",
                  rng.normal(size=(96, 128, 2)).astype(np.float32))
    with open(os.path.join(tmp, "FlyingChairs_release",
                           "chairs_split.txt"), "w") as f:
        f.write("\n".join(["1"] * n))


def _train_args(tmp: str, name: str, steps: int, extra=()):
    return ["--name", name, "--stage", "chairs", "--variant", "v1", "--small",
            "--num_steps", str(steps), "--batch_size", "2",
            "--image_size", "64", "64", "--iters", "2", "--lr", "1e-4",
            "--num_workers", "1", "--val_freq", "1000",
            "--output", f"{tmp}/ckpts", "--log_dir", f"{tmp}/runs", *extra]


def phase_corrupt_sample() -> None:
    from dexiraft_tpu.data.loader import Loader
    from dexiraft_tpu.resilience import chaos

    ds = chaos.SyntheticFlowDataset(n=8, size=(16, 16))
    bad = chaos.CorruptSampleDataset(ds, [0, 5])
    loader = Loader(bad, 2, num_workers=2, prefetch=2, max_retries=1,
                    retry_backoff_s=0.001)
    it = loader.batches()
    got = [next(it) for _ in range(8)]  # two epochs: both bad indices hit
    it.close()
    assert all(b["image1"].shape == (2, 16, 16, 3) for b in got), \
        "batch shape drifted under skips"
    assert loader.stats.skipped_samples >= 2, loader.stats.summary()
    print(f"    {loader.stats.summary()}")


def phase_worker_death() -> None:
    from dexiraft_tpu.data.loader import Loader
    from dexiraft_tpu.resilience import chaos

    ds = chaos.SyntheticFlowDataset(n=8, size=(16, 16))
    with tempfile.TemporaryDirectory() as sentinels:
        killer = chaos.WorkerDeathDataset(ds, [1], sentinels)
        loader = Loader(killer, 2, num_workers=1, prefetch=2,
                        worker_mode="process", mp_start_method="spawn",
                        max_retries=3, retry_backoff_s=0.01)
        it = loader.batches()
        got = [next(it) for _ in range(4)]
        it.close()
    assert loader.stats.worker_restarts >= 1, loader.stats.summary()
    clean = Loader(ds, 2, num_workers=1, prefetch=2)
    ic = clean.batches()
    ref = [next(ic) for _ in range(4)]
    ic.close()
    for a, b in zip(got, ref):
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])
    print(f"    {loader.stats.summary()}; batches bit-identical to clean run")


def phase_sigterm_resume(tmp: str) -> None:
    import jax

    from dexiraft_tpu.config import TrainConfig, raft_v1
    from dexiraft_tpu.train import checkpoint as ckpt
    from dexiraft_tpu.train.state import create_state
    from dexiraft_tpu.train_cli import main as train_main

    train_main(_train_args(tmp, "ref", 4))
    train_main(_train_args(tmp, "cut", 4, ["--chaos", "sigterm@2"]))
    saved = ckpt.latest_step(f"{tmp}/ckpts/cut")
    assert saved == 2, f"expected emergency save at step 2, got {saved}"
    assert os.path.exists(f"{tmp}/ckpts/cut/stream/2.json"), \
        "stream-position sidecar missing"
    train_main(_train_args(tmp, "cut", 4, ["--resume"]))
    assert ckpt.latest_step(f"{tmp}/ckpts/cut") == 4

    template = create_state(jax.random.PRNGKey(0), raft_v1(small=True),
                            TrainConfig())
    ref = ckpt.restore_checkpoint(f"{tmp}/ckpts/ref", template, step=4)
    cut = ckpt.restore_checkpoint(f"{tmp}/ckpts/cut", template, step=4)
    for a, b in zip(jax.tree.leaves(ref.params), jax.tree.leaves(cut.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("    SIGTERM@2 -> emergency save -> resume: params BIT-EXACT "
          "vs uninterrupted run")


def phase_truncated_checkpoint(tmp: str) -> None:
    import jax

    from dexiraft_tpu.config import TrainConfig, raft_v1
    from dexiraft_tpu.resilience import chaos, restore_verified
    from dexiraft_tpu.train.state import create_state

    ckpt_dir = f"{tmp}/ckpts/ref"  # steps 2 (val_freq path unused) … 4
    template = create_state(jax.random.PRNGKey(0), raft_v1(small=True),
                            TrainConfig())
    # damage the NEWEST step; verified restore must land on the previous
    from dexiraft_tpu.train import checkpoint as ckpt

    steps = ckpt.all_steps(ckpt_dir)
    assert len(steps) >= 1, steps
    if len(steps) == 1:
        # make a second step to fall back to
        ckpt.save_checkpoint(ckpt_dir, template, step=steps[-1] + 1)
        steps = ckpt.all_steps(ckpt_dir)
    damaged = chaos.truncate_checkpoint(ckpt_dir, steps[-1])
    assert damaged, "nothing truncated"
    state, got = restore_verified(ckpt_dir, template)
    assert got == steps[-2], (got, steps)
    print(f"    step {steps[-1]} truncated -> restored step {got} instead")


def _train_subprocess(tmp: str, cli_args, expect_rc: int,
                      timeout: float = 600.0) -> str:
    """Run train_cli in a SUBPROCESS (the injected fault is a real
    os._exit — in-process it would take the smoke down) and assert the
    exit code. Returns combined output."""
    repo = osp.dirname(osp.dirname(osp.abspath(__file__)))
    env = {**os.environ, "DEXIRAFT_DATA_DIR": tmp,
           "PYTHONPATH": repo + os.pathsep + os.environ.get("PYTHONPATH",
                                                            "")}
    proc = subprocess.run(
        [sys.executable, "-m", "dexiraft_tpu", "train", *cli_args],
        cwd=tmp, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, timeout=timeout)
    out = proc.stdout.decode(errors="replace")
    assert proc.returncode == expect_rc, \
        f"expected rc {expect_rc}, got {proc.returncode}:\n{out[-3000:]}"
    return out


def phase_kill_mid_flush(tmp: str) -> dict:
    import jax

    from dexiraft_tpu.config import TrainConfig, raft_v1
    from dexiraft_tpu.resilience import restore_verified, \
        uncommitted_flushes
    from dexiraft_tpu.train import checkpoint as ckpt
    from dexiraft_tpu.train.state import create_state

    args = _train_args(tmp, "flushkill", 6,
                       ["--val_freq", "2", "--validation"])
    # saves at 2/4/6; the chaos spec arms at step 3, so step 4's async
    # flush is the one killed in flight (rc 7 = the injector's exit)
    out = _train_subprocess(
        tmp, args + ["--chaos", "kill_mid_flush@3"], expect_rc=7)
    assert "killing process mid-flush of step 4" in out, out[-2000:]
    ckpt_dir = f"{tmp}/ckpts/flushkill"
    debris = uncommitted_flushes(ckpt_dir)
    assert debris, "kill was not mid-serialize: no uncommitted tmp dir"
    template = create_state(jax.random.PRNGKey(0), raft_v1(small=True),
                            TrainConfig())
    # clean_debris: this is the WRITER recovering its own directory
    state, got = restore_verified(ckpt_dir, template, clean_debris=True)
    assert got == 2, f"expected fallback to committed step 2, got {got}"
    assert uncommitted_flushes(ckpt_dir) == [], "debris not cleaned"
    # and the run completes from the prior committed step
    out = _train_subprocess(tmp, args + ["--resume"], expect_rc=0)
    assert ckpt.latest_step(ckpt_dir) == 6
    assert "flush" in out and "train blocked" in out  # async stats logged
    print(f"    killed mid-flush of step 4 (debris: {len(debris)} tmp "
          f"dir(s)) -> restore_verified landed on step {got}; --resume "
          f"completed to step 6")
    # the in-process half (restore_verified + the wait_pending barriers
    # above) ran the flush-lock fabric: pin zero order violations
    return _locks_verdict("kill-mid-flush")


# phase 6 publishes its exit-98 abort wall here; phase 8 records its
# elastic recovery next to it — the two numbers are the before/after of
# the elastic-membership story and belong in the same record
_EXIT98_BASELINE: dict = {}

# phase 8's survivor child publishes its collective flight-recorder
# snapshot here (analysis/collective_trace); the record's top-level
# collective_trace block folds it in next to the parent's own counters
_SURVIVOR_TRACE: dict = {}


def phase_multihost_kill(tmp: str) -> dict:
    repo = osp.dirname(osp.dirname(osp.abspath(__file__)))
    child = osp.join(repo, "tests", "multiproc_resilience_child.py")
    # the SAME pair orchestration the tier-1 multihost tests use (kill
    # + reap on timeout, placeholder logs), so smoke and suite cannot
    # drift
    from tests._mp_common import spawn_child_pair

    def spawn_pair(tag, ckpt_dir, extra):
        outs = [f"{tmp}/{tag}{pid}.json" for pid in range(2)]
        rcs, logs, _ = spawn_child_pair(
            child, outs, ckpt_dir,
            extra=["--num_steps", "8", "--save_every", "2", *extra],
            timeout=240.0)
        return rcs, logs

    rcs, logs = spawn_pair("ref", f"{tmp}/mh_ref",
                           ["--stall_timeout", "60"])
    assert rcs == [0, 0], f"reference pair failed:\n{logs[0][-2000:]}"
    t_kill = time.perf_counter()
    rcs, logs = spawn_pair("cut", f"{tmp}/mh_cut",
                           ["--die_step", "5", "--die_host", "1",
                            "--stall_timeout", "20"])
    abort_s = time.perf_counter() - t_kill
    assert rcs[1] == 3, logs[1][-1500:]
    survivor_rc = rcs[0]
    # the survivor must abort ITSELF (watchdog 98 / hard-exit 97) —
    # a -9 means spawn_child_pair's timeout killed a hung survivor,
    # which is exactly the outcome this phase exists to disprove
    assert survivor_rc not in (0, None, -9), \
        f"survivor rc {survivor_rc} — expected a coordinated nonzero " \
        f"exit:\n{logs[0][-1500:]}"
    assert "<killed: timed out>" not in logs[0], \
        "survivor hung past the spawn timeout — the watchdog did not " \
        "bound the dead-peer collective"
    assert abort_s < 150, \
        f"survivor took {abort_s:.0f}s to abort — the watchdog did " \
        f"not bound the hang"
    rcs, logs = spawn_pair("res", f"{tmp}/mh_cut",
                           ["--resume", "--stall_timeout", "60"])
    assert rcs == [0, 0], f"resume pair failed:\n{logs[0][-2000:]}"
    ref = [json.load(open(f"{tmp}/ref{i}.json")) for i in range(2)]
    res = [json.load(open(f"{tmp}/res{i}.json")) for i in range(2)]
    resumed = [r["events"][0]["resumed"] for r in res]
    assert resumed[0] == resumed[1], resumed
    assert res[0]["final_w"] == ref[0]["final_w"] == res[1]["final_w"], \
        "resumed params diverged from the uninterrupted reference"
    print(f"    host 1 killed at step 5 -> survivor aborted nonzero "
          f"(rc {survivor_rc}) in {abort_s:.0f}s; resume pair agreed on "
          f"step {resumed[0]} and finished BIT-EXACT vs the "
          f"uninterrupted pair")
    _EXIT98_BASELINE["abort_s"] = round(abort_s, 1)
    return {"abort_s": round(abort_s, 1)}


def phase_shrink_and_continue(tmp: str) -> dict:
    """Phase 6's kill under --elastic: the survivor must CONTINUE (rc 0,
    all 8 steps) through a membership reconfiguration instead of
    aborting for an orchestrator restart. recovery_s (verdict-to-new-
    world, from the survivor's membership event) lands in the record
    next to phase 6's abort wall — and must beat it: elastic recovery
    is only worth its complexity while it is cheaper than the exit-98
    path it replaces, BEFORE even counting the restart's re-init and
    re-compile that the baseline number does not include."""
    repo = osp.dirname(osp.dirname(osp.abspath(__file__)))
    child = osp.join(repo, "tests", "multiproc_resilience_child.py")
    from tests._mp_common import spawn_child_pair

    outs = [f"{tmp}/el{pid}.json" for pid in range(2)]
    rcs, logs, wall = spawn_child_pair(
        child, outs, f"{tmp}/mh_elastic",
        extra=["--elastic", "--die_step", "3", "--die_host", "1",
               "--num_steps", "8", "--stall_timeout", "25"],
        timeout=240.0)
    assert rcs == [0, 3], \
        f"elastic pair rcs {rcs}:\n{logs[0][-2000:]}\n{logs[1][-800:]}"
    surv = json.load(open(outs[0]))
    shrinks = [e for e in surv["membership_events"]
               if e["kind"] == "shrink"]
    assert len(shrinks) == 1, surv["membership_events"]
    assert shrinks[0]["members"] == [0]
    recovery_s = shrinks[0]["recovery_s"]
    assert 0 < recovery_s < 60, f"recovery_s {recovery_s}"
    assert surv["final_epoch"] == {"epoch": 1, "size": 1, "index": 0}
    assert "8" in surv["losses"], "survivor never finished the run"
    # the child's lock-order runtime ran the lease thread + flush
    # executor + watchdog fabric through the reconfiguration
    assert surv["locks"]["order_violations"] == 0, surv["locks"]
    assert surv["locks"]["cycles"] == 0, surv["locks"]
    # the child's collective flight recorder stamped every consensus
    # round, membership epoch, and orbax barrier across the
    # reconfiguration — lockstep must have verified clean (the in-band
    # check compares every peer stamp while the world is > 1 host)
    ct = surv.get("collective_trace") or {}
    assert ct.get("divergences") == 0, \
        f"survivor observed collective divergences: {ct}"
    assert ct.get("entries", 0) > 0, \
        f"flight recorder stamped nothing across the scenario: {ct}"
    _SURVIVOR_TRACE.update(ct)
    baseline = _EXIT98_BASELINE.get("abort_s")
    if baseline is not None:
        assert recovery_s < baseline, \
            f"elastic recovery ({recovery_s:.1f}s) is not cheaper than " \
            f"the exit-98 abort it replaces ({baseline:.1f}s)"
    print(f"    host 1 killed at step 3 under --elastic -> survivor "
          f"reconfigured to a solo epoch in {recovery_s:.2f}s and "
          f"finished all 8 steps (rc 0); exit-98 baseline abort: "
          f"{baseline}s; child locks clean")
    return {"recovery_s": round(recovery_s, 2),
            "exit98_abort_s": baseline,
            "locks": dict(surv["locks"]),
            "collective_trace": {
                "entries": ct.get("entries"),
                "verified_rounds": ct.get("verified_rounds"),
                "divergences": ct.get("divergences")}}


def phase_router_failover(tmp: str) -> dict:
    """Kill 1 of 2 replicas behind the fleet router under closed-loop
    session load. Recovery contract: zero accepted requests dropped
    (router failover + client connection-retry absorb the death), the
    victim's breaker opens inside the bound, sessions remap."""
    import threading
    from urllib.parse import urlparse

    repo = osp.dirname(osp.dirname(osp.abspath(__file__)))
    sys.path.insert(0, osp.join(repo, "scripts"))
    try:
        from serve_bench import _client_thread, _free_ports
    finally:
        sys.path.pop(0)
    from dexiraft_tpu.router_cli import spawn_replica, wait_ready
    from dexiraft_tpu.serve.router import Router, RouterConfig
    from dexiraft_tpu.serve.server import encode_request

    ports = _free_ports(2)
    serve_args = ["--synthetic_init", "--variant", "v1", "--small",
                  "--iters", "2", "--batch_size", "2", "--slo_ms", "100",
                  "--bucket_multiple", "8", "--session_ttl_s", "60",
                  "--max_queue", "64", "--warmup", "48x64", "--cpu"]
    env = {**os.environ,
           "PYTHONPATH": repo + os.pathsep + os.environ.get("PYTHONPATH",
                                                            "")}
    procs = {f"r{i}": spawn_replica(p, serve_args, chip=i, env=env)
             for i, p in enumerate(ports)}
    router = None
    try:
        for i, p in enumerate(ports):
            assert wait_ready("127.0.0.1", p, 240.0), \
                f"replica r{i} (port {p}) never became healthy"
        router = Router(
            {f"r{i}": f"127.0.0.1:{p}" for i, p in enumerate(ports)},
            port=0, config=RouterConfig(probe_interval_s=0.2,
                                        cooldown_s=1.0,
                                        fail_threshold=2)).start()
        rng = np.random.default_rng(0)
        body = encode_request(
            rng.uniform(0, 255, (48, 64, 3)).astype(np.float32),
            rng.uniform(0, 255, (48, 64, 3)).astype(np.float32))
        u = urlparse(router.url)
        n_clients, per = 4, 10
        latencies, rejects, retries, completions = [], [], [], []
        threads = [threading.Thread(
            target=_client_thread,
            args=(u.hostname, u.port, body, per, latencies, rejects,
                  f"cam-{i}", retries, completions))
            for i in range(n_clients)]
        for t in threads:
            t.start()
        t0 = time.perf_counter()
        # warm: every stream homed and ~1/3 of the traffic served
        while len(completions) < (n_clients * per) // 3:
            assert time.perf_counter() - t0 < 120, "load never warmed"
            time.sleep(0.02)
        aff_before = router.pool.affinity_record()
        # kill the replica that OWNS a live session — killing an idle
        # one proves nothing about affinity remap
        victim = router.pool.ring.lookup("cam-0")
        procs[victim].kill()        # SIGKILL mid-load: no drain, no flush
        procs[victim].wait()
        t_kill = time.perf_counter()
        while (router.pool.replicas[victim].state != "open"
               and time.perf_counter() - t_kill < 30):
            time.sleep(0.02)
        detect_s = time.perf_counter() - t_kill
        for t in threads:
            t.join(timeout=120.0)
        aff_after = router.pool.affinity_record()
        rec = router.stats.record()

        assert router.pool.replicas[victim].state == "open", \
            f"breaker never opened on the killed replica ({detect_s:.1f}s)"
        assert detect_s < 10.0, \
            f"breaker took {detect_s:.1f}s to open — recovery unbounded"
        assert rejects == [], \
            f"{len(rejects)} client-visible failures {rejects} — " \
            f"in-flight requests were dropped"
        assert len(latencies) == n_clients * per, \
            f"only {len(latencies)}/{n_clients * per} requests completed"
        assert aff_after["sticky_misses"] > aff_before["sticky_misses"], \
            "victim's sessions never remapped (sticky_misses flat)"
        print(f"    killed {victim} under load: breaker open in "
              f"{detect_s:.2f}s, {len(latencies)}/{n_clients * per} "
              f"requests OK (0 dropped, {len(retries)} client retries, "
              f"{rec['failovers']} router failovers), affinity "
              f"{aff_before['hit_rate']} -> {aff_after['hit_rate']} "
              f"({aff_after['sticky_misses']} sticky misses)")
        # the router ran IN-PROCESS with its full thread fabric
        # (handler threads x4 clients, health loop, passive breaker
        # marking) while a replica died under it: pin zero lock-order
        # violations across the failover
        return _locks_verdict("router-failover")
    finally:
        if router is not None:
            router.stop()
        for p in procs.values():
            if p.poll() is None:
                p.terminate()
        for p in procs.values():
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


def _lint_gate_verdict(failures: list) -> dict:
    """Run the static gate through its --json contract (no stdout
    scraping): the smoke's recovery phases prove the RUNTIME lock
    discipline holds under fire; this pins that the STATIC half
    (threadlint JL020+ with the rest of jaxlint) is clean on the same
    tree, in the same record."""
    gate = osp.join(osp.dirname(osp.abspath(__file__)), "lint_gate.py")
    proc = subprocess.run([sys.executable, gate, "--json"],
                          capture_output=True, text=True, timeout=120)
    try:
        blob = json.loads(proc.stdout)
    except ValueError:
        print(f"[chaos] lint gate emitted unparseable --json output "
              f"(rc {proc.returncode}):\n{proc.stdout[-1000:]}",
              flush=True)
        failures.append("lint-gate")
        return {"ok": False, "findings": None}
    verdict = {"ok": blob["ok"], "findings": len(blob["findings"]),
               "per_rule": {r: c["findings"]
                            for r, c in blob["per_rule"].items()
                            if c["findings"]},
               # per-family breakdown (jaxlint/shardlint/threadlint/
               # distlint): the record shows at a glance WHICH gate
               # family a regression landed in
               "per_family": {fam: {"rules": c["rules"],
                                    "findings": c["findings"]}
                              for fam, c in
                              blob.get("per_family", {}).items()}}
    if not blob["ok"]:
        print(f"[chaos] lint gate FAIL: {verdict}", flush=True)
        failures.append("lint-gate")
    else:
        print(f"[chaos] lint gate clean ({blob['files']} files)",
              flush=True)
    return verdict


def main() -> int:
    t_start = time.perf_counter()
    failures = []
    record: dict = {}
    gate_verdict = _lint_gate_verdict(failures)
    with tempfile.TemporaryDirectory() as tmp:
        _build_chairs_tree(tmp)
        os.environ["DEXIRAFT_DATA_DIR"] = tmp
        cwd = os.getcwd()
        os.chdir(tmp)
        phases = [
            ("corrupt-sample", phase_corrupt_sample),
            ("worker-death", phase_worker_death),
            ("sigterm-resume", lambda: phase_sigterm_resume(tmp)),
            ("truncated-ckpt", lambda: phase_truncated_checkpoint(tmp)),
            ("kill-mid-flush", lambda: phase_kill_mid_flush(tmp)),
            ("multihost-kill", lambda: phase_multihost_kill(tmp)),
            ("router-failover", lambda: phase_router_failover(tmp)),
            ("shrink-and-continue",
             lambda: phase_shrink_and_continue(tmp)),
        ]
        try:
            for name, fn in phases:
                t0 = time.perf_counter()
                print(f"[chaos] {name} ...", flush=True)
                extra: dict = {}
                try:
                    extra = fn() or {}
                    ok = True
                    print(f"[chaos] {name} PASS "
                          f"({time.perf_counter() - t0:.1f}s)", flush=True)
                except Exception:
                    traceback.print_exc()
                    ok = False
                    print(f"[chaos] {name} FAIL", flush=True)
                    failures.append(name)
                # per-phase recovery wall-time: the run-over-run signal
                # for recovery-latency regressions (+ the locks verdict
                # the concurrency-gate phases pin)
                record[name] = {"ok": ok,
                                "wall_s": round(time.perf_counter() - t0,
                                                1), **extra}
        finally:
            os.chdir(cwd)
    total = time.perf_counter() - t_start
    if failures:
        print(f"[chaos] FAILED: {failures} ({total:.1f}s)")
    else:
        print(f"[chaos] all {len(phases)} recovery paths recovered "
              f"({total:.1f}s)")
    from dexiraft_tpu.analysis import collective_trace, locks

    lrec = locks.stats_record()
    trec = collective_trace.recorder()
    print("[chaos] record " + json.dumps(
        {"phases": record, "failures": failures,
         "total_s": round(total, 1),
         # the whole smoke's lock-order verdict: every in-process
         # phase's thread fabric, one line, greppable run-over-run
         "locks": {"order_violations": lrec["order_violations"],
                   "cycles": lrec["cycles"],
                   "held_too_long": lrec["held_too_long"]},
         # ... and its collective-lockstep verdict: the parent's own
         # flight recorder plus the shrink survivor's (the process
         # that ran real consensus rounds through a reconfiguration);
         # divergences folds both — the pinned contract is 0
         "collective_trace": {
             "divergences": (trec.divergences
                             + int(_SURVIVOR_TRACE.get("divergences")
                                   or 0)),
             "local_entries": trec.recorded,
             "survivor_entries": _SURVIVOR_TRACE.get("entries"),
             "survivor_verified_rounds":
                 _SURVIVOR_TRACE.get("verified_rounds")},
         "lint_gate": gate_verdict},
        sort_keys=True), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
