"""Shared fixtures for the true multi-process distributed test.

Lives outside test_*.py so both the pytest parent and the spawned child
processes (tests/multiproc_child.py) import the exact same dataset and
model configuration — the grad-parity assertion is only meaningful if
every process derives identical samples and identical initial state.
"""

from __future__ import annotations

import numpy as np

from dexiraft_tpu.config import TrainConfig, raft_v1

GLOBAL_BATCH = 8
IMAGE_SIZE = (48, 64)
SEED = 7
N_STEPS = 3

# --- cross-process context-parallel (ring) test geometry ---------------------
# H must divide by n_seq * 2^(CP_LEVELS-1) = 4 * 4 (ring_corr_lookup's
# pooling-alignment requirement)
CP_B, CP_H, CP_W, CP_C = 1, 16, 16, 16
CP_LEVELS, CP_RADIUS = 3, 3


def cp_full_inputs():
    """Deterministic full-size ring-test inputs — identical in every
    child process and in the parent's unsharded reference."""
    rng = np.random.default_rng(42)
    f1 = rng.normal(size=(CP_B, CP_H, CP_W, CP_C)).astype(np.float32)
    f2 = rng.normal(size=(CP_B, CP_H, CP_W, CP_C)).astype(np.float32)
    ys, xs = np.meshgrid(np.arange(CP_H), np.arange(CP_W), indexing="ij")
    base = np.stack([xs, ys], axis=-1)[None].astype(np.float32)
    coords = base + rng.uniform(
        -2, 2, size=(CP_B, CP_H, CP_W, 2)).astype(np.float32)
    return f1, f2, coords


class SyntheticFlowDataset:
    """Deterministic function of the sample index alone (the loader's
    counter-based aug rng is deliberately ignored): any process can
    reproduce any sample, which is what lets the parent rebuild the
    children's global batches exactly. Each sample also carries its own
    index so the test can verify WHICH samples each host decoded."""

    def __init__(self, n: int = 32, size=IMAGE_SIZE):
        self.n = n
        self.h, self.w = size

    def __len__(self) -> int:
        return self.n

    def sample(self, index: int, rng) -> dict:
        del rng
        r = np.random.default_rng(1000 + index)
        img2 = r.uniform(0, 255, (self.h, self.w, 3)).astype(np.float32)
        # small smooth flow; image1 as a plain shift keeps this cheap —
        # convergence is not under test here, numerics parity is
        flow = np.broadcast_to(
            r.uniform(-2, 2, (1, 1, 2)), (self.h, self.w, 2)
        ).astype(np.float32)
        img1 = np.roll(img2, (1, 1), axis=(0, 1))
        return {
            "image1": img1,
            "image2": img2,
            "flow": np.ascontiguousarray(flow),
            "valid": np.ones((self.h, self.w), np.float32),
            "index": np.asarray(index, np.int32),
        }


def make_configs():
    cfg = raft_v1(small=True, mixed_precision=False)
    tc = TrainConfig(name="mp-test", num_steps=16, batch_size=GLOBAL_BATCH,
                     image_size=IMAGE_SIZE, iters=2, lr=1e-4, wdecay=1e-5)
    return cfg, tc


def spawn_child_pair(child_path, outs, ckpt_dir, extra=(),
                     timeout: float = 300.0):
    """Two spawned children, one rendezvous port; returns
    ([rc0, rc1], [log0, log1], wall_s).

    Shared by tests/test_zzmultihost_resilience.py and
    scripts/chaos_smoke.py (multihost phase) so the pair orchestration
    cannot drift between the suite and the smoke. Never raises on a
    hung child: it is killed and reaped, its log slot is the
    '<killed: timed out>' placeholder, and its returncode reports the
    kill — callers assert on exit codes with the surviving logs
    attached, which is exactly the diagnosis a hang needs.
    XLA_FLAGS is stripped so children control their own virtual device
    count."""
    import os
    import socket
    import subprocess
    import sys
    import time

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    t0 = time.perf_counter()
    procs = [subprocess.Popen(
        [sys.executable, str(child_path), "--port", str(port),
         "--process_id", str(pid), "--out", str(out),
         "--ckpt_dir", str(ckpt_dir), *[str(a) for a in extra]],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for pid, out in enumerate(outs)]
    logs = []
    try:
        for p in procs:
            try:
                logs.append(p.communicate(timeout=timeout)[0]
                            .decode(errors="replace"))
            except subprocess.TimeoutExpired:
                logs.append("<killed: timed out>")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10)
    return [p.returncode for p in procs], logs, time.perf_counter() - t0


def free_port() -> int:
    """An OS-assigned free TCP port for a coordination service."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def launch_child(child_path, out, ckpt_dir, port, process_id, extra=()):
    """Popen ONE child. The elastic scenarios need heterogeneous
    worlds — a solo incumbent plus a later --join replacement, or a
    parity-reference rerun — which the symmetric pair launcher cannot
    express. Same CLI surface and XLA_FLAGS hygiene as
    spawn_child_pair; reap with reap_children."""
    import os
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    return subprocess.Popen(
        [sys.executable, str(child_path), "--port", str(port),
         "--process_id", str(process_id), "--out", str(out),
         "--ckpt_dir", str(ckpt_dir), *[str(a) for a in extra]],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)


def reap_children(procs, timeout: float = 300.0):
    """Collect launch_child processes: ([rc...], [log...], wall_s),
    with the same never-raise/kill-on-timeout contract as
    spawn_child_pair."""
    import subprocess
    import time

    t0 = time.perf_counter()
    logs = []
    try:
        for p in procs:
            try:
                logs.append(p.communicate(timeout=timeout)[0]
                            .decode(errors="replace"))
            except subprocess.TimeoutExpired:
                logs.append("<killed: timed out>")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10)
    return [p.returncode for p in procs], logs, time.perf_counter() - t0


def patch_orbax_kv_barriers(cap_timeout_s=None) -> None:
    """Reroute orbax's process-sync onto its distributed-client barrier.

    orbax's ``sync_global_processes`` defaults to an XLA allgather
    (``multihost_utils.sync_global_devices``) that this container's CPU
    backend cannot run ("Multiprocess computations aren't implemented on
    the CPU backend") — but orbax already ships the non-XLA alternative,
    ``get_barrier_sync_fn`` over the jax.distributed coordination
    service (the path newer orbax versions default to). Semantically the
    same barrier, carried by gRPC instead of a compiled collective.

    Called by the multiprocess resilience children (and the chaos-smoke
    multihost phase): on real TPU pods the XLA barrier exists and this
    shim is unnecessary; on the 2-process virtual CPU mesh it is the
    difference between exercising the real multiprocess checkpoint path
    and not testing it at all.

    cap_timeout_s caps every barrier's timeout (elastic children pass a
    few seconds): a checkpoint barrier against a DEAD peer then fails
    fast instead of pinning the flush — and with it anything behind the
    wait_pending barrier — for orbax's default 300 s, which would
    swallow the whole elastic recovery budget. Healthy barriers are
    unaffected: the elastic worlds rendezvous at consensus boundaries,
    so real flush skew is milliseconds.
    """
    from orbax.checkpoint import multihost as omh_pkg
    from orbax.checkpoint._src.multihost import multihost as omh

    def kv_sync(name, *, timeout=None, processes=None,
                barrier_sync_fn=None, **_unused):
        from jax._src import distributed

        if barrier_sync_fn is None and distributed.global_state.client \
                is None:
            return  # solo world (or mid-elastic-reconfig): nobody to sync
        fn = barrier_sync_fn or omh.get_barrier_sync_fn(
            processes=processes)
        timeout_s = timeout or 300
        if cap_timeout_s is not None:
            timeout_s = min(timeout_s, cap_timeout_s)
        # flight-recorder stamp: the barrier key is the protocol
        # identity (identical across hosts for a lockstep barrier)
        from dexiraft_tpu.analysis import collective_trace

        collective_trace.record(
            "dexiraft/barrier", "orbax_sync",
            digest=collective_trace.args_digest(str(name)))
        fn(key=name, timeout_ms=int(timeout_s * 1000))

    omh.sync_global_processes = kv_sync
    omh_pkg.sync_global_processes = kv_sync
