"""Serving throughput — the eval-side analog of scripts/train_bench.py.

Three modes, one watchdogged script:

**Engine mode** (default): drives ONE mixed-geometry frame-pair stream
through the throughput-mode inference engine (dexiraft_tpu.serve) at
batch_size=1 (the reference per-image behavior) and at --batch, same
jitted eval step, and emits ONE JSON record: frame-pairs/s per config,
p50/p99 batch latency, bucket hit/compile counts (the mixed stream must
compile EXACTLY once per bucket), peak in-flight depth, fetch-blocked
time, and FLOPs/MFU from XLA's cost analysis. The speedup field is the
acceptance signal: batched throughput over the batch-1 configuration of
the same run.

**Closed-loop mode** (``--closed_loop``): a load generator against the
REAL service (serve.server.FlowService over HTTP on loopback — request
queue, SLO batching, sessions; the SERVE_r0* service record). Phases:
  1. sequential baseline — a batch_size=1 service under closed-loop
     load (each client waits for its response before sending the next),
  2. goodput-vs-concurrency — the batched service at >= 2 closed-loop
     concurrency levels, client-measured p50/p99 per level,
  3. overload — OPEN arrivals at ``--overload_factor`` x the measured
     batched goodput: admission control must shed with 503s while
     goodput holds near capacity instead of collapsing,
  4. session warm-start — a static synthetic stream posted K times
     under one ``X-Session-Id``: chained carry approximates a K*iters
     refinement, so the last warm response must sit measurably closer
     to a K*iters reference than the cold single-request response does
     (the service-side proof of the scripts/warmstart_bench.py win).
The acceptance signals: ``speedup_batched_over_sequential > 1`` and
``warm_start.warm_beats_cold``.

**Fleet mode** (``--fleet N``): spawns N ``--synthetic_init`` serve
replica PROCESSES and drives the router (serve/router.py) over them:
  1. goodput-vs-replica-count scaling curve (router re-pooled at each
     k in 1..N, session clients — affinity hit rate per level),
  2. kill-a-replica-under-load: SIGKILL one replica mid-traffic, then
     measure breaker-detection latency, client-visible recovery gap,
     failover retries, sticky-miss remaps, and the zero-drop check
     (``kill.zero_dropped``: no client saw a non-200 — router failover
     plus the client's connection-refused retry absorb the death).
The bench process itself never imports jax: replicas own the devices.
Record schema pinned by FLEET_RECORD_KEYS / tests/test_zzfleet_router.

**Adaptive mode** (``--adaptive``): the convergence-gated early-exit
engine (ServeConfig(adaptive=True) over make_eval_step(adaptive=True))
against the fixed-iteration engine with the SAME weights — the
synthetic-init contraction fixture (FlowHead_0 damped x0.01, see
docs/perf.md). Three phases: (1) quality/iters — per-pair EPE between
adaptive and fixed flows plus iters_used stats (the early-exit win must
not move the answer), (2) latency — per-pair wall time both legs,
(3) overload — OPEN arrivals at the same offered rate against BOTH
services: the adaptive scheduler must degrade iteration budgets
(iter_budget_p50 < max_iters) while goodput holds (ratio ~>= 1).
Record schema pinned by ADAPTIVE_RECORD_KEYS / tests/test_zzzadaptive.

Watchdog (the bench.py pattern, tests/test_bench_watchdog.py /
tests/test_zserve_bench.py): the measurement runs in a CHILD process;
the parent kills it when it goes silent past SERVE_BENCH_STALL_S or
overruns SERVE_BENCH_HARD_CAP_S and exits 8 — a hung device fetch must
never hang the caller. SERVE_BENCH_FAKE_HANG=1 swaps in
a child that blocks forever (watchdog tests). The parent imports no jax.

Usage: python scripts/serve_bench.py [--variant v1] [--small]
           [--batch 4] [--iters 4] [--sizes 40x56,44x60,36x52]
           [--frames 16] [--bucket_multiple 16] [--inflight 2]
           [--data_parallel 0] [--cpu] [--no_compile_cache]
       python scripts/serve_bench.py --closed_loop [--size 96x128]
           [--requests 32] [--concurrency 4] [--slo_ms 150]
           [--overload_factor 4] [--warm_frames 4] [--cpu]
       python scripts/serve_bench.py --fleet 2 [--size 64x96]
           [--requests 48] [--concurrency 4] [--iters 2] [--cpu]
       python scripts/serve_bench.py --adaptive [--size 96x128]
           [--iters 32] [--min_iters 4] [--converge_tol 0.02] [--cpu]
"""

from __future__ import annotations

import argparse
import json
import os
import os.path as osp
import subprocess
import sys
import time

sys.path.insert(0, osp.dirname(osp.dirname(osp.abspath(__file__))))

STALL_S = 600.0
HARD_CAP_S = 1500.0

RECORD_KEYS = {  # pinned by tests/test_zserve_bench.py
    "metric", "platform", "variant", "iters", "sizes", "frames",
    "bucket_multiple", "configs", "speedup_batched_over_b1",
    "corr_impl_resolved",
}
CONFIG_KEYS = {
    "batch_size", "inflight", "frame_pairs_per_sec", "latency_p50_ms",
    "latency_p99_ms", "bucket_count", "compiles", "buckets",
    "peak_inflight", "fetch_blocked_ms", "pad_frames", "compile_s",
    "flops_per_pair", "tflops_per_sec", "mfu",
}

# ---- closed-loop (service) record schema, pinned by
# tests/test_zzserve_service.py ------------------------------------------
CLOSED_LOOP_RECORD_KEYS = {
    "metric", "platform", "variant", "iters", "size", "batch", "slo_ms",
    "max_queue", "sequential", "levels", "overload", "warm_start",
    "speedup_batched_over_sequential", "corr_impl_resolved",
}
LEVEL_KEYS = {
    "concurrency", "requests", "goodput_rps", "p50_ms", "p99_ms",
    "rejected", "errors", "client_retries", "dispatch_full",
    "dispatch_slo", "mean_batch_fill", "queue_peak",
}

# ---- fleet (router) record schema, pinned by
# tests/test_zzfleet_router.py --------------------------------------------
FLEET_RECORD_KEYS = {
    "metric", "platform", "variant", "iters", "size", "batch", "slo_ms",
    "max_queue", "replicas", "concurrency", "requests", "scaling",
    "kill", "goodput_scaling", "corr_impl_resolved",
}
FLEET_SCALING_KEYS = {
    "replicas", "concurrency", "requests", "goodput_rps", "p50_ms",
    "p99_ms", "errors", "client_retries", "router_retries", "failovers",
    "affinity_hit_rate",
}
FLEET_KILL_KEYS = {
    "killed", "requests", "completed", "errors", "client_retries",
    "detect_s", "recovery_s", "max_gap_s", "router_retries", "failovers",
    "sticky_misses", "affinity_hit_rate_before", "affinity_hit_rate_after",
    "zero_dropped",
}
OVERLOAD_KEYS = {
    "offered_rps", "duration_s", "completed", "rejected", "errors",
    "goodput_rps", "p99_ms",
}
WARM_KEYS = {
    "frames", "iters", "iters_ref", "warm_dist", "cold_dist",
    "warm_beats_cold",
}

# ---- adaptive-iteration record schema, pinned by
# tests/test_zzzadaptive.py -----------------------------------------------
ADAPTIVE_RECORD_KEYS = {
    "metric", "platform", "variant", "iters", "size", "frames", "batch",
    "slo_ms", "max_queue", "converge_tol", "min_iters",
    "corr_impl_resolved",
    "epe_vs_fixed_px", "mean_iters_used", "p99_iters_used",
    "iters_drop_pct", "mean_final_delta",
    "fixed_ms_per_pair", "adaptive_ms_per_pair",
    "overload_fixed", "overload_adaptive", "overload_goodput_ratio",
}
# the adaptive overload entry carries the fixed OVERLOAD_KEYS plus the
# degradation evidence: what budgets the scheduler actually granted and
# how many iterations the while_loop actually ran
ADAPTIVE_OVERLOAD_KEYS = OVERLOAD_KEYS | {
    "iter_budget_p50", "iter_budget_p99", "iters_used_mean",
}


def build_parser() -> argparse.ArgumentParser:
    from dexiraft_tpu.config import CORR_IMPLS

    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", default="v5")
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="the batched configuration's micro-batch size")
    ap.add_argument("--iters", type=int, default=32)
    ap.add_argument("--sizes", default="440x1024,436x1020,432x1016",
                    help="comma-separated HxW geometries, cycled over "
                         "the stream (mixed-geometry bucket proof)")
    ap.add_argument("--frames", type=int, default=12,
                    help="frame pairs in the stream")
    ap.add_argument("--bucket_multiple", type=int, default=64,
                    help="bucket quantization granule (multiple of 8)")
    ap.add_argument("--inflight", type=int, default=2)
    ap.add_argument("--data_parallel", type=int, default=0,
                    help="shard each batch over this many chips (0 = one)")
    ap.add_argument("--no_compile_cache", action="store_true")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend (same as JAX_PLATFORMS=cpu)")
    ap.add_argument("--corr_impl", default="auto",
                    choices=["auto", *CORR_IMPLS],
                    help="'auto' (default) = the production config: "
                         "flash-blocked fused step on TPU, allpairs "
                         "off-chip; the RESOLVED value is stamped into "
                         "every record as corr_impl_resolved so A/Bs "
                         "are self-describing")
    ap.add_argument("--fused_update", action="store_true",
                    help="fused Pallas lookup+update kernel (requires "
                         "--corr_impl flash)")
    # ---- closed-loop (service) mode ------------------------------------
    ap.add_argument("--closed_loop", action="store_true",
                    help="load-generate against the real FlowService over "
                         "HTTP instead of driving the engine directly")
    ap.add_argument("--size", default="96x128",
                    help="closed-loop frame geometry HxW (one bucket: the "
                         "service phases measure scheduling, not bucket "
                         "spread — engine mode covers that)")
    ap.add_argument("--requests", type=int, default=32,
                    help="closed-loop requests per concurrency level")
    ap.add_argument("--concurrency", type=int, default=4,
                    help="highest closed-loop client count (levels are "
                         "1 and this)")
    ap.add_argument("--slo_ms", type=float, default=150.0,
                    help="service latency budget (scheduler hold window)")
    ap.add_argument("--max_queue", type=int, default=64,
                    help="service admission bound (503 past it)")
    ap.add_argument("--overload_factor", type=float, default=4.0,
                    help="open-arrival offered rate as a multiple of the "
                         "measured batched goodput")
    ap.add_argument("--overload_duration_s", type=float, default=3.0)
    ap.add_argument("--warm_frames", type=int, default=4,
                    help="frames chained through one session for the "
                         "warm-start convergence check")
    # ---- adaptive-iteration mode ---------------------------------------
    ap.add_argument("--adaptive", action="store_true",
                    help="adaptive-iteration leg: convergence-gated "
                         "early-exit engine vs the fixed-iters engine on "
                         "the damped contraction fixture — EPE delta, "
                         "iters_used, latency, overload goodput with "
                         "degraded budgets")
    ap.add_argument("--converge_tol", type=float, default=None,
                    help="override RAFTConfig.converge_tol for the "
                         "adaptive leg (default: the config's)")
    ap.add_argument("--min_iters", type=int, default=4,
                    help="adaptive scheduler budget floor (clamped to "
                         "--iters)")
    # ---- fleet (router) mode -------------------------------------------
    ap.add_argument("--fleet", type=int, default=0,
                    help="spawn this many --synthetic_init serve replica "
                         "processes and bench the router over them: "
                         "goodput-vs-replica-count scaling, kill-a-"
                         "replica recovery, session-affinity hit rate")
    ap.add_argument("--boot_timeout_s", type=float, default=600.0,
                    help="fleet replica boot bound (restore + warmup "
                         "compile)")
    return ap


def _build_eval_fn(args, iters=None, adaptive=False, damp_flow_head=None):
    """Model + jitted eval step + engine-contract eval_fn — shared by
    the engine-mode measurement and the closed-loop service phases.
    Returns (eval_fn, mesh, step, variables).

    adaptive=True builds the convergence-gated while_loop step
    (make_eval_step(adaptive=True)); the eval_fn then takes a trailing
    iter_budget (None -> the full configured iters, normalized to ONE
    np.int32 aval so every budget rides the bucket's single executable)
    and returns the 4-tuple (flow_low, flow_up, iters_used, final_delta).

    damp_flow_head scales every FlowHead_0 param leaf (the contraction
    fixture, docs/perf.md: random-init updates do not contract, damping
    the flow head's output gives the convergence plateau a trained model
    has — the adaptive leg needs weights that actually converge).
    Identical PRNGKey(0) init means two calls hand back identical
    weights, so a fixed/adaptive A/B shares one set of parameters."""
    import jax

    from dexiraft_tpu import config as C
    from dexiraft_tpu.config import TrainConfig
    from dexiraft_tpu.profiling import enable_persistent_cache
    from dexiraft_tpu.train.state import create_state
    from dexiraft_tpu.train.step import make_eval_step

    if not args.no_compile_cache:
        cache_dir = enable_persistent_cache()
        print(f"compile cache: {cache_dir}", file=sys.stderr)

    # resolve --corr_impl (default "auto" -> the platform's production
    # config) and remember the resolution for the record stamp — the
    # eval/serve CLIs print it, the records carry it (corr_impl_resolved)
    impl, fused = C.resolve_corr_impl_args(
        args, jax.devices()[0].platform, "serve_bench")
    args.corr_impl_resolved = impl
    cfg = getattr(C, f"raft_{args.variant}")(small=args.small,
                                             corr_impl=impl,
                                             fused_update=fused)
    if getattr(args, "converge_tol", None) is not None:
        import dataclasses

        cfg = dataclasses.replace(cfg, converge_tol=args.converge_tol)
    args.converge_tol_resolved = cfg.converge_tol
    state = create_state(jax.random.PRNGKey(0), cfg, TrainConfig())
    variables = {"params": state.params, "batch_stats": state.batch_stats}
    if damp_flow_head:
        from jax.tree_util import tree_map_with_path

        def _damp(path, leaf):
            keys = [getattr(p, "key", getattr(p, "name", None))
                    for p in path]
            return leaf * damp_flow_head if "FlowHead_0" in keys else leaf

        variables = {"params": tree_map_with_path(_damp,
                                                  variables["params"]),
                     "batch_stats": variables["batch_stats"]}

    mesh = None
    if args.data_parallel > 0:
        from dexiraft_tpu.parallel.layout import make_serve_mesh, replicate

        mesh = make_serve_mesh(args.data_parallel)
        # params must live replicated on the mesh up front, or the
        # pinned replicated in_sharding re-transfers them every dispatch
        variables = replicate(variables, mesh)
    full = iters or args.iters
    step = make_eval_step(cfg, iters=full, mesh=mesh, adaptive=adaptive)
    if adaptive:
        import numpy as np

        # the trailing iter_budget arrives from the engine already
        # np.int32-normalized (or None = ride the full iters) — resolve
        # None to the SAME int32 aval so warmup and budgeted dispatches
        # share one executable per bucket
        if mesh is None:
            put = jax.device_put
            eval_fn = lambda a, b, fi, ib=None: step(
                variables, put(a), put(b),
                flow_init=None if fi is None else put(fi),
                iter_budget=np.int32(full if ib is None else ib))
        else:
            eval_fn = lambda a, b, fi, ib=None: step(
                variables, a, b, None, None, fi,
                np.int32(full if ib is None else ib))
    elif mesh is None:
        # explicit H2D puts: the engine hands host-stacked numpy
        # batches; spelling the transfer keeps the strict regions
        # (guards.strict_mode) clean without widening their teeth
        put = jax.device_put
        eval_fn = lambda a, b, fi: step(
            variables, put(a), put(b),
            flow_init=None if fi is None else put(fi))
    else:
        eval_fn = lambda a, b, fi: step(variables, a, b, None, None, fi)
    return eval_fn, mesh, step, variables


def _measure(args) -> None:
    import jax
    import numpy as np

    from dexiraft_tpu.analysis import guards
    from dexiraft_tpu.serve import InferenceEngine, ServeConfig

    sizes = [tuple(int(v) for v in s.split("x")) for s in args.sizes.split(",")]
    eval_fn, mesh, step, variables = _build_eval_fn(args)
    print(f"platform={jax.devices()[0].platform} variant={args.variant} "
          f"small={args.small} iters={args.iters} sizes={args.sizes} "
          f"frames={args.frames} batch={args.batch} "
          f"multiple={args.bucket_multiple} dp={args.data_parallel}",
          file=sys.stderr)

    def stream_items():
        # pre-decoded, like the Loader hands over: host next() is free,
        # so any fetch-blocked time is genuinely device-side
        rng = np.random.default_rng(0)
        pool = []
        for k in range(args.frames):
            h, w = sizes[k % len(sizes)]
            pool.append({
                "image1": rng.uniform(0, 255, (h, w, 3)).astype(np.float32),
                "image2": rng.uniform(0, 255, (h, w, 3)).astype(np.float32),
            })
        return pool

    pool = stream_items()

    def run_config(batch_size: int) -> dict:
        engine = InferenceEngine(
            eval_fn,
            ServeConfig(batch_size=batch_size, mode="sintel",
                        bucket_multiple=args.bucket_multiple,
                        inflight=args.inflight),
            mesh=mesh)
        # warmup pass compiles every bucket (counted); the timed pass
        # must ride the in-process executable cache only. Draining
        # stream() IS the sync: every yielded Result was device_get-ed
        # by the engine's fetch side.
        t0 = time.perf_counter()  # jaxlint: disable=JL004
        for _ in engine.stream(dict(it) for it in pool):
            pass
        warm_s = time.perf_counter() - t0
        print(f"[b={batch_size}] warmup {warm_s:.1f}s "
              f"(compile {engine.compile_s:.1f}s, "
              f"{engine.registry.compiles} executables)", file=sys.stderr)
        engine.stats.reset()
        engine.registry.hits.clear()  # report the TIMED stream's hits
        # (the compiled-signature set survives: compiles stays honest)
        # steady-state contract (analysis/guards): warmup compiled every
        # bucket, so the timed stream must be compile-FLAT — a retrace
        # (or, single-chip, an implicit host transfer) here FAILS the
        # bench instead of silently deflating its number. The mesh path
        # keeps pinned in_shardings' own transfer semantics, so only the
        # recompile sentinel is armed there.
        # draining stream() fetches every Result to host (the sync)
        with guards.strict_mode(
                label=f"serve_bench[b={batch_size}]",
                transfer="disallow" if mesh is None else "allow"):
            t0 = time.perf_counter()  # jaxlint: disable=JL004
            n = sum(1 for _ in engine.stream(dict(it) for it in pool))
            dt = time.perf_counter() - t0
        print(f"[b={batch_size}] timed {dt * 1e3:.1f} ms for {n} pairs; "
              f"{engine.stats.summary()}", file=sys.stderr)

        # FLOPs of one compiled batch from XLA's own cost analysis
        # (None where the backend declines to count). A utilisation is
        # printed only on a TPU, against that device_kind's peak — an
        # unknown kind is an error there, not a default
        from bench import _counted_flops, chip_peak_bf16_flops

        flops_per_pair = tfps = mfu = None
        (bh, bw), _ = max(engine.registry.hits.items(),
                          key=lambda kv: kv[1])
        a = np.zeros((batch_size, bh, bw, 3), np.float32)
        lower_args = ((variables, a, a) if mesh is None
                      else (variables, a, a, None, None, None))
        flops = _counted_flops(step, *lower_args)
        if flops:
            flops_per_pair = flops / batch_size
            tfps = flops_per_pair * (n / dt) / 1e12
            if jax.devices()[0].platform == "tpu":
                peak = chip_peak_bf16_flops(jax.devices()[0].device_kind)
                mfu = round(tfps * 1e12 / peak, 4)

        reg = engine.registry.stats()
        return {
            "batch_size": batch_size,
            "inflight": args.inflight,
            "frame_pairs_per_sec": round(n / dt, 3),
            "latency_p50_ms": round(engine.stats.latency_ms(50), 2),
            "latency_p99_ms": round(engine.stats.latency_ms(99), 2),
            "bucket_count": reg["bucket_count"],
            "compiles": reg["compiles"],
            "buckets": reg["buckets"],
            "peak_inflight": engine.stats.peak_inflight,
            "fetch_blocked_ms": round(engine.stats.fetch_s * 1e3, 2),
            "pad_frames": engine.stats.pad_frames,
            "compile_s": round(engine.compile_s, 2),
            "flops_per_pair": flops_per_pair,
            "tflops_per_sec": round(tfps, 3) if tfps else None,
            "mfu": mfu,
        }

    # baseline: batch 1, or the smallest mesh-divisible batch when
    # data-parallel (a batch of 1 cannot shard over N chips)
    base_bs = max(1, args.data_parallel)
    configs = [run_config(base_bs)]
    if args.batch > base_bs:
        configs.append(run_config(args.batch))
    b1 = configs[0]["frame_pairs_per_sec"]
    record = {
        "metric": "serve_frame_pairs_per_sec",
        "platform": jax.devices()[0].platform,
        "variant": args.variant + ("-small" if args.small else ""),
        "iters": args.iters,
        "sizes": args.sizes,
        "frames": args.frames,
        "bucket_multiple": args.bucket_multiple,
        "corr_impl_resolved": args.corr_impl_resolved,
        "configs": configs,
        # None when only the baseline ran (e.g. --batch <= the
        # data-parallel baseline) — never a self-ratio of 1.0
        "speedup_batched_over_b1": (
            round(configs[-1]["frame_pairs_per_sec"] / b1, 3)
            if len(configs) > 1 and b1 else None),
    }
    assert set(record) == RECORD_KEYS, sorted(set(record) ^ RECORD_KEYS)
    assert all(set(c) == CONFIG_KEYS for c in configs)
    print(json.dumps(record), flush=True)


# ---- closed-loop (service) mode -----------------------------------------


def _http_get_json(host: str, port: int, path: str) -> dict:
    import http.client

    conn = http.client.HTTPConnection(host, port, timeout=30)
    try:
        conn.request("GET", path)
        return json.loads(conn.getresponse().read())
    finally:
        conn.close()


def _pctl_ms(samples, p: float) -> float:
    import numpy as np

    if not samples:
        return 0.0
    return round(float(np.percentile(samples, p)) * 1e3, 2)


_CLIENT_TRIES = 4          # attempts per request (1 + up to 3 retries)
_CLIENT_BACKOFF_S = 0.05   # doubling, jittered


def _client_thread(host: str, port: int, body: bytes, n: int,
                   latencies: list, rejects: list, session=None,
                   retries: list = None, completions: list = None) -> None:
    """One closed-loop client: POST, wait for the response, repeat.
    Keep-alive (HTTP/1.1) — one connection per client, like a real
    streaming caller. Appends per-request latency (s) or the reject
    status code; list.append is atomic, no lock needed.

    Connection-shaped failures (refused/reset — a replica restarting
    under the client) RETRY with doubling jittered backoff instead of
    counting as errors: a restart window is a liveness blip, not a
    service failure, and conflating the two made every rolling restart
    read as client errors. Each retry appends to `retries` (reported
    separately from `rejects`); only exhausting every attempt appends
    the sentinel -1 to `rejects`. `completions` (when given) collects
    (t_monotonic, status) per finished request — the fleet kill leg's
    gap/recovery analysis reads it."""
    import http.client

    headers = {"Content-Type": "application/x-npz"}
    if session:
        headers["X-Session-Id"] = session
    rng = __import__("random").Random(hash((port, session)) & 0xFFFF)
    conn = http.client.HTTPConnection(host, port, timeout=60)
    try:
        for _ in range(n):
            t0 = time.monotonic()
            status = -1
            for attempt in range(_CLIENT_TRIES):
                try:
                    conn.request("POST", "/v1/flow", body=body,
                                 headers=headers)
                    resp = conn.getresponse()
                    resp.read()
                    status = resp.status
                    break
                except (ConnectionRefusedError, ConnectionResetError,
                        BrokenPipeError, http.client.BadStatusLine,
                        http.client.RemoteDisconnected):
                    conn.close()
                    conn = http.client.HTTPConnection(host, port,
                                                      timeout=60)
                    if attempt == _CLIENT_TRIES - 1:
                        break
                    if retries is not None:
                        retries.append(attempt)
                    time.sleep(_CLIENT_BACKOFF_S * (2 ** attempt)
                               * (1 + rng.random()))
            now = time.monotonic()
            if completions is not None:
                completions.append((now, status))
            if status == 200:
                latencies.append(now - t0)
            else:
                rejects.append(status)
    finally:
        conn.close()


def _run_level(service, body: bytes, concurrency: int, requests: int) -> dict:
    """Closed-loop load at one concurrency level; the /stats?reset=1
    scrape hands the measurement window off exactly like a monitoring
    agent would (and pins that the reset path works under load)."""
    import threading

    host, port = service.address
    latencies: list = []
    rejects: list = []
    retries: list = []
    per = [requests // concurrency] * concurrency
    for i in range(requests % concurrency):
        per[i] += 1
    threads = [threading.Thread(target=_client_thread,
                                args=(host, port, body, n, latencies,
                                      rejects, None, retries))
               for n in per if n]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    sched = _http_get_json(host, port, "/stats?reset=1")["scheduler"]
    # "rejected" is ONLY admission shedding (503): folding 4xx/5xx or
    # connection failures in would let an erroring service masquerade
    # as one that is load-shedding gracefully
    shed = sum(1 for s in rejects if s == 503)
    out = {
        "concurrency": concurrency,
        "requests": requests,
        "goodput_rps": round(len(latencies) / wall, 3) if wall else 0.0,
        "p50_ms": _pctl_ms(latencies, 50),
        "p99_ms": _pctl_ms(latencies, 99),
        "rejected": shed,
        "errors": len(rejects) - shed,
        "client_retries": len(retries),
        "dispatch_full": sched["dispatch_full"],
        "dispatch_slo": sched["dispatch_slo"],
        "mean_batch_fill": sched["mean_batch_fill"],
        "queue_peak": sched["queue_peak"],
    }
    print(f"[closed c={concurrency}] {out['goodput_rps']} req/s, "
          f"p50 {out['p50_ms']} / p99 {out['p99_ms']} ms, "
          f"fill {out['mean_batch_fill']}, "
          f"full/slo {out['dispatch_full']}/{out['dispatch_slo']}",
          file=sys.stderr)
    return out


def _overload_sender(host: str, port: int, body: bytes, interval: float,
                     offset: float, t_end: float,
                     latencies: list, rejects: list) -> None:
    """One open-loop sender: fires on an absolute schedule (t0 + offset
    + k*interval) regardless of completions — if a request runs long the
    next one is already late and goes out immediately, preserving the
    offered rate. Keep-alive connection, reopened on error."""
    import http.client

    conn = http.client.HTTPConnection(host, port, timeout=60)
    t0 = time.monotonic()
    k = 0
    try:
        while True:
            nxt = t0 + offset + k * interval
            pause = nxt - time.monotonic()
            if pause > 0:
                time.sleep(pause)
            if time.monotonic() >= t_end:
                return
            k += 1
            t_req = time.monotonic()
            try:
                conn.request("POST", "/v1/flow", body=body,
                             headers={"Content-Type": "application/x-npz"})
                resp = conn.getresponse()
                resp.read()
                if resp.status == 200:
                    latencies.append(time.monotonic() - t_req)
                else:
                    rejects.append(resp.status)
            except Exception:
                rejects.append(-1)
                conn.close()
                conn = http.client.HTTPConnection(host, port, timeout=60)
    finally:
        conn.close()


def _run_overload(service, body: bytes, offered_rps: float,
                  duration_s: float, stats_out: dict = None) -> dict:
    """OPEN arrivals at a fixed offered rate (no back-pressure from
    completions): admission control must shed the excess with 503s and
    keep goodput near capacity — the queue-collapse counterexample.
    A FIXED pool of senders paces the rate (a thread per arrival would
    exhaust threads/fds at the offered rates real hardware produces)."""
    import threading

    host, port = service.address
    latencies: list = []
    rejects: list = []
    senders = max(4, min(64, int(offered_rps * 0.5)))
    interval = senders / max(offered_rps, 1e-6)
    t_end = time.monotonic() + duration_s
    threads = [threading.Thread(
        target=_overload_sender,
        args=(host, port, body, interval, i * interval / senders, t_end,
              latencies, rejects))
        for i in range(senders)]
    t0 = time.monotonic()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    wall = time.monotonic() - t0
    shed = sum(1 for s in rejects if s == 503)
    out = {
        "offered_rps": round(offered_rps, 3),
        "duration_s": round(duration_s, 3),
        "completed": len(latencies),
        "rejected": shed,
        "errors": len(rejects) - shed,
        "goodput_rps": round(len(latencies) / wall, 3) if wall else 0.0,
        "p99_ms": _pctl_ms(latencies, 99),
    }
    payload = _http_get_json(host, port, "/stats?reset=1")
    if stats_out is not None:
        # the adaptive leg reads the scheduler's granted-budget stats
        # out of the same scrape-and-reset the window handoff uses
        stats_out.update(payload)
    print(f"[overload] offered {out['offered_rps']} req/s for "
          f"{duration_s:g}s: {out['completed']} served, "
          f"{out['rejected']} shed / {out['errors']} errored, "
          f"goodput {out['goodput_rps']} req/s",
          file=sys.stderr)
    return out


def _measure_closed_loop(args) -> None:
    import threading

    import jax
    import numpy as np

    from dexiraft_tpu.data.padder import InputPadder
    from dexiraft_tpu.serve import InferenceEngine, ServeConfig, bucket_shape
    from dexiraft_tpu.serve.server import (FlowService, decode_response,
                                           encode_request)

    h, w = (int(v) for v in args.size.split("x"))
    rng = np.random.default_rng(0)
    im1 = rng.uniform(0, 255, (h, w, 3)).astype(np.float32)
    im2 = rng.uniform(0, 255, (h, w, 3)).astype(np.float32)
    body = encode_request(im1, im2)

    eval_fn, mesh, step, variables = _build_eval_fn(args)
    print(f"platform={jax.devices()[0].platform} variant={args.variant} "
          f"small={args.small} iters={args.iters} size={args.size} "
          f"batch={args.batch} slo_ms={args.slo_ms:g} "
          f"concurrency={args.concurrency}", file=sys.stderr)

    def make_service(batch_size: int, warm: bool) -> FlowService:
        engine = InferenceEngine(
            eval_fn,
            ServeConfig(batch_size=batch_size, mode="sintel",
                        bucket_multiple=args.bucket_multiple,
                        inflight=args.inflight, warm_start=warm),
            mesh=mesh)
        svc = FlowService(engine, port=0, slo_ms=args.slo_ms,
                          max_queue=args.max_queue,
                          session_ttl_s=60.0 if warm else 0.0,
                          request_timeout_s=60.0)
        svc.start()
        # warmup: compile the one bucket signature outside any timed
        # window, then hand off a clean measurement window
        _client_thread(*svc.address, body, 1, [], [])
        svc.reset_stats()
        return svc

    # -- phase 1: sequential baseline (batch_size=1 service) -------------
    seq_svc = make_service(1, warm=False)
    sequential = _run_level(seq_svc, body, args.concurrency, args.requests)
    seq_svc.drain_and_stop()

    # -- phases 2-4 share the batched, session-enabled service ----------
    svc = make_service(args.batch, warm=True)
    levels = [_run_level(svc, body, c, args.requests)
              for c in sorted({1, args.concurrency})]
    batched_rps = levels[-1]["goodput_rps"]

    overload = _run_overload(svc, body,
                             args.overload_factor * max(batched_rps, 0.5),
                             args.overload_duration_s)

    # -- phase 4: session warm-start convergence --------------------------
    # K chained warm requests ~ K*iters refinement (each frame seeds the
    # next through the session carry), so the K-th warm response must be
    # closer to a K*iters reference than the cold 1*iters response is
    import http.client

    host, port = svc.address
    conn = http.client.HTTPConnection(host, port, timeout=60)
    flow_cold = flow_warm = None
    try:
        for k in range(args.warm_frames):
            conn.request("POST", "/v1/flow", body=body,
                         headers={"X-Session-Id": "warm-bench"})
            resp = conn.getresponse()
            data = resp.read()
            assert resp.status == 200, (resp.status, data)
            if k == 0:
                flow_cold = decode_response(data)  # first frame IS cold
            flow_warm = decode_response(data)
    finally:
        conn.close()

    ref_eval_fn, _, _, _ = _build_eval_fn(
        args, iters=args.iters * args.warm_frames)
    bucket = bucket_shape(h, w, multiple=args.bucket_multiple)
    padder = InputPadder(im1.shape, mode="sintel", target=bucket)
    _, up_ref = ref_eval_fn(padder.pad(im1)[0][None],
                            padder.pad(im2)[0][None], None)
    flow_ref = padder.unpad(jax.device_get(up_ref)[0])
    warm_dist = float(np.mean(np.abs(flow_warm - flow_ref)))
    cold_dist = float(np.mean(np.abs(flow_cold - flow_ref)))
    warm_start = {
        "frames": args.warm_frames,
        "iters": args.iters,
        "iters_ref": args.iters * args.warm_frames,
        "warm_dist": round(warm_dist, 4),
        "cold_dist": round(cold_dist, 4),
        "warm_beats_cold": warm_dist < cold_dist,
    }
    print(f"[warm] dist-to-{warm_start['iters_ref']}-iter-ref: "
          f"cold {cold_dist:.4f} vs warm {warm_dist:.4f} "
          f"({'WIN' if warm_dist < cold_dist else 'NO WIN'})",
          file=sys.stderr)

    svc.drain_and_stop()

    record = {
        "metric": "serve_closed_loop",
        "platform": jax.devices()[0].platform,
        "variant": args.variant + ("-small" if args.small else ""),
        "iters": args.iters,
        "size": args.size,
        "batch": args.batch,
        "slo_ms": args.slo_ms,
        "max_queue": args.max_queue,
        "corr_impl_resolved": args.corr_impl_resolved,
        "sequential": sequential,
        "levels": levels,
        "overload": overload,
        "warm_start": warm_start,
        "speedup_batched_over_sequential": (
            round(batched_rps / sequential["goodput_rps"], 3)
            if sequential["goodput_rps"] else None),
    }
    assert set(record) == CLOSED_LOOP_RECORD_KEYS, \
        sorted(set(record) ^ CLOSED_LOOP_RECORD_KEYS)
    assert set(sequential) == LEVEL_KEYS
    assert all(set(lv) == LEVEL_KEYS for lv in levels)
    assert set(overload) == OVERLOAD_KEYS
    assert set(warm_start) == WARM_KEYS
    print(json.dumps(record), flush=True)


# ---- adaptive-iteration mode --------------------------------------------


def _measure_adaptive(args) -> None:
    """Adaptive-iteration leg (docstring "Adaptive mode"): the
    convergence-gated engine vs the fixed-iteration engine, SAME damped
    weights. Emits ONE JSON record (ADAPTIVE_RECORD_KEYS)."""
    import threading  # noqa: F401  (client threads under the hood)

    import jax
    import numpy as np

    from dexiraft_tpu.data.padder import InputPadder
    from dexiraft_tpu.serve import InferenceEngine, ServeConfig, bucket_shape
    from dexiraft_tpu.serve.server import FlowService, encode_request

    h, w = (int(v) for v in args.size.split("x"))
    rng = np.random.default_rng(0)
    body = encode_request(
        rng.uniform(0, 255, (h, w, 3)).astype(np.float32),
        rng.uniform(0, 255, (h, w, 3)).astype(np.float32))
    min_iters = max(1, min(args.min_iters, args.iters))

    # identical PRNGKey(0) init + identical damping -> the two steps
    # share one set of weights; only the refinement driver differs
    fixed_fn, mesh, _, _ = _build_eval_fn(args, damp_flow_head=0.01)
    adapt_fn, _, _, _ = _build_eval_fn(args, adaptive=True,
                                       damp_flow_head=0.01)
    tol = args.converge_tol_resolved
    print(f"platform={jax.devices()[0].platform} variant={args.variant} "
          f"small={args.small} iters={args.iters} size={args.size} "
          f"converge_tol={tol:g} min_iters={min_iters}", file=sys.stderr)

    # -- phase 1+2: per-pair quality / iters_used / latency ---------------
    bucket = bucket_shape(h, w, multiple=args.bucket_multiple)
    padder = InputPadder((h, w, 3), mode="sintel", target=bucket)
    pairs = [(rng.uniform(0, 255, (h, w, 3)).astype(np.float32),
              rng.uniform(0, 255, (h, w, 3)).astype(np.float32))
             for _ in range(args.frames)]

    def prep(im):
        return jax.device_put(padder.pad(im)[0][None])

    # warmup both signatures outside the timed loop (one compile each)
    a0, b0 = prep(pairs[0][0]), prep(pairs[0][1])
    jax.block_until_ready(fixed_fn(a0, b0, None))
    jax.block_until_ready(adapt_fn(a0, b0, None))

    epes, used, deltas = [], [], []
    t_fixed = t_adapt = 0.0
    for im1, im2 in pairs:
        a, b = prep(im1), prep(im2)
        t0 = time.perf_counter()  # jaxlint: disable=JL004
        _, up_f = jax.block_until_ready(fixed_fn(a, b, None))
        t_fixed += time.perf_counter() - t0  # jaxlint: disable=JL004
        t0 = time.perf_counter()  # jaxlint: disable=JL004
        _, up_a, iu, fd = jax.block_until_ready(adapt_fn(a, b, None))
        t_adapt += time.perf_counter() - t0  # jaxlint: disable=JL004
        ff, fa = jax.device_get((up_f, up_a))
        epes.append(float(np.sqrt(((fa - ff) ** 2).sum(-1)).mean()))
        used.append(int(jax.device_get(iu)[0]))
        deltas.append(float(jax.device_get(fd)[0]))
    mean_used = float(np.mean(used))
    print(f"[adaptive] epe_vs_fixed {np.mean(epes):.4f} px, iters_used "
          f"mean {mean_used:.1f}/{args.iters} "
          f"(p99 {np.percentile(used, 99):.1f}), final_delta mean "
          f"{np.mean(deltas):.2e}; per-pair fixed "
          f"{t_fixed / len(pairs) * 1e3:.1f} ms vs adaptive "
          f"{t_adapt / len(pairs) * 1e3:.1f} ms", file=sys.stderr)

    # -- phase 3: overload, fixed service vs adaptive service -------------
    def make_service(eval_fn, adaptive: bool) -> FlowService:
        engine = InferenceEngine(
            eval_fn,
            ServeConfig(batch_size=args.batch, mode="sintel",
                        bucket_multiple=args.bucket_multiple,
                        inflight=args.inflight, adaptive=adaptive),
            mesh=mesh)
        svc = FlowService(engine, port=0, slo_ms=args.slo_ms,
                          max_queue=args.max_queue,
                          request_timeout_s=60.0,
                          max_iters=args.iters, min_iters=min_iters)
        svc.start()
        _client_thread(*svc.address, body, 1, [], [])
        svc.reset_stats()
        return svc

    svc_fixed = make_service(fixed_fn, adaptive=False)
    # capacity probe on the FIXED service sets one shared offered rate:
    # both overload runs face the same open-arrival pressure
    level = _run_level(svc_fixed, body, args.concurrency, args.requests)
    offered = args.overload_factor * max(level["goodput_rps"], 0.5)
    overload_fixed = _run_overload(svc_fixed, body, offered,
                                   args.overload_duration_s)
    svc_fixed.drain_and_stop()

    svc_adapt = make_service(adapt_fn, adaptive=True)
    stats: dict = {}
    ov = _run_overload(svc_adapt, body, offered, args.overload_duration_s,
                       stats_out=stats)
    svc_adapt.drain_and_stop()
    sched = stats.get("scheduler", {})
    overload_adaptive = dict(
        ov,
        iter_budget_p50=sched.get("iter_budget_p50"),
        iter_budget_p99=sched.get("iter_budget_p99"),
        iters_used_mean=stats.get("engine", {}).get("iters_used_mean"),
    )
    print(f"[adaptive overload] budgets p50 "
          f"{overload_adaptive['iter_budget_p50']} / p99 "
          f"{overload_adaptive['iter_budget_p99']} (full {args.iters}), "
          f"goodput {ov['goodput_rps']} vs fixed "
          f"{overload_fixed['goodput_rps']} req/s", file=sys.stderr)

    record = {
        "metric": "serve_adaptive",
        "platform": jax.devices()[0].platform,
        "variant": args.variant + ("-small" if args.small else ""),
        "iters": args.iters,
        "size": args.size,
        "frames": args.frames,
        "batch": args.batch,
        "slo_ms": args.slo_ms,
        "max_queue": args.max_queue,
        "converge_tol": tol,
        "min_iters": min_iters,
        "corr_impl_resolved": args.corr_impl_resolved,
        "epe_vs_fixed_px": round(float(np.mean(epes)), 4),
        "mean_iters_used": round(mean_used, 2),
        "p99_iters_used": round(float(np.percentile(used, 99)), 2),
        # the early-exit win: % of the fixed iteration count NOT spent
        "iters_drop_pct": round(100.0 * (1.0 - mean_used / args.iters), 1),
        "mean_final_delta": round(float(np.mean(deltas)), 6),
        "fixed_ms_per_pair": round(t_fixed / len(pairs) * 1e3, 2),
        "adaptive_ms_per_pair": round(t_adapt / len(pairs) * 1e3, 2),
        "overload_fixed": overload_fixed,
        "overload_adaptive": overload_adaptive,
        "overload_goodput_ratio": (
            round(ov["goodput_rps"] / overload_fixed["goodput_rps"], 3)
            if overload_fixed["goodput_rps"] else None),
    }
    assert set(record) == ADAPTIVE_RECORD_KEYS, \
        sorted(set(record) ^ ADAPTIVE_RECORD_KEYS)
    assert set(overload_fixed) == OVERLOAD_KEYS
    assert set(overload_adaptive) == ADAPTIVE_OVERLOAD_KEYS, \
        sorted(set(overload_adaptive) ^ ADAPTIVE_OVERLOAD_KEYS)
    print(json.dumps(record), flush=True)


# ---- fleet (router) mode ------------------------------------------------


def _free_ports(n: int) -> list:
    import socket

    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def _fleet_serve_args(args) -> list:
    """Replica argv: --synthetic_init serve processes (random weights —
    the fleet legs measure routing/failover, not EPE), warmed up on the
    bench geometry so /healthz only answers once the compile is paid."""
    sa = ["--synthetic_init", "--variant", args.variant,
          "--iters", str(args.iters), "--batch_size", str(args.batch),
          "--slo_ms", str(args.slo_ms),
          "--max_queue", str(args.max_queue),
          "--session_ttl_s", "60",
          "--bucket_multiple", str(args.bucket_multiple),
          "--corr_impl", args.corr_impl,
          "--warmup", args.size, "--request_timeout_s", "60"]
    if args.fused_update:
        # without this a fleet A/B of the fused config silently spawns
        # UNFUSED replicas (explicit --corr_impl resolves fused=False)
        sa.append("--fused_update")
    if args.small:
        sa.append("--small")
    if args.cpu:
        sa.append("--cpu")
    return sa


def _fleet_router(urls, **overrides):
    from dexiraft_tpu.serve.router import Router, RouterConfig

    kw = dict(probe_interval_s=0.2, cooldown_s=1.0, fail_threshold=2,
              deadline_s=60.0)
    kw.update(overrides)
    return Router(urls, port=0, config=RouterConfig(**kw)).start()


def _measure_fleet(args) -> None:
    """Router-over-N-replicas legs: (1) goodput-vs-replica-count
    scaling curve, (2) kill-one-replica-under-load — recovery
    wall-time, zero-drop check, affinity hit rate before/after. The
    bench process itself NEVER imports jax: replicas own the devices
    (N processes cannot share one TPU chip), and the router/clients are
    pure control plane."""
    import threading
    from urllib.parse import urlparse

    from dexiraft_tpu.chips import refuse_more_than_chips
    from dexiraft_tpu.config import resolve_corr_impl
    from dexiraft_tpu.router_cli import spawn_replica, wait_ready
    from dexiraft_tpu.serve.server import encode_request

    import numpy as np

    h, w = (int(v) for v in args.size.split("x"))
    rng = np.random.default_rng(0)
    body = encode_request(
        rng.uniform(0, 255, (h, w, 3)).astype(np.float32),
        rng.uniform(0, 255, (h, w, 3)).astype(np.float32))

    n = args.fleet
    if n < 2:
        raise SystemExit("--fleet needs >= 2 replicas (the kill leg "
                         "must have a survivor)")
    ports = _free_ports(n)
    serve_args = _fleet_serve_args(args)
    refuse_more_than_chips(n, "serve_bench --fleet")
    procs = {f"r{i}": spawn_replica(p, serve_args, chip=i)
             for i, p in enumerate(ports)}
    urls = {f"r{i}": f"127.0.0.1:{p}" for i, p in enumerate(ports)}
    platform = "cpu" if args.cpu else os.environ.get("JAX_PLATFORMS",
                                                     "default")

    def run_clients(url, concurrency, per, prefix, completions=None):
        u = urlparse(url)
        latencies, rejects, retries = [], [], []
        threads = [threading.Thread(
            target=_client_thread,
            args=(u.hostname, u.port, body, per, latencies, rejects,
                  f"{prefix}-{i}", retries, completions))
            for i in range(concurrency)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        return threads, latencies, rejects, retries, t0

    try:
        for i, p in enumerate(ports):
            if not wait_ready("127.0.0.1", p, args.boot_timeout_s):
                raise RuntimeError(f"replica r{i} (port {p}) not healthy "
                                   f"within {args.boot_timeout_s:g}s")
            print(f"[fleet] replica r{i} healthy on port {p}",
                  file=sys.stderr, flush=True)

        per = max(1, args.requests // args.concurrency)

        # -- leg 1: goodput-vs-replica-count scaling curve ----------------
        scaling = []
        for k in range(1, n + 1):
            router = _fleet_router({r: urls[r] for r in list(urls)[:k]})
            threads, lat, rej, ret, t0 = run_clients(
                router.url, args.concurrency, per, f"scale{k}")
            for t in threads:
                t.join()
            wall = time.monotonic() - t0
            rec = router.stats.record()
            aff = router.pool.affinity_record()
            router.stop()
            entry = {
                "replicas": k,
                "concurrency": args.concurrency,
                "requests": per * args.concurrency,
                "goodput_rps": round(len(lat) / wall, 3) if wall else 0.0,
                "p50_ms": _pctl_ms(lat, 50),
                "p99_ms": _pctl_ms(lat, 99),
                "errors": len(rej),
                "client_retries": len(ret),
                "router_retries": rec["retries"],
                "failovers": rec["failovers"],
                "affinity_hit_rate": aff["hit_rate"],
            }
            scaling.append(entry)
            print(f"[fleet k={k}] {entry['goodput_rps']} req/s, p50 "
                  f"{entry['p50_ms']} / p99 {entry['p99_ms']} ms, "
                  f"affinity {entry['affinity_hit_rate']}",
                  file=sys.stderr)

        # -- leg 2: kill one replica under load ---------------------------
        router = _fleet_router(urls)
        completions: list = []
        kill_per = max(3, per)
        total = kill_per * args.concurrency
        threads, lat, rej, ret, t0 = run_clients(
            router.url, args.concurrency, kill_per, "kill", completions)
        # let the fleet warm (sessions homed, ~1/3 of traffic served) …
        while len(completions) < max(args.concurrency, total // 3):
            if time.monotonic() - t0 > 300:
                raise RuntimeError("kill leg warm phase stalled")
            time.sleep(0.02)
        aff_before = router.pool.affinity_record()
        # kill the replica that OWNS the first kill-stream's session —
        # a session-less victim would make the sticky-miss/remap
        # numbers vacuous
        victim = router.pool.ring.lookup("kill-0")
        procs[victim].kill()          # SIGKILL: abrupt death, no drain
        procs[victim].wait()
        t_kill = time.monotonic()
        print(f"[fleet] killed {victim} after {len(completions)}/{total} "
              f"requests", file=sys.stderr)
        while (router.pool.replicas[victim].state != "open"
               and time.monotonic() - t_kill < 60):
            time.sleep(0.02)
        detect_s = time.monotonic() - t_kill
        for t in threads:
            t.join()
        aff_end = router.pool.affinity_record()
        rec = router.stats.record()
        router.stop()

        succ = sorted(t for t, s in completions if s == 200)
        post = [t for t in succ if t >= t_kill]
        gaps = [b - a for a, b in zip(succ, succ[1:])]
        hits_d = aff_end["hits"] - aff_before["hits"]
        miss_d = aff_end["sticky_misses"] - aff_before["sticky_misses"]
        kill = {
            "killed": victim,
            "requests": total,
            "completed": len(succ),
            "errors": len(rej),
            "client_retries": len(ret),
            # breaker-open latency (the router stopped ASSIGNING to the
            # corpse this fast; individual requests failed over earlier
            # via the passive path)
            "detect_s": round(detect_s, 3),
            # first successful completion after the kill — the client-
            # visible service gap
            "recovery_s": (round(post[0] - t_kill, 3) if post else None),
            "max_gap_s": (round(max(gaps), 3) if gaps else None),
            "router_retries": rec["retries"],
            "failovers": rec["failovers"],
            "sticky_misses": aff_end["sticky_misses"],
            "affinity_hit_rate_before": aff_before["hit_rate"],
            "affinity_hit_rate_after": (
                round(hits_d / (hits_d + miss_d), 4)
                if hits_d + miss_d else None),
            "zero_dropped": len(rej) == 0,
        }
        print(f"[fleet kill] detect {kill['detect_s']}s, recovery "
              f"{kill['recovery_s']}s, {kill['errors']} errors / "
              f"{kill['client_retries']} client retries / "
              f"{kill['failovers']} failovers, affinity "
              f"{kill['affinity_hit_rate_before']} -> "
              f"{kill['affinity_hit_rate_after']}", file=sys.stderr)
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.terminate()
        for p in procs.values():
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()

    record = {
        "metric": "serve_fleet",
        "platform": platform,
        "variant": args.variant + ("-small" if args.small else ""),
        "iters": args.iters,
        "size": args.size,
        "batch": args.batch,
        "slo_ms": args.slo_ms,
        "max_queue": args.max_queue,
        "replicas": n,
        "concurrency": args.concurrency,
        "requests": args.requests,
        # the bench process never imports jax (replicas own the devices)
        # so it resolves for the platform the replicas run on: --cpu
        # forces cpu everywhere, otherwise the fleet is a TPU deployment
        "corr_impl_resolved": resolve_corr_impl(
            args.corr_impl, "cpu" if args.cpu else "tpu")[0],
        "scaling": scaling,
        "kill": kill,
        "goodput_scaling": (
            round(scaling[-1]["goodput_rps"] / scaling[0]["goodput_rps"],
                  3) if scaling[0]["goodput_rps"] else None),
    }
    assert set(record) == FLEET_RECORD_KEYS, \
        sorted(set(record) ^ FLEET_RECORD_KEYS)
    assert all(set(s) == FLEET_SCALING_KEYS for s in scaling)
    assert set(kill) == FLEET_KILL_KEYS, sorted(set(kill) ^ FLEET_KILL_KEYS)
    print(json.dumps(record), flush=True)


def main() -> int:
    """Parent: spawn the measurement child under the stall watchdog.
    No jax import on this side — a wedged backend can only hang the
    child, and the child gets killed."""
    import signal
    import threading

    stall_s = float(os.environ.get("SERVE_BENCH_STALL_S", STALL_S))
    hard_cap_s = float(os.environ.get("SERVE_BENCH_HARD_CAP_S", HARD_CAP_S))
    env = dict(os.environ, SERVE_BENCH_CHILD="1")
    child = subprocess.Popen([sys.executable, osp.abspath(__file__)]
                             + sys.argv[1:], env=env,
                             stdout=subprocess.PIPE, stderr=subprocess.PIPE)

    def _on_term(signum, frame):
        # the queue's outer `timeout` signals only the parent; forward
        # the kill so the measurement child is never orphaned holding a
        # device claim
        child.terminate()
        try:
            child.wait(timeout=10)
        except subprocess.TimeoutExpired:
            child.kill()
        sys.exit(128 + signum)

    for s in (signal.SIGTERM, signal.SIGINT):
        signal.signal(s, _on_term)

    last = [time.monotonic()]
    # shared watchdog stderr hygiene (bench.py): the XLA host-feature
    # warning goes to a side log once, never into the forwarded stderr —
    # the queue's recorded tail must end with the JSON metric line
    from bench import make_stderr_filter

    warn_filt = make_stderr_filter(tag="serve_bench")

    def pump(src, dst, is_stderr=False):
        for line in iter(src.readline, b""):
            last[0] = time.monotonic()
            if is_stderr:
                line = warn_filt(line)
                if line is None:
                    continue
            dst.buffer.write(line)
            dst.flush()

    threads = [
        threading.Thread(target=pump, args=(child.stdout, sys.stdout),
                         daemon=True),
        threading.Thread(target=pump, args=(child.stderr, sys.stderr, True),
                         daemon=True),
    ]
    for t in threads:
        t.start()
    t0 = time.monotonic()
    while True:
        rc = child.poll()
        if rc is not None:
            break
        time.sleep(min(2.0, stall_s / 4))
        now = time.monotonic()
        if now - last[0] > stall_s or now - t0 > hard_cap_s:
            why = (f"silent {now - last[0]:.0f}s (stalled)"
                   if now - last[0] > stall_s
                   else f"overran {hard_cap_s:.0f}s")
            print(f"[serve_bench] child stalled ({why}); killing",
                  file=sys.stderr)
            child.terminate()
            try:
                child.wait(timeout=15)
            except subprocess.TimeoutExpired:
                child.kill()
                child.wait()
            rc = 8
            break
    for t in threads:
        t.join(timeout=5)
    return rc


if __name__ == "__main__":
    if os.environ.get("SERVE_BENCH_CHILD"):
        if os.environ.get("SERVE_BENCH_FAKE_HANG"):
            print("fake child hanging", file=sys.stderr, flush=True)
            while True:
                time.sleep(3600)
        _args = build_parser().parse_args()
        if _args.fleet:
            # fleet mode never imports jax in this process (replicas
            # own the devices); --cpu is forwarded to them instead
            _measure_fleet(_args)
            sys.exit(0)
        if _args.cpu:
            import jax

            jax.config.update("jax_platforms", "cpu")
        (_measure_adaptive if _args.adaptive else
         _measure_closed_loop if _args.closed_loop else _measure)(_args)
        sys.exit(0)
    sys.exit(main())
