"""Persistent flow-service CLI: a restored checkpoint behind HTTP.

  python -m dexiraft_tpu serve --model checkpoints/raft-things \
      --variant v5 --port 8000 --batch_size 4 --bucket_multiple 64

One process = one worker: restore (verified, PR 4 fallback path) ->
jitted eval step -> InferenceEngine -> SLO Scheduler -> ThreadingHTTP
endpoint (serve/server.py). ``--workers N`` scales out: N stateless
worker processes bind ONE port via SO_REUSEPORT (the kernel balances
accepts) and share the persistent XLA compile cache, so workers 2..N
skip the multi-minute compile the first worker paid — relaunch-speed
scale-out, the PR 2 cache's serving payoff. Session warm-start is a
single-worker (or sticky-LB) feature: kernel accept-balancing has no
affinity, so ``--workers > 1`` forces ``--session_ttl_s 0`` (stateless
mode) unless an external sticky router fronts the pool
(docs/serving.md).

SIGTERM drains: admitted requests finish and flush before exit
(PR 4's preemption discipline, service-shaped); a second signal aborts.

Session carry is DEVICE-RESIDENT by default (the splat result never
leaves the chip; --host_carry restores the PR 6 host round-trip), and
single-worker session-enabled replicas also serve chained video through
``POST /v1/flow/stream`` — the split-encoder streaming engine
(serve/video.py) with a byte-budgeted device carry
(--stream_sessions_mb; docs/serving.md "Streaming").
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys

from dexiraft_tpu.chips import one_chip_env, refuse_more_than_chips
from dexiraft_tpu.config import CORR_IMPLS, VARIANTS
from dexiraft_tpu.serve.engine import ServeConfig, add_engine_args


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser("dexiraft-serve")
    p.add_argument("--model", default=None, help="orbax checkpoint dir "
                   "(restored via the verified-restore fallback path)")
    p.add_argument("--synthetic_init", action="store_true",
                   help="serve RANDOM-init weights instead of a "
                        "checkpoint — load/capacity benches and fleet "
                        "chaos tests exercise the full serving stack "
                        "without shipping a model around")
    p.add_argument("--variant", default="v1", choices=sorted(VARIANTS))
    p.add_argument("--small", action="store_true")
    p.add_argument("--mixed_precision", action="store_true")
    p.add_argument("--corr_impl", default="auto",
                   choices=["auto", *CORR_IMPLS],
                   help="'auto' (default) = the production config: "
                        "flash-blocked fused step on TPU (O(fmaps) "
                        "correlation memory at any geometry), allpairs "
                        "off-chip")
    p.add_argument("--corr_dtype", default="fp32",
                   choices=["fp32", "bf16", "int8"],
                   help="correlation-pyramid storage precision (bf16 "
                        "halves / int8 quarters per-request HBM traffic)")
    p.add_argument("--fused_update", action="store_true",
                   help="one fused Pallas lookup+update kernel per "
                        "refinement iteration (requires --corr_impl "
                        "flash)")
    p.add_argument("--scan_unroll", type=int, default=1)
    p.add_argument("--dexined_upconv", default="subpixel",
                   choices=["transpose", "subpixel"])
    p.add_argument("--iters", type=int, default=24,
                   help="refinement iterations per request (the budget "
                        "CAP with --adaptive)")
    p.add_argument("--adaptive", action="store_true",
                   help="adaptive-iteration inference: the refinement "
                        "while_loop freezes each item at convergence "
                        "(converge_tol) and the scheduler turns each "
                        "batch head's remaining SLO + queue pressure "
                        "into a per-dispatch iteration budget — "
                        "overload degrades refinement depth smoothly "
                        "before admission control sheds "
                        "(docs/serving.md \"Adaptive iterations\")")
    p.add_argument("--converge_tol", type=float, default=None,
                   help="override RAFTConfig.converge_tol (mean 1/8-res "
                        "flow-delta norm below which an item stops "
                        "refining; 0 disables the gate)")
    p.add_argument("--min_iters", type=int, default=4,
                   help="adaptive budget floor: no SLO/overload "
                        "pressure pushes a dispatch below this many "
                        "refinement iterations")
    p.add_argument("--mode", default="sintel", choices=["sintel", "kitti"],
                   help="pad placement for bucket padding")
    # engine knobs — the ONE shared surface with eval_cli/serve_bench
    # (ServeConfig.from_args); serving defaults raise batch + bucket
    # granule because bounded executables are the point of a service
    add_engine_args(p, batch_size=4, bucket_multiple=64)
    p.add_argument("--data_parallel", type=int, default=0,
                   help="shard each inference batch over this many chips "
                        "(0 = single chip); batch_size must divide by it")
    # service knobs
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--slo_ms", type=float, default=200.0,
                   help="per-request latency budget: a partial batch "
                        "dispatches when the oldest queued request's "
                        "budget (minus the bucket's learned service "
                        "time) runs out")
    p.add_argument("--max_queue", type=int, default=64,
                   help="queued-request admission bound; past it the "
                        "service sheds load with 503 instead of "
                        "stretching everyone's latency")
    p.add_argument("--session_ttl_s", type=float, default=60.0,
                   help="session warm-start TTL; 0 disables sessions "
                        "(stateless mode, forced when --workers > 1)")
    p.add_argument("--host_carry", action="store_true",
                   help="keep the PR 6 host-numpy session carry "
                        "(device_get per response + H2D per warm "
                        "request) instead of the device-resident "
                        "handoff; for pools/externally-restarted "
                        "workers that cannot share device state. "
                        "Implied by --workers > 1 and --data_parallel")
    p.add_argument("--stream_sessions_mb", type=float, default=256.0,
                   help="HBM byte budget for the streaming tier's "
                        "device-resident feature carries (POST "
                        "/v1/flow/stream; LRU-evicted past it, counted "
                        "in /stats). 0 disables the streaming endpoint")
    p.add_argument("--stream_chunk_frames", type=int, default=64,
                   help="max frames per /v1/flow/stream chunk (400 past "
                        "it): one chunk holds the streaming engine for "
                        "its whole frame loop, so the cap bounds how "
                        "long one request can starve other streams")
    p.add_argument("--request_timeout_s", type=float, default=60.0,
                   help="per-request server-side wait bound (504 past it)")
    p.add_argument("--workers", type=int, default=1,
                   help="worker processes sharing one SO_REUSEPORT port "
                        "and one persistent compile cache")
    p.add_argument("--warmup", default=None,
                   help="comma-separated HxW geometries to pre-compile "
                        "before accepting traffic (e.g. 440x1024,368x768)")
    p.add_argument("--no_compile_cache", action="store_true",
                   help="skip the persistent XLA cache (placed by "
                        "JAX_COMPILATION_CACHE_DIR, else a fixed path in "
                        "the checkout; workers share it for fast "
                        "scale-out)")
    p.add_argument("--strict", action="store_true",
                   help="PR 5 drift watch with teeth: a recompile on an "
                        "already-compiled bucket signature raises "
                        "instead of the one-line warning")
    p.add_argument("--cpu", action="store_true",
                   help="force the CPU backend (local shakeout)")
    p.add_argument("--reuse_port", action="store_true",
                   help=argparse.SUPPRESS)  # set by the --workers parent
    return p


# ---- multi-worker pool --------------------------------------------------


def _run_pool(args, argv) -> None:
    """Spawn N single-worker children on one SO_REUSEPORT port; forward
    SIGTERM/SIGINT so every child drains; exit with the worst child rc.
    Children are STATELESS (sessions off): kernel accept-balancing has
    no affinity, so carry state would be wrong half the time."""
    if args.port == 0:
        raise SystemExit("serve: --workers > 1 needs an explicit --port "
                         "(ephemeral port 0 would scatter the workers)")
    # appended flags override the parent's own --workers/--session_ttl_s
    # (argparse: the last occurrence of a store option wins)
    child_argv = list(argv) + ["--workers", "1", "--reuse_port",
                               "--session_ttl_s", "0"]
    refuse_more_than_chips(args.workers, "serve --workers")
    children = []
    for i in range(args.workers):
        # worker i owns chip i: a chip belongs to one process at a time
        env = dict(one_chip_env(i), DEXIRAFT_SERVE_WORKER=str(i))
        # own session: a foreground ^C delivers SIGINT to the whole
        # terminal process group, and _forward would deliver it AGAIN —
        # two signals is the children's abort gesture, not a drain.
        # Detached, every signal a child sees comes through _forward,
        # exactly once.
        children.append(subprocess.Popen(
            [sys.executable, "-m", "dexiraft_tpu", "serve"] + child_argv,
            env=env, start_new_session=True))
    print(f"[serve] pool: {args.workers} workers on "
          f"{args.host}:{args.port} (SO_REUSEPORT), shared compile cache, "
          f"stateless sessions", flush=True)

    def _forward(signum, frame):
        for c in children:
            try:
                c.send_signal(signum)
            except OSError:
                pass

    for s in (signal.SIGTERM, signal.SIGINT):
        signal.signal(s, _forward)
    rc = 0
    for c in children:
        try:
            rc = max(rc, abs(c.wait()))
        except KeyboardInterrupt:
            _forward(signal.SIGINT, None)
            rc = max(rc, abs(c.wait()))
    raise SystemExit(rc)


# ---- single worker ------------------------------------------------------


def _load(args):
    """Verified restore (PR 4): the newest checkpoint step that passes
    integrity checks serves; truncated/poisoned steps are skipped (and
    deleted) loudly instead of crashing the worker at boot.
    --synthetic_init skips the restore entirely (random weights): the
    fleet bench/chaos replicas measure the serving stack, not EPE."""
    import jax

    from dexiraft_tpu.config import TrainConfig
    from dexiraft_tpu.resilience import restore_verified
    from dexiraft_tpu.train import checkpoint as ckpt
    from dexiraft_tpu.train.state import create_state

    from dexiraft_tpu.config import resolve_corr_impl_args

    impl, fused = resolve_corr_impl_args(args, jax.devices()[0].platform,
                                         "serve")
    from dexiraft_tpu.profiling import device_banner

    device_banner("serve", corr_impl_arg=args.corr_impl, corr_impl=impl,
                  fused_update=fused)
    cfg = VARIANTS[args.variant](small=args.small,
                                 mixed_precision=args.mixed_precision,
                                 corr_impl=impl,
                                 corr_dtype=args.corr_dtype,
                                 fused_update=fused,
                                 dexined_upconv=args.dexined_upconv,
                                 scan_unroll=args.scan_unroll)
    if getattr(args, "converge_tol", None) is not None:
        import dataclasses

        # checkpoint-compatible: the gate threshold shapes no params
        cfg = dataclasses.replace(cfg, converge_tol=args.converge_tol)
    if args.synthetic_init:
        state = create_state(jax.random.PRNGKey(0), cfg, TrainConfig())
        print("[serve] synthetic init: serving RANDOM weights "
              "(bench/chaos mode — flow quality is meaningless)",
              flush=True)
        return cfg, state.variables
    try:
        ckpt.require_checkpoints(args.model)
    except FileNotFoundError as e:
        raise SystemExit(f"serve: {e}")
    template = create_state(jax.random.PRNGKey(0), cfg, TrainConfig())
    state, step = restore_verified(args.model, template)
    # the server never saves: release orbax's per-manager machinery now
    # instead of carrying it for the life of the process
    ckpt.close_managers()
    print(f"[serve] restored verified checkpoint step {step} from "
          f"{args.model}", flush=True)
    return cfg, state.variables


def _make_carry_fn(device: bool = True):
    """Session carry = the submission loop's splat: the previous frame's
    low-res flow forward-interpolated to the next frame's grid.

    device=True (the default): the splat result STAYS a device array —
    the store holds it, the engine stacks it into the next warm batch on
    device, and the carry path moves zero host<->device bytes per frame
    (engine.stats carry_h2d/d2h_bytes pin it). device=False keeps the
    PR 6 host round-trip (explicit device_get here, H2D on the next
    request) for deployments whose workers cannot share device state
    (--host_carry, pools, the data-parallel mesh path)."""
    import jax

    from dexiraft_tpu.eval.interpolate import forward_interpolate

    if device:
        return forward_interpolate
    return lambda flow_low: jax.device_get(forward_interpolate(flow_low))


def _warmup(engine, geometries, carry_fn=None, video=None) -> None:
    """Pre-compile the named buckets before the listener opens: the
    first real request on a cold bucket would otherwise eat the compile
    inside its latency budget. With sessions on, the engine always
    materializes flow_init (warm_start=True), so one signature per
    bucket covers cold AND warm traffic — and the carry splat
    (forward_interpolate, jitted per bucket shape) compiles here too,
    so --strict serving is compile-flat from the first request. With
    streaming enabled the video engine warms the same geometries (its
    encode/refine/splat signatures), extending the compile-flat
    guarantee to /v1/flow/stream."""
    import numpy as np

    for geom in geometries:
        h, w = (int(v) for v in geom.split("x"))
        item = {"image1": np.zeros((h, w, 3), np.float32),
                "image2": np.zeros((h, w, 3), np.float32)}
        (res,) = engine.run_batch([item])
        if carry_fn is not None:
            carry_fn(res.flow_low)
            engine.watch.mark_warm()  # expected compile, not drift
        if engine.config.adaptive:
            # the budget is a TRACED int32 scalar: a second dispatch at
            # a different explicit budget (plus the iters_used/delta
            # fetch it exercises) must ride the executable the first
            # dispatch compiled. check() turns any accidental budget
            # re-specialization into a boot-time error instead of a
            # first-request 500 under --strict.
            (res2,) = engine.run_batch([item], iter_budget=1)
            engine.watch.check()
            if res2.iters_used is None:
                raise RuntimeError(
                    "adaptive engine returned no iters_used during "
                    "warmup — eval_fn is not the adaptive 4-tuple "
                    "contract (make_eval_step(adaptive=True))")
    if video is not None:
        video.warmup(geometries)
    from dexiraft_tpu.analysis import guards

    # every named bucket has marked warm: what JAX spent on them
    print(guards.setup_line(), flush=True)
    engine.reset_stats()  # warmup is not traffic


def _make_video_engine(args, cfg, variables, mesh, sessions_on,
                       watch=None):
    """The streaming tier (serve/video.py), or None with a printed why.

    Streaming needs sessions (the carry IS the feature), a budget, a
    single-chip step (the chunk loop is batch-1 serially dependent —
    sharding one frame over a data mesh is the wrong axis), and a
    variant whose edges don't come from the dataset (v2/v3 without
    embed_dexined would need per-frame edge images on the wire)."""
    why = None
    if args.stream_sessions_mb <= 0:
        why = "--stream_sessions_mb 0"
    elif not sessions_on:
        why = "sessions off (the carry needs a session store)"
    elif mesh is not None:
        why = "--data_parallel (batch-1 chunks do not shard)"
    elif cfg.variant in ("early", "separate") and not cfg.embed_dexined:
        why = (f"variant {cfg.variant!r} needs data-supplied edge "
               "frames the stream wire format does not carry")
    if why is not None:
        print(f"[serve] streaming endpoint disabled: {why}", flush=True)
        return None

    import jax

    from dexiraft_tpu.eval.interpolate import forward_interpolate
    from dexiraft_tpu.serve.sessions import DeviceSessionStore
    from dexiraft_tpu.serve.video import VideoEngine
    from dexiraft_tpu.train.step import make_encode_step, make_refine_step

    import numpy as np

    encode_step = make_encode_step(cfg)
    adaptive = getattr(args, "adaptive", False)
    refine_step = make_refine_step(cfg, iters=args.iters,
                                   adaptive=adaptive)
    if adaptive:
        # streaming rides the FULL budget (chunks bypass the
        # scheduler's SLO policy); the convergence gate still exits
        # early per pair. One np.int32 aval = one executable per bucket.
        full = np.int32(args.iters)
        refine_fn = (lambda f1, f2, fi:
                     refine_step(variables, f1, f2, fi, full))
    else:
        refine_fn = lambda f1, f2, fi: refine_step(variables, f1, f2, fi)
    # the splat stays on device: flow_low (1, h/8, w/8, 2) -> the next
    # pair's seed, one jitted executable per bucket shape (warmup
    # absorbs the compile)
    splat = jax.jit(lambda low: forward_interpolate(low[0])[None])
    store = DeviceSessionStore(
        budget_bytes=int(args.stream_sessions_mb * 2**20),
        ttl_s=args.session_ttl_s,
        max_sessions=1024)
    return VideoEngine(
        lambda frame: encode_step(variables, frame),
        refine_fn,
        splat,
        sessions=store,
        put=jax.device_put,
        mode=args.mode,
        bucket_multiple=args.bucket_multiple,
        max_chunk_frames=args.stream_chunk_frames,
        adaptive=adaptive,
        strict=args.strict,
        # ONE RecompileWatch with the pair engine: the backend compile
        # counter is process-global, so a separate watch would let a
        # cold streaming bucket's expected compile read as drift to the
        # pair dispatcher's --strict check (and vice versa)
        watch=watch)


def _serve_one(args) -> None:
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    if args.strict:
        # --strict arms BOTH runtime sentinels: the recompile watch
        # (engine/video strict checks) and the lock-order runtime —
        # a rank inversion or ABBA cycle in the serve thread fabric
        # raises at the offending acquisition instead of warning
        from dexiraft_tpu.analysis import locks

        locks.set_strict(True)
    if not args.no_compile_cache:
        from dexiraft_tpu.profiling import enable_persistent_cache

        enable_persistent_cache()

    cfg, variables = _load(args)

    # one resident copy of the weights: the pair eval step and the
    # streaming encode/refine steps all close over THIS device tree
    # (device_put inside _make_eval_fn is a no-op on it)
    variables = jax.device_put(variables)

    from dexiraft_tpu.eval_cli import _make_eval_fn
    from dexiraft_tpu.serve import InferenceEngine
    from dexiraft_tpu.serve.server import FlowService

    eval_fn, mesh = _make_eval_fn(args, cfg, variables, args.iters)
    sessions_on = args.session_ttl_s > 0
    # device-resident carry is the default; the host round-trip stays
    # behind --host_carry (and is forced on the data-parallel mesh path,
    # whose pinned in_shardings re-lay the batch out host-side anyway)
    device_carry = sessions_on and not args.host_carry and mesh is None
    engine = InferenceEngine(
        eval_fn,
        ServeConfig.from_args(args, mode=args.mode, warm_start=sessions_on,
                              device_carry=device_carry),
        mesh=mesh)
    carry_fn = (_make_carry_fn(device=device_carry)
                if sessions_on else None)
    video = _make_video_engine(args, cfg, variables, mesh, sessions_on,
                               watch=engine.watch)
    if args.warmup:
        _warmup(engine, args.warmup.split(","), carry_fn, video)
        print(f"[serve] warmup: compiled "
              f"{engine.registry.compiles} signature(s)"
              f"{' (+streaming)' if video is not None else ''}",
              flush=True)

    service = FlowService(
        engine,
        host=args.host, port=args.port,
        slo_ms=args.slo_ms, max_queue=args.max_queue,
        # adaptive defaults from engine.config; the scheduler clamps
        # every SLO/overload budget to [min_iters, iters]
        max_iters=args.iters, min_iters=args.min_iters,
        session_ttl_s=args.session_ttl_s,
        carry_fn=carry_fn,
        request_timeout_s=args.request_timeout_s,
        reuse_port=args.reuse_port,
        video=video)
    service.install_signal_handlers()
    service.start()
    worker = os.environ.get("DEXIRAFT_SERVE_WORKER")
    tag = f" (worker {worker})" if worker is not None else ""
    print(f"[serve] listening on {service.url}{tag} — "
          f"batch_size={args.batch_size} slo_ms={args.slo_ms:g} "
          f"sessions={'on' if sessions_on else 'off'} "
          f"strict={'on' if args.strict else 'off'}"
          + (f" adaptive=on (tol={cfg.converge_tol:g}, "
             f"iters {args.min_iters}..{args.iters})"
             if args.adaptive else ""), flush=True)

    try:
        while not service.stopped.wait(1.0):
            pass
    except KeyboardInterrupt:
        # second signal (or bare ^C before the handler ran): best-effort
        # fast drain, then leave
        service.drain_and_stop(timeout=5.0)
    sched = service.scheduler.stats
    print(f"[serve] stopped after {service.uptime_s():.1f}s — "
          f"{sched.completed} served, {sched.rejected} shed, "
          f"{sched.failed} failed; {engine.stats.summary()}", flush=True)


def main(argv=None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    args = build_parser().parse_args(argv)
    if bool(args.model) == bool(args.synthetic_init):
        raise SystemExit("serve: exactly one of --model or "
                         "--synthetic_init is required")
    if args.workers < 1:
        raise SystemExit(f"serve: --workers must be >= 1, got "
                         f"{args.workers}")
    if args.workers > 1:
        if args.session_ttl_s > 0:
            # the PR 6 affinity gap, made loud: SO_REUSEPORT pools give
            # sessions no home — the kernel balances accepts blindly,
            # so a stream's warm carry lands on the wrong worker half
            # the time. The router (python -m dexiraft_tpu router) is
            # the sanctioned multi-replica path for session traffic.
            print("[serve] WARNING: --workers > 1 has NO session "
                  "affinity (SO_REUSEPORT accept-balancing is blind); "
                  "sessions are forced OFF in the pool. For warm-start "
                  "at scale, front single-worker replicas with "
                  "`python -m dexiraft_tpu router` (docs/serving.md "
                  "\"Fleet\").", flush=True)
        _run_pool(args, argv)
    else:
        _serve_one(args)


if __name__ == "__main__":
    main(sys.argv[1:])
