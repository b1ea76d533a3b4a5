"""Evaluation / submission CLI (reference: evaluate.py:212-243).

  python -m dexiraft_tpu eval --model checkpoints/raft-things \
      --dataset sintel --variant v5
  python -m dexiraft_tpu eval --model ... --submission sintel --warm_start
"""

from __future__ import annotations

import argparse
import sys

import jax

from dexiraft_tpu.config import CORR_IMPLS
from dexiraft_tpu.train_cli import VARIANTS, _VAL_ITERS


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser("dexiraft-eval")
    p.add_argument("--model", required=True, help="orbax checkpoint dir")
    p.add_argument("--dataset",
                   choices=["chairs", "sintel", "kitti", "hd1k", "edgesum"],
                   help="'edgesum' = the v1-lineage summed-fusion "
                        "validation (alt/evaluate_1.py): chairs val pairs "
                        "+ their edge images from --edge_root, per-iter "
                        "flows of both passes summed before EPE")
    p.add_argument("--edge_root", default=None,
                   help="parallel tree of edge-map PNGs (for "
                        "--dataset edgesum)")
    p.add_argument("--submission", choices=["sintel", "kitti"])
    p.add_argument("--warm_start", action="store_true")
    p.add_argument("--variant", default="v1", choices=sorted(VARIANTS))
    p.add_argument("--small", action="store_true")
    p.add_argument("--mixed_precision", action="store_true")
    p.add_argument("--corr_impl", default="auto",
                   choices=["auto", *CORR_IMPLS],
                   help="'local'/'flash' = the memory-efficient "
                        "on-demand paths (the reference's "
                        "--alternate_corr); 'auto' (default) = the "
                        "production config: flash-blocked fused step on "
                        "TPU, allpairs off-chip (Pallas kernels only run "
                        "off-TPU in debug-speed interpreter mode)")
    p.add_argument("--corr_dtype", default="fp32",
                   choices=["fp32", "bf16", "int8"],
                   help="storage precision of the correlation pyramid "
                        "(bf16 halves / int8 quarters the refinement "
                        "loop's HBM traffic; docs/perf.md has the "
                        "accuracy bounds)")
    p.add_argument("--fused_update", action="store_true",
                   help="fuse lookup + motion-encoder corr conv into one "
                        "Pallas kernel per iteration (requires "
                        "--corr_impl flash; same checkpoints)")
    p.add_argument("--scan_unroll", type=int, default=1,
                   help="refinement-scan unroll factor (XLA pipelining "
                        "knob; numerically identical)")
    p.add_argument("--dexined_upconv", default="subpixel",
                   choices=["transpose", "subpixel"],
                   help="embedded-DexiNed upsampler implementation "
                        "(numerically identical; see docs/perf.md)")
    p.add_argument("--iters", type=int, default=None)
    p.add_argument("--adaptive", action="store_true",
                   help="convergence-gated adaptive inference: the "
                        "refinement runs a while_loop that freezes each "
                        "item once its flow-delta norm drops below "
                        "converge_tol (docs/serving.md \"Adaptive "
                        "iterations\"); --iters becomes the budget CAP")
    p.add_argument("--converge_tol", type=float, default=None,
                   help="override RAFTConfig.converge_tol (mean 1/8-res "
                        "flow-delta norm below which an item stops "
                        "refining; 0 disables the gate — bit-exact "
                        "fixed-iteration parity)")
    p.add_argument("--adaptive_iters", default=None,
                   help="comma-separated iteration budgets (e.g. "
                        "4,8,16,32): runs the fixed baseline at --iters "
                        "plus the adaptive driver at each budget and "
                        "emits ONE EPE-vs-latency frontier JSON record "
                        "(docs/perf.md \"Adaptive-iteration frontier\")")
    p.add_argument("--frontier_out", default=None,
                   help="also write the --adaptive_iters frontier "
                        "record to this path")
    p.add_argument("--output", default=None, help="submission output dir")
    # engine knobs via the ONE shared surface (serve.engine
    # add_engine_args / ServeConfig.from_args) so the batch-eval and
    # persistent-service (serve_cli) batching paths cannot drift; eval
    # keeps batch_size=1 / reference pad shapes (the metric-parity
    # defaults)
    from dexiraft_tpu.serve.engine import add_engine_args

    add_engine_args(p, batch_size=1, bucket_multiple=None)
    p.add_argument("--serve", action="store_true",
                   help="route through the inference engine even at "
                        "batch_size 1 (async in-flight dispatch, bucket "
                        "accounting)")
    p.add_argument("--data_parallel", type=int, default=0,
                   help="shard each inference batch over this many "
                        "chips (0 = single chip); batch_size must "
                        "divide by it")
    # runtime guard mode (analysis/guards.py, docs/static_analysis.md)
    p.add_argument("--strict", action="store_true",
                   help="run evaluation inside guards.strict_mode: "
                        "implicit host<->device transfers raise and any "
                        "recompile beyond the expected one-per-geometry "
                        "warmup fails the run")
    return p


def load_variables(args):
    from dexiraft_tpu.config import TrainConfig
    from dexiraft_tpu.train import checkpoint as ckpt
    from dexiraft_tpu.train.state import create_state

    # a missing/empty --model dir is an operator typo, not a program
    # bug: fail as ONE actionable line (path + nearest candidate dirs)
    # instead of the orbax traceback it used to produce
    try:
        ckpt.require_checkpoints(args.model)
    except FileNotFoundError as e:
        raise SystemExit(f"eval: {e}")
    from dexiraft_tpu.config import resolve_corr_impl_args

    impl, fused = resolve_corr_impl_args(args, jax.devices()[0].platform,
                                         "eval")
    from dexiraft_tpu.profiling import device_banner

    device_banner("eval", corr_impl_arg=args.corr_impl, corr_impl=impl,
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

        # checkpoint-compatible: the gate threshold shapes no params,
        # only the adaptive driver's exit condition
        cfg = dataclasses.replace(cfg, converge_tol=args.converge_tol)
    template = create_state(jax.random.PRNGKey(0), cfg, TrainConfig())
    state = ckpt.restore_checkpoint(args.model, template)
    return cfg, state.variables


def _edgesum_dataset(edge_root: str):
    """Chairs validation pairs + their edge images from a parallel tree —
    the data side of the v1-lineage summed-fusion validation
    (alt/evaluate_1.py). Uses the same path-mapping convention as
    training-side edge pairing (data.datasets.wrap_with_edge_tree)."""
    from dexiraft_tpu.data.datasets import FlyingChairs, wrap_with_edge_tree

    return wrap_with_edge_tree(FlyingChairs(None, split="validation"),
                               edge_root)


def _serving(args) -> bool:
    return args.serve or args.batch_size > 1 or args.data_parallel > 0


def _make_eval_fn(args, cfg, variables, iters, adaptive=None):
    """Uniform eval-fn: (im1, im2, flow_init) — POSITIONAL-safe for the
    engine (the mesh path pins in_shardings, which rejects kwargs) and
    kwarg-friendly for the per-image loops. Sintel and KITTI now share
    one signature: flow_init=None is always accepted (the KITTI model
    simply never receives a warm start).

    Adaptive (default: args.adaptive) grows the trailing ``iter_budget``
    and the 4-tuple return (the ADAPTIVE engine contract in
    serve/engine.py): a ``None`` budget resolves to the full ``iters``
    HERE, normalized to the same np.int32 aval the engine's scheduler
    dispatches use — every budget value rides ONE traced scalar, so one
    executable per bucket serves them all."""
    import numpy as np

    from dexiraft_tpu.train.step import make_eval_step

    if adaptive is None:
        adaptive = getattr(args, "adaptive", False)
    mesh = None
    if args.data_parallel > 0:
        from dexiraft_tpu.parallel.layout import make_serve_mesh, replicate

        mesh = make_serve_mesh(args.data_parallel)
        # replicate once up front — the pinned replicated in_sharding
        # would otherwise re-transfer the params on every dispatch
        variables = replicate(variables, mesh)
    step = make_eval_step(cfg, iters=iters, mesh=mesh, adaptive=adaptive)
    if mesh is None:
        # explicit H2D put (jaxlint/guards): callers hand numpy frames;
        # device_put keeps the transfer visible and legal under the
        # strict transfer guard (the put is async — dispatch overlap is
        # preserved). Variables go up ONCE here — restored checkpoints
        # are host numpy, and re-transferring them per call would be an
        # implicit (guard-tripping) put on every frame.
        variables = jax.device_put(variables)
        put = jax.device_put
        if adaptive:
            return (lambda im1, im2, flow_init=None, iter_budget=None:
                    step(variables, put(im1), put(im2),
                         flow_init=(None if flow_init is None
                                    else put(flow_init)),
                         iter_budget=np.int32(
                             iters if iter_budget is None
                             else iter_budget))), None
        return (lambda im1, im2, flow_init=None:
                step(variables, put(im1), put(im2),
                     flow_init=(None if flow_init is None
                                else put(flow_init)))), None
    if adaptive:
        return (lambda im1, im2, flow_init=None, iter_budget=None:
                step(variables, im1, im2, None, None, flow_init,
                     np.int32(iters if iter_budget is None
                              else iter_budget))), mesh
    return (lambda im1, im2, flow_init=None:
            step(variables, im1, im2, None, None, flow_init)), mesh


# ---- adaptive frontier record schema, pinned by
# tests/test_zzzadaptive.py -----------------------------------------------
FRONTIER_RECORD_KEYS = {
    "record", "dataset", "iters", "converge_tol", "fixed", "sweep",
}
# every sweep leg carries the dataset's metric keys plus these
FRONTIER_LEG_KEYS = {
    "budget", "wall_s", "mean_iters_used", "p99_iters_used",
    "mean_final_delta",
}


def _adaptive_pair_view(eval_fn, sink=None):
    """Adapt the adaptive 4-tuple eval fn to the (flow_low, flow_up)
    contract of the per-image loops (eval.validate/_run unpacks exactly
    two). iters_used/final_delta land in ``sink`` (a list of per-call
    (iters_used, final_delta) host arrays) when one is given."""

    def fn(im1, im2, flow_init=None):
        flow_low, flow_up, iters_used, final_delta = eval_fn(
            im1, im2, flow_init)
        if sink is not None:
            # explicit D2H (jaxlint JL007) — (B,) scalars per call
            sink.append((jax.device_get(iters_used),
                         jax.device_get(final_delta)))
        return flow_low, flow_up

    return fn


def _sink_summary(sink) -> dict:
    import numpy as np

    used = np.concatenate([np.atleast_1d(iu) for iu, _ in sink])
    deltas = np.concatenate([np.atleast_1d(fd) for _, fd in sink])
    return {
        "mean_iters_used": round(float(used.mean()), 2),
        "p99_iters_used": round(float(np.percentile(used, 99)), 2),
        "mean_final_delta": round(float(deltas.mean()), 6),
    }


def _make_engine(args, eval_fn, mesh, mode, warm_start=False, watch=None):
    from dexiraft_tpu.serve import InferenceEngine, ServeConfig

    engine = InferenceEngine(
        eval_fn,
        ServeConfig.from_args(args, mode=mode, warm_start=warm_start),
        mesh=mesh)
    if watch is not None:
        # share the CLI's strict_mode watch: the engine's expected
        # bucket compiles re-baseline it, so the region's exit check
        # only fires on genuinely unplanned recompiles
        engine.watch = watch
    return engine


_setup_reported = False


def _report_setup() -> None:
    """The `[setup]` line (what JAX spent before warm, by jitted
    program), once a process: at the first geometry's mark_warm."""
    global _setup_reported
    if not _setup_reported:
        from dexiraft_tpu.analysis import guards

        _setup_reported = True
        print(guards.setup_line(), flush=True)


def _strict_wrap(eval_fn, watch):
    """Per-geometry compile absorption for the per-image eval loops.

    The first call on a new input-shape signature is an EXPECTED compile
    (re-baselines the watch); a repeat signature must ride the compiled
    executable — if it compiled anyway, that is shape/dtype drift and
    the watch raises.
    """
    import numpy as np

    seen = set()

    def wrapped(im1, im2, flow_init=None):
        sig = (np.shape(im1), np.shape(im2),
               None if flow_init is None else np.shape(flow_init))
        out = eval_fn(im1, im2, flow_init=flow_init)
        if sig in seen:
            watch.check()
        else:
            seen.add(sig)
            watch.mark_warm()
            _report_setup()
        return out

    return wrapped


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    if not args.dataset and not args.submission:
        raise SystemExit("need --dataset or --submission")
    if args.data_parallel and args.batch_size % max(args.data_parallel, 1):
        raise SystemExit(f"--batch_size {args.batch_size} must divide by "
                         f"--data_parallel {args.data_parallel}")

    cfg, variables = load_variables(args)

    import contextlib

    region = contextlib.ExitStack()
    watch = None
    if args.strict:
        from dexiraft_tpu.analysis import guards

        # ONE strict region over every eval/submission below: implicit
        # host<->device transfers raise at the offending call, and the
        # region's exit check fails the run on any compile the
        # per-geometry absorption (_strict_wrap / the engine's
        # mark_warm) did not expect. docs/static_analysis.md.
        # The data-parallel path keeps the pinned in_shardings' own
        # transfer semantics (the jitted step ingests host numpy frames
        # by design — same carve-out as serve_bench), so only the
        # recompile sentinel is armed there.
        watch = region.enter_context(guards.strict_mode(
            label="eval",
            transfer="allow" if args.data_parallel else "disallow"))

    with region:
        _run_eval(args, cfg, variables, watch)


def _run_eval(args, cfg, variables, watch) -> None:
    if args.adaptive_iters:
        _adaptive_sweep(args, cfg, variables)
        return
    if args.dataset:
        from dexiraft_tpu.eval.validate import run_validation

        dataset = None
        if args.dataset == "edgesum":
            if not args.edge_root:
                raise SystemExit("--dataset edgesum needs --edge_root")
            dataset = _edgesum_dataset(args.edge_root)

        iters = args.iters or _VAL_ITERS.get(args.dataset, 24)
        eval_fn, mesh = _make_eval_fn(args, cfg, variables, iters)
        engine = None
        sink: list = []
        if _serving(args):
            mode = "kitti" if args.dataset in ("kitti", "hd1k") else "sintel"
            engine = _make_engine(args, eval_fn, mesh, mode, watch=watch)
        else:
            if args.adaptive:
                eval_fn = _adaptive_pair_view(eval_fn, sink)
            if watch is not None:
                eval_fn = _strict_wrap(eval_fn, watch)
        run_validation(args.dataset, eval_fn, dataset,
                       batch_size=args.batch_size, engine=engine)
        if engine is not None:
            print(f"engine: {engine.stats.summary()}")
            if engine.config.adaptive:
                print(f"adaptive: mean iters_used "
                      f"{engine.stats.iters_used_mean():.1f} / "
                      f"p99 {engine.stats.iters_used_pctl(99):.0f} "
                      f"(budget {iters})")
        elif sink:
            s = _sink_summary(sink)
            print(f"adaptive: mean iters_used {s['mean_iters_used']} / "
                  f"p99 {s['p99_iters_used']} (budget {iters}), "
                  f"mean final delta {s['mean_final_delta']}")

    if args.submission == "sintel":
        from dexiraft_tpu.eval.submission import create_sintel_submission

        eval_fn, mesh = _make_eval_fn(args, cfg, variables, args.iters or 32)
        engine = (_make_engine(args, eval_fn, mesh, "sintel",
                               warm_start=args.warm_start, watch=watch)
                  if _serving(args) else None)
        if engine is None and args.adaptive:
            eval_fn = _adaptive_pair_view(eval_fn)
        if engine is None and watch is not None:
            eval_fn = _strict_wrap(eval_fn, watch)
        create_sintel_submission(
            eval_fn,
            output_path=args.output or "sintel_submission",
            warm_start=args.warm_start,
            batch_size=args.batch_size,
            engine=engine)
    elif args.submission == "kitti":
        from dexiraft_tpu.eval.submission import create_kitti_submission

        eval_fn, mesh = _make_eval_fn(args, cfg, variables, args.iters or 24)
        engine = (_make_engine(args, eval_fn, mesh, "kitti", watch=watch)
                  if _serving(args) else None)
        if engine is None and args.adaptive:
            eval_fn = _adaptive_pair_view(eval_fn)
        if engine is None and watch is not None:
            eval_fn = _strict_wrap(eval_fn, watch)
        create_kitti_submission(
            eval_fn,
            output_path=args.output or "kitti_submission",
            batch_size=args.batch_size,
            engine=engine)


def _adaptive_sweep(args, cfg, variables) -> None:
    """The EPE-vs-latency frontier protocol (docs/perf.md): ONE fixed
    baseline at --iters plus the adaptive driver at each budget in
    --adaptive_iters, all over the same dataset in the same process.
    Emits one self-describing JSON record (stdout, and --frontier_out).

    Per-image loop on purpose (no engine/batching): the legs differ
    only in the refinement driver, so their wall-clocks are directly
    comparable and the per-item iters_used samples are exact.
    """
    import json
    import time

    from dexiraft_tpu.eval.validate import run_validation

    if not args.dataset:
        raise SystemExit("--adaptive_iters needs --dataset")
    budgets = [int(tok) for tok in args.adaptive_iters.split(",")
               if tok.strip()]
    if not budgets:
        raise SystemExit(f"--adaptive_iters parsed to no budgets: "
                         f"{args.adaptive_iters!r}")
    dataset = None
    if args.dataset == "edgesum":
        if not args.edge_root:
            raise SystemExit("--dataset edgesum needs --edge_root")
        dataset = _edgesum_dataset(args.edge_root)
    iters = args.iters or _VAL_ITERS.get(args.dataset, 24)

    fixed_fn, _ = _make_eval_fn(args, cfg, variables, iters,
                                adaptive=False)
    t0 = time.perf_counter()
    fixed_metrics = run_validation(args.dataset, fixed_fn, dataset)
    fixed_wall = time.perf_counter() - t0

    adaptive_fn, _ = _make_eval_fn(args, cfg, variables, iters,
                                   adaptive=True)
    record = {
        "record": "adaptive_frontier",
        "dataset": args.dataset,
        "iters": iters,
        "converge_tol": cfg.converge_tol,
        "fixed": {**fixed_metrics, "wall_s": round(fixed_wall, 2)},
        "sweep": [],
    }
    for budget in budgets:
        sink: list = []
        fn = _adaptive_pair_view(
            lambda im1, im2, flow_init=None, _b=budget:
            adaptive_fn(im1, im2, flow_init, _b), sink)
        t0 = time.perf_counter()
        metrics = run_validation(args.dataset, fn, dataset)
        wall = time.perf_counter() - t0
        leg = {**metrics, "budget": budget, "wall_s": round(wall, 2)}
        if sink:
            leg.update(_sink_summary(sink))
        # the frontier's decision metric: quality cost of THIS budget
        # relative to the fixed anchor, per dataset key
        for k, v in fixed_metrics.items():
            if isinstance(v, float) and k in metrics:
                leg[f"{k}_delta"] = round(metrics[k] - v, 4)
        assert FRONTIER_LEG_KEYS <= set(leg), \
            sorted(FRONTIER_LEG_KEYS - set(leg))
        record["sweep"].append(leg)
    assert set(record) == FRONTIER_RECORD_KEYS, \
        sorted(set(record) ^ FRONTIER_RECORD_KEYS)
    line = json.dumps(record)
    print(line)
    if args.frontier_out:
        with open(args.frontier_out, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main(sys.argv[1:])
