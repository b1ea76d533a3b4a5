"""Training-step throughput on the real chip — async pipeline edition.

Chairs-stage geometry (train_standard.sh: batch 10 crop 368x496 on 2
GPUs -> 5/GPU; here per-chip batch 6, iters 12) for the flagship v5.
The step is driven the way train_cli drives it: batches flow through
the device-side double-buffered prefetcher (data/prefetch.py), the
precision policy and gradient accumulation run inside the one jitted
step, and the persistent XLA compile cache (default logs/xla_cache/)
makes the second launch skip the compile entirely.

Emits ONE JSON record: steps/s, pixel-iters/s (the tokens/s analog:
batch*H*W*iters per second), prefetch-stall time (≈0 after warmup when
the host keeps ahead), whole-step FLOPs + MFU, and compile time (watch
it collapse on the second identical launch).

Compute sharding (`--compute_sharding halo` + `--seq N`): runs the
explicit shard_map spatial partitioning (parallel/halo.py) instead of
the GSPMD gather-fence step — rows shard over the mesh's seq axis with
ppermute halo exchange, params stay fsdp-sharded through compute via
per-block all-gather. The record gains memory_analysis columns
(argument/temp bytes per device) so the fence-vs-halo A/B shows the
activation and peak-params HBM win, and `--mem_only` emits the same
columns as a JSON record without executing. `--remat` selects the
rematerialization policy (none | dots_saveable | per_iter; TrainConfig
.remat) for both step modes.

Usage: python scripts/train_bench.py [--variant v1|v5] [--batch 6]
           [--accum 2] [--precision bf16] [--prefetch 2] [--steps 8]
           [--remat none|dots_saveable|per_iter] [--fsdp 2] [--seq 2]
           [--compute_sharding fence|halo] [--freeze_bn] [--mem_only]
           [--no_compile_cache] [--cpu]
"""

from __future__ import annotations

import argparse
import json
import os
import os.path as osp
import sys
import time

sys.path.insert(0, osp.dirname(osp.dirname(osp.abspath(__file__))))

# --host_devices N must take effect BEFORE jax's backend initializes
# (same dance as scripts/shard_audit.py): it forces N virtual host
# devices so the fsdp A/B runs on a laptop/CI box without a TPU.
for _i, _arg in enumerate(sys.argv):
    if _arg == "--host_devices" and _i + 1 < len(sys.argv):
        _n = sys.argv[_i + 1]
    elif _arg.startswith("--host_devices="):
        _n = _arg.split("=", 1)[1]
    else:
        continue
    _flags = os.environ.get("XLA_FLAGS", "")
    if _n.isdigit() and \
            "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + f" --xla_force_host_platform_device_count={_n}")
    break  # a malformed value falls through to argparse's own refusal

import jax
import jax.numpy as jnp
import numpy as np


def main():
    from dexiraft_tpu.config import CORR_IMPLS
    from dexiraft_tpu.train_cli import fsdp_arg

    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", default="v5")
    ap.add_argument("--batch", type=int, default=6,
                    help="TOTAL batch per step (= accum * microbatch)")
    ap.add_argument("--iters", type=int, default=12)
    ap.add_argument("--size", type=int, nargs=2, default=(368, 496))
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation microbatch count "
                         "(lax.scan inside the jitted step)")
    ap.add_argument("--precision", choices=["fp32", "bf16"], default="fp32",
                    help="bf16 = bf16 compute/activations, fp32 master "
                         "weights and optimizer")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="device-prefetch depth (2 = double buffering; "
                         "0 disables)")
    ap.add_argument("--steps", type=int, default=5,
                    help="timed steady-state steps")
    ap.add_argument("--remat", default="none",
                    choices=["none", "dots_saveable", "per_iter"],
                    help="rematerialization policy (TrainConfig.remat): "
                         "per_iter recomputes each RAFT iteration in the "
                         "backward (the old --remat flag), dots_saveable "
                         "keeps matmul/conv outputs but recomputes "
                         "elementwise chains")
    ap.add_argument("--remat_lookup", action="store_true")
    ap.add_argument("--corr_impl", default="allpairs",
                    choices=CORR_IMPLS)
    ap.add_argument("--corr_dtype", choices=["fp32", "bf16"], default="fp32",
                    help="correlation-pyramid storage precision (int8 is "
                         "inference-only, so not offered here)")
    ap.add_argument("--fused_update", action="store_true",
                    help="fused Pallas lookup+update step kernel "
                         "(requires --corr_impl flash)")
    ap.add_argument("--no_compile_cache", action="store_true",
                    help="skip the persistent compile cache (cold "
                         "compile every launch)")
    ap.add_argument("--mem_only", action="store_true",
                    help="compile-only: print the executable's "
                         "memory_analysis and exit WITHOUT executing. "
                         "This is how the no-remat OOM proof is "
                         "captured, at no allocation")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend (same as JAX_PLATFORMS=cpu)")
    ap.add_argument("--fsdp", default=None, type=fsdp_arg,
                    help="shard params + optimizer state over the "
                         "mesh's fsdp axis ('auto' or an integer; see "
                         "train --fsdp). Enables the mesh path: the "
                         "step runs with pinned state shardings and "
                         "the record's state_bytes_per_device shows "
                         "the storage win; 1 = replicated mesh "
                         "baseline for the A/B")
    ap.add_argument("--compute_sharding", default="fence",
                    choices=["fence", "halo"],
                    help="'fence' = GSPMD step with one-shot entry "
                         "all-gather of fsdp params; 'halo' = explicit "
                         "shard_map spatial partitioning over the seq "
                         "axis with per-conv halo exchange and per-block "
                         "param gather (needs --seq >= 2; v1/fp32 only, "
                         "see parallel/halo.check_halo_support)")
    ap.add_argument("--seq", type=int, default=None,
                    help="shard image rows N-way over a mesh 'seq' axis "
                         "(needs an explicit integer --fsdp; use "
                         "--fsdp 1 for seq-only). Height must divide by "
                         "8*N for --compute_sharding halo")
    ap.add_argument("--freeze_bn", action="store_true",
                    help="freeze BatchNorm stats (TrainConfig.freeze_bn "
                         "— post-chairs stages do; required by halo on "
                         "non-small variants)")
    ap.add_argument("--host_devices", type=int, default=None,
                    help="force N virtual host devices (CPU) so the "
                         "fsdp A/B runs without a TPU; must be the "
                         "first jax-visible setting, handled before "
                         "import")
    args = ap.parse_args()
    if args.fused_update and args.corr_impl != "flash":
        ap.error("--fused_update requires --corr_impl flash")
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    from dexiraft_tpu import config as C
    from dexiraft_tpu.config import TrainConfig
    from dexiraft_tpu.data.prefetch import prefetch_to_device
    from dexiraft_tpu.profiling import ThroughputReport, enable_persistent_cache
    from dexiraft_tpu.train.state import create_state
    from dexiraft_tpu.train.step import make_train_step

    # --fsdp enables the mesh path: state stored sharded between steps
    # (parallel/layout.state_sharding), gathered inside the step's
    # fences (or per block inside the halo body); --fsdp 1 is the
    # replicated-mesh baseline of the A/B. --seq adds the spatial axis
    # halo compute sharding partitions over.
    mesh = None
    if args.seq is not None and args.seq > 1:
        from dexiraft_tpu.parallel.layout import make_mesh_fsdp

        if not isinstance(args.fsdp, int):
            ap.error("--seq needs an explicit integer --fsdp "
                     "(--fsdp 1 for a (data, seq)-shaped budget)")
        budget = len(jax.devices()) // (args.fsdp * args.seq)
        if budget < 1:
            ap.error(f"mesh fsdp={args.fsdp} x seq={args.seq} needs "
                     f"{args.fsdp * args.seq} devices, have "
                     f"{len(jax.devices())} (pass --host_devices N)")
        n_data = max(n for n in range(1, budget + 1)
                     if args.batch % n == 0)
        mesh = make_mesh_fsdp(n_data, args.fsdp, args.seq)
        print(f"mesh: {dict(mesh.shape)}", file=sys.stderr)
    elif args.fsdp is not None:
        from dexiraft_tpu.parallel.layout import make_train_mesh

        mesh = make_train_mesh(args.batch, fsdp=args.fsdp)
        print(f"mesh: {dict(mesh.shape)}", file=sys.stderr)
    if args.compute_sharding == "halo" and (args.seq or 0) < 2:
        ap.error("--compute_sharding halo needs --seq >= 2 (the halo "
                 "step partitions rows over the mesh's seq axis)")

    cache_dir = None
    if not args.no_compile_cache:
        cache_dir = enable_persistent_cache()
        print(f"compile cache: {cache_dir}", file=sys.stderr)

    # model compute dtype follows the training-policy flag, so the
    # fp32-vs-bf16 A/B compares genuinely different programs (the step
    # forces mixed_precision=True itself when precision=bf16)
    cfg = getattr(C, f"raft_{args.variant}")(
        mixed_precision=args.precision == "bf16",
        remat_lookup=args.remat_lookup, corr_impl=args.corr_impl,
        corr_dtype=args.corr_dtype, fused_update=args.fused_update)
    h, w = args.size
    tc = TrainConfig(name="bench", num_steps=1000, batch_size=args.batch,
                     image_size=(h, w), iters=args.iters, lr=4e-4,
                     precision=args.precision, accum_steps=args.accum,
                     prefetch_depth=args.prefetch, remat=args.remat,
                     freeze_bn=args.freeze_bn)
    print(f"platform={jax.devices()[0].platform} variant={args.variant} "
          f"batch={args.batch} {h}x{w} iters={args.iters} "
          f"precision={args.precision} accum={args.accum} "
          f"prefetch={args.prefetch} remat={args.remat} "
          f"compute_sharding={args.compute_sharding}", file=sys.stderr)

    t0 = time.perf_counter()
    state = create_state(jax.random.PRNGKey(0), cfg, tc)
    if mesh is not None:
        from dexiraft_tpu.parallel.layout import shard_state

        state = shard_state(state, mesh)
    step_fn = make_train_step(cfg, tc, mesh=mesh,
                              compute_sharding=args.compute_sharding)
    init_s = time.perf_counter() - t0
    print(f"init {init_s:.1f}s", file=sys.stderr)

    def mem_fields(compiled_exe):
        """memory_analysis of the per-device compiled module — the HBM
        columns of the record. argument bytes carry the fsdp storage
        win (params arrive sharded), temp bytes carry the halo
        activation win (spatial slabs shard over seq) AND the per-block
        gather win (peak gathered params = one block, not the tree).
        Best-effort: absent on backends without the analysis."""
        try:
            mem = compiled_exe.memory_analysis()
        except Exception as e:
            print(f"memory_analysis unavailable: {e}", file=sys.stderr)
            return {}
        out = {}
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                out[attr.replace("_size_in_bytes",
                                 "_bytes_per_device")] = int(v)
        total = (out.get("argument_bytes_per_device", 0)
                 + out.get("output_bytes_per_device", 0)
                 + out.get("temp_bytes_per_device", 0)
                 - out.get("alias_bytes_per_device", 0))
        out["hbm_bytes_per_device"] = total
        return out

    def host_batches():
        # a PRE-DECODED pool, cycled: the real Loader hands over batches
        # its worker pool already decoded, so next() is instant — an
        # in-line rng.uniform per yield would charge synchronous numpy
        # time to the "prefetch stall" metric and muddy the acceptance
        # signal (any residual stall must be transfer-side)
        rng = np.random.default_rng(0)
        pool = [{
            "image1": rng.uniform(0, 255, (args.batch, h, w, 3))
            .astype(np.float32),
            "image2": rng.uniform(0, 255, (args.batch, h, w, 3))
            .astype(np.float32),
            "flow": rng.uniform(-5, 5, (args.batch, h, w, 2))
            .astype(np.float32),
            "valid": np.ones((args.batch, h, w), np.float32),
        } for _ in range(max(4, args.prefetch + 2))]
        i = 0
        while True:
            yield pool[i % len(pool)]
            i += 1

    if args.mem_only:
        # compile WITHOUT executing: the memory_analysis of the
        # executable is the OOM proof (requirements vs the chip limit)
        # with no allocation and so no OOM crash
        if mesh is not None:
            from dexiraft_tpu.parallel.layout import batch_putter

            batch = batch_putter(mesh)(next(host_batches()))
        else:
            batch = jax.tree.map(jnp.asarray, next(host_batches()))
        t0 = time.perf_counter()
        compiled = step_fn.lower(state, batch).compile()
        print(f"compile-only {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
        fields = mem_fields(compiled)
        for k, v in fields.items():
            print(f"{k}: {v / 2**30:.2f} GiB", file=sys.stderr)
        record = {
            "metric": f"train_step_memory@{h}x{w}",
            "platform": jax.devices()[0].platform,
            "variant": args.variant,
            "batch": args.batch,
            "iters": args.iters,
            "precision": args.precision,
            "remat": args.remat,
            "compute_sharding": args.compute_sharding,
            "mesh": dict(mesh.shape) if mesh is not None else None,
            **fields,
        }
        try:
            stats = jax.local_devices()[0].memory_stats() or {}
            limit = stats.get("bytes_limit")
            if limit:
                record["chip_bytes_limit"] = int(limit)
        except Exception:
            pass
        print(json.dumps(record), flush=True)
        return

    pf = prefetch_to_device(host_batches(), mesh, depth=args.prefetch)

    # split the one-time cost into its phases so the persistent cache's
    # effect is legible: tracing/lowering is Python (never cached), the
    # BACKEND compile is what the cache collapses to a deserialize on
    # the second identical launch. The AOT phase only exists to seed and
    # time the cache — without one, jit's own compile path could not
    # reuse the AOT executable and the backend compile would be paid
    # TWICE, so --no_compile_cache times the combined first call instead
    first = next(pf)
    lower_s = None
    compiled = None
    if cache_dir is not None:
        t0 = time.perf_counter()
        lowered = step_fn.lower(state, first)
        lower_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0
        print(f"trace+lower {lower_s:.1f}s, backend compile "
              f"{compile_s:.1f}s (a second identical launch collapses "
              f"the compile via the persistent cache)", file=sys.stderr)

    # warmup step (hits the persistent cache the AOT compile just wrote;
    # uncached mode compiles here, once)
    t0 = time.perf_counter()
    state, metrics = step_fn(state, first)
    # explicit scalar fetch = the sync (jaxlint JL007)
    float(jax.device_get(metrics["loss"]))
    first_step_s = time.perf_counter() - t0
    if cache_dir is None or compiled is None:
        compile_s = first_step_s  # compile + one step, combined
    print(f"first step (compile included if uncached) {first_step_s:.1f}s",
          file=sys.stderr)

    # steady state: the chips pull already-resident batches; the only
    # host work between dispatches is the async device_put enqueue
    pf.stats.reset()  # exclude warmup/compile from the record
    # steady-state contract (analysis/guards): the warmup step above
    # compiled the ONE donated step, so this loop must be compile-flat
    # and transfer-explicit — a retrace or implicit host transfer FAILS
    # the bench instead of silently deflating steps/s. The prefetcher's
    # puts are explicit device_puts (and thread-local anyway); the one
    # loss fetch below is an explicit device_get — both pass.
    from dexiraft_tpu.analysis import guards

    with guards.strict_mode(label="train_bench"):
        t0 = time.perf_counter()
        for _ in range(args.steps):
            state, metrics = step_fn(state, next(pf))
        # ONE sync at the END: steps overlap transfers (jaxlint JL007)
        float(jax.device_get(metrics["loss"]))
        dt = (time.perf_counter() - t0) / args.steps
    print(f"steady-state {dt * 1e3:.1f} ms/step  "
          f"{1.0 / dt:.2f} steps/s  "
          f"{args.batch * args.iters / dt:.1f} pair-iters/s  "
          f"prefetch: {pf.stats.summary()}")

    # whole-train-step FLOPs from XLA's cost analysis of the compiled
    # executable, and MFU against the chip's bf16 peak. The AOT
    # lower().compile() hits the persistent disk cache, not the
    # in-memory jit cache. A utilisation is printed only on a TPU,
    # against that device_kind's peak — an unknown kind is an error
    # there, not a default.
    from bench import _counted_flops, chip_peak_bf16_flops

    flops = _counted_flops(step_fn, state, first)
    peak = (chip_peak_bf16_flops(jax.devices()[0].device_kind)
            if jax.devices()[0].platform == "tpu" else None)

    # persistent state footprint per device — params + opt_state as the
    # COMPILED step holds them between steps (its input shardings; the
    # live arrays' own shardings when the AOT executable was skipped).
    # This is the fsdp storage win in the record schema: on an fsdp=N
    # mesh it drops toward 1/N of the replicated figure, and it is
    # exact, not sampled — shard_shape of every leaf.
    def state_bytes_per_device() -> int:
        from jax.tree_util import tree_flatten_with_path

        sh_tree = None
        if compiled is not None:
            try:
                sh_tree = compiled.input_shardings[0][0]
            except Exception:
                sh_tree = None
        flat_state = tree_flatten_with_path(state)[0]
        flat_sh = (tree_flatten_with_path(sh_tree)[0]
                   if sh_tree is not None else None)
        total = 0
        for i, (path, leaf) in enumerate(flat_state):
            if getattr(path[0], "name", None) not in ("params",
                                                      "opt_state"):
                continue
            shape = np.shape(leaf)
            sharding = (flat_sh[i][1] if flat_sh is not None
                        else getattr(leaf, "sharding", None))
            if sharding is not None:
                shape = sharding.shard_shape(tuple(shape))
            total += (int(np.prod(shape, dtype=np.int64))
                      * np.dtype(leaf.dtype).itemsize)
        return total

    report = ThroughputReport(batch=args.batch, height=h, width=w,
                              iters=args.iters)
    record = {
        "metric": f"train_steps_per_sec@{h}x{w}",
        "platform": jax.devices()[0].platform,
        "variant": args.variant,
        "batch": args.batch,
        "iters": args.iters,
        "precision": args.precision,
        "accum_steps": args.accum,
        "prefetch_depth": args.prefetch,
        "remat": args.remat,
        "compute_sharding": args.compute_sharding,
        "loss": round(float(jax.device_get(metrics["loss"])), 6),
        # backend compile when cached (AOT-timed); compile+first-step
        # combined when --no_compile_cache
        "compile_s": round(compile_s, 2),
        **({"trace_lower_s": round(lower_s, 2)} if lower_s is not None
           else {}),
        "compile_cache_dir": cache_dir,
        "prefetch_stall_ms_per_step": round(
            pf.stats.stall_per_batch_s * 1e3, 3),
        "prefetch_stalled_steps": pf.stats.stalls,
        "mesh": dict(mesh.shape) if mesh is not None else None,
        "state_bytes_per_device": state_bytes_per_device(),
        # HBM columns (when the AOT executable exists — the cached
        # path; uncached runs get them from --mem_only instead)
        **(mem_fields(compiled) if compiled is not None else {}),
        **report.fields(dt, flops, peak),
    }
    if flops and peak is None:
        record["mfu"] = None  # a CPU run has no utilisation to print

    # peak HBM: the VERDICT training-record ask is steps/s AND memory
    # headroom at this geometry. memory_stats() is backend-dependent —
    # the CPU backend reports none — so report best-effort.
    try:
        stats = jax.local_devices()[0].memory_stats() or {}
        hbm = stats.get("peak_bytes_in_use")
        if hbm is not None:
            record["peak_hbm_gib"] = round(hbm / 2**30, 2)
            limit = stats.get("bytes_limit")
            if limit:
                record["hbm_limit_gib"] = round(limit / 2**30, 2)
    except Exception as e:
        print(f"memory_stats unavailable: {e}", file=sys.stderr)

    print(json.dumps(record), flush=True)


if __name__ == "__main__":
    main()
