"""Benchmark harness — the north-star metric.

Measures refinement iters/sec/chip for the flagship v5 Dexi-RAFT at the
Sintel eval resolution 436x1024 (padded to 440x1024, InputPadder contract),
test-mode forward with 32 refinement iterations — the configuration of
BASELINE.json ("refinement iters/sec/chip at 436x1024") and of
validate_sintel in the reference (evaluate.py:102-133, iters=32).

The reference records NO throughput numbers (BASELINE.md); vs_baseline is
computed against an estimated 320 refinement iters/sec for the reference's
CUDA path on a single modern GPU (upstream RAFT reports ~10 FPS at
1024x436 with 32 iters; 10*32=320). That estimate is carried in
BASELINE_ITERS_PER_SEC below and flagged as `baseline_kind: "estimate"`
in the JSON so the record is self-describing.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...},
which always names the `platform` and `device_kind` it ran on. It is a
device measurement: when JAX finds no TPU it exits non-zero, and a sweep
leg that fails fails the run. The one other mode is a rehearsal the
CALLER asks for with JAX_PLATFORMS=cpu — a small geometry that proves
the control flow in about a minute, labelled `platform: "cpu",
rehearsal: true`, never a device number.
"""

from __future__ import annotations

import json
import sys
import time

BASELINE_ITERS_PER_SEC = 320.0
ITERS = 32

# ---- record schema pin (tests/test_bench_watchdog.py) -------------------
# Top-level keys every bench record MUST carry. The per-config diagnostic
# keys are prefixed (e.g. "allpairs_forward_ms", "flash_int8_forward_flops") and
# open-ended; the conditional keys below appear only in the situations
# their comments in main() describe.
BENCH_RECORD_KEYS = frozenset({
    "metric", "value", "unit", "vs_baseline", "platform", "rehearsal",
    "baseline_kind", "baseline_iters_per_sec", "device_kind", "iters",
    "corr_impl", "corr_impl_resolved", "corr_dtype", "fused_update",
    "dexined_upconv",
    "loop_only_iters_per_sec", "loop_only_vs_whole_forward_baseline",
    "allpairs_iters_per_sec", "local_corr_iters_per_sec",
    "flash_corr_iters_per_sec",
})
BENCH_RECORD_OPTIONAL_KEYS = frozenset({
    "cpu_anchor_flax_over_torch", "cpu_anchor_flax_over_torch_train",
    "cpu_anchor_source", "forward_flops", "mfu",
    "chip_peak_bf16_flops",
})
# every sweep leg's diagnostics land under its tag prefix
BENCH_DIAG_PREFIXES = ("allpairs", "local", "flash")


def validate_record(rec: dict) -> None:
    """Schema gate for the ONE JSON line the driver greps: all required
    keys present; nothing outside required + optional + tag-prefixed
    diagnostics. Raises ValueError so a drifted record fails the run
    instead of silently changing shape under its readers."""
    missing = BENCH_RECORD_KEYS - set(rec)
    if missing:
        raise ValueError(f"bench record missing keys: {sorted(missing)}")
    for key in set(rec) - BENCH_RECORD_KEYS - BENCH_RECORD_OPTIONAL_KEYS:
        if not any(key.startswith(p + "_") for p in BENCH_DIAG_PREFIXES):
            raise ValueError(f"bench record carries unpinned key {key!r}; "
                             "extend BENCH_RECORD_KEYS (and the schema "
                             "test) deliberately, not by accident")

# ---- stderr hygiene (shared with scripts/serve_bench.py) ----------------
# On hosts whose CPU lacks features the wheels were built for, XLA prints
# a warning line carrying this marker. Forwarded verbatim by the watchdog
# pumps it buries the JSON metric line at the end of the output. The
# filter diverts it: first occurrence goes verbatim to a side log and is
# replaced by a one-line note; repeats are dropped.
XLA_HOST_WARNING_MARKER = b"This could lead to execution errors such as SIGILL"


def make_stderr_filter(log_path=None, tag="bench"):
    """Line filter for a watchdog stderr pump: returns fn(line: bytes)
    -> bytes | None. Lines carrying XLA_HOST_WARNING_MARKER are diverted
    — the first is appended verbatim to ``log_path`` (default
    $BENCH_XLA_WARN_LOG or /tmp/xla_host_warning.log) and replaced with
    a short note; later ones return None (drop). Everything else passes
    through untouched, so the forwarded stream still ends with the
    record's JSON line."""
    import os

    path = log_path or os.environ.get("BENCH_XLA_WARN_LOG",
                                      "/tmp/xla_host_warning.log")
    seen = [False]

    def filt(line: bytes):
        if XLA_HOST_WARNING_MARKER not in line:
            return line
        if seen[0]:
            return None
        seen[0] = True
        try:
            with open(path, "ab") as fh:
                fh.write(line)
            where = path
        except OSError:
            where = f"unwritable {path}; warning dropped"
        return (f"[{tag}] XLA host-feature warning suppressed "
                f"(full text: {where})\n").encode()

    return filt


HEIGHT, WIDTH = 440, 1024  # 436 padded to /8 (core/utils/utils.py:7-19)
# the JAX_PLATFORMS=cpu rehearsal proves control flow, not speed: spend
# seconds, not minutes
REHEARSAL_ITERS = 6
REHEARSAL_HEIGHT, REHEARSAL_WIDTH = 224, 512


def _log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - _T0:7.1f}s] {msg}", file=sys.stderr)


def _cpu_anchor_fields() -> dict:
    """The measured torch-vs-flax same-CPU anchors, parsed from the
    anchor script's log (one copy of the numbers: the measurement's).
    Per-geometry: the r5 anchor runs pin the framework-vs-framework
    ratio at every benched configuration (VERDICT r4 next-8), so all
    records are carried, keyed by their measured geometry; a re-run of
    the same geometry keeps the freshest value (the log appends)."""
    import os.path as osp

    path = osp.join(osp.dirname(osp.abspath(__file__)),
                    "logs", "torch_cpu_anchor.log")
    fwd: dict = {}
    train: dict = {}
    try:
        with open(path) as f:
            for line in f:
                if not line.lstrip().startswith("{"):
                    continue
                try:
                    rec = json.loads(line)
                    metric = rec.get("metric", "")
                    if "@" not in metric:
                        # legacy record without a geometry-bearing
                        # metric name — no key to file it under; skip
                        continue
                    geom = metric.rsplit("@", 1)[-1]
                    if "flax_over_torch" in rec:
                        fwd[geom] = rec["flax_over_torch"]
                    elif "flax_over_torch_train" in rec:
                        train[geom] = rec["flax_over_torch_train"]
                except ValueError:
                    continue
    except OSError:
        pass
    fields: dict = {}
    if fwd:
        fields["cpu_anchor_flax_over_torch"] = fwd
    if train:
        fields["cpu_anchor_flax_over_torch_train"] = train
    if fields:
        fields["cpu_anchor_source"] = "logs/torch_cpu_anchor.log"
    return fields


_T0 = time.perf_counter()

# bf16 peak matmul throughput per chip, by jax device_kind: the MFU
# denominator, named in the record so the ratio is auditable. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16). Only the
# device this repo is measured on is listed; any other device_kind is an
# error where a utilisation is printed, never a default.
CHIP_PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
}


def chip_peak_bf16_flops(device_kind: str) -> float:
    try:
        return CHIP_PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise SystemExit(
            f"no bf16 peak on record for device_kind {device_kind!r} "
            f"(known: {sorted(CHIP_PEAK_BF16_FLOPS)}): add it to "
            "bench.CHIP_PEAK_BF16_FLOPS with its source before printing "
            "a utilisation") from None


def _counted_flops(jitted, *args):
    """Whole-computation FLOPs from XLA's own cost analysis of the
    compiled executable (not an analytic estimate). Returns None if the
    backend declines — the bench must never fail over accounting."""
    try:
        cost = jitted.lower(*args).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):  # some versions wrap per-device
            cost = cost[0]
        flops = float(cost.get("flops", 0.0))
        return flops if flops > 0 else None
    except Exception as e:
        _log(f"cost_analysis unavailable: {e}")
        return None


# The measurement runs in a CHILD process: a device fetch that never
# returns would hang the run with nothing printed. The parent watches
# for output and, if the child goes silent longer than any legitimate
# compile could take (or overruns the hard cap), kills it and exits 8.
# The parent stays off JAX — a chip belongs to one process at a time.
# Env-overridable so the watchdog itself is testable
# (tests/test_bench_watchdog.py).
STALL_S = 900.0
HARD_CAP_S = 1950.0


def _run_child() -> int:
    """Spawn `bench.py` in measurement mode, forwarding its output.
    Returns its exit code; the child is killed on stall/overrun
    (rc -1)."""
    import os
    import signal
    import subprocess
    import threading

    stall_s = float(os.environ.get("BENCH_STALL_S", STALL_S))
    hard_cap_s = float(os.environ.get("BENCH_HARD_CAP_S", HARD_CAP_S))
    child = subprocess.Popen([sys.executable, __file__],
                             env=dict(os.environ, BENCH_CHILD="1"),
                             stdout=subprocess.PIPE, stderr=subprocess.PIPE)

    # an outer `timeout` SIGTERMs only THIS parent; without a handler
    # the measurement child would be orphaned still holding the chip —
    # forward the kill before dying
    def _on_term(signum, frame):
        child.terminate()
        try:
            child.wait(timeout=10)
        except subprocess.TimeoutExpired:
            child.kill()
        sys.exit(128 + signum)

    prev_handlers = {s: signal.signal(s, _on_term)
                     for s in (signal.SIGTERM, signal.SIGINT)}
    last = [time.monotonic()]
    warn_filt = make_stderr_filter(tag="bench")

    def pump(src, dst, is_stdout):
        for line in iter(src.readline, b""):
            last[0] = time.monotonic()
            if not is_stdout:
                # keep the XLA host-feature warning out of the
                # forwarded stream
                line = warn_filt(line)
                if line is None:
                    continue
            dst.buffer.write(line)
            dst.flush()

    threads = [threading.Thread(target=pump, args=(child.stdout, sys.stdout, True), daemon=True),
               threading.Thread(target=pump, args=(child.stderr, sys.stderr, False), daemon=True)]
    for t in threads:
        t.start()
    t0 = time.monotonic()
    rc = None
    while True:
        rc = child.poll()
        if rc is not None:
            break
        time.sleep(min(5.0, stall_s / 2))
        now = time.monotonic()
        if now - last[0] > stall_s or now - t0 > hard_cap_s:
            why = ("silent %.0fs" % (now - last[0])
                   if now - last[0] > stall_s else "overran %.0fs" % hard_cap_s)
            print(f"[bench] child stalled ({why}); killing", file=sys.stderr)
            # SIGTERM first: give Python/JAX a grace window to release
            # the device
            child.terminate()
            try:
                child.wait(timeout=15)
            except subprocess.TimeoutExpired:
                child.kill()
                child.wait()
            rc = -1
            break
    for t in threads:
        t.join(timeout=5)
    for s, h in prev_handlers.items():
        signal.signal(s, h)
    return rc


def main() -> None:
    import os

    if not os.environ.get("BENCH_CHILD"):
        # no fallback and no rescue: the child's failure is this run's
        rc = _run_child()
        sys.exit(rc if rc >= 0 else 8)

    if os.environ.get("BENCH_FAKE_HANG"):
        # test hook (tests/test_bench_watchdog.py): emit one line of
        # progress, then block forever — the parent's stall watchdog
        # must kill us
        print("[bench] fake child hanging", file=sys.stderr, flush=True)
        time.sleep(10_000)

    import jax

    from dexiraft_tpu.profiling import enable_persistent_cache

    _log(f"compile cache: {enable_persistent_cache()}")
    # a backend that cannot start raises here and fails the run
    platform = jax.devices()[0].platform
    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    on_tpu = platform == "tpu"
    if not on_tpu and not rehearsal:
        sys.exit(f"[bench] JAX found no TPU (platform {platform!r}): this "
                 "is a device measurement and has no other platform to "
                 "continue on. JAX_PLATFORMS=cpu asks for the labelled "
                 "rehearsal.")
    import jax.numpy as jnp

    from dexiraft_tpu.analysis import guards
    from dexiraft_tpu.config import raft_v5, resolve_corr_impl
    from dexiraft_tpu.models.raft import RAFT

    iters = REHEARSAL_ITERS if rehearsal else ITERS
    height, width = ((REHEARSAL_HEIGHT, REHEARSAL_WIDTH) if rehearsal
                     else (HEIGHT, WIDTH))
    device_kind = jax.devices()[0].device_kind
    _log(f"platform={platform} device_kind={device_kind} "
         f"devices={len(jax.devices())} geometry={height}x{width} "
         f"iters={iters}")

    # jit the init: eagerly it is hundreds of separate dispatches
    rng = jax.random.PRNGKey(0)
    small = jnp.zeros((1, 64, 64, 3), jnp.float32)
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    image1 = jax.random.uniform(k1, (1, height, width, 3), jnp.float32, 0, 255)
    image2 = jax.random.uniform(k2, (1, height, width, 3), jnp.float32, 0, 255)

    def measure(corr_impl: str, upconv: str = "subpixel",
                measure_loop: bool = True, corr_dtype: str = "fp32",
                fused: bool = False):
        cfg = raft_v5(mixed_precision=on_tpu, corr_impl=corr_impl,
                      dexined_upconv=upconv, corr_dtype=corr_dtype,
                      fused_update=fused)
        model = RAFT(cfg)
        init = jax.jit(
            lambda r, a, b: model.init(r, a, b, iters=1, train=False))
        variables = jax.block_until_ready(init(rng, small, small))
        _log(f"[{corr_impl}/{upconv}] init done")

        def make_forward(n):
            return jax.jit(lambda a, b: model.apply(
                variables, a, b, iters=n, train=False, test_mode=True))

        def timed_block(fn, reps):
            """Mean wall time of a forward that ends in
            block_until_ready (chip_smoke.py's probe checks on the chip
            that it waits for the device).

            The timed region runs under guards.strict_mode (the
            steady-state contract, same as train_bench/serve_bench): the
            warmup call above it absorbs the one expected compile, so
            any retrace or implicit host<->device transfer inside the
            window FAILS the bench instead of deflating the number."""
            jax.block_until_ready(fn(image1, image2))  # compile + warmup
            with guards.strict_mode(label="bench:steady"):
                t0 = time.perf_counter()
                for _ in range(reps):
                    jax.block_until_ready(fn(image1, image2))
                return (time.perf_counter() - t0) / reps

        reps = 3 if on_tpu else 1
        fwd = make_forward(iters)
        dt = timed_block(fwd, reps)
        _log(f"[{corr_impl}/{upconv}] steady-state {dt * 1e3:.1f} ms / "
             f"forward")

        diag = {"forward_ms": round(dt * 1e3, 2)}
        # whole-forward FLOPs for the MFU field. The AOT
        # lower().compile() does NOT reuse the in-memory jit executable;
        # it hits the persistent disk cache (enabled in this child,
        # above) so it costs seconds of deserialization.
        # Budget-guarded anyway: a cold cache must never push the child
        # into the watchdog's hard cap with the record unprinted.
        if time.perf_counter() - _T0 < float(
                os.environ.get("BENCH_HARD_CAP_S", HARD_CAP_S)) - 650:
            flops = _counted_flops(fwd, image1, image2)
            if flops is not None:
                diag["forward_flops"] = flops
                diag["forward_tflops_per_s"] = round(flops / dt / 1e12, 2)
        else:
            _log(f"[{corr_impl}/{upconv}] flops count skipped (budget)")
        loop_rate = None
        if on_tpu and measure_loop:
            # marginal per-iteration rate: isolates the refinement loop
            # from the amortized prelude (encoders/DexiNed/volume build)
            # — the number directly comparable to a per-lookup kernel
            dt1 = timed_block(make_forward(1), reps)
            if dt > dt1:
                loop_rate = (iters - 1) / (dt - dt1)
            diag["forward_1iter_ms"] = round(dt1 * 1e3, 2)
            _log(f"[{corr_impl}/{upconv}] prelude+1 "
                 f"{dt1 * 1e3:.1f} ms; "
                 f"loop {loop_rate and round(loop_rate, 1)} iters/s")
        return iters / dt, loop_rate, diag

    # the first-class corr paths are measured: the materialized MXU
    # volume, the memory-efficient on-demand path (the alt_cuda_corr
    # analog the north-star metric names, BASELINE.json) and the
    # flash-blocked Pallas kernel; the fastest is the headline. The
    # DexiNed upconv A/B (transposed conv vs the identical-map subpixel
    # phase form) is kept on both non-Pallas corr paths as a diagnostic;
    # the upconv choice only changes the prelude, so the transpose
    # variants skip the marginal-loop (1-iter) re-measurement and inherit
    # the loop rate of their subpixel sibling on the same corr path.
    allpairs_ips, allpairs_loop, ap_diag = measure("allpairs", "subpixel")
    diag = {f"allpairs_{k}": v for k, v in ap_diag.items()}
    # candidate = (corr_impl, upconv, corr_dtype, fused, ips, loop_ips)
    candidates = [("allpairs", "subpixel", "fp32", False,
                   allpairs_ips, allpairs_loop)]
    loop_by_corr = {"allpairs": allpairs_loop}
    # the parent kills us at HARD_CAP_S with the record unprinted — if
    # the sweep is running long (slow compiles), drop remaining
    # secondary configs and get the JSON out with what we have
    hard_cap_s = float(os.environ.get("BENCH_HARD_CAP_S", HARD_CAP_S))
    secondary_budget_s = float(os.environ.get("BENCH_SECONDARY_BUDGET_S",
                                              hard_cap_s - 550))
    if on_tpu:  # secondary metrics; not worth rehearsal time
        for corr_impl, upconv, corr_dtype, fused, tag in (
                ("local", "subpixel", "fp32", False, "local"),
                ("allpairs", "subpixel", "bf16", False, "allpairs_bf16"),
                ("allpairs", "subpixel", "int8", False, "allpairs_int8"),
                # the flash-blocked legs: ONE kernel/iteration, fmap2
                # row-block-streamed from HBM, no materialized volume —
                # what `--corr_impl auto` serves on a TPU
                ("flash", "subpixel", "fp32", True, "flash"),
                ("flash", "subpixel", "int8", True, "flash_int8"),
                ("allpairs", "transpose", "fp32", False,
                 "allpairs_transpose"),
                ("local", "transpose", "fp32", False, "local_transpose")):
            if time.perf_counter() - _T0 > secondary_budget_s:
                _log(f"[{tag}] skipped: over secondary budget "
                     f"({secondary_budget_s:.0f}s)")
                continue
            # a leg that throws fails the run: a record that quietly
            # lacks the production configuration is not a measurement
            with_loop = upconv == "subpixel"
            ips, loop, d = measure(corr_impl, upconv,
                                   measure_loop=with_loop,
                                   corr_dtype=corr_dtype, fused=fused)
            diag.update({f"{tag}_{k}": v for k, v in d.items()})
            diag[f"{tag}_iters_per_sec"] = round(ips, 2)
            if loop is not None and corr_dtype == "fp32" and not fused:
                loop_by_corr[corr_impl] = loop
            candidates.append(
                (corr_impl, upconv, corr_dtype, fused, ips,
                 loop if loop is not None else loop_by_corr.get(corr_impl)))

    impl, upconv_best, dtype_best, fused_best, iters_per_sec, loop_ips = max(
        candidates, key=lambda c: c[4])
    local_ips = diag.get("local_iters_per_sec")

    # MFU of the winning config: counted whole-forward FLOPs (XLA cost
    # analysis of the compiled executable) / measured forward time /
    # chip bf16 peak. Reported only when both the FLOP count and a
    # known chip peak exist; the record names both inputs.
    if fused_best:
        win_tag = "flash" + ("" if dtype_best == "fp32"
                             else f"_{dtype_best}")
    elif dtype_best != "fp32":
        win_tag = f"{impl}_{dtype_best}"
    else:
        win_tag = impl if upconv_best == "subpixel" else f"{impl}_transpose"
    win_flops = diag.get(f"{win_tag}_forward_flops")
    mfu_fields = {"device_kind": device_kind}
    if win_flops is not None:
        mfu_fields["forward_flops"] = win_flops
        if on_tpu:
            peak = chip_peak_bf16_flops(device_kind)
            forward_s = iters / iters_per_sec
            mfu_fields.update({
                "mfu": round(win_flops / forward_s / peak, 4),
                "chip_peak_bf16_flops": peak,
            })

    rec = {
        "metric": f"refinement_iters_per_sec_per_chip@{height}x{width}",
        "value": round(iters_per_sec, 2),
        "unit": "iters/s",
        # conservative: the headline amortizes the whole forward incl.
        # the DexiNed+encoder prelude over the 32 iterations, while the
        # 320 it/s denominator is an upstream-RAFT estimate WITHOUT the
        # dual edge stream or DexiNed the v5 model also runs.
        # In a rehearsal this ratio is diagnostic only (wrong platform,
        # reduced geometry) — `rehearsal: true` marks it so.
        "vs_baseline": round(iters_per_sec / BASELINE_ITERS_PER_SEC, 3),
        # the record must be self-describing: a CPU rehearsal line must
        # never be mistaken for a device number
        "platform": platform,
        "rehearsal": rehearsal,
        # the denominator is an ESTIMATE from upstream-RAFT FPS, not a
        # measured A100 number (none exists in the reference's record)
        "baseline_kind": "estimate",
        "baseline_iters_per_sec": BASELINE_ITERS_PER_SEC,
        # measured same-silicon framework anchor: flax v5 forward vs the
        # reference's torch v5 forward on this host's CPU, same process,
        # same geometry (scripts/torch_cpu_anchor.py, docs/perf.md) —
        # read from the measurement's own log so the record can never
        # drift from its source; absent if the anchor was never run
        **_cpu_anchor_fields(),
        **mfu_fields,
        "iters": iters,
        "corr_impl": impl,
        # what --corr_impl auto WOULD resolve to on this record's
        # platform (config.resolve_corr_impl) — eval/serve print it but
        # records never carried it, so cross-box A/Bs had to infer the
        # production config from the platform field. Distinct from
        # corr_impl: the sweep's WINNER vs the auto-resolution.
        "corr_impl_resolved": resolve_corr_impl("auto", platform)[0],
        # the winning config's pyramid storage precision and fused-step
        # flag (ISSUE 8): together with corr_impl/dexined_upconv these
        # four keys fully name the headline configuration
        "corr_dtype": dtype_best,
        "fused_update": fused_best,
        "dexined_upconv": upconv_best,
        "loop_only_iters_per_sec": (round(loop_ips, 2) if loop_ips
                                    else None),
        # marginal refinement-loop rate (prelude EXCLUDED) over the
        # whole-forward baseline estimate — numerator and denominator
        # are deliberately asymmetric; named so it cannot read as the
        # end-to-end headline speedup
        "loop_only_vs_whole_forward_baseline": (
            round(loop_ips / BASELINE_ITERS_PER_SEC, 3) if loop_ips
            else None),
        "allpairs_iters_per_sec": round(allpairs_ips, 2),
        "local_corr_iters_per_sec": local_ips,
        "flash_corr_iters_per_sec": diag.get("flash_iters_per_sec"),
        **diag,
    }
    validate_record(rec)  # schema pin — a drifted record fails loudly
    # flush: stdout is a block-buffered pipe under the watchdog parent
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
