"""Streaming-video perf probe: what the split encoder + device carry buy.

Four strict-mode experiments, emitted as ONE pinned JSON record (the
PR 8 bench convention: every timed window runs under
guards.strict_mode, so a retrace or implicit transfer FAILS the probe
instead of deflating a number):

  pairwise    the chained-pairs baseline: the monolithic eval step per
              frame (encoders run on BOTH frames of every pair), flow
              carried through the on-device splat.
  streamed    the split path (models/raft.py mode="encode"/"step"):
              each frame encoded ONCE, the previous frame's features
              reused — per-frame p50/p99 and the encoder-reuse speedup.
              Flow outputs must match the pairwise leg to <= 1e-4
              (identical chaining, so the A/B isolates encoder reuse).
  footprint   the streamed executables are length-independent: one
              compiled encode + refine + splat drive n in {2, 8, 32}
              frames with the SAME memory_analysis at every leg
              (extends the PR 12 highres_probe chained leg to the
              split path).
  carry       session-carry transfer bytes, MEASURED off the inference
              engine's ServeStats counters: the PR 6 host round-trip
              (flow_low D2H per response + flow_init H2D per warm
              request) vs the device-resident handoff's zero.

Off-TPU the Pallas kernels would run interpreter-mode, so
``resolve_corr_impl("auto")`` picks allpairs here — the record stamps
``corr_impl_resolved`` so A/Bs are self-describing across boxes.

Usage:
  python scripts/video_bench.py --cpu                  # full record
  python scripts/video_bench.py --variant v5 --iters 8 # heavier model
"""

from __future__ import annotations

import argparse
import json
import os.path as osp
import sys
import time

sys.path.insert(0, osp.dirname(osp.dirname(osp.abspath(__file__))))

import numpy as np

# ---- record schema pins (tests/test_zzvideo.py) --------------------------
VIDEO_RECORD_KEYS = frozenset({
    "metric", "platform", "variant", "small", "iters", "geometry",
    "strict", "corr_impl_resolved", "corr_dtype", "fused_update",
    "pairwise", "streamed", "speedup_streamed_over_pairwise",
    "parity_max_abs_diff", "parity_ok", "footprint", "carry",
})
LEG_KEYS = frozenset({
    "frames", "per_frame_ms_p50", "per_frame_ms_p99", "per_frame_ms_mean",
})
FOOTPRINT_KEYS = frozenset({
    "seq_lens", "encode_temp_mb", "refine_temp_mb", "per_frame_ms",
    "footprint_flat",
})
CARRY_KEYS = frozenset({
    "frames", "flow_init_bytes", "host_h2d_bytes_per_frame",
    "host_d2h_bytes_per_frame", "device_h2d_bytes_per_frame",
    "device_d2h_bytes_per_frame",
})


def validate_record(rec: dict) -> None:
    """Schema gate — a drifted record fails the probe loudly (the
    bench.validate_record convention)."""
    if set(rec) != VIDEO_RECORD_KEYS:
        raise ValueError(
            f"video record keys drifted: "
            f"missing {sorted(VIDEO_RECORD_KEYS - set(rec))}, "
            f"extra {sorted(set(rec) - VIDEO_RECORD_KEYS)}")
    for leg in ("pairwise", "streamed"):
        if set(rec[leg]) != LEG_KEYS:
            raise ValueError(f"{leg} leg keys drifted: {sorted(rec[leg])}")
    if set(rec["footprint"]) != FOOTPRINT_KEYS:
        raise ValueError(f"footprint keys drifted: "
                         f"{sorted(rec['footprint'])}")
    if set(rec["carry"]) != CARRY_KEYS:
        raise ValueError(f"carry keys drifted: {sorted(rec['carry'])}")


def _log(msg: str) -> None:
    print(f"[video_bench] {msg}", file=sys.stderr, flush=True)


def _pctl(samples, p):
    return round(float(np.percentile(samples, p)) * 1e3, 2)


def _leg_record(per_frame_s) -> dict:
    return {
        "frames": len(per_frame_s),
        "per_frame_ms_p50": _pctl(per_frame_s, 50),
        "per_frame_ms_p99": _pctl(per_frame_s, 99),
        "per_frame_ms_mean": round(float(np.mean(per_frame_s)) * 1e3, 2),
    }


def _temp_mb(compiled) -> float:
    ma = compiled.memory_analysis()
    return round(float(ma.temp_size_in_bytes) / 2**20, 2)


def _frames(n, h, w, seed=1):
    import jax

    key = jax.random.PRNGKey(seed)
    return [jax.device_get(jax.random.uniform(
        jax.random.fold_in(key, i), (1, h, w, 3), dtype="float32",
        minval=0, maxval=255)) for i in range(n)]


def _build(args):
    """(cfg, variables, resolved) — synthetic init (the probe measures
    the serving stack, not EPE), one resident device copy."""
    import jax

    from dexiraft_tpu.config import VARIANTS, TrainConfig, \
        resolve_corr_impl_args
    from dexiraft_tpu.train.state import create_state

    impl, fused = resolve_corr_impl_args(
        args, jax.devices()[0].platform, "video_bench")
    cfg = VARIANTS[args.variant](small=args.small, corr_impl=impl,
                                 corr_dtype=args.corr_dtype,
                                 fused_update=fused)
    state = create_state(jax.random.PRNGKey(0), cfg, TrainConfig())
    variables = jax.device_put({"params": state.params,
                                "batch_stats": state.batch_stats})
    return cfg, variables, impl, fused


def run_record(args) -> dict:
    import jax

    from dexiraft_tpu.analysis import guards
    from dexiraft_tpu.eval.interpolate import forward_interpolate
    from dexiraft_tpu.train.step import (make_encode_step, make_eval_step,
                                         make_refine_step)

    h, w = (int(v) for v in args.size.split("x"))
    assert h % 8 == 0 and w % 8 == 0, "geometry must be /8 (bucket shape)"
    cfg, variables, impl, fused = _build(args)
    platform = jax.devices()[0].platform
    _log(f"platform={platform} variant={args.variant}"
         f"{'-small' if args.small else ''} iters={args.iters} "
         f"size={h}x{w} corr_impl={impl} frames={args.frames}")

    frames = _frames(args.frames + 1, h, w)
    frames_dev = [jax.device_put(f) for f in frames]
    zero_fi = jax.device_put(np.zeros((1, h // 8, w // 8, 2), np.float32))

    splat = jax.jit(lambda low: forward_interpolate(low[0])[None])

    # ---- pairwise baseline: monolithic step per chained pair ------------
    pair_step = make_eval_step(cfg, iters=args.iters)
    pair_c = pair_step.lower(variables, frames_dev[0], frames_dev[1],
                             None, None, zero_fi).compile()
    splat_c = None

    def run_pairwise():
        nonlocal splat_c
        times, flows = [], []
        fi = zero_fi
        for i in range(args.frames):
            t0 = time.perf_counter()
            low, up = pair_c(variables, frames_dev[i], frames_dev[i + 1],
                             None, None, fi)
            fi = splat_c(low)
            flows.append(jax.device_get(up))   # the response payload
            times.append(time.perf_counter() - t0)
        return times, flows

    # warmup (compiles splat too), then the strict timed window
    low0, _ = pair_c(variables, frames_dev[0], frames_dev[1], None, None,
                     zero_fi)
    splat_c = splat.lower(low0).compile()
    run_pairwise()
    with guards.strict_mode(label="video_bench:pairwise"):
        pair_times, pair_flows = run_pairwise()
    pairwise = _leg_record(pair_times)
    _log(f"pairwise: {pairwise['per_frame_ms_mean']} ms/frame mean "
         f"(p50 {pairwise['per_frame_ms_p50']})")

    # ---- streamed: encode once per frame, features reused ---------------
    encode_step = make_encode_step(cfg)
    refine_step = make_refine_step(cfg, iters=args.iters)
    enc_c = encode_step.lower(variables, frames_dev[0]).compile()
    feats0 = enc_c(variables, frames_dev[0])
    ref_c = refine_step.lower(variables, feats0, feats0, zero_fi).compile()

    def run_streamed():
        times, flows = [], []
        fi = zero_fi
        feats_prev = enc_c(variables, frames_dev[0])
        for i in range(args.frames):
            t0 = time.perf_counter()
            feats = enc_c(variables, frames_dev[i + 1])
            low, up = ref_c(variables, feats_prev, feats, fi)
            fi = splat_c(low)
            feats_prev = feats
            flows.append(jax.device_get(up))
            times.append(time.perf_counter() - t0)
        return times, flows

    run_streamed()
    with guards.strict_mode(label="video_bench:streamed"):
        stream_times, stream_flows = run_streamed()
    streamed = _leg_record(stream_times)
    _log(f"streamed: {streamed['per_frame_ms_mean']} ms/frame mean "
         f"(p50 {streamed['per_frame_ms_p50']})")

    # ---- parity: identical chaining => identical outputs ----------------
    parity = max(float(np.max(np.abs(a - b)))
                 for a, b in zip(pair_flows, stream_flows))
    _log(f"parity max |streamed - pairwise| = {parity:.2e}")

    # ---- footprint: one executable, any stream length -------------------
    per_frame_ms, enc_temp, ref_temp = [], [], []
    for n in args.seq_lens:
        seq = [jax.device_put(f) for f in _frames(n + 1, h, w, seed=7)]
        fi = zero_fi
        feats_prev = enc_c(variables, seq[0])
        with guards.strict_mode(label=f"video_bench:footprint_{n}"):
            t0 = time.perf_counter()
            for i in range(n):
                feats = enc_c(variables, seq[i + 1])
                low, up = ref_c(variables, feats_prev, feats, fi)
                fi = splat_c(low)
                feats_prev = feats
            jax.block_until_ready(up)
            per_frame_ms.append(round((time.perf_counter() - t0) / n * 1e3,
                                      1))
        # same executables at every length => same buffer assignment;
        # read them each time anyway so a drifted recompile cannot hide
        enc_temp.append(_temp_mb(enc_c))
        ref_temp.append(_temp_mb(ref_c))
        _log(f"footprint n={n}: {per_frame_ms[-1]} ms/frame, encode temp "
             f"{enc_temp[-1]} MB, refine temp {ref_temp[-1]} MB")
    footprint = {
        "seq_lens": list(args.seq_lens),
        "encode_temp_mb": enc_temp,
        "refine_temp_mb": ref_temp,
        "per_frame_ms": per_frame_ms,
        "footprint_flat": (len(set(enc_temp)) == 1
                           and len(set(ref_temp)) == 1),
    }

    # ---- carry bytes: host round-trip vs device handoff, MEASURED ------
    carry = measure_carry(args, cfg, variables, h, w)

    rec = {
        "metric": "video_stream_per_frame",
        "platform": platform,
        "variant": args.variant,
        "small": args.small,
        "iters": args.iters,
        "geometry": [h, w],
        "strict": True,
        "corr_impl_resolved": impl,
        "corr_dtype": args.corr_dtype,
        "fused_update": fused,
        "pairwise": pairwise,
        "streamed": streamed,
        "speedup_streamed_over_pairwise": round(
            pairwise["per_frame_ms_mean"] / streamed["per_frame_ms_mean"],
            3),
        "parity_max_abs_diff": parity,
        "parity_ok": parity <= 1e-4,
        "footprint": footprint,
        "carry": carry,
    }
    validate_record(rec)
    print(json.dumps(rec), flush=True)
    return rec


def measure_carry(args, cfg, variables, h: int, w: int) -> dict:
    """Session-carry transfer bytes off the engine's own counters: K
    chained warm frames through the PR 6 host path (flow_low fetched
    per response, flow_init re-uploaded per request) and through the
    device-resident handoff (both stay on chip). The timed loops run
    strict with transfer='allow' — the host leg's round-trip is the
    MEASURED phenomenon, not an accident."""
    import jax

    from dexiraft_tpu.analysis import guards
    from dexiraft_tpu.eval.interpolate import forward_interpolate
    from dexiraft_tpu.serve import InferenceEngine, ServeConfig
    from dexiraft_tpu.train.step import make_eval_step

    k_frames = 4
    step = make_eval_step(cfg, iters=args.iters)

    def eval_fn(a, b, fi):
        put = jax.device_put
        return step(variables, put(a), put(b),
                    flow_init=None if fi is None else put(fi))

    rng = np.random.default_rng(3)
    items = [{"image1": rng.uniform(0, 255, (h, w, 3)).astype(np.float32),
              "image2": rng.uniform(0, 255, (h, w, 3)).astype(np.float32)}
             for _ in range(k_frames)]

    def drive(device_carry: bool):
        engine = InferenceEngine(eval_fn, ServeConfig(
            batch_size=1, warm_start=True, device_carry=device_carry))
        if device_carry:
            carry_fn = jax.jit(lambda low: forward_interpolate(low))
        else:
            carry_fn = (lambda low:
                        jax.device_get(forward_interpolate(
                            jax.device_put(low))))
        # warmup: compile the bucket + splat signatures outside the
        # measured window, then reset the byte counters
        (res,) = engine.run_batch([dict(items[0])])
        carry_fn(res.flow_low)
        engine.watch.mark_warm()  # the splat compile is expected, not drift
        engine.reset_stats()
        engine.stats.carry_h2d_bytes = engine.stats.carry_d2h_bytes = 0
        fi = None
        with guards.strict_mode(label=f"video_bench:carry_"
                                      f"{'dev' if device_carry else 'host'}",
                                transfer="allow"):
            for it in items:
                item = dict(it)
                if fi is not None:
                    item["flow_init"] = fi
                (res,) = engine.run_batch([item])
                fi = carry_fn(res.flow_low)
        return (engine.stats.carry_h2d_bytes // k_frames,
                engine.stats.carry_d2h_bytes // k_frames)

    host_h2d, host_d2h = drive(device_carry=False)
    dev_h2d, dev_d2h = drive(device_carry=True)
    fi_bytes = (h // 8) * (w // 8) * 2 * 4
    _log(f"carry bytes/frame: host {host_h2d} up / {host_d2h} down vs "
         f"device {dev_h2d} / {dev_d2h} (flow_init is {fi_bytes} B)")
    return {
        "frames": k_frames,
        "flow_init_bytes": fi_bytes,
        "host_h2d_bytes_per_frame": int(host_h2d),
        "host_d2h_bytes_per_frame": int(host_d2h),
        "device_h2d_bytes_per_frame": int(dev_h2d),
        "device_d2h_bytes_per_frame": int(dev_d2h),
    }


def main():
    from dexiraft_tpu.config import CORR_IMPLS

    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", default="v1")
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--iters", type=int, default=4,
                    help="refinement iterations per frame")
    ap.add_argument("--size", default="96x128",
                    help="frame geometry HxW (must be /8)")
    ap.add_argument("--frames", type=int, default=8,
                    help="frames in the timed pairwise/streamed legs")
    ap.add_argument("--seq_lens", type=int, nargs="+", default=(2, 8, 32),
                    help="stream lengths for the flat-footprint leg")
    ap.add_argument("--corr_impl", default="auto",
                    choices=["auto", *CORR_IMPLS])
    ap.add_argument("--corr_dtype", default="fp32",
                    choices=["fp32", "bf16", "int8"])
    ap.add_argument("--fused_update", action="store_true")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend (same as JAX_PLATFORMS=cpu)")
    args = ap.parse_args()
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    run_record(args)


if __name__ == "__main__":
    main()
