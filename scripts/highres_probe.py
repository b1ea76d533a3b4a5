"""Memory-scalability probe: what killing the materialized correlation
volume unlocks (ISSUE 12).

Three strict-mode experiments, emitted as ONE pinned JSON record (the
PR 8 bench convention: every timed window runs under guards.strict_mode,
so a retrace or implicit transfer FAILS the probe instead of deflating
a number):

  eval A/B    flash-blocked vs allpairs/int8-allpairs at the 440x1024
              eval geometry — steady-state forward ms plus a peak-memory
              column read off ``compiled.memory_analysis()`` (temp +
              argument + output bytes of the ACTUAL executable, not an
              estimate).
  1080p leg   a 1088x1920 (1080p-class) geometry: the flash path's
              compile-time footprint stays O(fmaps) while the allpairs
              level-0 volume alone is ~4.3 GB/stream — past a 15.75 GB
              chip before activations, reported as
              ``allpairs_infeasible_on_chip``.
  chained     warm-start video: K frames chained through one compiled
              step with ``flow_init`` carry — the per-frame executable
              (and therefore the footprint) is identical at every
              sequence length. O(1)-memory video, demonstrated rather
              than asserted.

Off-TPU the Pallas kernels run in interpreter mode (debug-speed): the
ms columns then only prove the paths are compile-flat and
transfer-clean; the MEMORY columns are the record's point and are
platform-independent (XLA buffer assignment of the same program).

Usage:
  python scripts/highres_probe.py                    # full record
  python scripts/highres_probe.py --mode single \
         --impl local --size 1440 2560               # legacy single run
"""

from __future__ import annotations

import argparse
import json
import os
import os.path as osp
import sys
import time

sys.path.insert(0, osp.dirname(osp.dirname(osp.abspath(__file__))))

import jax
import jax.numpy as jnp

from dexiraft_tpu.ops import pallas_corr

EVAL_GEOMETRY = (440, 1024)
HIGHRES_GEOMETRY = (1088, 1920)  # 1080p padded to /8
CHAINED_GEOMETRY = (256, 512)
CHIP_HBM_GB = 15.75  # the single-chip budget the volume blows

# ---- record schema pins (tests/test_zzzflashcorr.py) ---------------------
HIGHRES_RECORD_KEYS = frozenset({
    "metric", "platform", "model", "strict", "iters",
    "eval_geometry", "eval_ab",
    "highres_geometry", "highres",
    "chained",
})
EVAL_LEG_KEYS = frozenset({
    "corr_impl", "corr_dtype", "fused_update", "temp_mb", "peak_mb",
    "forward_ms", "executed",
})
HIGHRES_KEYS = frozenset({
    "flash_temp_mb", "flash_peak_mb", "flash_executed",
    "allpairs_level0_volume_gb", "allpairs_serve_batch_gb",
    "allpairs_infeasible_on_chip", "hbm_gb",
})
SERVE_BATCH = 4  # serve_cli's default --batch_size (the bucket granule)
CHAINED_KEYS = frozenset({
    "geometry", "seq_lens", "per_frame_ms", "per_frame_temp_mb",
    "footprint_flat",
})


def validate_record(rec: dict) -> None:
    """Schema gate — a drifted record fails the probe loudly (the
    bench.validate_record convention)."""
    if set(rec) != HIGHRES_RECORD_KEYS:
        raise ValueError(f"highres record keys drifted: "
                         f"missing {sorted(HIGHRES_RECORD_KEYS - set(rec))}, "
                         f"extra {sorted(set(rec) - HIGHRES_RECORD_KEYS)}")
    for leg in rec["eval_ab"]:
        if set(leg) != EVAL_LEG_KEYS:
            raise ValueError(f"eval_ab leg keys drifted: {sorted(leg)}")
    if set(rec["highres"]) != HIGHRES_KEYS:
        raise ValueError(f"highres keys drifted: {sorted(rec['highres'])}")
    if set(rec["chained"]) != CHAINED_KEYS:
        raise ValueError(f"chained keys drifted: {sorted(rec['chained'])}")


def _log(msg: str) -> None:
    print(f"[highres] {msg}", file=sys.stderr, flush=True)


def _mem(compiled):
    """(temp_mb, peak_mb) off the compiled executable's own buffer
    assignment. peak = temp + argument + output: the resident set the
    executable needs beyond the weights it shares with every config."""
    ma = compiled.memory_analysis()
    temp = float(ma.temp_size_in_bytes)
    peak = temp + float(ma.argument_size_in_bytes) \
        + float(ma.output_size_in_bytes)
    return round(temp / 2**20, 2), round(peak / 2**20, 2)


def _make_model(impl: str, dtype: str, fused: bool):
    from dexiraft_tpu.config import raft_v1
    from dexiraft_tpu.models.raft import RAFT

    # v1 full-size: the real 256-channel correlation load without the
    # DexiNed prelude dominating CPU wall time (the corr subsystem is
    # what this probe measures; bench.py owns the flagship v5 numbers)
    cfg = raft_v1(corr_impl=impl, corr_dtype=dtype, fused_update=fused)
    return RAFT(cfg)


def _init_variables(model):
    rng = jax.random.PRNGKey(0)
    small = jnp.zeros((1, 64, 64, 3), jnp.float32)
    init = jax.jit(lambda r, a, b: model.init(r, a, b, iters=1,
                                              train=False))
    return jax.block_until_ready(init(rng, small, small))


def _frames(h: int, w: int):
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    return (jax.random.uniform(k1, (1, h, w, 3), jnp.float32, 0, 255),
            jax.random.uniform(k2, (1, h, w, 3), jnp.float32, 0, 255))


def eval_ab_legs(iters: int, execute: bool) -> list:
    """The 440x1024 strict A/B: allpairs / int8-allpairs / flash /
    int8-flash, each with its executable's memory columns."""
    from dexiraft_tpu.analysis import guards

    h, w = EVAL_GEOMETRY
    im1, im2 = _frames(h, w)
    legs = []
    for impl, dtype, fused in (("allpairs", "fp32", False),
                               ("allpairs", "int8", False),
                               ("flash", "fp32", True),
                               ("flash", "int8", True)):
        model = _make_model(impl, dtype, fused)
        variables = _init_variables(model)

        @jax.jit
        def fwd(a, b, model=model, variables=variables):
            low, up = model.apply(variables, a, b, iters=iters,
                                  train=False, test_mode=True)
            return jnp.sum(low) + jnp.sum(up)

        compiled = fwd.lower(im1, im2).compile()
        temp_mb, peak_mb = _mem(compiled)
        forward_ms = None
        if execute:
            # execute the AOT executable itself: no second jit compile,
            # so the strict window's zero-recompile budget holds for
            # free and the memory numbers describe what actually ran
            float(jax.device_get(compiled(im1, im2)))  # warmup
            with guards.strict_mode(label=f"highres:{impl}_{dtype}"):
                t0 = time.perf_counter()
                float(jax.device_get(compiled(im1, im2)))
                forward_ms = round((time.perf_counter() - t0) * 1e3, 1)
        legs.append({"corr_impl": impl, "corr_dtype": dtype,
                     "fused_update": fused, "temp_mb": temp_mb,
                     "peak_mb": peak_mb, "forward_ms": forward_ms,
                     "executed": execute})
        _log(f"eval {impl}/{dtype}{'/fused' if fused else ''}: "
             f"temp {temp_mb} MB, peak {peak_mb} MB, "
             f"forward {forward_ms} ms")
    return legs


def highres_leg(iters: int, execute_flash: bool) -> dict:
    """1080p-class geometry: flash compiles (and on TPU runs) with an
    O(fmaps) footprint; the allpairs volume is arithmetic — level 0
    alone busts the chip, no need to compile a program XLA would spend
    minutes on."""
    h, w = HIGHRES_GEOMETRY
    n8 = (h // 8) * (w // 8)
    vol_gb = n8 * n8 * 4 / 1e9  # level-0, one sample/stream, fp32
    # what serving this geometry with allpairs would actually need:
    # the default serve batch x the full pooled pyramid (sum 4^-i over
    # 4 levels = 4/3) — the number that has to fit beside activations
    serve_gb = SERVE_BATCH * vol_gb * 4 / 3
    model = _make_model("flash", "int8", True)
    variables = _init_variables(model)
    im1, im2 = _frames(h, w)

    @jax.jit
    def fwd(a, b):
        low, up = model.apply(variables, a, b, iters=iters,
                              train=False, test_mode=True)
        return jnp.sum(low) + jnp.sum(up)

    compiled = fwd.lower(im1, im2).compile()
    temp_mb, peak_mb = _mem(compiled)
    executed = False
    if execute_flash:
        from dexiraft_tpu.analysis import guards

        float(jax.device_get(compiled(im1, im2)))  # warmup
        with guards.strict_mode(label="highres:flash_1080p"):
            float(jax.device_get(compiled(im1, im2)))
        executed = True
    out = {"flash_temp_mb": temp_mb, "flash_peak_mb": peak_mb,
           "flash_executed": executed,
           "allpairs_level0_volume_gb": round(vol_gb, 2),
           "allpairs_serve_batch_gb": round(serve_gb, 2),
           "allpairs_infeasible_on_chip": serve_gb > CHIP_HBM_GB,
           "hbm_gb": CHIP_HBM_GB}
    _log(f"1080p {h}x{w}: flash temp {temp_mb} MB vs allpairs "
         f"{vol_gb:.1f} GB level-0/sample, {serve_gb:.1f} GB at the "
         f"serve batch of {SERVE_BATCH} (chip HBM {CHIP_HBM_GB} GB) — "
         f"infeasible={out['allpairs_infeasible_on_chip']}")
    return out


def chained_leg(iters: int, seq_lens=(2, 4, 8)) -> dict:
    """Warm-start chained frames: ONE compiled step, flow_init carry.
    The executable is identical at every sequence length, so the
    per-frame footprint cannot grow with it — pinned by reading the
    same memory_analysis at each length and timing the frames."""
    from dexiraft_tpu.analysis import guards
    from dexiraft_tpu.eval.interpolate import forward_interpolate

    h, w = CHAINED_GEOMETRY
    model = _make_model("flash", "int8", True)
    variables = _init_variables(model)

    @jax.jit
    def step(a, b, flow_init):
        low, up = model.apply(variables, a, b, iters=iters, train=False,
                              flow_init=flow_init, test_mode=True)
        # the session-store warm start, on-device: splat the low-res
        # flow forward into the next frame's init (serve/sessions.py
        # carry semantics) — the whole video loop is ONE executable
        return forward_interpolate(low[0])[None], jnp.sum(up)

    zero_init = jnp.zeros((1, h // 8, w // 8, 2), jnp.float32)
    im1, _ = _frames(h, w)
    compiled = step.lower(im1, im1, zero_init).compile()
    temp_mb, _ = _mem(compiled)

    per_frame_ms, per_frame_temp = [], []
    for n in seq_lens:
        key = jax.random.PRNGKey(7)
        frames = [jax.random.uniform(jax.random.fold_in(key, i),
                                     (1, h, w, 3), jnp.float32, 0, 255)
                  for i in range(n + 1)]
        flow_init = zero_init
        jax.block_until_ready(compiled(frames[0], frames[1], flow_init))
        with guards.strict_mode(label=f"highres:chained_{n}"):
            t0 = time.perf_counter()
            for i in range(n):
                flow_init, s = compiled(frames[i], frames[i + 1],
                                        flow_init)
            float(jax.device_get(s))
            dt = (time.perf_counter() - t0) / n
        per_frame_ms.append(round(dt * 1e3, 1))
        # same executable at every length => same buffer assignment;
        # read it each time anyway so a drifted recompile cannot hide
        per_frame_temp.append(_mem(compiled)[0])
        _log(f"chained n={n}: {dt * 1e3:.1f} ms/frame, "
             f"step temp {per_frame_temp[-1]} MB")
    flat = len(set(per_frame_temp)) == 1
    return {"geometry": list(CHAINED_GEOMETRY), "seq_lens": list(seq_lens),
            "per_frame_ms": per_frame_ms,
            "per_frame_temp_mb": per_frame_temp, "footprint_flat": flat}


def run_record(args) -> dict:
    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if not on_tpu:
        # interpreter-mode kernels off-chip; a big pixel block keeps the
        # interpret grid (traced per step) small at 1080p
        os.environ.setdefault("DEXIRAFT_PALLAS_INTERPRET", "1")
        pallas_corr._FLASH_PIXEL_BLOCK = 2048
    iters = args.iters if args.iters is not None else (8 if on_tpu else 2)
    _log(f"platform={platform} iters={iters}")
    rec = {
        "metric": "flash_correlation_memory_probe",
        "platform": platform,
        "model": "raft_v1_full",
        "strict": True,
        "iters": iters,
        "eval_geometry": list(EVAL_GEOMETRY),
        "eval_ab": eval_ab_legs(iters, execute=True),
        "highres_geometry": list(HIGHRES_GEOMETRY),
        # 1080p execution is TPU-only: interpreter-mode matmuls at 32k
        # queries are minutes/iteration off-chip, and the leg's point —
        # the footprint — comes from the compile
        "highres": highres_leg(iters, execute_flash=on_tpu),
        "chained": chained_leg(iters),
    }
    validate_record(rec)
    print(json.dumps(rec), flush=True)
    return rec


# ---------------------------------------------------------------------------
# legacy single-run mode (the original probe)
# ---------------------------------------------------------------------------

def run_single(args) -> None:
    h, w = args.size
    assert h % 16 == 0 and w % 16 == 0

    from dexiraft_tpu.config import raft_v5
    from dexiraft_tpu.models.raft import RAFT

    platform = jax.devices()[0].platform
    print(f"platform={platform} size={h}x{w} chunk={args.chunk} "
          f"iters={args.iters} impl={args.impl}", file=sys.stderr)

    vol_bytes = 2 * (h // 8 * w // 8) ** 2 * 4  # level 0 only; pyramid +1/3
    print(f"materialized level-0 volume would need {vol_bytes / 1e9:.1f} GB; "
          f"on-demand transient ~"
          f"{2 * args.chunk * (w // 8) * (h // 8) * (w // 8) * 4 / 1e9:.2f} GB",
          file=sys.stderr)

    if args.impl == "flash" and platform != "tpu":
        # the kernel can only lower off-TPU in interpreter mode
        os.environ.setdefault("DEXIRAFT_PALLAS_INTERPRET", "1")
        pallas_corr._FLASH_PIXEL_BLOCK = 2048
    cfg = raft_v5(mixed_precision=(platform == "tpu"), corr_impl=args.impl,
                  corr_row_chunk=args.chunk,
                  fused_update=args.impl == "flash")
    model = RAFT(cfg)
    rng = jax.random.PRNGKey(0)
    small = jnp.zeros((1, 64, 64, 3), jnp.float32)
    init = jax.jit(lambda r, a, b: model.init(r, a, b, iters=1, train=False))
    variables = jax.block_until_ready(init(rng, small, small))
    print("init done", file=sys.stderr)

    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    im1 = jax.random.uniform(k1, (1, h, w, 3), jnp.float32, 0, 255)
    im2 = jax.random.uniform(k2, (1, h, w, 3), jnp.float32, 0, 255)

    @jax.jit
    def fwd(a, b):
        low, up = model.apply(variables, a, b, iters=args.iters,
                              train=False, test_mode=True)
        return jnp.sum(low) + jnp.sum(up)

    import math

    t0 = time.perf_counter()
    s = float(jax.device_get(fwd(im1, im2)))
    print(f"compile+first forward {time.perf_counter() - t0:.1f}s "
          f"(finite={math.isfinite(s)})", file=sys.stderr)
    t0 = time.perf_counter()
    s = float(jax.device_get(fwd(im1, im2)))
    dt = time.perf_counter() - t0
    print(f"steady-state {dt * 1e3:.1f} ms / forward "
          f"({args.iters} iters at {h}x{w}); finite={math.isfinite(s)}")


def main():
    from dexiraft_tpu.config import CORR_IMPLS

    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="record", choices=["record", "single"],
                    help="record = the pinned strict-mode JSON record "
                         "(eval A/B + 1080p + chained); single = the "
                         "legacy one-geometry probe")
    ap.add_argument("--size", type=int, nargs=2, default=(1440, 2560))
    ap.add_argument("--chunk", type=int, default=8,
                    help="query-row chunk for the on-demand path")
    ap.add_argument("--iters", type=int, default=None,
                    help="refinement iterations (record mode default: "
                         "8 on TPU, 2 on the CPU)")
    ap.add_argument("--impl", default="local",
                    choices=CORR_IMPLS,
                    help="corr path for --mode single")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend (same as JAX_PLATFORMS=cpu)")
    args = ap.parse_args()
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    if args.mode == "record":
        run_record(args)
    else:
        if args.iters is None:
            args.iters = 8
        run_single(args)


if __name__ == "__main__":
    main()
