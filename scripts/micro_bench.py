"""Component microbenchmark on the real chip — where does the forward go?

Times, around work that ends in block_until_ready:
  volume       all-pairs matmul + pyramid (x2 streams)
  dexi_b_bf16  the shipped DexiNed prelude (one batched bf16 call)
  enc_x4       4 encoder passes at eval res
  lookup32     32 chained corr_lookup calls (both streams, carry-dependent)
  lkp32_<dt>   the same loop with the pyramid stored fp32/bf16/int8
               (--corr_dtype sweep; each line also reports the estimated
               correlation bytes each lookup streams from HBM — the
               quantization win made legible even in a CPU run)
  flash32_<dt> the same chained loop through the flash-blocked kernel
               (ops/pallas_corr.py, ISSUE 12): no materialized volume —
               its bytes column is the O(fmaps) streaming BOUND, vs the
               O(N^2) volume bytes of lkp32. Interpreter-mode
               (debug-speed) in a CPU run; with lookup_ab
               --variant 4 the pinned records cover both
               formulations (allpairs / flash)
  forward      the full v5 test-mode forward (sanity: ~ sum of the above)
  fwd_iter1    iters=1 forward -> per-iteration + prelude split
  fwd_sp_unr4  candidate config: scan_unroll=4 (XLA software pipelining)

Run:  python scripts/micro_bench.py [--impl allpairs]
                                    [--corr_dtype {fp32,bf16,int8,all}]
"""

from __future__ import annotations

import argparse
import os.path as osp
import sys
import time

sys.path.insert(0, osp.dirname(osp.dirname(osp.abspath(__file__))))

import jax
import jax.numpy as jnp

HEIGHT, WIDTH = 440, 1024
ITERS = 32


def timeit(name, fn, *args, reps=3, strict=False):
    """Mean wall time of jitted ``fn`` over ``reps`` calls that each end
    in block_until_ready.

    strict=True arms guards.strict_mode around the post-warmup reps (the
    PR 5 steady-state contract): a retrace or implicit transfer inside
    the timed window fails the run instead of deflating the number."""
    jitted = jax.jit(fn)
    jax.block_until_ready(jitted(*args))  # compile + warmup
    import contextlib

    from dexiraft_tpu.analysis import guards

    ctx = (guards.strict_mode(label=f"micro_bench:{name}") if strict
           else contextlib.nullcontext())
    with ctx:
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(jitted(*args))
        dt = (time.perf_counter() - t0) / reps
    print(f"{name:>11s}: {dt * 1e3:8.1f} ms")
    return dt


def corr_bytes_per_lookup(batch: int, h8: int, w8: int, num_levels: int,
                          corr_dtype: str) -> int:
    """Estimated bytes ONE all-pairs corr_lookup streams from HBM: every
    pyramid level is read once per lookup, by the x axis' alignment (the
    kernel fetches a block of the level into VMEM once; corr_lookup is
    volume-streaming by construction — docs/perf.md).
    Level dims floor-halve exactly like build_corr_pyramid's VALID pool."""
    from dexiraft_tpu.ops.quant import corr_dtype_bytes

    n = batch * h8 * w8
    total = 0
    hl, wl = h8, w8
    for _ in range(num_levels):
        total += n * hl * wl * corr_dtype_bytes(corr_dtype)
        hl, wl = hl // 2, wl // 2
    return total


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--impl", default="allpairs")
    ap.add_argument("--corr_dtype", default="all",
                    choices=["fp32", "bf16", "int8", "all"],
                    help="pyramid storage precision(s) for the lkp32 "
                         "sweep ('all' = sweep the three)")
    ap.add_argument("--corr_sweep_only", action="store_true",
                    help="run the corr_dtype lookup sweep and exit — the "
                         "CPU A/B (the full component profile costs "
                         "minutes off-chip)")
    args = ap.parse_args()

    from dexiraft_tpu.config import raft_v5
    from dexiraft_tpu.models.raft import RAFT
    from dexiraft_tpu.ops.corr import build_corr_pyramid, corr_lookup
    from dexiraft_tpu.ops.grid import coords_grid

    print(f"platform={jax.devices()[0].platform} "
          f"device_kind={jax.devices()[0].device_kind}", file=sys.stderr)

    h8, w8, c = HEIGHT // 8, WIDTH // 8, 256
    kf1, kf2 = jax.random.split(jax.random.PRNGKey(0))
    f1 = jax.random.normal(kf1, (1, h8, w8, c), jnp.float32)
    f2 = jax.random.normal(kf2, (1, h8, w8, c))

    # --- volume build (both streams, all levels) ---
    def volume(f1, f2):
        p1 = build_corr_pyramid(f1, f2, 4, 4)
        p2 = build_corr_pyramid(f2, f1, 4, 4)
        return p1.levels + p2.levels

    if not args.corr_sweep_only:
        timeit("volume", volume, f1, f2)

    # --- pyramid storage-precision sweep (ISSUE 8): 32 chained 2-stream
    # lookups with the volume stored fp32/bf16/int8, timed inside a
    # strict steady-state window (a retrace or implicit transfer FAILS
    # the run), plus the bytes each lookup streams — the quantization
    # lever is bandwidth, so the bytes column is the prediction and the
    # ms column the measurement ---
    dtypes = (("fp32", "bf16", "int8") if args.corr_dtype == "all"
              else (args.corr_dtype,))
    t_by_dtype = {}
    for dt in dtypes:
        def lookup32_q(f1, f2, dt=dt):
            pyr = build_corr_pyramid(f1, f2, 4, 4, dtype=dt)
            pyr2 = build_corr_pyramid(f2, f1, 4, 4, dtype=dt)
            coords = coords_grid(1, h8, w8)

            def body(co, _):
                s = corr_lookup(pyr, co)
                s2 = corr_lookup(pyr2, co)
                co = co + 0.01 * (s.mean(axis=-1, keepdims=True)
                                  + s2.mean(axis=-1, keepdims=True))
                return co, None

            co, _ = jax.lax.scan(body, coords, None, length=ITERS)
            return co

        t_q = timeit(f"lkp32_{dt}", lookup32_q, f1, f2, strict=True)
        t_by_dtype[dt] = t_q
        mb = 2 * corr_bytes_per_lookup(1, h8, w8, 4, dt) / 1e6  # 2 streams
        print(f"  -> {dt}: {mb:8.1f} MB corr bytes/lookup, "
              f"{t_q / ITERS * 1e3:6.1f} ms/iter "
              f"({mb / max(t_q / ITERS, 1e-9) / 1e3:6.2f} GB/s implied)")

    # --- the flash-blocked formulation at the same dtypes (ISSUE 12):
    # fmap2 stays in HBM and streams in row blocks, so the volume bytes
    # above disappear entirely — the printed bound is the whole fmap
    # set, the most a lookup can stream. Off-TPU the kernel runs in
    # interpreter mode (debug-speed; timings prove the path is
    # compile-flat and transfer-clean, nothing more) ---
    import os

    from dexiraft_tpu.ops.quant import corr_dtype_bytes
    from dexiraft_tpu.ops.local_corr import build_local_corr

    if jax.devices()[0].platform != "tpu":
        os.environ.setdefault("DEXIRAFT_PALLAS_INTERPRET", "1")
    for dt in dtypes:
        def flash32_q(f1, f2, dt=dt):
            lc = build_local_corr(f1, f2, 4, 4, dtype=dt, kernel="flash")
            lc2 = build_local_corr(f2, f1, 4, 4, dtype=dt, kernel="flash")
            coords = coords_grid(1, h8, w8)

            def body(co, _):
                s = lc(co)
                s2 = lc2(co)
                co = co + 0.01 * (s.mean(axis=-1, keepdims=True)
                                  + s2.mean(axis=-1, keepdims=True))
                return co, None

            co, _ = jax.lax.scan(body, coords, None, length=ITERS)
            return co

        t_f = timeit(f"flash32_{dt}", flash32_q, f1, f2, strict=True)
        n = h8 * w8
        pyr_cells = sum((n >> (2 * i)) * c for i in range(4))
        # fmap1 is read fp32; the fmap2 pyramid streams in the storage
        # dtype — and only the row blocks the windows touch, so this is
        # an upper bound, not an estimate
        mb = 2 * (n * c * 4 + pyr_cells * corr_dtype_bytes(dt)) / 1e6
        print(f"  -> {dt}: <= {mb:6.1f} MB fmap bytes/lookup "
              f"(O(fmaps) bound — no volume), "
              f"{t_f / ITERS * 1e3:6.1f} ms/iter")
    if args.corr_sweep_only:
        return

    # --- DexiNed + encoders at eval res ---
    # (the historical fp32 two-call "dexined_x2" comparison is gone: its
    # conv_transpose graph at full 440x1024 compiled for >20 min on-chip
    # and timed the whole job out, 2026-08-02 queue run. The shipped
    # config is the batched bf16 call below; the transpose-vs-subpixel
    # A/B lives in prelude_profile.py and the bench 4-config sweep.)
    from dexiraft_tpu.models.dexined import DexiNed

    dimg = jnp.zeros((1, 64, 64, 3), jnp.float32)
    big = jax.random.uniform(jax.random.PRNGKey(3),
                             (1, HEIGHT, WIDTH, 3), jnp.float32, -1, 1)

    # the shipped v5 configuration: ONE batched call, bf16 body
    dexi16 = DexiNed(dtype=jnp.bfloat16, upconv="subpixel")
    dvars16 = jax.jit(lambda r, x: dexi16.init(r, x, train=False))(
        jax.random.PRNGKey(2), dimg)

    def dexined_batched_bf16(a):
        both = jnp.concatenate([a, -a], axis=0)
        return dexi16.apply(dvars16, both, train=False)[-1]

    timeit("dexi_b_bf16", dexined_batched_bf16, big)

    from dexiraft_tpu.models.extractor import Encoder

    enc = Encoder(256, "instance", 0.0, jnp.bfloat16)
    evars = jax.jit(lambda r, x: enc.init(r, x, train=False))(
        jax.random.PRNGKey(4), jnp.zeros((1, 64, 64, 3), jnp.bfloat16))

    def enc4(a):
        x = a.astype(jnp.bfloat16)
        return [enc.apply(evars, x, train=False) for _ in range(4)]

    timeit("enc_x4", enc4, big)

    # --- 32 chained lookups (2 streams): identical to the sweep's fp32
    # leg, so reuse its timing when it ran instead of compiling and
    # measuring the same scan twice ---
    if "fp32" in t_by_dtype:
        t_lookup = t_by_dtype["fp32"]
        print(f"{'lookup32':>11s}: = lkp32_fp32 ({t_lookup * 1e3:8.1f} ms)")
    else:
        @jax.jit
        def lookup32(f1, f2):
            pyr = build_corr_pyramid(f1, f2, 4, 4)
            pyr2 = build_corr_pyramid(f2, f1, 4, 4)
            coords = coords_grid(1, h8, w8)

            def body(carry, _):
                co = carry
                s = corr_lookup(pyr, co)
                s2 = corr_lookup(pyr2, co)
                co = co + 0.01 * (s.mean(axis=-1, keepdims=True)
                                  + s2.mean(axis=-1, keepdims=True))
                return co, None

            co, _ = jax.lax.scan(body, coords, None, length=ITERS)
            return co

        t_lookup = timeit("lookup32", lookup32, f1, f2)

    # --- full forward ---
    from dexiraft_tpu.config import raft_v5

    cfg = raft_v5(mixed_precision=True, corr_impl=args.impl)
    model = RAFT(cfg)
    img = jnp.zeros((1, 64, 64, 3), jnp.float32)
    init = jax.jit(lambda r, a, b: model.init(r, a, b, iters=1, train=False))
    variables = init(jax.random.PRNGKey(0), img, img)
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    im1 = jax.random.uniform(k1, (1, HEIGHT, WIDTH, 3), jnp.float32, 0, 255)
    im2 = jax.random.uniform(k2, (1, HEIGHT, WIDTH, 3), jnp.float32, 0, 255)

    @jax.jit
    def fwd(a, b):
        low, up = model.apply(variables, a, b, iters=ITERS, train=False,
                              test_mode=True)
        return jnp.sum(low) + jnp.sum(up)

    t_fwd = timeit("forward", fwd, im1, im2)

    # --- prelude: everything before the loop (iters=1 minus 1 lookup) ---
    @jax.jit
    def fwd1(a, b):
        low, up = model.apply(variables, a, b, iters=1, train=False,
                              test_mode=True)
        return jnp.sum(low) + jnp.sum(up)

    t_one = timeit("fwd_iter1", fwd1, im1, im2)
    per_iter = (t_fwd - t_one) / (ITERS - 1)
    print(f"  -> per-iteration cost {per_iter * 1e3:6.1f} ms; "
          f"prelude+1 {t_one * 1e3:.1f} ms; "
          f"lookup32/iter {t_lookup / ITERS * 1e3:6.1f} ms")

    # --- candidate shipping config: subpixel upconv (now the default)
    # + 4x unrolled scan (XLA can software-pipeline consecutive
    # refinement iterations) ---
    cfg_u = raft_v5(mixed_precision=True, corr_impl=args.impl,
                    scan_unroll=4)
    model_u = RAFT(cfg_u)

    @jax.jit
    def fwd_u(a, b):
        low, up = model_u.apply(variables, a, b, iters=ITERS, train=False,
                                test_mode=True)
        return jnp.sum(low) + jnp.sum(up)

    timeit("fwd_sp_unr4", fwd_u, im1, im2)


if __name__ == "__main__":
    main()
