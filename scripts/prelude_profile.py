"""On-chip profile of the v5 eval-forward PRELUDE — the part that gates
the end-to-end headline (its time against the ~4 ms ideal-MXU floor is
not measured on today's code).

Times each prelude component standalone at its production shape
(B=2: both frames batched through one DexiNed call; 440x1024 input),
in the production dtype (bf16 under mixed precision), around
block_until_ready like bench.py. The UpConv stages are timed in BOTH transposed-conv
implementations ("transpose" = lax.conv_transpose on the input-dilated
signal; "subpixel" = the numerically identical phase decomposition,
models/dexined.py:SubpixelConvTranspose) — the A/B that decides
config.dexined_upconv's default.

Usage: python scripts/prelude_profile.py [--cpu] [--fp32]
"""

from __future__ import annotations

import argparse
import os.path as osp
import sys
import time

sys.path.insert(0, osp.dirname(osp.dirname(osp.abspath(__file__))))

import jax
import jax.numpy as jnp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--fp32", action="store_true",
                    help="profile in fp32 instead of the production bf16")
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform
    dt = jnp.float32 if args.fp32 else jnp.bfloat16
    print(f"platform={platform} dtype={dt.__name__}", flush=True)

    from dexiraft_tpu.models.dexined import (
        DenseBlock,
        DexiNed,
        DoubleConvBlock,
        SingleConvBlock,
        UpConvBlock,
    )
    from dexiraft_tpu.models.extractor import BasicEncoder
    from dexiraft_tpu.ops.corr import build_corr_pyramid

    results = {}

    def bench(name, module, shapes, method=None):
        """Init `module` on random inputs of `shapes`, time jitted apply
        around block_until_ready. A component that throws fails the run."""
        keys = jax.random.split(jax.random.PRNGKey(0), len(shapes))
        xs = [jax.random.normal(k, s, jnp.float32) for k, s in zip(keys, shapes)]
        variables = jax.jit(lambda *a: module.init(
            jax.random.PRNGKey(1), *a))(*xs)
        fwd = jax.jit(lambda *a: module.apply(variables, *a))

        gflop = None
        cost = fwd.lower(*xs).compile().cost_analysis()
        if cost and cost.get("flops"):
            gflop = cost["flops"] / 1e9

        jax.block_until_ready(fwd(*xs))  # compile
        t0 = time.perf_counter()
        for _ in range(args.reps):
            jax.block_until_ready(fwd(*xs))
        dt_s = (time.perf_counter() - t0) / args.reps
        results[name] = dt_s
        eff = (f"  {gflop:8.1f} GFLOP -> {gflop / dt_s / 1e3:6.2f} TFLOP/s"
               if gflop else "")
        print(f"{name:>28s}: {dt_s * 1e3:8.2f} ms{eff}", flush=True)

    B = 2  # both frames in one batched DexiNed call (models/raft.py:190)
    H, W = 440, 1024

    # --- the full embedded-DexiNed forward, both upconv impls ---
    for impl in ("transpose", "subpixel"):
        bench(f"dexined_full[{impl}]",
              DexiNed(dtype=dt, upconv=impl), [(B, H, W, 3)])

    # --- DexiNed internals at production shapes ---
    bench("stem_double(3->32->64,s2)",
          DoubleConvBlock(32, 64, stride=2, dtype=dt), [(B, H, W, 3)])
    bench("block2_double(64->128)",
          DoubleConvBlock(128, use_act=False, dtype=dt),
          [(B, H // 2, W // 2, 64)])
    bench("dense3(2x256@110x256)", DenseBlock(2, 256, dtype=dt),
          [(B, H // 4, W // 4, 128), (B, H // 4, W // 4, 256)])
    bench("dense4(3x512@55x128)", DenseBlock(3, 512, dtype=dt),
          [(B, H // 8, W // 8, 256), (B, H // 8, W // 8, 512)])
    bench("dense5(3x512@28x64)", DenseBlock(3, 512, dtype=dt),
          [(B, 28, 64, 512), (B, 28, 64, 512)])
    bench("dense6(3x256@28x64)", DenseBlock(3, 256, dtype=dt),
          [(B, 28, 64, 512), (B, 28, 64, 256)])
    for impl in ("transpose", "subpixel"):
        bench(f"up1_b1[{impl}]", UpConvBlock(1, dtype=dt, upconv=impl),
              [(B, H // 2, W // 2, 64)])
        bench(f"up1_b2[{impl}]", UpConvBlock(1, dtype=dt, upconv=impl),
              [(B, H // 2, W // 2, 128)])
        bench(f"up2_b3[{impl}]", UpConvBlock(2, dtype=dt, upconv=impl),
              [(B, H // 4, W // 4, 256)])
        bench(f"up3_b4[{impl}]", UpConvBlock(3, dtype=dt, upconv=impl),
              [(B, H // 8, W // 8, 512)])
        bench(f"up4_b5[{impl}]", UpConvBlock(4, dtype=dt, upconv=impl),
              [(B, 28, 64, 512)])
        bench(f"up4_b6[{impl}]", UpConvBlock(4, dtype=dt, upconv=impl),
              [(B, 28, 64, 256)])
    bench("fusion_cat_1x1(6ch)", SingleConvBlock(1, use_bn=False, dtype=dt),
          [(B, H, W, 6)])

    # --- the RAFT side of the prelude, for scale ---
    bench("fnet(basic,instance)@full",
          BasicEncoder(output_dim=256, norm_fn="instance", dtype=dt),
          [(B, H, W, 3)])
    bench("cnet(basic,batch)@full",
          BasicEncoder(output_dim=256, norm_fn="batch", dtype=dt),
          [(B, H, W, 3)])

    @jax.jit
    def vol(f1, f2):
        pyr = build_corr_pyramid(f1, f2, num_levels=4, radius=4)
        return sum(jnp.sum(v) for v in pyr.levels)

    f1 = jax.random.normal(jax.random.PRNGKey(2), (1, H // 8, W // 8, 256))
    jax.block_until_ready(vol(f1, f1))
    t0 = time.perf_counter()
    for _ in range(args.reps):
        jax.block_until_ready(vol(f1, f1))
    raw = (time.perf_counter() - t0) / args.reps
    print(f"{'corr_pyramid_build':>28s}: {raw * 1e3:8.2f} ms", flush=True)

    # --- the refinement-loop components at loop shapes (B=2: the dual
    # streams share one batch; 55x128 = 440x1024 at 1/8) ---
    from dexiraft_tpu.config import raft_v5
    from dexiraft_tpu.models.update import BasicUpdateBlock
    from dexiraft_tpu.ops.grid import coords_grid

    h8, w8 = H // 8, W // 8
    bench("update_block(GRU+heads)", BasicUpdateBlock(hidden_dim=128, dtype=dt),
          [(2, h8, w8, 128), (2, h8, w8, 128), (2, h8, w8, 324),
           (2, h8, w8, 2)])

    for impl in ("allpairs", "local"):
        cfg = raft_v5(mixed_precision=not args.fp32, corr_impl=impl)
        f1 = jax.random.normal(jax.random.PRNGKey(3), (2, h8, w8, 256))
        f2 = jax.random.normal(jax.random.PRNGKey(4), (2, h8, w8, 256))

        @jax.jit
        def lookup_once(f1, f2):
            if impl == "allpairs":
                pyr = build_corr_pyramid(f1, f2, 4, 4)
            else:
                from dexiraft_tpu.ops.local_corr import build_local_corr
                pyr = build_local_corr(f1, f2, 4, 4, row_chunk=8)
            coords = coords_grid(2, h8, w8) + 1.3
            return jnp.sum(pyr(coords))

        jax.block_until_ready(lookup_once(f1, f2))
        t0 = time.perf_counter()
        for _ in range(args.reps):
            jax.block_until_ready(lookup_once(f1, f2))
        raw = (time.perf_counter() - t0) / args.reps
        print(f"{'build+lookup[' + impl + ']':>28s}: {raw * 1e3:8.2f} ms",
              flush=True)

    ups = [k for k in results if k.startswith("up") and "[" in k]
    t_total = sum(v for k, v in results.items()
                  if k.startswith("up") and "transpose" in k)
    s_total = sum(v for k, v in results.items()
                  if k.startswith("up") and "subpixel" in k)
    print(f"\nupconv stages total: transpose {t_total * 1e3:.2f} ms, "
          f"subpixel {s_total * 1e3:.2f} ms ({len(ups)} timed)", flush=True)
    if "dexined_full[transpose]" in results and "dexined_full[subpixel]" in results:
        print(f"dexined full: transpose "
              f"{results['dexined_full[transpose]'] * 1e3:.2f} ms, subpixel "
              f"{results['dexined_full[subpixel]'] * 1e3:.2f} ms", flush=True)


if __name__ == "__main__":
    main()
