"""A/B the correlation lookup on the real chip at Sintel eval shape.

Two experiment rounds are left (the per-round output formats are pinned,
logs/ carries records in them). Rounds 1 and 2 (`--variant 1|2`: bf16
storage, both streams through one set of einsums, contraction orders, one
block-diagonal matmul for all levels) compared formulations of the lookup
on the reference's flattening, one (H_l, W_l) slab per query; the stored
pyramid left that form in PR 32 (ops/corr.py: queries on the lanes, timed
against it in CHANGES.md), and the rounds went with it. docs/perf.md
keeps what they found.

  --variant 3   bf16 inputs for the on-demand (local) corr path
    fp32/bf16/bf16_all timing + max|delta| accuracy bound per variant

  --variant 4   the two lookup FORMULATIONS head-to-head (ISSUE 12):
    allpairs   materialized volume + corr_lookup
    flash      flash-blocked kernel — fmap2 row-block-streamed from HBM,
               partial-volume MXU matmuls, no materialized volume
    In a CPU run the Pallas leg runs in interpreter mode at a reduced
    geometry/iteration count (printed) — code-path proof only.

Each timed run is 32 chained 2-stream lookups inside one scan
(carry-dependent so iterations cannot be collapsed), one scalar out,
fetched to the host: the fetch waits for the device.
"""

from __future__ import annotations

import argparse
import os.path as osp
import sys
import time

sys.path.insert(0, osp.dirname(osp.dirname(osp.abspath(__file__))))

import jax
import jax.numpy as jnp

from dexiraft_tpu.ops.corr import (
    _axis_interp_matrix,
    avg_pool_2x2,
    build_corr_pyramid,
)
from dexiraft_tpu.ops.grid import coords_grid

H8, W8, C = 55, 128, 256
ITERS = 32
RADIUS = R = 4
WIN = 2 * R + 1
B3 = 2  # variant-3 dual-stream batch


# ---------------------------------------------------------------------------
# variant 3: bf16 inputs for the on-demand path (original lookup_ab3.py)
# ---------------------------------------------------------------------------
# The local path recomputes the all-pairs block f1·f2ᵀ every iteration —
# MXU FLOPs, not HBM reads, so input precision is the lever: fp32 matmuls
# on TPU run as multi-pass bf16 decompositions, while native bf16 inputs
# with fp32 accumulation (preferred_element_type) are one pass.

def _fmaps3():
    key = jax.random.PRNGKey(0)
    f1 = jax.random.normal(key, (B3, H8, W8, C), jnp.float32)
    f2 = jax.random.normal(jax.random.fold_in(key, 1), (B3, H8, W8, C))
    return f1, f2


def local_level(f1, f2, centers, in_dtype, hat_dtype):
    """One level of the on-demand lookup at the given precisions."""
    b, h, w, c = f1.shape
    n = b * h * w
    q = f1.reshape(b, h * w, c).astype(in_dtype)
    t = f2.reshape(b, -1, c).astype(in_dtype)
    vol = jnp.einsum("bnd,bmd->bnm", q, t,
                     preferred_element_type=jnp.float32)
    vol = (vol / jnp.sqrt(jnp.float32(c))).reshape(n, f2.shape[1], f2.shape[2])
    ay = _axis_interp_matrix(centers[:, 1], R, f2.shape[1]).astype(hat_dtype)
    ax = _axis_interp_matrix(centers[:, 0], R, f2.shape[2]).astype(hat_dtype)
    win = jnp.einsum("nby,nyx,nax->nab", ay, vol.astype(hat_dtype), ax,
                     preferred_element_type=jnp.float32)
    return win.reshape(n, WIN * WIN)


def make_run(in_dtype, hat_dtype):
    @jax.jit
    def run(f1, f2):
        pyr2 = [f2]
        for _ in range(3):
            pyr2.append(avg_pool_2x2(pyr2[-1]))
        coords = coords_grid(B3, H8, W8)

        def body(co, _):
            flat = co.reshape(-1, 2)
            out = [local_level(f1, lvl, flat / (2.0 ** i), in_dtype, hat_dtype)
                   for i, lvl in enumerate(pyr2)]
            s = jnp.concatenate(out, axis=-1).reshape(B3, H8, W8, -1)
            return co + 0.01 * s.mean(axis=-1, keepdims=True), None

        co, _ = jax.lax.scan(body, coords, None, length=ITERS)
        return jnp.sum(co)

    return run


def main_v3():
    f1, f2 = _fmaps3()

    # accuracy bound: one lookup at identity coords, each variant vs fp32
    flat = coords_grid(B3, H8, W8).reshape(-1, 2)
    ref = local_level(f1, f2, flat, jnp.float32, jnp.float32)
    for name, dts in [("bf16", (jnp.bfloat16, jnp.float32)),
                      ("bf16_all", (jnp.bfloat16, jnp.bfloat16))]:
        d = jnp.max(jnp.abs(local_level(f1, f2, flat, *dts) - ref))
        r = jnp.max(jnp.abs(ref))
        print(f"{name:>10s}: max|delta| {float(d):.4f} on max|corr| {float(r):.2f}")

    for name, dts in [("fp32", (jnp.float32, jnp.float32)),
                      ("bf16", (jnp.bfloat16, jnp.float32)),
                      ("bf16_all", (jnp.bfloat16, jnp.bfloat16))]:
        run = make_run(*dts)
        float(run(f1, f2))
        t0 = time.perf_counter()
        for _ in range(3):
            float(run(f1, f2))
        dt = (time.perf_counter() - t0) / 3
        print(f"{name:>10s}: {dt * 1e3:8.1f} ms total, "
              f"{dt / ITERS * 1e3:6.2f} ms/iter")


# ---------------------------------------------------------------------------
# variant 4: the two formulations head-to-head (ISSUE 12)
# ---------------------------------------------------------------------------
# allpairs amortizes one volume build over the loop but streams the
# O(N^2) volume from HBM every lookup; flash-blocked recomputes the needed
# partial-volume blocks as MXU matmuls with only the fmaps in HBM.

def main_v4():
    import os

    from dexiraft_tpu.ops.local_corr import build_local_corr

    on_tpu = jax.devices()[0].platform == "tpu"
    h8, w8, iters = (H8, W8, ITERS) if on_tpu else (16, 32, 4)
    if not on_tpu:
        # interpreter-mode kernels at the full geometry are debug-speed:
        # the CPU leg proves the code paths, not the ordering
        os.environ.setdefault("DEXIRAFT_PALLAS_INTERPRET", "1")
        print(f"cpu run: reduced geometry {h8}x{w8}, {iters} iters "
              "— code-path proof only, interpret-mode kernels",
              file=sys.stderr)

    key = jax.random.PRNGKey(0)
    f1 = jax.random.normal(key, (1, h8, w8, C), jnp.float32)
    f2 = jax.random.normal(jax.random.fold_in(key, 1), (1, h8, w8, C))

    def run_for(make_lookup):
        @jax.jit
        def run(f1, f2):
            lkp, lkp2 = make_lookup(f1, f2)
            coords = coords_grid(1, h8, w8)

            def body(co, _):
                s = lkp(co) + lkp2(co)
                co = co + 0.01 * s.mean(axis=-1, keepdims=True)
                return co, None

            co, _ = jax.lax.scan(body, coords, None, length=iters)
            return jnp.sum(co)

        return run

    def time_leg(name, make_lookup):
        run = run_for(make_lookup)
        float(run(f1, f2))
        t0 = time.perf_counter()
        reps = 3 if on_tpu else 1
        for _ in range(reps):
            float(run(f1, f2))
        dt = (time.perf_counter() - t0) / reps
        print(f"{name:>10s}: {dt * 1e3:8.1f} ms total, "
              f"{dt / iters * 1e3:6.2f} ms/iter")

    time_leg("allpairs", lambda a, b: (build_corr_pyramid(a, b, 4, RADIUS),
                                       build_corr_pyramid(b, a, 4, RADIUS)))
    time_leg("flash", lambda a, b: (
        build_local_corr(a, b, 4, RADIUS, kernel="flash"),
        build_local_corr(b, a, 4, RADIUS, kernel="flash")))


def main():
    ap = argparse.ArgumentParser(
        "lookup_ab", description="corr-lookup A/B experiment rounds")
    ap.add_argument("--variant", type=int, choices=[3, 4], default=4,
                    help="3 = bf16-input round of the local path, "
                         "4 = allpairs vs flash-blocked")
    args = ap.parse_args()
    print(f"platform={jax.devices()[0].platform}", file=sys.stderr)
    {3: main_v3, 4: main_v4}[args.variant]()


if __name__ == "__main__":
    main()
