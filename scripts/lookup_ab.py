"""A/B the corr_lookup formulation on the real chip at Sintel eval shape.

One script, three experiment rounds (formerly lookup_ab.py / lookup_ab2.py
/ lookup_ab3.py — consolidated; the per-round output formats are pinned,
logs/ carries records in them):

  --variant 1   formulation A/B:
    matmul    one-hot separable matmul (current corr_lookup)
    matmul16  same but the volume stored bf16 (halved HBM traffic)
    batched   both streams' lookups through ONE set of einsums
    batched16 the whole lookup in bf16 (hats + volume), fp32 accumulate

  --variant 2   second round — where do the 2.9 ms/iter go?
    current/xfirst/fused   contraction-order A/B on interp_window
    build_only             just the one-hot A matrices each iteration
    mm_only                pre-built A matrices, only the matmuls
    blockdiag              all 4 levels through ONE block-diagonal matmul

  --variant 3   bf16 inputs for the on-demand (local) corr path
    fp32/bf16/bf16_all timing + max|delta| accuracy bound per variant

  --variant 4   the two lookup FORMULATIONS head-to-head (ISSUE 12):
    allpairs   materialized volume + one-hot matmul lookup (corr_lookup)
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
    CorrPyramid,
    _axis_interp_matrix,
    avg_pool_2x2,
    build_corr_pyramid,
    corr_lookup,
)
from dexiraft_tpu.ops.grid import coords_grid

H8, W8, C = 55, 128, 256
ITERS = 32
RADIUS = R = 4
WIN = 2 * R + 1
B3 = 2  # variant-3 dual-stream batch


# ---------------------------------------------------------------------------
# variant 1: lookup formulation A/B (original lookup_ab.py)
# ---------------------------------------------------------------------------

def slice_lookup(pyramid: CorrPyramid, coords: jax.Array) -> jax.Array:
    r = pyramid.radius
    b, h, w = pyramid.batch, pyramid.ht, pyramid.wd
    win = 2 * r + 1
    k = 2 * r + 2
    pad = k
    flat = coords.reshape(b * h * w, 2).astype(jnp.float32)
    out = []
    for i, corr in enumerate(pyramid.levels):
        hl, wl = corr.shape[1], corr.shape[2]
        c = flat / (2.0 ** i)
        x = jnp.clip(c[:, 0], -(r + 1.0), wl - 1 + r + 1.0)
        y = jnp.clip(c[:, 1], -(r + 1.0), hl - 1 + r + 1.0)
        x0 = jnp.floor(x)
        y0 = jnp.floor(y)
        fx = (x - x0)[:, None, None]
        fy = (y - y0)[:, None, None]
        sx = x0.astype(jnp.int32) + (r + 2)
        sy = y0.astype(jnp.int32) + (r + 2)
        volp = jnp.pad(corr[..., 0], ((0, 0), (pad, pad), (pad, pad)))

        patch = jax.vmap(
            lambda v, py, px: jax.lax.dynamic_slice(v, (py, px), (k, k))
        )(volp, sy, sx)  # (N, k, k)

        tl = patch[:, 0:win, 0:win]
        tr = patch[:, 0:win, 1:win + 1]
        bl = patch[:, 1:win + 1, 0:win]
        br = patch[:, 1:win + 1, 1:win + 1]
        o = ((1 - fy) * (1 - fx) * tl + (1 - fy) * fx * tr
             + fy * (1 - fx) * bl + fy * fx * br)
        out.append(o.swapaxes(1, 2).reshape(b, h, w, win * win))
    return jnp.concatenate(out, axis=-1)


def bench(name, lookup, cast=lambda x: x):
    key = jax.random.PRNGKey(0)
    f1 = jax.random.normal(key, (1, H8, W8, C), jnp.float32)
    f2 = jax.random.normal(jax.random.fold_in(key, 1), (1, H8, W8, C))

    @jax.jit
    def run(f1, f2):
        pyr = build_corr_pyramid(f1, f2, 4, RADIUS)
        pyr2 = build_corr_pyramid(f2, f1, 4, RADIUS)
        pyr = pyr.replace(levels=tuple(cast(l) for l in pyr.levels))
        pyr2 = pyr2.replace(levels=tuple(cast(l) for l in pyr2.levels))
        coords = coords_grid(1, H8, W8)

        def body(co, _):
            s = lookup(pyr, co) + lookup(pyr2, co)
            co = co + 0.01 * s.mean(axis=-1, keepdims=True)
            return co, None

        co, _ = jax.lax.scan(body, coords, None, length=ITERS)
        return jnp.sum(co)

    float(run(f1, f2))
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        float(run(f1, f2))
    dt = (time.perf_counter() - t0) / reps
    print(f"{name:>10s}: {dt * 1e3:8.1f} ms total, "
          f"{dt / ITERS * 1e3:6.2f} ms/iter")


def bench_batched(name, adt):
    """Both streams' lookups through ONE set of einsums: pyramids built
    from batch-2 fmaps (N doubles, matmul count halves); optionally the
    whole lookup in bf16 (one-hot A and volume) with fp32 accumulate."""
    key = jax.random.PRNGKey(0)
    f1 = jax.random.normal(key, (2, H8, W8, C), jnp.float32)
    f2 = jax.random.normal(jax.random.fold_in(key, 1), (2, H8, W8, C))

    def lookup(pyr, coords):
        r, b, h, w = pyr.radius, pyr.batch, pyr.ht, pyr.wd
        win = 2 * r + 1
        flat = coords.reshape(b * h * w, 2).astype(jnp.float32)
        out = []
        for i, corr in enumerate(pyr.levels):
            hl, wl = corr.shape[1], corr.shape[2]
            center = flat / (2.0 ** i)
            ax = _axis_interp_matrix(center[:, 0], r, wl).astype(adt)
            ay = _axis_interp_matrix(center[:, 1], r, hl).astype(adt)
            vol = corr[..., 0].astype(adt)
            rows = jnp.einsum("nby,nyx->nbx", ay, vol,
                              preferred_element_type=jnp.float32).astype(adt)
            window = jnp.einsum("nax,nbx->nab", ax, rows,
                                preferred_element_type=jnp.float32)
            out.append(window.reshape(b, h, w, win * win))
        return jnp.concatenate(out, axis=-1).astype(jnp.float32)

    @jax.jit
    def run(f1, f2):
        pyr = build_corr_pyramid(f1, f2, 4, RADIUS)  # batch-2 = 2 streams
        coords = coords_grid(2, H8, W8)

        def body(co, _):
            s = lookup(pyr, co)
            co = co + 0.01 * s.mean(axis=-1, keepdims=True)
            return co, None

        co, _ = jax.lax.scan(body, coords, None, length=ITERS)
        return jnp.sum(co)

    float(run(f1, f2))
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        float(run(f1, f2))
    dt = (time.perf_counter() - t0) / reps
    print(f"{name:>10s}: {dt * 1e3:8.1f} ms total, "
          f"{dt / ITERS * 1e3:6.2f} ms/iter")


def main_v1():
    bench("matmul", corr_lookup)
    bench("matmul16", corr_lookup,
          cast=lambda l: l.astype(jnp.bfloat16))
    bench_batched("batched", jnp.float32)
    bench_batched("batched16", jnp.bfloat16)


# ---------------------------------------------------------------------------
# variant 2: second-round lookup experiments (original lookup_ab2.py)
# ---------------------------------------------------------------------------

def _pyr2():
    key = jax.random.PRNGKey(0)
    f1 = jax.random.normal(key, (2, H8, W8, C), jnp.float32)
    f2 = jax.random.normal(jax.random.fold_in(key, 1), (2, H8, W8, C))
    return f1, f2


def _time(name, run, *args):
    float(run(*args))
    t0 = time.perf_counter()
    for _ in range(3):
        float(run(*args))
    dt = (time.perf_counter() - t0) / 3
    print(f"{name:>10s}: {dt * 1e3:8.1f} ms total, {dt / ITERS * 1e3:6.2f} ms/iter")


def bench_lookup(name, level_fn):
    f1, f2 = _pyr2()

    @jax.jit
    def run(f1, f2):
        pyr = build_corr_pyramid(f1, f2, 4, R)
        coords = coords_grid(2, H8, W8)

        def body(co, _):
            flat = co.reshape(-1, 2)
            out = []
            for i, corr in enumerate(pyr.levels):
                out.append(level_fn(corr[..., 0], flat / (2.0 ** i)))
            s = jnp.concatenate(out, axis=-1).reshape(2, H8, W8, -1)
            return co + 0.01 * s.mean(axis=-1, keepdims=True), None

        co, _ = jax.lax.scan(body, coords, None, length=ITERS)
        return jnp.sum(co)

    _time(name, run, f1, f2)


def lvl_current(vol, centers):
    ay = _axis_interp_matrix(centers[:, 1], R, vol.shape[1])
    ax = _axis_interp_matrix(centers[:, 0], R, vol.shape[2])
    rows = jnp.einsum("nby,nyx->nbx", ay, vol,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("nax,nbx->nab", ax, rows,
                      preferred_element_type=jnp.float32).reshape(
        vol.shape[0], WIN * WIN)


def lvl_xfirst(vol, centers):
    ay = _axis_interp_matrix(centers[:, 1], R, vol.shape[1])
    ax = _axis_interp_matrix(centers[:, 0], R, vol.shape[2])
    cols = jnp.einsum("nax,nyx->nay", ax, vol,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("nby,nay->nab", ay, cols,
                      preferred_element_type=jnp.float32).reshape(
        vol.shape[0], WIN * WIN)


def lvl_fused(vol, centers):
    ay = _axis_interp_matrix(centers[:, 1], R, vol.shape[1])
    ax = _axis_interp_matrix(centers[:, 0], R, vol.shape[2])
    return jnp.einsum("nby,nyx,nax->nab", ay, vol, ax,
                      preferred_element_type=jnp.float32).reshape(
        vol.shape[0], WIN * WIN)


def bench_build_only():
    f1, f2 = _pyr2()

    @jax.jit
    def run(f1, f2):
        coords = coords_grid(2, H8, W8)
        sizes = [(H8, W8), (27, 64), (13, 32), (6, 16)]

        def body(co, _):
            flat = co.reshape(-1, 2)
            acc = 0.0
            for i, (hl, wl) in enumerate(sizes):
                c = flat / (2.0 ** i)
                ay = _axis_interp_matrix(c[:, 1], R, hl)
                ax = _axis_interp_matrix(c[:, 0], R, wl)
                acc = acc + ay.sum() + ax.sum()
            return co + 1e-9 * acc, None

        co, _ = jax.lax.scan(body, coords, None, length=ITERS)
        return jnp.sum(co)

    _time("build_only", run, f1, f2)


def bench_mm_only():
    f1, f2 = _pyr2()

    @jax.jit
    def run(f1, f2):
        pyr = build_corr_pyramid(f1, f2, 4, R)
        coords = coords_grid(2, H8, W8)
        flat = coords.reshape(-1, 2)
        mats = []
        for i, corr in enumerate(pyr.levels):
            c = flat / (2.0 ** i)
            mats.append((_axis_interp_matrix(c[:, 1], R, corr.shape[1]),
                         _axis_interp_matrix(c[:, 0], R, corr.shape[2])))

        def body(carry, _):
            acc = carry
            outs = []
            for (ay, ax), corr in zip(mats, pyr.levels):
                vol = corr[..., 0] + acc  # keep iteration-dependent
                rows = jnp.einsum("nby,nyx->nbx", ay, vol,
                                  preferred_element_type=jnp.float32)
                w = jnp.einsum("nax,nbx->nab", ax, rows,
                               preferred_element_type=jnp.float32)
                outs.append(w.sum())
            return acc + 1e-9 * sum(outs), None

        acc, _ = jax.lax.scan(body, jnp.float32(0), None, length=ITERS)
        return acc

    _time("mm_only", run, f1, f2)


def bench_blockdiag():
    """All 4 levels' y-einsums fused into ONE batched matmul against a
    block-diagonal concatenated volume (built once, loop-invariant);
    probes whether per-matmul-instance overhead dominates."""
    f1, f2 = _pyr2()
    sizes = [(55, 128), (27, 64), (13, 32), (6, 16)]
    yoff = [0, 55, 82, 95]
    xoff = [0, 128, 192, 224]
    ktot, xtot = 101, 240

    @jax.jit
    def run(f1, f2):
        pyr = build_corr_pyramid(f1, f2, 4, R)
        n = 2 * H8 * W8
        vol_cat = jnp.zeros((n, ktot, xtot), jnp.float32)
        for lvl, corr in enumerate(pyr.levels):
            hl, wl = sizes[lvl]
            vol_cat = jax.lax.dynamic_update_slice(
                vol_cat, corr[..., 0], (0, yoff[lvl], xoff[lvl]))
        coords = coords_grid(2, H8, W8)

        def hats(flat):
            ays, axs = [], []
            for lvl in range(4):
                c = flat / (2.0 ** lvl)
                hl, wl = sizes[lvl]
                ays.append(_axis_interp_matrix(c[:, 1], R, hl))
                axs.append(_axis_interp_matrix(c[:, 0], R, wl))
            # place each level's hat into its global K/X range
            ay = jnp.zeros((flat.shape[0], 4, WIN, ktot), jnp.float32)
            ax = jnp.zeros((flat.shape[0], 4, WIN, xtot), jnp.float32)
            for lvl in range(4):
                hl, wl = sizes[lvl]
                ay = ay.at[:, lvl, :, yoff[lvl]:yoff[lvl] + hl].set(ays[lvl])
                ax = ax.at[:, lvl, :, xoff[lvl]:xoff[lvl] + wl].set(axs[lvl])
            return ay.reshape(-1, 4 * WIN, ktot), ax

        def body(co, _):
            flat = co.reshape(-1, 2)
            ay, ax = hats(flat)
            rows = jnp.einsum("nby,nyx->nbx", ay, vol_cat,
                              preferred_element_type=jnp.float32)
            rows = rows.reshape(-1, 4, WIN, xtot)
            w = jnp.einsum("nlax,nlbx->nlab", ax, rows,
                           preferred_element_type=jnp.float32)
            s = w.reshape(2, H8, W8, -1)
            return co + 0.01 * s.mean(axis=-1, keepdims=True), None

        co, _ = jax.lax.scan(body, coords, None, length=ITERS)
        return jnp.sum(co)

    _time("blockdiag", run, f1, f2)


def main_v2():
    bench_lookup("current", lvl_current)
    bench_lookup("xfirst", lvl_xfirst)
    bench_lookup("fused", lvl_fused)
    bench_build_only()
    bench_mm_only()
    bench_blockdiag()


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
    ap.add_argument("--variant", type=int, choices=[1, 2, 3, 4], default=1,
                    help="1 = formulation A/B, 2 = contraction-order / "
                         "instance-overhead round, 3 = bf16-input round, "
                         "4 = allpairs vs flash-blocked")
    args = ap.parse_args()
    print(f"platform={jax.devices()[0].platform}", file=sys.stderr)
    {1: main_v1, 2: main_v2, 3: main_v3, 4: main_v4}[args.variant]()


if __name__ == "__main__":
    main()
