"""Capture an on-chip profiler trace of the flagship v5 forward.

Where the prelude's time goes is a trace question: its small-channel
ops are each too small to time from the host. This job captures that
trace (xplane protos via `dexiraft_tpu.profiling.trace`,
SURVEY.md §5) at the bench geometry so any later session — or an
operator with TensorBoard's profile plugin / Perfetto — can read
per-fusion device times without needing chip access of their own.

Writes to logs/profile_trace/<platform>/ and prints the artifact list.

Usage: python scripts/profile_trace.py [--iters 32] [--reps 3] [--cpu]
"""

from __future__ import annotations

import argparse
import glob
import os
import os.path as osp
import sys

sys.path.insert(0, osp.dirname(osp.dirname(osp.abspath(__file__))))

import jax
import jax.numpy as jnp

HEIGHT, WIDTH = 440, 1024


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=32)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend (same as JAX_PLATFORMS=cpu)")
    args = ap.parse_args()
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    from dexiraft_tpu.config import raft_v5
    from dexiraft_tpu.models.raft import RAFT
    from dexiraft_tpu.profiling import trace

    platform = jax.devices()[0].platform
    print(f"platform={platform} geometry={HEIGHT}x{WIDTH} "
          f"iters={args.iters}", file=sys.stderr)

    cfg = raft_v5(mixed_precision=(platform == "tpu"))
    model = RAFT(cfg)
    small = jnp.zeros((1, 64, 64, 3), jnp.float32)
    variables = jax.jit(
        lambda r, a, b: model.init(r, a, b, iters=1, train=False))(
            jax.random.PRNGKey(0), small, small)
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    im1 = jax.random.uniform(k1, (1, HEIGHT, WIDTH, 3), jnp.float32, 0, 255)
    im2 = jax.random.uniform(k2, (1, HEIGHT, WIDTH, 3), jnp.float32, 0, 255)

    @jax.jit
    def fwd(a, b):
        low, up = model.apply(variables, a, b, iters=args.iters,
                              train=False, test_mode=True)
        return jnp.sum(low) + jnp.sum(up)

    float(fwd(im1, im2))  # compile + warm OUTSIDE the trace window

    out_dir = osp.join(osp.dirname(osp.dirname(osp.abspath(__file__))),
                       "logs", "profile_trace", platform)
    os.makedirs(out_dir, exist_ok=True)
    with trace(out_dir):
        for _ in range(args.reps):
            float(fwd(im1, im2))  # the fetch waits for the device

    arts = sorted(glob.glob(osp.join(out_dir, "**", "*"), recursive=True))
    files = [a for a in arts if osp.isfile(a)]
    total = sum(osp.getsize(f) for f in files)
    print(f"trace captured: {len(files)} files, {total / 1e6:.1f} MB "
          f"under {out_dir}")
    for f in files[:12]:
        print(f"  {osp.relpath(f, out_dir)}  {osp.getsize(f)}")
    if not files:
        raise SystemExit("no trace artifacts written")


if __name__ == "__main__":
    main()
