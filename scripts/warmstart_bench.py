"""Sequential (video-mode) inference throughput with warm start.

The submission path (create_sintel_submission, evaluate.py:22-54) chains
frames: each forward starts from the previous frame's low-res flow,
forward-splatted to the new frame. The reference pays a device->host->
device scipy round-trip per frame for that splat (core/utils/utils.py:
26-54); here the whole chain — forward, on-device forward_interpolate,
next forward — stays on device. This measures per-frame latency in that
regime for the flagship v5 at Sintel eval size.

Usage: python scripts/warmstart_bench.py [--frames 8] [--iters 32]
       [--corr_impl local] [--cpu]
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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=8)
    ap.add_argument("--iters", type=int, default=32)
    ap.add_argument("--corr_impl", default="local")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    from dexiraft_tpu.config import raft_v5
    from dexiraft_tpu.eval.interpolate import forward_interpolate
    from dexiraft_tpu.models.raft import RAFT

    platform = jax.devices()[0].platform
    print(f"platform={platform} frames={args.frames} iters={args.iters} "
          f"corr_impl={args.corr_impl}", file=sys.stderr)

    cfg = raft_v5(mixed_precision=(platform == "tpu"),
                  corr_impl=args.corr_impl)
    model = RAFT(cfg)
    rng = jax.random.PRNGKey(0)
    small = jnp.zeros((1, 64, 64, 3), jnp.float32)
    init = jax.jit(lambda r, a, b: model.init(r, a, b, iters=1, train=False))
    variables = jax.block_until_ready(init(rng, small, small))
    print("init done", file=sys.stderr)

    @jax.jit
    def frame_step(variables, a, b, flow_prev):
        """One video frame: warm-started forward + next frame's seed.
        Returns (seed for next frame, checksum of the full-res flow).
        variables is an argument (not a closure) so the weights aren't
        baked into the lowered computation — the make_eval_step pattern."""
        low, up = model.apply(variables, a, b, iters=args.iters,
                              train=False, test_mode=True,
                              flow_init=flow_prev)
        # forward_interpolate is unbatched (H, W, 2), like the
        # submission loop's flow_low[0] usage (eval/submission.py)
        return forward_interpolate(low[0])[None], jnp.sum(up)

    keys = jax.random.split(jax.random.PRNGKey(1), args.frames + 1)
    frames = [jax.random.uniform(k, (1, HEIGHT, WIDTH, 3), jnp.float32,
                                 0, 255) for k in keys]
    seed = jnp.zeros((1, HEIGHT // 8, WIDTH // 8, 2), jnp.float32)

    # compile + warmup
    t0 = time.perf_counter()
    seed_w, s = frame_step(variables, frames[0], frames[1], seed)
    float(jax.device_get(s))
    print(f"compile+first frame {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)

    t0 = time.perf_counter()
    seed = seed_w
    acc = 0.0
    for i in range(args.frames):
        seed, s = frame_step(variables, frames[i], frames[i + 1], seed)
    acc = float(jax.device_get(s))
    # ONE sync at the end: frames chain through `seed`,
    # so fetching the last checksum bounds the whole pipeline (per-frame
    # fetches would each stall the dispatch queue)
    dt = (time.perf_counter() - t0) / args.frames
    print(f"warm-start sequential: {dt * 1e3:.1f} ms/frame "
          f"({1.0 / dt:.2f} FPS at {HEIGHT}x{WIDTH}, {args.iters} iters, "
          f"checksum finite={acc == acc})")


if __name__ == "__main__":
    main()
