"""Pin the whole-forward FLOP count at the bench geometry, chip-free.

XLA's cost analysis counts the arithmetic of the optimized HLO — a
property of the program, not the silicon — so the 440x1024x32-iters
forward FLOPs can be pinned by a compile-only pass on the CPU backend.
The on-chip bench (bench.py MFU fields) counts the same quantity on the
TPU executable; this record is the cross-check for the MFU numerator
math in docs/perf.md.

Compile only — never executes the forward (a 440x1024 CPU run costs
~100 s/forward; the count needs none of it).

Usage: python scripts/flops_pin.py [--iters 32] [--size 440 1024]
"""

from __future__ import annotations

import argparse
import json
import os.path as osp
import sys
import time

sys.path.insert(0, osp.dirname(osp.dirname(osp.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=32)
    ap.add_argument("--size", type=int, nargs=2, default=(440, 1024))
    ap.add_argument("--corr_impl", default="allpairs")
    ap.add_argument("--mixed", action="store_true",
                    help="bf16 policy like the on-chip bench (flop "
                         "count is precision-independent; default fp32 "
                         "avoids CPU bf16 conv corner cases)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")

    from dexiraft_tpu.config import raft_v5
    from dexiraft_tpu.models.raft import RAFT

    h, w = args.size
    cfg = raft_v5(mixed_precision=args.mixed, corr_impl=args.corr_impl)
    model = RAFT(cfg)
    rng = jax.random.PRNGKey(0)
    small = jnp.zeros((1, 64, 64, 3), jnp.float32)
    variables = jax.jit(
        lambda r, a, b: model.init(r, a, b, iters=1, train=False))(
            rng, small, small)

    @jax.jit
    def forward(a, b):
        low, up = model.apply(variables, a, b, iters=args.iters,
                              train=False, test_mode=True)
        return jnp.sum(low) + jnp.sum(up)

    spec = jax.ShapeDtypeStruct((1, h, w, 3), jnp.float32)
    t0 = time.perf_counter()
    cost = forward.lower(spec, spec).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    print(f"# compile {time.perf_counter() - t0:.0f}s", file=sys.stderr)
    print(json.dumps({
        "metric": f"v5_forward_flops@{h}x{w}x{args.iters}it",
        "flops": flops,
        "tflops": round(flops / 1e12, 3),
        "corr_impl": args.corr_impl,
        "backend": "cpu-compile cost_analysis (program property)",
        "bytes_accessed": cost.get("bytes accessed"),
    }), flush=True)


if __name__ == "__main__":
    main()
