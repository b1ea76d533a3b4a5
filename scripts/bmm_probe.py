"""Probe raw batched-matmul cost on the chip for lookup-shaped operands.

Each case: scan of 32 chained einsums (carry-dependent) -> per-call cost.
"""

from __future__ import annotations

import os.path as osp
import sys
import time

sys.path.insert(0, osp.dirname(osp.dirname(osp.abspath(__file__))))

import jax
import jax.numpy as jnp

ITERS = 32


def probe(name, batch, m, k, n, dtype=jnp.float32):
    a = jax.random.normal(jax.random.PRNGKey(0), (batch, m, k), dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (batch, k, n), dtype)

    @jax.jit
    def run(a, b):
        def body(carry, _):
            out = jnp.einsum("bmk,bkn->bmn", a + carry, b,
                             preferred_element_type=jnp.float32)
            return jnp.float32(1e-6) * jnp.mean(out), None

        c, _ = jax.lax.scan(body, jnp.float32(0), None, length=ITERS)
        return c

    float(run(a, b))
    t0 = time.perf_counter()
    for _ in range(3):
        float(run(a, b))
    dt = (time.perf_counter() - t0) / 3 / ITERS
    per = dt / batch
    print(f"{name:>28s}: {dt * 1e3:7.2f} ms/call  {per * 1e9:7.1f} ns/elem")


def main():
    print(f"platform={jax.devices()[0].platform}", file=sys.stderr)

    probe("L0 y-einsum b14080 9x55x128", 14080, 9, 55, 128)
    probe("L0 x-einsum b14080 9x128x9 ", 14080, 9, 128, 9)
    probe("L1 y-einsum b14080 9x27x64 ", 14080, 9, 27, 64)
    probe("wide-M     b3520 36x55x128 ", 3520, 36, 55, 128)
    probe("wide-M    b1760 72x55x128  ", 1760, 72, 55, 128)
    probe("bf16 L0    b14080 9x55x128 ", 14080, 9, 55, 128, jnp.bfloat16)
    probe("tall-K    b14080 55x9x128  ", 14080, 55, 9, 128)


if __name__ == "__main__":
    main()
