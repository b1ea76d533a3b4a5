"""MFU accounting helpers in bench.py (VERDICT r4 next-3).

The on-chip MFU number itself needs the real chip; what is testable here
is the accounting machinery: XLA's cost analysis yields a plausible FLOP
count for a known workload, and the chip-peak table is sane.
"""

import jax
import jax.numpy as jnp

import bench


def test_counted_flops_matches_matmul_arithmetic():
    n = 256
    f = jax.jit(lambda a, b: a @ b)
    a = jnp.ones((n, n), jnp.float32)
    flops = bench._counted_flops(f, a, a)
    assert flops is not None
    # one n^3 matmul = 2n^3 flops; XLA may count fused epilogue ops on
    # top, so bound loosely from both sides
    assert 0.5 * 2 * n**3 <= flops <= 4 * 2 * n**3


def test_counted_flops_never_raises_on_junk():
    # a non-jitted callable has no .lower — the helper must return None,
    # not propagate (the bench record may never fail over accounting)
    assert bench._counted_flops(lambda x: x, jnp.ones(3)) is None


def test_chip_peak_table_sane():
    import pytest

    # the table holds the device this project is measured on, under the
    # device_kind jax reports for it; any other kind is an error where
    # a utilisation is printed, never a default
    assert bench.CHIP_PEAK_BF16_FLOPS == {"TPU v5 lite": 197e12}
    assert bench.chip_peak_bf16_flops("TPU v5 lite") == 197e12
    with pytest.raises(SystemExit, match="no bf16 peak on record"):
        bench.chip_peak_bf16_flops("TPU v9")
