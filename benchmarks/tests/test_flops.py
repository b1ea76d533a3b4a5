"""flops.py against hand arithmetic."""

import jax
import jax.numpy as jnp

from benchmarks import flops

X = jax.ShapeDtypeStruct((2, 8, 8, 4), jnp.float32)
K = jax.ShapeDtypeStruct((3, 3, 4, 16), jnp.float32)
W = jax.ShapeDtypeStruct((16, 16), jnp.float32)
CONV = 2 * (2 * 8 * 8 * 16) * (3 * 3 * 4)   # outputs x taps x in features
MATMUL = 2 * (2 * 8 * 8) * 16 * 16          # (128, 16) @ (16, 16)


def toy(x, w, k, steps=8):
    y = jax.lax.conv_general_dilated(
        x, k, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def body(c, _):
        return jnp.tanh(c @ w), None

    c, _ = jax.lax.scan(body, y.reshape(-1, 16), None, length=steps)
    return c.sum()


def test_scan_body_counts_once_per_iteration():
    # the case XLA's cost_analysis gets wrong: it reports the same FLOPs
    # for a scan of 1 and of 8
    assert flops.count(toy, X, W, K) == CONV + 8 * MATMUL
    assert flops.count(lambda *a: toy(*a, steps=1), X, W, K) == CONV + MATMUL


def test_gradient_is_three_times_the_forward():
    # each matmul and conv has two transposes in the backward pass
    grad = jax.grad(toy, argnums=(0, 1, 2))
    assert flops.count(grad, X, W, K) == 3 * (CONV + 8 * MATMUL)


def test_remat_recompute_is_not_counted_in_the_plain_form():
    # the cells count their plain form; this pins that a checkpointed
    # body WOULD count more, which is why they do
    def remat_toy(x, w, k):
        y = jax.lax.conv_general_dilated(
            x, k, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        body = jax.checkpoint(lambda c, _: (jnp.tanh(c @ w), None))
        c, _ = jax.lax.scan(body, y.reshape(-1, 16), None, length=8)
        return c.sum()

    plain = flops.count(jax.grad(toy, argnums=(0, 1, 2)), X, W, K)
    remat = flops.count(jax.grad(remat_toy, argnums=(0, 1, 2)), X, W, K)
    assert remat == plain + 8 * MATMUL


def test_transposed_conv_counts_real_taps_only():
    x = jax.ShapeDtypeStruct((1, 8, 8, 4), jnp.float32)
    k = jax.ShapeDtypeStruct((4, 4, 4, 2), jnp.float32)

    def up(x, k):
        return jax.lax.conv_transpose(x, k, (2, 2), "SAME",
                                      dimension_numbers=("NHWC", "HWIO", "NHWC"))

    # 16x16x2 outputs, each fed by 4x4x4 taps of which one in four is real
    assert flops.count(up, x, k) == 2 * (16 * 16 * 2) * (4 * 4 * 4) // 4


def test_while_without_trip_count_is_refused():
    import pytest

    def loop(x):
        return jax.lax.while_loop(lambda c: c[0, 0] < 10, lambda c: c @ c, x)

    with pytest.raises(ValueError, match="trip count"):
        flops.count(loop, W)
