"""Parity test for convex upsampling vs. the reference implementation
(core/raft.py:87-98), re-expressed in torch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dexiraft_tpu.ops import upsample_flow_convex

torch = pytest.importorskip("torch")
import torch.nn.functional as F  # noqa: E402


def torch_upsample_flow(flow, mask):
    """Reference core/raft.py:87-98. flow (N,2,H,W), mask (N,576,H,W)."""
    N, _, H, W = flow.shape
    mask = mask.view(N, 1, 9, 8, 8, H, W)
    mask = torch.softmax(mask, dim=2)
    up_flow = F.unfold(8 * flow, [3, 3], padding=1)
    up_flow = up_flow.view(N, 2, 9, 1, 1, H, W)
    up_flow = torch.sum(mask * up_flow, dim=2)
    up_flow = up_flow.permute(0, 1, 4, 2, 5, 3)
    return up_flow.reshape(N, 2, 8 * H, 8 * W)


def test_convex_upsample_matches_reference():
    rng = np.random.RandomState(0)
    N, H, W = 2, 5, 7
    flow = rng.randn(N, H, W, 2).astype(np.float32)
    mask = rng.randn(N, H, W, 576).astype(np.float32)

    ours = np.asarray(upsample_flow_convex(flow, mask))

    # NHWC mask channels are (9, 8, 8) row-major = torch's view(N,1,9,8,8,H,W)
    t_flow = torch.from_numpy(flow.transpose(0, 3, 1, 2))
    t_mask = torch.from_numpy(mask.transpose(0, 3, 1, 2))
    ref = torch_upsample_flow(t_flow, t_mask).numpy().transpose(0, 2, 3, 1)

    assert ours.shape == (N, 8 * H, 8 * W, 2)
    np.testing.assert_allclose(ours, ref, rtol=1e-5, atol=1e-5)


def test_convex_upsample_uniform_mask_is_identityish():
    # with a uniform mask every output subpixel is the mean of the 3x3
    # neighborhood of 8*flow; for constant flow that equals 8*flow exactly
    # except at borders (zero padding) — check the interior.
    flow = np.ones((1, 4, 4, 2), np.float32) * 2.0
    mask = np.zeros((1, 4, 4, 576), np.float32)
    up = np.asarray(upsample_flow_convex(flow, mask))
    np.testing.assert_allclose(up[0, 8:24, 8:24], 16.0, rtol=1e-6)


def _oracle(flow, mask):
    """The formulation `ops/upsample.py` had until PR 29: the mask as
    (B, H, W, 9, 8, 8), softmaxed over the taps, contracted with the
    (B, H, W, 9, 2) patches by one six-dimensional einsum. Kept here as
    the reference for the lane-dense form; fp32, as `_upsample` cast."""
    mask = mask.astype(jnp.float32)
    b, h, w, _ = flow.shape
    m = jax.nn.softmax(mask.reshape(b, h, w, 9, 8, 8), axis=3)
    fp = jnp.pad(8.0 * flow, ((0, 0), (1, 1), (1, 1), (0, 0)))
    patches = jnp.stack(
        [fp[:, dy:dy + h, dx:dx + w, :] for dy in range(3) for dx in range(3)],
        axis=3,
    )
    up = jnp.einsum("bhwkij,bhwkc->bhwijc", m, patches,
                    precision=jax.lax.Precision.HIGHEST)
    return up.transpose(0, 1, 3, 2, 4, 5).reshape(b, 8 * h, 8 * w, 2)


@pytest.mark.parametrize("mask_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("shape", [(2, 5, 7), (1, 6, 9), (1, 46, 62)],
                         ids=lambda s: "x".join(map(str, s)))
def test_lane_dense_form_matches_the_einsum_oracle(shape, mask_dtype):
    """Output and gradients against the old six-dimensional body. The two
    differ in the order of nine additions and in dividing the weighted
    sum once instead of normalising nine weights, each a few fp32 ulps
    at |8 * flow| ~ 30 (ulp 2e-6): hence 1e-5. A flow gradient is a sum
    of 9 * 64 products of magnitude up to ~30 that largely cancel, added
    in another order: its absolute error is a few 1e-5 whatever its
    size, hence the wider atol there. The gradient with respect to a bf16
    mask is itself bf16, so a value on a rounding boundary may land one
    bf16 ulp (2**-8) away."""
    b, h, w = shape
    rng = np.random.RandomState(7)
    flow = jnp.asarray(rng.randn(b, h, w, 2).astype(np.float32))
    mask = jnp.asarray(2.0 * rng.randn(b, h, w, 576).astype(np.float32))
    mask = mask.astype(mask_dtype)
    weight = jnp.asarray(rng.randn(b, 8 * h, 8 * w, 2).astype(np.float32))

    def both(fn):
        def weighted(flow, mask):
            return jnp.sum(fn(flow, mask) * weight)
        return jax.jit(lambda f, m: (fn(f, m), jax.grad(weighted, (0, 1))(f, m)))

    out, (g_flow, g_mask) = both(upsample_flow_convex)(flow, mask)
    ref, (r_flow, r_mask) = both(_oracle)(flow, mask)

    assert out.shape == (b, 8 * h, 8 * w, 2) and out.dtype == jnp.float32
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(g_flow, r_flow, rtol=1e-5, atol=1e-4)
    assert g_mask.dtype == mask_dtype
    tol = 1e-5 if mask_dtype == jnp.float32 else 2.0 ** -7
    np.testing.assert_allclose(np.asarray(g_mask, np.float32),
                               np.asarray(r_mask, np.float32),
                               rtol=tol, atol=1e-5)
