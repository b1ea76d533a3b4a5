"""Quantized correlation pyramid + the fused step's XLA reference.

The int8/bf16 pyramid accuracy bounds (corr-value max-abs error and
end-to-end flow drift on a tiny fixture), and fused_reference — what
the flash kernel's tests compare with and its VJP recomputes through —
against the materialized volume. The kernel's own parity is
tests/test_zzzflashcorr.py's.

Named to sort last (tier-1 budget convention): everything here is
CPU-only and tiny.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dexiraft_tpu.ops.corr import build_corr_pyramid, corr_lookup
from dexiraft_tpu.ops.local_corr import build_local_corr
from dexiraft_tpu.ops.pallas_corr import fused_reference
from dexiraft_tpu.ops.quant import (
    corr_dtype_bytes,
    dequantize,
    quantize_symmetric,
)


def _setup(key, b=1, h=6, w=8, c=32, levels=3, radius=2):
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    f1 = jax.random.normal(k1, (b, h, w, c), jnp.float32)
    f2 = jax.random.normal(k2, (b, h, w, c), jnp.float32)
    ys, xs = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                          jnp.arange(w, dtype=jnp.float32), indexing="ij")
    coords = (jnp.stack([xs, ys], axis=-1)[None].repeat(b, 0)
              + jax.random.uniform(k3, (b, h, w, 2), jnp.float32, -2, 2))
    win = 2 * radius + 1
    feat = 16
    weight = jax.random.normal(k4, (levels * win * win, feat),
                               jnp.float32) * 0.05
    bias = jax.random.normal(k5, (feat,), jnp.float32) * 0.1
    return f1, f2, coords, weight, bias


class TestQuantizedPyramid:
    def test_quantize_roundtrip_bound(self):
        x = jax.random.normal(jax.random.PRNGKey(3), (7, 9, 5), jnp.float32)
        q, scale = quantize_symmetric(x)
        assert q.dtype == jnp.int8
        err = jnp.max(jnp.abs(dequantize(q, scale) - x))
        # symmetric round-to-nearest: error <= scale/2 (+ eps)
        assert float(err) <= float(scale) * 0.5 + 1e-7

    def test_zero_size_level_quantizes(self):
        q, scale = quantize_symmetric(jnp.zeros((4, 0, 3), jnp.float32))
        assert q.shape == (4, 0, 3) and q.dtype == jnp.int8
        assert float(scale) == 1.0

    def test_corr_dtype_bytes(self):
        assert (corr_dtype_bytes("fp32"), corr_dtype_bytes("bf16"),
                corr_dtype_bytes("int8")) == (4, 2, 1)
        with pytest.raises(ValueError):
            corr_dtype_bytes("fp16")

    @pytest.mark.parametrize("dtype,tol_frac", [("bf16", 0.01),
                                                ("int8", 0.02)])
    def test_allpairs_lookup_error_bound(self, dtype, tol_frac):
        """corr-value max-abs error of the quantized allpairs pyramid,
        relative to the fp32 lookup's value range."""
        f1, f2, coords, _, _ = _setup(jax.random.PRNGKey(4), h=8, w=10)
        ref = corr_lookup(build_corr_pyramid(f1, f2, 4, 4), coords)
        out = corr_lookup(build_corr_pyramid(f1, f2, 4, 4, dtype=dtype),
                          coords)
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err <= tol_frac * float(jnp.max(jnp.abs(ref)))

    @pytest.mark.parametrize("dtype", ["bf16", "int8"])
    def test_local_lookup_error_bound(self, dtype):
        f1, f2, coords, _, _ = _setup(jax.random.PRNGKey(5), h=8, w=10)
        ref = build_local_corr(f1, f2, 4, 4)(coords)
        out = build_local_corr(f1, f2, 4, 4, dtype=dtype)(coords)
        err = float(jnp.max(jnp.abs(out - ref)))
        # the on-demand path quantizes fmap2 BEFORE the C-dim dot, so the
        # error grows ~sqrt(C); still small relative to the corr range
        assert err <= 0.05 * float(jnp.max(jnp.abs(ref)))

    @pytest.mark.parametrize("dtype,tol_frac", [("fp32", 1e-5),
                                                ("bf16", 0.05),
                                                ("int8", 0.05)])
    def test_fused_reference_matches_the_volume(self, dtype, tol_frac):
        """The chain the benchmark's `correct` leans on, closed on the
        CPU: the flash kernel is compared with fused_reference, and
        fused_reference here with the materialized volume's lookup and
        the 1x1 contraction written out. Both sides store in ``dtype``
        (the volume its levels, the reference fmap2's): the bound is the
        on-demand path's above, through the contraction."""
        f1, f2, coords, _, _ = _setup(jax.random.PRNGKey(8), h=8, w=10)
        kw, kb = jax.random.split(jax.random.PRNGKey(9))
        ww = 81  # (2 * 4 + 1) ** 2 window taps a level
        weight = jax.random.normal(kw, (4 * ww, 16), jnp.float32) * 0.05
        bias = jax.random.normal(kb, (16,), jnp.float32) * 0.1
        corr = corr_lookup(build_corr_pyramid(f1, f2, 4, 4), coords)
        ref = jnp.einsum("bhwc,cf->bhwf", corr, weight) + bias
        lc = build_local_corr(f1, f2, 4, 4, dtype=dtype)
        w = weight
        if lc.scales is not None:  # as FusedCorrEncoder folds them
            w = jnp.concatenate([weight[i * ww:(i + 1) * ww] * lc.scales[i]
                                 for i in range(4)])
        out = fused_reference(lc.fmap1, lc.fmap2_pyramid, coords, w, bias, 4)
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err <= tol_frac * float(jnp.max(jnp.abs(ref)))

    def test_bf16_pyramid_gradients_flow(self):
        """bf16 storage must stay trainable (the astype is
        differentiable); this is what licenses --corr_dtype bf16 on
        train_cli."""
        f1, f2, coords, _, _ = _setup(jax.random.PRNGKey(6), h=6, w=6, c=8)

        def loss(f1_, f2_):
            lc = build_local_corr(f1_, f2_, 2, 2, dtype="bf16")
            return jnp.sum(lc(coords) ** 2)

        g1, g2 = jax.grad(loss, argnums=(0, 1))(f1, f2)
        assert float(jnp.abs(g1).max()) > 0
        assert float(jnp.abs(g2).max()) > 0


class TestFlashOperandsMadeOnce:
    """build_local_corr(kernel="flash") stores the kernel's operands in
    the form the kernel reads (ops/pallas_corr.py pad_flash_operands):
    what they hold is the xla build's arrays, a level with x as its
    major axis and zero rows after its own. The kernel's parity on them
    is tests/test_zzzflashcorr.py's."""

    # (h, w, levels): level widths 64 / 32 / 16; odd widths 13 / 6 / 3 / 1
    # with 65 queries and a degenerate 0-row tail level; 240 / 120 columns
    @pytest.mark.parametrize("dtype", ["fp32", "bf16", "int8"])
    @pytest.mark.parametrize("h,w,levels", [(4, 64, 3), (5, 13, 4),
                                            (3, 240, 2)])
    def test_stored_once_in_the_storage_dtype(self, h, w, levels, dtype):
        from dexiraft_tpu.ops import pallas_corr as pc

        f1, f2, coords, _, _ = _setup(jax.random.PRNGKey(h * w), b=2, h=h,
                                      w=w, c=8)
        lc = build_local_corr(f1, f2, levels, 2, dtype=dtype, kernel="flash")
        lx = build_local_corr(f1, f2, levels, 2, dtype=dtype)
        n = h * w
        assert lc.level_shapes == lx.level_shapes == tuple(
            (h >> i, w >> i) for i in range(levels))
        assert (lc.batch, lc.ht, lc.wd) == (2, h, w)
        # the queries: fp32, flattened, zeros up to a pixel-block multiple
        assert lc.fmap1.dtype == jnp.float32
        assert lc.fmap1.shape == (2, -(-n // pc._FLASH_PIXEL_BLOCK)
                                  * pc._FLASH_PIXEL_BLOCK, 8)
        np.testing.assert_array_equal(
            np.asarray(lc.fmap1[:, :n]).reshape(lx.fmap1.shape),
            np.asarray(lx.fmap1))
        np.testing.assert_array_equal(np.asarray(lc.fmap1[:, n:]), 0.0)
        # the levels: the stored bytes as (B, W2, H2p, C), x a major axis
        # with no pad, zero rows up to a row-block multiple; a level with
        # no rows stays empty
        for lv, raw, (h2, w2) in zip(lc.fmap2_pyramid, lx.fmap2_pyramid,
                                     lc.level_shapes):
            assert lv.dtype == raw.dtype
            if not h2 or not w2:
                assert lv.shape == raw.shape and lv.size == 0
                continue
            assert lv.shape == (2, w2, -(-h2 // pc._FLASH_ROWS)
                                * pc._FLASH_ROWS, 8)
            as_np = lambda a: np.asarray(a.astype(jnp.float32))  # noqa: E731
            np.testing.assert_array_equal(
                as_np(lv[:, :, :h2]), as_np(raw).swapaxes(1, 2))
            np.testing.assert_array_equal(as_np(lv[:, :, h2:]), 0.0)
        if dtype == "int8":
            for a, b in zip(lc.scales, lx.scales):
                assert float(a) == float(b)
        else:
            assert lc.scales is None


class TestModelQuantizedPath:
    """The whole model on a quantized pyramid against fp32 storage, SAME
    parameters, and the refusals that belong to it."""

    @pytest.fixture(scope="class")
    def fixture(self):
        from _models import init_raft, jit_apply
        from dexiraft_tpu.config import raft_v1

        im1 = jax.random.uniform(jax.random.PRNGKey(1), (1, 32, 32, 3),
                                 jnp.float32, 0, 255)
        im2 = jax.random.uniform(jax.random.PRNGKey(2), (1, 32, 32, 3),
                                 jnp.float32, 0, 255)
        model, variables = init_raft(raft_v1(small=True, corr_impl="local"),
                                     32, 32)
        ref = jit_apply(model)(variables, im1, im2, iters=2)
        return im1, im2, variables, ref

    @pytest.mark.parametrize("dtype,px_bound", [("bf16", 0.05),
                                                ("int8", 0.25)])
    def test_quantized_flow_drift_bounded(self, fixture, dtype, px_bound):
        """End-to-end flow drift of the quantized pyramid on the tiny
        fixture (allpairs path — no interpret-mode kernel, so cheap).
        Measured: bf16 ~0.016 px max, int8 ~0.041 px max at 2 iters;
        bounds leave headroom for rng/platform wiggle without ever
        letting a broken dequant (errors >> 1 px) pass."""
        from _models import jit_apply
        from dexiraft_tpu.config import raft_v1
        from dexiraft_tpu.models.raft import RAFT

        im1, im2, variables, ref = fixture
        cfg_q = raft_v1(small=True, corr_dtype=dtype)
        out = jit_apply(RAFT(cfg_q))(variables, im1, im2, iters=2)
        drift = float(jnp.max(jnp.abs(out - ref)))
        assert drift <= px_bound, f"{dtype} flow drift {drift} px"

    def test_int8_train_refused(self, fixture):
        from dexiraft_tpu.config import raft_v1
        from dexiraft_tpu.models.raft import RAFT

        im1, im2, variables, _ = fixture
        with pytest.raises(ValueError, match="int8.*inference"):
            RAFT(raft_v1(small=True, corr_dtype="int8")).apply(
                variables, im1, im2, iters=1, train=True)

    def test_fused_requires_flash(self, fixture):
        from dexiraft_tpu.config import raft_v1
        from dexiraft_tpu.models.raft import RAFT

        im1, im2, variables, _ = fixture
        with pytest.raises(ValueError, match="fused_update.*flash"):
            RAFT(raft_v1(small=True, fused_update=True)).apply(
                variables, im1, im2, iters=1, train=False)
