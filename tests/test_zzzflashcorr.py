"""Flash-blocked correlation kernel (ISSUE 12).

Interpret-mode parity of flash_fused_step / flash_local_corr_level
against the unfused XLA references (forward AND gradients, including
through bf16/int8-quantized levels, at the published width and radii
and at the frame's edges), blocked-tiling vs single-block equivalence,
config-time refusals (the whole model on the flash path:
tests/test_zzzflashmodel.py), the one list of --corr_impl
choices, and the compile-time memory_analysis pin that the flash executable's temp footprint is
O(fmaps) — not O(volume) — at a geometry where the all-pairs volume
dominates.

Named to sort last (tier-1 budget convention); every fixture is
tiny because interpret-mode Pallas pays per traced grid step.
"""

import glob
import importlib.util
import os.path as osp
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _models import as_one_program
from dexiraft_tpu.ops import corr, local_corr, pallas_corr
from dexiraft_tpu.ops.pallas_corr import pad_flash_operands

build_corr_pyramid = as_one_program(corr.build_corr_pyramid)
corr_lookup = as_one_program(corr.corr_lookup)
build_local_corr = as_one_program(local_corr.build_local_corr)
local_corr_level = as_one_program(local_corr.local_corr_level)
fused_reference = as_one_program(pallas_corr.fused_reference)

REPO = osp.dirname(osp.dirname(osp.abspath(__file__)))


@as_one_program
def flash_fused_step(fmap1, levels, coords, weight, bias, radius,
                     interpret=None):
    """The kernel's fused entry point on RAW arrays: the operands padded
    as build_local_corr(kernel="flash") pads them, inside whatever
    differentiates this — a gradient w.r.t. the raw arrays runs the
    VJP's slices of the padded operands and the pad's own transpose."""
    f1, padded = pad_flash_operands(fmap1, tuple(levels))
    return pallas_corr.flash_fused_step(
        f1, padded, coords, weight, bias, radius,
        tuple(tuple(lv.shape[1:3]) for lv in levels), interpret)


@as_one_program
def flash_local_corr_level(fmap1, fmap2, coords, radius, interpret=None):
    """The kernel's lookup entry point on RAW arrays, as above."""
    f1, (level,) = pad_flash_operands(fmap1, (fmap2,))
    return pallas_corr.flash_local_corr_level(
        f1, level, coords, radius, tuple(fmap2.shape[1:3]), interpret)


@pytest.fixture(autouse=True)
def _small_flash_blocks(monkeypatch):
    """Interpret mode traces the kernel once per grid step and pays per
    padded pixel: tiny fixtures want tiny blocks (the tiling never
    changes values — test_rows_block_equivalence pins that)."""
    monkeypatch.setattr(pallas_corr, "_FLASH_PIXEL_BLOCK", 16)
    monkeypatch.setattr(pallas_corr, "_FLASH_ROWS", 2)


def _grid(b, h, w):
    ys, xs = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                          jnp.arange(w, dtype=jnp.float32), indexing="ij")
    return jnp.stack([xs, ys], axis=-1)[None].repeat(b, 0)


def _setup(key, b=1, h=6, w=8, c=32, levels=3, radius=2):
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    f1 = jax.random.normal(k1, (b, h, w, c), jnp.float32)
    f2 = jax.random.normal(k2, (b, h, w, c), jnp.float32)
    coords = (_grid(b, h, w)
              + jax.random.uniform(k3, (b, h, w, 2), jnp.float32, -2, 2))
    win = 2 * radius + 1
    feat = 16
    weight = jax.random.normal(k4, (levels * win * win, feat),
                               jnp.float32) * 0.05
    bias = jax.random.normal(k5, (feat,), jnp.float32) * 0.1
    return f1, f2, coords, weight, bias


class TestFlashKernelParity:
    @pytest.mark.parametrize("radius", [2, 4])
    def test_fused_forward_matches_reference(self, radius):
        f1, f2, coords, weight, bias = _setup(jax.random.PRNGKey(0),
                                              radius=radius)
        lc = build_local_corr(f1, f2, num_levels=3, radius=radius)
        out = flash_fused_step(lc.fmap1, lc.fmap2_pyramid, coords,
                               weight, bias, radius, True)
        ref = fused_reference(lc.fmap1, lc.fmap2_pyramid, coords,
                              weight, bias, radius)
        # acceptance pin: fwd <= 1e-3 (measured ~1e-6 — same dots,
        # different accumulation order over row blocks)
        assert float(jnp.max(jnp.abs(out - ref))) <= 1e-3
        assert out.shape == (1, 6, 8, weight.shape[1])

    def test_lookup_level_matches_reference(self):
        radius = 2
        f1, f2, coords, _, _ = _setup(jax.random.PRNGKey(3), radius=radius)
        out = flash_local_corr_level(f1, f2, coords, radius, True)
        ref = local_corr_level(f1, f2, coords, radius)
        assert float(jnp.max(jnp.abs(out - ref))) <= 1e-3

    def test_far_out_of_frame_coords_are_zero(self):
        """Divergent-flow robustness: coords far outside the frame must
        produce all-zero windows (hat support empty), with every row
        block skipped rather than sliced out of range — flash needs no
        coordinate clipping."""
        radius = 2
        f1, f2, coords, _, _ = _setup(jax.random.PRNGKey(4), radius=radius)
        far = coords + 1000.0
        out = flash_local_corr_level(f1, f2, far, radius, True)
        np.testing.assert_array_equal(np.asarray(out), 0.0)
        ref = local_corr_level(f1, f2, far, radius)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref))

    def test_gradients_match_reference(self):
        radius = 2
        f1, f2, coords, weight, bias = _setup(jax.random.PRNGKey(1),
                                              h=4, w=6, c=16, radius=radius)
        lc = build_local_corr(f1, f2, num_levels=3, radius=radius)

        def loss_flash(f1_, f2s_, co_, w_, b_):
            return jnp.sum(
                flash_fused_step(f1_, f2s_, co_, w_, b_, radius, True) ** 2)

        def loss_ref(f1_, f2s_, co_, w_, b_):
            return jnp.sum(
                fused_reference(f1_, f2s_, co_, w_, b_, radius) ** 2)

        gf = as_one_program(jax.grad(loss_flash, argnums=(0, 1, 2, 3, 4)))(
            lc.fmap1, lc.fmap2_pyramid, coords, weight, bias)
        gr = as_one_program(jax.grad(loss_ref, argnums=(0, 1, 2, 3, 4)))(
            lc.fmap1, lc.fmap2_pyramid, coords, weight, bias)
        for a, b_ in zip(jax.tree_util.tree_leaves(gf),
                         jax.tree_util.tree_leaves(gr)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-3, atol=1e-3)
        # zero coords gradient — the CUDA-kernel semantics every corr
        # path shares (custom-VJP contract)
        np.testing.assert_allclose(np.asarray(gf[2]), 0.0)

    def test_gradients_through_bf16_levels(self):
        radius = 2
        f1, f2, coords, weight, bias = _setup(jax.random.PRNGKey(2),
                                              h=4, w=6, c=16, radius=radius)
        lc = build_local_corr(f1, f2, num_levels=3, radius=radius,
                              dtype="bf16")

        def loss_flash(f1_, f2s_, w_, b_):
            return jnp.sum(flash_fused_step(f1_, f2s_, coords, w_, b_,
                                            radius, True) ** 2)

        def loss_ref(f1_, f2s_, w_, b_):
            return jnp.sum(fused_reference(f1_, f2s_, coords, w_, b_,
                                           radius) ** 2)

        gf = as_one_program(jax.grad(loss_flash, argnums=(0, 1, 2, 3)))(
            lc.fmap1, lc.fmap2_pyramid, weight, bias)
        gr = as_one_program(jax.grad(loss_ref, argnums=(0, 1, 2, 3)))(
            lc.fmap1, lc.fmap2_pyramid, weight, bias)
        for a, b_ in zip(jax.tree_util.tree_leaves(gf),
                         jax.tree_util.tree_leaves(gr)):
            np.testing.assert_allclose(np.asarray(a, dtype=np.float32),
                                       np.asarray(b_, dtype=np.float32),
                                       rtol=1e-2, atol=1e-2)

    def test_gradients_through_int8_levels(self):
        """int8 levels are non-differentiable by construction (float0
        cotangents); grads to fmap1/weight/bias must still match the
        reference recompute to 1e-3."""
        radius = 2
        f1, f2, coords, weight, bias = _setup(jax.random.PRNGKey(5),
                                              h=4, w=6, c=16, radius=radius)
        lc8 = build_local_corr(f1, f2, num_levels=3, radius=radius,
                               dtype="int8")

        def loss_flash(f1_, w_, b_):
            return jnp.sum(flash_fused_step(
                f1_, lc8.fmap2_pyramid, coords, w_, b_, radius, True) ** 2)

        def loss_ref(f1_, w_, b_):
            return jnp.sum(fused_reference(
                f1_, lc8.fmap2_pyramid, coords, w_, b_, radius) ** 2)

        gf = as_one_program(jax.grad(loss_flash, argnums=(0, 1, 2)))(lc8.fmap1, weight, bias)
        gr = as_one_program(jax.grad(loss_ref, argnums=(0, 1, 2)))(lc8.fmap1, weight, bias)
        for a, b_ in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-3, atol=1e-3)

    def test_quantized_levels_through_flash_kernel(self):
        """int8-stored levels + scale-folded weights stay within the
        quantization error bound of the fp32 flash output."""
        radius = 2
        f1, f2, coords, weight, bias = _setup(jax.random.PRNGKey(6),
                                              radius=radius)
        lc = build_local_corr(f1, f2, num_levels=3, radius=radius)
        lc8 = build_local_corr(f1, f2, num_levels=3, radius=radius,
                               dtype="int8")
        win = 2 * radius + 1
        ww = win * win
        w8 = jnp.concatenate(
            [weight[i * ww:(i + 1) * ww] * lc8.scales[i] for i in range(3)],
            axis=0)
        ref = flash_fused_step(lc.fmap1, lc.fmap2_pyramid, coords,
                               weight, bias, radius, True)
        out8 = flash_fused_step(lc8.fmap1, lc8.fmap2_pyramid, coords,
                                w8, bias, radius, True)
        bound = 0.05 * float(jnp.max(jnp.abs(ref)))
        assert float(jnp.max(jnp.abs(out8 - ref))) <= max(bound, 1e-3)


# -- operands made once, where the pyramid is built (ISSUE 36) ---------------

# (h, w, levels): level widths 64 / 32 / 16 under the lane width; odd
# widths 13 / 6 / 3 / 1 with h*w = 65 not a multiple of the pixel block
# and a degenerate 0-row tail level; widths 27 / 13 / 6, no multiple of
# a sublane tile (240 columns: LEVEL_WIDTHS below, and the stored form
# in tests/test_zzzfused_corr.py)
PREPARED = {"w64_32_16": (4, 64, 3), "odd_tail": (5, 13, 4),
            "w27_13_6": (6, 27, 3)}


def _stored_back(g, h2):
    """A level's cotangent in the stored form (B, W2, H2p, C) -> the
    (B, H2, W2, C) part of it and the padding rows."""
    g = jnp.swapaxes(g, 1, 2)
    return g[:, :h2], g[:, h2:]


def _prepared_case(name, dtype, radius=2):
    """-> (flash pyramid, xla pyramid, coords, weight with any int8
    scales folded in, bias)."""
    h, w, levels = PREPARED[name]
    f1, f2, coords, weight, bias = _setup(
        jax.random.PRNGKey(sum(map(ord, name + dtype))), b=2, h=h, w=w,
        c=16, levels=levels, radius=radius)
    lc = build_local_corr(f1, f2, levels, radius, dtype=dtype, kernel="flash")
    lx = build_local_corr(f1, f2, levels, radius, dtype=dtype)
    if lx.scales is not None:
        ww = (2 * radius + 1) ** 2
        weight = jnp.concatenate([weight[i * ww:(i + 1) * ww] * lx.scales[i]
                                  for i in range(levels)])
    return lc, lx, coords, weight, bias


class TestPreparedOperands:
    """build_local_corr(kernel="flash") hands the kernel its operands as
    it reads them; a lookup pads nothing but the coordinates."""

    @pytest.mark.parametrize("dtype", ["fp32", "bf16", "int8"])
    @pytest.mark.parametrize("name", list(PREPARED))
    def test_fused_matches_reference(self, name, dtype):
        lc, lx, coords, weight, bias = _prepared_case(name, dtype)
        out = as_one_program(pallas_corr.flash_fused_step)(
            lc.fmap1, lc.fmap2_pyramid, coords, weight, bias, lc.radius,
            lc.level_shapes, True)
        ref = fused_reference(lx.fmap1, lx.fmap2_pyramid, coords, weight,
                              bias, lx.radius)
        assert out.shape == ref.shape
        assert float(jnp.max(jnp.abs(out - ref))) <= 1e-3 * max(
            1.0, float(jnp.max(jnp.abs(ref))))

    @pytest.mark.parametrize("dtype", ["fp32", "bf16", "int8"])
    @pytest.mark.parametrize("name", list(PREPARED))
    def test_lookup_matches_local_corr_level(self, name, dtype, monkeypatch):
        """The pyramid's own lookup: a kernel call a level (the tail
        level of `odd_tail` none: its windows are zero), scales after."""
        monkeypatch.setenv("DEXIRAFT_PALLAS_INTERPRET", "1")
        lc, lx, coords, _, _ = _prepared_case(name, dtype)
        lookup = as_one_program(lambda pyramid, at: pyramid(at))
        out, ref = lookup(lc, coords), lookup(lx, coords)
        assert out.shape == ref.shape
        assert float(jnp.max(jnp.abs(out - ref))) <= 1e-3 * max(
            1.0, float(jnp.max(jnp.abs(ref))))
        if name == "odd_tail":
            assert lc.level_shapes[-1][0] == 0
            np.testing.assert_array_equal(np.asarray(out[..., -25:]), 0.0)

    @pytest.mark.parametrize("dtype", ["fp32", "bf16"])
    @pytest.mark.parametrize("name", list(PREPARED))
    def test_fused_vjp_slices_the_padded_operands(self, name, dtype):
        """jax.grad through flash_fused_step w.r.t. the operands AS
        STORED (queries padded, levels x-major with padded rows): on the
        true extents the reference's gradients, in the padding exactly
        zero."""
        lc, lx, coords, weight, bias = _prepared_case(name, dtype)
        h, w = lc.ht, lc.wd

        def loss_flash(f1_, lv_, w_, b_):
            return jnp.sum(pallas_corr.flash_fused_step(
                f1_, lv_, coords, w_, b_, lc.radius, lc.level_shapes,
                True) ** 2)

        def loss_ref(f1_, lv_, w_, b_):
            return jnp.sum(fused_reference(f1_, lv_, coords, w_, b_,
                                           lx.radius) ** 2)

        gf = as_one_program(jax.grad(loss_flash, argnums=(0, 1, 2, 3)))(
            lc.fmap1, lc.fmap2_pyramid, weight, bias)
        gr = as_one_program(jax.grad(loss_ref, argnums=(0, 1, 2, 3)))(
            lx.fmap1, lx.fmap2_pyramid, weight, bias)
        tol = dict(rtol=1e-3, atol=1e-3) if dtype == "fp32" else dict(
            rtol=1e-2, atol=1e-2)
        f32 = lambda a: np.asarray(a, dtype=np.float32)  # noqa: E731
        assert gf[0].shape == lc.fmap1.shape
        np.testing.assert_allclose(
            f32(gf[0][:, :h * w]).reshape(gr[0].shape), f32(gr[0]), **tol)
        np.testing.assert_array_equal(f32(gf[0][:, h * w:]), 0.0)
        for g, lv, ref, (h2, w2) in zip(gf[1], lc.fmap2_pyramid, gr[1],
                                        lc.level_shapes):
            assert g.shape == lv.shape and g.dtype == lv.dtype
            if not h2:  # the empty tail level: stored as it is
                assert g.size == 0
                continue
            # x a major axis and unpadded, the rows to a row-block multiple
            assert g.shape[1] == w2 and g.shape[2] % pallas_corr._FLASH_ROWS == 0
            inside, padding = _stored_back(g, h2)
            np.testing.assert_allclose(f32(inside), f32(ref), **tol)
            np.testing.assert_array_equal(f32(padding), 0.0)
        for g, ref in zip(gf[2:], gr[2:]):
            np.testing.assert_allclose(f32(g), f32(ref), **tol)
        assert float(jnp.abs(gr[0]).max()) > 0

    @pytest.mark.parametrize("name", list(PREPARED))
    def test_lookup_vjp_slices_the_padded_operands(self, name):
        """The same through flash_local_corr_level, at level 1."""
        lc, lx, coords, _, _ = _prepared_case(name, "fp32")
        (h2, w2), co = lc.level_shapes[1], coords / 2.0

        def grads(fn, f1_, lv_):
            return as_one_program(jax.grad(
                lambda a, b_, c_: jnp.sum(fn(a, b_, c_) ** 2),
                argnums=(0, 1, 2)))(f1_, lv_, co)

        gf = grads(lambda a, b_, c_: pallas_corr.flash_local_corr_level(
            a, b_, c_, 2, (h2, w2), True), lc.fmap1, lc.fmap2_pyramid[1])
        gr = grads(lambda a, b_, c_: local_corr_level(a, b_, c_, 2),
                   lx.fmap1, lx.fmap2_pyramid[1])
        n = lc.ht * lc.wd
        np.testing.assert_allclose(
            np.asarray(gf[0][:, :n]).reshape(gr[0].shape), np.asarray(gr[0]),
            rtol=1e-3, atol=1e-3)
        np.testing.assert_array_equal(np.asarray(gf[0][:, n:]), 0.0)
        assert gf[1].shape == lc.fmap2_pyramid[1].shape
        inside, padding = _stored_back(gf[1], h2)
        assert inside.shape[2] == w2
        np.testing.assert_allclose(np.asarray(inside), np.asarray(gr[1]),
                                   rtol=1e-3, atol=1e-3)
        np.testing.assert_array_equal(np.asarray(padding), 0.0)
        np.testing.assert_array_equal(np.asarray(gf[2]), 0.0)
        assert float(jnp.abs(gr[1]).max()) > 0


# -- the model's own width and radii, and the frame's edges -----------------


def _geometry_case(name):
    """-> (fmap1, fmap2, coords in fmap2's pixels, radius) at C=128 on an
    8x16 query grid: what the fixtures above (C=32, radius 1-2, 6x8) do
    not reach."""
    key = jax.random.PRNGKey(sum(map(ord, name)))
    b, h, w, c = 1, 8, 16, 128
    k1, k2, k3 = jax.random.split(key, 3)
    f1 = jax.random.normal(k1, (b, h, w, c), jnp.float32)
    f2 = jax.random.normal(k2, (b, h, w, c), jnp.float32)
    noisy = _grid(b, h, w) + jax.random.uniform(k3, (b, h, w, 2),
                                                jnp.float32, -3.0, 3.0)
    if name in ("radius3", "radius4"):
        return f1, f2, noisy, int(name[-1])
    if name == "integer_centres":  # every fraction exactly zero
        return f1, f2, jnp.round(noisy), 4
    if name in LEVEL_WIDTHS:  # a level as wide as no tile: x takes no pad
        rows2, w2 = LEVEL_WIDTHS[name]
        f2 = jax.random.normal(k2, (b, rows2, w2, c), jnp.float32)
        scale = jnp.asarray([w2 / w, rows2 / h], jnp.float32)
        return f1, f2, noisy * scale, 4
    if name.startswith("edge_"):
        return _edge_case(name, f1, f2, noisy)
    if name == "frame_edge":  # every window straddles two frame edges
        edge = jnp.stack([jnp.full((b, h, w), -0.4),
                          jnp.full((b, h, w), h - 0.6)], axis=-1)
        return f1, f2, edge, 4
    if name == "far_out_both_signs":  # half the queries at -500, half +500
        sign = jnp.where(jnp.arange(w) % 2 == 0, -1.0, 1.0)
        far = jnp.broadcast_to(500.0 * sign[None, None, :, None],
                               (b, h, w, 2))
        return f1, f2, far, 4
    assert name == "half_size_level"  # a coarser level than the query grid
    f2 = jax.random.normal(k2, (b, h // 2, w // 2, c), jnp.float32)
    return f1, f2, noisy / 2.0, 3


# level (rows, columns): 27, 13 and 6 columns are no multiple of 8, 240
# (the 1088x1920 bucket's level 0) none of 128
LEVEL_WIDTHS = {"w27": (8, 27), "w13": (8, 13), "w6": (4, 6),
                "w240": (4, 240)}
# each edge of the frame x the window wholly or partly beyond it x an
# integer or a fractional centre
EDGE_CASES = [f"edge_{edge}_{how}_{centre}"
              for edge in ("left", "right", "top", "bottom")
              for how in ("out", "part") for centre in ("int", "frac")]


def _edge_case(name, f1, f2, noisy):
    """Every query's window beyond one edge of a 4x8 level at radius 2
    (a corner of the fixture, 32 channels: the edges need no width):
    wholly (the nearest tap's support ends before the frame: all zeros)
    or partly (some taps inside); the other coordinate stays the noisy
    grid's."""
    _, edge, how, centre = name.split("_")
    radius = 2
    f1, f2, noisy = f1[:, :4, :8, :32], f2[:, :4, :8, :32], noisy[:, :4, :8]
    axis = 0 if edge in ("left", "right") else 1
    size = f2.shape[2 - axis]
    beyond = (radius + 2.0) if how == "out" else 1.0
    frac = 0.0 if centre == "int" else 0.4
    at = (-beyond - frac) if edge in ("left", "top") else (
        size - 1 + beyond + frac)
    return f1, f2, noisy.at[..., axis].set(at), radius


class TestModelWidthGeometry:
    CASES = ["radius3", "radius4", "frame_edge", "far_out_both_signs",
             "half_size_level", "integer_centres", *LEVEL_WIDTHS]

    @pytest.mark.parametrize("name", CASES + EDGE_CASES)
    def test_lookup_matches_reference(self, name):
        f1, f2, coords, radius = _geometry_case(name)
        out = flash_local_corr_level(f1, f2, coords, radius, True)
        ref = local_corr_level(f1, f2, coords, radius)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        if name == "far_out_both_signs" or "_out_" in name:
            np.testing.assert_array_equal(np.asarray(out), 0.0)
        else:
            assert float(jnp.abs(ref).max()) > 0.1

    @pytest.mark.parametrize("name", CASES)
    def test_fused_matches_reference(self, name):
        """The same level as the fused step's only one (level 0: its
        coords are the level's)."""
        f1, f2, coords, radius = _geometry_case(name)
        k4, k5 = jax.random.split(jax.random.PRNGKey(28))
        weight = jax.random.normal(k4, ((2 * radius + 1) ** 2, 16)) * 0.05
        bias = jax.random.normal(k5, (16,)) * 0.1
        out = flash_fused_step(f1, (f2,), coords, weight, bias, radius, True)
        ref = fused_reference(f1, (f2,), coords, weight, bias, radius)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        if name == "far_out_both_signs":
            np.testing.assert_array_equal(
                np.asarray(out), np.broadcast_to(np.asarray(bias), out.shape))

    def test_lookup_vjp_matches_reference(self):
        """flash_local_corr_level's own VJP (the fused step's is pinned
        above): local_corr_level's gradients to both fmaps, exactly zero
        to coords."""
        f1, f2, coords, _ = _geometry_case("radius3")
        f1, f2, coords = f1[:, :4, :8], f2[:, :4, :8], coords[:, :4, :8]

        def grads(level):
            return as_one_program(jax.grad(
                lambda a, b_, c_: jnp.sum(level(a, b_, c_) ** 2),
                argnums=(0, 1, 2)))(f1, f2, coords)

        gf = grads(lambda a, b_, c_: flash_local_corr_level(a, b_, c_, 2,
                                                            True))
        gr = grads(lambda a, b_, c_: local_corr_level(a, b_, c_, 2))
        for a, b_ in zip(gf[:2], gr[:2]):
            assert float(jnp.abs(b_).max()) > 0
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-3, atol=1e-3)
        np.testing.assert_array_equal(np.asarray(gf[2]), 0.0)


# -- the two-slot row-block pipeline (ISSUE 25) -----------------------------

RADIUS, ROWS, PIXEL_BLOCK = 1, 2, 16  # of the visited-range cases
H, W = 16, 8  # 8 query blocks of two rows; levels of 16, 8, 4 rows


def _visited(coords, level_rows, rows, pixel_block, radius):
    """The kernel's visited row blocks as [level] -> (first, end) arrays
    over (batch, query block): the rule of _flash_kernel on the host, so
    a case can assert that it is the case it says it is."""
    b = coords.shape[0]
    ty = np.asarray(coords[..., 1], np.float32).reshape(b, -1)
    ty = np.pad(ty, ((0, 0), (0, (-ty.shape[1]) % pixel_block)))
    ty = ty.reshape(b, -1, pixel_block)
    out = []
    for lvl, h2 in enumerate(level_rows):
        n_blocks = -(-h2 // rows)
        t = ty / np.float32(2.0 ** lvl)
        first = np.ceil((t.min(-1) - (radius + 1) - (rows - 1)) / rows)
        end = np.floor((t.max(-1) + (radius + 1)) / rows) + 1
        out.append((np.clip(first, 0, n_blocks).astype(int),
                    np.clip(end, 0, n_blocks).astype(int)))
    return out


def _n(v):
    """[level] -> visits of each (batch, query block)."""
    return [e - f for f, e in v]


def _pipeline_case(name):
    """-> (fmap1, levels, coords, weight, bias, check) for one shape of
    the visited ranges; ``check`` asserts on _visited's answer that the
    case is what its name says."""
    b, h, w, dtype = 1, H, W, None
    key = jax.random.PRNGKey(sum(map(ord, name)))
    if name == "batch2_tail":
        b, h, w = 2, 7, 6  # 42 queries: the third block is 10 real + 6 pad
    elif name == "unaligned_query_block":
        h, w = 8, 6  # a block is 2 2/3 query rows: it spans row blocks
    elif name == "degenerate_tail":
        h = 3  # levels of 3, 1 and 0 rows
    elif name in ("bf16", "int8"):
        dtype = name
    f1, f2, coords, weight, bias = _setup(key, b=b, h=h, w=w, radius=RADIUS)
    lc = build_local_corr(f1, f2, num_levels=3, radius=RADIUS,
                          **({"dtype": dtype} if dtype else {}))
    levels = tuple(lc.fmap2_pyramid)
    n_blocks = [-(-lv.shape[1] // ROWS) for lv in levels]
    grid = _grid(b, h, w)
    xs = coords[..., :1]  # the fixture's x flow stays

    def with_y(y):
        return jnp.concatenate([xs, jnp.broadcast_to(y, (b, h, w, 1))], -1)

    if name == "small":
        coords = grid + 0.2 * (coords - grid)  # flow within +-0.4

        def check(v):  # 1-3 blocks at every level, three somewhere
            assert all(1 <= n.min() and n.max() <= 3 for n in _n(v))
            assert _n(v)[0].max() == 3
    elif name == "level0_empty":
        # flow shifted up past the frame: no window reaches level 0's
        # first row, levels 1 and 2 (coarser rows) are still in reach
        coords = with_y(-2.5 + 0.15 * (coords - grid)[..., 1:])

        def check(v):
            n0, n1, n2 = _n(v)
            assert (n0 == 0).all() and (n1 == 1).all() and (n2 == 1).all()
    elif name == "bottom_empty":
        # flow shifted down: the last query blocks look past the end of
        # levels 0 and 1 and still into level 2
        coords = with_y(grid[..., 1:] + 5.25)

        def check(v):
            n0, n1, n2 = _n(v)
            past = (n0 == 0) & (n1 == 0)
            assert past.any() and not past.all() and (n2[past] > 0).all()
            assert (n0 > 0).any()
    elif name == "all_empty":
        coords = coords + 1000.0

        def check(v):
            assert all((n == 0).all() for n in _n(v))
    elif name == "one_visit":
        coords = with_y(-5.0)  # out of reach of levels 0 and 1

        def check(v):
            assert (sum(_n(v)) == 1).all() and (_n(v)[2] == 1).all()
    elif name == "clipped_both":
        # each row of queries looks past both ends of every level
        ys = jnp.where(jnp.arange(w) % 2 == 0, -2.0, h + 2.0)
        coords = with_y(ys[None, None, :, None])

        def check(v):
            for (f, e), nb in zip(v, n_blocks):
                assert (f == 0).all() and (e == nb).all()
    elif name == "unaligned_query_block":
        coords = grid + 0.1 * (coords - grid)

        def check(v):  # its own rows lie in two row blocks, the reach adds
            assert _n(v)[0].max() >= 4
    elif name == "degenerate_tail":
        def check(v):
            assert [lv.shape[1] for lv in levels] == [3, 1, 0]
            assert (_n(v)[2] == 0).all() and (_n(v)[0] > 0).all()
    else:
        def check(v):
            assert any(n.max() > 1 for n in _n(v))
    if dtype == "int8":  # dequantization scales ride the weights
        ww = (2 * RADIUS + 1) ** 2
        weight = jnp.concatenate(
            [weight[i * ww:(i + 1) * ww] * lc.scales[i] for i in range(3)])
    return lc.fmap1, levels, coords, weight, bias, check


class TestVisitedRangePipeline:
    """The row-block loop runs over [first, end) of each level and hands
    the next copy over level boundaries, through two slots. Interpret
    mode copies at `start`, so what these pin is the ranges, the slots
    and the hand-over's choice of block — not the overlap (the chip's)."""

    CASES = ["small", "level0_empty", "bottom_empty", "all_empty",
             "one_visit", "clipped_both", "degenerate_tail", "batch2_tail",
             "unaligned_query_block", "bf16", "int8"]

    @staticmethod
    def _case(name):
        f1, levels, coords, weight, bias, check = _pipeline_case(name)
        check(_visited(coords, [lv.shape[1] for lv in levels], ROWS,
                       PIXEL_BLOCK, RADIUS))
        return f1, levels, coords, weight, bias

    @pytest.mark.parametrize("name", CASES)
    def test_fused_matches_reference(self, name):
        f1, levels, coords, weight, bias = self._case(name)
        out = flash_fused_step(f1, levels, coords, weight, bias, RADIUS, True)
        ref = fused_reference(f1, levels, coords, weight, bias, RADIUS)
        assert float(jnp.max(jnp.abs(out - ref))) <= 1e-3
        if name == "all_empty":  # no visit, no copy: the bias alone
            np.testing.assert_array_equal(
                np.asarray(out), np.broadcast_to(np.asarray(bias), out.shape))

    @pytest.mark.parametrize("name", CASES)
    def test_lookup_matches_reference(self, name):
        """The unfused kernel, one call a level at that level's coords
        (one level a call: the ranges and slots without the hand-over)."""
        f1, levels, coords, _, _ = self._case(name)
        for lvl, f2 in enumerate(levels):
            co = coords / (2.0 ** lvl)
            out = flash_local_corr_level(f1, f2, co, RADIUS, True)
            if name == "all_empty" or f2.shape[1] == 0:
                np.testing.assert_array_equal(np.asarray(out), 0.0)
            else:
                ref = local_corr_level(f1, f2.astype(jnp.float32), co, RADIUS)
                assert float(jnp.max(jnp.abs(out - ref))) <= 1e-3

    def test_rows_outside_every_range_are_never_read(self):
        """Poison: NaN rows that no query block's range reaches leave the
        output finite and equal; a NaN row inside a range reaches it."""
        f1, f2, coords, weight, bias = _setup(jax.random.PRNGKey(25), h=H,
                                              radius=RADIUS)
        coords = coords.at[..., 1].set(coords[..., 1] % 3.0)  # all look up
        lc = build_local_corr(f1, f2, num_levels=3, radius=RADIUS)
        levels = tuple(lc.fmap2_pyramid)
        clean = flash_fused_step(lc.fmap1, levels, coords, weight, bias,
                                 RADIUS, True)
        visited = _visited(coords, [lv.shape[1] for lv in levels], ROWS,
                           PIXEL_BLOCK, RADIUS)
        poisoned, n_poisoned = [], 0
        for lv, (_, end) in zip(levels, visited):
            reach = int(end.max()) * ROWS  # rows from here on: in no range
            n_poisoned += max(lv.shape[1] - reach, 0)
            poisoned.append(lv.at[:, reach:].set(jnp.nan))
        assert n_poisoned >= 8  # the case poisons something
        out = flash_fused_step(lc.fmap1, tuple(poisoned), coords, weight,
                               bias, RADIUS, True)
        assert bool(jnp.isfinite(out).all())
        np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))
        hit = (levels[0], levels[1].at[:, 0].set(jnp.nan), levels[2])
        out = flash_fused_step(lc.fmap1, hit, coords, weight, bias, RADIUS,
                               True)
        assert bool(jnp.isnan(out).any())


class TestBlockedTilingEquivalence:
    """One big block vs fine row tiling: the same sum, associativity
    aside."""

    def test_rows_block_equivalence(self, monkeypatch):
        radius = 2
        f1, f2, coords, weight, bias = _setup(jax.random.PRNGKey(7),
                                              radius=radius)
        lc = build_local_corr(f1, f2, num_levels=3, radius=radius)
        monkeypatch.setattr(pallas_corr, "_FLASH_ROWS", 64)  # single block
        one = flash_fused_step(lc.fmap1, lc.fmap2_pyramid, coords,
                               weight, bias, radius, True)
        monkeypatch.setattr(pallas_corr, "_FLASH_ROWS", 1)  # finest tiling
        many = flash_fused_step(lc.fmap1, lc.fmap2_pyramid, coords,
                                weight, bias, radius, True)
        assert float(jnp.max(jnp.abs(one - many))) <= 1e-4

    @pytest.mark.parametrize("rows,pixel_block", [(1, 16), (3, 48), (4, 32),
                                                  (8, 128)])
    def test_toy_tiles_match_reference(self, rows, pixel_block, monkeypatch):
        """Any row block and any query block: the sizes of the scratch,
        the shifter's axis and the strided row sum follow them."""
        radius = 2
        f1, f2, coords, weight, bias = _setup(jax.random.PRNGKey(8),
                                              radius=radius)
        lc = build_local_corr(f1, f2, num_levels=3, radius=radius)
        monkeypatch.setattr(pallas_corr, "_FLASH_ROWS", rows)
        monkeypatch.setattr(pallas_corr, "_FLASH_PIXEL_BLOCK", pixel_block)
        out = flash_fused_step(lc.fmap1, lc.fmap2_pyramid, coords, weight,
                               bias, radius, True)
        ref = fused_reference(lc.fmap1, lc.fmap2_pyramid, coords, weight,
                              bias, radius)
        assert float(jnp.max(jnp.abs(out - ref))) <= 1e-4

    def test_pixel_block_override_identical(self, monkeypatch):
        radius = 2
        f1, f2, coords, _, _ = _setup(jax.random.PRNGKey(9), radius=radius)
        a = flash_local_corr_level(f1, f2, coords, radius, True)
        monkeypatch.setattr(pallas_corr, "_FLASH_PIXEL_BLOCK", 64)
        b = flash_local_corr_level(f1, f2, coords, radius, True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def _kernel_equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold
    (the kernel inside `pallas_call`, loop and branch bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _kernel_equations(sub)


class TestWindowingMechanism:
    """What a visit does, read from the kernel's jaxpr: one product with
    the queries on the lanes, x aligned by selects between registers, no
    per-query matmul and no hat over a level's width."""

    @pytest.mark.parametrize("fused", [True, False])
    def test_no_batched_products_and_no_hat_over_the_width(self, fused):
        radius, w2, c = 2, 24, 32  # a width no other size of the call has
        f1, f2, coords, weight, bias = _setup(
            jax.random.PRNGKey(11), h=4, w=w2, c=c, levels=1, radius=radius)
        if fused:
            fn = lambda a, b_, co: flash_fused_step(  # noqa: E731
                a, (b_,), co, weight, bias, radius, True)
        else:
            fn = lambda a, b_, co: flash_local_corr_level(  # noqa: E731
                a, b_, co, radius, True)
        call, = [e for e in _kernel_equations(
            jax.make_jaxpr(fn)(f1, f2, coords).jaxpr)
            if e.primitive.name == "pallas_call"]
        eqns = list(_kernel_equations(call.params["jaxpr"]))
        dots = [e for e in eqns if e.primitive.name == "dot_general"]
        # the correlation itself (and the fused weight product): plain 2-D
        # products, none batched over the queries
        assert len(dots) == (2 if fused else 1)
        for e in dots:
            (_, _), (lhs_batch, rhs_batch) = e.params["dimension_numbers"]
            assert not lhs_batch and not rhs_batch
            assert all(v.aval.ndim == 2 for v in e.invars)
        p_block, rows = pallas_corr._FLASH_PIXEL_BLOCK, pallas_corr._FLASH_ROWS
        dots_t, = [e.outvars[0].aval.shape for e in dots
                   if e.invars[0].aval.shape == (w2 * rows, c)]
        assert dots_t == (w2 * rows, p_block)  # positions x queries
        # nothing holds the level's width on its minor axis: no
        # (P, 2r+1, w2) hat, no (P, rows, w2) relayout of the product
        assert not hasattr(pallas_corr, "_hat")
        minor = [e for e in eqns for v in e.outvars
                 if getattr(v.aval, "shape", ()) and v.aval.shape[-1] == w2]
        assert not minor, minor
        # x by the shifter: one select a binary digit of the window's
        # start on the zero-filled axis
        n = 2 * radius + 2
        selects = [e for e in eqns if e.primitive.name == "select_n"
                   and e.outvars[0].aval.ndim == 3]
        assert len(selects) == (w2 + n).bit_length()
        assert all(e.outvars[0].aval.shape[1:] == (rows, p_block)
                   for e in selects)


class TestConfigTimeRefusals:
    """ISSUE 12 satellite: unknown combinations die at RAFTConfig
    construction, not deep in build_local_corr mid-trace."""

    def test_unknown_corr_impl_refused(self):
        from dexiraft_tpu.config import raft_v1

        with pytest.raises(ValueError, match="unknown corr_impl"):
            raft_v1(corr_impl="cuda")

    def test_unknown_corr_dtype_refused(self):
        from dexiraft_tpu.config import raft_v1

        with pytest.raises(ValueError, match="unknown corr_dtype"):
            raft_v1(corr_dtype="fp16")

    @pytest.mark.parametrize("impl", ["allpairs", "local"])
    def test_fused_requires_flash_names_flash(self, impl):
        from dexiraft_tpu.config import raft_v1

        with pytest.raises(ValueError, match="fused_update.*flash"):
            raft_v1(corr_impl=impl, fused_update=True)
        raft_v1(corr_impl="flash", fused_update=True)  # the one that is

    def test_resolve_corr_impl(self):
        from dexiraft_tpu.config import resolve_corr_impl

        assert resolve_corr_impl("auto", "tpu") == ("flash", True)
        assert resolve_corr_impl("auto", "cpu") == ("allpairs", False)
        assert resolve_corr_impl("local", "tpu") == ("local", False)
        assert resolve_corr_impl("flash", "cpu") == ("flash", False)

    def test_build_local_corr_unknown_kernel_refused(self):
        f1 = jnp.zeros((1, 4, 4, 8), jnp.float32)
        with pytest.raises(ValueError, match="unknown local-corr kernel"):
            build_local_corr(f1, f1, 2, 2, kernel="cuda")

    def test_retired_corr_impl_refused_naming_flash(self):
        """The per-pixel family's name is no configuration any more."""
        from dexiraft_tpu.config import raft_v1

        with pytest.raises(ValueError, match="unknown corr_impl.*flash"):
            raft_v1(corr_impl="pallas")

    def test_retired_kernel_refused_naming_flash(self):
        f1 = jnp.zeros((1, 4, 4, 8), jnp.float32)
        with pytest.raises(ValueError, match="local-corr kernel.*flash"):
            build_local_corr(f1, f1, 2, 2, kernel="pallas")


class TestOneCorrelationDecision:
    """config.py names the implementations; every front end offers that
    list and the kernel module has one environment switch."""

    @pytest.mark.parametrize("cli,auto", [("train_cli", False),
                                          ("eval_cli", True),
                                          ("serve_cli", True)])
    def test_cli_choices_are_the_config_list(self, cli, auto):
        import importlib

        from dexiraft_tpu.config import CORR_IMPLS

        parser = importlib.import_module(f"dexiraft_tpu.{cli}").build_parser()
        action, = [a for a in parser._actions if a.dest == "corr_impl"]
        expected = (["auto"] if auto else []) + list(CORR_IMPLS)
        assert list(action.choices) == expected
        assert "pallas" not in action.choices

    def test_ops_read_one_environment_variable(self):
        reads = []
        for path in glob.glob(osp.join(REPO, "dexiraft_tpu", "ops", "*.py")):
            src = open(path).read()
            reads += re.findall(r"(?:environ|getenv)[^\n]*", src)
        assert reads and all("DEXIRAFT_PALLAS_INTERPRET" in r for r in reads)


class TestMemoryFootprint:
    """The compile-time pin: at a geometry where the all-pairs volume
    dominates everything else, the flash executable's temp footprint is
    a small multiple of the fmaps — not the volume."""

    def test_flash_temp_is_o_fmaps_not_o_volume(self):
        # big enough that N^2 >> N*C, small enough to trace fast:
        # N = 5120 queries, C = 64 -> level-0 volume 105 MB vs fmaps
        # 2.6 MB. The flash kernel's scratch is a constant few MB at the
        # default pixel block — the geometry has to be large enough that
        # this constant sits well under 8x fmaps
        h8, w8, c, radius, levels = 40, 128, 64, 4, 4
        n = h8 * w8
        f1 = jax.random.normal(jax.random.PRNGKey(0), (1, h8, w8, c),
                               jnp.float32)
        f2 = jax.random.normal(jax.random.PRNGKey(1), (1, h8, w8, c),
                               jnp.float32)
        ys, xs = jnp.meshgrid(jnp.arange(h8, dtype=jnp.float32),
                              jnp.arange(w8, dtype=jnp.float32),
                              indexing="ij")
        coords = jnp.stack([xs, ys], axis=-1)[None]
        win = 2 * radius + 1
        weight = jnp.ones((levels * win * win, 64), jnp.float32) * 0.01
        bias = jnp.zeros((64,), jnp.float32)

        def flash(f1_, f2_, co_):
            lc = build_local_corr(f1_, f2_, levels, radius, kernel="flash")
            return pallas_corr.flash_fused_step(
                lc.fmap1, lc.fmap2_pyramid, co_, weight, bias, radius,
                lc.level_shapes, True)

        def allpairs(f1_, f2_, co_):
            pyr = build_corr_pyramid(f1_, f2_, levels, radius)
            corr = corr_lookup(pyr, co_)
            return jnp.einsum("bhwc,cf->bhwf", corr, weight) + bias

        def temp_bytes(fn):
            compiled = jax.jit(fn).lower(f1, f2, coords).compile()
            ma = compiled.memory_analysis()
            if ma is None:  # backend declined — nothing to pin
                pytest.skip("memory_analysis unavailable on this backend")
            return float(ma.temp_size_in_bytes)

        flash_temp = temp_bytes(flash)
        allpairs_temp = temp_bytes(allpairs)
        volume_bytes = n * n * 4  # level 0 alone
        fmap_bytes = 2 * n * c * 4
        # the allpairs executable really does carry the volume...
        assert allpairs_temp >= volume_bytes
        # ...and the flash executable carries only fmap-scale buffers:
        # padded fmaps + pyramid + per-tile transients. 8x fmaps is
        # comfortable headroom; the volume is 40x fmaps here, so the
        # assertion genuinely separates O(fmaps) from O(volume)
        assert flash_temp <= 8 * fmap_bytes
        assert flash_temp < allpairs_temp / 2


class TestHighresProbeSchema:
    """Record schema pin for scripts/highres_probe.py (the bench
    validate_record convention — drift fails, silently shifted records
    cannot happen)."""

    @staticmethod
    def _mod():
        spec = importlib.util.spec_from_file_location(
            "_highres_probe", osp.join(REPO, "scripts", "highres_probe.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_validate_record_roundtrip(self):
        hp = self._mod()
        leg = {k: None for k in hp.EVAL_LEG_KEYS}
        rec = {
            "metric": "flash_correlation_memory_probe", "platform": "cpu",
            "model": "raft_v1_full", "strict": True, "iters": 2,
            "eval_geometry": [440, 1024], "eval_ab": [leg],
            "highres_geometry": [1088, 1920],
            "highres": {k: None for k in hp.HIGHRES_KEYS},
            "chained": {k: None for k in hp.CHAINED_KEYS},
        }
        hp.validate_record(rec)  # passes
        with pytest.raises(ValueError, match="drifted"):
            hp.validate_record({**rec, "extra": 1})
        bad = dict(rec)
        del bad["chained"]
        with pytest.raises(ValueError, match="drifted"):
            hp.validate_record(bad)

    def test_bench_schema_covers_flash(self):
        spec = importlib.util.spec_from_file_location(
            "_bench_flash", osp.join(REPO, "bench.py"))
        bench = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bench)
        assert "flash_corr_iters_per_sec" in bench.BENCH_RECORD_KEYS
        assert "flash" in bench.BENCH_DIAG_PREFIXES
