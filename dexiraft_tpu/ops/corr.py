"""All-pairs 4D correlation volume: build, pyramid, windowed lookup.

TPU-native re-design of the reference centerpiece (core/corr.py:12-60):
the volume is one big batched matmul (MXU-friendly), the pyramid is
slice+reshape-mean 2x2 average pooling (NOT lax.reduce_window — see
avg_pool_2x2), and the per-iteration lookup takes a (2r+1)^2 bilinear
window per pixel per level.

Layouts: feature maps are (B, H, W, D). A pyramid level is stored
(B, H_l, W_l, H*W): the QUERY pixels on the minor axis, which the TPU
spreads over the 128 lanes of a vector register, the target rows and
columns as major axes. The reference flattens the other way round,
(B*H*W, H_l, W_l, 1): there the minor
pair (H_l, W_l) fills (8, 128) register tiles as (48, 128), (24, 128),
(16, 128), (8, 128) at the chairs crop's 46x62, 23x31, 11x15, 5x7 —
2.24 GB of HBM for 0.69 GB of values, read twice in every training
iteration. With the queries on
the lanes 2852 pads to 2944 (1.03x); the chip's compiler puts the batch on
the sublanes (`f32[16,46,62,2852]{3,0,2,1:T(8,128)}`: 0.710 GB), so a
target position is a whole register and the lookup ALIGNS each query's
window by selects between registers, then weights neighbours by one lerp
(corr_lookup, _axis_window; on a TPU the Pallas kernels of
ops/pallas_window.py). A loop of lookups that is differentiated places
the levels' gradient once, after its backward loop (place_once): the
loop's iterations hand back window cotangents, 59 MB each, and no
level-sized array is written, read back or added inside it.
docs/perf.md "Correlation memory & precision" has the chip's numbers.

This module is the materialized path; the memory-efficient on-demand
equivalent of the reference's alt_cuda_corr CUDA kernel
(alt_cuda_corr/correlation_kernel.cu) is dexiraft_tpu.ops.local_corr,
whose transient per-chunk blocks keep the reference's slab form
(all_pairs_correlation + interp_window below).
"""

from __future__ import annotations

import functools
from typing import Callable, List, Optional

import flax.struct
import jax
import jax.numpy as jnp

from dexiraft_tpu.ops.quant import store_corr


@flax.struct.dataclass
class CorrPyramid:
    """Correlation pyramid + lookup geometry.

    A pytree whose leaves are only the level arrays (and the per-level
    quantization scales, when present); the geometry ints are static aux
    data, so instances are safe to pass through jit boundaries and
    lax.scan carries without tracer leakage into shape arithmetic.
    """

    levels: tuple  # tuple of (B, H_l, W_l, H*W) arrays (fp32/bf16/int8)
    batch: int = flax.struct.field(pytree_node=False)
    ht: int = flax.struct.field(pytree_node=False)
    wd: int = flax.struct.field(pytree_node=False)
    radius: int = flax.struct.field(pytree_node=False)
    # per-level fp32 scalar dequantization scales for int8 storage; None
    # for the scale-free dtypes (ops/quant.py). A pytree leaf tuple.
    scales: Optional[tuple] = None

    @property
    def level_shapes(self) -> tuple:
        """((H_0, W_0), (H_1, W_1), ...): each level's target extent."""
        return tuple(lvl.shape[1:3] for lvl in self.levels)

    def __call__(self, coords: jax.Array,
                 probe: Optional[tuple] = None) -> jax.Array:
        return corr_lookup(self, coords, probe)


def all_pairs_correlation(fmap1: jax.Array, fmap2: jax.Array) -> jax.Array:
    """corr[b, i, j, k, l] = <fmap1[b,i,j,:], fmap2[b,k,l,:]> / sqrt(D).

    fmap1, fmap2: (B, H, W, D). Returns (B*H*W, H, W, 1) in float32 —
    the reference's flattening, one slab per query. For a block that is
    made and dropped inside one lookup (ops/local_corr.py's dense chunk,
    parallel/context.py's ring); the stored pyramid's levels come from
    _query_minor_volume.
    Reference: core/corr.py:52-60 (matmul + /sqrt(dim)), fp32 like
    core/raft.py:139-142.
    """
    b, h, w, d = fmap1.shape
    h2, w2 = fmap2.shape[1:3]  # may differ from (h, w) when the query
    # axis is sharded (context parallelism, parallel/context.py)
    f1 = fmap1.reshape(b, h * w, d).astype(jnp.float32)
    f2 = fmap2.reshape(b, h2 * w2, d).astype(jnp.float32)
    corr = jnp.einsum("bnd,bmd->bnm", f1, f2, preferred_element_type=jnp.float32)
    corr = corr / jnp.sqrt(jnp.float32(d))
    return corr.reshape(b * h * w, h2, w2, 1)


def _query_minor_volume(fmap1: jax.Array, fmap2: jax.Array) -> jax.Array:
    """The same products as all_pairs_correlation, queries last:
    vol[b, k, l, i*W + j] = <fmap1[b,i,j,:], fmap2[b,k,l,:]> / sqrt(D),
    (B, H2, W2, H*W) float32. With fmap2 as the left operand the matmul
    writes this form itself; no transpose follows it.
    """
    b, h, w, d = fmap1.shape
    h2, w2 = fmap2.shape[1:3]  # differ from (h, w) under a sharded query axis
    f1 = fmap1.reshape(b, h * w, d).astype(jnp.float32)
    f2 = fmap2.reshape(b, h2 * w2, d).astype(jnp.float32)
    corr = jnp.einsum("bmd,bnd->bmn", f2, f1, preferred_element_type=jnp.float32)
    corr = corr / jnp.sqrt(jnp.float32(d))
    return corr.reshape(b, h2, w2, h * w)


def avg_pool_2x2(x: jax.Array) -> jax.Array:
    """2x2 stride-2 average pool over the spatial dims of (N, H, W, C).

    VALID padding so odd trailing rows/cols are dropped — exactly
    torch.nn.functional.avg_pool2d(x, 2, stride=2) (core/corr.py:26).

    Implemented as slice + reshape + mean rather than lax.reduce_window:
    identical numerics, cleanly differentiable in reverse mode (reduce_window
    linearization is unsupported on some backends), and XLA lowers it to the
    same windowed reduction.
    """
    n, h, w, c = x.shape
    h2, w2 = h // 2, w // 2
    x = x[:, : 2 * h2, : 2 * w2, :]
    x = x.reshape(n, h2, 2, w2, 2, c)
    return x.mean(axis=(2, 4))


@jax.named_scope("build_corr_pyramid")
def build_corr_pyramid(
    fmap1: jax.Array, fmap2: jax.Array, num_levels: int = 4, radius: int = 4,
    dtype: str = "fp32",
) -> CorrPyramid:
    """Materialize the all-pairs volume and its average-pool pyramid.

    Reference: core/corr.py:13-27. Level i has shape
    (B, H >> i, W >> i, H*W) (floor division via VALID pooling; the
    queries on the minor axis — module docstring).

    The reference pools the VOLUME; correlation is linear in fmap2, so
    avg-pooling the volume's target dims equals correlating against the
    avg-pooled fmap2 — mathematically identical (mean of dots = dot of
    mean), but each level is then a direct MXU matmul instead of strided
    2x2 pooling passes over the ~200 MB level-0 volume, which on TPU cost
    more than the matmul itself.

    ``dtype`` is the STORAGE precision of the pyramid ("fp32", "bf16",
    "int8" — ops/quant.py): correlation is always computed fp32, then
    each level is stored low-precision (per-level scale for int8) and
    dequantized inside the lookup's first contraction. This halves/quarters the
    HBM bytes every refinement iteration streams — the loop's bandwidth
    term (docs/perf.md "Correlation memory & precision").
    """
    b, h, w, _ = fmap1.shape
    f2 = fmap2
    levels: List[jax.Array] = []
    scales: List[Optional[jax.Array]] = []
    for _ in range(num_levels):
        lvl, scale = store_corr(_query_minor_volume(fmap1, f2), dtype)
        levels.append(lvl)
        scales.append(scale)
        f2 = avg_pool_2x2(f2.astype(jnp.float32))
    return CorrPyramid(
        levels=tuple(levels), batch=b, ht=h, wd=w, radius=radius,
        scales=tuple(scales) if dtype == "int8" else None)


def _window_delta(radius: int, dtype=jnp.float32) -> jax.Array:
    """(2r+1, 2r+1, 2) offset lattice, channels (x-offset, y-offset).

    Matches the reference's ordering EXACTLY (core/corr.py:37-43): it
    stacks meshgrid(dy, dx) onto (x, y) centroids, so the x offset varies
    along window axis 0 and the y offset along axis 1 (a transposed
    window). Bit-compatibility here is what lets reference-trained
    checkpoints load via interop.torch_convert — the motion encoder's
    first conv consumes these 324 channels in this order.
    """
    d = jnp.arange(-radius, radius + 1, dtype=dtype)
    di, dj = jnp.meshgrid(d, d, indexing="ij")  # di varies along axis 0
    return jnp.stack([di, dj], axis=-1)  # (x + di, y + dj)


def _axis_interp_matrix(center: jax.Array, radius: int, size: int,
                        offset=0) -> jax.Array:
    """Per-pixel 1-D bilinear selection matrix A (N, 2r+1, size).

    Row j interpolates the axis at coordinate t = c_n + (j - radius);
    linear interpolation between floor(t) and floor(t)+1 is exactly the
    triangular hat kernel, so A[n, j, p] = relu(1 - |p - t|) — one fused
    elementwise expression, and out-of-range taps have empty support,
    reproducing the zero padding of bilinear_sampler /
    F.grid_sample(zeros). d/dc matches grid_sample's coordinate gradient
    almost everywhere.

    ``offset`` shifts the axis positions: column p represents global
    coordinate offset + p (used by ring context parallelism, where each
    chip holds a row BLOCK of the target axis).
    """
    t = center[:, None] + jnp.arange(-radius, radius + 1,
                                     dtype=jnp.float32)  # (N, win)
    pos = offset + jnp.arange(size, dtype=jnp.float32)[None, None, :]
    return jnp.maximum(0.0, 1.0 - jnp.abs(pos - t[..., None]))


def interp_window(vol: jax.Array, centers: jax.Array,
                  radius: int) -> jax.Array:
    """Bilinear (2r+1)^2 window of each volume SLAB around its center.

    vol (N, Hl, Wl), centers (N, 2) in level pixels -> (N, (2r+1)^2).

    The slab-form helper: for a transient block in the reference's
    flattening (all_pairs_correlation), one slab per query — the dense
    chunk of ops/local_corr.py. The stored pyramid's lookup is corr_lookup
    below; the mathematics is the same.

    The taps sit at INTEGER offsets from one real-valued center per slab,
    so every tap shares the slab's fractional part and the 2-D bilinear
    interpolation separates into per-axis 1-D stencils,

        window[n] = A_x[n] · vol[n]ᵀ · A_y[n]ᵀ,

    batched matmuls against per-pixel hat matrices (_axis_interp_matrix),
    written as one three-operand einsum. On the chip its operands pad: a
    (9, W_l) or (H_l, W_l) minor pair fills a fraction of an (8, 128)
    tile, which is why the stored pyramid is not in this form (timings
    in corr_lookup's docstring).

    The window axis order matches _window_delta: x offset on the SLOW
    axis — the reference's transposed window (core/corr.py:37-43).
    """
    win = 2 * radius + 1
    hl, wl = vol.shape[1], vol.shape[2]
    ax = _axis_interp_matrix(centers[:, 0], radius, wl)  # (N, win, Wl)
    ay = _axis_interp_matrix(centers[:, 1], radius, hl)  # (N, win, Hl)
    # TPU's default matmul precision truncates fp32 inputs to bf16
    window = jnp.einsum("nby,nyx,nax->nab", ay, vol, ax,
                        preferred_element_type=jnp.float32)
    return window.reshape(vol.shape[0], win * win)


def _window_geometry(center: jax.Array, radius: int, size: int):
    """What a lookup reads of one target axis of length ``size``.

    A window is the n = 2r + 2 positions from i0 = floor(c) - r on; i0 is
    clamped to [-n, size], at either end a window wholly outside the frame.
    center (B, Q) -> start = i0 + n (int32, the window's first position on
    the axis padded by n zeros in front) and frac = c - floor(c), the weight
    of each position's right neighbour.
    """
    n = 2 * radius + 2
    whole = jnp.floor(center)
    start = (jnp.clip(whole - radius, -n, size) + n).astype(jnp.int32)
    return start, center - whole


def _digits(start: jax.Array, top: int) -> list:
    """The masks of start's binary digits up to ``top``, lowest first."""
    return [(start & (1 << k)) != 0 for k in range(max(top, 1).bit_length())]


def _padded_length(n: int, size: int) -> int:
    """Positions of the zero-filled axis the shifter's first stage reads."""
    return n + (1 << max(size + n, 1).bit_length()) - 1


def _shift_in(padded, digits, n: int, axis: int):
    """padded[start + j], j < n, along ``axis``: a log-step shifter. The
    positions are a major axis and the masks cover what is minor to it, so
    a shift is a static slice and a stage one select; highest digit first,
    stage k keeps the n + 2**k - 1 positions the lower digits still reach.
    """
    x = padded
    for k in reversed(range(len(digits))):
        step, width = 1 << k, n + (1 << k) - 1
        x = jnp.where(digits[k],
                      jax.lax.slice_in_dim(x, step, step + width, axis=axis),
                      jax.lax.slice_in_dim(x, 0, width, axis=axis))
    return x


def _shift_out(taps, digits, limit: int, axis: int):
    """_shift_in's transpose: tap j to position start + j of an axis of
    ``limit`` positions, zero elsewhere; lowest digit first."""
    def fill(like, count):
        shape = list(like.shape)
        shape[axis] = count
        return jnp.zeros(shape, like.dtype)

    y = taps
    for k in range(len(digits)):
        step = 1 << k
        width = min(y.shape[axis] + step, limit)
        gap = fill(y, step)
        y = jnp.where(
            digits[k],
            jax.lax.slice_in_dim(jnp.concatenate([gap, y], axis), 0, width,
                                 axis=axis),
            jax.lax.slice_in_dim(jnp.concatenate([y, gap], axis), 0, width,
                                 axis=axis))
    if y.shape[axis] < limit:
        y = jnp.concatenate([y, fill(y, limit - y.shape[axis])], axis)
    return y


def _lerp(taps, frac, axis):
    n = taps.shape[axis]
    lo = jax.lax.slice_in_dim(taps, 0, n - 1, axis=axis)
    hi = jax.lax.slice_in_dim(taps, 1, n, axis=axis)
    return (1.0 - frac) * lo + frac * hi


def _lerp_transposed(g, frac, axis):
    """The cotangent of the n - 1 lerped values on the n taps."""
    edge = list(g.shape)
    edge[axis] = 1
    edge = jnp.zeros(edge, g.dtype)
    return (jnp.concatenate([(1.0 - frac) * g, edge], axis)
            + jnp.concatenate([edge, frac * g], axis))


def _kernel_interpret() -> Optional[bool]:
    """None: the plain form. Else the Pallas kernels (ops/pallas_window.py),
    compiled on a TPU and interpreted under DEXIRAFT_PALLAS_INTERPRET."""
    from dexiraft_tpu.ops.pallas_corr import _interpret_default

    if jax.default_backend() == "tpu":
        return False
    return True if _interpret_default() else None


def _axis_taps(vol, start, n, axis):
    """The n positions of each query's window, zero outside the frame."""
    size = vol.shape[axis]
    pads = [(0, 0, 0)] * vol.ndim
    pads[axis] = (n, _padded_length(n, size) - n - size, 0)
    padded = jax.lax.pad(vol.astype(jnp.float32), jnp.float32(0), pads)
    digits = [d[:, None, None, :] for d in _digits(start, size + n)]
    return _shift_in(padded, digits, n, axis)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _axis_window(vol: jax.Array, center: jax.Array, radius: int,
                 axis: int) -> jax.Array:
    """The 2r+1 bilinear taps around ``center`` along one target axis.

    vol (B, S1, S2, Q) as stored (fp32, bf16, int8: upcast where it is
    read) with ``axis`` (1 or 2) the target axis, center (B, Q) in that
    axis' pixels -> the same array in float32 with 2r+1 on ``axis``:
    out[j] = (1 - f) * vol[i0 + j] + f * vol[i0 + j + 1], zero outside
    the frame, i0 = floor(c) - r and f = c - floor(c) per (b, q).
    The taps sit at integer offsets from one centre, so they share f and
    are 2r+2 CONSECUTIVE positions: the window is aligned by selects
    between whole registers (a log-step shifter, _shift_in: one select a
    binary digit of the window's start) and weighted by one lerp. On a
    TPU the stages run in VMEM (ops/pallas_window.py); elsewhere they are
    plain `where` on the level.
    """
    n = 2 * radius + 2
    start, frac = _window_geometry(center, radius, vol.shape[axis])
    interpret = _kernel_interpret()
    if interpret is None or vol.size == 0:
        return _lerp(_axis_taps(vol, start, n, axis),
                     frac[:, None, None, :], axis)
    from dexiraft_tpu.ops.pallas_window import align_axis

    return align_axis(vol, start, frac, n, axis, interpret)


def _axis_window_fwd(vol, center, radius, axis):
    return _axis_window(vol, center, radius, axis), (vol, center)


def _axis_placed(g, start, frac, n, size, axis):
    """The plain form of _axis_window's transpose in vol: g (B, S1, S2, Q)
    with n - 1 taps on ``axis`` -> ``size`` positions there, float32."""
    digits = [d[:, None, None, :] for d in _digits(start, size + n)]
    d_taps = _lerp_transposed(g, frac[:, None, None, :], axis)
    return jax.lax.slice_in_dim(
        _shift_out(d_taps, digits, n + size, axis), n, n + size, axis=axis)


def _axis_window_bwd(radius, axis, res, g):
    """The mirror image: the cotangent's taps placed back under the same
    masks, so the level's gradient is written once, dense, in the level's
    own form (B, S1, S2, Q)."""
    vol, center = res
    n, size = 2 * radius + 2, vol.shape[axis]
    start, frac = _window_geometry(center, radius, size)

    # d/d center: the lerp's slope (floor has none), summed per query
    taps = _axis_taps(vol, start, n, axis)
    slope = (jax.lax.slice_in_dim(taps, 1, n, axis=axis)
             - jax.lax.slice_in_dim(taps, 0, n - 1, axis=axis))
    d_center = jnp.sum(g * slope, axis=(1, 2))

    interpret = _kernel_interpret()
    if vol.size == 0:
        d_vol = jnp.zeros(vol.shape, jnp.float32)
    elif interpret is None:
        d_vol = _axis_placed(g, start, frac, n, size, axis)
    else:
        from dexiraft_tpu.ops.pallas_window import place_axis

        d_vol = place_axis(g, start, frac, n, size, axis, interpret)
    if not jnp.issubdtype(vol.dtype, jnp.floating):  # int8: no tangent space
        return None, d_center
    return d_vol.astype(vol.dtype), d_center


_axis_window.defvjp(_axis_window_fwd, _axis_window_bwd)


def lookup_centres(coords: jax.Array) -> jax.Array:
    """(B, H, W, 2) coordinates as a lookup reads them: (B, 2, H*W)
    float32, x then y, the queries on the lanes."""
    b, h, w, _ = coords.shape
    return jnp.swapaxes(coords.reshape(b, h * w, 2).astype(jnp.float32), 1, 2)


@jax.named_scope("corr_lookup")
def corr_lookup(pyramid: CorrPyramid, coords: jax.Array,
                probe: Optional[tuple] = None) -> jax.Array:
    """Sample a (2r+1)^2 window around ``coords / 2^i`` at every level.

    coords: (B, H, W, 2) current correspondence estimates in level-0 pixels.
    Returns (B, H, W, num_levels * (2r+1)^2) float32 correlation features.
    Reference: core/corr.py:29-50. ``probe``, an array (B, 2r+1, 2r+1, H*W)
    a level, is added to the level's window (y, x, the queries on the
    lanes) as the kernels leave it: a zero whose cotangent is the window's,
    in the form the placing kernels take (place_once).

    The same bilinear window as interp_window, zero outside the frame, on
    the stored form (B, H_l, W_l, Q), without the hats: the taps sit at
    integer offsets from one centre, so along an axis they are 2r+2
    CONSECUTIVE positions from i0 = floor(c) - r sharing one fractional
    part, and the queries are on the minor axis, so a position is a whole
    register. Each axis in turn (_axis_window; x first: the level is read
    once and what is left for y is (B, H_l, 2r+1, Q)) takes those positions
    by selects under per-(b, q) masks and weights neighbours by one lerp,
    in fp32; nothing is multiplied by a zero. The gradient with respect to
    the level is the mirror image (a custom_vjp: the cotangent's taps
    placed back under the same masks), written once in the level's own
    form. That is a single lookup's gradient; a loop of lookups under
    place_once leaves this rule unused and places all its iterations'
    cotangents at once. One algorithm for every shape and backend
    (a log-step shifter, _shift_in); on a TPU its stages run in VMEM
    (ops/pallas_window.py), elsewhere as plain `where`. Timed alone at
    N = 45,632, forward | the level's gradient | build + twelve lookups +
    gradient, ms (my chip run, PR 34): the kernels 1.34 | 1.76 | 84.2;
    the dense hats this replaced 3.56 | 5.89 | 209.6; a two-stage select
    chain left to XLA 4.08 | 6.51 | 188.8 (it fetches every static slice
    of a chain from HBM on its own).

    ``levels`` below fp32 are upcast where they are read; an int8 level's
    scale multiplies its window, exact because the lookup is linear in the
    volume.
    """
    r = pyramid.radius
    b, h, w = pyramid.batch, pyramid.ht, pyramid.wd
    win = 2 * r + 1

    centres = lookup_centres(coords.reshape(b, h, w, 2))
    cx, cy = centres[:, 0], centres[:, 1]  # (B, Q)
    out = []
    for i, vol in enumerate(pyramid.levels):
        rows = _axis_window(vol, cx / (2.0**i), r, 2)
        window = _axis_window(rows, cy / (2.0**i), r, 1)  # (B, y, x, Q)
        if pyramid.scales is not None:
            window = window * pyramid.scales[i]
        if probe is not None:
            window = window + probe[i]
        # (B, win_x, win_y, Q): x offset on the slow axis (_window_delta)
        out.append(jnp.swapaxes(window, 1, 2).reshape(b, win * win, h * w))
    out = jnp.concatenate(out, axis=1)  # (B, L*win^2, Q)
    return jnp.swapaxes(out, 1, 2).reshape(b, h, w, -1)


def _placed_once(g, cx, cy, radius: int, hl: int, wl: int, mesh):
    """sum_t of lookup t's gradient with respect to one level: g
    (T, B, 2r+1, 2r+1, Q) the window cotangents (y, x), cx and cy (T, B, Q)
    the centres in the level's pixels -> (B, hl, wl, Q) float32. y first,
    on the nine columns of a window; then x, summed over the stack: on a
    TPU one kernel that writes each block of the level's gradient once
    (ops/pallas_window.py place_axis_sum), elsewhere a scan of plain
    `where`. ``mesh`` is the level's: g, the cotangent of a zero, may not
    say where it lives."""
    n = 2 * radius + 2
    zero = jnp.zeros((g.shape[1], hl, wl, g.shape[-1]), jnp.float32)
    if zero.size == 0:
        return zero
    sy, fy = _window_geometry(cy, radius, hl)
    sx, fx = _window_geometry(cx, radius, wl)
    interpret = _kernel_interpret()
    if interpret is None:
        def add(total, lookup):
            g, sy, fy, sx, fx = lookup
            rows = _axis_placed(g, sy, fy, n, hl, 1)
            return total + _axis_placed(rows, sx, fx, n, wl, 2), None

        return jax.lax.scan(add, zero, (g, sy, fy, sx, fx))[0]
    from dexiraft_tpu.ops.pallas_window import place_axis, place_axis_sum

    rows = place_axis(g, sy, fy, n, hl, 1, interpret, mesh)
    return place_axis_sum(rows, sx, fx, n, wl, 2, interpret, mesh)


def place_once(loop: Callable, pyramid, *args, iters: int):
    """``loop``, ``iters`` lookups into ``pyramid``, with the levels'
    gradient placed once, after the backward loop.

    loop(pyramid, probe, *args) -> (out, centres). ``probe`` is None or,
    a level, zeros (iters, B, 2r+1, 2r+1, Q); lookup t takes every level's
    slice t (corr_lookup's ``probe``: a scan's per-iteration input), and
    centres (iters, B, 2, Q) are the coordinates it read (lookup_centres),
    carrying no gradient. Everything the loop differentiates is in
    ``args``. Returns out.

    Reverse mode sums the cotangent of what a loop only reads over its
    iterations: with the pyramid a constant of a scan, every backward
    iteration writes each level's gradient whole (mostly zeros: a query
    touches 10 of level 0's 62 columns), and the scan's transpose reads it
    back and adds it to the sum it carries, four level-sized passes an
    iteration. The placing is linear in the window's cotangent and the
    coordinates carry no gradient, so d level = sum_t place(g_t; centres_t)
    can wait: here the loop reads the levels as constants, the probe's
    cotangent collects g_t, 59 MB a lookup at the chairs crop where level 0
    alone is 521 MB, and _placed_once places each level's stack as the
    loop left it. What decides is what can be seen: a CorrPyramid with
    floating levels, and a gradient being taken (the rule below runs only
    then; without one the loop runs as it is written). The other arguments'
    gradients are autodiff's own; remat inside the loop keeps its meaning.
    A bf16 level's gradient is summed in fp32 and cast once.
    """
    if not (isinstance(pyramid, CorrPyramid) and pyramid.scales is None
            and all(jnp.issubdtype(lvl.dtype, jnp.floating)
                    for lvl in pyramid.levels)):
        return loop(pyramid, None, *args)[0]
    like = jax.tree.structure(pyramid)
    levels = [(lvl.shape, lvl.dtype, jax.typeof(lvl).sharding.mesh)
              for lvl in pyramid.levels]
    radius = pyramid.radius

    @jax.custom_vjp
    def run(pyramid, *args):
        return loop(pyramid, None, *args)[0]

    def run_fwd(pyramid, *args):
        (b, _, _, q), win = levels[0][0], 2 * radius + 1
        probe = tuple(jnp.zeros((iters, b, win, win, q), jnp.float32)
                      for _ in levels)
        out, pullback, centres = jax.vjp(
            lambda probe, *args: loop(pyramid, probe, *args), probe, *args,
            has_aux=True)
        return out, (pullback, centres)

    def run_bwd(res, g):
        pullback, centres = res
        d_windows, *d_args = pullback(g)
        d_levels = []
        for i, ((_, hl, wl, _), dtype, mesh) in enumerate(levels):
            d = _placed_once(d_windows[i], centres[:, :, 0] / (2.0**i),
                             centres[:, :, 1] / (2.0**i), radius, hl, wl,
                             mesh)
            d_levels.append(d.astype(dtype))
        return (jax.tree.unflatten(like, d_levels), *d_args)

    run.defvjp(run_fwd, run_bwd)
    return run(pyramid, *args)
