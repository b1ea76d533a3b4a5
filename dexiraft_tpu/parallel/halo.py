"""Compute-sharded RAFT step: shard_map spatial partitioning with
explicit halo exchange + per-block fsdp all-gather.

The fence-mode train step (train/step.py) keeps fsdp a STORAGE axis:
state gathers to replicated at entry, compute is the replicated
program, and every device holds the full activation set. This module is
the COMPUTE-sharded alternative: the heavy spatial work runs inside one
``shard_map`` over the (data, fsdp, seq) mesh where

  * each device owns a contiguous slab of image rows (the 'seq' axis;
    in/out spec :meth:`SpecLayout.batch_spatial_compute`). Convolutions
    exchange exactly their receptive-field boundary rows with ppermute
    neighbors (:func:`halo_exchange`; permutations from
    :func:`seq_halo_perms`) and compute on own+halo rows — byte-parity
    with the unsharded program, because the non-circular exchange's
    zero-fill at the mesh edges IS the global conv's zero padding;
  * params stay fsdp-sharded BETWEEN and DURING compute: each top-level
    module block (``param_block_names`` — fnet / cnet / ScanRAFTStep_0)
    is all-gathered immediately before it runs, inside
    ``jax.checkpoint``, so the gathered copies are dropped after use
    and re-gathered in backward — peak gathered-params HBM is ONE
    block, not the tree (:func:`_run_block`). GSPMD never sees an
    fsdp-sharded tensor inside a conv (the miscompile the fence
    guards against, tests/test_zzzfsdp.py), because inside shard_map
    there is no GSPMD — every collective here is explicit.

Halo widths are not folklore: each module's H-axis conv chain is
declared NEXT to its convs (models/extractor.block_conv_chain /
encoder_conv_chain, models/update.*_CHAIN) and composed into
receptive-field margins by :func:`chain_halo`; the resulting per-module
table (:func:`halo_rows`) is pinned by tests/test_zzzhalo.py. The
implementation itself exchanges PER CONV (k, s, p) -> (lo=p,
hi=max(0, k-s-p)) rows, so a single conv never moves more than its own
kernel's support.

The forward here is a manual re-implementation of the flax modules
(exact auto-names, exact op order) rather than flax.apply under
shard_map — flax normalization layers reduce over the LOCAL slab,
which is silently wrong under row sharding; the manual forward psums
the instance-norm moments over 'seq' and runs frozen BatchNorm as a
pure affine. The price is a strict support matrix
(:func:`check_halo_support`): v1 ('raft') variant, allpairs fp32
correlation, no dropout/noise/accumulation, and BatchNorm only frozen.
Loss parity vs the fence step is pinned by tests/test_zzzhalo.py.

Correlation under row sharding: fmap2 (the target space every query
row needs) all-gathers over 'seq' once per step; the pyramid builds
from (local queries x global targets), so each device materializes
only its ROW-BLOCK of the quadratic volume — the context-parallel
formulation of parallel/context.py, now inside the train step. The
lookup is bit-exact vs unsharded (per-query-pixel local math).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh

from dexiraft_tpu.config import RAFTConfig, TrainConfig
from dexiraft_tpu.models.extractor import encoder_conv_chain
from dexiraft_tpu.models.raft import _normalize
from dexiraft_tpu.models.update import (
    CONV_GRU_CHAIN,
    FLOW_HEAD_CHAIN,
    MASK_HEAD_CHAIN,
    MOTION_ENCODER_CHAIN,
    SEP_CONV_GRU_CHAIN,
)
from dexiraft_tpu.ops.corr import (build_corr_pyramid, corr_lookup,
                                   lookup_centres, place_once)
from dexiraft_tpu.ops.grid import _resize_matrix, coords_grid
from dexiraft_tpu.ops.losses import MAX_FLOW
from dexiraft_tpu.ops.upsample import convex_combine
from dexiraft_tpu.parallel.layout import (
    DATA_AXIS,
    FSDP_AXIS,
    LAYOUT,
    SEQ_AXIS,
    param_block_names,
    seq_halo_perms,
)

Chain = Tuple[Tuple[int, int, int], ...]  # ((kernel, stride, pad), ...)


# --------------------------------------------------------------------------
# halo arithmetic — compose a conv chain into receptive-field margins
# --------------------------------------------------------------------------


def chain_halo(chain: Chain) -> Tuple[int, int]:
    """(top, bottom) input-row margins one output row of the chain needs
    beyond the rows it owns.

    Walking the chain LAST conv to FIRST: a single conv (k, s, p) reads
    p rows above its first input row (lo = p) and max(0, k - s - p)
    below its last (hi); a downstream margin of m rows becomes s*m
    input rows through a stride-s conv. Hence the recursion
    lo = p + s*lo_next, hi = max(0, k - s - p) + s*hi_next — the
    standard receptive-field-radius composition, derived from the same
    (k, s, p) triples the convs themselves are built from.
    """
    lo = hi = 0
    for k, s, p in reversed(chain):
        lo = p + s * lo
        hi = max(0, k - s - p) + s * hi
    return lo, hi


def halo_rows() -> Dict[str, int]:
    """Per-module halo width (rows of neighbor context one device needs,
    max of the top/bottom margins) at the module's INPUT resolution.

    Derived live from the declarative conv chains pinned next to the
    modules (models/extractor.py, models/update.py); the expected
    values are pinned by tests/test_zzzhalo.py so a kernel-size change
    that forgets its exchange width fails a test, not a pod run.
    upsample_convex / upflow8 read one coarse row past each slab edge
    (3x3 taps / the bilinear hat's support) — pinned directly, they
    have no conv chain.
    """
    table = {
        "encoder_basic": chain_halo(encoder_conv_chain("residual")),
        "encoder_small": chain_halo(encoder_conv_chain("bottleneck")),
        "motion_encoder": chain_halo(MOTION_ENCODER_CHAIN),
        "gru_conv": chain_halo(CONV_GRU_CHAIN),
        "gru_sep": chain_halo(SEP_CONV_GRU_CHAIN),
        "flow_head": chain_halo(FLOW_HEAD_CHAIN),
        "mask_head": chain_halo(MASK_HEAD_CHAIN),
    }
    rows = {name: max(lo, hi) for name, (lo, hi) in table.items()}
    rows["upsample_convex"] = 1
    rows["upflow8"] = 1
    return rows


# --------------------------------------------------------------------------
# exchange + conv primitives (shard_map-body code: collectives explicit)
# --------------------------------------------------------------------------


def halo_exchange(x: jax.Array, lo: int, hi: int, n_seq: int) -> jax.Array:
    """Extend a (B, L, ...) row slab with ``lo`` rows from the seq
    predecessor and ``hi`` from the successor via neighbor ppermute.

    Non-circular (seq_halo_perms): the first device's top halo and the
    last device's bottom halo arrive ZERO-filled — byte-identical to
    the unsharded conv's symmetric zero padding at the image edges, so
    callers never special-case edge devices. Guards lo/hi == 0 before
    slicing (``x[:, -0:]`` is the whole array, not an empty slab).
    """
    if n_seq <= 1 or (lo == 0 and hi == 0):
        return x
    fwd, bwd = seq_halo_perms(n_seq)
    parts = []
    if lo > 0:
        parts.append(jax.lax.ppermute(x[:, -lo:], SEQ_AXIS, fwd))
    parts.append(x)
    if hi > 0:
        parts.append(jax.lax.ppermute(x[:, :hi], SEQ_AXIS, bwd))
    return jnp.concatenate(parts, axis=1)


def halo_conv(
    x: jax.Array,
    kernel: jax.Array,
    bias: Optional[jax.Array],
    *,
    stride: int = 1,
    n_seq: int = 1,
) -> jax.Array:
    """One NHWC conv on a row slab: exchange the kernel's own H support
    (lo = p, hi = max(0, k - s - p)), then convolve VALID in H and SAME
    in W. Output rows = L/stride, aligned with the device's global row
    block — the composition over a whole chain therefore equals the
    unsharded conv chain row-for-row (parity pinned at bit level by
    tests/test_zzzhalo.py). n_seq == 1 pads zeros locally instead, which
    is the identical global program.
    """
    kh, kw = int(kernel.shape[0]), int(kernel.shape[1])
    p_h, p_w = kh // 2, kw // 2
    lo, hi = p_h, max(0, kh - stride - p_h)
    if lo or hi:
        if n_seq > 1:
            x = halo_exchange(x, lo, hi, n_seq)
        else:
            x = jnp.pad(x, ((0, 0), (lo, hi), (0, 0), (0, 0)))
    y = jax.lax.conv_general_dilated(
        x, kernel,
        window_strides=(stride, stride),
        padding=((0, 0), (p_w, p_w)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    if bias is not None:
        y = y + bias
    return y


def _instance_norm(x: jax.Array, n_seq: int, eps: float = 1e-5) -> jax.Array:
    """Instance norm (per sample, per channel over the FULL H x W) on a
    row slab: local first/second moments psum over 'seq'. Matches flax
    GroupNorm(group_size=1, no scale/bias): var = max(0, E[x^2] - E[x]^2)
    with the same clamp. Association of the cross-device sum differs
    from the single-pass reduction, so this is float-tolerance (not
    bit) parity — covered by the fence-vs-halo loss-parity pin."""
    s = jnp.sum(x, axis=(1, 2))
    ss = jnp.sum(x * x, axis=(1, 2))
    cnt = x.shape[1] * x.shape[2]
    if n_seq > 1:
        s = jax.lax.psum(s, SEQ_AXIS)
        ss = jax.lax.psum(ss, SEQ_AXIS)
        cnt = cnt * n_seq
    mu = s / cnt
    var = jnp.maximum(ss / cnt - mu * mu, 0.0)
    return (x - mu[:, None, None]) * jax.lax.rsqrt(var[:, None, None] + eps)


def _frozen_bn(x, scale, bias, mean, var, eps: float = 1e-5):
    """BatchNorm on running stats — a pure per-channel affine, in flax's
    exact op order ((x - mean) * (rsqrt(var+eps) * scale) + bias), so it
    is bit-identical to the unsharded frozen-BN path row-for-row."""
    mul = jax.lax.rsqrt(var + eps) * scale
    return (x - mean) * mul + bias


def _norm(norm_fn: str, p: Any, st: Any, idx: int, x, n_seq: int):
    if norm_fn == "instance":
        return _instance_norm(x, n_seq)
    if norm_fn == "batch":
        bn_p, bn_s = p[f"BatchNorm_{idx}"], st[f"BatchNorm_{idx}"]
        return _frozen_bn(x, bn_p["scale"], bn_p["bias"],
                          bn_s["mean"], bn_s["var"])
    return x  # "none"


def _conv(p: Any, name: str, x, *, stride: int = 1, n_seq: int = 1):
    leaf = p[name]
    return halo_conv(x, leaf["kernel"], leaf["bias"],
                     stride=stride, n_seq=n_seq)


# --------------------------------------------------------------------------
# manual module forwards (flax auto-names, flax op order)
# --------------------------------------------------------------------------


def _residual_block(p, st, x, stride, norm_fn, n_seq):
    y = jax.nn.relu(_norm(norm_fn, p, st, 0,
                          _conv(p, "Conv_0", x, stride=stride, n_seq=n_seq),
                          n_seq))
    y = jax.nn.relu(_norm(norm_fn, p, st, 1,
                          _conv(p, "Conv_1", y, n_seq=n_seq), n_seq))
    if stride != 1:
        x = _conv(p, "Conv_2", x, stride=stride, n_seq=n_seq)
        x = _norm(norm_fn, p, st, 2, x, n_seq)
    return jax.nn.relu(x + y)


def _bottleneck_block(p, st, x, stride, norm_fn, n_seq):
    y = jax.nn.relu(_norm(norm_fn, p, st, 0,
                          _conv(p, "Conv_0", x, n_seq=n_seq), n_seq))
    y = jax.nn.relu(_norm(norm_fn, p, st, 1,
                          _conv(p, "Conv_1", y, stride=stride, n_seq=n_seq),
                          n_seq))
    y = jax.nn.relu(_norm(norm_fn, p, st, 2,
                          _conv(p, "Conv_2", y, n_seq=n_seq), n_seq))
    if stride != 1:
        x = _conv(p, "Conv_3", x, stride=stride, n_seq=n_seq)
        x = _norm(norm_fn, p, st, 3, x, n_seq)
    return jax.nn.relu(x + y)


def _encoder_fwd(p, st, x, *, small: bool, norm_fn: str, n_seq: int):
    """models/extractor.Encoder, manually: 7x7/2 stem -> 2 blocks per
    stage -> 1x1 projection, with sharded-aware norms. Stage schedule
    and block auto-names mirror the flax module exactly (param trees
    are shared with the fence path — checkpoints interchange)."""
    from dexiraft_tpu.models.extractor import BASIC_STAGES, SMALL_STAGES
    stages = SMALL_STAGES if small else BASIC_STAGES
    block_fwd = _bottleneck_block if small else _residual_block
    cls = "BottleneckBlock" if small else "ResidualBlock"

    x = _conv(p, "Conv_0", x, stride=2, n_seq=n_seq)
    x = jax.nn.relu(_norm(norm_fn, p, st, 0, x, n_seq))
    i = 0
    for _, stride in stages:
        for s in (stride, 1):
            name = f"{cls}_{i}"
            x = block_fwd(p[name], st.get(name, {}) if st else {},
                          x, s, norm_fn, n_seq)
            i += 1
    return _conv(p, "Conv_1", x, n_seq=n_seq)


def _small_update(p, net, inp, corr, flow, n_seq):
    """models/update.SmallUpdateBlock, manually. ``p`` is the
    ScanRAFTStep_0 subtree (the update block is its one child)."""
    p = p["SmallUpdateBlock_0"]
    me = p["SmallMotionEncoder_0"]
    cor = jax.nn.relu(_conv(me, "Conv_0", corr, n_seq=n_seq))
    flo = jax.nn.relu(_conv(me, "Conv_1", flow, n_seq=n_seq))
    flo = jax.nn.relu(_conv(me, "Conv_2", flo, n_seq=n_seq))
    out = jax.nn.relu(_conv(me, "Conv_3",
                            jnp.concatenate([cor, flo], -1), n_seq=n_seq))
    motion = jnp.concatenate([out, flow], -1)

    x = jnp.concatenate([inp, motion], -1)
    g = p["ConvGRU_0"]
    hx = jnp.concatenate([net, x], -1)
    z = jax.nn.sigmoid(_conv(g, "Conv_0", hx, n_seq=n_seq))
    r = jax.nn.sigmoid(_conv(g, "Conv_1", hx, n_seq=n_seq))
    q = jnp.tanh(_conv(g, "Conv_2",
                       jnp.concatenate([r * net, x], -1), n_seq=n_seq))
    net = (1 - z) * net + z * q

    fh = p["FlowHead_0"]
    delta = _conv(fh, "Conv_1", jax.nn.relu(_conv(fh, "Conv_0", net,
                                                  n_seq=n_seq)), n_seq=n_seq)
    return net, None, delta


def _sep_gru_pass(g, base: int, h, x, n_seq):
    hx = jnp.concatenate([h, x], -1)
    z = jax.nn.sigmoid(_conv(g, f"Conv_{base}", hx, n_seq=n_seq))
    r = jax.nn.sigmoid(_conv(g, f"Conv_{base + 1}", hx, n_seq=n_seq))
    q = jnp.tanh(_conv(g, f"Conv_{base + 2}",
                       jnp.concatenate([r * h, x], -1), n_seq=n_seq))
    return (1 - z) * h + z * q


def _basic_update(p, net, inp, corr, flow, n_seq):
    """models/update.BasicUpdateBlock, manually (incl. the mask head,
    whose Conv_0/Conv_1 live at the update block's own scope). ``p`` is
    the ScanRAFTStep_0 subtree."""
    p = p["BasicUpdateBlock_0"]
    me = p["BasicMotionEncoder_0"]
    cor = jax.nn.relu(_conv(me, "Conv_0", corr, n_seq=n_seq))
    cor = jax.nn.relu(_conv(me, "Conv_1", cor, n_seq=n_seq))
    flo = jax.nn.relu(_conv(me, "Conv_2", flow, n_seq=n_seq))
    flo = jax.nn.relu(_conv(me, "Conv_3", flo, n_seq=n_seq))
    out = jax.nn.relu(_conv(me, "Conv_4",
                            jnp.concatenate([cor, flo], -1), n_seq=n_seq))
    motion = jnp.concatenate([out, flow], -1)

    x = jnp.concatenate([inp, motion], -1)
    g = p["SepConvGRU_0"]
    net = _sep_gru_pass(g, 0, net, x, n_seq)  # (1,5) horizontal
    net = _sep_gru_pass(g, 3, net, x, n_seq)  # (5,1) vertical

    fh = p["FlowHead_0"]
    delta = _conv(fh, "Conv_1", jax.nn.relu(_conv(fh, "Conv_0", net,
                                                  n_seq=n_seq)), n_seq=n_seq)

    mask = jax.nn.relu(_conv(p, "Conv_0", net, n_seq=n_seq))
    mask = 0.25 * _conv(p, "Conv_1", mask, n_seq=n_seq)
    return net, mask, delta


# --------------------------------------------------------------------------
# upsampling on row slabs
# --------------------------------------------------------------------------


def _upflow8_halo(flow: jax.Array, n_seq: int) -> jax.Array:
    """ops/grid.upflow8 on a (B, L, W, 2) row slab, bit-exact.

    Output rows [8*c0, 8*(c0+L)) of the global align_corners resize read
    input rows [c0-1, c0+L] only (the hat's support is two adjacent
    taps and the stretch factor is < 1/8 per output row), i.e. the
    local slab + a 1-row halo each side. The hat matrix is the GLOBAL
    one (_resize_matrix — same linspace arithmetic as the unsharded
    path), dynamic-sliced to the device's row block; a zero column
    padded each side makes the c0-1 / c0+L taps in-bounds WITHOUT
    dynamic_slice's start clamping shifting the window at the mesh
    edges. Zero-weight taps against zero-filled halo rows contribute
    exact +-0, so the two-tap sums match the unsharded einsum bitwise.
    """
    b, lc, wc = flow.shape[:3]
    if n_seq <= 1:
        from dexiraft_tpu.ops.grid import upflow8
        return upflow8(flow)
    h_tot = lc * n_seq
    ry = _resize_matrix(h_tot, 8 * h_tot, flow.dtype)
    ry = jnp.pad(ry, ((0, 0), (1, 1)))
    c0 = jax.lax.axis_index(SEQ_AXIS) * lc
    m_h = jax.lax.dynamic_slice(ry, (8 * c0, c0), (8 * lc, lc + 2))
    rx = _resize_matrix(wc, 8 * wc, flow.dtype)

    xh = halo_exchange(flow, 1, 1, n_seq)  # (B, L+2, W, 2)
    out = jnp.einsum("oy,nyxc->noxc", m_h, xh,
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32).astype(flow.dtype)
    out = jnp.einsum("px,noxc->nopc", rx, out,
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32).astype(flow.dtype)
    return 8.0 * out


def _upsample_convex_halo(flow: jax.Array, mask: jax.Array,
                          n_seq: int) -> jax.Array:
    """ops/upsample.upsample_flow_convex on a row slab: the 3x3 patch
    extraction needs one coarse row past each slab edge —
    halo-exchanged where the unsharded path zero-pads (same zeros at
    the global edges, by the non-circular exchange contract)."""
    fp = halo_exchange(8.0 * flow, 1, 1, n_seq)  # rows: L + 2
    fp = jnp.pad(fp, ((0, 0), (0, 0), (1, 1), (0, 0)))
    return convex_combine(fp, mask)


def _upsample_halo(flow, mask, n_seq):
    if mask is None:
        return _upflow8_halo(flow, n_seq)
    return _upsample_convex_halo(flow.astype(jnp.float32), mask, n_seq)


def _coords_grid_sharded(b: int, l8: int, w8: int, n_seq: int) -> jax.Array:
    """coords_grid in GLOBAL pixel coordinates on a row slab: the local
    grid plus this device's global row offset on the y channel. Global
    coords are what makes the correlation lookup bit-exact — the level
    arrays span the full (gathered) target height."""
    c = coords_grid(b, l8, w8)
    if n_seq > 1:
        off = (jax.lax.axis_index(SEQ_AXIS) * l8).astype(jnp.float32)
        c = c + jnp.stack([jnp.zeros_like(off), off])
    return c


# --------------------------------------------------------------------------
# sharded loss / metrics (global sums via psum; static global count)
# --------------------------------------------------------------------------


def _flow_metrics_sharded(pred, gt, valid_mask):
    epe = jnp.sqrt(jnp.sum((pred - gt) ** 2, axis=-1))
    v = valid_mask.astype(jnp.float32)
    sums = jnp.stack([
        jnp.sum(epe * v),
        jnp.sum((epe < 1.0).astype(jnp.float32) * v),
        jnp.sum((epe < 3.0).astype(jnp.float32) * v),
        jnp.sum((epe < 5.0).astype(jnp.float32) * v),
        jnp.sum(v),
    ])
    sums = jax.lax.psum(sums, (DATA_AXIS, SEQ_AXIS))
    denom = jnp.maximum(sums[4], 1.0)
    return {"epe": sums[0] / denom, "1px": sums[1] / denom,
            "3px": sums[2] / denom, "5px": sums[3] / denom}


def _sequence_loss_sharded(flow_preds, flow_gt, valid, gamma,
                           n_data, n_seq):
    """ops/losses.sequence_loss on (data, seq)-sharded predictions,
    returned as this device's LOCAL CONTRIBUTION to the global loss:
    local |err| sums divided by the STATIC GLOBAL element count — the
    psum over (data, seq) happens OUTSIDE value_and_grad (body), so the
    gradient seed is the plain per-device cotangent and the grads'
    cross-device psum counts each contribution exactly once (psum's
    transpose is itself a psum: seeding the replicated psum'd scalar
    would scale every grad by n_data*n_seq). Masking semantics match
    the unsharded loss exactly (invalid pixels zeroed but counted)."""
    n = flow_preds.shape[0]
    mag = jnp.sqrt(jnp.sum(flow_gt ** 2, axis=-1))
    valid_mask = (valid >= 0.5) & (mag < MAX_FLOW)
    vf = valid_mask.astype(jnp.float32)[None, ..., None]

    weights = gamma ** jnp.arange(n - 1, -1, -1, dtype=jnp.float32)
    i_loss = jnp.abs(flow_preds - flow_gt[None])
    local = jnp.sum(vf * i_loss, axis=(1, 2, 3, 4))  # (n,)
    count = ((flow_preds.shape[1] * n_data)
             * (flow_preds.shape[2] * n_seq)
             * flow_preds.shape[3] * 2)
    local_loss = jnp.sum(weights * (local / count))

    metrics = _flow_metrics_sharded(flow_preds[-1], flow_gt, valid_mask)
    return local_loss, metrics


# --------------------------------------------------------------------------
# per-block fsdp gather (gather -> use -> drop)
# --------------------------------------------------------------------------


def _spec_dim(spec) -> int:
    """Index of the fsdp-sharded dim in a param leaf spec, -1 if the
    leaf is replicated. Spec trees are NOT tree-mapped over
    (PartitionSpec is a tuple subclass — jax.tree would descend into
    it); the int trees this produces are what the body logic walks."""
    for i, entry in enumerate(tuple(spec)):
        if entry == FSDP_AXIS:
            return i
    return -1


def _run_block(fn: Callable, block_params: Any, block_dims: Any,
               n_fsdp: int, *args):
    """Run ``fn(full_params, *args)`` with the block's fsdp-sharded
    leaves all-gathered just-in-time. The gather AND the block compute
    sit inside one jax.checkpoint: the gathered leaves are not residuals
    (backward re-gathers and recomputes), so peak gathered-params HBM is
    one block — gather -> use -> drop. Replicated leaves (dim -1: small
    biases/norm params per LAYOUT.param_leaf_spec) pass through."""
    dims = jax.tree.leaves(block_dims)
    if n_fsdp <= 1 or not any(d >= 0 for d in dims):
        return fn(block_params, *args)

    def gathered_call(bp, *a):
        full = jax.tree.map(
            lambda leaf, d: (jax.lax.all_gather(leaf, FSDP_AXIS,
                                                axis=d, tiled=True)
                             if d >= 0 else leaf),
            bp, block_dims)
        return fn(full, *a)

    return jax.checkpoint(gathered_call)(block_params, *args)


# --------------------------------------------------------------------------
# support matrix
# --------------------------------------------------------------------------


def check_halo_support(cfg: RAFTConfig, tc: TrainConfig,
                       mesh: Optional[Mesh]) -> None:
    """Refuse configurations the halo forward does not reproduce, each
    with a one-line actionable error — the v1 support matrix
    (docs/parallel.md "Compute sharding")."""
    if mesh is None or not LAYOUT.has_seq(mesh):
        raise ValueError(
            "compute_sharding='halo' needs a mesh with a 'seq' axis — "
            "build one with make_mesh_fsdp(n_data, n_fsdp, n_seq) or "
            "make_mesh_2d(n_data, n_seq)")
    if cfg.variant != "raft":
        raise ValueError(
            f"compute_sharding='halo' supports variant='raft' (v1) only, "
            f"got {cfg.variant!r} — edge streams / DexiNed are not halo-"
            "sharded yet; use compute_sharding='fence'")
    if cfg.corr_impl != "allpairs" or cfg.corr_dtype != "fp32":
        raise ValueError(
            f"compute_sharding='halo' needs corr_impl='allpairs' with "
            f"corr_dtype='fp32' (got {cfg.corr_impl!r}/{cfg.corr_dtype!r}) "
            "— the sharded lookup builds the row-block pyramid explicitly")
    if cfg.fused_update:
        raise ValueError(
            "compute_sharding='halo' does not support fused_update — the "
            "Pallas fused step is not shard_map-partitioned; use "
            "compute_sharding='fence'")
    if cfg.mixed_precision or tc.precision != "fp32":
        raise ValueError(
            "compute_sharding='halo' is fp32-only for now (precision="
            f"{tc.precision!r}, mixed_precision={cfg.mixed_precision}) — "
            "bit-parity with the fence step is pinned in fp32")
    if cfg.dropout > 0.0:
        raise ValueError(
            "compute_sharding='halo' does not support dropout>0 — the "
            "manual forward draws no per-device RNG; set dropout=0.0")
    if tc.add_noise:
        raise ValueError(
            "compute_sharding='halo' does not support add_noise — noise "
            "RNG is not split per row slab; disable it or use 'fence'")
    if tc.accum_steps != 1:
        raise ValueError(
            f"compute_sharding='halo' needs accum_steps=1 (got "
            f"{tc.accum_steps}) — accumulate by growing the data axis")
    if tc.edge_sum_fusion:
        raise ValueError(
            "compute_sharding='halo' does not support edge_sum_fusion "
            "(v1-lineage double forward); use compute_sharding='fence'")
    if (not cfg.small) and not tc.freeze_bn:
        raise ValueError(
            "compute_sharding='halo' runs BatchNorm frozen only: set "
            "freeze_bn=True (post-chairs stages already do) or use the "
            "small model — train-mode sync-BN stats are not exchanged")
    n_data = LAYOUT.data_size(mesh)
    n_seq = LAYOUT.seq_size(mesh)
    if tc.batch_size % n_data != 0:
        raise ValueError(
            f"batch_size {tc.batch_size} not divisible by the mesh's "
            f"{n_data}-way data axis")
    h = tc.image_size[0]
    if h % (8 * n_seq) != 0:
        raise ValueError(
            f"image height {h} must be divisible by 8*n_seq={8 * n_seq} "
            f"so every device owns whole 1/8-resolution rows — pad with "
            f"data.padder.InputPadder(shape, seq={n_seq})")
    if h // (8 * n_seq) < 3:
        raise ValueError(
            f"image height {h} over {n_seq} seq shards leaves "
            f"{h // (8 * n_seq)} rows per device at 1/8 resolution; "
            "need >= 3 (the update block's 7x7 support) — use fewer seq "
            "shards or taller crops")


# --------------------------------------------------------------------------
# the sharded forward + train/eval fn factories
# --------------------------------------------------------------------------


def _halo_forward(cfg: RAFTConfig, params, batch_stats, im1, im2, *,
                  n_seq: int, n_fsdp: int, param_dims, iters: int,
                  remat_mode: str, unroll: int, emit: bool,
                  flow_init=None):
    """The v1 RAFT forward on (B_loc, H_loc, W, C) slabs — mirrors
    models/raft.RAFT.__call__ (mode='pair') op-for-op, with per-block
    fsdp gathers and explicit halo exchange. emit=True returns the
    per-iteration upsampled flows (training); emit=False returns
    (flow_low, flow_up) (test mode)."""
    small = cfg.small
    ctx_norm = "none" if small else "batch"
    hdim = cfg.hidden_dim
    update_fwd = _small_update if small else _basic_update

    x1 = _normalize(im1.astype(jnp.float32))
    x2 = _normalize(im2.astype(jnp.float32))

    # fnet on both frames, one batched call like the flax path (instance
    # norm is per-sample, so batch concat changes nothing numerically)
    both = jnp.concatenate([x1, x2], axis=0)
    fmaps = _run_block(
        lambda p, x: _encoder_fwd(p, {}, x, small=small,
                                  norm_fn="instance", n_seq=n_seq),
        params["fnet"], param_dims["fnet"], n_fsdp, both)
    fmap1, fmap2 = jnp.split(fmaps, 2, axis=0)

    cnet_stats = batch_stats.get("cnet", {}) if batch_stats else {}
    ctx = _run_block(
        lambda p, x: _encoder_fwd(p, cnet_stats, x, small=small,
                                  norm_fn=ctx_norm, n_seq=n_seq),
        params["cnet"], param_dims["cnet"], n_fsdp, x1)
    net = jnp.tanh(ctx[..., :hdim])
    inp = jax.nn.relu(ctx[..., hdim:])

    # row-block correlation pyramid: local queries x gathered targets —
    # each device holds only its (B*H_loc*W, H, W) volume slice
    f2_full = (jax.lax.all_gather(fmap2, SEQ_AXIS, axis=1, tiled=True)
               if n_seq > 1 else fmap2)
    pyr = build_corr_pyramid(fmap1, f2_full, cfg.corr_levels, cfg.radius)

    b_loc, l8, w8 = fmap1.shape[:3]
    coords0 = _coords_grid_sharded(b_loc, l8, w8, n_seq)
    coords1 = coords0 if flow_init is None else coords0 + flow_init

    def refine(pyr, probe, up_params, net, coords1, inp, coords0):
        # probe: None, or a zero for each lookup's windows, with which a
        # step also emits the coordinates it looked up (place_once)
        def step(carry, probe):
            net, coords1 = carry
            coords1 = looked_up = jax.lax.stop_gradient(coords1)
            flow = coords1 - coords0
            corr = corr_lookup(pyr, coords1, probe)
            net, up_mask, delta = update_fwd(up_params, net, inp, corr,
                                             flow, n_seq)
            coords1 = coords1 + delta.astype(jnp.float32)
            ys = (_upsample_halo(coords1 - coords0, up_mask, n_seq) if emit
                  else up_mask)
            return (net, coords1), (
                ys, None if probe is None else lookup_centres(looked_up))

        if remat_mode == "per_iter":
            step = jax.checkpoint(step, prevent_cse=False)
        elif remat_mode == "dots_saveable":
            step = jax.checkpoint(
                step, prevent_cse=False,
                policy=jax.checkpoint_policies.dots_saveable)
        (net, coords1), (ys, centres) = jax.lax.scan(
            step, (net, coords1), probe, length=iters,
            unroll=max(1, min(unroll, iters)))
        return (coords1, ys), centres

    def scan_block(up_params, net, coords1, inp, pyr, coords0):
        # a gradient taken through the loop places the levels' once
        return place_once(refine, pyr, up_params, net, coords1, inp, coords0,
                          iters=iters)

    coords1, ys = _run_block(scan_block, params["ScanRAFTStep_0"],
                             param_dims["ScanRAFTStep_0"], n_fsdp,
                             net, coords1, inp, pyr, coords0)
    if emit:
        return ys  # (iters, B_loc, 8*L, 8*W, 2)
    flow_low = coords1 - coords0
    up_mask = None if small else ys[-1]
    return flow_low, _upsample_halo(flow_low, up_mask, n_seq)


def _param_geometry(mesh: Mesh, abstract_params):
    """(spec tree, int dims tree) for a param tree on this mesh. The
    spec tree goes ONLY to shard_map in_specs/out_specs; all body logic
    walks the int tree (-1 = replicated) — PartitionSpec is a tuple
    subclass, so tree-mapping over spec trees would descend into them."""
    specs = jax.tree.map(
        lambda leaf: LAYOUT.param_leaf_spec(mesh, leaf.shape),
        abstract_params)
    dims = jax.tree.map(
        lambda leaf: _spec_dim(LAYOUT.param_leaf_spec(mesh, leaf.shape)),
        abstract_params)
    return specs, dims


def make_halo_train_fn(cfg: RAFTConfig, tc: TrainConfig, mesh: Mesh,
                       abstract_params, remat_mode: str = "none"):
    """Build the shard_map'd sharded-compute gradient fn:

        (params, batch_stats, image1, image2, flow, valid)
            -> (loss, metrics, grads)

    params enter/leave in their fsdp STORAGE layout (param_leaf_spec) —
    no fences; batch leaves enter as (data, seq) slabs
    (batch_spatial_compute); loss/metrics replicate; grads leave in the
    params' layout, ready for a sharded optimizer update OUTSIDE the
    shard_map (train/step.py wires that). batch_stats pass through
    read-only (halo trains with instance norm / frozen BN only, per
    check_halo_support). The gradient rule: value_and_grad runs on the
    LOCAL loss contribution (the global loss is its (data, seq) psum,
    taken outside the grad — seeding the psum'd replicated scalar would
    scale every grad by n_data*n_seq, since psum's transpose is again a
    psum), per-device grads then psum over (data, seq) to assemble the
    global gradient; gathered leaves additionally divide by n_fsdp (the
    all-gather transpose — a psum_scatter over fsdp — sums n_fsdp
    identical replicas)."""
    check_halo_support(cfg, tc, mesh)
    n_data = LAYOUT.data_size(mesh)
    n_seq = LAYOUT.seq_size(mesh)
    n_fsdp = LAYOUT.fsdp_size(mesh)
    param_specs, param_dims = _param_geometry(mesh, abstract_params)
    blocks = param_block_names(abstract_params)
    for required in ("fnet", "cnet", "ScanRAFTStep_0"):
        if required not in blocks:
            raise ValueError(
                f"param tree is missing block {required!r} (have "
                f"{blocks}) — not a v1 RAFT tree")

    def body(params, batch_stats, im1, im2, flow_gt, valid):
        def loss_fn(p):
            preds = _halo_forward(
                cfg, p, batch_stats, im1, im2, n_seq=n_seq,
                n_fsdp=n_fsdp, param_dims=param_dims, iters=tc.iters,
                remat_mode=remat_mode, unroll=cfg.scan_unroll, emit=True)
            return _sequence_loss_sharded(preds, flow_gt, valid,
                                          tc.gamma, n_data, n_seq)

        (local_loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        # loss_fn returns the LOCAL loss contribution; the global loss
        # is its (data, seq) psum — taken HERE, outside value_and_grad,
        # so each device's grads are its own contribution exactly once
        # and the psum below assembles the true global gradient
        loss = jax.lax.psum(local_loss, (DATA_AXIS, SEQ_AXIS))
        grads = jax.tree.map(
            lambda g: jax.lax.psum(g, (DATA_AXIS, SEQ_AXIS)), grads)
        if n_fsdp > 1:
            grads = jax.tree.map(
                lambda g, d: g / n_fsdp if d >= 0 else g,
                grads, param_dims)
        return loss, metrics, grads

    bsc = LAYOUT.batch_spatial_compute()
    repl = LAYOUT.replicated()
    return shard_map(
        body, mesh=mesh,
        in_specs=(param_specs, repl, bsc, bsc, bsc, bsc),
        out_specs=(repl, repl, param_specs),
        check_vma=False)


def make_halo_eval_fn(cfg: RAFTConfig, mesh: Mesh, abstract_params,
                      iters: int = 24):
    """shard_map'd test-mode forward on (data, seq) slabs:

        (params, batch_stats, image1, image2, flow_init)
            -> (flow_low, flow_up)   # both row-sharded like the inputs

    flow_init is always materialized ((B, H/8, W/8, 2); zeros = cold
    start), mirroring the refine step's one-executable contract. The
    support matrix is the train one minus the train-only knobs — reuse
    check_halo_support with a neutral TrainConfig shell for the shared
    checks (variant/corr/precision/shape)."""
    from dexiraft_tpu.config import TrainConfig as _TC
    n_seq = LAYOUT.seq_size(mesh)
    shell = _TC(batch_size=LAYOUT.data_size(mesh),
                image_size=(8 * n_seq * 3, 64), freeze_bn=True)
    check_halo_support(cfg, shell, mesh)
    n_fsdp = LAYOUT.fsdp_size(mesh)
    param_specs, param_dims = _param_geometry(mesh, abstract_params)

    def body(params, batch_stats, im1, im2, flow_init):
        return _halo_forward(
            cfg, params, batch_stats, im1, im2, n_seq=n_seq,
            n_fsdp=n_fsdp, param_dims=param_dims, iters=iters,
            remat_mode="none", unroll=cfg.scan_unroll, emit=False,
            flow_init=flow_init)

    bsc = LAYOUT.batch_spatial_compute()
    repl = LAYOUT.replicated()
    return shard_map(
        body, mesh=mesh,
        in_specs=(param_specs, repl, bsc, bsc, bsc),
        out_specs=(bsc, bsc),
        check_vma=False)
