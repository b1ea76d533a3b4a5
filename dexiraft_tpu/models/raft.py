"""RAFT — the iterative optical-flow estimator, TPU-first.

Re-design of the reference model family (core/raft.py and its raft_1..raft_4
variants, SURVEY.md §2.5) as one Flax module driven by RAFTConfig:

  v1 'raft'      image stream only (core/raft_1.py)
  v2 'early'     6-ch early fusion, edges from data (core/raft_2.py)
  v3 'separate'  dual stream, edges from data, decoupled updates +
                 RefineFlow fusion (core/raft_3.py, output-width bug fixed)
  v4 'early'+embed_dexined   10-ch early fusion, embedded DexiNed (core/raft_4.py)
  v5 'dual'+embed_dexined    dual stream, frozen DexiNed, shared update block,
                 coupled update coords1 += Δflow + Δeflow (core/raft.py:183)

TPU-first design choices (vs. the reference's Python loop over CUDA calls):
  * the refinement loop is nn.scan (lax.scan) with weights broadcast — all
    iterations compile into ONE on-device graph; `iters` is static.
  * NHWC layouts; under mixed_precision encoders/update run in bf16 while
    the correlation volume stays fp32 (mirrors core/raft.py:134-148).
  * the correlation pyramid is a pytree the scan reads as a broadcast
    constant; where a gradient is taken through an all-pairs pyramid the
    loop hands back its iterations' window cotangents and the levels'
    gradient is placed once, after the backward loop (ops/corr.py
    place_once): the scan carries no level-sized sum.
  * coords are stop_gradient'ed at each iteration start, matching the
    reference's per-iteration detach (core/raft.py:170-171).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from dexiraft_tpu.config import RAFTConfig
from dexiraft_tpu.models.dexined import DexiNed, stack_edge_maps
from dexiraft_tpu.models.extractor import BasicEncoder, SmallEncoder
from dexiraft_tpu.models.update import BasicUpdateBlock, RefineFlow, SmallUpdateBlock
from dexiraft_tpu.ops.corr import (CorrPyramid, build_corr_pyramid,
                                   lookup_centres, place_once)
from dexiraft_tpu.ops.local_corr import build_local_corr
from dexiraft_tpu.ops.grid import as_planes, coords_grid, upflow8
from dexiraft_tpu.ops.upsample import upsample_flow_convex


def _normalize(img: jax.Array) -> jax.Array:
    """[0, 255] -> [-1, 1] (core/raft.py:104-105)."""
    return 2.0 * (img / 255.0) - 1.0


class RAFTStep(nn.Module):
    """One refinement iteration; scanned with params broadcast.

    Both streams of the dual/separate variants ride ONE batch: the edge
    stream is concatenated on the batch axis (the reference's two
    update-block calls share a single update_block, core/raft.py:179-180,
    so one call on batch 2B is the same math in half the dispatches — and
    every correlation-lookup matmul runs at double batch instead of twice).

    ``emit`` selects the scan output: per-iteration upsampled flows for
    training (sequence_loss consumes all of them, train.py:48-73), nothing
    in test mode — the final flow is upsampled ONCE after the scan from
    the carried mask (test_mode returns only the last prediction,
    core/raft.py:194-197).

    ``probe``, the scan's per-iteration input, is None or this iteration's
    zeros for the lookup's windows (ops/corr.py place_once); with it the
    step also emits the coordinates its lookup read.
    """

    cfg: RAFTConfig
    dtype: Any = jnp.float32
    emit: bool = True

    @nn.compact
    def __call__(self, carry: Dict[str, Any], probe, consts: Dict[str, Any]):
        cfg = self.cfg
        if cfg.small:
            update_block = SmallUpdateBlock(hidden_dim=cfg.hidden_dim, dtype=self.dtype)
        else:
            update_block = BasicUpdateBlock(hidden_dim=cfg.hidden_dim, dtype=self.dtype)

        pyr = consts["pyr"]
        dual = cfg.has_edge_stream
        b = pyr.batch // 2 if dual else pyr.batch
        coords0 = coords_grid(b, pyr.ht, pyr.wd)

        # (2B or B, h, w, 2)
        coords1 = looked_up = jax.lax.stop_gradient(carry["coords1"])
        flow = coords1 - coords0[:1]  # the grid broadcasts over both streams
        if cfg.fused_update:
            # fused step (config.fused_update): the lookup and the motion
            # encoder's 1x1 corr conv run in ONE Pallas kernel inside the
            # update block — the (B, H, W, L*win^2) corr features never
            # materialize in HBM, which also makes remat_lookup moot here
            # (the fused VJP recomputes through the XLA reference anyway)
            net, up_mask, delta = update_block(
                carry["net"], consts["inp"], None, flow,
                pyr=pyr, coords=coords1)
        else:
            # a probe goes with an all-pairs pyramid only (place_once)
            at = (coords1,) if probe is None else (coords1, probe)
            if cfg.remat_lookup and not cfg.remat:
                # recompute the lookup in backward instead of storing its
                # intermediates (the per-iteration hat matrices dominate
                # training memory — config.py remat_lookup). The pyramid
                # is passed as an argument so its gradients flow
                # normally; prevent_cse=False matches the full-remat
                # scan convention (the scan already rules out the CSE
                # hazard)
                corr = jax.checkpoint(lambda p, *at: p(*at),
                                      prevent_cse=False)(pyr, *at)
            else:
                corr = pyr(*at)
            net, up_mask, delta = update_block(carry["net"], consts["inp"],
                                               corr, flow)
        delta = delta.astype(jnp.float32)

        if dual:
            delta_flow, delta_eflow = delta[:b], delta[b:]
            ic, ec = coords1[:b], coords1[b:]
            if cfg.variant == "dual":
                # coupled update: edge deltas injected into the image flow
                # (core/raft.py:183-184)
                ic = ic + delta_flow + delta_eflow
                ec = ec + delta_eflow
            else:  # 'separate' (v3): decoupled (core/raft_3.py:160-161)
                ic = ic + delta_flow
                ec = ec + delta_eflow
            coords1 = jnp.concatenate([ic, ec], 0)
        else:
            coords1 = coords1 + delta

        # the carry keeps the queries on the lanes: the update above and
        # every reader of coords1 (the lookup, the flow) run on planes
        carry = {**carry, "coords1": as_planes(coords1), "net": net}

        if not self.emit:
            # test mode: keep only what the post-scan upsample needs
            carry["up_mask"] = up_mask
            return carry, None

        prediction = self._predict(cfg, coords1, coords0, up_mask, b)
        if probe is None:
            return carry, prediction
        return carry, (prediction, lookup_centres(looked_up))

    def _predict(self, cfg, coords1, coords0, up_mask, b):
        if cfg.has_edge_stream:
            flow_up = _upsample(coords1[:b] - coords0,
                                None if up_mask is None else up_mask[:b])
            if cfg.variant == "separate":
                eflow_up = _upsample(coords1[b:] - coords0,
                                     None if up_mask is None else up_mask[b:])
                return RefineFlow(dtype=self.dtype)(
                    flow_up, eflow_up).astype(jnp.float32)
            return flow_up
        return _upsample(coords1 - coords0, up_mask)


def _upsample(flow: jax.Array, mask: Optional[jax.Array]) -> jax.Array:
    if mask is None:  # small model has no mask head (core/raft.py:187-190)
        return upflow8(flow)
    return upsample_flow_convex(flow.astype(jnp.float32), mask)


class RAFT(nn.Module):
    """Full model: encoders + correlation pyramids + scanned refinement.

    Three entry modes share ONE param tree (checkpoints interchange):

      mode="pair"    (default) the monolithic two-frame forward — the
                     reference behavior, byte-identical to the
                     pre-split implementation (both frames ride one
                     batched encoder call).
      mode="encode"  per-FRAME encoder stage: fnet + cnet (and the
                     edge-stream efnet/ecnet twins) on a single frame,
                     returning the feature dict a later refinement can
                     consume. The streaming engine runs this ONCE per
                     new frame and pulls the previous frame's features
                     from the session carry — half the encoder FLOPs of
                     chained pair calls.
      mode="step"    refinement from two feature dicts (features1 is
                     the EARLIER frame — its ctx seeds the GRU) —
                     pyramid build + scanned update loop, same returns
                     as mode="pair".

    In test mode the split composition equals the monolithic call to
    float tolerance: the only difference is batched-vs-per-frame
    encoder calls, and every encoder norm is per-sample there (instance
    norm; BatchNorm on running stats). Parity is pinned in
    tests/test_zzvideo.py.
    """

    cfg: RAFTConfig = RAFTConfig()

    # ---- shared construction helpers (called inside the compact ctx) ----

    def _encoders(self, dtype):
        """The four encoder modules with their historical pinned names —
        both encode paths MUST construct them identically or the param
        tree forks between fused and split serving."""
        cfg = self.cfg
        hdim, cdim = cfg.hidden_dim, cfg.context_dim
        Encoder = SmallEncoder if cfg.small else BasicEncoder
        enc_norm = "instance"
        ctx_norm = "none" if cfg.small else "batch"
        fnet = Encoder(cfg.fnet_dim, enc_norm, cfg.dropout, dtype,
                       name="fnet")
        cnet = Encoder(hdim + cdim, ctx_norm, cfg.dropout, dtype,
                       name="cnet")
        efnet = ecnet = None
        if cfg.has_edge_stream:
            if cfg.variant == "dual":
                # v5: dedicated 7-channel edge encoders (core/raft.py:61-71)
                efnet = Encoder(cfg.fnet_dim, enc_norm, cfg.dropout, dtype,
                                name="efnet")
                ecnet = Encoder(hdim + cdim, ctx_norm, cfg.dropout, dtype,
                                name="ecnet")
            else:
                # v3: image and edge streams share fnet/cnet
                # (core/raft_3.py:110-127)
                efnet, ecnet = fnet, cnet
        return fnet, cnet, efnet, ecnet

    def _dexined(self, dtype):
        # name pinned to the historical auto-name so the pair and
        # per-frame paths bind the same frozen extractor params
        return DexiNed(dtype=dtype, upconv=self.cfg.dexined_upconv,
                       name="DexiNed_0")

    def _encode_pair(self, image1, image2, edges1, edges2, train, bn_train,
                     dtype):
        """The monolithic encoder stage: both frames through ONE batched
        call per encoder (better MXU utilization than two passes).
        Returns the two per-frame feature dicts _refine consumes; only
        frame 1 carries ctx (the GRU seeds from the earlier frame)."""
        cfg = self.cfg
        image1 = _normalize(image1.astype(jnp.float32))
        image2 = _normalize(image2.astype(jnp.float32))

        em1 = em2 = None
        if cfg.embed_dexined:
            # frozen edge extraction: raw logits, gradients stopped — the
            # no_grad contract of core/raft.py:111-123; under
            # mixed_precision the frozen extractor runs in bf16 like the
            # encoders — the reference keeps it fp32 only because it sits
            # outside the autocast region (docs/parity.md)
            both = jnp.concatenate([image1, image2], axis=0)
            maps = stack_edge_maps(self._dexined(dtype)(both, train=False))
            maps = jax.lax.stop_gradient(maps.astype(jnp.float32))
            em1, em2 = jnp.split(maps, 2, axis=0)
        elif cfg.variant in ("early", "separate"):
            if edges1 is None or edges2 is None:
                raise ValueError(
                    f"variant {cfg.variant!r} without embed_dexined requires "
                    "data-supplied edges1/edges2"
                )
            em1 = _normalize(edges1.astype(jnp.float32))
            em2 = _normalize(edges2.astype(jnp.float32))

        if cfg.variant == "early":
            image1 = jnp.concatenate([image1, em1], axis=-1)
            image2 = jnp.concatenate([image2, em2], axis=-1)
            em1 = em2 = None

        fnet, cnet, efnet, ecnet = self._encoders(dtype)
        fmap1, fmap2 = fnet((image1.astype(dtype), image2.astype(dtype)),
                            train=train, bn_train=bn_train)
        f1: Dict[str, Any] = {"fmap": fmap1.astype(jnp.float32),
                              "ctx": cnet(image1.astype(dtype), train=train,
                                          bn_train=bn_train)}
        f2: Dict[str, Any] = {"fmap": fmap2.astype(jnp.float32)}
        if cfg.has_edge_stream:
            fem1, fem2 = efnet((em1.astype(dtype), em2.astype(dtype)),
                               train=train, bn_train=bn_train)
            f1["efmap"] = fem1.astype(jnp.float32)
            f2["efmap"] = fem2.astype(jnp.float32)
            f1["ectx"] = ecnet(em1.astype(dtype), train=train,
                               bn_train=bn_train)
        return f1, f2

    def _encode_frame(self, image, edges, train, bn_train, dtype):
        """Per-frame encoder stage (mode="encode"): everything a frame
        contributes to ANY pair it joins — fmap (as frame 1 or 2) AND
        ctx (consumed only when it is the earlier frame). Computing ctx
        unconditionally is what makes the streaming carry work: frame t
        was frame 2 of pair (t-1, t) and becomes frame 1 of (t, t+1)
        without re-encoding."""
        cfg = self.cfg
        image = _normalize(image.astype(jnp.float32))
        em = None
        if cfg.embed_dexined:
            maps = stack_edge_maps(self._dexined(dtype)(image, train=False))
            em = jax.lax.stop_gradient(maps.astype(jnp.float32))
        elif cfg.variant in ("early", "separate"):
            if edges is None:
                raise ValueError(
                    f"variant {cfg.variant!r} without embed_dexined requires "
                    "a data-supplied edge frame in mode='encode'")
            em = _normalize(edges.astype(jnp.float32))
        if cfg.variant == "early":
            image = jnp.concatenate([image, em], axis=-1)
            em = None

        fnet, cnet, efnet, ecnet = self._encoders(dtype)
        out: Dict[str, Any] = {
            "fmap": fnet(image.astype(dtype), train=train,
                         bn_train=bn_train).astype(jnp.float32),
            "ctx": cnet(image.astype(dtype), train=train,
                        bn_train=bn_train),
        }
        if cfg.has_edge_stream:
            out["efmap"] = efnet(em.astype(dtype), train=train,
                                 bn_train=bn_train).astype(jnp.float32)
            out["ectx"] = ecnet(em.astype(dtype), train=train,
                                bn_train=bn_train)
        return out

    @nn.compact
    def __call__(
        self,
        image1: Optional[jax.Array],
        image2: Optional[jax.Array] = None,
        edges1: Optional[jax.Array] = None,
        edges2: Optional[jax.Array] = None,
        iters: int = 12,
        flow_init: Optional[jax.Array] = None,
        train: bool = False,
        freeze_bn: bool = False,
        test_mode: bool = False,
        mode: str = "pair",
        features1: Optional[Dict[str, Any]] = None,
        features2: Optional[Dict[str, Any]] = None,
        adaptive: bool = False,
        iter_budget: Optional[jax.Array] = None,
    ):
        """Estimate flow between two (B, H, W, 3) [0,255] frames.

        edges1/edges2: (B, H, W, 3) edge images for the v2/v3 variants
        (data-supplied edge contract); ignored when embed_dexined=True.

        mode="encode" consumes only (image1 [, edges1]) and returns the
        per-frame feature dict; mode="step" consumes features1/features2
        (dicts from mode="encode" or the streaming carry) and ignores
        the images. See the class docstring.

        Returns stacked per-iteration upsampled flows (iters, B, H, W, 2),
        or (flow_low, flow_up) in test_mode (core/raft.py:194-197).

        adaptive=True (inference only): the fixed scan is replaced by a
        lax.while_loop with a per-item convergence gate — an item
        freezes (masked no-op update, carry preserved) once the mean
        per-pixel L2 norm of its 1/8-res flow delta drops below
        cfg.converge_tol, and the loop exits when every item is done or
        ``iter_budget`` (a TRACED int32 scalar, clamped to [0, iters] —
        one compiled executable serves every budget) expires. Returns
        (flow_low, flow_up, iters_used[B], final_delta[B]). The train
        path is untouched; variant='separate' is refused (its RefineFlow
        head must stay inside the emitting scan for parameter-path
        stability, which the non-emitting while_loop cannot host).
        """
        cfg = self.cfg
        if adaptive:
            if not test_mode:
                raise ValueError(
                    "adaptive=True is an inference path: it needs "
                    "test_mode=True (the sequence loss consumes every "
                    "iteration's prediction — early exit has no training "
                    "meaning, and the scan+remat train path stays as-is)")
            if cfg.variant == "separate":
                raise ValueError(
                    "adaptive=True does not support variant='separate': "
                    "its RefineFlow fusion head lives INSIDE the scanned "
                    "step (emit=True even in test mode, models/raft.py) "
                    "and the adaptive while_loop drives the non-emitting "
                    "step; use v1/v2/v4/v5 or the fixed-iters path")
        elif iter_budget is not None:
            raise ValueError(
                "iter_budget only has meaning with adaptive=True (the "
                "fixed path compiles its iteration count statically)")
        # corr_impl/corr_dtype/fused_update combinations are refused at
        # CONFIG time (RAFTConfig.__post_init__) — by the time a config
        # reaches apply() they are known-valid. Only the runtime-
        # dependent refusals live here.
        if train and cfg.corr_dtype == "int8":
            raise ValueError(
                "corr_dtype='int8' is an inference format: the round() in "
                "quantization zeroes the fmap gradients, which would train "
                "the feature encoder silently dead. Use 'bf16' (or 'fp32') "
                "for training and 'int8' for eval/serve")
        if cfg.variant == "dual" and not cfg.embed_dexined:
            raise ValueError(
                "variant='dual' requires embed_dexined=True (the v5 edge "
                "stream consumes DexiNed's 7 logit maps; use raft_v5())"
            )
        dtype = jnp.bfloat16 if cfg.mixed_precision else jnp.float32
        # freeze_bn: post-chairs stages run BN on running stats (train.py:149-150)
        bn_train = train and not freeze_bn

        if mode == "encode":
            return self._encode_frame(image1, edges1, train, bn_train, dtype)
        if mode == "step":
            if features1 is None or features2 is None:
                raise ValueError(
                    "mode='step' needs features1 AND features2 (per-frame "
                    "dicts from mode='encode'; features1 is the EARLIER "
                    "frame)")
        elif mode == "pair":
            if image1 is None or image2 is None:
                # images became Optional for the split modes; fail the
                # monolithic path loudly instead of a NoneType
                # AttributeError deep inside _normalize
                raise ValueError(
                    "mode='pair' needs image1 AND image2 (two (B, H, W, "
                    "3) frames; mode='encode' takes one, mode='step' "
                    "takes feature dicts)")
            features1, features2 = self._encode_pair(
                image1, image2, edges1, edges2, train, bn_train, dtype)
        else:
            raise ValueError(f"unknown mode {mode!r}; expected "
                             "'pair' | 'encode' | 'step'")

        hdim = cfg.hidden_dim

        def build_pyr(f1, f2):
            # plugin seam (BASELINE.json): materialized MXU volume vs
            # on-demand local correlation (the alt_cuda_corr analog);
            # corr_dtype sets the pyramid's STORAGE precision on both
            # (ops/quant.py — dequantized inside the lookup)
            if cfg.corr_impl == "allpairs":
                return build_corr_pyramid(f1, f2, cfg.corr_levels, cfg.radius,
                                          dtype=cfg.corr_dtype)
            return build_local_corr(f1, f2, cfg.corr_levels, cfg.radius,
                                    row_chunk=cfg.corr_row_chunk,
                                    dtype=cfg.corr_dtype,
                                    kernel=("xla" if cfg.corr_impl == "local"
                                            else cfg.corr_impl))

        fmap1, fmap2 = features1["fmap"], features2["fmap"]
        ctx = features1["ctx"]
        net = jnp.tanh(ctx[..., :hdim])
        inp = nn.relu(ctx[..., hdim:])

        b, h8, w8 = fmap1.shape[0], fmap1.shape[1], fmap1.shape[2]
        coords0 = coords_grid(b, h8, w8)
        coords1 = coords_grid(b, h8, w8)
        if flow_init is not None:
            coords1 = coords1 + flow_init

        if cfg.has_edge_stream:
            fem1, fem2 = features1["efmap"], features2["efmap"]
            ectx = features1["ectx"]
            # both streams share one batch axis: one pyramid build, one
            # lookup and one update-block call per iteration (RAFTStep)
            pyr = build_pyr(jnp.concatenate([fmap1, fem1], 0),
                            jnp.concatenate([fmap2, fem2], 0))
            coords1 = jnp.concatenate([coords1, coords_grid(b, h8, w8)], 0)
            net = jnp.concatenate([net, jnp.tanh(ectx[..., :hdim])], 0)
            inp = jnp.concatenate([inp, nn.relu(ectx[..., hdim:])], 0)
        else:
            pyr = build_pyr(fmap1, fmap2)

        carry: Dict[str, Any] = {"coords1": coords1, "net": net}
        consts = {"pyr": pyr, "inp": inp}

        # per-iteration upsampled flows are only consumed by the sequence
        # loss; in test mode (except v3, whose RefineFlow head must stay
        # inside the scanned module for parameter-path stability) the scan
        # emits nothing and the final flow is upsampled once afterwards
        emit = (not test_mode) or cfg.variant == "separate"
        if not emit:
            if cfg.small:
                carry["up_mask"] = None
            else:
                nb = 2 * b if cfg.has_edge_stream else b
                carry["up_mask"] = jnp.zeros((nb, h8, w8, 64 * 9), dtype)

        if adaptive:
            # adaptive implies test_mode and not 'separate', so emit is
            # False here and the carry already holds the up_mask slot
            return self._adaptive_refine(carry, consts, coords0, b, iters,
                                         iter_budget, dtype)

        step_cls = RAFTStep
        if cfg.remat:
            # recompute each iteration's activations in backward instead
            # of storing iters x (GRU state + corr features) in HBM;
            # remat_policy="dots_saveable" keeps matmul/conv outputs
            # saved (cheap elementwise chains recompute) — the
            # intermediate point on the HBM/FLOPs axis (config.py)
            kw = {}
            if cfg.remat_policy == "dots_saveable":
                kw["policy"] = jax.checkpoint_policies.dots_saveable
            step_cls = nn.remat(RAFTStep, prevent_cse=False, **kw)
        scan = nn.scan(
            step_cls,
            variable_broadcast="params",
            split_rngs={"params": False},
            in_axes=(0, nn.broadcast),
            length=iters,
            unroll=max(1, min(cfg.scan_unroll, iters)),
        )
        # pin the module name so parameter paths (and thus checkpoints and
        # interop name maps) are identical with and without remat
        name = "ScanRAFTStep_0"
        if (test_mode or self.is_initializing()
                or not isinstance(pyr, CorrPyramid)):
            carry, predictions = scan(cfg=cfg, dtype=dtype, emit=emit,
                                      name=name)(carry, None, consts)
        else:
            # the train path over an all-pairs pyramid: the same scan as a
            # function of its parameters, so that a gradient taken through
            # it places the levels' gradient once (place_once)
            def refine(pyr, probe, params, carry, inp):
                _, ys = scan(cfg=cfg, dtype=dtype, emit=emit, parent=None).apply(
                    {"params": params}, carry, probe, {"pyr": pyr, "inp": inp})
                return ys if probe is not None else (ys, None)

            return place_once(refine, pyr, self.variables["params"][name],
                              carry, inp, iters=iters)

        if test_mode:
            flow_low = carry["coords1"][:b] - coords0
            if emit:
                return flow_low, predictions[-1]
            flow_up = _upsample(
                flow_low,
                None if carry["up_mask"] is None else carry["up_mask"][:b])
            return flow_low, flow_up
        return predictions

    def _adaptive_refine(self, carry, consts, coords0, b, iters,
                         iter_budget, dtype):
        """Convergence-gated refinement (``adaptive=True``): an
        nn.while_loop over the SAME step module the scan path drives —
        the module name is pinned to "ScanRAFTStep_0" with params
        broadcast, so the parameter tree (and thus every checkpoint) is
        identical between the two drivers.

        Per-item gate: after each update, the item's flow delta at 1/8
        res (the image stream's coords1 movement) reduces to a mean
        per-pixel L2 norm; once it drops below cfg.converge_tol the item
        is DONE — subsequent iterations freeze its carry rows via a
        masked select (dual variants freeze the edge-stream row b+i
        together with its image row i), so a converged item's result is
        bit-identical to having stopped. The loop exits when every item
        is done or the traced ``iter_budget`` expires; with tol=0 the
        gate never fires (the norm is >= 0) and a full budget replays
        the scan path's update sequence exactly.

        Returns (flow_low, flow_up, iters_used[B], final_delta[B]):
        iters_used counts the updates each item actually applied;
        final_delta is the item's last pre-freeze delta norm (0.0 if
        the budget was 0 and no update ever ran).
        """
        cfg = self.cfg
        # no remat wrapper: this path never differentiates, and the
        # plain module binds the same "ScanRAFTStep_0" parameter paths
        step = RAFTStep(cfg=cfg, dtype=dtype, emit=False,
                        name="ScanRAFTStep_0")

        def finish(c, iters_used, final_delta):
            flow_low = c["coords1"][:b] - coords0
            flow_up = _upsample(
                flow_low,
                None if c["up_mask"] is None else c["up_mask"][:b])
            return flow_low, flow_up, iters_used, final_delta

        if self.is_initializing():
            # nn.while_loop cannot create variables inside its body; one
            # direct step call initializes the (broadcast) params — the
            # same tree the while_loop then closes over read-only
            c, _ = step(carry, None, consts)
            return finish(c, jnp.zeros((b,), jnp.int32),
                          jnp.zeros((b,), jnp.float32))

        budget = iters if iter_budget is None else iter_budget
        budget = jnp.clip(jnp.asarray(budget, jnp.int32), 0, iters)
        tol = jnp.float32(cfg.converge_tol)

        state = {
            "carry": carry,
            "done": jnp.zeros((b,), bool),
            "iters_used": jnp.zeros((b,), jnp.int32),
            "final_delta": jnp.zeros((b,), jnp.float32),
            "it": jnp.zeros((), jnp.int32),
        }

        def cond_fn(_mdl, s):
            return jnp.logical_and(s["it"] < budget,
                                   jnp.any(jnp.logical_not(s["done"])))

        def body_fn(mdl, s):
            old = s["carry"]
            new, _ = mdl(old, None, consts)
            # the convergence signal: how far this update moved the
            # IMAGE stream's 1/8-res flow, as a mean per-pixel L2 norm
            d = new["coords1"][:b] - old["coords1"][:b]
            dn = jnp.sqrt(jnp.sum(jnp.square(d), -1)).mean((1, 2))
            active = jnp.logical_not(s["done"])

            def freeze(o, n):
                m = active
                if n.shape[0] != b:
                    # dual variants: the edge-stream row rides (and
                    # freezes with) its image row
                    m = jnp.concatenate([active, active], 0)
                return jnp.where(m.reshape((-1,) + (1,) * (n.ndim - 1)),
                                 n, o)

            return {
                "carry": jax.tree.map(freeze, old, new),
                "done": jnp.logical_or(s["done"], dn < tol),
                "iters_used": s["iters_used"] + active.astype(jnp.int32),
                "final_delta": jnp.where(active, dn, s["final_delta"]),
                "it": s["it"] + 1,
            }

        state = nn.while_loop(cond_fn, body_fn, step, state)
        return finish(state["carry"], state["iters_used"],
                      state["final_delta"])
