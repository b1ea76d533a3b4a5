"""Streaming video engine: cross-frame feature reuse over the split model.

The pair engine (serve.engine) treats every request as two fresh frames:
for a chained video stream that recomputes the full encoder stack on
BOTH frames of every pair even though frame t+1's ``fmap1`` is
byte-identical to frame t's ``fmap2`` (models/raft.py). This engine
serves the chained-pairs workload (the reference repo's demo.py loop)
through the split model instead:

  * ``encode_fn`` (train.step.make_encode_step) runs ONCE per NEW frame
    — the previous frame's feature dict comes from the device-resident
    session carry (sessions.DeviceSessionStore), so a warm stream pays
    half the encoder FLOPs of chained pair calls;
  * ``refine_fn`` (train.step.make_refine_step) runs the scanned
    refinement from the two feature dicts with an always-materialized
    flow_init (zeros == cold — one executable per bucket);
  * ``splat_fn`` forward-interpolates flow_low into the next frame's
    seed ON DEVICE — together with the feature carry, the per-frame
    host<->device traffic is exactly one frame up and one flow_up down
    (the payload), ZERO carry bytes.

Chunk semantics (the ``POST /v1/flow/stream`` wire contract): a chunk of
T same-geometry frames under one ``X-Session-Id`` yields

  * T flows when the session has a carry (pairs: (carry, f_0),
    (f_0, f_1), ..., (f_{T-2}, f_{T-1})),
  * T-1 flows cold (consecutive pairs only; a cold T=1 chunk yields no
    flow and just primes the carry).

Frames are processed one at a time, so memory is CONSTANT in T and in
the total stream length; a bucket change mid-stream restarts that one
stream cold (the misaligned-seed rule, same as SessionStore).

Compile discipline: ``warmup()`` drives a 2-frame zero chunk per named
geometry, compiling the encode, refine, and splat signatures before
traffic; after that a strict service is compile-flat (the engine keys
compiled buckets and raises through the shared RecompileWatch on an
unexpected retrace). Like the pair engine, this module imports no jax at
module level — numpy-stub encode/refine/splat fns unit-test the chunk
and carry logic without a model.
"""

from __future__ import annotations

import collections
import contextlib
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from dexiraft_tpu.analysis.locks import OrderedLock
from dexiraft_tpu.data.padder import InputPadder
from dexiraft_tpu.serve.buckets import bucket_shape
from dexiraft_tpu.serve.sessions import DeviceSessionStore

EncodeFn = Callable[[Any], Dict[str, Any]]
RefineFn = Callable[[Dict[str, Any], Dict[str, Any], Any], Tuple[Any, Any]]
SplatFn = Callable[[Any], Any]

_PCTL_WINDOW = 4096  # bounded latency window, same rationale as ServeStats


class StreamOverloaded(RuntimeError):
    """Raised at admission when too many chunks are already queued on
    the engine lock — the streaming twin of scheduler.QueueFull (the
    HTTP layer sheds with a 503 + Retry-After instead of letting every
    handler thread pile up behind one in-flight chunk)."""


def _to_host(x):
    if isinstance(x, np.ndarray):
        return x
    import jax  # deferred: module stays importable without jax

    # explicit device->host fetch (jaxlint JL007): flow_up IS the
    # response payload — the one sanctioned D2H of the streaming path
    return jax.device_get(x)


class ChunkResult(NamedTuple):
    """One processed chunk: host flows (each unpadded (H, W, 2)), and
    what served it — the HTTP layer maps these onto response headers."""

    flows: List[np.ndarray]
    warm: bool                  # the session carry seeded the first pair
    bucket: Tuple[int, int]
    frames_in: int
    # adaptive engines only (None on fixed refine fns): mean refinement
    # iterations actually run across this chunk's pairs — the stream
    # twin of the pair endpoint's X-Iters-Used header
    iters_used: Optional[float] = None


class VideoEngine:
    """Session-carried streaming driver over the split encode/refine
    steps. One chunk at a time (``_lock``): frames of a stream are
    serially dependent anyway, and one in-order device stream keeps the
    compile/strict discipline simple — parallelism at this tier comes
    from replicas (serve/router.py), not intra-process threads."""

    def __init__(
        self,
        encode_fn: EncodeFn,
        refine_fn: RefineFn,
        splat_fn: Optional[SplatFn] = None,
        *,
        sessions: Optional[DeviceSessionStore] = None,
        put: Optional[Callable[[Any], Any]] = None,
        mode: str = "sintel",
        stride: int = 8,
        bucket_multiple: Optional[int] = None,
        max_chunk_frames: int = 64,
        max_pending_chunks: int = 8,
        adaptive: bool = False,
        strict: bool = False,
        watch=None,
    ):
        if max_chunk_frames < 1:
            raise ValueError(
                f"max_chunk_frames must be >= 1, got {max_chunk_frames}")
        if max_pending_chunks < 1:
            raise ValueError(
                f"max_pending_chunks must be >= 1, got {max_pending_chunks}")
        self.encode_fn = encode_fn
        self.refine_fn = refine_fn
        # identity splat = raw flow_low seeds the next pair (numpy-stub
        # tests); serve_cli wires the jitted on-device forward_interpolate
        self.splat_fn = splat_fn if splat_fn is not None else (lambda x: x)
        self.sessions = sessions
        # identity put suits numpy-stub fns; jax callers MUST pass
        # jax.device_put (an implicit H2D inside the jitted encode would
        # trip the strict transfer guard — and hide a real per-frame copy)
        self.put = put if put is not None else (lambda x: x)
        self.mode = mode
        self.stride = stride
        self.bucket_multiple = bucket_multiple
        self.max_chunk_frames = max_chunk_frames
        self.max_pending_chunks = max_pending_chunks
        # adaptive contract: refine_fn returns (flow_low, flow_up,
        # iters_used, final_delta) — the convergence gate exits early
        # per-pair; streaming rides the FULL iteration budget (chunks
        # bypass the scheduler's SLO budgets; the gate is the win here)
        self.adaptive = adaptive
        self.strict = strict
        if watch is None:
            from dexiraft_tpu.analysis.guards import RecompileWatch

            watch = RecompileWatch("video")
        self.watch = watch
        # named + rank-ordered (analysis/locks.py LOCK_ORDER): the chunk
        # lock is the fleet's outermost — a chunk's frame loop nests the
        # stats lock, the device session store, and the shared watch
        self._lock = OrderedLock("serve.video.chunk")
        # chunks admitted but unanswered (waiting on _lock OR mid-loop):
        # the router's zero-drop drain polls /healthz inflight to 0, so
        # streaming work must count there like scheduler.inflight()
        self._inflight_lock = OrderedLock("serve.video.inflight")
        self._inflight = 0
        # counters/latency get their OWN lock: _lock is held for a whole
        # chunk's frame loop, and a /stats scrape must not stall behind
        # one live chunk
        self._stats_lock = OrderedLock("serve.video.stats")
        self._compiled: set = set()
        self._zero_fi: Dict[Tuple[int, ...], Any] = {}
        # counters (reset via reset_stats; surfaced on /stats)
        self.chunks = 0
        self.frames_in = 0
        self.flows_out = 0
        self.warm_chunks = 0
        self.cold_chunks = 0
        self.flow_latency_s: "collections.deque" = collections.deque(
            maxlen=_PCTL_WINDOW)
        # adaptive mode: per-pair iters_used / final-delta samples
        # (empty deques on fixed engines — /stats keys are conditional)
        self.iters_used: "collections.deque" = collections.deque(
            maxlen=_PCTL_WINDOW)
        self.final_delta: "collections.deque" = collections.deque(
            maxlen=_PCTL_WINDOW)

    # ---- input validation ----------------------------------------------

    def validate_frames(self, frames: Any) -> np.ndarray:
        """Reject a malformed chunk at the door (HTTP 400) instead of a
        shape error deep inside the jitted encode step."""
        frames = np.asarray(frames)
        if frames.ndim != 4 or frames.shape[-1] != 3:
            raise ValueError(
                f"frames must be rank-4 (T, H, W, 3) RGB, got shape "
                f"{frames.shape}")
        if frames.shape[0] < 1:
            raise ValueError("frames chunk is empty (T must be >= 1)")
        if frames.shape[0] > self.max_chunk_frames:
            # one chunk holds the engine lock for its whole frame loop:
            # an unbounded T would starve every other stream behind one
            # request — clients split long video into bounded chunks
            # (the carry makes that free)
            raise ValueError(
                f"frames chunk has T={frames.shape[0]} frames; this "
                f"replica caps chunks at {self.max_chunk_frames} — "
                f"split the stream into smaller chunks (the session "
                f"carry keeps them warm across requests)")
        if not (np.issubdtype(frames.dtype, np.floating)
                or np.issubdtype(frames.dtype, np.integer)):
            raise ValueError(
                f"frames dtype must be a real numeric type castable to "
                f"float32, got {frames.dtype}")
        return frames

    # ---- core ----------------------------------------------------------

    def _zero_flow_init(self, h8: int, w8: int):
        """Cached cold seed at the bucket's 1/8 shape — flow_init is
        ALWAYS materialized so cold and warm pairs share one refine
        executable (zeros == no warm start; the engine contract)."""
        key = (h8, w8)
        fi = self._zero_fi.get(key)
        if fi is None:
            fi = self._zero_fi[key] = self.put(
                np.zeros((1, h8, w8, 2), np.float32))
        return fi

    def process_chunk(self, session_id: Optional[str],
                      frames: Any) -> ChunkResult:
        """Run one chunk of same-geometry frames through the stream.

        With a ``session_id`` (and a session store) the carry persists
        across chunks: the previous chunk's last frame pairs with this
        chunk's first frame, and the newest frame's features + splatted
        seed are stored back — all device-resident, no per-frame
        host<->device carry bytes. ``session_id=None`` processes the
        chunk standalone (cold, nothing stored).
        """
        # empty/blank id == sessionless, matching the pair endpoint's
        # truthiness check — "" as a real key would silently share one
        # carry across every client that sends a blank header
        session_id = session_id or None
        frames = self.validate_frames(frames)
        t_frames, h, w = frames.shape[0], frames.shape[1], frames.shape[2]
        bucket = bucket_shape(h, w, self.stride, self.bucket_multiple)
        padder = InputPadder((h, w, 3), mode=self.mode, stride=self.stride,
                             target=bucket)
        h8, w8 = bucket[0] // self.stride, bucket[1] // self.stride

        with self._inflight_lock:
            if self._inflight >= self.max_pending_chunks:
                # bounded admission (scheduler.QueueFull discipline):
                # chunks serialize on the engine lock, so past the cap
                # each extra request pins a handler thread for minutes —
                # shed loudly instead
                raise StreamOverloaded(
                    f"{self._inflight} chunk(s) already queued "
                    f"(max_pending_chunks={self.max_pending_chunks}); "
                    f"retry with backoff")
            self._inflight += 1
        try:
            return self._process_locked(session_id, frames, t_frames,
                                        bucket, padder, h8, w8)
        finally:
            with self._inflight_lock:
                self._inflight -= 1

    def _process_locked(self, session_id, frames, t_frames, bucket, padder,
                        h8, w8) -> ChunkResult:
        with self._lock:
            fresh = bucket not in self._compiled
            feats_prev = flow_init = None
            warm = False
            if session_id is not None and self.sessions is not None:
                carry = self.sessions.get(session_id, bucket)
                if carry is not None:
                    feats_prev, flow_init = carry
                    warm = True

            flows: List[np.ndarray] = []
            chunk_iters: List[int] = []
            # a fresh bucket's frame loop compiles encode/refine/splat:
            # run it inside a sanctioned window so the pair dispatcher's
            # concurrent strict check (shared watch, process-global
            # compile counter, separate thread) never reads the expected
            # compiles as drift
            win = (self.watch.sanctioned() if fresh
                   else contextlib.nullcontext())
            with win:
                for i in range(t_frames):
                    t0 = time.perf_counter()
                    # cast, placed and edge-padded in one pass (the pair
                    # engine's way); a buffer of the frame's own, since
                    # the put may still read it after it returns
                    padded = np.empty((1,) + bucket + (3,), np.float32)
                    padder.pad_into(padded[0], frames[i])
                    feats = self.encode_fn(self.put(padded))
                    if feats_prev is not None:
                        if flow_init is None:
                            flow_init = self._zero_flow_init(h8, w8)
                        if self.adaptive:
                            (flow_low, flow_up, pair_iters,
                             pair_delta) = self.refine_fn(
                                feats_prev, feats, flow_init)
                            # one fetch per pair, same sanctioned D2H as
                            # flow_up (the (1,) scalars piggyback on the
                            # payload fetch, not a new transfer class)
                            iu = int(_to_host(pair_iters)[0])
                            fd = float(_to_host(pair_delta)[0])
                        else:
                            flow_low, flow_up = self.refine_fn(
                                feats_prev, feats, flow_init)
                        flow_init = self.splat_fn(flow_low)
                        flows.append(padder.unpad(_to_host(flow_up)[0]))
                        with self._stats_lock:
                            self.flow_latency_s.append(
                                time.perf_counter() - t0)
                            if self.adaptive:
                                chunk_iters.append(iu)
                                self.iters_used.append(iu)
                                self.final_delta.append(fd)
                    feats_prev = feats

            if session_id is not None and self.sessions is not None:
                self.sessions.put(
                    session_id, bucket, feats_prev,
                    flow_init if flow_init is not None
                    else self._zero_flow_init(h8, w8))

            with self._stats_lock:
                self.chunks += 1
                self.frames_in += t_frames
                self.flows_out += len(flows)
                if warm:
                    self.warm_chunks += 1
                else:
                    self.cold_chunks += 1
            if fresh:
                # expected compiles (encode + refine + splat for a new
                # bucket): move the shared drift baseline past them,
                # exactly like the pair engine's first bucket dispatch
                with self._stats_lock:
                    self._compiled.add(bucket)
                self.watch.mark_warm()
            elif self.strict:
                self.watch.check()
            else:
                self.watch.warn_if_drifted()
        mean_iters = (sum(chunk_iters) / len(chunk_iters)
                      if chunk_iters else None)
        return ChunkResult(flows, warm, bucket, t_frames, mean_iters)

    # ---- lifecycle / observability -------------------------------------

    def inflight(self) -> int:
        """Chunks admitted but unanswered (queued on the engine lock or
        mid-frame-loop) — counted into /healthz ``inflight`` so the
        router's zero-drop drain waits out live streaming work exactly
        like scheduler-admitted pairs."""
        with self._inflight_lock:
            return self._inflight

    def warmup(self, geometries) -> None:
        """Pre-compile the streaming signatures (encode, refine, splat)
        for each "HxW" geometry with a 2-frame zero chunk — after this a
        --strict service is compile-flat from the first streamed frame.
        Nothing is stored (no session id) and the counters are reset:
        warmup is not traffic."""
        for geom in geometries:
            h, w = (int(v) for v in geom.split("x"))
            self.process_chunk(None, np.zeros((2, h, w, 3), np.float32))
        self.reset_stats()

    def reset_stats(self) -> None:
        """Zero the traffic counters; compiled buckets, the warm
        baseline, and live session carries survive (state, not
        statistics) — the /stats?reset=1 window handoff."""
        with self._stats_lock:
            self.chunks = self.frames_in = self.flows_out = 0
            self.warm_chunks = self.cold_chunks = 0
            self.flow_latency_s.clear()
            self.iters_used.clear()
            self.final_delta.clear()
        if self.sessions is not None:
            self.sessions.reset_counters()

    def _pctl_ms(self, p: float) -> float:
        if not self.flow_latency_s:
            return 0.0
        return round(float(np.percentile(self.flow_latency_s, p)) * 1e3, 2)

    def stats_record(self) -> dict:
        """Self-describing blob for /stats: chunk/flow counters,
        per-flow latency percentiles, and the device-carry session store
        (byte budget, evictions). Takes only the stats lock — a scrape
        never stalls behind a live chunk's frame loop."""
        with self._stats_lock:
            rec = {
                "chunks": self.chunks,
                "frames_in": self.frames_in,
                "flows_out": self.flows_out,
                "warm_chunks": self.warm_chunks,
                "cold_chunks": self.cold_chunks,
                "flow_p50_ms": self._pctl_ms(50),
                "flow_p99_ms": self._pctl_ms(99),
                "compiled_buckets": sorted(
                    f"{h}x{w}" for h, w in self._compiled),
            }
            if self.adaptive:
                # conditional like the engine's block: fixed-path /stats
                # schema pins stay byte-identical
                iu = list(self.iters_used)
                rec.update(
                    adaptive=True,
                    iters_used_mean=(round(sum(iu) / len(iu), 2)
                                     if iu else 0.0),
                    iters_used_p99=(round(float(
                        np.percentile(iu, 99)), 2) if iu else 0.0),
                    final_delta_p50=(round(float(np.percentile(
                        list(self.final_delta), 50)), 5)
                        if self.final_delta else 0.0),
                )
        rec["sessions"] = (self.sessions.stats_record()
                          if self.sessions is not None else None)
        return rec
