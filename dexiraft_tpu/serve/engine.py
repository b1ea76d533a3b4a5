"""Throughput-mode inference engine over the jitted eval step.

The per-image eval loop (eval/validate.py pre-engine) ran one padded
frame pair at a time, synchronously: pad -> dispatch -> np.asarray (host
blocks until the chip finishes) -> unpad, with every distinct geometry
paying a fresh XLA compile. This engine gives the forward's consumers
the same treatment PR 2 gave training:

  * shape buckets (serve.buckets): geometries quantize to a bounded set
    of stride-aligned bucket shapes; one executable per bucket, cached
    in-process and in the PR 2 persistent XLA cache.
  * micro-batching: same-bucket frame pairs group into batches of
    `batch_size`, amortizing the DexiNed prelude / pyramid build exactly
    like training batches do. The tail batch of a bucket is padded back
    up to `batch_size` by replicating its last item — shape stability
    keeps the one-executable-per-bucket contract — and the filler
    results are masked out, so metrics cover exactly the dataset. A
    batch is assembled in ONE pass over its bytes: each frame is cast
    to float32, placed and edge-padded straight into its row of a host
    buffer the engine owns and reuses (InputPadder.pad_into;
    _batch_buffers has the ownership rule).
  * async in-flight dispatch: eval_fn only ENQUEUES device work (jax
    async dispatch) and the host->device put is async too, so holding
    `inflight` dispatched tickets before fetching overlaps device
    compute with host assemble/encode work. ServeStats (profiling.py)
    accounts the residual honestly: fetch_s is the compute the window
    failed to hide.
  * data-parallel serving: with a mesh, each batch device_puts sharded
    over the 'data' axis and the pinned eval step (train.step
    make_eval_step(mesh=...)) runs it SPMD across chips.

eval_fn contract: eval_fn(image1, image2, flow_init) -> (flow_low,
flow_up), POSITIONAL (the mesh path pins in_shardings, and jit rejects
kwargs when shardings are pinned), batched NHWC in [0, 255], flow_init
either None or a (B, H/8, W/8, 2) array. A flow_init row of ZEROS is
numerically identical to no warm start (RAFT adds it to coords0), which
is what makes per-item carry work: one batch can mix warm-started items
and cold items without a second executable.

ADAPTIVE engines (ServeConfig.adaptive): the eval_fn grows a trailing
``iter_budget`` positional and returns (flow_low, flow_up,
iters_used[B], final_delta[B]) — the convergence-gated while_loop path
(train.step make_eval_step(adaptive=True)). The budget is a TRACED
int32 scalar, so every budget value rides the bucket's ONE compiled
executable; the engine normalizes it to np.int32 in exactly one place
(_dispatch) so a warmup dispatch and a scheduler-budgeted dispatch can
never present different scalar avals (= a second executable). A None
budget means "the step's full configured iters" and is resolved by the
eval_fn wrapper, again to the same normalized aval.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Any, Callable, Dict, Iterable, Iterator, List, NamedTuple, Optional, Tuple

import numpy as np

from dexiraft_tpu.data.padder import InputPadder
from dexiraft_tpu.profiling import ServeStats, span
from dexiraft_tpu.serve.buckets import BucketRegistry

EvalFn = Callable[..., Tuple[Any, Any]]

# host bytes the batch-buffer rings of one engine may pin (_batch_buffers):
# the Sintel eval bucket at batch 32 and inflight 2 holds 1.04 GB of it
_RING_BYTES = 2 << 30


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine knobs (see module docstring for the design)."""

    batch_size: int = 1
    mode: str = "sintel"         # pad placement (data.padder modes)
    stride: int = 8
    # bucket quantization granule; None -> stride (reference pad shapes,
    # the metric-parity configuration)
    bucket_multiple: Optional[int] = None
    # dispatched-unfetched tickets to hold before blocking on a fetch
    inflight: int = 2
    # always materialize flow_init (zeros for cold items) so warm-start
    # streams keep one executable per bucket instead of two (None vs
    # array signatures)
    warm_start: bool = False
    # strict guard mode (analysis/guards.py): a recompile on an
    # already-compiled bucket signature RAISES RecompileBudgetExceeded
    # instead of the default one-line drift warning
    strict: bool = False
    # device-resident warm-start carry: per-item flow_init may be a jax
    # DEVICE array (the session store's splatted carry, never fetched to
    # host) — the engine assembles the batch's flow_init ON DEVICE (a
    # jitted row stack over cached zero rows) and keeps each Result's
    # flow_low as a device row instead of fetching it, so the carry
    # path moves ZERO host<->device bytes per frame. flow_up is still
    # fetched (it IS the response). Off (default): the PR 6 host-numpy
    # carry semantics, kept for multi-worker pools and the data-parallel
    # mesh path (pinned shardings re-lay the batch out anyway).
    device_carry: bool = False
    # adaptive-iteration eval_fn (module docstring "ADAPTIVE engines"):
    # dispatches thread an iter_budget scalar through the eval_fn and
    # Results carry per-item iters_used / final_delta
    adaptive: bool = False

    def __post_init__(self):
        if self.batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {self.batch_size}")
        if self.inflight < 1:
            raise ValueError(f"inflight must be >= 1, got {self.inflight}")

    @classmethod
    def from_args(cls, args, *, mode: str = "sintel",
                  warm_start: bool = False,
                  strict: Optional[bool] = None,
                  device_carry: bool = False,
                  adaptive: Optional[bool] = None) -> "ServeConfig":
        """Build from an argparse namespace that went through
        :func:`add_engine_args` — the ONE construction path eval_cli,
        serve_cli, and serve_bench share, so the batching knobs cannot
        drift between the batch-eval and persistent-service code paths."""
        return cls(
            batch_size=args.batch_size,
            mode=mode,
            bucket_multiple=args.bucket_multiple,
            inflight=args.inflight,
            warm_start=warm_start,
            strict=(getattr(args, "strict", False)
                    if strict is None else strict),
            device_carry=device_carry,
            adaptive=(getattr(args, "adaptive", False)
                      if adaptive is None else adaptive),
        )


def add_engine_args(p, *, batch_size: int = 1,
                    bucket_multiple: Optional[int] = None) -> None:
    """The shared engine-knob argparse surface (see ServeConfig.from_args).
    Defaults differ by caller — and the help text reflects the CALLER's
    defaults, not a hardcoded story: eval keeps batch_size=1 / reference
    pad shapes (the metric-parity configuration); the serving CLI raises
    both (batching + bounded executables are the point of a service)."""
    p.add_argument("--batch_size", type=int, default=batch_size,
                   help="frame pairs per forward: 1 = the reference "
                        "per-image loop; >1 streams through the "
                        "throughput-mode inference engine "
                        "(dexiraft_tpu.serve) with identical metrics "
                        f"(default: {batch_size})")
    p.add_argument("--inflight", type=int, default=2,
                   help="dispatched-unfetched batches the engine holds "
                        "before blocking on a host fetch (default: 2)")
    p.add_argument("--bucket_multiple", type=int, default=bucket_multiple,
                   help="quantize pad shapes up to multiples of this "
                        "(bounds compiled executables across mixed "
                        "geometries); default: "
                        + (f"{bucket_multiple}" if bucket_multiple
                           else "stride 8, the exact reference pad "
                                "shapes"))


class Result(NamedTuple):
    """One frame pair's inference output.

    flow_up is unpadded back to the item's own (H, W, 2); flow_low stays
    at the bucket's padded 1/8 resolution — it is the warm-start carry,
    and the next frame of the same sequence pads to the same bucket.

    iters_used / final_delta are the adaptive path's per-item
    convergence evidence (refinement updates actually applied; last
    pre-freeze 1/8-res flow-delta norm); None on fixed-iteration
    engines.
    """

    index: int
    item: Dict[str, Any]
    flow_low: np.ndarray
    flow_up: np.ndarray
    iters_used: Optional[int] = None
    final_delta: Optional[float] = None


class _Ticket(NamedTuple):
    flow_low: Any             # device array future (B, bh/8, bw/8, 2)
    flow_up: Any              # device array future (B, bh, bw, 2)
    entries: List[Tuple[int, Dict[str, Any], InputPadder]]
    t_dispatch: float
    iters_used: Any = None    # adaptive: device (B,) int32 future
    final_delta: Any = None   # adaptive: device (B,) float32 future


class InferenceEngine:
    """Bucketed, batched, pipelined driver for a jitted eval forward."""

    def __init__(
        self,
        eval_fn: EvalFn,
        config: ServeConfig = ServeConfig(),
        *,
        mesh=None,
        put: Optional[Callable[[Any], Any]] = None,
    ):
        self.eval_fn = eval_fn
        self.config = config
        self.mesh = mesh
        if mesh is not None:
            n_data = int(np.prod(list(mesh.shape.values())))
            if config.batch_size % n_data:
                raise ValueError(
                    f"batch_size {config.batch_size} not divisible by the "
                    f"mesh's {n_data} devices — every chip needs a full "
                    f"shard of each dispatched batch")
        if put is None:
            from dexiraft_tpu.parallel.layout import batch_putter

            put = batch_putter(mesh)
        self.put = put
        self.registry = BucketRegistry(config.stride, config.bucket_multiple)
        self.stats = ServeStats()
        self.compile_s = 0.0  # time inside first-dispatch eval_fn calls
        self._inflight: "collections.deque[_Ticket]" = collections.deque()
        # recompile drift sentinel (analysis.guards): a fresh bucket is
        # an EXPECTED compile; a compile on an already-compiled signature
        # is shape/dtype drift eating throughput — surfaced as a
        # one-line warning even when the caller never asked for --strict
        from dexiraft_tpu.analysis.guards import RecompileWatch

        self.watch = RecompileWatch("serve")
        # device-carry machinery (config.device_carry): cached per-shape
        # device zero rows (cold seeds) and the jitted row stack that
        # assembles a batch's flow_init on device — one executable per
        # (batch_size, row shape) signature, compiled inside the
        # bucket's expected first-dispatch window
        self._zero_rows: Dict[Tuple[int, ...], Any] = {}
        # host batch buffers, a ring per bucket in order of last use
        # (_batch_buffers)
        self._rings: Dict[Tuple[int, int], collections.deque] = {}
        self._stack_fn = None

    # ---- input validation ----------------------------------------------

    def validate_item(self, item: Dict[str, Any]) -> None:
        """Public single-item validation (see _validate_item): the HTTP
        server rejects malformed requests with a 400 at the door instead
        of poisoning the scheduler's whole batch with a 500."""
        self._validate_item(0, item)

    def _validate_item(self, index: int, item: Dict[str, Any]) -> None:
        """Reject malformed frames at the door with a clear ValueError.

        Without this, a wrong rank/dtype/channel count fails deep inside
        the jitted bucket step (a shape mismatch against a compiled
        executable, or a tracer-time TypeError) where the message names
        engine internals rather than the offending input.

        The normalized arrays are written back into `item`: validating
        an np.asarray VIEW while the engine later indexes the raw value
        would let an array-like (a nested list) pass the checks and
        still crash on `.shape` — the exact opacity this guard removes.
        """
        shapes = {}
        for key in ("image1", "image2"):
            im = item.get(key)
            if im is None:
                raise ValueError(f"item {index}: missing {key!r}")
            im = item[key] = np.asarray(im)
            if im.ndim != 3:
                raise ValueError(
                    f"item {index}: {key!r} must be rank-3 (H, W, C), got "
                    f"shape {im.shape}")
            if im.shape[-1] != 3:
                raise ValueError(
                    f"item {index}: {key!r} must have 3 channels (RGB HWC), "
                    f"got {im.shape[-1]} (shape {im.shape})")
            if not (np.issubdtype(im.dtype, np.floating)
                    or np.issubdtype(im.dtype, np.integer)):
                raise ValueError(
                    f"item {index}: {key!r} dtype must be a real numeric "
                    f"type castable to float32, got {im.dtype}")
            shapes[key] = im.shape
        if shapes["image1"] != shapes["image2"]:
            raise ValueError(
                f"item {index}: image1 {shapes['image1']} and image2 "
                f"{shapes['image2']} must agree (one flow field per pair)")
        fi = item.get("flow_init")
        if fi is not None:
            if not (hasattr(fi, "ndim") and hasattr(fi, "shape")):
                fi = item["flow_init"] = np.asarray(fi)
            # a real array — numpy OR a jax device array (the session
            # store's device-resident carry) — passes through untouched:
            # np.asarray on a device array would be exactly the implicit
            # D2H transfer the device-carry path exists to remove.
            # Spatial dims are bucket-relative (the carry stays at the
            # PADDED 1/8 resolution), so only rank/channels are checkable
            if fi.ndim != 3 or fi.shape[-1] != 2:
                raise ValueError(
                    f"item {index}: flow_init must be rank-3 (H/{self.config.stride}, "
                    f"W/{self.config.stride}, 2), got shape {fi.shape}")

    # ---- dispatch side -------------------------------------------------

    def _dispatch(self, bucket: Tuple[int, int],
                  group: List[Tuple[int, Dict[str, Any]]],
                  mode: str,
                  iter_budget: Optional[int] = None) -> None:
        cfg = self.config
        if iter_budget is not None and not cfg.adaptive:
            raise ValueError(
                "iter_budget passed to a fixed-iteration engine — build "
                "it with ServeConfig(adaptive=True) and an adaptive "
                "eval_fn (make_eval_step(adaptive=True))")
        t0 = time.perf_counter()
        with span("engine:assemble") as assemble:
            padders = [InputPadder(it["image1"].shape, mode=mode,
                                   stride=cfg.stride, target=bucket)
                       for _, it in group]
            # one pass over the batch's bytes: each frame is cast, placed
            # and edge-padded straight into its row of the batch buffer
            n = len(group)
            im1, im2 = self._batch_buffers(bucket)
            for row, (p, (_, it)) in enumerate(zip(padders, group)):
                p.pad_into(im1[row], it["image1"])
                p.pad_into(im2[row], it["image2"])
            if n < cfg.batch_size:
                # tail: replicate the last item up to the batch shape
                im1[n:] = im1[n - 1]
                im2[n:] = im2[n - 1]
                self.stats.pad_frames += cfg.batch_size - n

        inits = [it.get("flow_init") for _, it in group]
        will_fi = cfg.warm_start or any(x is not None for x in inits)
        fresh = self.registry.mark_compiled((bucket, will_fi))
        # every expected first-dispatch compile rides ONE sanctioned
        # window: the watch is SHARED with the streaming engine
        # (process-global compile counter), whose handler-thread check
        # must not read an in-progress expected compile as drift. That
        # covers the carry stack fn (_assemble_fi device path), the
        # bucket step itself, and the per-row carry slices below.
        win = (self.watch.sanctioned() if fresh
               else contextlib.nullcontext())
        iters_used = final_delta = None
        with win:
            with span("engine:put") as put:
                fi = self._assemble_fi(bucket, inits) if will_fi else None
                im1, im2, fi = self.put((im1, im2, fi))
            # the first call on a fresh signature traces+compiles
            # synchronously before enqueueing — its seconds go to
            # compile_s ONLY (a plain clock read, no span), so
            # dispatch_s and engine:enqueue stay what ServeStats
            # documents (host assemble/put/enqueue time)
            t1 = time.perf_counter()
            with (contextlib.nullcontext() if fresh
                  else span("engine:enqueue")) as enqueue:
                if cfg.adaptive:
                    # the ONE budget-normalization site (module
                    # docstring): every dispatch — warmup,
                    # scheduler-budgeted, default — presents the same
                    # int32 scalar aval, so the signature stays one
                    # executable per bucket
                    ib = (None if iter_budget is None
                          else np.int32(iter_budget))
                    flow_low, flow_up, iters_used, final_delta = \
                        self.eval_fn(im1, im2, fi, ib)
                else:
                    flow_low, flow_up = self.eval_fn(im1, im2, fi)
                if (fresh and cfg.device_carry
                        and not isinstance(flow_low, np.ndarray)):
                    # pre-compile the per-row carry slices: _fetch_one's
                    # low[row] is one executable per STATIC row index,
                    # and warmup batches carry one real item — without
                    # this the first multi-warm batch would compile rows
                    # 1.. after mark_warm and trip a --strict check
                    for row in range(cfg.batch_size):
                        flow_low[row]
            t2 = time.perf_counter()
        self.stats.dispatch_s += assemble.seconds + put.seconds
        if fresh:
            self.compile_s += t2 - t1
            # expected compile: move the drift baseline past it
            self.watch.mark_warm()
        else:
            self.stats.dispatch_s += enqueue.seconds
            # compiled-signature dispatch that still compiled = drift:
            # strict engines fail the run, default engines warn once
            if cfg.strict:
                self.watch.check()
            else:
                self.watch.warn_if_drifted()
        self.stats.batches += 1
        self._inflight.append(_Ticket(
            flow_low, flow_up,
            [(idx, it, p) for (idx, it), p in zip(group, padders)],
            t_dispatch=t0, iters_used=iters_used,
            final_delta=final_delta))
        self.stats.peak_inflight = max(self.stats.peak_inflight,
                                       len(self._inflight))

    def _batch_buffers(self, bucket: Tuple[int, int]):
        """This batch's two (batch_size, H, W, 3) float32 host buffers.

        The engine owns them: a bucket keeps a ring of `inflight + 1`
        pairs and a dispatch takes the oldest. `device_put` may still be
        reading a buffer after it returns (on a TPU the copy is
        asynchronous; the CPU backend may alias a large aligned array
        instead of copying it), so a pair is written again only once
        `inflight` younger batches have been dispatched — and stream()
        fetches a ticket before it dispatches the `inflight`-th batch
        after it, run_batch before it returns — by when its own batch's
        results are on the host and nothing reads its inputs. Reuse is
        the point: a fresh 170 MB array costs more in page faults than
        the pass that fills it (PERF.md section 6, PR 41).

        What the rings pin is bounded by `_RING_BYTES` over all buckets:
        past it the least recently used buckets' rings go (their
        buffers live on until their transfers are done: the runtime
        holds them), so a bucket too large for the budget, or one seen
        rarely among many, gets fresh buffers, which is always safe.
        """
        cfg = self.config
        ring = self._rings.pop(bucket, None) or collections.deque()
        if len(ring) > cfg.inflight:
            pair = ring.popleft()
        else:
            shape = (cfg.batch_size,) + bucket + (3,)
            pair = (np.empty(shape, np.float32), np.empty(shape, np.float32))
        ring.append(pair)
        self._rings[bucket] = ring  # most recently used last
        held = sum(2 * len(r) * r[0][0].nbytes for r in self._rings.values())
        while held > _RING_BYTES:
            old = self._rings.pop(next(iter(self._rings)))
            held -= 2 * len(old) * old[0][0].nbytes
        return pair

    def _assemble_fi(self, bucket: Tuple[int, int], inits: List[Any]):
        """The dispatch group's (batch_size, h/8, w/8, 2) flow_init.

        Host path (device_carry off): a host zeros batch with warm rows
        copied in, transferred with the frames — the PR 6 semantics,
        with the warm rows' bytes counted as carry H2D traffic.

        Device path (device_carry on — ALWAYS, even for an all-cold
        group, so a warmup dispatch compiles the same executables real
        warm traffic rides): rows are stacked ON DEVICE by a jitted
        stack over cached zero rows — warm device rows are never
        fetched, cold rows reuse one resident zero row, and the only
        executable is one stack per (batch_size, row shape), compiled
        inside the bucket's expected first-dispatch window.
        """
        cfg = self.config
        bh, bw = bucket
        shape = (bh // cfg.stride, bw // cfg.stride, 2)
        if not cfg.device_carry:
            if any(init is not None and not isinstance(init, np.ndarray)
                   for init in inits):
                raise ValueError(
                    "a device-array flow_init reached an engine without "
                    "ServeConfig(device_carry=True) — np.asarray on it "
                    "would silently round-trip the carry through the "
                    "host; enable device_carry or hand host numpy")
            fi = np.zeros((cfg.batch_size,) + shape, np.float32)
            for row, init in enumerate(inits):
                if init is not None:
                    fi[row] = np.asarray(init, np.float32)
                    self.stats.carry_h2d_bytes += fi[row].nbytes
            return fi
        import jax  # deferred: module stays importable without jax

        zero = self._zero_rows.get(shape)
        if zero is None:
            # explicit H2D (jaxlint JL007 / strict transfer guard): one
            # resident zero row per shape seeds every cold slot
            zero = self._zero_rows[shape] = jax.device_put(
                np.zeros(shape, np.float32))
        rows = []
        for init in inits:
            if init is None:
                rows.append(zero)
            elif isinstance(init, np.ndarray):
                # mixed stream: a host-carry row (e.g. a client-supplied
                # seed) rides an explicit put; still counted as carry
                # H2D — it IS host carry traffic
                self.stats.carry_h2d_bytes += init.nbytes
                rows.append(jax.device_put(
                    np.ascontiguousarray(init, np.float32)))
            else:
                rows.append(init)
        rows += [zero] * (cfg.batch_size - len(rows))
        if self._stack_fn is None:
            self._stack_fn = jax.jit(lambda *rs: jax.numpy.stack(rs))
        return self._stack_fn(*rows)

    # ---- fetch side ----------------------------------------------------

    def _fetch_one(self) -> Iterator[Result]:
        ticket = self._inflight.popleft()
        # stub eval_fns (unit tests, the fleet tests' subprocess
        # replicas) already returned host arrays — nothing to wait for
        # or to fetch
        host = (isinstance(ticket.flow_low, np.ndarray)
                and isinstance(ticket.flow_up, np.ndarray))
        if not host or ticket.iters_used is not None:
            import jax  # deferred: module stays importable without jax
        with span("engine:wait") as wait:
            # the device is still computing this ticket: the host's
            # slack. Blocks on what copy_out is about to fetch, so it
            # changes no result and no order of transfers
            if not host:
                jax.block_until_ready(
                    ticket.flow_up if self.config.device_carry
                    else (ticket.flow_low, ticket.flow_up))
        with span("engine:copy_out") as copy_out:
            if host:
                low, up = ticket.flow_low, ticket.flow_up
            else:
                # explicit device->host fetch (jaxlint JL007): this sync
                # IS the fetch side's job, and device_get passes a strict
                # transfer guard
                if self.config.device_carry:
                    # the carry consumer (session splat) lives on device
                    # — keep flow_low there; Result.flow_low rows become
                    # device slices and the carry never crosses the bus
                    low = ticket.flow_low
                else:
                    low = jax.device_get(ticket.flow_low)
                    if self.config.warm_start:
                        # carry traffic only when the engine is
                        # configured for session carry (serve sets
                        # warm_start with sessions); a stateless
                        # replica's flow_low fetch is plain Result
                        # plumbing, not carry bytes
                        self.stats.carry_d2h_bytes += low.nbytes
                up = jax.device_get(ticket.flow_up)
            iu = fd = None
            if ticket.iters_used is not None:
                if isinstance(ticket.iters_used, np.ndarray):
                    # stub eval_fns hand host arrays straight through
                    iu, fd = ticket.iters_used, ticket.final_delta
                else:
                    # explicit D2H (jaxlint JL007): (B,) vectors, a few
                    # bytes
                    iu = jax.device_get(ticket.iters_used)
                    fd = jax.device_get(ticket.final_delta)
        self.stats.fetch_s += wait.seconds + copy_out.seconds
        self.stats.fetches += 1
        self.stats.batch_latency_s.append(
            time.perf_counter() - ticket.t_dispatch)
        with span("engine:deliver"):
            results = [
                Result(idx, item, low[row], padder.unpad(up[row]),
                       **({} if iu is None else {
                           "iters_used": int(iu[row]),
                           "final_delta": float(fd[row])}))
                for row, (idx, item, padder) in enumerate(ticket.entries)]
        # the consumer's work between two next() calls runs on this same
        # serial thread: the generator stays suspended at each yield for
        # as long as the caller takes with the row
        with span("engine:caller"):
            for result in results:
                self.stats.frames += 1
                if iu is not None:
                    self.stats.iters_used.append(result.iters_used)
                    self.stats.final_delta.append(result.final_delta)
                yield result

    def _drain_to(self, n: int) -> Iterator[Result]:
        while len(self._inflight) > n:
            yield from self._fetch_one()

    # ---- public API ----------------------------------------------------

    def stream(self, items: Iterable[Dict[str, Any]],
               mode: Optional[str] = None,
               iter_budget: Optional[int] = None) -> Iterator[Result]:
        """Run every item through the engine; yield Results as their
        batches complete (bucket-grouped, NOT input order — each Result
        carries its original index).

        items: dicts with image1/image2 (H, W, C) and anything else the
        caller wants back on the Result (gt flow, extra_info, ...);
        an optional per-item flow_init rides the same dict.

        iter_budget (adaptive engines only) caps every dispatched
        batch's refinement iterations; None rides the full iters.
        """
        mode = mode or self.config.mode
        cfg = self.config
        pending: Dict[Tuple[int, int], List[Tuple[int, Dict[str, Any]]]] = {}
        for index, item in enumerate(items):
            self._validate_item(index, item)
            h, w = item["image1"].shape[-3], item["image1"].shape[-2]
            bucket = self.registry.bucket_for(h, w)
            pending.setdefault(bucket, []).append((index, item))
            if len(pending[bucket]) == cfg.batch_size:
                # fetch down to a free slot BEFORE dispatching, so at
                # most `inflight` tickets are ever outstanding
                yield from self._drain_to(cfg.inflight - 1)
                self._dispatch(bucket, pending.pop(bucket), mode,
                               iter_budget=iter_budget)
        for bucket in sorted(pending):  # partial tails, deterministic order
            yield from self._drain_to(cfg.inflight - 1)
            self._dispatch(bucket, pending.pop(bucket), mode,
                           iter_budget=iter_budget)
        yield from self._drain_to(0)

    def run_batch(self, items: List[Dict[str, Any]],
                  mode: Optional[str] = None,
                  iter_budget: Optional[int] = None) -> List[Result]:
        """Dispatch ONE batch synchronously and return Results in input
        order — the building block for sequenced workloads (Sintel
        warm-start carries the previous frame's flow_low, so frame j+1
        cannot dispatch before frame j fetches). All items must share a
        bucket; len(items) <= batch_size (the tail pad fills the rest).

        iter_budget (adaptive engines only): this dispatch's iteration
        budget — the scheduler's SLO/overload policy hands it in here;
        None rides the step's full configured iters.
        """
        if not items:
            return []
        if len(items) > self.config.batch_size:
            raise ValueError(f"{len(items)} items > batch_size "
                             f"{self.config.batch_size}")
        mode = mode or self.config.mode
        for index, item in enumerate(items):
            self._validate_item(index, item)
        buckets = {self.registry.bucket_for(
            it["image1"].shape[-3], it["image1"].shape[-2]) for it in items}
        if len(buckets) > 1:
            raise ValueError(f"run_batch items span buckets {buckets}")
        if self._inflight:
            # fetching here would silently discard an unfinished
            # stream()'s Results — make the misuse loud instead
            raise RuntimeError(
                f"run_batch with {len(self._inflight)} ticket(s) still in "
                "flight from a previous stream(); consume that iterator "
                "first (or use a separate engine)")
        self._dispatch(buckets.pop(), list(enumerate(items)), mode,
                       iter_budget=iter_budget)
        out = sorted(self._fetch_one(), key=lambda r: r.index)
        return out

    def reset_stats(self) -> None:
        """Zero the accounting for a fresh measurement window while
        keeping the compiled-executable state.

        A long-lived server scrapes /stats on a cadence; without this the
        ServeStats counters (and the latency sample list) accumulate for
        the life of the process and every scrape re-reports history. The
        compiled-signature set and the watch baseline survive on purpose:
        resetting them would misreport the next dispatch on a warm bucket
        as a fresh compile (and re-arm the drift warning the bucket
        already absorbed). serve_bench's warmup->timed handoff is the
        same operation.
        """
        self.stats.reset()
        self.registry.hits.clear()
        self.compile_s = 0.0

    def stats_record(self) -> dict:
        """Self-describing stats blob for bench records / logs.

        The adaptive keys appear ONLY on adaptive engines: fixed-path
        records (and the serve_bench schemas pinned over them) are
        byte-identical to before the adaptive path existed.
        """
        rec = {
            "batch_size": self.config.batch_size,
            "inflight": self.config.inflight,
            "frames": self.stats.frames,
            "batches": self.stats.batches,
            "pad_frames": self.stats.pad_frames,
            "peak_inflight": self.stats.peak_inflight,
            "fetch_blocked_ms": round(self.stats.fetch_s * 1e3, 2),
            "dispatch_ms": round(self.stats.dispatch_s * 1e3, 2),
            "compile_s": round(self.compile_s, 2),
            "device_carry": self.config.device_carry,
            "carry_h2d_bytes": self.stats.carry_h2d_bytes,
            "carry_d2h_bytes": self.stats.carry_d2h_bytes,
            "latency_p50_ms": round(self.stats.latency_ms(50), 2),
            "latency_p99_ms": round(self.stats.latency_ms(99), 2),
            **self.registry.stats(),
        }
        if self.config.adaptive:
            rec.update(
                adaptive=True,
                iters_used_mean=round(self.stats.iters_used_mean(), 2),
                iters_used_p50=round(self.stats.iters_used_pctl(50), 2),
                iters_used_p99=round(self.stats.iters_used_pctl(99), 2),
                final_delta_p50=round(self.stats.final_delta_pctl(50), 5),
                final_delta_p99=round(self.stats.final_delta_pctl(99), 5),
            )
        return rec
