"""Traffic kind `eval_closed`: offline validation as `eval_cli --batch_size`
runs it. A seeded pool of frame pairs goes through
`InferenceEngine.stream` in a closed loop (the next batch is dispatched
when a slot of the in-flight window frees), batch after batch until the
window is over.

`eval_pairs_per_s` is the device-ready cadence of whole batches: the
clock ticks when a batch's `flow_up` is ready on the device (a watcher
thread blocks on each dispatched batch in turn); it runs from the
window's first batch to its last and counts the batches after the
first. The `Pacer` stops offering work at a batch boundary and the loop
then drains, so no batch is cut by the clock. Over a dataset that
cadence IS the end-to-end rate: the engine cannot dispatch batch k+2
before it has fetched batch k, so a fetch or a dispatch that stops
being hidden shows as the device waiting, and the cadence slows.
Ticking at the fetch instead puts the first and last device-to-host
copies' jitter on the clock (~0.45 +- 0.1 s each on the chip's shared
host: 2 % run-to-run spread of a 10 s window, against 0.02 % for the
device's own cadence; my chip runs, PR 22), and starting it at the
first dispatch adds the pipeline's fill (one pad, stack and put with
the device idle, 0.6-0.7 s) that a dataset of a thousand pairs never
notices. The counters carry both clocks on every run (`window_s`, and
`window_s_dispatch_to_fetch` over `window_batches_dispatch_to_fetch`),
so a fetch that is no longer hidden can be seen from outside too.

Parameters (traffic file): `frame_hw`, `batch`, `iters`, `pad_mode`, `bucket_multiple`, `inflight`,
`model_flags` (RAFTConfig flags of the cell), `plain_flags` (the same
model as plain XLA in fp32: the reference of the check), `check_pairs`,
`check_tol`, `traced_batches`. From the configuration file:
`weights.eval_flow_head_scale`.
"""

from __future__ import annotations

import queue
import threading
import time

import numpy as np

from benchmarks import harness, synth


def _variables(cfg, seed: int, flow_head_scale: float):
    """Model variables from the seed, on the device, in one jitted call:
    what `create_state` does without the optimizer state an evaluator
    never reads. RAFT is fully convolutional, so a small dummy input
    gives the same parameter shapes as the real geometry.

    The flow head's last convolution is reshaped (the configuration
    file's `weights` says why): its kernel k becomes (k - k mirrored in
    x) / sqrt(2) times `flow_head_scale`, its bias zero. A kernel that
    is antisymmetric along x answers a feature map with its horizontal
    differences, which sum to about nothing along an image row, so the
    flow has no drift, both signs in every row, and a size the scale
    sets."""
    import jax
    import jax.numpy as jnp

    from dexiraft_tpu.models.raft import RAFT

    model = RAFT(cfg)
    reshaped = []

    def flow_head(path, x):
        keys = [getattr(k, "key", "") for k in path]
        if not (any(k.startswith("FlowHead") for k in keys)
                and "Conv_1" in keys):
            return x
        reshaped.append(keys)
        if x.ndim == 1:
            return jnp.zeros_like(x)
        return (x - x[:, ::-1]) * (flow_head_scale / 2 ** 0.5)

    def init(rng):
        dummy = jnp.zeros((1, 64, 64, 3), jnp.float32)
        variables = model.init(rng, dummy, dummy, iters=1, train=False)
        return {**variables, "params": jax.tree_util.tree_map_with_path(
            flow_head, variables["params"])}

    variables = jax.jit(init)(jax.random.PRNGKey(seed))
    if len(reshaped) != 2:  # one kernel, one bias
        raise harness.BenchError(
            f"expected one FlowHead_*/Conv_1 kernel and bias in the "
            f"parameters, found {reshaped}")
    return variables


def _eval_fn(cfg, variables, iters: int, on_dispatch=None):
    """`eval_cli._make_eval_fn`'s single-device form: positional
    (image1, image2, flow_init), explicit puts. `on_dispatch` is handed
    each call's `flow_up` future."""
    import jax

    from dexiraft_tpu.train.step import make_eval_step

    step = make_eval_step(cfg, iters=iters)
    put = jax.device_put

    def fn(im1, im2, flow_init=None):
        out = step(variables, put(im1), put(im2),
                   flow_init=None if flow_init is None else put(flow_init))
        if on_dispatch is not None:
            on_dispatch(out[1])
        return out

    return fn


class _ReadyClock:
    """When each dispatched batch became ready on the device, in dispatch
    order: one thread that blocks on each batch's `flow_up` in turn.
    `on_tick(n)`, if set, runs on that thread right after tick n."""

    def __init__(self):
        self.times = []
        self.on_tick = None
        self.error = None
        self._queue = queue.SimpleQueue()
        self._thread = threading.Thread(target=self._watch, daemon=True)
        self._thread.start()

    def _watch(self):
        import jax

        while True:
            item = self._queue.get()
            if item is None:
                return
            if isinstance(item, threading.Event):
                item.set()
                continue
            jax.block_until_ready(item)
            self.times.append(time.perf_counter())
            if self.on_tick is not None and self.error is None:
                try:
                    self.on_tick(len(self.times))
                except Exception as e:  # raised where `ticks` is read
                    self.error = e

    def __call__(self, flow_up) -> None:
        self._queue.put(flow_up)

    def ticks(self) -> list:
        """The times so far, once the watcher has seen all that was put."""
        seen = threading.Event()
        self._queue.put(seen)
        seen.wait()
        if self.error is not None:
            raise self.error
        return list(self.times)

    def close(self) -> None:
        self._queue.put(None)
        self._thread.join()


def _plain_flows(ctx, variables, pairs, tr):
    """The check's reference: each pair alone through the plain path
    (materialised correlation volume, no Pallas, fp32 everywhere, true
    fp32 matmuls), same weights, same iterations, same padding."""
    import jax

    from dexiraft_tpu.data.padder import InputPadder

    cfg = harness.build_config(ctx.cell.config, tr["plain_flags"], ctx.platform)
    with jax.default_matmul_precision("highest"):
        fn = _eval_fn(cfg, variables, tr["iters"])
        flows = []
        for pair in pairs:
            padder = InputPadder(pair["image1"].shape, mode=tr["pad_mode"],
                                 stride=8)
            im1, im2 = padder.pad(pair["image1"].astype(np.float32)[None],
                                  pair["image2"].astype(np.float32)[None])
            _, up = fn(im1, im2)
            flows.append(padder.unpad(np.asarray(jax.device_get(up))[0]))
    return flows


def run(ctx: harness.Context) -> harness.Outcome:
    import jax

    from dexiraft_tpu.analysis.guards import RecompileWatch
    from dexiraft_tpu.serve import InferenceEngine, ServeConfig

    tr = ctx.cell.traffic
    batch = tr["batch"]
    hw = tuple(tr["frame_hw"])
    log = ctx.log

    with ctx.spans.span("init"):
        cfg = harness.build_config(ctx.cell.config, tr["model_flags"],
                                   ctx.platform)
        scale = ctx.cell.config["weights"]["eval_flow_head_scale"]
        variables = jax.block_until_ready(_variables(cfg, ctx.seed, scale))
        n_params = sum(x.size for x in jax.tree.leaves(variables["params"]))
        pool = synth.frame_pairs(ctx.seed, batch, hw)
    log(f"{ctx.cell.config_name}: {n_params} parameters, flow head x{scale}, "
        f"corr_impl={cfg.corr_impl} fused_update={cfg.fused_update}; batch "
        f"{batch} of {hw[0]}x{hw[1]}, {tr['iters']} iterations")

    ready = _ReadyClock()
    engine = InferenceEngine(
        _eval_fn(cfg, variables, tr["iters"], on_dispatch=ready),
        ServeConfig(batch_size=batch, mode=tr["pad_mode"],
                    bucket_multiple=tr["bucket_multiple"],
                    inflight=tr["inflight"]))

    def fresh(i: int) -> dict:  # the engine writes normalised arrays back
        return dict(pool[i % len(pool)])

    with ctx.spans.span("warm"):
        # the cell's one shape: the first call compiles, or loads from
        # the persistent cache
        warm = {r.index: r.flow_up
                for r in engine.stream(fresh(i) for i in range(batch))}

    # the reference's compiles are expected: keep them out of the
    # engine's drift warning
    with ctx.spans.span("check"), engine.watch.sanctioned():
        n_check = tr["check_pairs"]
        ref = _plain_flows(ctx, variables, pool[:n_check], tr)
        rel = [float(np.mean(np.linalg.norm(warm[i] - ref[i], axis=-1))
                     / np.mean(np.linalg.norm(ref[i], axis=-1)))
               for i in range(n_check)]
        check_ok = all(np.isfinite(rel)) and max(rel) <= tr["check_tol"]
        mags = [np.linalg.norm(f, axis=-1) for f in ref]
    log(f"check against the plain path: relative end-point difference "
        f"{[round(r, 5) for r in rel]} (tolerance {tr['check_tol']}): "
        f"{'ok' if check_ok else 'FAILED'}; the reference's flow is "
        f"{[round(float(m.mean()), 2) for m in mags]} px in the mean, "
        f"{[round(float(m.max()), 2) for m in mags]} px at most")

    # ---- the measured window: profiler off ----
    first = len(ready.ticks())
    engine.reset_stats()
    watch = RecompileWatch("bench-window")
    watch.mark_warm()
    # four batches at least: the first starts the clock, three are on
    # it. No guess of a batch's time is needed: with `inflight` batches
    # out, the loop cannot offer a third before the first has finished
    pacer = harness.Pacer(ctx.seconds, ctx.seconds, least=4)
    state = {"bad": 0, "got": 0}

    def offered(pacer):
        i = 0
        while pacer.more():
            pacer.note_dispatch()  # the engine dispatches once these are in
            for _ in range(batch):
                yield fresh(i)
                i += 1

    def consume(results, pacer):
        for r in results:
            state["got"] += 1
            if r.flow_up.shape != hw + (2,) or not np.isfinite(r.flow_up).all():
                state["bad"] += 1
            if state["got"] % batch == 0:
                pacer.note_finish()
                yield

    for _ in consume(engine.stream(offered(pacer)), pacer):
        pass
    attempted, got, bad = pacer.dispatched * batch, state["got"], state["bad"]
    ticks = ready.ticks()[first:]
    window_s = ticks[-1] - ticks[0]
    counters = {
        "window_batches": len(ticks) - 1,
        "window_s": window_s,
        "window_s_dispatch_to_fetch": pacer.elapsed,
        "window_batches_dispatch_to_fetch": pacer.finished,
        "window_compiles": watch.drift,
        "engine_dispatch_s": engine.stats.dispatch_s,
        "engine_fetch_blocked_s": engine.stats.fetch_s,
        "engine_batches": engine.stats.batches,
        "batch": batch,
        "iters": tr["iters"],
        "params": n_params,
    }
    rate = (len(ticks) - 1) * batch / window_s

    # ---- the traced tail: a few more batches of the same loop ----
    trace = None
    if ctx.trace:
        from benchmarks import flops

        tw = harness.TraceWindow(ctx)
        tw.start()
        traced = tr["traced_batches"]
        lead = tr["inflight"]  # batches that refill the pipeline first
        # the window opens on the device-ready tick of batch `lead` and
        # closes on that of batch `lead + traced`, with `inflight` more
        # batches offered behind it: the pipeline is full at both ends,
        # as it is all through a dataset, and the window holds `traced`
        # batches of device work. The watcher thread makes the span (a
        # TraceAnnotation starts when it is made)
        base = len(ready.ticks())
        span = []

        def on_tick(n):
            if n == base + lead:
                span.append(tw.span("window"))
                span[0].__enter__()
            elif n == base + lead + traced:
                span[0].__exit__(None, None, None)

        ready.on_tick = on_tick
        tp = harness.Pacer(0.0, 0.0, least=lead + traced + tr["inflight"])
        # this thread's line of the trace is kept for its span: what the
        # host did in an idle gap is read from it
        with tw.span("tail"):
            for _ in consume(engine.stream(offered(tp)), tp):
                pass
        ready.ticks()
        ready.on_tick = None
        trace = tw.stop()
        counters["traced_units"] = traced
        from dexiraft_tpu.models.raft import RAFT
        from dexiraft_tpu.train.step import make_eval_step

        shape = jax.ShapeDtypeStruct((batch,) + engine.registry.bucket_for(*hw)
                                     + (3,), np.float32)
        with engine.watch.sanctioned():  # a cache load, after the window
            counters.update(harness.compiled_memory(
                make_eval_step(cfg, iters=tr["iters"]).lower(
                    variables, shape, shape)))
        plain = harness.build_config(ctx.cell.config, tr["plain_flags"],
                                     ctx.platform)
        counters["flops_per_unit"] = flops.count(
            lambda v, a, b: RAFT(plain).apply(
                v, a, b, iters=tr["iters"], train=False, test_mode=True),
            variables, shape, shape)
    ready.close()

    return harness.Outcome(
        attempted=attempted, failed=bad + (attempted - got),
        correct=check_ok and bad == 0 and got == attempted,
        end_to_end={"eval_pairs_per_s": rate}, window_start=pacer.start,
        counters=counters, trace=trace)


def compile_for(cell, topo, report) -> None:
    """compile_check.py: this cell's step and its plain reference, from
    shapes, for the described topology's first chip."""
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from dexiraft_tpu.models.raft import RAFT
    from dexiraft_tpu.serve.buckets import BucketRegistry
    from dexiraft_tpu.train.step import make_eval_step

    tr = cell.traffic
    chip = SingleDeviceSharding(topo.devices[0])
    batch = tr["batch"]
    bucket = BucketRegistry(8, tr["bucket_multiple"]).bucket_for(*tr["frame_hw"])

    def shaped(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)

    for label, flags, b, precision in (
            ("step", tr["model_flags"], batch, None),
            ("plain reference", tr["plain_flags"], 1, "highest")):
        cfg = harness.build_config(cell.config, flags, "tpu")
        dummy = np.zeros((1, 64, 64, 3), np.float32)
        variables = shaped(jax.eval_shape(
            lambda: RAFT(cfg).init(jax.random.PRNGKey(0), dummy, dummy,
                                   iters=1, train=False)))
        image = jax.ShapeDtypeStruct((b,) + tuple(bucket) + (3,), np.float32,
                                     sharding=chip)
        with jax.default_matmul_precision(precision or "default"):
            lowered = make_eval_step(cfg, iters=tr["iters"]).lower(
                variables, image, image)
            report(f"{cell.name} {label} (batch {b}, {bucket[0]}x{bucket[1]}, "
                    f"{tr['iters']} iters, corr_impl={cfg.corr_impl}, "
                    f"fused={cfg.fused_update})", lowered)
