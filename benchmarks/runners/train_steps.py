"""Traffic kind `train_steps`: the training loop as `train_cli` wires it.
A seeded FlyingChairs tree on disk -> `fetch_dataset` (full augmentation)
-> `Loader` (worker threads) -> `prefetch_to_device` -> `make_train_step`
on `make_train_mesh` over the cell's chips, state placed with
`layout.shard_state`.

`train_samples_per_s` is global batch x steps completed over the time
from the first dispatch of the window to `block_until_ready` on the last
step's loss. The loader and the prefetcher run as they do for a user.
Run-ahead is bounded to two steps: the loop blocks on the loss of step
k-2 before it dispatches step k, so the host cannot queue the whole
window at once and then wait.

Parameters (traffic file): `stage` (of config's `MIXED_STAGES`, which
gives lr, crop, weight decay), `batch` (global), `iters`, `tree_pairs`, `tree_hw`, `num_workers`,
`prefetch_depth`, `model_flags`, `plain_flags` (the check's reference:
fp32, plain XLA, no remat), `check_tol`, `warm_steps`, `traced_steps`,
`loader_drain_s`.
"""

from __future__ import annotations

import dataclasses
import os
import time

import numpy as np

from benchmarks import harness, synth


def _train_config(tr: dict, seed: int):
    import dexiraft_tpu.config as cfglib

    base = next(tc for tc in cfglib.MIXED_STAGES if tc.stage == tr["stage"])
    return dataclasses.replace(
        base, batch_size=tr["batch"], iters=tr["iters"], seed=seed,
        prefetch_depth=tr["prefetch_depth"],
        **({"image_size": tuple(tr["image_size"])} if "image_size" in tr
           else {}))


def _plain_loss_fn(cfg, tc):
    """Forward-only loss of one batch under the plain configuration, with
    BatchNorm in train mode as in the step: what the step's first loss
    is checked against."""
    import jax

    from dexiraft_tpu.models.raft import RAFT
    from dexiraft_tpu.ops.losses import sequence_loss

    model = RAFT(cfg)

    def loss(params, batch_stats, batch):
        flows, _ = model.apply(
            {"params": params, "batch_stats": batch_stats},
            batch["image1"], batch["image2"], iters=tc.iters, train=True,
            freeze_bn=tc.freeze_bn, mutable=["batch_stats"],
            rngs={"dropout": jax.random.PRNGKey(0)})
        return sequence_loss(flows.astype(np.float32), batch["flow"],
                             batch["valid"], tc.gamma)[0]

    return loss


def _drain(loader, seconds: float) -> float:
    """Samples a second the loader gives with nothing consuming but this
    loop and the device idle: the host's ceiling for this cell."""
    it = loader.batches()
    try:
        next(it)  # workers started, queue primed
        t0 = time.perf_counter()
        n = 0
        while time.perf_counter() - t0 < seconds:
            n += next(it)["image1"].shape[0]
        return n / (time.perf_counter() - t0)
    finally:
        it.close()


def run(ctx: harness.Context) -> harness.Outcome:
    import jax

    from dexiraft_tpu.analysis.guards import RecompileWatch
    from dexiraft_tpu.data.datasets import fetch_dataset
    from dexiraft_tpu.data.loader import Loader
    from dexiraft_tpu.data.prefetch import prefetch_to_device
    from dexiraft_tpu.parallel import layout
    from dexiraft_tpu.train.state import create_state, param_count
    from dexiraft_tpu.train.step import make_train_step

    tr = ctx.cell.traffic
    log = ctx.log
    counters = {}

    with ctx.spans.span("init"):
        os.environ["DEXIRAFT_DATA_DIR"] = synth.chairs_tree(
            ctx.work_dir(), ctx.seed, tr["tree_pairs"], tr["tree_hw"])
        cfg = harness.build_config(ctx.cell.config, tr["model_flags"],
                                   ctx.platform)
        tc = _train_config(tr, ctx.seed)
        mesh = layout.make_train_mesh(tc.batch_size, devices=ctx.devices)
        if mesh.size != len(ctx.devices):
            raise harness.BenchError(
                f"batch {tc.batch_size} spans {mesh.size} of the cell's "
                f"{len(ctx.devices)} chips")
        state = create_state(jax.random.PRNGKey(ctx.seed), cfg, tc)
        state = jax.block_until_ready(layout.shard_state(state, mesh))
        n_params = param_count(state.params)
        dataset = fetch_dataset(tc.stage, tc.image_size)
        loader = Loader(dataset, tc.batch_size, seed=ctx.seed,
                        num_workers=tr["num_workers"], worker_mode="thread")
    log(f"{ctx.cell.config_name}: {n_params} parameters, mesh "
        f"{dict(mesh.shape)}, global batch {tc.batch_size}, crop "
        f"{tc.image_size}, {tc.iters} iterations, remat={cfg.remat}, "
        f"corr_impl={cfg.corr_impl}, {len(dataset)} pairs on disk, "
        f"{tr['num_workers']} loader threads")

    if ctx.trace:
        # a host measurement, taken in the traced run only so that the
        # timed runs' set-up stays what the cell's traffic needs
        with ctx.spans.span("loader_drain"):
            counters["loader_samples_per_s"] = _drain(loader,
                                                      tr["loader_drain_s"])

    step_fn = make_train_step(cfg, tc, mesh=mesh)
    batches = prefetch_to_device(loader.batches(), mesh,
                                 depth=tc.prefetch_depth,
                                 pipeline_stats=loader.stats)
    losses, finite = [], []
    try:
        with mesh:
            first = next(batches)
            with ctx.spans.span("check"):
                # before step 1: the step donates the state it is given
                plain = harness.build_config(ctx.cell.config,
                                             tr["plain_flags"], ctx.platform)
                with jax.default_matmul_precision("highest"):
                    ref_loss = float(jax.device_get(jax.jit(
                        _plain_loss_fn(plain, tc))(
                            state.params, state.batch_stats, first)))

            def step(batch):
                nonlocal state
                state, metrics = step_fn(state, batch)
                losses.append(metrics["loss"])
                finite.append(metrics["state_finite"])

            with ctx.spans.span("warm"):
                # first call compiles or loads from the cache; the rest
                # settle the loader and give the pacer its first guess
                step(first)
                jax.block_until_ready(losses[-1])
                t0 = time.perf_counter()
                for _ in range(tr["warm_steps"] - 1):
                    step(next(batches))
                jax.block_until_ready(losses[-1])
                step_guess = ((time.perf_counter() - t0)
                              / max(tr["warm_steps"] - 1, 1))

            first_loss = float(jax.device_get(losses[0]))
            rel = abs(first_loss - ref_loss) / abs(ref_loss)
            check_ok = bool(np.isfinite(rel)) and rel <= tr["check_tol"]
            log(f"first losses {[round(float(jax.device_get(l)), 4) for l in losses[:3]]}; "
                f"step-1 loss {first_loss:.4f} against the plain forward's "
                f"{ref_loss:.4f}: {rel:.5f} apart (tolerance "
                f"{tr['check_tol']}): {'ok' if check_ok else 'FAILED'}")

            tw = harness.TraceWindow(ctx)  # its spans are inert until started

            def run_steps(pacer, on_finish=lambda n: None):
                """Steps while the pacer says more, two steps of run-ahead:
                before step k is dispatched the loss of step k-2 is
                waited for. Then the rest is waited for."""
                base = len(losses)

                def finish():
                    jax.block_until_ready(losses[base + pacer.finished])
                    pacer.note_finish()
                    on_finish(pacer.finished)

                while pacer.more():
                    if pacer.dispatched >= 2:
                        finish()
                    with tw.span("loader_wait"):
                        batch = next(batches)
                    with tw.span("dispatch"):
                        step(batch)
                    pacer.note_dispatch()
                while pacer.finished < pacer.dispatched:
                    finish()

            # ---- the measured window: profiler off ----
            batches.stats.reset()
            watch = RecompileWatch("bench-window")
            watch.mark_warm()
            pacer = harness.Pacer(ctx.seconds, step_guess)
            run_steps(pacer)
            steps = pacer.finished
            counters.update(
                window_steps=steps, window_s=pacer.elapsed,
                window_compiles=watch.drift,
                prefetch_stall_s=batches.stats.stall_s,
                prefetch_stalls=batches.stats.stalls,
                prefetch_batches=batches.stats.batches,
                loader_faults=loader.stats.faults,
                batch=tc.batch_size, iters=tc.iters, params=n_params)
            rate = tc.batch_size * steps / pacer.elapsed
            attempted = pacer.dispatched

            # ---- the traced tail: a few more steps of the same loop ----
            trace = None
            if ctx.trace:
                tw.start()
                # the span opens once `lead` steps have refilled the
                # pipeline and closes `traced` steps later, with two
                # more steps still queued behind it
                lead, traced = 3, tr["traced_steps"]
                span = []

                def on_finish(n):
                    if n == lead:
                        # a TraceAnnotation starts when it is made
                        span.append(tw.span("window"))
                        span[0].__enter__()
                    elif n == lead + traced:
                        span[0].__exit__(None, None, None)

                run_steps(harness.Pacer(0.0, step_guess,
                                        least=lead + traced + 2), on_finish)
                trace = tw.stop()
                counters["traced_units"] = traced
                counters.update(harness.compiled_memory(
                    step_fn.lower(state, first)))
    finally:
        batches.close()

    bad = sum(1 for l, f in zip(losses, finite)
              if not (np.isfinite(float(jax.device_get(l)))
                      and bool(jax.device_get(f))))
    if ctx.trace:
        counters["flops_per_unit"] = _step_flops(ctx, tr, tc, state, first)
    return harness.Outcome(
        attempted=attempted, failed=bad + (attempted - steps),
        correct=check_ok and bad == 0 and steps == attempted,
        end_to_end={"train_samples_per_s": rate},
        window_start=pacer.start,
        counters=counters, trace=trace)


def _step_flops(ctx, tr, tc, state, batch) -> int:
    """Loss and gradients of one global batch in the plain form."""
    import jax

    from benchmarks import flops

    plain = harness.build_config(ctx.cell.config, tr["plain_flags"],
                                 ctx.platform)
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
    return flops.count(jax.value_and_grad(_plain_loss_fn(plain, tc)),
                       state.params, state.batch_stats, shapes)


def compile_for(cell, topo, report) -> None:
    """compile_check.py: this cell's step and its plain reference, from
    shapes, on a mesh over the described topology's chips."""
    import jax
    import numpy as np

    from dexiraft_tpu.parallel import layout
    from dexiraft_tpu.train.state import create_state
    from dexiraft_tpu.train.step import make_train_step

    tr = cell.traffic
    cfg = harness.build_config(cell.config, tr["model_flags"], "tpu")
    tc = _train_config(tr, 0)
    mesh = layout.make_train_mesh(tc.batch_size, devices=topo.devices[:cell.chips])
    repl = layout.replicated_sharding(mesh)
    data = layout.batch_input_sharding(mesh)
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=repl),
        jax.eval_shape(lambda: create_state(jax.random.PRNGKey(0), cfg, tc)))
    h, w = tc.image_size
    b = tc.batch_size
    f32 = np.float32
    batch = {k: jax.ShapeDtypeStruct(s, f32, sharding=data) for k, s in {
        "image1": (b, h, w, 3), "image2": (b, h, w, 3),
        "flow": (b, h, w, 2), "valid": (b, h, w)}.items()}
    what = (f"(global batch {b} on mesh {dict(mesh.shape)}, {h}x{w}, "
            f"{tc.iters} iters")
    with mesh:
        report(f"{cell.name} step {what}, remat={cfg.remat}, corr_impl="
                f"{cfg.corr_impl})",
                make_train_step(cfg, tc, mesh=mesh).lower(state, batch))
        plain = harness.build_config(cell.config, tr["plain_flags"], "tpu")
        with jax.default_matmul_precision("highest"):
            report(f"{cell.name} plain reference {what}, forward only, fp32)",
                    jax.jit(_plain_loss_fn(plain, tc)).lower(
                        state.params, state.batch_stats, batch))
