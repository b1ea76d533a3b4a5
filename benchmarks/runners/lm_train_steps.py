"""Traffic kind `lm_train_steps`: the language model through the training
loop as `train_cli` wires it. A seeded token file on disk ->
`PackedTokens` (first-fit rows) -> `Loader` (worker threads) ->
`prefetch_to_device` -> `make_train_step` (the seam, train/family.py) on
`make_train_mesh` over the cell's chips, state placed with
`layout.shard_state`.

`train_samples_per_s` is rows x steps completed over the time from the
first dispatch of the window to `block_until_ready` on the last step's
loss, as in `train_steps`: a sample is one row of the batch, here
`seq_len` positions. Loader and prefetcher run as for a user; run-ahead
is two steps.

`correct`, outside the window, on the timed path's first batch and
weights: the first step's own loss, gradient norm (the step reports
`grad_norm`) and three leaves of its gradient against the plain
reference (interop/lm_reference.py, fp32, `highest`) walked a sequence
and a layer at a time before the step runs, with the optimizer state
parked on the host meanwhile. The step's gradient is read back from what
the step itself leaves behind: after the first AdamW step the first
moment is `(1 - b1) x` the clipped gradient, so a leaf of `mu`, divided
by `(1 - b1)` and by the clip's factor `min(1, clip / grad_norm)`, is the
gradient the timed program computed (bf16, layers recomputed), to fp32's
last bits; no second program is compiled for the check. Also: no slot
dropped in any step; losses and state finite; steps attempted =
completed. The traffic file gives each tolerance with its reason.

Parameters (traffic file): `batch`, `lr`, `wdecay`, `num_steps`,
`documents` (the generator's: `count`, `median`, `sigma`, `shortest`,
`longest`), `num_workers`, `prefetch_depth`, `model_flags`, `check`
(`leaves`, `tolerances`), `warm_steps`, `traced_steps`, `loader_drain_s`,
and for the rehearsal `toy_model`.
"""

from __future__ import annotations

import os
import time

import numpy as np

from benchmarks import harness

SCOPES = ("lm/moe/experts", "lm/moe/router", "lm/moe/dispatch",
          "lm/moe/shared", "lm/moe/combine", "lm/mla", "lm/mlp",
          "lm/head_loss", "lm/embed", "lm/norm", "optimizer")
# the only grouped products of the step are the experts' (ops/grouped.py);
# XLA renames them and their metadata `ragged-dot-*`
ALIASES = {"ragged-dot": "lm/moe/experts"}


def _configs(cell, seed: int):
    """(LMConfig, TrainConfig) of the cell: the configuration file's
    published keys with its share, the traffic file's flags."""
    try:
        import dexiraft_tpu.config as cfglib
        make = getattr(cfglib, cell.config["constructor"])
    except (ImportError, AttributeError) as e:
        raise harness.BenchError(
            f"the program in this checkout has no language model ({e})")
    tr, c = cell.traffic, cell.config
    if tr.get("toy_model"):
        cfg = cfglib.kanana2_toy(experts_held=(0, 4), heads_held=(0, 2),
                                 **tr["model_flags"])
    else:
        share = c["deployment"]
        keys = ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "n_shared_experts", "num_experts_per_tok",
                "routed_scaling_factor", "norm_topk_prob", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "rms_norm_eps", "first_k_dense_replace", "num_hidden_layers",
                "vocab_size")
        cfg = make(**{k: c[k] for k in keys},
                   rope_theta=float(c["rope_theta"]),
                   n_routed_experts=c["published"]["n_routed_experts"],
                   num_attention_heads=c["published"]["num_attention_heads"],
                   heads_held=tuple(share["heads_held"]),
                   experts_held=tuple(share["experts_held"]),
                   **tr["model_flags"])
    tc = cfglib.TrainConfig(
        name=cell.name, stage="tokens", lr=tr["lr"], wdecay=tr["wdecay"],
        num_steps=tr["num_steps"], batch_size=tr["batch"], seed=seed,
        precision=tr["precision"], prefetch_depth=tr["prefetch_depth"],
        validation=())
    return cfg, tc


def _token_file(ctx, cfg) -> str:
    from benchmarks import lm_synth

    docs = ctx.cell.traffic["documents"]
    return lm_synth.token_file(
        ctx.work_dir(), ctx.seed, docs["count"], cfg.vocab_size,
        docs["median"], docs["sigma"], docs["shortest"],
        min(docs["longest"], cfg.seq_len))


def _drain(loader, seconds: float) -> float:
    """Rows a second the loader gives with nothing consuming but this
    loop and the device idle: the host's ceiling for this cell."""
    it = loader.batches()
    try:
        next(it)  # workers started, queue primed
        t0 = time.perf_counter()
        n = 0
        while time.perf_counter() - t0 < seconds:
            n += next(it)["tokens"].shape[0]
        return n / (time.perf_counter() - t0)
    finally:
        it.close()


def _leaf(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def _rel(a, b) -> float:
    """|a - b| / |b| in the 2-norm, on the host in float64."""
    a, b = (np.asarray(x, np.float64) for x in (a, b))
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))


ADAM_B1 = 0.9  # train/optimizer.py make_optimizer


def _step_gradient_leaves(opt_state, grad_norm: float, clip: float, leaves):
    """The named leaves of the gradient the first step computed, from
    AdamW's first moment after it (module docstring)."""
    import jax

    adam = next(s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu") and hasattr(s, "nu"))
        if hasattr(s, "mu"))
    factor = min(1.0, clip / grad_norm) if clip and clip > 0 else 1.0
    return [np.asarray(jax.device_get(_leaf(adam.mu, p)), np.float64)
            / ((1.0 - ADAM_B1) * factor) for p in leaves]


def _reference(cfg, state, batch, leaves, dtype):
    """Loss, gradient norm and the named leaves of the plain reference,
    a sequence and a layer at a time."""
    import jax
    import jax.numpy as jnp
    import optax

    from dexiraft_tpu.interop import lm_reference

    loss, grads = lm_reference.blocked_loss_and_grads(
        state.params, batch, cfg, dtype=dtype)
    return (float(loss), float(optax.global_norm(
        jax.tree.map(lambda g: g.astype(jnp.float32), grads))),
        [np.asarray(jax.device_get(_leaf(grads, p)), np.float32)
         for p in leaves])


def _park(tree):
    """A device pytree to the host, its device buffers freed; returns
    what `_unpark` needs to put it back as it was."""
    import jax

    shardings = jax.tree.map(lambda x: x.sharding, tree)
    host = jax.device_get(tree)
    jax.tree.map(lambda x: x.delete(), tree)
    return host, shardings


def _unpark(parked):
    import jax

    host, shardings = parked
    return jax.block_until_ready(jax.device_put(host, shardings))


class ScopedTraceWindow(harness.TraceWindow):
    """`harness.TraceWindow` whose `stop()` also sums the window's device
    time by the program's named scopes (benchmarks/lm_scopes.py), before
    the raw trace is deleted: `scope_s` after `stop()`."""

    def __init__(self, ctx, compiled_text_fn):
        super().__init__(ctx)
        self._compiled_text_fn = compiled_text_fn
        self.scope_s = None

    def stop(self):
        import shutil

        import jax

        from benchmarks import lm_scopes, trace_reduce

        jax.profiler.stop_trace()
        self.active = False
        trace = trace_reduce.load_xplane(trace_reduce.find_xplane(self.dir))
        shutil.rmtree(self.dir, ignore_errors=True)
        if not trace["devices"]:
            if self.rehearsal:  # the CPU backend writes no device plane
                return None
            raise harness.BenchError("the trace holds no device plane with "
                                     f"an {trace_reduce.OPS_LINE!r} line")
        self.scope_s = lm_scopes.scope_seconds(
            trace["devices"], trace_reduce.window_of(trace),
            lm_scopes.instruction_scopes(self._compiled_text_fn()), SCOPES,
            aliases=ALIASES)
        return trace_reduce.summarize(trace)


def run(ctx: harness.Context) -> harness.Outcome:
    cfg, tc = _configs(ctx.cell, ctx.seed)  # before jax: the parent ends here

    import jax
    import jax.numpy as jnp

    from benchmarks import lm_counts
    from dexiraft_tpu.analysis.guards import RecompileWatch
    from dexiraft_tpu.data.loader import Loader
    from dexiraft_tpu.data.prefetch import prefetch_to_device
    from dexiraft_tpu.data.tokens import PackedTokens
    from dexiraft_tpu.parallel import layout
    from dexiraft_tpu.train.state import create_state, param_count
    from dexiraft_tpu.train.step import make_train_step

    tr = ctx.cell.traffic
    log = ctx.log
    counters = {}
    leaves = [tuple(p) for p in tr["check"]["leaves"]]
    tol = tr["check"]["tolerances"]

    with ctx.spans.span("init"):
        dataset = PackedTokens(_token_file(ctx, cfg), cfg.seq_len)
        mesh = layout.make_train_mesh(tc.batch_size, devices=ctx.devices)
        if mesh.size != len(ctx.devices):
            raise harness.BenchError(
                f"batch {tc.batch_size} spans {mesh.size} of the cell's "
                f"{len(ctx.devices)} chips")
        state = create_state(jax.random.PRNGKey(ctx.seed), cfg, tc)
        state = jax.block_until_ready(layout.shard_state(state, mesh))
        n_params = param_count(state.params)
        loader = Loader(dataset, tc.batch_size, seed=ctx.seed,
                        num_workers=tr["num_workers"], worker_mode="thread")
    pairs_a_row = float(np.mean([
        lm_counts.pairs_in_document(dataset.sample(i)["segment_ids"])
        for i in range(len(dataset))]))
    log(f"{ctx.cell.config_name}: {n_params} parameters "
        f"({n_params * 16 / 1e9:.2f} GB of state at 16 B each), mesh "
        f"{dict(mesh.shape)}, {tc.batch_size} rows of {cfg.seq_len} a step, "
        f"{cfg.num_hidden_layers} layers, heads {cfg.heads_held}, experts "
        f"{cfg.experts_held} of {cfg.n_routed_experts}, vocabulary "
        f"{cfg.vocab_size}, precision {tc.precision}, remat={cfg.remat}; "
        f"{len(dataset)} rows on disk, {dataset.fill:.4f} filled, "
        f"{pairs_a_row:.0f} in-document pairs a row, "
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
    metrics_log = []
    try:
        with mesh:
            first = next(batches)
            with ctx.spans.span("check"):
                # before step 1: the step donates the state it is given.
                # The reference donates nothing, so AdamW's moments (8 of
                # the 16 bytes a parameter) wait on the host meanwhile
                t0 = time.perf_counter()
                parked = _park(state.opt_state)
                t1 = time.perf_counter()
                ref_loss, ref_norm, ref_leaves = _reference(
                    cfg, state, first, leaves, jnp.float32)
                t2 = time.perf_counter()
                if os.environ.get("LM_CHECK_SECOND_READING"):
                    # the builder's second reading (PERF.md): the same
                    # reference in the precision below the cell's
                    low = _reference(cfg, state, first, leaves, jnp.bfloat16)
                    log("second reading, the reference in bf16 against "
                        "itself in fp32: " + _readings_line(
                            _readings(low[0], low[1], low[2], ref_loss,
                                      ref_norm, ref_leaves, leaves), tol))
                t3 = time.perf_counter()
                state = state.replace(opt_state=_unpark(parked))
                log(f"check: parking the optimizer state {t1 - t0:.1f} s, "
                    f"the reference {t2 - t1:.1f} s, putting the state "
                    f"back {time.perf_counter() - t3:.1f} s")

            def step(batch):
                nonlocal state
                state, metrics = step_fn(state, batch)
                metrics_log.append(metrics)

            with ctx.spans.span("warm"):
                # first call compiles or loads from the cache; the rest
                # settle the loader and give the pacer its first guess
                step(first)
                step1 = jax.device_get(metrics_log[0])
                sys_leaves = _step_gradient_leaves(
                    state.opt_state, float(step1["grad_norm"]), tc.clip,
                    leaves)
                t0 = time.perf_counter()
                for _ in range(tr["warm_steps"] - 1):
                    step(next(batches))
                jax.block_until_ready(metrics_log[-1]["loss"])
                step_guess = ((time.perf_counter() - t0)
                              / max(tr["warm_steps"] - 1, 1))

            readings = _readings(float(step1["loss"]),
                                 float(step1["grad_norm"]), sys_leaves,
                                 ref_loss, ref_norm, ref_leaves, leaves)
            check_ok = all(np.isfinite(v) and v <= tol[k]
                           for k, v in readings.items())
            log(f"step-1 loss {float(step1['loss']):.6f} (reference "
                f"{ref_loss:.6f}), gradient norm "
                f"{float(step1['grad_norm']):.6f} (reference {ref_norm:.6f}); "
                f"against their limits: {_readings_line(readings, tol)}: "
                f"{'ok' if check_ok else 'FAILED'}")

            def compiled_text():
                return step_fn.lower(state, first).compile().as_text()

            tw = ScopedTraceWindow(ctx, compiled_text)  # inert until started

            def run_steps(pacer, on_finish=lambda n: None):
                """Steps while the pacer says more, two steps of run-ahead:
                before step k is dispatched the loss of step k-2 is
                waited for. Then the rest is waited for."""
                base = len(metrics_log)

                def finish():
                    jax.block_until_ready(
                        metrics_log[base + pacer.finished]["loss"])
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
            window_from = len(metrics_log)
            pacer = harness.Pacer(ctx.seconds, step_guess)
            run_steps(pacer)
            steps = pacer.finished
            window = jax.device_get(metrics_log[window_from:])
            counters.update(
                window_steps=steps, window_s=pacer.elapsed,
                window_compiles=watch.drift,
                prefetch_stall_s=batches.stats.stall_s,
                prefetch_stalls=batches.stats.stalls,
                prefetch_batches=batches.stats.batches,
                loader_faults=loader.stats.faults,
                batch=tc.batch_size, params=n_params,
                seq_len=cfg.seq_len, pack_fill=dataset.fill,
                **{k: float(np.mean([m[k] for m in window])) for k in (
                    "tokens_real", "moe_slots_held", "moe_load_max",
                    "moe_load_mean")})
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

                tail_from = len(metrics_log)
                run_steps(harness.Pacer(0.0, step_guess,
                                        least=lead + traced + 2), on_finish)
                trace = tw.stop()
                counters["traced_units"] = traced
                tail = jax.device_get(
                    metrics_log[tail_from + lead:tail_from + lead + traced])
                counters["traced_slots_held"] = float(
                    np.mean([m["moe_slots_held"] for m in tail]))
                scope_s = dict(tw.scope_s or {})
                loose = scope_s.pop("unattributed_top", [])
                for scope, seconds in scope_s.items():
                    counters["scope_s:" + scope] = seconds / traced
                if scope_s:
                    log("device ms a step by scope: " + ", ".join(
                        f"{k} {v / traced * 1e3:.2f}"
                        for k, v in scope_s.items())
                        + "; unattributed, largest first: " + "; ".join(
                            f"{k} {v / traced * 1e3:.2f}" for k, v in loose))
                counters.update(harness.compiled_memory(
                    step_fn.lower(state, first)))
    finally:
        batches.close()

    every = jax.device_get(metrics_log)
    bad = sum(1 for m in every if not (np.isfinite(float(m["loss"]))
                                       and bool(m["state_finite"])))
    dropped = int(sum(int(m["moe_dropped_slots"]) for m in every))
    counters["moe_dropped_slots"] = dropped
    if ctx.trace:
        parts = lm_counts.step_flops(
            cfg, counters["tokens_real"], counters["moe_slots_held"],
            pairs_a_row * tc.batch_size)
        counters["flops_per_unit"] = parts["total"]
        log("FLOPs a step needs, by part: " + ", ".join(
            f"{k} {v:.3e}" for k, v in parts.items()))
        counters["experts_layers"] = (cfg.num_hidden_layers
                                      - cfg.first_k_dense_replace)
        counters["remat"] = float(cfg.remat)
        # the readers of the grouped products' roofline share need the
        # configuration's widths: kept as counters, not re-read
        counters.update(hidden_size=cfg.hidden_size,
                        moe_intermediate_size=cfg.moe_intermediate_size,
                        experts_held=cfg.experts_held[1])
    return harness.Outcome(
        attempted=attempted, failed=bad + (attempted - steps),
        correct=(check_ok and bad == 0 and dropped == 0
                 and steps == attempted),
        end_to_end={"train_samples_per_s": rate},
        window_start=pacer.start,
        counters=counters, trace=trace)


def _readings(loss, norm, grads, ref_loss, ref_norm, ref_grads, leaves):
    out = {"loss": abs(loss - ref_loss) / abs(ref_loss),
           "grad_norm": abs(norm - ref_norm) / ref_norm}
    for path, g, r in zip(leaves, grads, ref_grads):
        out["/".join(str(p) for p in path)] = _rel(g, r)
    return out


def _readings_line(readings, tol) -> str:
    return ", ".join(f"{k} {v:.3g} (limit {tol.get(k)})"
                     for k, v in readings.items())


def compile_for(cell, topo, report) -> None:
    """compile_check.py: this cell's step, and the program its check
    differentiates, from shapes, on a mesh over the described
    topology's chips."""
    import jax
    import numpy as np

    from dexiraft_tpu.parallel import layout
    from dexiraft_tpu.train.state import create_state
    from dexiraft_tpu.train.step import make_train_step

    cfg, tc = _configs(cell, 0)
    mesh = layout.make_train_mesh(tc.batch_size,
                                  devices=topo.devices[:cell.chips])
    repl = layout.replicated_sharding(mesh)
    data = layout.batch_input_sharding(mesh)
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=repl),
        jax.eval_shape(lambda: create_state(jax.random.PRNGKey(0), cfg, tc)))
    batch = {k: jax.ShapeDtypeStruct((tc.batch_size, cfg.seq_len), np.int32,
                                     sharding=data)
             for k in ("tokens", "positions", "segment_ids")}
    with mesh:
        report(f"{cell.name} step ({tc.batch_size} rows of {cfg.seq_len} on "
               f"mesh {dict(mesh.shape)}, {cfg.num_hidden_layers} layers, "
               f"precision {tc.precision}, remat={cfg.remat})",
               make_train_step(cfg, tc, mesh=mesh).lower(state, batch))
