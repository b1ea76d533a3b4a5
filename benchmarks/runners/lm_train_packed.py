"""Traffic kind `lm_train_packed`: a language model of `models/lm` through
the training loop as `train_cli` wires it, built from the cell's
configuration file and not from a model's name.

The configuration file's `program` group names the constructor in
`dexiraft_tpu.config` and the one of the CPU rehearsal, the file's keys
the constructor takes as they stand (`keys`), those it takes at the
model's whole count from `published` (`whole_counts`: the router and the
head grouping keep their published widths) and from `deployment`
(`share`), the `jax.named_scope`s whose device time is summed, and the
module under benchmarks/ that counts the architecture's FLOPs
(`counts`: `layers_by_kind(cfg)`, `pairs_by_kind(cfg, rows)` and
`step_flops(cfg, tokens, slots, pairs)`). The next language
configuration brings a file, not a runner.

The loop, the window, the pacer, the check and the traced tail are
`lm_train_steps`'s (its docstring says what each measures); what names
no model there is taken from it through `harness.load_runner`.
`train_samples_per_s` is rows x steps completed over the time from the
first dispatch of the window to `block_until_ready` on the last step's
loss; a sample is one row of `seq_len` positions.

`correct`: on the timed path's first batch and weights, the first step's
own loss, `grad_norm` and the named gradient leaves (read back from
AdamW's first moment after the step) against the plain reference
(interop/lm_reference.py, fp32, `highest`), walked a sequence, a layer
and `check.reference_block` rows at a time before the step runs; no slot
dropped; losses and state finite; steps attempted = completed.
`LM_CHECK_SECOND_READING=1` takes the builder's other readings (PERF.md)
through the same comparison and logs each one's verdict, which has to be
FAILED: the reference wholly in bf16 against itself in fp32, and the
program against the reference with one mechanism taken away, for each of
`check.controls` (a name and the fields of the configuration the
reference is given instead).

Parameters (traffic file): `batch`, `lr`, `wdecay`, `num_steps`,
`documents`, `num_workers`, `prefetch_depth`, `model_flags`, `check`
(`leaves`, `tolerances`, `reference_block`, `controls`), `warm_steps`,
`traced_steps`, `loader_drain_s`, and for the rehearsal `toy_model`.
"""

from __future__ import annotations

import dataclasses
import importlib
import os
import time

import numpy as np

from benchmarks import harness

# this runner's own instance of the module: `run` hands its
# `ScopedTraceWindow` the configuration's scopes through the two names it
# reads them from
_steps = harness.load_runner("lm_train_steps")


def _configs(cell, seed: int):
    """(the model's configuration, TrainConfig) of the cell."""
    prog, c, tr = cell.config["program"], cell.config, cell.traffic
    try:
        import dexiraft_tpu.config as cfglib
        make = getattr(cfglib, prog["toy_constructor"] if tr.get("toy_model")
                       else prog["constructor"])
    except (ImportError, AttributeError) as e:
        raise harness.BenchError(
            f"the program in this checkout cannot build {cell.config_name} "
            f"({e})")
    if tr.get("toy_model"):
        kw = {k: tuple(v) for k, v in prog["toy_share"].items()}
    else:
        kw = {k: c[k] for k in prog["keys"]}
        kw.update({k: c["published"][k] for k in prog["whole_counts"]})
        kw.update({k: tuple(c["deployment"][k]) for k in prog["share"]})
    cfg = make(**{k: tuple(v) if isinstance(v, list) else v
                  for k, v in kw.items()}, **tr["model_flags"])
    tc = cfglib.TrainConfig(
        name=cell.name, stage="tokens", lr=tr["lr"], wdecay=tr["wdecay"],
        num_steps=tr["num_steps"], batch_size=tr["batch"], seed=seed,
        precision=tr["precision"], prefetch_depth=tr["prefetch_depth"],
        validation=())
    return cfg, tc


def _counts(cell):
    return importlib.import_module(
        "benchmarks." + cell.config["program"]["counts"])


def _reference(cfg, params, batch, leaves, dtype, block):
    """Loss, gradient norm, the named leaves and each part's own gradient
    norm (a layer, the embedding, the head) of the plain reference, a
    sequence, a layer and `block` rows at a time."""
    import jax
    import jax.numpy as jnp
    import optax

    from dexiraft_tpu.interop import lm_reference

    loss, grads = lm_reference.blocked_loss_and_grads(
        params, batch, cfg, dtype=dtype, block=block)
    grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
    return (float(loss), float(optax.global_norm(grads)),
            [np.asarray(jax.device_get(_steps._leaf(grads, p)), np.float32)
             for p in leaves],
            {part: float(optax.global_norm(g)) for part, g in grads.items()})


def _within(readings, tol) -> bool:
    return all(np.isfinite(v) and v <= tol[k] for k, v in readings.items())


def _control(log, what: str, readings, tol) -> None:
    """A control's readings through the check's own comparison."""
    over = [k for k, v in readings.items()
            if not (np.isfinite(v) and v <= tol[k])]
    log(f"control, {what}: {_steps._readings_line(readings, tol)}: "
        + (f"FAILED by {', '.join(over)}" if over
           else "ok (the check cannot tell it from the program)"))


def run(ctx: harness.Context) -> harness.Outcome:
    cfg, tc = _configs(ctx.cell, ctx.seed)  # before any work: the parent ends here
    counts = _counts(ctx.cell)

    import jax
    import jax.numpy as jnp

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
    block = tr["check"].get("reference_block")
    program = ctx.cell.config["program"]
    _steps.SCOPES = tuple(program["scopes"])
    _steps.ALIASES = dict(program.get("scope_aliases", {}))

    with ctx.spans.span("init"):
        dataset = PackedTokens(_steps._token_file(ctx, cfg), cfg.seq_len)
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
    rows_pairs = [counts.pairs_by_kind(
        cfg, dataset.sample(i)["segment_ids"][None])
        for i in range(len(dataset))]
    pairs_a_row = {k: float(np.mean([p[k] for p in rows_pairs]))
                   for k in rows_pairs[0]}
    log(f"{ctx.cell.config_name}: {n_params} parameters "
        f"({n_params * 16 / 1e9:.2f} GB of state at 16 B each), mesh "
        f"{dict(mesh.shape)}, {tc.batch_size} rows of {cfg.seq_len} a step, "
        f"{cfg.num_hidden_layers} layers, heads {cfg.heads_held}, experts "
        f"{cfg.experts_held} of {cfg.n_routed_experts}, vocabulary "
        f"{cfg.vocab_size}, precision {tc.precision}, remat={cfg.remat}; "
        f"{len(dataset)} rows on disk, {dataset.fill:.4f} filled, pairs a "
        f"row and layer {pairs_a_row}, {tr['num_workers']} loader threads")

    if ctx.trace:
        # a host measurement, taken in the traced run only so that the
        # timed runs' set-up stays what the cell's traffic needs
        with ctx.spans.span("loader_drain"):
            counters["loader_samples_per_s"] = _steps._drain(
                loader, tr["loader_drain_s"])

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
                parked = _steps._park(state.opt_state)
                t1 = time.perf_counter()
                ref_loss, ref_norm, ref_leaves, ref_parts = _reference(
                    cfg, state.params, first, leaves, jnp.float32, block)
                t2 = time.perf_counter()
                controls = {}
                if os.environ.get("LM_CHECK_SECOND_READING"):
                    low = _reference(cfg, state.params, first, leaves,
                                     jnp.bfloat16, block)
                    _control(log, "the reference in bf16 against itself in "
                             "fp32", _steps._readings(
                                 *low[:3], ref_loss, ref_norm, ref_leaves,
                                 leaves), tol)
                    # what the step's norm (the clip's factor) would read
                    # had it left one part of the model out
                    short = {part: 1 - np.sqrt(max(
                        1 - (n / ref_norm) ** 2, 0.0))
                        for part, n in ref_parts.items()}
                    log("a gradient norm without one part reads: "
                        + ", ".join(f"{k} {v:.3g}" for k, v in short.items()))
                    _control(log, "the reference's gradient norm without "
                             "the decoder layer that adds least to it",
                             {"grad_norm": min(
                                 v for k, v in short.items()
                                 if k.startswith("layers_"))}, tol)
                    for name, fault in tr["check"].get("controls",
                                                       {}).items():
                        controls[name] = _reference(
                            dataclasses.replace(cfg, **fault), state.params,
                            first, leaves, jnp.float32, block)[:3]
                t3 = time.perf_counter()
                state = state.replace(opt_state=_steps._unpark(parked))
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
                sys_leaves = _steps._step_gradient_leaves(
                    state.opt_state, float(step1["grad_norm"]), tc.clip,
                    leaves)
                t0 = time.perf_counter()
                for _ in range(tr["warm_steps"] - 1):
                    step(next(batches))
                jax.block_until_ready(metrics_log[-1]["loss"])
                step_guess = ((time.perf_counter() - t0)
                              / max(tr["warm_steps"] - 1, 1))

            mine = (float(step1["loss"]), float(step1["grad_norm"]),
                    sys_leaves)
            readings = _steps._readings(*mine, ref_loss, ref_norm, ref_leaves,
                                        leaves)
            check_ok = _within(readings, tol)
            log(f"step-1 loss {mine[0]:.6f} (reference {ref_loss:.6f}), "
                f"gradient norm {mine[1]:.6f} (reference {ref_norm:.6f}); "
                f"against their limits: "
                f"{_steps._readings_line(readings, tol)}: "
                f"{'ok' if check_ok else 'FAILED'}")
            for name, theirs in controls.items():
                _control(log, f"the program against the reference with "
                         f"{tr['check']['controls'][name]}",
                         _steps._readings(*mine, *theirs, leaves), tol)

            def compiled_text():
                return step_fn.lower(state, first).compile().as_text()

            tw = _steps.ScopedTraceWindow(ctx, compiled_text)  # inert until started

            def run_steps(pacer, on_finish=lambda n: None,
                          on_batch=lambda batch: None):
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
                    on_batch(batch)
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
                **{k: float(np.mean([m[k] for m in window]))
                   for k in window[0]
                   if k.startswith(("moe_", "attn_block_pairs_"))
                   or k == "tokens_real"})
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
                tail_rows = []  # each tail batch's rows, on the host

                def on_finish(n):
                    if n == lead:
                        # a TraceAnnotation starts when it is made
                        span.append(tw.span("window"))
                        span[0].__enter__()
                    elif n == lead + traced:
                        span[0].__exit__(None, None, None)

                tail_from = len(metrics_log)
                run_steps(harness.Pacer(0.0, step_guess,
                                        least=lead + traced + 2), on_finish,
                          lambda b: tail_rows.append(
                              jax.device_get(b["segment_ids"])))
                trace = tw.stop()
                counters["traced_units"] = traced
                tail = jax.device_get(
                    metrics_log[tail_from + lead:tail_from + lead + traced])
                counters["traced_slots_held"] = float(
                    np.mean([m["moe_slots_held"] for m in tail]))
                # the pairs the traced steps' own rows need, exactly: the
                # attention kernels' roofline share is of these steps
                traced_pairs = [counts.pairs_by_kind(cfg, r)
                                for r in tail_rows[lead:lead + traced]]
                for kind in traced_pairs[0]:
                    counters["traced_pairs_" + kind] = float(
                        np.mean([p[kind] for p in traced_pairs]))
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
        parts = counts.step_flops(
            cfg, counters["tokens_real"], counters["moe_slots_held"],
            {k: v * tc.batch_size for k, v in pairs_a_row.items()})
        counters["flops_per_unit"] = parts["total"]
        log("FLOPs a step needs, by part: " + ", ".join(
            f"{k} {v:.3e}" for k, v in parts.items()))
        counters["experts_layers"] = (cfg.num_hidden_layers
                                      - cfg.first_k_dense_replace)
        counters["remat"] = float(cfg.remat)
        # the readers of the roofline shares need the configuration's
        # widths: kept as counters, not re-read
        counters.update(hidden_size=cfg.hidden_size,
                        moe_intermediate_size=cfg.moe_intermediate_size,
                        experts_held=cfg.experts_held[1],
                        attn_heads_held=cfg.heads_held[1],
                        attn_kv_heads_held=getattr(
                            cfg, "kv_heads_held", cfg.heads_held)[1],
                        attn_head_dim=cfg.qk_head_dim,
                        **{"attn_layers_" + kind: n for kind, n in
                           counts.layers_by_kind(cfg).items()})
    return harness.Outcome(
        attempted=attempted, failed=bad + (attempted - steps),
        correct=(check_ok and bad == 0 and dropped == 0
                 and steps == attempted),
        end_to_end={"train_samples_per_s": rate},
        window_start=pacer.start,
        counters=counters, trace=trace)


def compile_for(cell, topo, report) -> None:
    """compile_check.py: this cell's step from shapes, on a mesh over the
    described topology's chips, and the largest program of its check (the
    reference's gradient of the last layer, `check.reference_block` rows
    at a time). `document_attention` picks its path from the backend,
    which here is the CPU: it is handed the kernel path it takes on the
    chip."""
    from unittest import mock

    import jax
    import jax.numpy as jnp

    from dexiraft_tpu.interop import lm_reference
    from dexiraft_tpu.ops import lm_attention
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

    def on_the_chip(q, k, v, segment_ids, *, scale, block, window=None):
        if lm_attention.kernel_blocks(q.shape[1], q.shape[-1], v.shape[-1]):
            return lm_attention.flash_document_attention(
                q, k, v, segment_ids, scale=scale, window=window)
        return lm_attention.xla_document_attention(
            q, k, v, segment_ids, scale=scale, block=block, window=window)

    with mesh, mock.patch("dexiraft_tpu.models.lm.attention."
                          "document_attention", on_the_chip):
        report(f"{cell.name} step ({tc.batch_size} rows of {cfg.seq_len} on "
               f"mesh {dict(mesh.shape)}, {cfg.num_hidden_layers} layers, "
               f"precision {tc.precision}, remat={cfg.remat})",
               make_train_step(cfg, tc, mesh=mesh).lower(state, batch))

    last = cfg.num_hidden_layers - 1
    run = lm_reference._layer_of(cfg, last,
                                 cell.traffic["check"].get("reference_block"))

    def layer_gradient(p, x, pos, seg, dy):
        _, pull = jax.vjp(lambda p, x: run(p, x, pos, seg), p, x)
        return pull(dy)

    one = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=repl)
    x = one((cfg.seq_len, cfg.hidden_size), jnp.float32)
    row = one((cfg.seq_len,), np.int32)
    report(f"{cell.name} check: the reference's gradient of layer {last}, "
           f"one row of {cfg.seq_len} in fp32",
           jax.jit(layer_gradient).lower(
               state.params[f"layers_{last}"], x, row, row, x))
