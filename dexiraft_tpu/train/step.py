"""Jitted train / eval steps, single-chip or sharded over a device mesh.

The seam. `make_train_step(cfg, tc)` asks `train/family.py` for the
model family of `cfg` and takes from it three functions: `init(rng)`
(through `create_state`), `loss_fn(params, batch_stats, batch, rng) ->
(loss, (metrics, new_stats))`, `augment(batch, rng)` and
`grad_metrics(grads)`. What is in this file after that is the same for
every family: value-and-grad, the
microbatch scan, clip + AdamW on the OneCycle schedule from fp32
masters, the `all_finite` verdict, donation, the mesh's shardings and
the fsdp fences. RAFT v1-v5 (a `RAFTConfig`) is the first family, the
language model of models/lm (an `LMConfig`) the second; the eval, encode
and refine steps below are RAFT's alone.

The reference's inner loop (train.py:163-186: forward, sequence loss,
backward, unscale/clip/step, scheduler) becomes ONE jitted function —
the 12-iteration refinement loop, loss, and optimizer update all compile
into a single on-device graph. Data parallelism is declarative: the batch
is sharded over the mesh's 'data' axis, the state is replicated, and the
SPMD partitioner inserts the gradient all-reduce over ICI (the TPU-native
replacement for DataParallel's NCCL gather, SURVEY.md §2.7).

On an fsdp mesh (parallel/layout.make_train_mesh(..., fsdp=...)) the
state is additionally STORED sharded: params and Adam moments live
split over the 'fsdp' axis between steps (per-leaf layout in
layout.state_sharding). How the COMPUTE relates to that storage is the
``compute_sharding`` axis:

  * "fence" (default) — the step gathers the state to replicated at
    entry and re-shards at exit (the fence pattern, docs/perf.md
    "Sharded state (fsdp)"). Compute inside the fences is byte-for-byte
    the replicated program; what changes is the persistent per-device
    HBM (state at ~1/fsdp) and the checkpoint path (per-shard orbax
    I/O). Works for every variant/config.
  * "halo" — the heavy spatial compute itself shards: a shard_map over
    the mesh's (data, seq) axes gives each device a contiguous
    image-row slab, convolutions exchange receptive-field boundary rows
    with lax.ppermute (parallel/halo.py), and params stay fsdp-sharded
    THROUGH compute — each block all-gathers its weights immediately
    before running and drops them after (gather->use->drop inside
    jax.checkpoint), so peak gathered-params HBM is one block. The
    optimizer update runs OUTSIDE the shard_map on the sharded grads
    (elementwise; GSPMD partitions it over fsdp for free), so no
    fences exist anywhere in this mode. v1/fp32-only support matrix:
    halo.check_halo_support refuses everything else with actionable
    errors.

BatchNorm note: under a sharded batch the normalizing statistics are
GLOBAL across chips (XLA inserts the cross-chip mean) — i.e. sync-BN.
The reference's DataParallel computes per-device stats; sync-BN is the
strictly better-behaved variant, so we adopt it deliberately.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from dexiraft_tpu.config import RAFTConfig, TrainConfig
from dexiraft_tpu.models.raft import RAFT
from dexiraft_tpu.parallel import halo
from dexiraft_tpu.parallel.layout import (
    LAYOUT,
    batch_input_sharding,
    batch_sharding,
    replicated_sharding,
    state_sharding,
    variables_sharding,
)
from dexiraft_tpu.train.family import family_of, thread_remat
from dexiraft_tpu.train.optimizer import training_schedule
from dexiraft_tpu.train.state import TrainState, create_state, make_optimizer_from

Batch = Dict[str, jax.Array]  # image1, image2, flow, valid [, edges1, edges2]


def all_finite(*trees: Any) -> jax.Array:
    """Scalar bool: every inexact leaf of every tree is finite.

    The checkpoint gate's poison detector. The guard's loss check alone
    has a one-step blind spot: value_and_grad computes the loss from the
    PRE-update params, but the checkpoint saves the POST-update state —
    a step whose update introduces non-finite values passes the loss
    check and the poisoned state reaches disk. Emitting this signal from
    the step itself (computed on the NEW state) closes that gap; it is
    one elementwise pass over the state, noise next to the backward.
    """
    ok = jnp.bool_(True)
    for tree in trees:
        for leaf in jax.tree_util.tree_leaves(tree):
            leaf = jnp.asarray(leaf)
            if jnp.issubdtype(leaf.dtype, jnp.inexact):
                ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(leaf)))
    return ok


def make_train_step(
    cfg: RAFTConfig,
    tc: TrainConfig,
    mesh: Optional[Mesh] = None,
    compute_sharding: str = "fence",
) -> Callable[[TrainState, Batch], Tuple[TrainState, Dict[str, jax.Array]]]:
    """Build the jitted train step. With a mesh, in/out shardings pin the
    batch to the 'data' axis (rows additionally over 'seq' on 2-D
    meshes) and everything else replicated/fsdp-stored.
    ``compute_sharding`` picks how fsdp storage meets compute: "fence"
    gathers at entry / re-shards at exit; "halo" shard_maps the spatial
    compute with explicit halo exchange and keeps params sharded
    throughout (module docstring has the full contrast)."""
    if tc.precision not in ("fp32", "bf16"):
        raise ValueError(f"precision must be fp32|bf16, got {tc.precision!r}")
    if tc.accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {tc.accum_steps}")
    if compute_sharding not in ("fence", "halo"):
        raise ValueError(f"compute_sharding must be fence|halo, "
                         f"got {compute_sharding!r}")
    if tc.remat not in ("none", "per_iter", "dots_saveable"):
        raise ValueError(f"remat must be none|per_iter|dots_saveable, "
                         f"got {tc.remat!r}")
    if compute_sharding == "halo":
        if not isinstance(cfg, RAFTConfig):
            raise ValueError("compute_sharding='halo' shards image rows: "
                             "RAFT only")
        return _make_halo_train_step(thread_remat(cfg, tc), tc, mesh)
    # the family threads tc's remat and precision axes into its own
    # model config (train/family.py)
    family = family_of(cfg, tc)
    tx = make_optimizer_from(tc)
    schedule = training_schedule(tc.lr, tc.num_steps)

    grad_fn = jax.value_and_grad(family.loss_fn, has_aux=True)

    # fsdp fence shardings, filled in below when the mesh has the axis;
    # None on every other path so the step body compiles unchanged
    fence_repl = None

    def step(state: TrainState, batch: Batch):
        if fence_repl is not None:
            # ENTRY FENCE (fsdp): the state arrives in its storage
            # layout (params/opt_state sharded over 'fsdp' per
            # layout.state_sharding); gather it to replicated HERE so
            # the partitioner never sees an fsdp-sharded tensor inside
            # the model — GSPMD miscompiles feature-dim-partitioned
            # convolutions on this backend (the conv-of-concat repro in
            # tests/test_zzzfsdp.py), so fsdp is a storage axis only.
            # Everything below computes exactly the replicated program.
            state = jax.lax.with_sharding_constraint(state, fence_repl)
        rng, noise_rng, dropout_rng = jax.random.split(state.rng, 3)
        batch = family.augment(batch, noise_rng)

        accum = tc.accum_steps
        if accum > 1:
            # gradient accumulation: scan over microbatches INSIDE the
            # jitted step, so a large effective batch fits one chip and
            # the accumulation loop compiles once. The batch's leading
            # dim is (accum * micro); per-microbatch mean grads average
            # to exactly the full-batch mean grad FOR BN-FREE VARIANTS
            # (small RAFT — pinned by test). With BatchNorm in train
            # mode each microbatch normalizes over micro samples, not
            # the full batch (the usual accumulation caveat, same as
            # every framework's; equivalent to training at the smaller
            # BN batch). Running stats thread sequentially through the
            # scan carry, like sequential steps would
            b = jax.tree.leaves(batch)[0].shape[0]
            if b % accum:
                raise ValueError(
                    f"batch {b} not divisible by accum_steps {accum}")
            if mesh is not None:
                # each microbatch must still split over the data axis,
                # or GSPMD reshards / idles chips on EVERY scan
                # iteration — the opposite of what accumulation buys
                n_data = LAYOUT.data_size(mesh)
                if (b // accum) % n_data:
                    raise ValueError(
                        f"microbatch {b // accum} (batch {b} / accum "
                        f"{accum}) not divisible by the mesh's "
                        f"{n_data}-way data axis — every chip must "
                        f"keep a full shard per scan iteration")
            micro = jax.tree.map(
                lambda x: x.reshape((accum, b // accum) + x.shape[1:]),
                batch)
            rngs = jax.random.split(dropout_rng, accum)

            def body(carry, xs):
                stats, acc = carry
                mb, r = xs
                (mb_loss, (mb_metrics, stats)), grads = grad_fn(
                    state.params, stats, mb, r)
                acc = jax.tree.map(jnp.add, acc, grads)
                return (stats, acc), (mb_loss, mb_metrics)

            zeros = jax.tree.map(jnp.zeros_like, state.params)
            (batch_stats, gsum), (losses, seq_metrics) = jax.lax.scan(
                body, (state.batch_stats, zeros), (micro, rngs))
            grads = jax.tree.map(lambda g: g / accum, gsum)
            loss = jnp.mean(losses)
            metrics = jax.tree.map(jnp.mean, seq_metrics)
        else:
            (loss, (metrics, batch_stats)), grads = grad_fn(
                state.params, state.batch_stats, batch, dropout_rng)

        with jax.named_scope("optimizer"):
            # what a family wants to read of the gradients before the
            # clip (RAFT: nothing, so nothing is traced for it)
            metrics = dict(metrics, **family.grad_metrics(grads))
            updates, opt_state = tx.update(grads, state.opt_state,
                                           state.params)
            params = jax.tree.map(lambda p, u: p + u, state.params, updates)
            # in the scope too: XLA fuses the verdict's pass over the new
            # state with the update that writes it
            state_finite = all_finite(params, batch_stats, opt_state)

        new_state = TrainState(
            step=state.step + 1,
            params=params,
            batch_stats=batch_stats,
            opt_state=opt_state,
            rng=rng,
        )
        metrics = dict(metrics, loss=loss, lr=schedule(state.step),
                       state_finite=state_finite)
        if fence_repl is not None:
            # EXIT FENCE (fsdp): pin the finished state replicated so
            # sharding propagation from the sharded out_shardings below
            # stops at this boundary — the re-shard back to storage
            # layout is a pure slice at the jit output, never a
            # different partitioning of the compute above.
            new_state = jax.lax.with_sharding_constraint(
                new_state, fence_repl)
        return new_state, metrics

    if mesh is None:
        return jax.jit(step, donate_argnums=0)

    repl = replicated_sharding(mesh)
    # 2-D (data, seq) mesh: image rows additionally shard over 'seq' —
    # GSPMD partitions the convs (halo exchange) and the correlation
    # volume's query axis (context parallelism); every batch leaf is >=3D
    # (B, H, ...), so one spec covers the dict. batch_input_sharding is
    # the same helper the device prefetcher puts with, so prefetched
    # batches arrive already in this layout
    data = batch_input_sharding(mesh)
    state_sh = repl
    if LAYOUT.has_fsdp(mesh):
        # fsdp mesh: pin the state's STORAGE layout per leaf — params
        # and Adam moments sharded over 'fsdp' (layout.param_leaf_spec
        # decides dim + divisibility fallback centrally), the rest
        # replicated. The step body gathers at entry and re-pins at
        # exit (fences above); in/out match, so donation still aliases
        # shard-for-shard. The abstract eval_shape costs one host-side
        # trace of create_state, only on fsdp meshes.
        abstract = jax.eval_shape(
            lambda: create_state(jax.random.PRNGKey(0), cfg, tc))
        state_sh = state_sharding(mesh, abstract)
        fence_repl = jax.tree.map(lambda _: repl, abstract)
    return jax.jit(
        step,
        in_shardings=(state_sh, data),
        out_shardings=(state_sh, repl),
        donate_argnums=0,
    )


def _make_halo_train_step(
    cfg: RAFTConfig,
    tc: TrainConfig,
    mesh: Optional[Mesh],
) -> Callable[[TrainState, Batch], Tuple[TrainState, Dict[str, jax.Array]]]:
    """The compute_sharding="halo" train step (make_train_step
    dispatches here): the shard_map'd gradient fn from
    parallel/halo.py plus the optimizer update OUTSIDE the shard_map.

    Grads leave the shard_map already in the params' fsdp storage
    layout, so the Adam update (elementwise per leaf; the global-norm
    clip reduces over shards, which GSPMD handles) never materializes a
    replicated param tree — persistent AND peak optimizer HBM stay at
    ~1/fsdp. batch_stats pass through unchanged: halo trains with
    instance norm / frozen BN only (check_halo_support), so there are
    no running-stat updates to thread. The rng splits once per step to
    keep the TrainState contract (fresh carry each step) even though
    the halo forward draws no randomness (dropout/noise refused)."""
    halo.check_halo_support(cfg, tc, mesh)
    tx = make_optimizer_from(tc)
    schedule = training_schedule(tc.lr, tc.num_steps)
    abstract = jax.eval_shape(
        lambda: create_state(jax.random.PRNGKey(0), cfg, tc))
    halo_fn = halo.make_halo_train_fn(cfg, tc, mesh, abstract.params,
                                      remat_mode=tc.remat)
    state_sh = state_sharding(mesh, abstract)
    repl = replicated_sharding(mesh)
    data = batch_input_sharding(mesh)  # P('data', 'seq') on seq meshes

    def step(state: TrainState, batch: Batch):
        rng, _ = jax.random.split(state.rng)
        loss, metrics, grads = halo_fn(
            state.params, state.batch_stats, batch["image1"],
            batch["image2"], batch["flow"], batch["valid"])
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = jax.tree.map(lambda p, u: p + u, state.params, updates)
        new_state = TrainState(
            step=state.step + 1,
            params=params,
            batch_stats=state.batch_stats,
            opt_state=opt_state,
            rng=rng,
        )
        metrics = dict(metrics, loss=loss, lr=schedule(state.step),
                       state_finite=all_finite(params, state.batch_stats,
                                               opt_state))
        return new_state, metrics

    return jax.jit(
        step,
        in_shardings=(state_sh, data),
        out_shardings=(state_sh, repl),
        donate_argnums=0,
    )


def make_eval_step(
    cfg: RAFTConfig,
    iters: int = 24,
    mesh: Optional[Mesh] = None,
    compute_sharding: str = "fence",
    adaptive: bool = False,
) -> Callable[..., Tuple[jax.Array, ...]]:
    """Jitted test-mode forward: (flow_low, flow_up) like core/raft.py:194-197.

    Batched NHWC inputs throughout — the serving engine
    (dexiraft_tpu.serve) feeds bucket-padded batches straight in.
    flow_init enables warm-start inference (evaluate.py:40-44); a
    flow_init row of zeros equals no warm start (RAFT adds it to
    coords0), so one batch can carry PER-ITEM warm starts — warm rows
    next to cold zero rows — which is how the batched Sintel submission
    threads each sequence's carry through a shared batch.

    With a mesh the step pins its shardings like the train step does:
    batch args over the 'data' axis, variables replicated, outputs left
    sharded (the engine's per-item host fetch assembles them; no
    all-gather on device). Pinned shardings mean the mesh-path step must
    be called POSITIONALLY with all six arguments (jit rejects kwargs
    when in_shardings is set) — mesh=None keeps the kwarg-friendly
    reference behavior.

    ``compute_sharding="halo"`` swaps in the shard_map'd row-slab
    forward (parallel/halo.make_halo_eval_fn): image rows shard over
    the mesh's 'seq' axis and params stay in fsdp storage layout
    through compute. That step's signature differs — (variables,
    image1, image2, flow_init), positional, no edge arguments (v1
    only) and flow_init always materialized (zeros = cold start) —
    because its in_shardings pin the halo contract, not the engine's.

    ``adaptive=True`` swaps the fixed scan for the convergence-gated
    while_loop (RAFT adaptive=True): the step grows a trailing
    ``iter_budget`` positional — a TRACED int32 scalar, so ONE compiled
    executable per bucket serves every budget — and returns
    (flow_low, flow_up, iters_used[B], final_delta[B]). The (B,)
    outputs pin batch-only shardings on a mesh (they have no spatial
    dims for a seq axis to split).
    """
    if compute_sharding not in ("fence", "halo"):
        raise ValueError(f"compute_sharding must be fence|halo, "
                         f"got {compute_sharding!r}")
    model = RAFT(cfg)
    if compute_sharding == "halo":
        if adaptive:
            raise ValueError(
                "adaptive=True is not supported with "
                "compute_sharding='halo' (the shard_map'd row-slab "
                "forward drives the fixed-iteration halo loop)")
        return _make_halo_eval_step(cfg, iters, mesh, model)

    def step(
        variables: Dict[str, Any],
        image1: jax.Array,
        image2: jax.Array,
        edges1: Optional[jax.Array] = None,
        edges2: Optional[jax.Array] = None,
        flow_init: Optional[jax.Array] = None,
        iter_budget: Optional[jax.Array] = None,
    ):
        kwargs: Dict[str, Any] = {}
        if edges1 is not None:
            kwargs = dict(edges1=edges1, edges2=edges2)
        if adaptive:
            kwargs.update(adaptive=True, iter_budget=iter_budget)
        return model.apply(
            variables,
            image1,
            image2,
            iters=iters,
            flow_init=flow_init,
            train=False,
            test_mode=True,
            **kwargs,
        )

    if mesh is None:
        return jax.jit(step)
    repl = replicated_sharding(mesh)
    data = batch_input_sharding(mesh)
    vec = batch_sharding(mesh)  # (B,) outputs: batch axis only
    # one `data` leaf per batched positional (images, edges, flow_init);
    # a None optional consumes its sharding entry as an empty pytree.
    # The adaptive budget scalar replicates like every other scalar.
    if adaptive:
        return jax.jit(
            step,
            in_shardings=(repl, data, data, data, data, data, repl),
            out_shardings=(data, data, vec, vec),
        )
    return jax.jit(
        step,
        in_shardings=(repl, data, data, data, data, data),
        out_shardings=(data, data),
    )


def _make_halo_eval_step(
    cfg: RAFTConfig,
    iters: int,
    mesh: Optional[Mesh],
    model: RAFT,
) -> Callable[..., Tuple[jax.Array, jax.Array]]:
    """The compute_sharding="halo" eval step (make_eval_step dispatches
    here): (variables, image1, image2, flow_init) -> (flow_low,
    flow_up), all batch leaves row-sharded over (data, seq), variables
    pinned to their STORAGE layout (params per param_leaf_spec,
    batch_stats replicated — layout.variables_sharding), so fsdp-stored
    checkpoints evaluate without a host-side gather. The abstract
    model.init costs one host-side trace; its variables tree is what
    the sharding pins resolve against, and it matches any checkpoint of
    the same config by construction."""
    if mesh is None or not LAYOUT.has_seq(mesh):
        raise ValueError(
            "compute_sharding='halo' needs a mesh with a 'seq' axis — "
            "build one with make_mesh_fsdp(n_data, n_fsdp, n_seq) or "
            "make_mesh_2d(n_data, n_seq)")
    n_seq = LAYOUT.seq_size(mesh)
    h = 8 * n_seq * 3  # smallest halo-legal geometry; params are
    w = 64             # size-independent (fully convolutional)
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, h, w, 3), jnp.float32),
                           jnp.zeros((1, h, w, 3), jnp.float32),
                           iters=1, train=False, test_mode=True))
    halo_fn = halo.make_halo_eval_fn(cfg, mesh, abstract["params"],
                                     iters=iters)
    var_sh = variables_sharding(mesh, abstract)
    data = batch_input_sharding(mesh)  # P('data', 'seq')

    def step(
        variables: Dict[str, Any],
        image1: jax.Array,
        image2: jax.Array,
        flow_init: jax.Array,
    ):
        stats = variables.get("batch_stats", {})
        return halo_fn(variables["params"], stats, image1, image2,
                       flow_init)

    return jax.jit(
        step,
        in_shardings=(var_sh, data, data, data),
        out_shardings=(data, data),
    )


def make_encode_step(
    cfg: RAFTConfig,
    mesh: Optional[Mesh] = None,
) -> Callable[..., Dict[str, jax.Array]]:
    """Jitted per-frame encoder stage (RAFT mode="encode").

    (variables, frame [, edges]) -> the frame's feature dict {fmap, ctx
    [, efmap, ectx]} — everything a frame contributes to any pair it
    joins. The streaming video path runs this ONCE per new frame; the
    previous frame's dict comes from the device-resident session carry
    (serve.sessions.DeviceSessionStore), so a chained stream pays half
    the encoder FLOPs of repeated pair calls. Composes with
    :func:`make_refine_step` to reproduce the monolithic eval step
    exactly (parity pinned in tests/test_zzvideo.py).

    With a mesh, shardings pin like make_eval_step: variables
    replicated, frame batch (and every feature-dict leaf — all leaves
    are batch-leading >=3D) over the 'data' axis.
    """
    model = RAFT(cfg)

    def encode(
        variables: Dict[str, Any],
        frame: jax.Array,
        edges: Optional[jax.Array] = None,
    ) -> Dict[str, jax.Array]:
        return model.apply(variables, frame, edges1=edges, train=False,
                           mode="encode")

    if mesh is None:
        return jax.jit(encode)
    repl = replicated_sharding(mesh)
    data = batch_input_sharding(mesh)
    return jax.jit(encode, in_shardings=(repl, data, data),
                   out_shardings=data)


def make_refine_step(
    cfg: RAFTConfig,
    iters: int = 24,
    mesh: Optional[Mesh] = None,
    adaptive: bool = False,
) -> Callable[..., Tuple[jax.Array, ...]]:
    """Jitted refinement stage (RAFT mode="step"), test-mode returns.

    (variables, features1, features2, flow_init) -> (flow_low, flow_up)
    where features1 is the EARLIER frame's dict (its ctx seeds the GRU)
    and flow_init is always materialized (a zeros flow_init equals no
    warm start — the engine's one-executable-per-bucket contract).
    Same param tree as the monolithic step; checkpoints interchange.

    ``adaptive=True``: same contract extension as make_eval_step — a
    trailing traced ``iter_budget`` scalar and (flow_low, flow_up,
    iters_used[B], final_delta[B]) returns.
    """
    model = RAFT(cfg)

    def refine(
        variables: Dict[str, Any],
        features1: Dict[str, jax.Array],
        features2: Dict[str, jax.Array],
        flow_init: Optional[jax.Array] = None,
        iter_budget: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, ...]:
        kwargs: Dict[str, Any] = {}
        if adaptive:
            kwargs.update(adaptive=True, iter_budget=iter_budget)
        return model.apply(variables, None, iters=iters,
                           flow_init=flow_init, train=False,
                           test_mode=True, mode="step",
                           features1=features1, features2=features2,
                           **kwargs)

    if mesh is None:
        return jax.jit(refine)
    repl = replicated_sharding(mesh)
    data = batch_input_sharding(mesh)
    if adaptive:
        vec = batch_sharding(mesh)
        return jax.jit(refine,
                       in_shardings=(repl, data, data, data, repl),
                       out_shardings=(data, data, vec, vec))
    return jax.jit(refine, in_shardings=(repl, data, data, data),
                   out_shardings=(data, data))
