"""Canonical sharding layout — the single source of truth for GSPMD.

Every mesh axis name, every ``PartitionSpec``, and every
``Mesh``/``NamedSharding`` construction in this codebase lives HERE and
only here. The static gate enforces it: jaxlint rules JL010+ (see
``analysis/shardlint.py``) fail the commit on any inline spec literal,
ad-hoc mesh-axis string, or unpinned mesh-path jit outside this module,
and ``analysis/shardaudit.py`` diffs the compiled train/eval/serve
steps' resolved shardings against ``analysis/layout_golden.json`` so
spec drift is a CI failure, not a pod-debugging session.

Why one frozen layout object: the sharding story grew organically
(mesh.py helpers, per-CLI glue, context.py shard_map specs) and the
ROADMAP's pod-scale item is blocked on collapsing it — the SNIPPETS.md
exemplar ("8-chip v4 to 6000-chip v5p without changing application
code") is a frozen ``SpecLayout`` dataclass exactly like this one.
Application code asks the layout for *meaning* ("the batch's sharding
on this mesh"), never spells axes.

Axes (``SpecLayout``):

  data  — batch data-parallelism. Every mesh has it; gradients
          all-reduce over it (the SPMD partitioner inserts the psum).
  seq   — context parallelism: image rows (and with them the quadratic
          correlation volume's query axis) shard over it on 2-D train
          meshes (parallel/context.py has the math).
  fsdp  — parameter/optimizer-state sharding (LIVE since the fsdp PR).
          ``make_train_mesh(batch, fsdp=...)`` grows the axis over the
          devices left after data takes its largest batch divisor;
          ``params(mesh)``/``opt_state(mesh)`` resolve to the fsdp spec
          on such meshes, with the per-leaf divisibility fallback
          decided HERE (``param_leaf_spec``) — small leaves (biases,
          norm params, scalars) and leaves with no dividing dim stay
          replicated, and call sites never decide.

fsdp is a STORAGE axis by default: the fence-mode train step gathers
the state to replicated at entry and re-shards at exit (train/step.py's
fence pattern — see docs/perf.md "Sharded state (fsdp)" for why the
GSPMD partitioner must never see fsdp-sharded tensors inside the model:
feature-dim-partitioned convolutions miscompile under this backend's
GSPMD, pinned by tests/test_zzzfsdp.py). The halo compute-sharding mode
(parallel/halo.py, ``make_train_step(compute_sharding="halo")``) keeps
fsdp sharded DURING compute too — per-block all-gather inside a
shard_map body, where GSPMD never sees the gathered tensors — and
shards the spatial compute itself over 'seq' with explicit ppermute
halo exchange (:meth:`SpecLayout.batch_spatial_compute`,
:func:`seq_halo_perms`). The persistent HBM win — params + Adam
moments at ~1/N per device between steps, and per-shard checkpoint I/O
— is exactly what the ``state_bytes_per_device`` bench metric records.

The compat surface ``parallel/mesh.py`` re-exports everything below, so
existing imports keep working; new code should import from here.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import numpy as np

# jax import kept function-local where possible is NOT viable here: the
# module's whole job is constructing jax.sharding objects. Callers that
# must stay jax-free (data/__init__, loaders) already import lazily.
import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec


@dataclasses.dataclass(frozen=True)
class SpecLayout:
    """Frozen mesh-axis names + canonical PartitionSpecs.

    Methods return ``PartitionSpec``s (mesh-independent); pair them with
    a mesh via :func:`named`. Specs that depend on the mesh's rank
    (batch, correlation volume) take the mesh and pick the 1-D or 2-D
    form — call sites never branch on axis names themselves.
    """

    data_axis: str = "data"
    fsdp_axis: str = "fsdp"
    seq_axis: str = "seq"

    # ---- mesh-independent specs ---------------------------------------

    #: Leaves smaller than this (elements) stay replicated on fsdp
    #: meshes: biases, norm scales, and scalars cost more to gather
    #: than they save, and their shard would be sub-tile anyway.
    FSDP_MIN_LEAF_SIZE = 4096

    def replicated(self) -> PartitionSpec:
        """Fully replicated: scalars, metrics, BN stats — and params/
        opt_state on meshes without an fsdp axis."""
        return PartitionSpec()

    def params(self, mesh: Optional[Mesh] = None) -> PartitionSpec:
        """Model parameters: the canonical GROUP spec. Replicated on
        meshes without an fsdp axis; ``fsdp_params()`` on meshes with
        one. Per-LEAF resolution (which dim, divisibility fallback) is
        :meth:`param_leaf_spec` — this group-level answer is what the
        audit's declared section and the docs tables pin."""
        if mesh is None or self.fsdp_axis not in mesh.axis_names:
            return PartitionSpec()
        return self.fsdp_params()

    def opt_state(self, mesh: Optional[Mesh] = None) -> PartitionSpec:
        """Optimizer state mirrors the param layout (Adam's mu/nu are
        param-shaped; the step counter falls back to replicated via the
        per-leaf policy like every other small leaf)."""
        return self.params(mesh)

    def fsdp_params(self) -> PartitionSpec:
        """The canonical fsdp GROUP marker spec: sharded over 'fsdp'.
        Real leaves resolve per-dim via :meth:`param_leaf_spec` (a conv
        kernel's dividing dim is rarely the leading one)."""
        return PartitionSpec(self.fsdp_axis)

    def param_leaf_spec(self, mesh: Mesh,
                        shape: Sequence[int]) -> PartitionSpec:
        """Per-leaf fsdp resolution — THE divisibility-fallback policy,
        decided centrally so no call site ever reimplements it.

        Shards the LARGEST dim that the mesh's fsdp axis divides
        (ties: the earliest). Conv kernels are HWIO — their leading
        dims are 1/3/7-sized taps, so a leading-dim-only rule would
        exempt the entire model; the largest dim is a channel dim.
        Falls back to replicated for leaves under FSDP_MIN_LEAF_SIZE
        (biases, norm params, scalars) and leaves no dim of which
        divides the axis — exactly the leaves whose gather would cost
        more than their shard saves."""
        n = self.fsdp_size(mesh)
        shape = tuple(int(s) for s in shape)
        if n <= 1 or int(np.prod(shape, dtype=np.int64)) < \
                self.FSDP_MIN_LEAF_SIZE:
            return PartitionSpec()
        best = None
        for i, d in enumerate(shape):
            if d and d % n == 0 and (best is None or d > shape[best]):
                best = i
        if best is None:
            return PartitionSpec()
        entry: "list" = [None] * len(shape)
        entry[best] = self.fsdp_axis
        return PartitionSpec(*entry)

    def batch(self) -> PartitionSpec:
        """Batch leaves on a 1-D mesh: leading (batch) dim over 'data'."""
        return PartitionSpec(self.data_axis)

    def batch_spatial(self) -> PartitionSpec:
        """Batch leaves on a 2-D (data, seq) mesh: batch over 'data' AND
        image rows over 'seq' — GSPMD partitions convolutions with halo
        exchange and the correlation volume by query rows."""
        return PartitionSpec(self.data_axis, self.seq_axis)

    def batch_spatial_compute(self) -> PartitionSpec:
        """shard_map in/out spec for HALO compute sharding
        (parallel/halo.py): batch leaves enter the body as per-device
        (B/data, H/seq, ...) slabs — batch over 'data', contiguous image
        rows over 'seq'. Same axes as :meth:`batch_spatial`, but pinned
        as its own canonical surface: batch_spatial is a GSPMD
        annotation (the partitioner decides the collectives), while this
        spec is a shard_map CONTRACT — the body sees local slabs and
        does its own ppermute halo exchange (:func:`seq_halo_perms`), so
        the audit tracks the two modes separately."""
        return PartitionSpec(self.data_axis, self.seq_axis)

    def carry(self) -> PartitionSpec:
        """Flow/carry state (flow_init, flow_low — (B, H/8, W/8, 2)):
        batch-sharded like the frames it warm-starts."""
        return PartitionSpec(self.data_axis)

    def corr_query_rows(self) -> PartitionSpec:
        """shard_map spec for explicit context parallelism
        (parallel/context.py): (B, H, W, D) feature maps / coords with
        H (the volume's query axis) over 'seq', everything else local."""
        return PartitionSpec(None, self.seq_axis, None, None)

    # ---- mesh-dependent specs -----------------------------------------

    def batch_for(self, mesh: Mesh) -> PartitionSpec:
        """THE batch spec for a given mesh: spatial (data, seq) when the
        mesh has a seq axis, else batch-only. Shared by the train step's
        in_shardings and the device prefetcher's put, so a prefetched
        batch lands already in the step's input layout. Contract: one
        spec for the whole batch dict, so every batch leaf must be
        >=3-D (B, H, ...) on a 2-D mesh — true for image1/2, flow,
        valid, edges; a future <3-D leaf needs per-leaf specs here AND
        in batch_putter (shard_batch_spatial already splits by ndim on
        the put side)."""
        return (self.batch_spatial() if self.seq_axis in mesh.axis_names
                else self.batch())

    def corr_volume(self, mesh: Mesh) -> PartitionSpec:
        """The ~200 MB all-pairs correlation volume (B, H, W, H*W):
        batch over 'data', query rows over 'seq' when the mesh has the
        axis. Since the flash-blocked kernel became the production
        eval/serve config (ISSUE 12) the volume only materializes behind
        --corr_impl allpairs; its canonical spec is kept for that path,
        but the audit's declared canary moved to corr_fmaps."""
        return self.batch_for(mesh)

    def corr_fmaps(self, mesh: Mesh) -> PartitionSpec:
        """The on-demand correlation paths' streamed tensor set — fmap1
        plus the pooled fmap2 pyramid, (B, H/8, W/8, C)-shaped — the
        audit's canary group now that eval/serve default to the
        volume-free flash kernel: batch over 'data', rows over 'seq'
        like every spatial activation. O(fmaps) is the whole point;
        replicating them at pod batch sizes would still be a layout
        bug the size tripwire must catch."""
        return self.batch_for(mesh)

    def corr_window(self, mesh, shape) -> Optional[
            Tuple[PartitionSpec, PartitionSpec]]:
        """shard_map specs of the lookup's alignment kernels
        (ops/pallas_window.py): for an array in the order the chip stores
        a level, (S1, S2, B, H*W), and for its per-query (B, H*W)
        operands; a stack of lookups leads both with its own axis,
        (T, S1, S2, B, H*W) and (T, B, H*W). The batch over 'data' and
        the queries (whole image rows) over 'seq', each where the mesh
        has the axis partitioned automatically and it divides the extent;
        the two target axes and the stack stay whole. None where nothing
        is left to split: no mesh, one chip, or inside a shard_map that
        already did."""
        auto = {name: size for name, size, kind in zip(
            mesh.axis_names, mesh.axis_sizes, mesh.axis_types)
            if kind == AxisType.Auto and size > 1}
        split = []
        for axis, extent in ((self.data_axis, shape[-2]),
                             (self.seq_axis, shape[-1])):
            ways = auto.get(axis)
            split.append(axis if ways and extent % ways == 0 else None)
        if not any(split):
            return None
        stack = [None] * (len(shape) - 4)
        return (PartitionSpec(*stack, None, None, *split),
                PartitionSpec(*stack, *split))

    # ---- mesh shape queries -------------------------------------------

    def data_size(self, mesh: Mesh) -> int:
        """Number of ways the batch splits on this mesh's data axis."""
        return dict(mesh.shape).get(self.data_axis, 1)

    def has_seq(self, mesh: Mesh) -> bool:
        return self.seq_axis in mesh.axis_names

    def has_fsdp(self, mesh: Mesh) -> bool:
        """True when the mesh instantiates a >1-way fsdp axis (a 1-way
        axis is storage-identical to replicated, so callers skip the
        gather fences for it)."""
        return self.fsdp_size(mesh) > 1

    def fsdp_size(self, mesh: Mesh) -> int:
        """Number of ways params/opt_state shard on this mesh."""
        return dict(mesh.shape).get(self.fsdp_axis, 1)

    def seq_size(self, mesh: Mesh) -> int:
        """Number of ways image rows shard on this mesh's seq axis."""
        return dict(mesh.shape).get(self.seq_axis, 1)


#: The one layout instance application code threads around.
LAYOUT = SpecLayout()

#: Logical array groups the shard audit may see fully replicated without
#: flagging, with the reason pinned next to the exemption. params and
#: opt_state are deliberately NOT here anymore: since the fsdp axis went
#: live they resolve to the fsdp spec on fsdp meshes, and on data-only
#: meshes they sit under the size threshold — the over-threshold
#: replicated canary is ARMED on them (an opt_state that ever resolves
#: fully replicated above the tripwire fails the audit, no exemption).
REPLICATED_OK = {
    "batch_stats": "BatchNorm running stats are global (sync-BN)",
    "rng": "scalar-sized PRNG key",
    "step": "scalar step counter",
    "metrics": "scalar loss/metric outputs",
}

# legacy axis-name constants (parallel/mesh.py re-exports them); new
# code should take names from LAYOUT
DATA_AXIS = LAYOUT.data_axis
SEQ_AXIS = LAYOUT.seq_axis
FSDP_AXIS = LAYOUT.fsdp_axis


def named(mesh: Mesh, spec: PartitionSpec) -> NamedSharding:
    """The ONE NamedSharding constructor (JL010 bans inline ones)."""
    return NamedSharding(mesh, spec)


# --------------------------------------------------------------------------
# mesh constructors — the only Mesh() call sites in the tree (JL011)
# --------------------------------------------------------------------------


def make_mesh(devices: Optional[Sequence[jax.Device]] = None,
              axis: Optional[str] = None) -> Mesh:
    """1-D data mesh over the given (default: all) devices."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), (axis or LAYOUT.data_axis,))


def make_mesh_2d(
    n_data: int,
    n_seq: int,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """(data, seq) mesh: batch DP x spatial/sequence CP.

    The seq axis shards image rows (and with them the quadratic
    correlation volume's query axis — see parallel.context). Keep seq
    groups on adjacent devices so the fmap2 all-gather rides ICI
    neighbors.
    """
    if devices is None:
        devices = jax.devices()
    if n_data * n_seq > len(devices):
        raise ValueError(
            f"mesh {n_data}x{n_seq} needs {n_data * n_seq} devices, "
            f"have {len(devices)}")
    grid = np.asarray(devices[: n_data * n_seq]).reshape(n_data, n_seq)
    return Mesh(grid, (LAYOUT.data_axis, LAYOUT.seq_axis))


def make_mesh_fsdp(
    n_data: int,
    n_fsdp: int,
    n_seq: Optional[int] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """(data, fsdp[, seq]) mesh: batch DP x parameter sharding [x CP].

    The fsdp axis holds params and optimizer state sharded between
    steps (param_leaf_spec); the batch still shards over 'data' (and
    rows over 'seq'), replicated across fsdp — fsdp is storage
    parallelism, gathered for compute by the train step's fences.

    Placement: the INNERMOST axis gets adjacent devices. On a 2-axis
    (data, fsdp) mesh that is fsdp, so the entry gather rides ICI
    neighbors; with ``n_seq`` it is seq — fsdp groups then stride by
    n_seq, deliberately: seq carries a halo exchange per sharded conv
    inside every step (make_mesh_2d's placement argument), while the
    fsdp gather happens once at step entry, so seq keeps the neighbor
    links when both want them.
    """
    if devices is None:
        devices = jax.devices()
    shape = (n_data, n_fsdp) + (() if n_seq is None else (n_seq,))
    total = int(np.prod(shape))
    if total > len(devices):
        raise ValueError(
            f"mesh {'x'.join(str(s) for s in shape)} needs {total} "
            f"devices, have {len(devices)}")
    axes = (LAYOUT.data_axis, LAYOUT.fsdp_axis) + (
        () if n_seq is None else (LAYOUT.seq_axis,))
    grid = np.asarray(devices[:total]).reshape(shape)
    return Mesh(grid, axes)


def make_train_mesh(batch_size: int,
                    devices: Optional[Sequence[jax.Device]] = None,
                    fsdp: "Optional[object]" = None) -> Mesh:
    """The training CLI's mesh policy (was inline glue in train_cli).

    data axis: the largest device count that divides the batch — pick
    batch sizes that are multiples of the slice size to use every chip
    for data parallelism.

    fsdp axis (``fsdp=``):
      * None / 1 — no fsdp axis: the historical 1-D data mesh.
      * 'auto'   — largest divisor after data: the axis grows over the
        devices data-parallelism left idle (a 2-batch on 8 chips:
        data=2, fsdp=4), host-count-aware — the size is walked down to
        one that keeps each fsdp shard group within whole host blocks
        (divides, or is a multiple of, the local device count) so the
        step-entry gather rides intra-host ICI.
      * int N    — exactly N-way fsdp: the axis is carved FIRST and
        data takes the largest batch divisor of the remaining budget
        (an 8-batch on 8 chips with fsdp=4 trains data=2 x fsdp=4) —
        the explicit form benches and A/B tests use.
    """
    if devices is None:
        devices = jax.devices()
    n_data = max(n for n in range(1, len(devices) + 1)
                 if batch_size % n == 0)
    if fsdp is None or fsdp == 1:
        return make_mesh(devices[:n_data])
    if fsdp == "auto":
        n_fsdp = len(devices) // n_data
        local = max(1, jax.local_device_count())
        while n_fsdp > 1 and not (local % n_fsdp == 0
                                  or n_fsdp % local == 0):
            n_fsdp -= 1
    else:
        n_fsdp = int(fsdp)
        if n_fsdp < 1 or n_fsdp > len(devices):
            raise ValueError(
                f"fsdp={n_fsdp}: need between 1 and {len(devices)} "
                f"device(s)")
        n_data = max(n for n in range(1, len(devices) // n_fsdp + 1)
                     if batch_size % n == 0)
    if n_fsdp <= 1:
        return make_mesh(devices[:n_data])
    return make_mesh_fsdp(n_data, n_fsdp, devices=devices)


def make_serve_mesh(n_chips: Optional[int] = None) -> Mesh:
    """1-D data mesh for the serving engine (dexiraft_tpu.serve): an
    inference batch shards over the 'data' axis across `n_chips` (default
    all). Serving never needs the 2-D (data, seq) train mesh — eval
    batches are the parallelism, not image rows."""
    devices = jax.devices()
    if n_chips is not None:
        if not 1 <= n_chips <= len(devices):
            raise ValueError(
                f"n_chips {n_chips} out of range 1..{len(devices)}")
        devices = devices[:n_chips]
    return make_mesh(devices)


# --------------------------------------------------------------------------
# shardings for a concrete mesh
# --------------------------------------------------------------------------


def batch_sharding(mesh: Mesh, axis: Optional[str] = None) -> NamedSharding:
    """Shard the leading (batch) dim over the data axis."""
    if axis is not None and axis != LAYOUT.data_axis:
        # explicit non-canonical axis: honored, but the layout owns the
        # PartitionSpec construction
        return named(mesh, PartitionSpec(axis))
    return named(mesh, LAYOUT.batch())


def spatial_sharding(mesh: Mesh) -> NamedSharding:
    """Batch over 'data' AND image rows over 'seq' (context parallelism):
    GSPMD partitions convolutions with halo exchange and the correlation
    volume by query rows under this annotation."""
    return named(mesh, LAYOUT.batch_spatial())


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully replicated (parameters, optimizer state, scalars)."""
    return named(mesh, LAYOUT.replicated())


def batch_input_sharding(mesh: Mesh) -> NamedSharding:
    """The sharding the jitted train step pins its batch argument to —
    LAYOUT.batch_for(mesh) as a NamedSharding. Shared by train.step and
    the device prefetcher, so a prefetched batch lands ALREADY in the
    step's input layout and consuming it triggers no resharding copy."""
    return named(mesh, LAYOUT.batch_for(mesh))


def carry_sharding(mesh: Mesh) -> NamedSharding:
    """Warm-start carry (flow_init / flow_low) sharding."""
    return named(mesh, LAYOUT.carry())


#: TrainState fields whose leaves shard over fsdp; everything else in
#: the state (step, rng, batch_stats — see REPLICATED_OK) replicates.
_FSDP_STATE_FIELDS = ("params", "opt_state")


def state_sharding(mesh: Mesh, state: Any) -> Any:
    """Per-leaf NamedSharding tree for a TrainState-shaped pytree.

    On fsdp meshes, leaves under the ``params``/``opt_state`` fields
    resolve via LAYOUT.param_leaf_spec (largest dividing dim, small-leaf
    fallback); every other field — and every field on non-fsdp meshes —
    is replicated. ``state`` may be abstract (jax.eval_shape output):
    only shapes are read. This is THE tree the train step pins as its
    state in/out shardings and the one shard_state puts with, so
    storage layout and the jit boundary can never drift apart."""
    repl = replicated_sharding(mesh)
    if not LAYOUT.has_fsdp(mesh):
        return jax.tree.map(lambda _: repl, state)
    flat, treedef = jax.tree_util.tree_flatten_with_path(state)
    shardings = []
    for path, leaf in flat:
        field = getattr(path[0], "name", None)
        if field in _FSDP_STATE_FIELDS:
            shardings.append(
                named(mesh, LAYOUT.param_leaf_spec(mesh, np.shape(leaf))))
        else:
            shardings.append(repl)
    return jax.tree_util.tree_unflatten(treedef, shardings)


def variables_sharding(mesh: Mesh, variables: Any) -> Any:
    """Per-leaf NamedSharding tree for a flax variables dict
    ({"params": ..., "batch_stats": ...}): leaves under "params"
    resolve via LAYOUT.param_leaf_spec — the same storage layout the
    train state pins — and every other collection replicates. The halo
    eval step (train/step.py, ``compute_sharding="halo"``) pins its
    variables argument with this tree, so eval consumes fsdp-STORED
    params directly (the shard_map body gathers per block); on meshes
    without an fsdp axis every leaf resolves replicated. ``variables``
    may be abstract — only shapes are read."""
    repl = replicated_sharding(mesh)
    if not LAYOUT.has_fsdp(mesh):
        return jax.tree.map(lambda _: repl, variables)
    flat, treedef = jax.tree_util.tree_flatten_with_path(variables)
    shardings = []
    for path, leaf in flat:
        top = path[0]
        key = getattr(top, "key", getattr(top, "name", None))
        if key == "params":
            shardings.append(
                named(mesh, LAYOUT.param_leaf_spec(mesh, np.shape(leaf))))
        else:
            shardings.append(repl)
    return jax.tree_util.tree_unflatten(treedef, shardings)


def shard_state(state: Any, mesh: Mesh) -> Any:
    """Device-put a host/replicated TrainState into its storage layout
    (state_sharding). Multi-process safe: sharded leaves assemble via
    make_array_from_callback — every host holds the full host-side copy
    (create_state is deterministic per host) and contributes the slices
    its devices own."""
    shardings = state_sharding(mesh, state)

    def put(x: Any, sharding: NamedSharding) -> jax.Array:
        if sharding.spec == PartitionSpec():
            return _put(x, sharding)
        host = np.asarray(x)
        return jax.make_array_from_callback(
            host.shape, sharding, lambda idx: host[idx])

    return jax.tree.map(put, state, shardings)


def gather_state(tree: Any, mesh: Mesh) -> Any:
    """Explicit all-gather of a (possibly fsdp-sharded) pytree back to
    replicated — the host-side companion of the train step's entry
    fence, used where sharded leaves must not reach a consumer that
    compiles without the fences (validation's eval step, interop
    exports). No-op cost on already-replicated leaves."""
    repl = replicated_sharding(mesh)
    return jax.tree.map(
        lambda x: (jax.device_put(x, repl)
                   if isinstance(x, jax.Array)
                   and not x.is_fully_replicated else x), tree)


# --------------------------------------------------------------------------
# host -> device placement
# --------------------------------------------------------------------------


def _put(x: Any, sharding: NamedSharding) -> jax.Array:
    """Host array -> global sharded array.

    Single-process: plain device_put. Multi-process: the host holds only
    its jax.process_index() slice of the global batch (Loader slices at
    decode time), so assemble the global array from per-process locals —
    the multi-host analog of DataParallel's scatter."""
    if jax.process_count() == 1:
        return jax.device_put(x, sharding)
    return jax.make_array_from_process_local_data(sharding, np.asarray(x))


def shard_batch(batch: Any, mesh: Mesh, axis: Optional[str] = None) -> Any:
    """Device-put every leaf of a host batch with its leading dim sharded.

    The per-host analog of DataParallel's scatter (but zero-copy once the
    arrays are on device; donation happens in the jitted step). In a
    multi-process run each host contributes its local Loader slice and
    the result is the global batch.
    """
    sharding = batch_sharding(mesh, axis)
    return jax.tree.map(lambda x: _put(x, sharding), batch)


def shard_batch_spatial(batch: Any, mesh: Mesh) -> Any:
    """device_put a host batch with (data, seq) sharding: 3D/4D image-like
    leaves shard over (batch, rows); everything else batch-only."""
    sp = spatial_sharding(mesh)
    bo = batch_sharding(mesh)
    return jax.tree.map(
        lambda x: _put(x, sp if np.ndim(x) >= 3 else bo), batch)


def replicate(tree: Any, mesh: Mesh) -> Any:
    """Device-put every leaf of a pytree fully replicated over the mesh.

    Needed explicitly in multi-process runs: host-local state (e.g. from
    create_state, identical on every process by construction) must become
    global replicated arrays before a pjitted step can consume it."""
    repl = replicated_sharding(mesh)
    return jax.tree.map(lambda x: _put(x, repl), tree)


def batch_putter(mesh: Optional[Mesh]):
    """batch -> on-device batch, in the train step's input layout.

    The transfer-side helper for data.prefetch.DevicePrefetcher: returns
    a callable that device_puts a host batch dict with the SAME shardings
    make_train_step pins via in_shardings (batch_input_sharding above —
    same >=3-D-leaf contract on a 2-D mesh). jax.device_put is
    asynchronous, so the returned callable only ENQUEUES the
    host->device copy — the prefetcher keeps several in flight while
    the current step computes. mesh=None: plain device_put to the
    default device (single-chip)."""
    if mesh is None:
        return lambda batch: jax.tree.map(jax.device_put, batch)
    if LAYOUT.has_seq(mesh):
        return lambda batch: shard_batch_spatial(batch, mesh)
    return lambda batch: shard_batch(batch, mesh)


# --------------------------------------------------------------------------
# halo compute sharding — the seq-axis exchange topology and the
# per-block gather schedule (parallel/halo.py consumes both; they live
# HERE so every ppermute call site draws its permutation and axis name
# from the layout, per JL011)
# --------------------------------------------------------------------------


def seq_halo_perms(n_seq: int) -> Tuple[list, list]:
    """ppermute permutation pairs for NON-CIRCULAR neighbor halo
    exchange over the seq axis: ``fwd`` sends each device's boundary
    rows to its successor (filling the successor's TOP halo), ``bwd``
    to its predecessor (BOTTOM halo).

    Non-circular on purpose: ppermute zero-fills unaddressed outputs,
    which is byte-identical to the unsharded program's symmetric zero
    padding at the global image edges — device 0's top halo and device
    n-1's bottom halo get exactly the zeros the global conv would pad,
    so no edge-device special-casing exists anywhere downstream."""
    fwd = [(i, i + 1) for i in range(n_seq - 1)]
    bwd = [(i + 1, i) for i in range(n_seq - 1)]
    return fwd, bwd


def param_block_names(params: Any) -> Tuple[str, ...]:
    """The per-block all-gather schedule for halo compute sharding: the
    top-level module keys of the param tree (fnet / cnet /
    ScanRAFTStep_0), in tree order. Each block's leaves are gathered
    from their fsdp shards immediately before the block runs and
    dropped after (gather→use→drop), so peak gathered-params HBM is one
    block, not the tree. Pinned here so the step, the audit's declared
    groups, and the docs table agree on the grouping."""
    return tuple(params)


def spec_str(spec: PartitionSpec) -> str:
    """Stable, human-diffable serialization of a PartitionSpec — the
    representation layout_golden.json pins ("P()", "P('data', 'seq')",
    "P(None, 'seq', None, None)")."""
    parts = []
    for entry in tuple(spec):
        if entry is None:
            parts.append("None")
        elif isinstance(entry, tuple):
            parts.append("(" + ", ".join(repr(e) for e in entry) + ")")
        else:
            parts.append(repr(entry))
    return "P(" + ", ".join(parts) + ")"
