"""Multi-host initialization.

The reference's parallelism is single-process DataParallel (SURVEY.md
§2.7) — it has no multi-node story at all. Here multi-host is the same
code path as single-host: call initialize() once per process before any
jax usage, build a mesh over jax.devices() (which enumerates EVERY chip
in the slice, all hosts), and the sharded train step's collectives ride
ICI; DCN only enters for multi-slice meshes.

Per-host data loading is already process-aware (Loader's
process_index/process_count slices the global batch), so no further
changes are needed for multi-host training.
"""

from __future__ import annotations

import os
from typing import Optional

import jax


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """jax.distributed.initialize with env-var defaults; called by the
    train CLI before any jax usage.

    Modes:
      * explicit: coordinator_address given (arg or
        JAX_COORDINATOR_ADDRESS) + num_processes/process_id (args or
        JAX_NUM_PROCESSES / JAX_PROCESS_ID);
      * auto-bootstrap: JAX_AUTO_DISTRIBUTED=1 -> no-arg
        jax.distributed.initialize() (TPU pods self-discover);
      * otherwise: no-op (single process — one host owning all chips).
    """
    coordinator_address = (coordinator_address
                           or os.environ.get("JAX_COORDINATOR_ADDRESS"))
    if coordinator_address is None:
        if os.environ.get("JAX_AUTO_DISTRIBUTED") == "1":
            jax.distributed.initialize()
        return
    num_processes = num_processes or _env_int("JAX_NUM_PROCESSES")
    process_id = process_id if process_id is not None \
        else _env_int("JAX_PROCESS_ID")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def _env_int(name: str) -> int:
    value = os.environ.get(name)
    if value is None:
        raise ValueError(
            f"multi-host init: coordinator address was given but {name} "
            "is not set (and no explicit argument was passed)")
    return int(value)


# --- elastic runtime lifecycle (resilience.membership) ---------------------
#
# jax.distributed.initialize is once-per-process by design: its client is
# constructed with the DEFAULT missed-heartbeat behavior (terminate the
# process when the coordination service reports ANY peer in error — see
# xla pjrt distributed client.h), and State.initialize refuses a second
# call. Elastic membership needs the opposite on both counts: a survivor
# must OUTLIVE a dead peer, then tear the whole runtime down and
# re-initialize at the new world size. The helpers below mirror
# jax._src.distributed.State.initialize/shutdown (jax 0.9.0: the
# factories live in jaxlib._jax and take one heartbeat_timeout in
# seconds) with three deliberate differences:
#
#   * service AND client heartbeat timeouts are relaxed to
#     effectively-never (~1e6 s): the coordination service never
#     declares a silent peer dead, so it never propagates the fatal
#     error that the default client answers with process termination.
#     Liveness detection moves wholesale to the KV-store leases the
#     membership runtime owns, where a missed lease is a catchable
#     verdict, not a SIGABRT.
#   * the client is built with shutdown_on_destruction=False and a small
#     shutdown_timeout, so teardown against DEAD peers is bounded: the
#     explicit client.shutdown() below stops the client's error-polling
#     thread FIRST (shutting the service down under a live poller is the
#     other path to the fatal callback), fails its shutdown barrier
#     after shutdown_timeout at worst, and never hangs or aborts.
#   * teardown clears jax's backend caches (xla_bridge process_count /
#     local_devices lru_caches included — stale entries otherwise leak
#     the OLD world size into orbax's barrier participation decisions)
#     so the next elastic_initialize presents the new world to
#     jax.process_count()/jax.devices() consistently on every member.

_ELASTIC_HEARTBEAT_TIMEOUT_S = 1_000_000


def elastic_initialize(coordinator_address: str, num_processes: int,
                       process_id: int, *, start_service: bool,
                       init_timeout_s: int = 60,
                       shutdown_timeout_s: int = 5) -> None:
    """Install a survivable distributed runtime (see block comment).

    Safe to call repeatedly with elastic_teardown between calls — that
    pair is exactly one membership epoch transition. ``start_service``
    is True on the epoch's rank 0 (the coordinator host).
    """
    from jax._src import distributed
    from jaxlib import _jax

    st = distributed.global_state
    if st.client is not None:
        raise RuntimeError(
            "elastic_initialize: a distributed runtime is already "
            "installed — elastic_teardown() first (one epoch at a time)")
    if start_service:
        st.service = _jax.get_distributed_runtime_service(
            "[::]:" + coordinator_address.rsplit(":", 1)[1],
            int(num_processes),
            heartbeat_timeout=_ELASTIC_HEARTBEAT_TIMEOUT_S)
    st.coordinator_address = coordinator_address
    st.num_processes = int(num_processes)
    st.process_id = int(process_id)
    client = _jax.get_distributed_runtime_client(
        coordinator_address, int(process_id),
        init_timeout=int(init_timeout_s),
        shutdown_timeout=int(shutdown_timeout_s),
        heartbeat_timeout=_ELASTIC_HEARTBEAT_TIMEOUT_S,
        shutdown_on_destruction=False, use_compression=True)
    client.connect()
    st.client = client
    st.preemption_sync_manager = _jax.create_preemption_sync_manager()
    st.preemption_sync_manager.initialize(client)
    # flight-recorder stamp: the connect above is itself a collective
    # rendezvous (every member of the new world must dial in), so the
    # (addr, size) digest is identical across the world
    from dexiraft_tpu.analysis import collective_trace

    collective_trace.record(
        "dexiraft/elastic", "elastic_initialize",
        digest=collective_trace.args_digest(coordinator_address,
                                            num_processes))


def elastic_teardown(graceful: bool = True) -> None:
    """Dismantle the current distributed runtime so a new epoch can
    initialize at a different size.

    graceful=False is the shrink path (peers are DEAD): the client
    shutdown still runs first — its barrier fails after the small
    shutdown_timeout, but the attempt stops the error-polling thread
    before the service goes away, which is what keeps a survivor
    alive — and every error is swallowed. Backend caches are refreshed
    either way; live arrays become invalid (the elastic contract:
    state is re-restored from the checkpoint after re-initialization).
    """
    import gc

    from jax._src import distributed

    from dexiraft_tpu.analysis import collective_trace

    collective_trace.record(
        "dexiraft/elastic", "elastic_teardown",
        digest=collective_trace.args_digest(bool(graceful)))
    st = distributed.global_state
    client, service = st.client, st.service
    st.client = None
    st.service = None
    st.preemption_sync_manager = None
    if client is not None:
        try:
            client.shutdown()
        except Exception as e:
            if graceful:
                print(f"[elastic] client shutdown: {type(e).__name__}: "
                      f"{str(e)[:120]}", flush=True)
    del client
    gc.collect()  # any backend-held client refs die before the service
    if service is not None:
        try:
            service.shutdown()
        except Exception as e:
            if graceful:
                print(f"[elastic] service shutdown: {type(e).__name__}: "
                      f"{str(e)[:120]}", flush=True)
    refresh_backend_world()


def refresh_backend_world() -> None:
    """Drop every cached view of the device world. jax rebuilds the
    backend from jax._src.distributed.global_state on next use, so after
    this the NEW world's process_count/process_index/devices are what
    every consumer (orbax's barrier participation above all) observes."""
    import jax as _jax
    from jax._src import xla_bridge

    xla_bridge._clear_backends()
    # process_count/local_devices carry their own lru_caches on top of
    # the backend cache — stale entries here are how an incumbent kept
    # reporting the OLD world size after re-initialization
    xla_bridge.process_count.cache_clear()
    xla_bridge.local_devices.cache_clear()
    _jax.clear_caches()
    # orbax keeps its own: the async save's signalling client is cached
    # with the distributed client of the world it was first asked in,
    # and every save after a reconfiguration then dials a coordinator
    # that is gone ("Connection refused"; the step never commits)
    try:
        from orbax.checkpoint._src.futures import signaling_client
    except ImportError:  # another orbax: nothing cached under this name
        pass
    else:
        signaling_client.get_signaling_client.cache_clear()
