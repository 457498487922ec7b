"""Device mesh management.

The reference's parallel substrate is an executor fleet reached over TCP
(SURVEY.md §2.5); vega_tpu's is a jax.sharding.Mesh. One axis, "shards",
spans every addressable device: dense-RDD partitions map 1:1 onto mesh
shards, shuffles ride all_to_all over ICI, and multi-host meshes come from
jax.distributed (the DCN analogue of the reference's multi-host deployment,
context.rs:209-303).
"""

from __future__ import annotations

import contextlib
import os
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from vega_tpu.lint.sync_witness import named_lock
from vega_tpu.tpu import spans

SHARD_AXIS = "shards"

_lock = named_lock("tpu.mesh._lock")
_default_mesh: Optional[Mesh] = None

# Serializes device program dispatch against host transfers on XLA:CPU.
_device_door = named_lock("tpu.mesh._device_door")


def device_door():
    """Mutual exclusion between device program dispatch and blocking host
    transfers, ON THE CPU BACKEND ONLY.

    Old XLA:CPU under --xla_force_host_platform_device_count on a 1-core
    box deadlocks when one thread sits inside jax.device_get while another
    dispatches a program (runtime pool starvation: the transfer waits on a
    computation whose execution needs the thread the dispatcher holds).
    Block.shard_rows' serialized device_get covered the slice+get pair;
    the same wedge fires between an exchange launch and a concurrent get
    (two cogroup partitions materializing their grouped sides on separate
    task threads). Every launch/transfer that can run on a scheduler task
    thread takes this door: shard_rows' get, host_get, and
    _run_exchange's program launches. On real accelerators this is a
    no-op context — dispatch and transfers pipeline freely. Callers must
    already be past backend init (the door itself reads
    jax.default_backend(), which must never run on import paths)."""
    if jax.default_backend() == "cpu":
        return _device_door
    return contextlib.nullcontext()


def init_multihost(coordinator: Optional[str] = None,
                   num_processes: Optional[int] = None,
                   process_id: Optional[int] = None,
                   heartbeat_timeout_s: Optional[int] = None) -> None:
    """Join a multi-host device mesh via jax.distributed.

    The DCN analogue of the reference's multi-host deployment
    (context.rs:209-303 ssh bootstrap): every host runs the same program,
    jax.distributed glues their local chips into one global device set, and
    default_mesh() then spans all of them — collectives ride ICI within a
    slice and DCN across slices, inserted by XLA from the same shard_map
    programs. No code changes anywhere else: exchanges are mesh-size
    agnostic.

    Args default from the standard env vars (JAX_COORDINATOR_ADDRESS /
    JAX_NUM_PROCESSES / JAX_PROCESS_ID) or the TPU metadata service.

    Failure semantics (peer loss): a process that dies mid-pipeline stops
    heartbeating; the jax.distributed coordination service detects this
    within heartbeat_timeout_s (jax default 100s) and TERMINATES every
    surviving process with a fatal "another task died" error — a crisp,
    bounded failure instead of survivors hanging forever inside a
    collective that can no longer complete (the SPMD analogue of the
    reference's executor-loss detection,
    distributed_scheduler.rs:434-445; tested in
    tests/test_multihost.py::test_multihost_dense_peer_loss_fails_crisply).
    Lower heartbeat_timeout_s to tighten the bound."""
    coordinator, num_processes, process_id = _normalize_multihost(
        coordinator, num_processes, process_id)
    kwargs = {}
    if coordinator:
        kwargs["coordinator_address"] = coordinator
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    if heartbeat_timeout_s is not None:
        kwargs["heartbeat_timeout_seconds"] = heartbeat_timeout_s
    jax.distributed.initialize(**kwargs)
    set_default_mesh(None)  # rebuild over the now-global device set
    # The eviction-policy memo (LRU/weakref vs multi-process FIFO) was
    # possibly resolved under the pre-distributed single-process device
    # set; it must re-resolve over the now-global one.
    from vega_tpu.tpu import dense_rdd

    dense_rdd._reset_lifetime_multiproc_memo()
    global _multihost_settings, _multihost_heartbeat_s
    _multihost_settings = (coordinator, num_processes, process_id)
    # Record the EFFECTIVE timeout (jax's own default when none was
    # passed) so a later Context explicitly requesting that same value
    # is recognized as compatible, not spuriously rejected.
    _multihost_heartbeat_s = (heartbeat_timeout_s
                              if heartbeat_timeout_s is not None
                              else _jax_default_heartbeat_s())


_multihost_settings: Optional[tuple] = None  # set once per process
_multihost_heartbeat_s: Optional[int] = None  # the timeout actually applied


def _jax_default_heartbeat_s() -> Optional[int]:
    """jax.distributed.initialize's own heartbeat_timeout_seconds
    default, read from its signature (100 in jax 0.9)."""
    import inspect

    try:
        p = inspect.signature(jax.distributed.initialize).parameters
        return p["heartbeat_timeout_seconds"].default
    except (KeyError, ValueError, TypeError):
        return None


def _normalize_multihost(coordinator, num_processes, process_id) -> tuple:
    """Apply the env-var defaults so equivalent settings compare equal
    regardless of whether they came explicit or from the environment."""
    import os

    coordinator = coordinator or os.environ.get("JAX_COORDINATOR_ADDRESS")
    if num_processes is None and os.environ.get("JAX_NUM_PROCESSES"):
        num_processes = os.environ["JAX_NUM_PROCESSES"]
    if process_id is None and os.environ.get("JAX_PROCESS_ID"):
        process_id = os.environ["JAX_PROCESS_ID"]
    return (coordinator,
            None if num_processes is None else int(num_processes),
            None if process_id is None else int(process_id))


def ensure_multihost(coordinator: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     heartbeat_timeout_s: Optional[int] = None) -> None:
    """Idempotent init_multihost: jax.distributed.initialize raises on a
    second call, but a process may legitimately build several successive
    Contexts (stop() then a new one) against the SAME global mesh. Asking
    for a different rendezvous than the one this process already joined
    cannot be honored and must fail loudly, not be masked."""
    if _multihost_settings is not None:
        requested = _normalize_multihost(coordinator, num_processes,
                                         process_id)
        if requested != _multihost_settings:
            from vega_tpu.errors import VegaError

            raise VegaError(
                "this process already joined a jax.distributed mesh with "
                f"settings {_multihost_settings}; a Context requesting "
                f"{requested} cannot re-rendezvous (jax.distributed "
                "initializes once per process)"
            )
        if heartbeat_timeout_s is not None \
                and heartbeat_timeout_s != _multihost_heartbeat_s:
            from vega_tpu.errors import VegaError

            raise VegaError(
                "this process already joined its jax.distributed mesh "
                f"with heartbeat_timeout_s={_multihost_heartbeat_s}; "
                f"requesting {heartbeat_timeout_s} cannot be honored "
                "(the coordination service is configured once per "
                "process)"
            )
        return
    init_multihost(coordinator, num_processes, process_id,
                   heartbeat_timeout_s=heartbeat_timeout_s)


def ensure_compile_cache() -> str:
    """Point jax's persistent compilation cache somewhere durable — the ONE
    place this repo sets it — and return the directory in use. Runs where
    the library first touches the backend (make_mesh), so every program a
    Context compiles is covered.

    JAX_COMPILATION_CACHE_DIR set: jax already reads it; set nothing here,
    so whoever launched the process places the cache (and tunes it through
    jax's own variables). Unset: <checkout>/.jax_cache next to the package
    (git-ignored) — a fixed path, because the path is part of how a cache is
    found again — keeping programs that took 0.5 s or more to compile: most
    of this repo's programs compile in under jax's default threshold of 1 s,
    and the processes a test run or a fleet starts recompile the same ones."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    cache_dir = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return cache_dir


def make_mesh(n_devices: Optional[int] = None) -> Mesh:
    """Build a 1-D mesh over the first n devices (default: all)."""
    ensure_compile_cache()
    devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"requested {n_devices} devices, have {len(devices)}"
            )
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (SHARD_AXIS,))


def default_mesh() -> Mesh:
    global _default_mesh
    with _lock:
        if _default_mesh is None:
            _default_mesh = make_mesh()
        return _default_mesh


def set_default_mesh(mesh: Optional[Mesh]) -> None:
    global _default_mesh
    with _lock:
        _default_mesh = mesh


def shard_spec(mesh: Mesh) -> NamedSharding:
    """Rows sharded over the mesh axis (axis 0 of every column)."""
    return NamedSharding(mesh, P(SHARD_AXIS))


def replicated_spec(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _identity_outputs(*xs):
    return xs


# One replicate-gather program per mesh: jit wrappers own their dispatch
# caches, so minting a fresh wrapper per host_get would re-trace every
# fetch. Keyed by Mesh (hashable); bounded — a process holds O(1) meshes.
_gather_jit_cache: dict = {}


def host_get(tree):
    """Multiprocess-safe jax.device_get over a pytree — ONE transfer.

    Pure-numpy trees (host-tier _HostMeshStub blocks on worker processes)
    pass straight through WITHOUT touching the jax backend: the driver
    process owns the chip, and a worker that initialized the backend to
    read host numpy would try to take it. Single-process trees are
    exactly jax.device_get. Multi-process (jax.distributed global mesh):
    non-fully-addressable leaves cannot be fetched directly; all of them
    are replicated in ONE jitted identity program (an XLA all-gather —
    every process dispatches the same program, SPMD-safe) and then read
    locally. Drivers on every process therefore observe identical
    counts/flags and keep making identical dispatch decisions, which is
    what keeps the multi-controller model coherent."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not any(isinstance(x, jax.Array) for x in leaves):
        # vegalint: ignore[VG016] — numpy passthrough: no device touched
        return jax.device_get(tree)  # numpy passthrough, backend-free
    # One blocking device->host round trip: the wait for the device's own
    # work, then the copy.
    with spans.span("fetch") as sp:
        if sp.on:
            sp.nbytes = sum(x.nbytes for x in leaves
                            if isinstance(x, jax.Array))
        if jax.process_count() > 1:
            by_mesh: dict = {}
            for i, x in enumerate(leaves):
                if isinstance(x, jax.Array) and not x.is_fully_addressable:
                    by_mesh.setdefault(x.sharding.mesh, []).append(i)
            for m, idx in by_mesh.items():
                prog = _gather_jit_cache.get(m)
                if prog is None:
                    prog = jax.jit(_identity_outputs,
                                   out_shardings=NamedSharding(m, P()))
                    _gather_jit_cache[m] = prog
                with device_door():
                    gathered = prog(*[leaves[i] for i in idx])
                for i, g in zip(idx, gathered):
                    leaves[i] = g  # fully replicated: locally readable
        # The dense tier's stage-launch transfer itself: DenseRDD.splits
        # materializes on the per-job drive thread BY DESIGN (one SPMD
        # program per stage), so the round trip is that job's own work,
        # bounded by device compute — it cannot park other tenants'
        # scheduling.
        with device_door():
            # vegalint: ignore[VG016] — stage-launch transfer on the job's own drive thread (see above)
            fetched = jax.device_get(leaves)
        return jax.tree_util.tree_unflatten(treedef, fetched)


def host_put(value, spec: NamedSharding) -> jax.Array:
    """Multiprocess-safe jax.device_put of a host value every process
    holds identically (the SPMD driver model guarantees it): each process
    materializes only its addressable shards via make_array_from_callback;
    single-process falls through to plain device_put."""
    with spans.span("put") as sp:
        if sp.on:  # a list or a scalar has no nbytes: what it becomes has
            sp.nbytes = int(getattr(value, "nbytes", None)
                            or np.asarray(value).nbytes)
        if jax.process_count() == 1:
            return jax.device_put(value, spec)
        arr = np.asarray(value)
        return jax.make_array_from_callback(arr.shape, spec,
                                            lambda idx: arr[idx])
