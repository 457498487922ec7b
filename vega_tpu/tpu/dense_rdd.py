"""DenseRDD: the device tier — RDDs whose partitions are columnar array
shards on a jax Mesh and whose operations compile to SPMD XLA programs.

Architecture (SURVEY.md §7): partition == mesh shard; narrow op chains fuse
into ONE jitted shard_map program per stage (replacing the reference's Rust
iterator chaining, mapper_rdd.rs:161-163); a shuffle is ONE fused program of
  local pre-combine -> hash bucket -> all_to_all over ICI -> segment reduce
replacing the reference's entire shuffle machinery (dependency.rs:164-229,
shuffle_manager.rs, shuffle_fetcher.rs, map_output_tracker.rs) for on-mesh
data. "Within one TPU slice, a stage is a single SPMD program launch" — so
the per-task DAG fan-out collapses: the host DAGScheduler still owns the
graph, but a dense stage executes as one program, not num_partitions tasks.

DenseRDD subclasses RDD, so anything not device-accelerated (arbitrary
Python closures, cogroup with a host RDD, ...) transparently falls back to
the host tier through compute()/iterator() interop.

Raggedness: every block has static per-shard capacity; validity is
(count, mask). Exchange capacities are estimated, checked on device, and
retried with exact histogram-based sizes on overflow.

Related public work: DrJAX (arXiv:2403.07128) expresses MapReduce primitives
as JAX transforms the same way the dense tier lowers RDD ops to shard_map
programs; Exoshuffle (arXiv:2203.05072) argues for application-level,
pluggable shuffles — here the exchange implementation is planned per
launch (all_to_all | staged | ring, cost-modeled under the HBM budget by
tpu/exchange_plan.py, or forced via dense_exchange).
"""

from __future__ import annotations

import logging
import math
import threading
import time
import weakref
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from vega_tpu.errors import VegaError
from vega_tpu.rdd.base import RDD
from vega_tpu.split import Split
from vega_tpu.tpu import block as block_lib
from vega_tpu.tpu import dict_encoding
from vega_tpu.tpu import kernels
from vega_tpu.tpu import pallas_kernels
from vega_tpu.tpu import mesh as mesh_lib
from vega_tpu.tpu import spans
from vega_tpu.tpu.block import KEY, KEY_LO, VALUE, Block

log = logging.getLogger("vega_tpu")

_SPEC = P(mesh_lib.SHARD_AXIS)
_REPL = P()


def _join_rename(nm: str, prefix: str) -> str:
    """VALUE -> lv/rv and VALUE.lo -> lv.lo/rv.lo by EXACT match — a
    substring replace would mangle any future name containing 'v'. Only
    canonical layouts reach the join (see _dense_joinable), so anything
    else passing through unchanged is a programming error upstream."""
    if nm == VALUE:
        return prefix
    if nm == block_lib.lo_of(VALUE):
        return block_lib.lo_of(prefix)
    return nm


def _canonical_value_layout(schema) -> bool:
    """True when the non-key columns are exactly the canonical VALUE — or
    the wide (VALUE, VALUE.lo) int64 pair — i.e. the block has a host-tier
    (k, v) row form and the lv/rv join renames apply cleanly."""
    names = [nm for nm, _ in schema if nm not in (KEY, KEY_LO)]
    return names in ([VALUE], [VALUE, block_lib.lo_of(VALUE)])


def _shard_program(mesh, fn, in_specs, out_specs):
    """jit(shard_map(fn)), named `<fn>.<spans.stage_placement()>`: the
    compile cache then never hands a tree an executable traced under another
    tree's stage scopes (the cache's key leaves metadata out, names in)."""
    from vega_tpu.tpu import compat

    if isinstance(in_specs, int):
        in_specs = (_SPEC,) * in_specs
    mapped = compat.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
    )
    mapped.__name__ = f"{fn.__name__}.{spans.stage_placement()}"
    return jax.jit(mapped)


# Structural program cache: identical pipelines (same op kinds, same closure
# code, same static capacities) reuse one compiled XLA program across RDD
# instances — the replacement for the reference's "serialize the closure"
# portability story (SURVEY.md §2.1): here the *fingerprint* of the traced
# function is the identity, and XLA's own jit cache handles shape changes.
_PROGRAM_CACHE: dict = {}


def program_mints() -> int:
    """Count of shard programs BUILT so far (cache hits excluded). The
    frame planner's whole-stage-fusion acceptance test reads this to
    prove a select->filter->with_column chain compiled to ONE program;
    spans.programs() has the same count by kind."""
    return spans.program_mints()


def _fp(obj) -> str:
    """Stable fingerprint of a callable/closure for program-cache keys."""
    import hashlib

    with spans.span("fingerprint"):
        try:
            import cloudpickle

            return hashlib.sha1(cloudpickle.dumps(obj)).hexdigest()[:16]
        except Exception:  # noqa: BLE001 — unpicklable: identity-cached only
            return f"id:{id(obj)}"


def _lowered_again(prog, args):
    """`prog.lower(*args)` once `prog(*args)` has returned: jax's trace and
    lowering caches serve it (0.4 ms, nothing compiles), and the result pins
    no array. None where `prog` cannot be lowered again: the stage table is
    an extra, never an error on the launch path."""
    try:
        return prog.lower(*args)
    except Exception:  # noqa: BLE001 — see above
        return None


def _spanned_program(kind: str, prog):
    """`prog` under a `launch <kind>` span: the host's dispatch of one shard
    program. The first call (trace, lower, compile or persistent-cache
    load, dispatch) is timed into spans.programs() whether or not a
    profiler session runs: it happens once a program. That call also leaves
    its lowering with spans.program_stages(), which reads the program's
    stages off the compiled text when someone asks."""
    first = threading.Lock()  # taken by the first call, never released

    def launch(*args):
        if not first.locked() and first.acquire(blocking=False):
            t0 = time.perf_counter()
            try:
                out = launch(*args)  # `first` is spent: the plain path
            finally:
                spans.program_first_call(kind, time.perf_counter() - t0)
            spans.program_lowered(kind, _lowered_again(prog, args))
            return out
        with spans.span("launch", kind):
            return prog(*args)

    return launch


def _cached_program(key, build):
    prog = _PROGRAM_CACHE.get(key)
    if prog is None:
        prog = _spanned_program(key[0], build())
        _PROGRAM_CACHE[key] = prog
        spans.program_minted(key[0])
    return prog


class _HostMeshStub:
    """Stands in for a jax Mesh on the far side of a pickle: Block only
    reads .size, and mesh_lib.host_get passes numpy through, so a Block whose
    columns are host numpy works unchanged for reading."""

    def __init__(self, size: int):
        self.size = size


# ---------------------------------------------------------------------------
# dense block lifetime (HBM accounting + LRU eviction)
# ---------------------------------------------------------------------------
#
# Every materialized intermediate registers in a per-Context LRU keyed by
# node identity. When the tracked resident bytes exceed
# Configuration.dense_hbm_budget, least-recently-used blocks are RELEASED:
# the node's memoized Block reference is dropped, so HBM frees once no
# computation holds the buffers, and the next access re-materializes from
# lineage — recompute-over-spill, the device analogue of the host tier's
# BoundedMemoryCache LRU (cache.py; the reference leaves eviction as
# todo!(), cache.rs:68-76). Sources are exempt (their Block IS the data —
# nothing to rebuild from; their footprint is gated at creation by the
# streaming planner) and so are unsettled speculative blocks (their pending
# entry must settle/repair through the SAME object).
#
# Multi-process note: in SPMD multihost runs the driver program is
# replicated, so eviction decisions must be identical on every process —
# a divergent decision would make one process re-dispatch exchange
# collectives the others skip. Round-4 advisor finding: weakref liveness
# (GC timing) and LRU touch order are NOT replicated — a user reference
# cycle collects at process-divergent times, and concurrent host-tier
# task threads reorder touches thread-interleaving-dependently. So when
# jax.process_count() > 1 the policy hardens to a deterministic FIFO:
#   - entries are keyed by rdd_id (allocation order is replicated;
#     id() reuse after GC is not),
#   - touches do not reorder (registration order is the eviction order),
#   - accounting uses the byte size RECORDED AT REGISTRATION and an
#     entry leaves the accounting only via eviction or explicit
#     release — never via weakref death (a dead entry's eviction is a
#     deterministic no-op pop; its HBM freed when the object died, only
#     the accounting persists until the sweep reaches it).
# Single-process keeps true LRU with live-byte accounting and dead-ref
# pruning (no cross-process divergence to protect against there).


_lifetime_multiproc_memo: Optional[bool] = None


def _lifetime_multiproc() -> bool:
    # Safe to ask jax here: lifetime hooks only run on nodes that hold a
    # materialized device block, so the backend is already initialized
    # (the CLAUDE.md "never probe backends" rule is about import paths:
    # a process that merely imports vega_tpu must not take the chip).
    # Memoized — process count
    # is fixed once jax.distributed is up (Context joins the mesh before
    # any materialization), and this runs on every touch/sweep in the
    # hot block_spec() path.
    global _lifetime_multiproc_memo
    if _lifetime_multiproc_memo is None:
        try:
            _lifetime_multiproc_memo = jax.process_count() > 1
        except Exception:  # noqa: BLE001 — no backend: single-process
            return False  # don't memoize a pre-init answer
    return _lifetime_multiproc_memo


def _reset_lifetime_multiproc_memo() -> None:
    """mesh.init_multihost calls this next to set_default_mesh(None): a
    process that materialized dense blocks under a single-process Context
    and then joined a jax.distributed mesh (stop() + new multihost Context
    is supported) must re-resolve the eviction policy — keeping the stale
    False memo would run the LRU/weakref policy on a multi-process mesh,
    the exact cross-process divergence the FIFO hardening prevents."""
    global _lifetime_multiproc_memo
    _lifetime_multiproc_memo = None


def _lifetime_lru(ctx) -> dict:
    return ctx.__dict__.setdefault("_dense_block_lru", {})


def _lifetime_touch(rdd) -> None:
    lru = rdd.context.__dict__.get("_dense_block_lru")
    if lru is None:
        return
    if _lifetime_multiproc():
        return  # FIFO: touch order is thread-interleaving-dependent
    entry = lru.pop(rdd.rdd_id, None)
    if entry is not None:
        lru[rdd.rdd_id] = entry  # re-insert at MRU end


def _lifetime_register(rdd) -> None:
    lru = _lifetime_lru(rdd.context)
    blk = rdd._block
    lru.pop(rdd.rdd_id, None)
    lru[rdd.rdd_id] = (weakref.ref(rdd), blk.nbytes if blk is not None
                       else 0)
    _lifetime_evict(rdd.context, keep=rdd.rdd_id)


def _lifetime_forget(rdd) -> None:
    lru = rdd.context.__dict__.get("_dense_block_lru")
    if lru is not None:
        lru.pop(rdd.rdd_id, None)


def _lifetime_sweep(lru: dict, multiproc: bool) -> Tuple[int, list]:
    """Return (total tracked bytes, candidate keys in eviction order).
    Single-process: prunes dead/evicted entries and counts live block
    bytes (LRU->MRU order). Multi-process: counts REGISTERED bytes of
    every entry, dead or alive, in registration order — liveness is GC
    timing, which diverges across processes, so it must not influence
    totals or ordering (dead entries fall out when the evictor reaches
    them, identically everywhere). Concurrent-safe against evict/
    unpersist on other host-tier task threads: every read is a single
    snapshot (.get, one _block capture), never a check-then-reread."""
    live = []
    total = 0
    for key in list(lru):
        entry = lru.get(key)
        if entry is None:
            continue
        ref, reg_bytes = entry
        if multiproc:
            total += reg_bytes
            live.append(key)
            continue
        rdd = ref()
        blk = rdd._block if rdd is not None else None
        if blk is None:
            lru.pop(key, None)
            continue
        total += blk.nbytes
        live.append(key)
    return total, live


def dense_hbm_in_use(ctx) -> int:
    """Tracked device-resident bytes of materialized dense intermediates
    (sources excluded — see the lifetime note above). Single-process this
    prunes dead refs and reports live bytes; multi-process it reports the
    deterministic registered-byte accounting (which may briefly include
    blocks whose owner died — see the multi-process note)."""
    lru = ctx.__dict__.get("_dense_block_lru")
    if not lru:
        return 0
    return _lifetime_sweep(lru, _lifetime_multiproc())[0]


def _lifetime_evict(ctx, keep: Optional[int] = None) -> None:
    from vega_tpu.env import Env

    budget = getattr(Env.get().conf, "dense_hbm_budget", 4 << 30)
    lru = ctx.__dict__.get("_dense_block_lru")
    if not lru:
        return
    multiproc = _lifetime_multiproc()
    total, live = _lifetime_sweep(lru, multiproc)
    if total <= budget:
        return
    for key in live:  # registration (FIFO) / LRU order
        if total <= budget:
            break
        if key == keep:
            continue
        entry = lru.get(key)
        if entry is None:
            continue
        ref, reg_bytes = entry
        rdd = ref()
        blk = rdd._block if rdd is not None else None
        if blk is None:
            # Dead or already-released: deterministic pop, accounting
            # freed. (Multi-process: one process's GC may see the object
            # alive while another's doesn't — both still pop this entry
            # here, subtract the same registered bytes, and dispatch no
            # collectives, so decisions stay aligned.)
            total -= reg_bytes if multiproc else 0
            lru.pop(key, None)
            continue
        if blk.settle is not None:
            # Pending speculation: evictable only once settled. This
            # check is multi-process deterministic: a pending node is
            # strongly held by ctx._dense_pending (entry["rdd"]) on
            # every process, so ref() cannot be dead on one process and
            # pending-alive on another.
            continue
        level = getattr(rdd, "_storage_level", None)
        if level is not None and level.use_disk:
            # persist(MEMORY_AND_DISK / DISK_ONLY): demote the block to
            # the disk tier instead of dropping it — the next access
            # promotes (reload + reshard) rather than recomputing
            # lineage. Accounting below is identical either way (the
            # entry leaves the LRU with the same registered bytes), so
            # the FIFO/registered-byte invariants are untouched.
            _demote_block_to_disk(rdd, blk)
        total -= reg_bytes if multiproc else blk.nbytes
        rdd._block = None
        rdd.__dict__.pop("_pickle_state_memo", None)
        lru.pop(key, None)
        log.debug("dense lifetime: evicted block of rdd %s (%d bytes)",
                  rdd.rdd_id, blk.nbytes)


def _dense_spill_key(rdd) -> str:
    return f"dense-{rdd.rdd_id}"


def _demote_block_to_disk(rdd, blk) -> bool:
    """Write an evicted node's block to the disk tier (store/ DiskStore,
    via the TieredCache raw-block API so spill bytes are counted and a
    BlockSpilled event reaches the bus) as a host-numpy snapshot in the
    SAME shard layout splits() uses for host interop: concatenated
    [n_shards * capacity] columns + per-shard counts + capacity. Promotion
    reproduces device placement bit-identically, so a reloaded node's
    hash_placed/key_sorted claims stay true.

    Multi-process meshes skip demotion (drop-and-recompute, as before):
    gathering host columns dispatches a collective, and eviction can run
    on host-tier task threads whose interleaving is not replicated across
    processes — the same reason splits() pre-gathers on the driver
    thread. A failed spill degrades to recompute, never to bad data."""
    import io

    from vega_tpu.env import Env

    first = next(iter(blk.cols.values()), None)
    if isinstance(first, jax.Array) and not first.is_fully_addressable:
        return False
    cache = Env.get().cache
    if not hasattr(cache, "spill_raw"):  # bare memory cache (unit tests)
        return False
    key = _dense_spill_key(rdd)
    if cache.contains_raw(key):
        return True  # blocks are immutable per rdd_id: one demotion is enough
    try:
        buf = io.BytesIO()
        arrays = {f"col:{n}": np.asarray(c)
                  for n, c in blk.host_cols().items()}
        np.savez(buf, counts=blk.counts_np,
                 capacity=np.int64(blk.capacity), **arrays)
        cache.spill_raw(key, buf.getvalue(), store="dense")
        return True
    except Exception:  # noqa: BLE001 — spill failure means recompute, not loss
        log.exception("dense block spill failed; node will recompute")
        return False


def _load_spilled_block(rdd) -> "Optional[Block]":
    """Promote a demoted node's block back onto the device mesh (checksummed
    read through the disk tier; a corrupt or mesh-mismatched snapshot is a
    miss and the node recomputes from lineage)."""
    import io

    from vega_tpu.env import Env

    level = getattr(rdd, "_storage_level", None)
    if level is None or not level.use_disk:
        return None
    cache = Env.get().cache
    if not hasattr(cache, "read_raw"):
        return None
    data = cache.read_raw(_dense_spill_key(rdd), store="dense")
    if data is None:
        return None
    try:
        with np.load(io.BytesIO(data)) as z:
            counts = np.asarray(z["counts"])
            capacity = int(z["capacity"])
            cols = {n[len("col:"):]: np.asarray(z[n])
                    for n in z.files if n.startswith("col:")}
    except Exception:  # noqa: BLE001
        log.warning("dense spill snapshot unreadable; recomputing",
                    exc_info=True)
        cache.remove_raw(_dense_spill_key(rdd))
        return None
    if len(counts) != rdd.mesh.size:
        return None  # mesh changed since the spill: recompute
    spec = mesh_lib.shard_spec(rdd.mesh)
    return Block(
        cols={n: mesh_lib.host_put(c, spec) for n, c in cols.items()},
        counts=mesh_lib.host_put(counts, spec),
        capacity=capacity, mesh=rdd.mesh, counts_host=counts,
    )


# Attributes a detached clone must NOT carry: lineage links, the Context,
# materialized blocks, and speculation state. Everything else (user fns,
# schemas, op names, scalars) is the per-shard transform state cached
# programs legitimately need for retraces.
_HEAVY_ATTRS = frozenset({
    "context", "_deps", "_dense_parents", "parent", "left", "right",
    "first", "second", "_block", "_pickle_state_memo", "_fp_memo",
    "_cfp_memo", "_checkpointed_rdd", "_deferred_entry",
    "_host_stage_block",
})


def _heavy_value(v) -> bool:
    """Fail-closed backstop for _detach: any attribute VALUE that is (or
    contains, at any container depth) an RDD or Block pins lineage/HBM if
    captured in a process-lifetime program closure — strip it even under
    a name _HEAVY_ATTRS doesn't know (e.g. a future `self.table =
    other_rdd`). Full recursion through tuples/lists/sets/dicts (an RDD
    inside a dict-valued attribute must not slip through); the visited
    set bounds cyclic structures."""
    stack = [v]
    seen = set()
    while stack:
        x = stack.pop()
        if isinstance(x, (RDD, Block)):
            return True
        if id(x) in seen:
            continue
        if isinstance(x, (tuple, list, set, frozenset)):
            seen.add(id(x))
            stack.extend(x)
        elif isinstance(x, dict):
            seen.add(id(x))
            stack.extend(x.keys())
            stack.extend(x.values())
    return False


def _detach(node):
    """Light clone of a node for program-cache closures.

    Programs in the structural cache live for the process (they retrace on
    new capacities), so a closure that captures the node itself pins its
    whole lineage — parents, Context, and every block those ever
    materialize, including un-evictable source data — long after the
    pipeline dies. The clone shares the node's class (so _shard_fn /
    _segment_reduce and friends work unchanged) but carries only the
    light transform state, never lineage or blocks: known-heavy names are
    denylisted, and _heavy_value strips RDD/Block-valued attributes under
    ANY name so a new attribute fails closed, not open."""
    clone = object.__new__(type(node))
    clone.__dict__.update(
        (k, v) for k, v in node.__dict__.items()
        if k not in _HEAVY_ATTRS and not _heavy_value(v))
    return clone


def _detached_chain(chain):
    return [_detach(nd) for nd in chain]


def _yield_rows(rows: dict):
    """Host-facing row iteration over one shard's columns — shared by
    DenseRDD.compute and the unpickled _HostDenseView so the two tiers'
    row semantics cannot drift."""
    names = list(rows)
    if names == [VALUE]:
        yield from rows[VALUE].tolist()
    elif set(names) == {KEY, VALUE}:
        yield from zip(rows[KEY].tolist(), rows[VALUE].tolist())
    else:
        cols = [rows[n] for n in names]
        for i in range(len(cols[0])):
            yield tuple(c[i] for c in cols)


class DenseRDD(RDD):
    """Base dense node. Subclasses implement _materialize() -> Block."""

    def __init__(self, ctx, mesh, deps_rdds: Sequence["DenseRDD"] = ()):
        from vega_tpu.dependency import OneToOneDependency

        super().__init__(ctx, deps=[OneToOneDependency(r) for r in deps_rdds])
        self.mesh = mesh
        self._dense_parents = tuple(deps_rdds)
        self._block: Optional[Block] = None

    def _fp_extra(self):
        """Node-type-specific part of the structural lineage fingerprint
        (closure fingerprints, op names, flags)."""
        return ()

    def _lineage_fp(self):
        """Structural identity of this node's lineage: node types + their
        parameters, NOT rdd ids — two runs of the same pipeline (fresh
        nodes, same shape) share a fingerprint. Keys the exchange capacity
        hints so warm re-runs skip the sizing histogram's device round
        trip (the overflow-retry loop remains the safety net if the data
        distribution changed). Iterative walk + per-node memo: lineages
        can be thousands of narrow nodes deep (the chain materializer
        supports that depth, so this must too), and _fp_extra pickles
        closures — compute each node's fingerprint once."""
        if getattr(self, "_fp_memo", None) is None:
            stack = [(self, False)]
            while stack:
                node, ready = stack.pop()
                if getattr(node, "_fp_memo", None) is not None:
                    continue
                if ready:
                    node._fp_memo = (
                        type(node).__name__, node._fp_extra(),
                    ) + tuple(p._fp_memo for p in node._dense_parents)
                else:
                    stack.append((node, True))
                    stack.extend((p, False) for p in node._dense_parents)
        return self._fp_memo

    # --- process portability ------------------------------------------------
    def __getstate__(self):
        """Dense nodes cross process boundaries as HOST data: jax arrays,
        meshes, and traced programs are process-local, so the block is
        materialized at pickle time (driver side) and ships as numpy
        columns. The restored object is a _HostDenseView — same shard
        structure, iteration-only (the moral analogue of the reference's
        ParallelCollectionSplit carrying its data slice inside the split,
        parallel_collection_rdd.rs:30-56).

        Memoized: a host-tier stage with P tasks pickles this node P times
        (one dumps per task, distributed/backend.py), so the device->host
        gather happens once, not per task. NOTE pickling is intended for
        driver-side task serialization; an incidental pickle (e.g. a user
        closure capturing a DenseRDD) also materializes the node here."""
        memo = getattr(self, "_pickle_state_memo", None)
        if memo is None:
            blk = self.block()
            memo = {
                "context": self.context,
                "rdd_id": self.rdd_id,
                "should_cache": self.should_cache,
                "_pinned": self._pinned,
                "cols": {n: np.asarray(c) for n, c in
                         mesh_lib.host_get(dict(blk.cols)).items()},
                "counts": blk.counts_np,
                "capacity": blk.capacity,
                "dicts": blk.dicts,
            }
            self._pickle_state_memo = memo
        return memo

    def __setstate__(self, state):
        self.__class__ = _HostDenseView
        self.context = state["context"]
        self.rdd_id = state["rdd_id"]
        self._deps = []
        self._partitioner = None
        self.should_cache = state["should_cache"]
        self._pinned = state["_pinned"]
        self._checkpoint_dir = None
        self._checkpointed_rdd = None
        self._host_block = Block(
            cols=state["cols"], counts=state["counts"],
            capacity=state["capacity"],
            mesh=_HostMeshStub(len(state["counts"])),
            dicts=state.get("dicts"),
        )

    def dense(self):
        """Already on the device tier — identity (RDD.dense() lifts host
        lineages; re-lifting a dense node would round-trip the data)."""
        return self

    # --- device plane -------------------------------------------------------
    def block(self) -> Block:
        """Materialize this node's Block (memoized — dense lineage is
        materialized-once, which is the finished version of the reference's
        half-built .cache(), SURVEY.md §2.6). SETTLED: any pending
        speculative exchange is verified (and repaired on overflow) before
        the block is handed out, so callers may trust its data. Launch
        sites that can tolerate speculation (exchange materializers, whose
        outputs register their own pending entry) use block_spec()."""
        blk = self.block_spec()
        if blk.settle is not None:
            blk.settle()
        return blk

    def block_spec(self) -> Block:
        """block() without settlement: the returned Block may still carry
        an unverified overflow flag. Only for consumers that register
        their own pending entry (so a failed speculation invalidates and
        repairs them too) — everything else must use block()."""
        blk = self._block
        if blk is None:
            # A demoted (persist-to-disk) block promotes from the spill
            # tier — a disk hit, not a lineage recompute; anything else
            # rematerializes from lineage.
            blk = _load_spilled_block(self)
            if blk is None:
                blk = self._materialize()
            if blk.dicts is None:
                # ONE attachment point for the dictionary sidecar: every
                # materializer builds plain code-column Blocks; the
                # lineage-propagated dictionaries (_dicts) hang on here so
                # host-facing reads (to_numpy/shard_rows) decode. Sources
                # already carry dicts from from_numpy and keep theirs.
                d = self._dicts()
                if d:
                    blk.dicts = dict(d)
            self._block = blk
            # Only lineage-recomputable nodes enter the eviction LRU:
            # sources set _block in __init__ and never take this path.
            # Return the captured local: a concurrent eviction (host-tier
            # task threads share dense nodes) may null _block again.
            _lifetime_register(self)
        else:
            _lifetime_touch(self)
        return blk

    def persist(self, level=None) -> "DenseRDD":
        """Storage level for this node's materialized device block. Dense
        nodes are materialized-once already (block() memoizes — the
        finished .cache()); MEMORY_AND_DISK / DISK_ONLY additionally make
        HBM-budget eviction *demote* the block to the disk tier as a
        host-numpy snapshot instead of dropping it, and the next access
        *promote* it (reload + reshard, placement-identical) instead of
        recomputing lineage. Device data must be HBM-resident to compute,
        so for dense nodes DISK_ONLY behaves like MEMORY_AND_DISK. Does
        NOT engage the host-tier row cache (should_cache): dense
        partitions live as blocks, not row lists."""
        from vega_tpu.store import StorageLevel

        self._storage_level = StorageLevel.coerce(level)
        return self

    def unpersist(self) -> "DenseRDD":
        """Release this node's materialized device block (the analogue of
        the host tier's uncache; reference eviction is todo!(),
        cache.rs:68-76). Pending speculation settles first so a captured
        Block reference can't observe truncated data. The next access
        re-materializes from lineage. Returns self for chaining."""
        blk = self._block
        if blk is not None:
            if blk.settle is not None:
                blk.settle()
            self._block = None
            self.__dict__.pop("_pickle_state_memo", None)
            _lifetime_forget(self)
        self.__dict__.pop("_host_stage_block", None)
        from vega_tpu.env import Env

        cache = Env.get().cache
        if hasattr(cache, "remove_raw"):  # drop any demoted disk snapshot
            cache.remove_raw(_dense_spill_key(self))
        return self

    def _counts_fp(self):
        """Fetch-free identity of this node's input sizes: materialized
        counts where already host-known, else the tuple of parent
        identities down to leaf sources (whose counts are always
        host-known). Keys the exchange capacity hints WITHOUT forcing the
        driver-blocking counts fetch that keyed them in round 2 — that
        fetch was the RTT between pipelined launches. Same lineage + same
        leaf counts but different data values can alias; the overflow
        retry (settle-repair) is the safety net, as ever."""
        memo = getattr(self, "_cfp_memo", None)
        if memo is not None:
            return memo
        if self._dense_parents:
            # Non-leaf nodes ALWAYS use the structural parents form —
            # never their own materialized counts, which would make the
            # fingerprint depend on whether the node happened to be
            # settled when first fingerprinted (identical warm reruns
            # would mint different hint keys and miss the cache).
            # Iterative (chains can be thousands of nodes deep).
            stack = [(self, False)]
            while stack:
                node, ready = stack.pop()
                if getattr(node, "_cfp_memo", None) is not None:
                    continue
                if not node._dense_parents:
                    node._cfp_memo = node.block_spec().counts_np.tobytes()
                elif ready:
                    node._cfp_memo = tuple(
                        p._cfp_memo for p in node._dense_parents)
                else:
                    stack.append((node, True))
                    stack.extend((p, False) for p in node._dense_parents)
        else:
            # Leaf source: counts are builder-known (block_range /
            # from_numpy / dense_from_block set counts_host) — at worst
            # a settle, never a separate fetch.
            self._cfp_memo = self.block_spec().counts_np.tobytes()
        return self._cfp_memo

    def _materialize(self) -> Block:
        raise NotImplementedError

    @property
    def is_pair(self) -> bool:
        return KEY in dict(self._schema())

    @property
    def hash_placed(self) -> bool:
        """True when every key's rows provably live only on shard
        hash(key) % n — the output of any hash exchange. Downstream
        keyed shuffles over hash-placed inputs elide the exchange
        entirely (one per-shard program, zero collectives): the device
        analogue of the host tier's partitioner-equality shuffle elision
        (reference: co_grouped_rdd.rs:102-127, a CLAUDE.md invariant).
        Key-preserving narrow ops propagate it; anything that can rewrite
        keys resets it.

        PURE: reading this property never materializes anything. Nodes
        whose placement is only knowable post-materialization (the
        reduce's host-exact fold takeover) answer conservatively (False)
        while unmaterialized; exchange planners call _settle_placement()
        first to get the materialized truth."""
        return False

    @property
    def key_sorted(self) -> bool:
        """True when each shard's valid rows are provably key-sorted
        (reduce/group/join outputs). Together with hash_placed this lets
        downstream keyed ops skip their own sort: order survives the
        stable compact of an elided (passthrough) exchange, but NOT a real
        exchange or a union concat."""
        return False

    def _settle_placement(self) -> None:
        """Make hash_placed/key_sorted answer truthfully, materializing
        whatever that requires (explicit side effect — the property reads
        themselves stay pure). Narrow nodes forward to the parent their
        placement delegates to; the reduce materializes itself (its
        host-fold takeover is only known post-exchange); everything else
        is a no-op. Exchange planners MUST call this on an input before
        reading its flags for an elision decision (round-4 advisor:
        a bare property read must never launch an exchange)."""

    def _schema(self) -> Tuple[Tuple[str, jnp.dtype], ...]:
        """(name, dtype) of columns without materializing."""
        raise NotImplementedError

    def _dicts(self) -> Dict[str, np.ndarray]:
        """{column name -> sorted host dictionary array} for every
        dictionary-encoded (string) column of THIS node's output
        (tpu/dict_encoding.py). Pure host metadata, known at
        graph-construction time — never materializes device data.

        Default: union of the parents' dictionaries (first parent wins a
        name tie — binary nodes that mix sides override), filtered to
        this node's schema. Nodes that mint or move columns set
        `_dict_renames` ({out name -> parent name}), which REPLACES the
        walk: only listed columns inherit dict-ness ({} = mints all
        columns fresh, e.g. a traced map). Memoized per node (lineage
        walks are repeated by every public-method gate)."""
        memo = getattr(self, "_dicts_memo", None)
        if memo is not None:
            return memo
        parent_dicts: Dict[str, np.ndarray] = {}
        for p in self._dense_parents:
            for nm, d in p._dicts().items():
                parent_dicts.setdefault(nm, d)
        renames = getattr(self, "_dict_renames", None)
        if renames is not None:
            out = {out_nm: parent_dicts[src]
                   for out_nm, src in renames.items() if src in parent_dicts}
        else:
            out = parent_dicts
        names = {nm for nm, _ in self._schema()}
        res = {nm: d for nm, d in out.items() if nm in names}
        self._dicts_memo = res
        return res

    # --- RDD interop (host tier sees a normal RDD) --------------------------
    @property
    def num_partitions(self) -> int:
        return self.mesh.size

    def _spans_processes(self) -> bool:
        """Does this node's data live on a multi-process (jax.distributed)
        mesh? Read from the materialized block when there is one (no
        backend probe); otherwise from the mesh's device->process map —
        safe, because a Mesh only exists after backend init."""
        blk = self._block
        if blk is not None and blk.cols:
            first = next(iter(blk.cols.values()))
            return (isinstance(first, jax.Array)
                    and not first.is_fully_addressable)
        devs = getattr(self.mesh, "devices", None)
        if devs is None:  # _HostMeshStub: host data, single process
            return False
        try:
            return len({d.process_index for d in devs.flat}) > 1
        except Exception:  # noqa: BLE001 — stub/CPU meshes: no span
            return False

    def splits(self) -> List[Split]:
        # Host-tier interop only (dense actions bypass the scheduler).
        # On a multi-process mesh the block is materialized AND
        # snapshotted to host numpy HERE: splits() runs on the driver
        # thread at stage submission (dag.py submit_missing_tasks /
        # _get_preferred_locs), while compute() fans out to scheduler
        # task threads whose interleaving differs across processes — and
        # jax.distributed collectives must be dispatched in the same
        # order on every process. Materializing here (not just
        # pre-gathering an already-built block, as rounds 3-4 did) also
        # closes the round-4 advisor race: _lifetime_evict may null
        # _block between stage submission and compute(), and the
        # re-materialization would otherwise dispatch collectives from
        # task threads. The snapshot hangs off the node (not the LRU'd
        # Block), so a mid-stage eviction cannot resurrect device work;
        # unpersist() drops it.
        if self._spans_processes() \
                and self.__dict__.get("_host_stage_block") is None:
            blk = self.block()  # driver thread: deterministic collectives
            self._host_stage_block = Block(
                cols={n: np.asarray(c)
                      for n, c in blk.host_cols().items()},
                counts=blk.counts_np, capacity=blk.capacity,
                mesh=_HostMeshStub(self.mesh.size),
                dicts=blk.dicts,
            )
        return [Split(i) for i in range(self.num_partitions)]

    def compute(self, split: Split, task_context=None):
        hb = self.__dict__.get("_host_stage_block")
        if hb is not None:  # multi-process: device-free task threads
            yield from _yield_rows(hb.shard_rows(split.index))
            return
        yield from _yield_rows(self.block().shard_rows(split.index))

    @property
    def columns(self) -> List[str]:
        return [n for n, _ in self._schema()]

    def select(self, *names: str) -> "DenseRDD":
        """Project a subset of columns (narrow, fused). Selecting a wide
        (two-column int64) column — key or value — implicitly keeps its
        low-word partner: the two columns are one logical column."""
        schema = dict(self._schema())
        for n in names:
            if n not in schema:
                raise VegaError(f"no such column: {n!r}")
            if block_lib.is_lo(n) and n[:-len(block_lib.LO_SUFFIX)] \
                    not in names:
                # An orphaned low word decodes to nothing on host reads —
                # data would silently vanish.
                raise VegaError(
                    f"{n!r} is the low word of a wide int64 column; "
                    f"select {n[:-len(block_lib.LO_SUFFIX)]!r} instead "
                    "(the pair travels together)"
                )
        expanded = []
        for n in names:
            expanded.append(n)
            lo = block_lib.lo_of(n)
            if lo in schema and lo not in names:
                expanded.append(lo)
        return _SelectRDD(self, tuple(expanded))

    def rename(self, mapping: dict) -> "DenseRDD":
        """Rename value columns (narrow, fused). A wide int64 column's low
        word travels with it. rename({'w': VALUE}) is the named->canonical
        bridge that re-opens host fallbacks and lv/rv joins for blocks
        built with user column names."""
        schema = dict(self._schema())
        full = {}
        for old, new in mapping.items():
            if old not in schema:
                raise VegaError(f"no such column: {old!r}")
            if old in (KEY, KEY_LO) or new in (KEY, KEY_LO):
                raise VegaError(
                    "the key columns cannot be renamed (or renamed onto): "
                    "a value column renamed to the key name would fabricate "
                    "a pair RDD out of non-key data")
            if block_lib.is_lo(old) or block_lib.is_lo(new):
                raise VegaError(
                    f"the {block_lib.LO_SUFFIX!r} suffix is reserved for "
                    "wide int64 low words; rename the base column instead")
            full[old] = new
            if block_lib.lo_of(old) in schema:
                full[block_lib.lo_of(old)] = block_lib.lo_of(new)
        out_names = [full.get(nm, nm) for nm in schema]
        if len(set(out_names)) != len(out_names):
            raise VegaError(f"rename would collide columns: {out_names}")
        return _RenameRDD(self, full)

    def to_rdd(self) -> RDD:
        """Explicit hand-off to the host tier (identity view)."""
        from vega_tpu.rdd.narrow import MapPartitionsRDD

        return MapPartitionsRDD(self, lambda _i, it: it)

    # --- narrow ops ---------------------------------------------------------
    def _dict_row_gate(self) -> None:
        """Raise _NotTraceable when any column is dictionary-encoded: a
        traced row closure would see int32 codes where the user wrote
        string logic (silently wrong results — codes are private to the
        device tier). The host fallback sees decoded strings, so the
        normal two-tier contract covers strings too."""
        d = self._dicts()
        if d:
            raise _NotTraceable(
                f"dictionary-encoded (string) columns {sorted(d)}; row "
                "closures see decoded strings on the host tier"
            )

    def map(self, f: Callable):
        """Vectorized per-row map if f is traceable, else host fallback
        (the two-tier contract, SURVEY.md §7 hard part 2)."""
        try:
            self._dict_row_gate()
            return _MapRDD(self, f)
        except _NotTraceable as e:
            log.info("dense map fell back to host tier: %s", e)
            return super().map(f)

    def filter(self, predicate: Callable):
        try:
            self._dict_row_gate()
            return _FilterRDD(self, predicate)
        except _NotTraceable as e:
            log.info("dense filter fell back to host tier: %s", e)
            return super().filter(predicate)

    def key_by(self, f: Callable):
        return self.map(lambda x: (f(x), x))

    def map_expand(self, f: Callable, factor: int):
        """Static-arity flat_map: f maps one row to `factor` output rows
        (returned as length-`factor` arrays / tuples of arrays). The fixed
        expansion keeps shapes static — the XLA-compatible subset of
        flat_map (dynamic-arity flat_map falls back to the host tier
        automatically via the normal RDD method)."""
        try:
            self._dict_row_gate()
            return _MapExpandRDD(self, f, factor)
        except _NotTraceable as e:
            log.info("dense map_expand fell back to host tier: %s", e)

            def expand(x):
                out = f(x)
                if isinstance(out, tuple):
                    cols = [np.asarray(o).tolist() for o in out]
                    return list(zip(*cols))
                return np.asarray(out).tolist()

            return super().flat_map(expand)

    def flat_map_ragged(self, f: Callable, max_out_per_row: int):
        """Variable-arity flat_map that stays on device: f maps one row to
        (out, n_valid) — out a (max_out_per_row,) array (or a (keys,
        values) pair of them), n_valid how many lead entries are real.
        This is the XLA-compatible form of the reference's fully-dynamic
        flat_map (rdd.rs:207-214): the per-row bound keeps shapes static;
        genuinely unbounded closures use .flat_map (host tier)."""
        try:
            self._dict_row_gate()
            return _FlatMapRaggedRDD(self, f, max_out_per_row)
        except _NotTraceable as e:
            log.info("dense flat_map_ragged fell back to host tier: %s", e)

            def expand(x):
                out, n = f(x)
                # Same clamp as the device path: host and device results
                # must be identical, only placement may differ.
                n = max(0, min(int(n), max_out_per_row))
                if isinstance(out, tuple):
                    ks, vs = (np.asarray(o)[:n] for o in out)
                    return list(zip(ks.tolist(), vs.tolist()))
                return np.asarray(out)[:n].tolist()

            return super().flat_map(expand)

    def zip(self, other):
        """Dense-dense zip of single-value-column RDDs: per-shard column
        concatenation when shard counts line up (host semantics:
        rdd.rs:818-829); pair / multi-column operands use the host path so
        elements keep their full structure."""
        if (isinstance(other, DenseRDD) and other.mesh == self.mesh
                and [n for n, _ in self._schema()] == [VALUE]
                and [n for n, _ in other._schema()] == [VALUE]):
            return _DenseZipRDD(self, other)
        return RDD.zip(self, other)

    def zip_with_index(self):
        """(value, global index) — the index offsets come from a tiny
        counts transfer at materialization; no second data pass (the host
        tier needs a full counting job, base.py zip_with_index)."""
        if self.is_pair:
            raise VegaError("zip_with_index on pair DenseRDD — use values()")
        if self._wide_value():
            # the wide pair would become a wide KEY with dropped low word
            return RDD.zip_with_index(self)
        return _ZipWithIndexRDD(self)

    def map_values(self, f: Callable):
        if not self.is_pair:
            raise VegaError("map_values on non-pair DenseRDD")
        # Collapse wide (name, name.lo) int64 pairs to ONE logical column
        # each, so user-facing counts and error messages never leak the
        # internal .lo encoding as a phantom second column.
        names = [nm for nm, _ in self._schema()]
        wide_los = set(block_lib.wide_value_pairs(names).values())
        value_names = [nm for nm in names
                       if nm not in (KEY, KEY_LO) and nm not in wide_los]
        if value_names == [VALUE] and block_lib.lo_of(VALUE) in wide_los:
            # Wide int64 VALUE: no traced row form, but the canonical
            # pair layout decodes to (k, v) rows — silent host fallback,
            # the two-tier contract.
            log.info("dense map_values fell back to host tier: wide "
                     "int64 value column")
            return super().map_values(f)
        if isinstance(self, _JoinRDD):
            # A joined block's rows are (k, (lv, rv)) on both tiers
            # (_JoinRDD._rows), so f has a host form whatever the sides
            # hold; the device traces it on the pair of scalars.
            if wide_los or set(value_names) & set(self._dicts()):
                log.info("dense map_values fell back to host tier: wide "
                         "int64 or dictionary-encoded (string) side of a "
                         "join")
                return super().map_values(f)
        elif len(value_names) != 1:
            # Named/multi-column blocks (wide or not) have no host (k, v)
            # row form — the documented crisp-error exception.
            raise VegaError(
                "map_values needs exactly one value column (have "
                f"{value_names}); use select(...) or a tuple-valued "
                "reduce_by_key on multi-column blocks"
            )
        elif value_names[0] in self._dicts():
            if value_names == [VALUE]:
                # Dictionary-encoded (string) VALUE: a traced f would see
                # int32 codes, not strings; the canonical pair layout
                # decodes to (k, v) rows — silent host fallback.
                log.info("dense map_values fell back to host tier: "
                         "dictionary-encoded (string) value column")
                return super().map_values(f)
            raise VegaError(
                f"map_values over dictionary-encoded (string) column "
                f"{value_names[0]!r} on a named block has no device trace "
                f"or host row form; rename({{{value_names[0]!r}: "
                f"{VALUE!r}}}) to the canonical layout for the host "
                "fallback"
            )
        elif value_names[0] in block_lib.wide_value_pairs(names):
            # ONE named wide column: a traced f would see only the hi
            # word, and a named block has no host (k, v) row form to fall
            # back on — crisp, naming the one logical column.
            raise VegaError(
                f"map_values over wide int64 column {value_names[0]!r} on "
                "a named block has no device trace or host row form; "
                f"rename({{{value_names[0]!r}: {VALUE!r}}}) to the "
                "canonical layout for the host fallback"
            )
        try:
            return _MapValuesRDD(self, f)
        except _NotTraceable as e:
            log.info("dense map_values fell back to host tier: %s", e)
            return super().map_values(f)

    # --- shuffles -----------------------------------------------------------
    def reduce_by_key(self, func=None, partitioner_or_num=None, *,
                      op: Optional[str] = None,
                      exchange: Optional[str] = None):
        """Device shuffle: pre-combine, all_to_all, segment-reduce.
        `op` in {'add','min','max','prod'} takes the XLA segment fast path;
        a traceable binary `func` uses the segmented associative scan.
        partitioner_or_num is accepted for API parity; dense output is always
        one partition per mesh shard.

        Dtype contract: int64 values use the wide (hi, lo) encoding and
        op='add' tracks signed overflow on device (kernels.wide_add_checked
        flags ride the exchange like capacity flags). A set flag routes to
        a host-exact fold: totals that fit int64 are rebuilt densely
        (transient wraps under reassociation are harmless — mod-2^64
        results equal exact totals whenever they fit), totals beyond int64
        raise a crisp VegaError pointing at the host tier, which keeps
        exact Python bignums. op='add' and an untraceable lambda a, b:
        a + b therefore agree wherever both are representable."""
        if not self.is_pair:
            raise VegaError("reduce_by_key on non-pair DenseRDD")
        if op is None and func is None:
            raise TypeError("need func or op")
        if op is None:
            inferred = _infer_named_op(func)
            if inferred is not None:
                op = inferred
            if op == "prod" and block_lib.wide_value_pairs(
                    nm for nm, _ in self._schema()):
                # A multiplication CLOSURE over wide int64 values: the
                # named path would reject it crisply, but the user gave a
                # closure, so the fallback contract applies — let the
                # func path raise _NotTraceable and fold on the host.
                op = None
        dict_vals = sorted(nm for nm in self._dicts()
                           if nm not in (KEY, KEY_LO))
        if dict_vals and op not in ("min", "max"):
            # Codes are RANK codes, so min/max of codes == lexicographic
            # min/max of the strings (one dictionary per lineage; binary
            # ops unify first) and those folds stay on device. Any other
            # fold (add/prod/closure) would compute on the code VALUES —
            # no string meaning — so host semantics apply (e.g. '+'
            # concatenates strings there).
            plain = {nm for nm, _ in self._schema()
                     if not block_lib.is_lo(nm)}
            if plain != {KEY, VALUE}:
                raise VegaError(
                    "reduce_by_key over dictionary-encoded (string) value "
                    f"columns {dict_vals} needs op='min'/'max' (codes are "
                    "rank codes; other folds have no string meaning on "
                    "device), and a named/multi-column block has no host "
                    "row form to fall back on"
                )
            log.info("dense reduce_by_key fell back to host tier: "
                     "dictionary-encoded (string) value column under "
                     "op=%s", op)
            import operator

            host_func = func if func is not None else \
                {"add": operator.add, "prod": operator.mul}[op]
            return super().reduce_by_key(host_func, partitioner_or_num)
        if op is not None:
            return _with_exchange(_ReduceByKeyRDD(self, op=op, func=None),
                                  exchange)
        try:
            return _with_exchange(_ReduceByKeyRDD(self, op=None, func=func),
                                  exchange)
        except _NotTraceable as e:
            plain = {nm for nm, _ in self._schema()
                     if not block_lib.is_lo(nm)}
            if plain != {KEY, VALUE}:
                # Named/multi-column blocks have no host-tier row form a
                # binary func could fold (compute() yields schema-order
                # tuples, not (k, v) pairs) — the silent fallback would
                # produce WRONG results, so this is the documented
                # exception to the fallback-never-error contract. (Wide
                # keys/values are fine: they decode to (k, v) rows.)
                raise VegaError(
                    "reduce_by_key over a named/multi-column block needs a "
                    f"traceable binop (not traceable: {e}); use "
                    "op='add'/'min'/'max'/'prod' or a traceable tuple binop"
                ) from e
            log.info("dense reduce_by_key fell back to host tier: %s", e)
            return super().reduce_by_key(func, partitioner_or_num)

    def sum_by_key(self):
        return self.reduce_by_key(op="add")

    def count_by_key_dense(self):
        """(key, occurrence count) pairs. Works on any keyed block — pair,
        key-only (a bare key column is a valid thing to count), and
        named/multi-column — by synthesizing a ones column and riding the
        named-op exchange; no traced user closure involved."""
        if not self.is_pair:
            raise VegaError("count_by_key_dense on un-keyed DenseRDD")
        return _OnesValueRDD(self).reduce_by_key(op="add")

    def combine_by_key(self, create_combiner: Callable,
                       merge_value: Callable, merge_combiners: Callable,
                       partitioner_or_num=None, *,
                       exchange: Optional[str] = None):
        """Device combine_by_key for scalar traceable combiners
        (reference: pair_rdd.rs:20-33): lowered to
        map_values(create_combiner) + segment-reduce(merge_combiners),
        which equals the host semantics under the standard combiner
        compatibility contract merge_value(c, v) ==
        merge_combiners(c, create_combiner(v)). Untraceable or non-scalar
        combiners fall back to the host tier DIRECTLY (the host mixin's
        own reduce_by_key lowers through self.combine_by_key, so the
        fallback must not re-dispatch through this override)."""
        if not self.is_pair:
            raise VegaError("combine_by_key on non-pair DenseRDD")
        if block_lib.wide_value_pairs(nm for nm, _ in self._schema()) or \
                any(nm not in (KEY, KEY_LO) for nm in self._dicts()):
            # Wide int64 values: _MapValuesRDD would trace create_combiner
            # over the hi word alone and silently drop the low word.
            # Dictionary-encoded (string) values: the combiner would see
            # int32 codes, not strings. Either way no device trace -> host
            # tier (exact int64 / decoded-string combiners).
            log.info("dense combine_by_key fell back to host tier: wide "
                     "int64 or dictionary-encoded value column")
            from vega_tpu.rdd.pair import PairOpsMixin

            return PairOpsMixin.combine_by_key(
                self, create_combiner, merge_value, merge_combiners,
                partitioner_or_num,
            )
        try:
            mapped = _MapValuesRDD(self, create_combiner)
            op = _infer_named_op(merge_combiners)
            node = _ReduceByKeyRDD(mapped, op=op,
                                   func=None if op else merge_combiners)
            return _with_exchange(node, exchange)
        except _NotTraceable as e:
            log.info("dense combine_by_key fell back to host tier: %s", e)
            from vega_tpu.rdd.pair import PairOpsMixin

            return PairOpsMixin.combine_by_key(
                self, create_combiner, merge_value, merge_combiners,
                partitioner_or_num,
            )

    # fold_by_key / aggregate_by_key deliberately have NO device lowering:
    # their zero is applied once per key per PARTITION (host tier,
    # rdd/pair.py:74-93 — our extension; the reference has neither op), and
    # that partition-coupled semantic is not expressible as an associative
    # device combine without silently changing results for non-neutral
    # zeros. For the device path, express the job as
    # map_values(...) + reduce_by_key(op=...) explicitly.

    def group_by_key(self, partitioner_or_num=None,
                     exchange: Optional[str] = None):
        """Device group_by_key: exchange by key hash, sort within shard.
        The result block holds sorted runs; collect() reassembles the
        (key, [values]) lists on the host — the dense analogue of the
        reference's Vec-collecting aggregator (aggregator.rs:33-53)."""
        if not self.is_pair:
            raise VegaError("group_by_key on non-pair DenseRDD")
        return _with_exchange(_GroupByKeyRDD(self), exchange)

    def join(self, other, partitioner_or_num=None,
             exchange: Optional[str] = None):
        """Device sort-merge join with full duplicate-key semantics (dup x
        dup product per key, reference pair_rdd.rs:104-121). Falls back to
        the host cogroup-based join only when `other` is not dense or an
        explicit partitioner is requested."""
        if self._dense_joinable(other, partitioner_or_num):
            pair = _align_keys(self, other)
            if pair is not None:
                return _with_exchange(_JoinRDD(*pair), exchange)
        self._reject_named_join([other], "join")
        return super().join(other, partitioner_or_num)

    def _dense_joinable(self, other, partitioner_or_num) -> bool:
        """Same preconditions as the dense cogroup: both dense pairs, no
        explicit partitioner request, one mesh (mismatched meshes would pair
        unrelated shards), and BOTH sides in the canonical value layout —
        the join kernel names its outputs lv/rv, so a named/multi-column
        side would come out mangled (see _reject_named_join)."""
        return (isinstance(other, DenseRDD) and self.is_pair and other.is_pair
                and partitioner_or_num is None and other.mesh == self.mesh
                and _canonical_value_layout(self._schema())
                and _canonical_value_layout(other._schema()))

    def _reject_named_join(self, others, op: str) -> None:
        """Named/multi-column pair blocks can reach neither the dense join
        (its lv/rv output contract is (k, (lv, rv)) rows) nor the host
        cogroup fallback (named blocks have no host-tier (k, v) row form)
        — the documented crisp-error exception to the silent-fallback
        contract, same as reduce_by_key's untraceable-binop case."""
        for label, side in [("left", self)] + [("right", o) for o in others]:
            if (isinstance(side, DenseRDD) and side.is_pair
                    and not _canonical_value_layout(side._schema())):
                raise VegaError(
                    f"{op} over a named/multi-column DenseRDD ({label} side"
                    f" columns {[nm for nm, _ in side._schema()]}) has no"
                    " (k, v) row form on either tier; select(...) down to"
                    f" one value column and rename(...) it to {VALUE!r}"
                    " first"
                )

    def left_outer_join(self, other, partitioner_or_num=None,
                        fill_value=0, exchange: Optional[str] = None):
        """Device left-outer join (duplicate keys allowed on both sides):
        unmatched left rows keep fill_value in the right column (None is
        not representable
        in a dense column — host semantics with None come via
        .to_rdd().left_outer_join(...)). The host fallback also honors
        fill_value so results don't depend on which path ran."""
        wide_right = isinstance(other, DenseRDD) and other.is_pair and \
            block_lib.wide_value_pairs(nm for nm, _ in other._schema())
        dict_right = isinstance(other, DenseRDD) and other.is_pair and \
            any(nm not in (KEY, KEY_LO) for nm in other._dicts())
        if fill_value is not None and not wide_right and not dict_right \
                and self._dense_joinable(other, partitioner_or_num):
            # wide_right gate: the kernel fills unmatched right columns
            # with one scalar per column, which would land RAW in the
            # encoded (hi, lo) words and decode to garbage — the host
            # path fills the real int64. dict_right likewise: the fill
            # scalar would land in the CODE column and decode to an
            # arbitrary dictionary string instead of fill_value.
            pair = _align_keys(self, other)
            if pair is not None:
                return _with_exchange(
                    _JoinRDD(*pair, outer=True, fill_value=fill_value),
                    exchange,
                )
        self._reject_named_join([other], "left_outer_join")
        if fill_value is None:
            # Host None semantics (a dense column can't hold None).
            return super().left_outer_join(other, partitioner_or_num)
        # Host fallback with fill: emit per GROUP so a legitimate None right
        # value is never conflated with "unmatched".

        def emit(groups):
            lvs, rvs = groups
            if not rvs:
                return [(lv, fill_value) for lv in lvs]
            return [(lv, rv) for lv in lvs for rv in rvs]

        return self.cogroup(
            other, partitioner_or_num=partitioner_or_num
        ).flat_map_values(emit)

    def cogroup(self, *others, partitioner_or_num=None):
        """Dense-dense cogroup: both sides exchange + sort on device (hash
        placement is shared, so co-keyed rows land on the same shard); only
        the ragged (k, ([lvs], [rvs])) assembly happens on the host.
        Reference semantics: pair_rdd.rs:123-155 / co_grouped_rdd.rs."""
        if len(others) == 1 and self._dense_joinable(others[0],
                                                     partitioner_or_num):
            # An explicit partitioner request or a mesh mismatch must honor
            # host-path semantics (and mismatched meshes would pair
            # unrelated shards) — those fall through to the host cogroup.
            # Key widths/dtypes must align so co-keyed rows share a shard.
            pair = _align_keys(self, others[0])
            if pair is not None:
                return _DenseCoGroupRDD(*pair)
        self._reject_named_join(others, "cogroup")
        return super().cogroup(*others, partitioner_or_num=partitioner_or_num)

    def cartesian(self, other):
        """Device cross product (BASELINE config 4; reference
        cartesian_rdd.rs): the right side replicates to every shard and
        each shard ragged-expands its left rows against it — one program,
        no collectives beyond the replication. Products too big for the
        HBM budget (or non-dense/multi-column operands) use the host
        tier's lazy cartesian, which streams instead of materializing."""
        from vega_tpu.env import Env

        if (isinstance(other, DenseRDD) and other.mesh == self.mesh
                and [n for n, _ in self._schema()] == [VALUE]
                and [n for n, _ in other._schema()] == [VALUE]
                and not self._dicts() and not other._dicts()):
            # dict gate: the kernel snapshots the right side via
            # to_numpy(), which decodes strings — re-staging them on
            # device has no form. The host tier streams decoded rows.
            budget = getattr(Env.get().conf, "dense_hbm_budget", 4 << 30)
            try:
                return _CartesianDenseRDD(self, other, budget)
            except _NotTraceable as e:
                log.info("dense cartesian fell back to host tier: %s", e)
        return RDD.cartesian(self, other)

    def sort_by_key(self, ascending: bool = True, num_partitions=None,
                    sample_size_hint: int = 4096,
                    exchange: Optional[str] = None):
        """Distributed sample sort: driver samples keys, computes range
        bounds, range-exchange, local sort (BASELINE config 5)."""
        if not self.is_pair:
            raise VegaError("sort_by_key on non-pair DenseRDD")
        return _with_exchange(_SortByKeyRDD(self, ascending, sample_size_hint),
                              exchange)

    def distinct(self, num_partitions=None):
        if self.is_pair:
            return super().distinct(num_partitions)
        keyed = _MapRDD(self, lambda v: (v, jnp.int32(0)))
        # Trusted internal closure: the value moves to the key unchanged,
        # so dict-ness (string codes) follows it — dedup by code == dedup
        # by string within one lineage's dictionary.
        keyed._dict_renames = {KEY: VALUE}
        return _ReduceByKeyRDD(keyed, op="min", func=None).keys_dense()

    def _dense_set_op_ok(self, other) -> bool:
        """Device set ops need value RDDs on one mesh with MATCHING value
        dtypes: an int32 2 and a float32 2.0 hash to different buckets on
        device but compare equal on the host, so mismatched dtypes must
        take the host path (Python equality semantics), never silently
        miss matches."""
        return (isinstance(other, DenseRDD) and other.mesh == self.mesh
                and not self.is_pair and not other.is_pair
                and dict(self._schema())[VALUE]
                == dict(other._schema())[VALUE])

    def intersection(self, other, num_partitions=None):
        """Device set intersection of value RDDs: each side dedups
        through a keyed reduce (output hash-placed and key-sorted, so the
        join elides BOTH exchanges and sorts), then keeps the joined keys
        (reference semantics: rdd.rs:831-841, deduplicated)."""
        if self._dense_set_op_ok(other):
            pair = _unify_dict_cols(self, other, (VALUE,))
            if pair is None:  # dict-ness mismatch: only host equality holds
                return RDD.intersection(self, other, num_partitions)
            left, right = pair

            def dedup(side):
                keyed = _MapRDD(side, lambda v: (v, jnp.int32(0)))
                keyed._dict_renames = {KEY: VALUE}  # value moves to key
                return _ReduceByKeyRDD(keyed, op="min", func=None)

            return _JoinRDD(dedup(left), dedup(right)).keys_dense()
        return RDD.intersection(self, other, num_partitions)

    def subtract(self, other, num_partitions=None):
        """Device set subtraction: keep self's elements (duplicates
        included) whose value never appears in `other` — a left outer
        join against other's deduped values with an unambiguous marker
        (right values are all 1; fill is 0), filtered on the device.
        The marks side is a reduce output, so its exchange elides
        (reference semantics: rdd.rs:843-870)."""
        if self._dense_set_op_ok(other):
            pair = _unify_dict_cols(self, other, (VALUE,))
            if pair is None:  # dict-ness mismatch: only host equality holds
                return RDD.subtract(self, other, num_partitions)
            left, right = pair
            keyed = _MapRDD(left, lambda v: (v, jnp.int32(1)))
            keyed._dict_renames = {KEY: VALUE}  # value moves to key
            marked = _MapRDD(right, lambda v: (v, jnp.int32(1)))
            marked._dict_renames = {KEY: VALUE}
            marks = _ReduceByKeyRDD(marked, op="min", func=None)
            joined = _JoinRDD(keyed, marks, outer=True, fill_value=0)
            # Trusted internal predicate: it reads only the int32 mark
            # column, so construct _FilterRDD directly — the public
            # filter's dict gate would see the (possibly dict-encoded)
            # KEY and needlessly force the host tier.
            return _FilterRDD(
                joined.select(KEY, "rv"), lambda row: row[1] == 0
            ).keys_dense()
        return RDD.subtract(self, other, num_partitions)

    def keys_dense(self):
        if KEY_LO in dict(self._schema()):
            # int64 keys cannot live in a single device value column;
            # hand off to the host tier (decoded rows).
            return self.to_rdd().map(lambda kv: kv[0])
        return _ProjectRDD(self, KEY)

    def values_dense(self):
        if self._wide_value():
            # keep the wide pair on device: select() carries the low-word
            # partner, yielding a keyless wide block (named reductions
            # fold it on device; closures fall back to decoded rows)
            return self.select(VALUE)
        return _ProjectRDD(self, VALUE)

    # --- actions ------------------------------------------------------------
    def count(self) -> int:
        return self.block().num_rows

    def collect(self) -> list:
        cols = self.block().to_numpy()
        names = list(cols)
        with spans.span("pivot"):
            if names == [VALUE]:
                return cols[VALUE].tolist()
            if set(names) == {KEY, VALUE}:
                return list(zip(cols[KEY].tolist(), cols[VALUE].tolist()))
            return list(zip(*[cols[n].tolist() for n in names]))

    def collect_arrays(self) -> dict:
        """Columnar collect — no per-row Python objects."""
        return self.block().to_numpy()

    def _wide_value(self) -> bool:
        """True when VALUE is a wide (two-column int64) encoding."""
        return block_lib.lo_of(VALUE) in dict(self._schema())

    def sum(self):
        return self._named_reduce("add")

    def min(self):
        return self._named_reduce("min")

    def max(self):
        return self._named_reduce("max")

    def mean(self):
        n = self.count()
        if n == 0:
            raise VegaError("mean of empty DenseRDD")
        return self.sum() / n

    def reduce(self, f: Callable):
        """Arbitrary traceable binop: per-shard segmented reduce on device,
        tiny cross-shard fold on the driver (two-level reduction,
        SURVEY.md §7 step 3; host-tier semantics rdd.rs:274-309)."""
        blk = self.block()
        col = VALUE if not self.is_pair else None
        if col is None:
            return super().reduce(f)  # pairs: host semantics
        if self._wide_value():
            # No scalar row form for wide int64 — host fold sees the
            # decoded int64s (and keeps exact bignum arithmetic).
            return super().reduce(f)
        if VALUE in self._dicts():
            # Dictionary-encoded strings: the traced binop would fold
            # int32 codes — host fold sees the decoded strings.
            return super().reduce(f)
        cap = blk.capacity

        def shard_reduce(vals, counts):
            count = counts[0]
            cols = {VALUE: vals}
            combine = lambda a, b: {VALUE: f(a[VALUE], b[VALUE])}
            # Single segment: constant key over valid rows.
            keyed = dict(cols)
            keyed["__k"] = jnp.zeros((cap,), jnp.int32)
            out, n_out = kernels.segment_reduce_sorted(
                keyed, count, "__k", combine, presorted=True
            )
            return out[VALUE][:1], (n_out > 0).reshape(1)

        prog = _cached_program(
            ("reduce", self.mesh, _fp(f)),
            lambda: _shard_program(self.mesh, shard_reduce, 2, (_SPEC, _SPEC)),
        )
        partials, flags = prog(blk.cols[VALUE], blk.counts)
        partials, flags = mesh_lib.host_get((partials, flags))  # one RTT
        partials, flags = np.asarray(partials), np.asarray(flags)
        vals = [partials[i] for i in range(len(flags)) if flags[i]]
        if not vals:
            raise VegaError("reduce() of empty RDD")
        acc = vals[0]
        for x in vals[1:]:
            acc = np.asarray(f(acc, x))
        return acc.item() if acc.ndim == 0 else acc

    def _named_reduce(self, op: str):
        vdict = self._dicts().get(VALUE)
        if vdict is not None and op == "add":
            # A sum of dictionary codes has no string meaning, and there
            # is no host sum of strings either — crisp, not silent.
            raise VegaError(
                "sum() over a string (dictionary-encoded) column has no "
                "meaning; min()/max() are the defined string reductions"
            )
        blk = self.block()
        if self.is_pair:
            raise VegaError(f"{op}() on pair DenseRDD — reduce values instead")
        if block_lib.lo_of(VALUE) in blk.cols:
            return self._named_reduce_wide(op, blk)

        def shard_fn(vals, counts):
            partial = kernels.masked_reduce(vals, counts[0], op)
            return partial.reshape((1,) + partial.shape)

        prog = _cached_program(
            ("named_reduce", self.mesh, op),
            lambda: _shard_program(self.mesh, shard_fn, 2, _SPEC),
        )
        partials = np.asarray(mesh_lib.host_get(prog(blk.cols[VALUE], blk.counts)))
        if op == "add":
            return partials.sum(axis=0).item()
        code = (partials.min(axis=0) if op == "min"
                else partials.max(axis=0)).item()
        if vdict is not None:
            # min/max of rank codes == lexicographic min/max; decode the
            # winning code back to its string at this collect boundary.
            # An out-of-range code is the masked_reduce identity sentinel:
            # every row was padding.
            if not 0 <= code < len(vdict):
                raise VegaError(f"{op}() of empty DenseRDD")
            return vdict[code].item()
        return code

    def _named_reduce_wide(self, op: str, blk: Block):
        """sum/min/max over a wide (two-column int64) keyless VALUE: one
        per-shard device fold with the same carry/lex combine the keyed
        exchanges use, then an exact Python fold over the n_shards
        partials on the driver. add partials carry the sticky overflow
        flag (kernels.wide_add_checked) — a flagged shard's partial may
        have wrapped, so the driver refolds exactly from the decoded
        rows. Actions return Python ints, so even beyond-int64 totals
        come back exact (host-tier semantics)."""
        vlo = block_lib.lo_of(VALUE)
        track = op == "add"

        def shard_fold(hi, lo, counts):
            count = counts[0]
            cap = hi.shape[0]
            keyed = {"__k": jnp.zeros((cap,), jnp.int32), VALUE: hi,
                     vlo: lo}
            names = [VALUE, vlo]
            if track:
                keyed[_SOVF] = jnp.zeros((cap,), jnp.int32)
                names.append(_SOVF)
            combine = _named_wide_combine(
                op, names, {VALUE: vlo},
                ovf_name=_SOVF if track else None)
            out, n_out = kernels.segment_reduce_sorted(
                keyed, count, "__k", combine, presorted=True)
            flag = out[_SOVF][:1] if track else jnp.zeros((1,), jnp.int32)
            return (out[VALUE][:1], out[vlo][:1], flag,
                    (n_out > 0).reshape(1).astype(jnp.int32))

        prog = _cached_program(
            ("named_reduce_wide", self.mesh, op),
            lambda: _shard_program(self.mesh, shard_fold, 3, (_SPEC,) * 4),
        )
        his, los, flags, nonempty = (
            np.asarray(x) for x in mesh_lib.host_get(
                prog(blk.cols[VALUE], blk.cols[vlo], blk.counts)))
        valid = nonempty.reshape(-1) != 0
        partials = block_lib.decode_i64(his.reshape(-1), los.reshape(-1))
        if op == "add":
            if np.any(flags.reshape(-1)[valid]):
                # some shard partial wrapped int64: exact host refold
                col = blk.to_numpy()[VALUE]
                return sum(int(x) for x in col.tolist())
            return sum(int(x) for x in partials[valid])
        picked = partials[valid]
        if picked.size == 0:
            raise VegaError(f"{op}() of empty DenseRDD")
        return int(picked.min()) if op == "min" else int(picked.max())

    def sample(self, with_replacement: bool, fraction: float,
               seed: Optional[int] = None):
        """Device-side Bernoulli sampling (per-shard threefry stream,
        host-tier analogue: utils/random.py BernoulliSampler). Poisson
        (with-replacement) sampling falls back to the host tier."""
        if with_replacement:
            return RDD.sample(self, True, fraction, seed)
        return _SampleRDD(self, fraction, seed or 0)

    def union(self, other):
        """Dense-dense union: per-shard block concatenation in one program;
        anything else falls back to the host UnionRDD."""
        if isinstance(other, DenseRDD) and \
                dict(self._schema()) == dict(other._schema()):
            names = tuple(nm for nm, _ in self._schema())
            pair = _unify_dict_cols(self, other, names)
            if pair is None:  # dict-ness mismatch: host rows compare right
                return RDD.union(self, other)
            return _DenseUnionRDD(*pair)
        return RDD.union(self, other)

    def count_by_value(self) -> dict:
        """Device count_by_value: value->key exchange + segment count
        (host semantics: rdd.rs:450-464)."""
        if self.is_pair or self._wide_value():
            # wide: no scalar row form for the value->key map closure
            return RDD.count_by_value(self)
        keyed = _MapRDD(self, lambda x: (x, jnp.int32(1)))
        # Trusted internal closure: the value moves to the key unchanged,
        # so dict-ness follows it; counts per code == counts per string,
        # and collect() decodes the keys.
        keyed._dict_renames = {KEY: VALUE}
        return dict(_ReduceByKeyRDD(keyed, op="add", func=None).collect())

    def take_ordered(self, n: int, key=None) -> list:
        """Smallest n via per-shard lax.top_k (values) or masked row sort
        (pairs, ordered like host tuples: key then values) + driver merge
        (host analogue: BoundedPriorityQueue, rdd.rs:1124-1153). Custom key
        functions fall back to the host path — closures don't trace into
        an ordering."""
        if key is not None:
            return RDD.take_ordered(self, n, key)
        if self.is_pair or self._wide_value():
            # wide int64 values: the row sort orders the adjacent
            # (VALUE, VALUE.lo) pair lexicographically == int64 order
            return self._device_topk_rows(n, largest=False)
        return self._device_topk(n, largest=False)

    def top(self, n: int, key=None) -> list:
        if key is not None:
            return RDD.top(self, n, key)
        if self.is_pair or self._wide_value():
            return self._device_topk_rows(n, largest=True)
        return self._device_topk(n, largest=True)

    def _device_topk(self, n: int, largest: bool) -> list:
        blk = self.block()
        k = min(n, blk.capacity)

        @spans.stage("topk")
        def shard_topk(vals, counts):
            mask = kernels.valid_mask(vals.shape[0], counts[0])
            if largest:
                if jnp.issubdtype(vals.dtype, jnp.floating):
                    lo = jnp.array(-jnp.inf, vals.dtype)
                else:
                    lo = jnp.array(jnp.iinfo(vals.dtype).min, vals.dtype)
                masked = jnp.where(mask, vals, lo)
                best, _ = lax.top_k(masked, k)
            else:
                hi = kernels._orderable_max(vals)
                masked = jnp.where(mask, vals, hi)
                if jnp.issubdtype(vals.dtype, jnp.floating):
                    best = -lax.top_k(-masked, k)[0]
                else:
                    # Bitwise complement is an overflow-free order flip for
                    # ints (arithmetic negation wraps on iinfo.min).
                    best = ~lax.top_k(~masked, k)[0]
            n_valid = jnp.minimum(counts[0], k)
            return best, n_valid.reshape(1)

        prog = _cached_program(
            ("topk", self.mesh, k, largest),
            lambda: _shard_program(self.mesh, shard_topk, 2, (_SPEC, _SPEC)),
        )
        best, n_valid = prog(blk.cols[VALUE], blk.counts)
        best, n_valid = mesh_lib.host_get((best, n_valid))  # one RTT
        vdict = self._dicts().get(VALUE)
        with spans.span("pivot"):
            best = np.asarray(best).reshape(blk.n_shards, k)
            n_valid = np.asarray(n_valid)
            candidates = np.concatenate(
                [best[s, : n_valid[s]] for s in range(blk.n_shards)]
            ) if blk.n_shards else np.empty((0,))
            candidates = np.sort(candidates)
            if largest:
                candidates = candidates[::-1]
            if vdict is not None:
                # Rank codes ordered == strings ordered; decode the
                # survivors at this collect boundary.
                candidates = vdict[candidates.astype(np.int64)]
            out = candidates[:n].tolist()
            return out

    def _device_topk_rows(self, n: int, largest: bool) -> list:
        """First/last n ROWS in natural element order — the order of the
        tuples collect() emits (schema order; for the canonical pair
        block that is (key, value), matching the host tier's tuple
        ordering). Guarantees sorted(collect())[:n] == take_ordered(n)
        whatever the schema. Per shard: one stable lax.sort over
        (validity, every column), slice n; driver merges the n_shards*n
        survivors with the same lexicographic order. Total-order caveat:
        XLA sorts NaN after +inf; Python's NaN comparisons are unordered,
        so like the host sort the result is only well-defined for
        NaN-free data."""
        blk = self.block()
        names = [nm for nm, _ in self._schema()]
        # Sort operands in schema order: a two-column int64 key sits as
        # adjacent (KEY=hi, KEY_LO=lo) columns, so lexicographic schema
        # order IS int64 order in place.
        k = min(max(n, 1), blk.capacity)
        def shard_sorted(counts, *cols):
            capacity = cols[0].shape[0]
            invalid = ~kernels.valid_mask(capacity, counts[0])
            operands = [invalid.astype(jnp.int32)]
            for c in cols:
                if largest:
                    if jnp.issubdtype(c.dtype, jnp.floating):
                        flipped = -c
                    else:
                        flipped = ~c  # overflow-free order reversal
                    # invalid rows must still sink: flag is operand 0
                    operands.append(flipped)
                else:
                    operands.append(c)
            with spans.stage("key_sort"):
                out = lax.sort(tuple(operands), num_keys=len(operands),
                               is_stable=True)
            n_valid = jnp.minimum(counts[0], k).reshape(1)
            with spans.stage("topk"):
                return (n_valid,) + tuple(o[:k] for o in out[1:])

        prog = _cached_program(
            ("topk_rows", self.mesh, tuple(names), k, largest,
             tuple(str(dt) for _, dt in self._schema())),
            lambda: _shard_program(
                self.mesh, shard_sorted, 1 + len(names),
                (_SPEC,) * (1 + len(names)),
            ),
        )
        outs = prog(blk.counts, *[blk.cols[nm] for nm in names])
        outs = mesh_lib.host_get(outs)  # one RTT
        dicts = self._dicts()
        with spans.span("pivot"):  # the driver's merge, to rows
            n_valid = np.asarray(outs[0]).reshape(-1)
            per_col = [np.asarray(o).reshape(blk.n_shards, k)
                       for o in outs[1:]]
            keep = []
            for s in range(blk.n_shards):
                c = int(n_valid[s])
                if c:
                    keep.append([col[s, :c] for col in per_col])
            if not keep:
                return []
            merged = {nm: np.concatenate([rows[i] for rows in keep])
                      for i, nm in enumerate(names)}
            if largest:
                # un-flip (the sort returned its flipped operands)
                for nm in names:
                    col = merged[nm]
                    merged[nm] = -col if np.issubdtype(col.dtype, np.floating) \
                        else ~col
            merged = block_lib._decode_key_cols(merged)  # schema order kept
            order_cols = list(merged.values())
            # np.lexsort: last key is primary -> reverse; stable like the
            # device sort. Dictionary-encoded columns order by their RANK
            # codes here — identical to string order — and decode below.
            order = np.lexsort([c if not largest else
                                (-c if np.issubdtype(c.dtype, np.floating)
                                 else ~c)
                                for c in reversed(order_cols)])
            out_names = [nm for nm in names if not block_lib.is_lo(nm)]
            for nm in out_names:
                if nm in dicts:  # collect boundary: codes -> strings
                    merged[nm] = dicts[nm][merged[nm]]
            first = order[:n]
            picked = [merged[nm][first].tolist() for nm in out_names]
            if len(out_names) == 1:  # keyless single column: scalars,
                return picked[0]     # not 1-tuples
            return list(zip(*picked))

    def stats(self) -> dict:
        """count/mean/stdev/min/max in one device pass (host analogue:
        rdd.rs-adjacent stats; see base.py)."""
        import math

        blk = self.block()
        if self.is_pair or self._wide_value() or VALUE in self._dicts():
            # wide/dict: host sees decoded int64 / string rows (and the
            # host path raises its normal TypeError for string stats)
            return RDD.stats(self)

        def shard_stats(vals, counts):
            count = counts[0]
            v = vals.astype(jnp.float32)
            s = kernels.masked_reduce(v, count, "add")
            ss = kernels.masked_reduce(v * v, count, "add")
            mn = kernels.masked_reduce(v, count, "min")
            mx = kernels.masked_reduce(v, count, "max")
            # Count stays integer (float32 is exact only to 2^24 — a v5e-8
            # shard of the 1B-row target holds ~125M rows).
            return counts.reshape(1), jnp.stack([s, ss, mn, mx]).reshape(1, 4)

        prog = _cached_program(
            ("stats", self.mesh),
            lambda: _shard_program(self.mesh, shard_stats, 2, (_SPEC, _SPEC)),
        )
        int_counts, parts = prog(blk.cols[VALUE], blk.counts)
        int_counts, parts = mesh_lib.host_get((int_counts, parts))  # one RTT
        int_counts = np.asarray(int_counts).reshape(-1)
        parts = np.asarray(parts)
        n = int(int_counts.sum())
        s = float(parts[:, 0].sum())
        ss = float(parts[:, 1].sum())
        valid = int_counts > 0
        mn = float(parts[valid, 2].min()) if valid.any() else float("inf")
        mx = float(parts[valid, 3].max()) if valid.any() else float("-inf")
        mean = s / n if n else float("nan")
        var = max(0.0, ss / n - mean * mean) if n else float("nan")
        return {"count": n, "mean": mean,
                "stdev": math.sqrt(var) if n else float("nan"),
                "min": mn, "max": mx}

    def _min_max(self):
        """Fused single-pass min+max (one device program, not two). Only
        histogram() calls this, and it routes wide-value blocks to the
        host tier first, so this always sees a narrow VALUE column."""
        blk = self.block()

        def shard_mm(vals, counts):
            count = counts[0]
            mn = kernels.masked_reduce(vals, count, "min")
            mx = kernels.masked_reduce(vals, count, "max")
            return jnp.stack([mn, mx]).reshape(1, 2), counts.reshape(1)

        prog = _cached_program(
            ("minmax", self.mesh),
            lambda: _shard_program(self.mesh, shard_mm, 2, (_SPEC, _SPEC)),
        )
        parts, int_counts = prog(blk.cols[VALUE], blk.counts)
        parts, int_counts = mesh_lib.host_get((parts, int_counts))  # one RTT
        parts = np.asarray(parts)
        valid = np.asarray(int_counts).reshape(-1) > 0
        if not valid.any():
            raise VegaError("min/max of empty DenseRDD")
        return parts[valid, 0].min().item(), parts[valid, 1].max().item()

    def histogram(self, buckets):
        """Device histogram: bucketize + per-shard bincount + driver sum."""
        if self.is_pair or self._wide_value() or VALUE in self._dicts():
            # wide: float32 bucketing would mangle int64s; host is exact.
            # dict: bucketing codes is not bucketing strings — the host
            # path raises its normal TypeError for string histograms.
            return RDD.histogram(self, buckets)
        if isinstance(buckets, int):
            lo, hi = self._min_max()
            if lo == hi:
                return [lo, hi], [self.count()]
            step = (hi - lo) / buckets
            edges = [lo + i * step for i in range(buckets)] + [hi]
        else:
            edges = list(buckets)
        n_bins = len(edges) - 1
        blk = self.block()
        edges_dev = mesh_lib.host_put(
            np.asarray(edges, dtype=np.float32),
            mesh_lib.replicated_spec(self.mesh))

        def shard_hist(bnds, vals, counts):
            v = vals.astype(jnp.float32)
            mask = kernels.valid_mask(v.shape[0], counts[0])
            mask = mask & (v >= bnds[0]) & (v <= bnds[-1])
            idx = jnp.clip(jnp.searchsorted(bnds, v, side="right") - 1,
                           0, n_bins - 1)
            idx = jnp.where(mask, idx, n_bins)
            return jnp.bincount(idx, length=n_bins + 1)[:n_bins].reshape(1, -1)

        prog = _cached_program(
            ("hist", self.mesh, n_bins),
            lambda: _shard_program(
                self.mesh, shard_hist, (_REPL, _SPEC, _SPEC), _SPEC
            ),
        )
        parts = np.asarray(mesh_lib.host_get(
            prog(edges_dev, blk.cols[VALUE], blk.counts)
        ))
        return edges, parts.sum(axis=0).tolist()

    def save_npz(self, path: str) -> str:
        """Persist the materialized block's valid rows as one .npz of
        column arrays — the dense analogue of checkpoint(): reloading with
        ctx.dense_load_npz() re-sources the data with no lineage. One file;
        shard layout is reconstructed on load for the current mesh."""
        import os as _os

        if type(self).collect is not DenseRDD.collect:
            raise VegaError(
                "save_npz persists raw columns; this RDD's elements are "
                "derived from them (grouped/joined) — save an upstream RDD "
                "or materialize via collect()/to_rdd() instead"
            )
        blk = self.block()
        cols = blk.to_numpy()  # valid rows only, shard order
        _os.makedirs(_os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:  # file object: savez keeps the exact name
            np.savez(f, **cols)
        _os.replace(tmp, path)
        return path

    def take(self, n: int) -> list:
        # Pull shard by shard until satisfied; avoids full collect.
        out = []
        blk = self.block()
        for s in range(blk.n_shards):
            rows = blk.shard_rows(s)
            names = list(rows)
            if names == [VALUE]:
                out.extend(rows[VALUE].tolist())
            elif set(names) == {KEY, VALUE}:
                out.extend(zip(rows[KEY].tolist(), rows[VALUE].tolist()))
            else:
                out.extend(zip(*[rows[n].tolist() for n in names]))
            if len(out) >= n:
                break
        return out[:n]


class _NotTraceable(Exception):
    pass


# ---------------------------------------------------------------------------
# element <-> column conventions
# ---------------------------------------------------------------------------


def _row_struct(schema):
    """Abstract per-row value for tracing: scalar v, or (k, v) pair."""
    cols = dict(schema)
    if any(block_lib.is_lo(nm) for nm in cols):
        # Wide (two-column int64) keys or values have no device row form
        # (the int64 scalar cannot be traced without x64); row-wise
        # closures take the host tier, which sees the reassembled int64s.
        raise _NotTraceable("int64 keys/values: no device row form")
    if set(cols) == {KEY, VALUE}:
        return (jax.ShapeDtypeStruct((), cols[KEY]),
                jax.ShapeDtypeStruct((), cols[VALUE]))
    if set(cols) == {VALUE}:
        return jax.ShapeDtypeStruct((), cols[VALUE])
    return tuple(jax.ShapeDtypeStruct((), dt) for _n, dt in schema)


def _trace_row_fn(f, schema):
    """Introspect f's output structure on abstract rows; returns
    (out_schema, cols_fn) where cols_fn maps column dict -> column dict.
    Raises _NotTraceable for non-jax functions."""
    in_struct = _row_struct(schema)
    try:
        out_struct = jax.eval_shape(f, in_struct)
    except Exception as e:  # noqa: BLE001 — any trace error means host tier
        raise _NotTraceable(str(e)) from e

    def check_scalar(s):
        if s.shape != ():
            raise _NotTraceable(f"row fn must return scalars, got {s.shape}")

    if isinstance(out_struct, tuple) and len(out_struct) == 2:
        for s in out_struct:
            check_scalar(s)
        out_schema = ((KEY, out_struct[0].dtype), (VALUE, out_struct[1].dtype))

        def cols_fn(cols):
            args = _cols_to_row(cols, schema)
            k, v = jax.vmap(f)(args)
            return {KEY: k, VALUE: v}

    elif hasattr(out_struct, "shape"):
        check_scalar(out_struct)
        out_schema = ((VALUE, out_struct.dtype),)

        def cols_fn(cols):
            args = _cols_to_row(cols, schema)
            return {VALUE: jax.vmap(f)(args)}

    else:
        raise _NotTraceable(f"unsupported row fn output: {out_struct}")
    return out_schema, cols_fn


def _cols_to_row(cols, schema):
    names = [n for n, _ in schema]
    if set(names) == {KEY, VALUE}:
        return (cols[KEY], cols[VALUE])
    if names == [VALUE]:
        return cols[VALUE]
    return tuple(cols[n] for n in names)


# ---------------------------------------------------------------------------
# narrow nodes (fused at materialization)
# ---------------------------------------------------------------------------


class _NarrowRDD(DenseRDD):
    """A narrow dense op: shard-local (cols, count) -> (cols, count).
    Chains of narrow nodes compose into one jitted program."""

    # Nodes that override _materialize (capacity-changing expansions) are
    # chain BREAKS: a downstream narrow chain must materialize them via
    # their own program, never call their _shard_fn.
    _chainable = True

    def __init__(self, parent: DenseRDD, out_schema):
        super().__init__(parent.context, parent.mesh, [parent])
        self.parent = parent
        self._out_schema = tuple(out_schema)

    def _schema(self):
        return self._out_schema

    def _shard_fn(self, cols, count):
        raise NotImplementedError

    def _node_fp(self):
        """Program-cache identity of this node (kind + closure fingerprint)."""
        return (type(self).__name__, _fp(getattr(self, "_user_fn", None)))

    def _fp_extra(self):
        return self._node_fp()

    def _materialize(self) -> Block:
        # Collect the narrow chain down to the nearest materialization
        # root via the shared walk (exchange fusion uses the same one, so
        # the two sites cannot disagree about what a chain is).
        chain, root = _narrow_chain(self)
        chain = _detached_chain(chain)  # cached program must not pin nodes
        return _run_narrow_chain(self.mesh, chain, root.block(),
                                 self._out_schema)


def _run_narrow_chain(mesh, chain, root_block: Block, out_schema) -> Block:
    """Compile+launch ONE shard program applying a (detached) narrow
    chain over a materialized root block — the shared materializer behind
    _NarrowRDD._materialize and the frame A/B's chain-broken unfused
    nodes (one program-cache key scheme, one Block contract)."""
    names = list(root_block.cols)
    out_names = [n for n, _ in out_schema]
    cap = root_block.capacity

    def fused(counts, *col_arrays):
        cols = dict(zip(names, col_arrays))
        cols, count = _apply_chain(chain, cols, counts[0])
        return (count.reshape(1),) + tuple(cols[n] for n in out_names)

    key = ("narrow", mesh, tuple(names), tuple(out_names),
           _chain_fp(chain))
    prog = _cached_program(
        key,
        lambda: _shard_program(
            mesh, fused, 1 + len(names),
            (_SPEC,) * (1 + len(out_names)),
        ),
    )
    out = prog(root_block.counts, *[root_block.cols[n] for n in names])
    return Block(
        cols=dict(zip(out_names, out[1:])),
        counts=out[0], capacity=cap, mesh=mesh,
    )


class _MapRDD(_NarrowRDD):
    def __init__(self, parent: DenseRDD, f):
        out_schema, cols_fn = _trace_row_fn(f, parent._schema())
        super().__init__(parent, out_schema)
        self._cols_fn = cols_fn
        self._user_fn = f
        # A traced closure mints its outputs fresh — no dictionary rides
        # through by default. Trusted internal callers that merely MOVE a
        # dict column (distinct/set ops/count_by_value) overwrite this
        # right after construction.
        self._dict_renames = {}

    def _shard_fn(self, cols, count):
        return self._cols_fn(cols), count


class _MapValuesRDD(_NarrowRDD):
    def __init__(self, parent: DenseRDD, f):
        pschema = dict(parent._schema())
        if isinstance(parent, _JoinRDD):
            # f sees the joined pair (lv, rv), as the host tier calls it
            # on (k, (lv, rv)) rows, and mints the one column VALUE.
            self._in_names, self._vname = ("lv", "rv"), VALUE
        else:
            # The single value column, whatever its name (canonical 'v'
            # or a named column from dense_from_columns).
            self._vname = next(nm for nm in pschema
                               if nm not in (KEY, KEY_LO))
            self._in_names = (self._vname,)
        try:
            out = jax.eval_shape(f, self._value_arg(
                {nm: jax.ShapeDtypeStruct((), pschema[nm])
                 for nm in self._in_names}))
        except Exception as e:  # noqa: BLE001
            raise _NotTraceable(str(e)) from e
        if not hasattr(out, "shape") or out.shape != ():
            raise _NotTraceable("map_values fn must return a scalar")
        key_schema = ((KEY, pschema[KEY]),)
        if KEY_LO in pschema:
            key_schema += ((KEY_LO, pschema[KEY_LO]),)
        super().__init__(parent, key_schema + ((self._vname, out.dtype),))
        self._f = f
        self._user_fn = f
        # Keys pass through untouched (dict KEY keeps its dictionary);
        # the value column is minted by the closure.
        self._dict_renames = {KEY: KEY}

    def _value_arg(self, cols):
        vals = tuple(cols[nm] for nm in self._in_names)
        return vals[0] if len(vals) == 1 else vals

    def _shard_fn(self, cols, count):
        out = {KEY: cols[KEY],
               self._vname: jax.vmap(self._f)(self._value_arg(cols))}
        if KEY_LO in cols:
            out[KEY_LO] = cols[KEY_LO]
        return out, count

    @property
    def hash_placed(self) -> bool:
        return self.parent.hash_placed  # keys untouched

    @property
    def key_sorted(self) -> bool:
        return self.parent.key_sorted  # order untouched

    def _settle_placement(self) -> None:
        self.parent._settle_placement()


class _FilterRDD(_NarrowRDD):
    def __init__(self, parent: DenseRDD, pred):
        schema = parent._schema()
        in_struct = _row_struct(schema)
        try:
            out = jax.eval_shape(pred, in_struct)
        except Exception as e:  # noqa: BLE001
            raise _NotTraceable(str(e)) from e
        if not hasattr(out, "shape") or out.shape != ():
            raise _NotTraceable("predicate must return a scalar bool")
        super().__init__(parent, schema)
        self._pred = pred
        self._user_fn = pred

    def _shard_fn(self, cols, count):
        cap = next(iter(cols.values())).shape[0]
        keep = jax.vmap(self._pred)(_cols_to_row(cols, self._out_schema))
        keep = keep.astype(jnp.bool_) & kernels.valid_mask(cap, count)
        return kernels.compact(cols, keep, cap)

    @property
    def hash_placed(self) -> bool:
        return self.parent.hash_placed  # surviving rows keep their keys

    @property
    def key_sorted(self) -> bool:
        return self.parent.key_sorted  # compact is stable

    def _settle_placement(self) -> None:
        self.parent._settle_placement()


def _fixed_payload_schema(payload, width: int, what: str):
    """Schema for a (width,)-array payload — one array (values) or a
    (keys, values) pair. Shared by map_expand and flat_map_ragged."""
    if isinstance(payload, tuple) and len(payload) == 2:
        if any(getattr(s, "shape", None) != (width,) for s in payload):
            raise _NotTraceable(
                f"{what} fn must return shape ({width},) arrays"
            )
        return ((KEY, payload[0].dtype), (VALUE, payload[1].dtype))
    if hasattr(payload, "shape"):
        if payload.shape != (width,):
            raise _NotTraceable(
                f"{what} fn must return a ({width},) array"
            )
        return ((VALUE, payload.dtype),)
    raise _NotTraceable(f"unsupported {what} output: {payload}")


class _MapExpandRDD(_NarrowRDD):
    """Fixed-factor row expansion: vmapped f gives [n, factor] outputs which
    interleave into factor*capacity rows, compacted to valid prefix."""

    _chainable = False  # overrides _materialize (capacity changes)

    def __init__(self, parent: DenseRDD, f, factor: int):
        if factor <= 0:
            raise VegaError("map_expand factor must be positive")
        in_struct = _row_struct(parent._schema())
        try:
            out = jax.eval_shape(f, in_struct)
        except Exception as e:  # noqa: BLE001
            raise _NotTraceable(str(e)) from e
        schema = _fixed_payload_schema(out, factor, "map_expand")
        super().__init__(parent, schema)
        self._f = f
        self._factor = factor
        self._user_fn = (f, factor)
        self._dict_renames = {}  # closure-minted outputs: no dict rides

    def _materialize(self) -> Block:
        # Expansion changes capacity; run as its own program (not chained).
        parent_blk = self.parent.block()
        names_in = list(parent_blk.cols)
        out_names = [n for n, _ in self._out_schema]
        factor = self._factor
        cap_in = parent_blk.capacity
        cap_out = block_lib._round_capacity(cap_in * factor)
        f = self._f
        in_schema = self.parent._schema()

        def prog_fn(counts, *col_arrays):
            cols = dict(zip(names_in, col_arrays))
            count = counts[0]
            args = _cols_to_row(cols, in_schema)
            out = jax.vmap(f)(args)  # leaves [cap_in, factor]
            if not isinstance(out, tuple):
                out = (out,)
            flat = {
                name: jnp.pad(o.reshape(-1), (0, cap_out - cap_in * factor))
                for name, o in zip(out_names, out)
            }
            idx = lax.iota(jnp.int32, cap_out)
            keep = idx < count * factor
            res, new_count = kernels.compact(flat, keep, cap_out)
            return (new_count.reshape(1),) + tuple(res[n] for n in out_names)

        key = ("map_expand", self.mesh, _fp(self._user_fn), cap_in, factor)
        prog = _cached_program(
            key,
            lambda: _shard_program(
                self.mesh, prog_fn, 1 + len(names_in),
                (_SPEC,) * (1 + len(out_names)),
            ),
        )
        outs = prog(parent_blk.counts,
                    *[parent_blk.cols[n] for n in names_in])
        return Block(cols=dict(zip(out_names, outs[1:])), counts=outs[0],
                     capacity=cap_out, mesh=self.mesh)

    def _shard_fn(self, cols, count):  # not chained; materialize overrides
        raise NotImplementedError


class _FlatMapRaggedRDD(_NarrowRDD):
    """Variable-arity flat_map on device: f(row) -> (out, n_valid) where
    out is one (max_out,) array (values) or a pair of (max_out,) arrays
    (key, value) and n_valid is how many lead entries are real.

    The XLA-compatible general flat_map (reference rdd.rs:207-214 is fully
    dynamic): per-row counts -> exclusive prefix sums -> each row marks its
    first output slot and a running max carries the mark over the row's run
    (kernels.ragged_expand, the pattern merge_join_expand shares). Output
    capacity is the static bound
    capacity * max_out, so no overflow is possible."""

    _chainable = False  # overrides _materialize (capacity changes)

    def __init__(self, parent: DenseRDD, f, max_out: int):
        if max_out <= 0:
            raise VegaError("flat_map_ragged max_out_per_row must be > 0")
        in_struct = _row_struct(parent._schema())
        try:
            out = jax.eval_shape(f, in_struct)
        except Exception as e:  # noqa: BLE001
            raise _NotTraceable(str(e)) from e
        if not (isinstance(out, tuple) and len(out) == 2):
            raise _NotTraceable(
                "flat_map_ragged fn must return (out_arrays, n_valid)"
            )
        payload, n_struct = out
        if getattr(n_struct, "shape", None) != ():
            raise _NotTraceable("n_valid must be a scalar")
        schema = _fixed_payload_schema(payload, max_out, "flat_map_ragged")
        super().__init__(parent, schema)
        self._f = f
        self._max_out = max_out
        self._user_fn = (f, max_out)
        self._dict_renames = {}  # closure-minted outputs: no dict rides

    def _materialize(self) -> Block:
        parent_blk = self.parent.block()
        names_in = list(parent_blk.cols)
        out_names = [n for n, _ in self._out_schema]
        max_out = self._max_out
        cap_in = parent_blk.capacity
        cap_out = block_lib._round_capacity(cap_in * max_out)
        f = self._f
        in_schema = self.parent._schema()

        def prog_fn(counts, *col_arrays):
            cols = dict(zip(names_in, col_arrays))
            count = counts[0]
            args = _cols_to_row(cols, in_schema)
            payload, n = jax.vmap(f)(args)  # leaves [cap_in, max_out]
            if not isinstance(payload, tuple):
                payload = (payload,)
            mask = kernels.valid_mask(cap_in, count)
            n = jnp.where(mask, jnp.clip(n.astype(jnp.int32), 0, max_out), 0)
            li, off, total = kernels.ragged_expand(n, cap_out)
            off = jnp.clip(off, 0, max_out - 1)
            res = {
                name: leaf[li, off]
                for name, leaf in zip(out_names, payload)
            }
            return (total.reshape(1),) + tuple(res[n_] for n_ in out_names)

        key = ("flat_map_ragged", self.mesh, _fp(self._user_fn), cap_in,
               max_out)
        prog = _cached_program(
            key,
            lambda: _shard_program(
                self.mesh, prog_fn, 1 + len(names_in),
                (_SPEC,) * (1 + len(out_names)),
            ),
        )
        outs = prog(parent_blk.counts,
                    *[parent_blk.cols[n] for n in names_in])
        return Block(cols=dict(zip(out_names, outs[1:])), counts=outs[0],
                     capacity=cap_out, mesh=self.mesh)

    def _shard_fn(self, cols, count):  # not chained; materialize overrides
        raise NotImplementedError


class _ZipWithIndexRDD(DenseRDD):
    def __init__(self, parent: DenseRDD):
        super().__init__(parent.context, parent.mesh, [parent])
        self.parent = parent
        # The value moves to the key slot unchanged; the index is fresh.
        self._dict_renames = {KEY: VALUE}

    def _schema(self):
        pschema = dict(self.parent._schema())
        return ((KEY, pschema[VALUE]), (VALUE, jnp.int32))

    def _materialize(self) -> Block:
        blk = self.parent.block()
        counts_host = blk.counts_np
        offsets = np.concatenate(
            [[0], np.cumsum(counts_host)[:-1]]
        ).astype(np.int32)
        offsets_dev = mesh_lib.host_put(offsets,
                                        mesh_lib.shard_spec(self.mesh))

        def prog_fn(offsets, counts, vals):
            shard_off = offsets[0]
            positions = shard_off + lax.iota(jnp.int32, vals.shape[0])
            return counts.reshape(1), vals, positions

        prog = _cached_program(
            ("zip_index", self.mesh, blk.capacity),
            lambda: _shard_program(self.mesh, prog_fn, 3, (_SPEC,) * 3),
        )
        counts, vals, pos = prog(offsets_dev, blk.counts, blk.cols[VALUE])
        return Block(cols={KEY: vals, VALUE: pos}, counts=counts,
                     capacity=blk.capacity, mesh=self.mesh,
                     counts_host=counts_host)


class _DenseZipRDD(DenseRDD):
    """Pairwise zip of co-indexed shards: (left value, right value). Shard
    counts must match (host semantics raise otherwise,
    reference: zip_rdd.rs:119-150)."""

    def __init__(self, left: DenseRDD, right: DenseRDD):
        super().__init__(left.context, left.mesh, [left, right])
        self.left = left
        self.right = right

    def _schema(self):
        l = dict(self.left._schema())
        r = dict(self.right._schema())
        return ((KEY, l[VALUE]), (VALUE, r[VALUE]))

    def _dicts(self):
        # Sides keep their OWN dictionaries (no cross-side comparison
        # happens in a zip): left value -> KEY, right value -> VALUE.
        out = {}
        ld = self.left._dicts().get(VALUE)
        rd = self.right._dicts().get(VALUE)
        if ld is not None:
            out[KEY] = ld
        if rd is not None:
            out[VALUE] = rd
        return out

    def _materialize(self) -> Block:
        lb = self.left.block()
        rb = self.right.block()
        lc = lb.counts_np
        rc = rb.counts_np
        if not np.array_equal(lc, rc):
            raise VegaError(
                "dense zip requires equal per-shard counts; repartition or "
                "use .to_rdd().zip(...)"
            )
        cap = max(lb.capacity, rb.capacity)

        def prog_fn(counts, lv, rv):
            pad_l = cap - lv.shape[0]
            pad_r = cap - rv.shape[0]
            return (counts.reshape(1),
                    jnp.pad(lv, (0, pad_l)), jnp.pad(rv, (0, pad_r)))

        prog = _cached_program(
            ("dense_zip", self.mesh, lb.capacity, rb.capacity),
            lambda: _shard_program(self.mesh, prog_fn, 3, (_SPEC,) * 3),
        )
        counts, lv, rv = prog(lb.counts, lb.cols[VALUE], rb.cols[VALUE])
        return Block(cols={KEY: lv, VALUE: rv}, counts=counts, capacity=cap,
                     mesh=self.mesh, counts_host=lc)


class _SelectRDD(_NarrowRDD):
    def __init__(self, parent: DenseRDD, names):
        pschema = dict(parent._schema())
        super().__init__(parent, tuple((n, pschema[n]) for n in names))
        self._names = tuple(names)
        self._user_fn = self._names

    def _shard_fn(self, cols, count):
        return {n: cols[n] for n in self._names}, count

    @property
    def hash_placed(self) -> bool:
        return KEY in self._names and self.parent.hash_placed

    @property
    def key_sorted(self) -> bool:
        return KEY in self._names and self.parent.key_sorted

    def _settle_placement(self) -> None:
        self.parent._settle_placement()


class _RenameRDD(_NarrowRDD):
    """Value-column rename (keys untouched, so placement/order survive)."""

    def __init__(self, parent: DenseRDD, mapping: dict):
        pschema = parent._schema()
        super().__init__(parent, tuple(
            (mapping.get(nm, nm), dt) for nm, dt in pschema))
        self._mapping = dict(mapping)
        self._user_fn = tuple(sorted(mapping.items()))
        # Dictionaries follow their columns to the new names (identity
        # for unrenamed columns).
        self._dict_renames = {mapping.get(nm, nm): nm for nm, _ in pschema}

    def _shard_fn(self, cols, count):
        return {self._mapping.get(nm, nm): col
                for nm, col in cols.items()}, count

    @property
    def hash_placed(self) -> bool:
        return self.parent.hash_placed

    @property
    def key_sorted(self) -> bool:
        return self.parent.key_sorted

    def _settle_placement(self) -> None:
        self.parent._settle_placement()


class _OnesValueRDD(_NarrowRDD):
    """Key columns + a synthesized int32 ones VALUE column —
    count_by_key_dense's map side (counting needs no value bytes, so any
    existing value columns are dropped before the exchange moves data;
    the canonical VALUE name keeps the (k, count) host row form)."""

    def __init__(self, parent: DenseRDD):
        pschema = dict(parent._schema())
        out = [(nm, pschema[nm]) for nm in (KEY, KEY_LO) if nm in pschema]
        out.append((VALUE, jnp.int32))
        super().__init__(parent, tuple(out))
        self._user_fn = "ones_value"
        # KEY passes through (keeps its dictionary); VALUE is fresh ones.
        self._dict_renames = {KEY: KEY}

    def _shard_fn(self, cols, count):
        out = {nm: cols[nm] for nm in cols if nm in (KEY, KEY_LO)}
        out[VALUE] = jnp.ones_like(cols[KEY], dtype=jnp.int32)
        return out, count

    @property
    def hash_placed(self) -> bool:
        return self.parent.hash_placed

    @property
    def key_sorted(self) -> bool:
        return self.parent.key_sorted

    def _settle_placement(self) -> None:
        self.parent._settle_placement()


class _WidenKeyRDD(_NarrowRDD):
    """Re-encode an int32 KEY as the (hi, lo) two-column int64 encoding so
    the side can join/cogroup an int64-keyed block (same logical keys ->
    same bucket under the composite hash). hash_placed intentionally resets
    (default False): placement under the single-key hash says nothing
    about placement under the composite hash."""

    def __init__(self, parent: DenseRDD):
        out = []
        for nm, dt in parent._schema():
            if nm == KEY:
                out.append((KEY, jnp.int32))
                out.append((KEY_LO, jnp.int32))
            else:
                out.append((nm, dt))
        super().__init__(parent, tuple(out))
        self._user_fn = "widen_key"

    def _shard_fn(self, cols, count):
        k = cols[KEY]
        # hi = sign word (== int64(k) >> 32); lo = bits of k with the sign
        # bit flipped (signed compare == unsigned compare of true low word)
        # — identical to block.encode_i64 on the host.
        hi = k >> jnp.int32(31)
        lo = lax.bitcast_convert_type(
            lax.bitcast_convert_type(k, jnp.uint32) ^ jnp.uint32(0x80000000),
            jnp.int32,
        )
        out = {KEY: hi, KEY_LO: lo}
        for nm, c in cols.items():
            if nm != KEY:
                out[nm] = c
        return out, count


def _align_keys(a: DenseRDD, b: DenseRDD):
    """Make two dense pair sides key-compatible for device matching
    (join/cogroup): equal logical keys must hash to the same shard and
    compare equal in the merge kernel. Returns the (possibly widened)
    sides, or None when only the host tier can match them faithfully
    (mismatched key dtypes — e.g. int32 2 vs float32 2.0 hash apart on
    device but compare equal under Python semantics)."""
    pair = _unify_dict_cols(a, b, (KEY,))
    if pair is None:
        # One side's KEY is dictionary-encoded strings, the other's is
        # plain ints: a code 2 and an int 2 would match on device but
        # differ on the host — only the host tier matches faithfully.
        return None
    a, b = pair
    sa, sb = dict(a._schema()), dict(b._schema())
    wide_a, wide_b = KEY_LO in sa, KEY_LO in sb
    if wide_a == wide_b:
        if jnp.dtype(sa[KEY]) == jnp.dtype(sb[KEY]):
            return a, b
        return None
    narrow = b if wide_a else a
    if jnp.dtype(dict(narrow._schema())[KEY]) != jnp.dtype(jnp.int32):
        return None
    widened = _WidenKeyRDD(narrow)
    return (a, widened) if wide_a else (widened, b)


class _DictUnification:
    """Shared host-side dictionary merge for one binary op: both sides'
    _DictUnifyRDD wrappers reference ONE instance, so the merge runs once
    and the sides agree bit-identically on the unified code space. The
    merge itself (np.union1d + searchsorted remap tables,
    dict_encoding.merge_dicts) is lazy — graph construction stays cheap
    until a wrapper actually needs the tables."""

    def __init__(self, left_dicts, right_dicts, names):
        self.names = tuple(names)
        self._left = {nm: left_dicts[nm] for nm in self.names}
        self._right = {nm: right_dicts[nm] for nm in self.names}
        self._memo = None

    def tables(self):
        """(merged, left_maps, right_maps): per-name merged sorted
        dictionary plus int32 remap tables (old code -> merged code)."""
        if self._memo is None:
            from vega_tpu.tpu import dict_encoding

            merged, lmaps, rmaps = {}, {}, {}
            for nm in self.names:
                m, lt, rt = dict_encoding.merge_dicts(
                    self._left[nm], self._right[nm])
                merged[nm], lmaps[nm], rmaps[nm] = m, lt, rt
            self._memo = (merged, lmaps, rmaps)
        return self._memo

    def token(self):
        """Cheap picklable identity for fingerprints — input dictionary
        shapes and endpoints, no forced merge. Collisions only alias
        capacity HINTS (the overflow retry is the safety net, as ever)."""
        out = []
        for nm in self.names:
            for d in (self._left[nm], self._right[nm]):
                out.append((nm, len(d),
                            str(d[0]) if len(d) else "",
                            str(d[-1]) if len(d) else ""))
        return tuple(out)


class _DictUnifyRDD(_NarrowRDD):
    """Remap one side's dictionary codes onto the shared merged
    dictionary: ONE device gather through a staged remap table per
    unified column. The staged table capacity is a REAL capacity
    (Configuration.dense_dict_capacity): a valid code at or past the
    staged prefix sets the device overflow flag — checked on the RAW
    codes — and the driver retries with the capacity doubled. Monotonic
    remap (sorted dicts in, sorted merge out), so per-shard key order
    survives; hash placement does NOT (the codes hashed into buckets
    changed), hence the default hash_placed False."""

    _chainable = False  # own program (replicated table operands)

    def __init__(self, parent: DenseRDD, unif: _DictUnification, side: int):
        super().__init__(parent, parent._schema())
        self._unif = unif
        self._side = side
        self._dict_retries = 0  # overflow->grown-capacity rounds (tests)
        self._user_fn = ("dict_unify", side, unif.token())

    def _dicts(self):
        merged = self._unif.tables()[0]
        out = dict(self.parent._dicts())
        for nm in self._unif.names:
            if nm in out:
                out[nm] = merged[nm]
        return out

    @property
    def key_sorted(self) -> bool:
        return self.parent.key_sorted  # monotonic remap keeps order

    def _settle_placement(self) -> None:
        self.parent._settle_placement()

    def _materialize(self) -> Block:
        from vega_tpu.tpu import dict_encoding

        blk = self.parent.block()
        _, lmaps, rmaps = self._unif.tables()
        side_tables = lmaps if self._side == 0 else rmaps
        names = [nm for nm in self._unif.names if nm in blk.cols]
        if not names:
            return blk
        in_names = list(blk.cols)
        cap_tab = max(128, dict_encoding.dict_capacity())
        table_n = max(len(side_tables[nm]) for nm in names)
        for _round in range(8):
            staged_n = tuple(min(len(side_tables[nm]), cap_tab)
                             for nm in names)
            tabs = []
            for nm, sn in zip(names, staged_n):
                t = np.zeros(cap_tab, dtype=np.int32)
                t[:sn] = side_tables[nm][:sn]
                tabs.append(mesh_lib.host_put(
                    t, mesh_lib.replicated_spec(self.mesh)))
            n_tab = len(names)

            def prog_fn(*args):
                tables = dict(zip(names, args[:n_tab]))
                counts = args[n_tab]
                cols = dict(zip(in_names, args[n_tab + 1:]))
                count = counts[0]
                cap_rows = next(iter(cols.values())).shape[0]
                valid = kernels.valid_mask(cap_rows, count)
                flag = jnp.zeros((), jnp.int32)
                out = dict(cols)
                for nm, sn in zip(names, staged_n):
                    codes = cols[nm]
                    # Overflow checked on the RAW codes (never the
                    # clamped gather index): any valid code past the
                    # staged prefix means the table was truncated.
                    bad = valid & ((codes < 0)
                                   | (codes >= jnp.int32(sn)))
                    flag = flag | jnp.any(bad).astype(jnp.int32)
                    out[nm] = jnp.take(
                        tables[nm], jnp.clip(codes, 0, cap_tab - 1))
                return ((flag.reshape(1),)
                        + tuple(out[nm] for nm in in_names))

            prog = _cached_program(
                ("dict_remap", self.mesh, tuple(in_names), tuple(names),
                 cap_tab, staged_n, blk.capacity),
                lambda: _shard_program(
                    self.mesh, prog_fn,
                    tuple([_REPL] * n_tab) + (_SPEC,) * (1 + len(in_names)),
                    (_SPEC,) * (1 + len(in_names)),
                ),
            )
            outs = prog(*tabs, blk.counts,
                        *[blk.cols[nm] for nm in in_names])
            flag = np.asarray(mesh_lib.host_get(outs[0]))
            if not flag.any():
                return Block(
                    cols=dict(zip(in_names, outs[1:])),
                    counts=blk.counts, capacity=blk.capacity,
                    mesh=self.mesh, counts_host=blk.counts_host,
                )
            self._dict_retries += 1
            cap_tab *= 2
        raise VegaError(
            f"dictionary remap overflowed {table_n} entries after 8 "
            "capacity-doubling retries — raise dense_dict_capacity"
        )


def _unify_dict_cols(a: DenseRDD, b: DenseRDD, names):
    """Align the named dictionary-encoded columns of two sides onto one
    merged dictionary so device code equality == string equality.
    Returns the (possibly wrapped) sides; (a, b) unchanged when nothing
    needs remapping (no dict columns, or both sides already share the
    same dictionary arrays); None when dict-ness MISMATCHES on a name —
    codes on one side and plain values on the other only compare
    faithfully on the host tier."""
    da, db = a._dicts(), b._dicts()
    shared = [nm for nm in names if nm in da or nm in db]
    if not shared:
        return a, b
    if any((nm in da) != (nm in db) for nm in shared):
        return None
    todo = [nm for nm in shared if da[nm] is not db[nm]]
    if not todo:
        return a, b
    unif = _DictUnification(da, db, todo)
    return _DictUnifyRDD(a, unif, 0), _DictUnifyRDD(b, unif, 1)


class _ProjectRDD(_NarrowRDD):
    def __init__(self, parent: DenseRDD, col: str):
        pschema = dict(parent._schema())
        if col not in pschema:
            raise VegaError(
                f"no {col!r} column on this DenseRDD (columns: "
                f"{list(pschema)})"
            )
        super().__init__(parent, ((VALUE, pschema[col]),))
        self._col = col
        self._user_fn = col
        # The projected column keeps its dictionary under the VALUE name.
        self._dict_renames = {VALUE: col}

    def _shard_fn(self, cols, count):
        return {VALUE: cols[self._col]}, count


class _ColsPipelineRDD(_NarrowRDD):
    """Multi-op traced closure entry: ONE narrow node applying an arbitrary
    columnwise (cols, count) -> (cols, count) pipeline with a declared
    output schema and a stable fingerprint token. The frame planner
    (vega_tpu/frame) lowers a whole select/filter/with_column stage onto a
    single instance, so the stage compiles to exactly one shard program —
    and still rides the existing chain fusion when stacked on other narrow
    nodes. `fused=False` breaks the chain: the node materializes through
    its OWN single-step program (the frame A/B's unfused leg)."""

    def __init__(self, parent: DenseRDD, cols_fn, out_schema, token,
                 fused: bool = True, dict_renames=None):
        super().__init__(parent, out_schema)
        self._cols_fn = cols_fn
        self._user_fn = token  # _node_fp pickles this, not the closure
        # The planner DECLARES which output columns are pass-throughs of
        # dictionary-encoded parent columns ({out name -> parent name});
        # everything else is closure-minted and drops its dictionary.
        self._dict_renames = dict(dict_renames or {})
        if not fused:
            self._chainable = False

    def _shard_fn(self, cols, count):
        return self._cols_fn(cols, count)

    def _materialize(self) -> Block:
        if self._chainable:
            return _NarrowRDD._materialize(self)
        # Unfused: a one-node chain over the materialized parent — its own
        # program launch and its own intermediate block, deliberately (the
        # fusion A/B's control leg must pay per-op launches).
        return _run_narrow_chain(self.mesh, _detached_chain([self]),
                                 self.parent.block(), self._out_schema)


def dense_pipeline(parent: DenseRDD, cols_fn, out_schema, token,
                   fused: bool = True, dict_renames=None) -> DenseRDD:
    """Public factory for _ColsPipelineRDD (the frame planner's whole-stage
    entry). `out_schema` is ((name, dtype), ...); `token` must be a stable
    picklable description of the pipeline (it keys the program cache);
    `dict_renames` maps output columns that pass a dictionary-encoded
    parent column through unchanged to that parent column's name."""
    return _ColsPipelineRDD(parent, cols_fn, out_schema, token, fused=fused,
                            dict_renames=dict_renames)


# ---------------------------------------------------------------------------
# source nodes
# ---------------------------------------------------------------------------


class _HostDenseView(RDD):
    """What an unpickled DenseRDD is: the materialized rows as host numpy,
    original shard structure preserved, iteration-only surface (compute /
    iterator / collect). Device ops are not available — a shipped dense
    node is consumed by host-tier tasks, never re-launched as SPMD."""

    def __init__(self, *a, **kw):  # pragma: no cover — pickle-only
        raise TypeError("_HostDenseView is created by unpickling a DenseRDD")

    @property
    def num_partitions(self) -> int:
        return self._host_block.n_shards

    def block(self) -> Block:
        return self._host_block

    def __getstate__(self):
        return self.__dict__.copy()

    def __setstate__(self, state):
        self.__dict__.update(state)

    def compute(self, split: Split, task_context=None):
        yield from _yield_rows(self._host_block.shard_rows(split.index))


class _SourceRDD(DenseRDD):
    def __init__(self, ctx, blk: Block, hash_placed: bool = False):
        super().__init__(ctx, blk.mesh)
        self._block = blk
        self._hash_placed = hash_placed

    @property
    def hash_placed(self) -> bool:
        return self._hash_placed

    def _materialize(self) -> Block:
        return self._block

    def unpersist(self) -> "DenseRDD":
        """No-op: a source's Block IS its data — there is no lineage to
        rebuild it from, so releasing it would lose the dataset. Source
        footprint is gated at creation (the streaming planner caps
        whole-block sources at dense_hbm_budget)."""
        return self

    def _schema(self):
        return tuple((n, c.dtype) for n, c in self._block.cols.items())

    def _dicts(self):
        return dict(self._block.dicts or {})

    def _fp_extra(self):
        return (tuple((n, str(c.dtype)) for n, c in self._block.cols.items()),
                self._block.capacity, self._hash_placed)


def dense_range(ctx, n: int, num_partitions=None, dtype=None,
                chunk_rows: Optional[int] = None):
    """Device iota source. When the estimated exchange footprint over the
    whole block (the exchange planner's peak estimate under
    dense_exchange=auto; ~6x block bytes otherwise) exceeds
    Configuration.dense_hbm_budget, returns a StreamedDenseRDD that flows
    chunk by chunk through the mesh instead of materializing whole (the
    1B-row single-chip path); pass chunk_rows to force streaming."""
    from vega_tpu.env import Env
    from vega_tpu.tpu.stream import planned_chunk_rows, streamed_range

    mesh = mesh_lib.default_mesh()
    dtype = dtype or jnp.int32
    rows = planned_chunk_rows(
        n, jnp.dtype(dtype).itemsize,
        getattr(Env.get().conf, "dense_hbm_budget", 4 << 30),
        chunk_rows, n_shards=mesh.size,
    )
    if rows is not None and rows < n:
        return streamed_range(ctx, n, rows, mesh, dtype)
    return _SourceRDD(ctx, block_lib.block_range(n, mesh, dtype))


def dense_from_numpy(ctx, columns, num_partitions=None):
    """columns: one array (values) or two arrays (keys, values).

    Data the device tier cannot represent faithfully (int64 beyond int32
    range without jax x64 — keys would silently collide) degrades to the
    HOST tier, never errors: the two-tier contract applied to dtypes. The
    host tier keeps exact int64 semantics."""
    mesh = mesh_lib.default_mesh()
    try:
        if len(columns) == 1:
            blk = block_lib.single_column(columns[0], mesh)
        elif len(columns) == 2:
            blk = block_lib.pair_block(columns[0], columns[1], mesh)
        else:
            named = {f"c{i}": np.asarray(c) for i, c in enumerate(columns)}
            blk = block_lib.from_numpy(named, mesh)
    except VegaError as e:
        log.info("dense_from_numpy fell back to host tier: %s", e)
        arrays = [np.asarray(c) for c in columns]
        if len(arrays) == 1:
            data = arrays[0].tolist()
        elif len(arrays) == 2:
            data = list(zip(arrays[0].tolist(), arrays[1].tolist()))
        else:
            data = list(zip(*[a.tolist() for a in arrays]))
        return ctx.parallelize(data, num_partitions)
    return _SourceRDD(ctx, blk)


def dense_from_columns(ctx, columns: Optional[dict] = None,
                       key: Optional[str] = None, **kwcolumns) -> DenseRDD:
    """Named-column dense source (the columnar-analytics face of the tier):
    any number of value columns; `key=` names the column used as the shuffle
    key. reduce_by_key with a named op reduces EVERY value column per key in
    one program (kernels.segment_reduce_named is generic over columns) —
    e.g. a parquet table flows in with zero pivoting:

        blk = pq.read_table(p).to_pydict()
        rdd = ctx.dense_from_columns(blk, key="ip")
        per_ip = rdd.reduce_by_key(op="add")     # sums every other column

    Columns may come as a dict (works for any column names, including
    "key") and/or keywords.
    """
    named = {}
    for source in (columns or {}), kwcolumns:
        for name, col in source.items():
            if name in named:
                raise VegaError(f"duplicate column {name!r}")
            if block_lib.is_lo(name):
                # The ".lo" suffix is reserved for the low word of wide
                # (two-column int64) encodings: a user column with such a
                # name would be silently consumed as low-word bits (wrong
                # int64 values, vanished data).
                raise VegaError(
                    f"column name {name!r} is reserved (the "
                    f"{block_lib.LO_SUFFIX!r} suffix marks low words of "
                    "two-column int64 encodings) — rename the column"
                )
            named[name] = np.asarray(col)
    lengths = {name: len(col) for name, col in named.items()}
    if len(set(lengths.values())) > 1:
        raise VegaError(f"columns have unequal lengths: {lengths}")
    if key is not None:
        if key not in named:
            raise VegaError(f"key column {key!r} not in columns")
        if KEY in named and key != KEY:
            raise VegaError(
                f"column {KEY!r} already exists; key={key!r} would "
                f"overwrite it — rename one of them"
            )
        named[KEY] = named.pop(key)
    try:
        blk = block_lib.from_numpy(named, mesh_lib.default_mesh())
    except VegaError as e:
        if set(named) == {KEY, VALUE}:
            # Same dtype degrade as dense_from_numpy: the canonical pair
            # layout has a host row form, so fall back instead of erroring.
            log.info("dense_from_columns fell back to host tier: %s", e)
            return ctx.parallelize(
                list(zip(np.asarray(named[KEY]).tolist(),
                         np.asarray(named[VALUE]).tolist()))
            )
        raise  # named/multi-column blocks: documented crisp-error exception
    return _SourceRDD(ctx, blk)


def dense_from_block(ctx, blk: Block, hash_placed: bool = False) -> DenseRDD:
    return _SourceRDD(ctx, blk, hash_placed=hash_placed)


def dense_load_npz(ctx, path: str, chunk_rows: Optional[int] = None):
    """Load a block persisted with DenseRDD.save_npz; data is re-sharded
    over the current default mesh (so a block saved on one topology loads
    onto another — the persistence story the reference lacks entirely,
    SURVEY.md §5 'Checkpoint/resume: none'). Files bigger than the HBM
    budget stream chunk by chunk (host RAM holds the file; HBM holds one
    chunk); pass chunk_rows to force streaming."""
    from vega_tpu.env import Env
    from vega_tpu.tpu.stream import planned_chunk_rows, streamed_npz

    with np.load(path, allow_pickle=False) as data:
        cols = {n: data[n] for n in data.files}
    n = len(next(iter(cols.values()))) if cols else 0
    bytes_per_row = sum(
        c.dtype.itemsize * int(np.prod(c.shape[1:], dtype=np.int64))
        for c in cols.values()
    ) or 1
    rows = planned_chunk_rows(
        n, bytes_per_row,
        getattr(Env.get().conf, "dense_hbm_budget", 4 << 30),
        chunk_rows, n_shards=mesh_lib.default_mesh().size,
    )
    if rows is not None and rows < n:
        # Reuse the already-loaded host columns — no second npz read.
        return streamed_npz(ctx, cols, rows, mesh_lib.default_mesh())
    blk = block_lib.from_numpy(cols, mesh_lib.default_mesh())
    return _SourceRDD(ctx, blk)


# ---------------------------------------------------------------------------
# exchange nodes (device shuffles)
# ---------------------------------------------------------------------------


def _cap_round(c: int) -> int:
    """Shape-stable capacity rounding (pow2 under 1M, 1M-multiples above —
    see block._round_capacity)."""
    return block_lib._round_capacity(c)


def _exchange_capacities(counts: np.ndarray, n_shards: int,
                         attempt: int) -> Tuple[int, int]:
    """Heuristic slot/out capacities with growth on retry, rounded to
    shape-stable buckets so repeated pipelines at similar scale reuse
    compiled programs."""
    max_count = int(counts.max()) if counts.size else 1
    total = int(counts.sum())
    grow = 2 ** attempt
    slot = min(
        _cap_round(max_count),
        _cap_round((math.ceil(max_count / max(n_shards, 1)) * 2 + 64) * grow),
    )
    out = min(
        _cap_round(total),
        _cap_round((math.ceil(total / max(n_shards, 1)) * 2 + 64) * grow),
    )
    return slot, out


def _histogram_capacities(hists: List[np.ndarray], attempt: int,
                          slot_hists: Optional[List[np.ndarray]] = None
                          ) -> Tuple[int, int]:
    """Exact slot/out capacities from per-shard destination histograms.

    Each hist is [n_shards, n_shards]: hist[s, t] = rows shard s sends to
    target t. slot must hold the largest single (sender, target) cell; out
    must hold the largest per-target column sum. Sized from the real key
    distribution, overflow retries (which recompile a bigger program,
    multi-second jit stalls on TPU) become an anomaly instead of the
    expected path under skew. Growth on retry is kept as a safety net for
    exchanges whose histogram is an estimate (none today).

    slot_hists, when given, restricts the slot (send-buffer) sizing to
    those hists: elided (diagonal) sides never send, and letting their
    per-shard totals into the slot max would oversize the other side's
    [n_shards, slot] buffers."""
    grow = 2 ** attempt
    src = hists if slot_hists is None else slot_hists
    slot = max((int(h.max()) for h in src), default=1)
    out = max(int(h.sum(axis=0).max()) for h in hists)
    return _cap_round(max(slot, 1) * grow), _cap_round(max(out, 1) * grow)


def _with_exchange(node, exchange: Optional[str]):
    if exchange is not None:
        node.exchange_mode = exchange
    return node


# The elided / planner-bypassed token builds program-cache keys on paths
# that never launch a collective (passthrough or single-shard): the key
# slot stays populated so elided and planned programs of one lineage
# never collide.
_X_ELIDED = ("elided",)


def _lo_of(names) -> Optional[str]:
    """KEY_LO when the schema carries a two-column int64 key, else None —
    the switch every keyed device kernel takes."""
    return KEY_LO if KEY_LO in names else None


def _narrow_chain(node):
    """(chain, root) where chain is the longest not-yet-materialized
    chainable narrow run ending at `node` (possibly empty) and root is the
    nearest materialization point above it. Exchanges fuse the chain into
    their own program: the map/filter work runs inside the exchange launch
    (one launch instead of two, no intermediate block in HBM) — XLA-style
    rematerialization applied to the lineage. A chain parent that was
    already materialized (shared by another consumer) is used as-is."""
    chain: List[_NarrowRDD] = []
    cur = node
    while isinstance(cur, _NarrowRDD) and cur._block is None \
            and cur._chainable:
        chain.append(cur)
        cur = cur.parent
    chain.reverse()
    return chain, cur


@spans.stage("narrow")
def _apply_chain(chain, cols, count):
    for nd in chain:
        cols, count = nd._shard_fn(cols, count)
    return cols, count


def _chain_fp(chain) -> tuple:
    return tuple(nd._node_fp() for nd in chain)


@spans.stage("exchange_group")
def _bucket_cols(cols, n: int) -> jax.Array:
    """Hash-bucket rows by key, two-column int64 keys included. The
    composite hash mixes BOTH words (hash32_pair) so placement keeps its
    contract: equal int64 keys — and only those — share a bucket."""
    if KEY_LO in cols:
        return (kernels.hash32_pair(cols[KEY], cols[KEY_LO])
                % jnp.uint32(n)).astype(jnp.int32)
    return pallas_kernels.hash_bucket(cols[KEY], n)


def _elide_out_cap(blk: Block) -> int:
    """Output capacity for an elided (passthrough) exchange: rows stay
    put, so the parent's max shard count bounds it exactly when already
    host-known; otherwise the parent's static capacity (a safe superset,
    usually the same rounding bucket) — never worth a counts fetch."""
    if blk.counts_host is not None and blk.counts_host.size:
        return block_lib._round_capacity(max(int(blk.counts_host.max()), 1))
    return blk.capacity


def _count_fill(moved, n_shards: int, out_cap: int) -> None:
    """How full a succeeded exchange ran, for the session tally: each side
    in `moved` (its root block and the narrow chain fused above it) put its
    rows into the exchange and the program held `n_shards x out_cap` receive
    slots for it. Rows in equal rows out, so the block's host-known counts
    are the rows; a side whose counts are still on the device, or whose
    fused chain may filter, counts neither rows nor slots (never a fetch)."""
    rows = [int(blk.counts_host.sum()) for blk, chain in moved
            if not chain and blk.counts_host is not None]
    if rows:
        spans.count("exchange_rows", sum(rows))
        spans.count("exchange_slots", len(rows) * n_shards * out_cap)


def _settle_pending(ctx) -> None:
    """Verify every deferred (speculative) exchange in ONE device
    transfer; repair failures in place.

    A hinted/fixed-capacity exchange launches without its blocking
    (counts, overflow) fetch — each such fetch is a blocking
    driver<->device round trip between otherwise-pipelined launches — and
    registers here instead. The next genuine host read settles the whole
    backlog: one device_get over all pending flags, then per entry either
    commit (write counts_host, refresh the capacity hint) or, from the
    first failure onward, invalidate and re-materialize with deferral
    disabled (the normal histogram-sized blocking path) and copy the
    clean result INTO the old Block object so every captured reference
    observes the repair. Entries registered after a failure are rebuilt
    too: they were launched against the failed block's truncated data."""
    pend = ctx.__dict__.get("_dense_pending")
    if not pend:
        return
    entries = list(pend)
    pend.clear()  # repairs below re-enter _run_exchange -> _settle_pending
    hint_store = ctx.__dict__.setdefault("_dense_capacity_hints", {})

    def commit(e, head):
        blk = e["block"]
        blk.counts_host = head[0].reshape(-1)
        blk.settle = None
        if e["hint_key"] is not None:
            # pop-then-insert refreshes recency (front of the dict is
            # the eviction end, _run_exchange's bookkeeping).
            hint_store.pop(e["hint_key"], None)
            hint_store[e["hint_key"]] = e["caps"]
            while len(hint_store) > 4096:
                hint_store.pop(next(iter(hint_store)))
        if e["on_success"] is not None:
            e["on_success"](head)
        _count_fill(e["moved"], e["rdd"].mesh.size, e["caps"][1])

    def depends_on(rdd, failed_rdds) -> bool:
        """True if rdd's dense lineage reaches any failed node (possibly
        through non-pending intermediates)."""
        seen = set()
        stack = [rdd]
        while stack:
            node = stack.pop()
            if id(node) in seen:
                continue
            seen.add(id(node))
            if id(node) in failed_rdds:
                return True
            stack.extend(node._dense_parents)
        return False

    failed = []          # entries to invalidate + rebuild, in order
    failed_rdds = set()
    i = 0
    try:
        fetched = mesh_lib.host_get(
            [(e["outs_head"], e["overflow"]) for e in entries])
        for i, (e, (head, ovf)) in enumerate(zip(entries, fetched)):
            head = [np.asarray(h) for h in head]
            bad = failed_rdds and depends_on(e["rdd"], failed_rdds)
            if not bad:
                ok = not bool(np.any(np.asarray(ovf)))
                validator_said_no = False
                if ok and e["validate"] is not None:
                    # Join product checks; a hard limit raises VegaError.
                    ok = e["validate"](head)
                    validator_said_no = not ok
                if ok:
                    # Clean flags AND no failed ancestor: commit even
                    # after an unrelated pipeline's failure — only
                    # lineage descendants consumed truncated data.
                    commit(e, head)
                    continue
                # An exchange overflow means the hinted capacities were
                # wrong — drop the hint so the repair sizes from
                # histograms. A validator failure (join product exceeded
                # its cap) keeps the exchange hint: the validator already
                # stashed its corrected cap.
                if e["hint_key"] is not None and not validator_said_no:
                    hint_store.pop(e["hint_key"], None)
            failed.append(e)
            failed_rdds.add(id(e["rdd"]))
    except Exception:
        # Settlement died mid-way (validator hard error, transport
        # failure): every entry not yet committed goes BACK on the
        # backlog, in order — a stranded entry whose settle became a
        # no-op would silently serve capacity-truncated data later.
        # That includes entries already triaged into `failed` but not
        # yet repaired: re-processing them is idempotent (their
        # overflow flags re-fail and route back through repair).
        # (A deterministic validator error thus re-raises on every
        # subsequent read of the affected pipeline: loud, never wrong.)
        pend[:0] = failed + entries[i:]
        raise
    if not failed:
        return
    log.info("speculative exchange failed (%d of %d entries); repairing",
             len(failed), len(entries))
    for e in failed:
        e["rdd"]._block = None
        e["rdd"].__dict__.pop("_pickle_state_memo", None)
        # Until repaired, reads through captured references must fail
        # loudly, not fetch the truncated speculative buffers.
        e["block"].settle = _unrepaired_raise
    ctx.__dict__["_dense_no_defer"] = True
    try:
        for e in failed:
            rdd = e["rdd"]
            spans.count("exchange_repair")
            fresh = rdd.block()  # blocking path: sized, fetched, verified
            old = e["block"]
            old.cols = fresh.cols
            old.counts = fresh.counts
            old.capacity = fresh.capacity
            old.counts_host = fresh.counts_np
            old.settle = None
            old._host_cols_cache = None  # repaired cols: drop stale copy
            rdd._block = old  # keep the object identity callers captured
    finally:
        ctx.__dict__["_dense_no_defer"] = False


def _unrepaired_raise():
    raise VegaError(
        "speculative block was invalidated by an exchange overflow and "
        "its repair did not complete; re-run the pipeline"
    )


class _ExchangeRDD(DenseRDD):
    """Common driver loop: run the fused exchange program, check overflow
    flags, retry with grown capacities (capacity-factor pattern). The
    collective implementation (one-shot all_to_all, staged K-round, or
    ring) is resolved per launch by the cost model in
    tpu/exchange_plan.py under Configuration.dense_exchange="auto", or
    forced by an explicit mode / the node's exchange_mode attribute."""

    # Last resolved plan; stays None on single-shard meshes (the
    # passthrough plans nothing) so readers must null-check.
    _exchange_plan = None

    def _attach_pending(self, blk: Block) -> Block:
        """Register the deferred entry _run_exchange left behind (if any)
        against the just-built Block; returns blk either way."""
        entry = self.__dict__.pop("_deferred_entry", None)
        if entry is None:
            return blk
        entry["block"] = blk
        ctx = self.context
        ctx.__dict__.setdefault("_dense_pending", []).append(entry)
        blk.settle = lambda: _settle_pending(ctx)
        return blk

    @property
    def exchange_mode(self) -> str:
        mode = getattr(self, "_exchange_mode", None)
        if mode is None:
            from vega_tpu.env import Env

            mode = getattr(Env.get().conf, "dense_exchange", "auto")
        return mode

    @exchange_mode.setter
    def exchange_mode(self, mode: str) -> None:
        self._exchange_mode = mode

    def _resolve_exchange(self, blks, slot_capacity: int,
                          out_capacity: int):
        """Resolve the exchange implementation for ONE launch through the
        collective-aware planner (tpu/exchange_plan.py): explicit modes
        map straight to their program; "auto" picks the fewest-rounds
        program whose estimated per-shard peak fits dense_hbm_budget
        (all_to_all -> staged -> ring). Returns (exchange_callable,
        plan_token); the token goes into the program-cache key — the
        budget is config, not key, so the RESOLVED choice must be.

        Called from inside build(slot, out_cap): capacities are only
        known per launch (hints, histograms, growth retries), and a
        retry's grown slot may legitimately shift the plan. `blks` are
        the operand blocks actually exchanged — a join passes both
        non-elided sides, and the estimate models the JOINT launch
        footprint (both operands and outputs live together, the
        costlier side's transients on top), not the max of the sides.
        Records the plan on the node (_exchange_plan), the module counters,
        the tally (exchange_plan_rounds, once a launch) and the event bus."""
        from vega_tpu.env import Env
        from vega_tpu.tpu import exchange_plan

        n = self.mesh.size
        if n == 1:
            # Passthrough territory: nothing to plan, nothing to record.
            return kernels.bucket_exchange, ("single",)
        budget = getattr(Env.get().conf, "dense_hbm_budget", 4 << 30)
        plan = exchange_plan.plan_exchange(
            n_shards=n,
            capacity=max(b.capacity for b in blks),
            slot_capacity=slot_capacity,
            out_capacity=out_capacity,
            row_bytes=max(exchange_plan.block_row_bytes(b) for b in blks),
            budget_bytes=budget,
            mode=self.exchange_mode,
            blocks=[(b.capacity, exchange_plan.block_row_bytes(b))
                    for b in blks],
        )
        self._exchange_plan = plan
        exchange_plan.record_plan(plan)
        spans.count("exchange_plan_rounds", plan.rounds)
        bus = getattr(self.context, "bus", None)
        if bus is not None:
            from vega_tpu.scheduler import events as ev

            bus.post(ev.DenseExchangePlanned(
                rdd_id=self.rdd_id, program=plan.program,
                rounds=plan.rounds, group=plan.group,
                est_peak_bytes=plan.est_peak_bytes,
                budget_bytes=budget, n_shards=n, fits=plan.fits,
            ))
        return exchange_plan.exchange_callable(plan), plan.cache_token()

    def _hash_histogram(self, blk: Block,
                        chain=()) -> Optional[np.ndarray]:
        """One cheap counting pass over the keys: hist[s, t] = rows shard s
        will send to target t under hash bucketing. Costs a hash + bincount
        per shard (no sort, no value movement) and one tiny [n, n]
        transfer; buys exactly-sized exchange capacities. `chain` is a
        fused narrow run applied to the root block's columns first (the
        exchange recomputes it too — cheaper than materializing)."""
        n = self.mesh.size
        if n == 1:
            return None
        chain = chain or ()
        # Without a fused chain the histogram only needs the key columns:
        # keep the program universal across value schemas (one compile)
        # and skip staging value columns it never reads.
        if chain:
            in_names = list(blk.cols)
        else:
            in_names = [KEY] + ([KEY_LO] if KEY_LO in blk.cols else [])

        @spans.stage("exchange_group")
        def prog_fn(counts, *col_arrays):
            cols = dict(zip(in_names, col_arrays))
            cols, count = _apply_chain(chain, cols, counts[0])
            cap = cols[KEY].shape[0]
            bucket = _bucket_cols(cols, n)
            bucket = jnp.where(kernels.valid_mask(cap, count), bucket, n)
            return jnp.bincount(bucket, length=n + 1)[:n].astype(jnp.int32)

        prog = _cached_program(
            ("hash_hist", self.mesh, n, tuple(in_names), _chain_fp(chain)),
            lambda: _shard_program(self.mesh, prog_fn, 1 + len(in_names),
                                   _SPEC),
        )
        out = prog(blk.counts, *[blk.cols[nm] for nm in in_names])
        return np.asarray(mesh_lib.host_get(out)).reshape(n, n)

    def _range_histogram(self, blk: Block, bounds_dev,
                         ascending: bool, bounds_lo_dev=None,
                         chain=()) -> Optional[np.ndarray]:
        """Destination histogram under range partitioning (sort_by_key).
        bounds_lo_dev carries the low-word bounds of two-column int64
        keys; `chain` is a fused narrow run applied first."""
        n = self.mesh.size
        if n == 1:
            return None
        composite = bounds_lo_dev is not None
        chain = chain or ()
        if chain:
            in_names = list(blk.cols)
        else:
            in_names = [KEY] + ([KEY_LO] if composite else [])

        @spans.stage("exchange_group")
        def prog_fn(*args):
            n_bounds = 1 + composite
            bnds = args[0]
            bnds_lo = args[1] if composite else None
            counts = args[n_bounds]
            cols = dict(zip(in_names, args[n_bounds + 1:]))
            cols, count = _apply_chain(chain, cols, counts[0])
            keys = cols[KEY]
            cap = keys.shape[0]
            bucket = kernels.range_bucket(
                bnds, keys, ascending, bounds_lo=bnds_lo,
                keys_lo=cols[KEY_LO] if composite else None,
            )
            bucket = jnp.where(kernels.valid_mask(cap, count), bucket, n)
            return jnp.bincount(bucket, length=n + 1)[:n].astype(jnp.int32)

        in_specs = ((_REPL,) * (1 + composite)
                    + (_SPEC,) * (1 + len(in_names)))
        prog = _cached_program(
            ("range_hist", self.mesh, n, ascending, composite,
             tuple(in_names), _chain_fp(chain)),
            lambda: _shard_program(self.mesh, prog_fn, in_specs, _SPEC),
        )
        args = ((bounds_dev,) + ((bounds_lo_dev,) if composite else ())
                + (blk.counts,)
                + tuple(blk.cols[nm] for nm in in_names))
        out = prog(*args)
        return np.asarray(mesh_lib.host_get(out)).reshape(n, n)

    def _hint_key(self, *extra):
        """Capacity-hint identity: structural lineage + fetch-free input
        size identity (_counts_fp — leaf counts, or materialized counts
        where already host-known). Same pipeline shape over same-size
        inputs (the steady-state rerun and the streamed per-chunk case)
        reuses last run's capacities and skips both the sizing histogram
        AND the post-launch overflow fetch (deferred to _settle_pending);
        a changed key distribution under equal counts surfaces at
        settlement, which repairs through the exact histogram."""
        return (self._lineage_fp(), self._counts_fp(), extra)

    def _run_exchange(self, build_program, counts,
                      hists: Optional[List[np.ndarray]] = None,
                      slot_hists: Optional[List[np.ndarray]] = None,
                      make_hists=None, hint_key=None, fixed_caps=None,
                      validate=None, on_success=None, moved=()):
        """Run the fused exchange program with capacity sizing.

        Sizing order: (1) `fixed_caps` — capacities known a priori
        (elided passthroughs, which cannot overflow), (2) a memoized
        capacity hint for this lineage+sizes (no device work), (3) exact
        histograms — passed eagerly via `hists`/`slot_hists` or computed
        lazily by `make_hists()` (a device pass, skipped entirely on a
        hint hit), (4) the heuristic growth schedule; `counts` may be a
        callable so cold-path-only sizing inputs are never fetched on the
        warm path. Overflow at any stage falls through to the next.

        Deferred mode (fixed/hinted, unless a settle-repair is running):
        the program launches WITHOUT the blocking (counts, overflow)
        fetch — each such fetch is a blocking driver<->device round trip
        between otherwise async-pipelined launches — and leaves a
        pending entry for _attach_pending/_settle_pending to verify at
        the next genuine host read. `validate`/`on_success` ride the
        entry (join product checks / node bookkeeping), as does `moved`:
        the (root block, fused chain) of each side that crosses shards,
        tallied by _count_fill once the launch is known to have fit."""
        from vega_tpu.scheduler import events as ev

        n = self.mesh.size
        hist_pair = (None if make_hists is not None
                     else (hists, slot_hists))
        ctx = self.context
        hint_store = ctx.__dict__.setdefault("_dense_capacity_hints", {})
        hinted = hint_key is not None and hint_key in hint_store
        bus = getattr(ctx, "bus", None)
        t_start = time.perf_counter()
        spans.count("exchange")
        if ((fixed_caps is not None or hinted)
                and not ctx.__dict__.get("_dense_no_defer")):
            slot, out_cap = (fixed_caps if fixed_caps is not None
                             else hint_store[hint_key])
            if bus is not None:
                bus.post(ev.StageSubmitted(
                    stage_id=-self.rdd_id, num_tasks=n, is_shuffle_map=True,
                ))
            try:
                prog, args = build_program(slot, out_cap)
                spans.count("exchange_round")
                # Launch under the CPU dispatch door: a concurrent
                # device_get on another task thread (shard_rows /
                # host_get) deadlocks old XLA:CPU (mesh.device_door).
                with mesh_lib.device_door():
                    *outs, overflow = prog(*args)
            finally:
                if bus is not None:
                    # JAX dispatch is async: prog() returned but the device
                    # may still be executing — this timing is dispatch-only.
                    bus.post(ev.StageCompleted(
                        stage_id=-self.rdd_id,
                        duration_s=time.perf_counter() - t_start,
                        speculative=True,
                    ))
            self._last_attempts = 1
            extra = getattr(self, "_fetch_extra_outs", 0)
            self._deferred_entry = {
                "rdd": self,
                "outs_head": tuple(outs[:1 + extra]),
                "overflow": overflow,
                "hint_key": None if fixed_caps is not None else hint_key,
                "caps": (slot, out_cap),
                "validate": validate,
                "on_success": on_success,
                "moved": moved,
            }
            self._last_counts_host = None
            self._last_extra_host = None
            return outs, out_cap
        # Blocking path: before sizing from (or launching over) parent
        # data, settle the speculation backlog — histogram passes and the
        # heuristic's counts would otherwise trust possibly-truncated
        # blocks. Repairs rewrite failed blocks in place, so references
        # captured above this frame stay valid.
        _settle_pending(ctx)
        if bus is not None:
            # Dense stages bypass the task scheduler (one SPMD launch);
            # surface them on the same event bus for observability. One
            # Submitted/Completed pair per exchange, retries included.
            bus.post(ev.StageSubmitted(
                stage_id=-self.rdd_id, num_tasks=n, is_shuffle_map=True,
            ))
        try:
            attempt = 0  # histogram/heuristic growth step
            for round_i in range(6):
                if fixed_caps is not None and round_i == 0:
                    slot, out_cap = fixed_caps
                elif hinted and round_i == 0:
                    slot, out_cap = hint_store[hint_key]
                else:
                    if hist_pair is None:
                        hist_pair = make_hists()
                    hs = [h for h in (hist_pair[0] or []) if h is not None]
                    sh = hist_pair[1]
                    if sh is not None:
                        sh = [h for h in sh if h is not None]
                    if hs:
                        slot, out_cap = _histogram_capacities(hs, attempt,
                                                              sh)
                    else:
                        if callable(counts):
                            counts = counts()
                        slot, out_cap = _exchange_capacities(counts, n,
                                                             attempt)
                    attempt += 1
                prog, args = build_program(slot, out_cap)
                spans.count("exchange_round")
                with mesh_lib.device_door():  # see the deferred launch
                    *outs, overflow = prog(*args)
                self._last_attempts = round_i + 1
                # One transfer for (counts, any extra driver-needed outputs,
                # overflow): each separate device_get is a full
                # driver<->device round trip. Nodes that need more outputs
                # on the host (join's exact product sizes) set
                # _fetch_extra_outs to ride the same transfer.
                extra = getattr(self, "_fetch_extra_outs", 0)
                fetched, overflow_host = mesh_lib.host_get(
                    (tuple(outs[:1 + extra]), overflow)
                )
                if not bool(np.any(np.asarray(overflow_host))):
                    self._last_counts_host = np.asarray(
                        fetched[0]
                    ).reshape(-1)
                    self._last_extra_host = [np.asarray(x)
                                             for x in fetched[1:]]
                    if hint_key is not None:
                        # pop-then-insert refreshes recency: eviction pops
                        # the FRONT of the insertion-ordered dict, and the
                        # hot steady-state key (re-stored every warm run)
                        # must not be the one that goes.
                        hint_store.pop(hint_key, None)
                        hint_store[hint_key] = (slot, out_cap)
                        # Bound the store: data-dependent counts (filters,
                        # ragged tail chunks) mint fresh keys per run; drop
                        # oldest entries past the cap.
                        while len(hint_store) > 4096:
                            hint_store.pop(next(iter(hint_store)))
                    _count_fill(moved, n, out_cap)
                    return outs, out_cap
                log.info("exchange overflow (slot=%d out=%d), retrying",
                         slot, out_cap)
            raise VegaError(
                "exchange capacity overflow after retries — key skew "
                "exceeds capacity growth; repartition or use host tier"
            )
        finally:
            if bus is not None:
                bus.post(ev.StageCompleted(
                    stage_id=-self.rdd_id, duration_s=time.perf_counter() - t_start,
                ))


# Synthetic flag column tracking signed overflow of wide int64 adds through
# an exchange: injected before the map-side combine, OR-merged per key by
# _named_wide_combine, and collapsed to one per-shard flag output (the
# capacity-flag pattern applied to arithmetic).
_SOVF = "__sovf"


def _named_wide_combine(op: str, value_names, wide: dict,
                        ovf_name: Optional[str] = None):
    """Per-column combine for a named op over a mix of narrow columns and
    wide (hi, lo) int64 pairs: narrow columns use the plain monoid, wide
    pairs use carry addition / lexicographic select (kernels.wide_add /
    wide_select). With ovf_name (add only), the named column carries a
    sticky int32 flag OR-ing every pair-add's signed-overflow predicate —
    clean flags PROVE the mod-2^64 results equal the exact totals."""
    narrow_ops = {"add": jnp.add, "min": jnp.minimum, "max": jnp.maximum,
                  "prod": jnp.multiply}
    lo_names = set(wide.values())

    def combine(a, b):
        out = {}
        flag = None
        for nm in value_names:
            if nm in lo_names or nm == ovf_name:
                continue
            if nm in wide:
                lo = wide[nm]
                if op == "add":
                    if ovf_name is not None:
                        out[nm], out[lo], o = kernels.wide_add_checked(
                            a[nm], a[lo], b[nm], b[lo])
                        flag = o if flag is None else (flag | o)
                    else:
                        out[nm], out[lo] = kernels.wide_add(
                            a[nm], a[lo], b[nm], b[lo])
                else:  # min/max (prod is rejected at build time)
                    out[nm], out[lo] = kernels.wide_select(
                        a[nm], a[lo], b[nm], b[lo], op == "min")
            else:
                out[nm] = narrow_ops[op](a[nm], b[nm])
        if ovf_name is not None:
            f = a[ovf_name] | b[ovf_name]
            if flag is not None:
                f = f | flag.astype(f.dtype)
            out[ovf_name] = f
        return out

    return combine


class _ReduceByKeyRDD(_ExchangeRDD):
    @property
    def hash_placed(self) -> bool:
        """Output rows live on shard hash(key) % n — EXCEPT after a
        host-exact fold (wide-sum overflow takeover), which rebuilds with
        no device placement. PURE read: while unmaterialized the answer
        is a conservative False (a bare attribute read — repr, debug,
        monitoring — must not launch the exchange as a side effect);
        planners call _settle_placement() first for the materialized
        truth. block_spec() doesn't settle, and a later failed
        speculation invalidates dependents through _settle_pending's
        lineage walk, so an early post-materialization read stays
        sound."""
        if self._block is None:
            return False
        return not getattr(self, "_host_folded", False)

    @property
    def key_sorted(self) -> bool:
        """Segment ends come out in key order — except after a host-exact
        fold (same conservative-until-materialized read as hash_placed)."""
        if self._block is None:
            return False
        return not getattr(self, "_host_folded", False)

    def _settle_placement(self) -> None:
        self.block_spec()

    def __init__(self, parent: DenseRDD, op: Optional[str], func):
        super().__init__(parent.context, parent.mesh, [parent])
        self.parent = parent
        self._op = op
        pschema = parent._schema()
        self._value_names = [nm for nm, _ in pschema
                             if nm not in (KEY, KEY_LO)]
        if op == "prod" and \
                block_lib.wide_value_pairs(nm for nm, _ in pschema):
            # 64-bit product needs full 64x64 multiply emulation — not
            # worth a device path; int64 products overflow almost
            # immediately anyway. Keys decode on the host tier, so point
            # there.
            raise VegaError(
                "reduce_by_key(op='prod') over int64 (wide) values has no "
                "device path — use the host tier (.to_rdd()) for exact "
                "products"
            )
        if func is not None:
            if block_lib.wide_value_pairs(nm for nm, _ in pschema):
                # A traced binop would see encoded (hi, lo) words as two
                # separate int32 scalars — silently wrong. No row form ->
                # host tier (which folds real int64s).
                raise _NotTraceable(
                    "wide int64 value columns: no scalar row form")
            dtypes = dict(pschema)
            structs = [jax.ShapeDtypeStruct((), dtypes[nm])
                       for nm in self._value_names]
            # Single value column: func is scalar x scalar -> scalar.
            # Multi-column block: func is tuple x tuple -> tuple, one
            # scalar per value column (device mean/variance etc. without
            # leaving the columnar layout).
            arg = structs[0] if len(structs) == 1 else tuple(structs)
            try:
                out = jax.eval_shape(func, arg, arg)
            except Exception as e:  # noqa: BLE001
                raise _NotTraceable(str(e)) from e
            if len(structs) == 1:
                if not hasattr(out, "shape") or out.shape != ():
                    raise _NotTraceable("binop must return a scalar")
                if out.dtype != structs[0].dtype:
                    raise _NotTraceable(
                        f"binop changes the value dtype "
                        f"({structs[0].dtype} -> {out.dtype}); cast the "
                        "column first so the block schema stays truthful"
                    )
            else:
                if not (isinstance(out, tuple) and len(out) == len(structs)):
                    raise _NotTraceable(
                        f"binop over {len(structs)} value columns must "
                        f"return a {len(structs)}-tuple"
                    )
                for nm, s, o in zip(self._value_names, structs, out):
                    if getattr(o, "shape", None) != ():
                        raise _NotTraceable("binop outputs must be scalars")
                    if o.dtype != s.dtype:
                        raise _NotTraceable(
                            f"binop changes dtype of column {nm!r} "
                            f"({s.dtype} -> {o.dtype}); cast the column "
                            "first so the block schema stays truthful"
                        )
        self._func = func

    def _schema(self):
        return self.parent._schema()

    def _fp_extra(self):
        return (self._op or _fp(self._func), self.exchange_mode)

    def _segment_reduce(self, cols, count, presorted):
        lo_name = _lo_of(cols)
        if self._op is not None:
            wide = block_lib.wide_value_pairs(cols)
            if wide:
                # Wide int64 values can't ride the XLA segment ops (the
                # carry couples the two words) — same segmented scan the
                # traced combiners use, with the carry/lex combine. An
                # injected _SOVF column (add only) accumulates the
                # overflow flags through the scan.
                combine = _named_wide_combine(
                    self._op, [nm for nm in cols
                               if nm not in (KEY, KEY_LO)], wide,
                    ovf_name=_SOVF if _SOVF in cols else None)
                return kernels.segment_reduce_sorted(
                    cols, count, KEY, combine, presorted=presorted,
                    lo_name=lo_name)
            return kernels.segment_reduce_named(
                cols, count, KEY, self._op, presorted=presorted,
                lo_name=lo_name)
        f = self._func
        names = self._value_names
        if len(names) == 1:
            nm0 = names[0]

            def combine(a, b):
                return {nm0: f(a[nm0], b[nm0])}
        else:
            def combine(a, b):
                out = f(tuple(a[nm] for nm in names),
                        tuple(b[nm] for nm in names))
                return dict(zip(names, out))

        return kernels.segment_reduce_sorted(
            cols, count, KEY, combine, presorted=presorted, lo_name=lo_name)

    def _host_exact_fold(self) -> Block:
        """Host-tier takeover after the device flagged a possible wide
        int64 sum overflow: fold exact Python bignums over the parent's
        decoded rows, then rebuild a block in THIS node's schema (wide
        pairs re-encoded). A clean rebuild means the flagged wrap was
        transient (reassociation) and the exact totals fit; totals beyond
        int64 are not representable on device and raise crisply — the
        host tier (.to_rdd()) keeps exact bignums. The rebuilt block has
        no device placement/order guarantees: hash_placed/key_sorted
        report the materialized truth, so downstream exchanges skip
        elision instead of trusting stale placement."""
        log.info("wide int64 device sum flagged overflow; "
                 "host-exact fold takes over")
        parent_cols = self.parent.block().to_numpy()  # wide pairs decoded
        schema = dict(self._schema())
        keys = np.asarray(parent_cols[KEY])
        keys_list = keys.tolist()
        vnames = [nm for nm in parent_cols if nm != KEY]
        slot_of: dict = {}
        for k in keys_list:
            if k not in slot_of:
                slot_of[k] = len(slot_of)
        i64 = np.iinfo(np.int64)
        out_cols: dict = {}
        if block_lib.KEY_LO in schema:
            hi, lo = block_lib.encode_i64(
                np.asarray(list(slot_of), dtype=np.int64))
            out_cols[KEY], out_cols[block_lib.KEY_LO] = hi, lo
        else:
            kdict = self.parent._dicts().get(KEY)
            if kdict is not None:
                # to_numpy DECODED a dictionary key to strings; re-encode
                # through the PARENT dictionary (every key is in it) so
                # the rebuilt codes stay in the lineage's code space —
                # from_numpy minting a fresh local dictionary here would
                # diverge from what _dicts() reports downstream.
                out_cols[KEY] = np.searchsorted(
                    kdict, np.asarray(list(slot_of), dtype=kdict.dtype),
                ).astype(dict_encoding.CODE_DTYPE)
            else:
                out_cols[KEY] = np.asarray(list(slot_of), dtype=keys.dtype)
        for nm in vnames:
            col = np.asarray(parent_cols[nm])
            if np.issubdtype(col.dtype, np.integer):
                acc = [0] * len(slot_of)
                for k, v in zip(keys_list, col.tolist()):
                    acc[slot_of[k]] += v  # exact python ints
            else:
                acc = [0.0] * len(slot_of)
                for k, v in zip(keys_list, col.tolist()):
                    acc[slot_of[k]] += v
            if block_lib.lo_of(nm) in schema:  # wide in this schema
                if any(v < i64.min or v > i64.max for v in acc):
                    raise VegaError(
                        f"reduce_by_key(op='add'): exact total of column "
                        f"{nm!r} exceeds the int64 range and cannot be "
                        "represented on device — use the host tier "
                        "(.to_rdd()) for exact bignum sums"
                    )
                hi, lo = block_lib.encode_i64(
                    np.asarray(acc, dtype=np.int64))
                out_cols[nm], out_cols[block_lib.lo_of(nm)] = hi, lo
            elif np.issubdtype(col.dtype, np.integer):
                # narrow int columns wrap to their dtype, matching the
                # device's modular arithmetic
                info = np.iinfo(np.dtype(schema[nm]))
                span = 1 << info.bits
                acc = [((v - info.min) % span) + info.min for v in acc]
                out_cols[nm] = np.asarray(acc, dtype=np.dtype(schema[nm]))
            else:
                out_cols[nm] = np.asarray(acc, dtype=np.dtype(schema[nm]))
        self._host_folded = True
        return block_lib.from_numpy(out_cols, self.mesh)

    def _materialize(self) -> Block:
        n = self.mesh.size
        # Partitioner-equality elision, device edition: a hash-placed
        # parent already has every key's rows on their reducer shard, so
        # the whole exchange (hash + multi-key sort + collective)
        # collapses to one per-shard segment reduce — zero collectives.
        self.parent._settle_placement()  # materialized truth, explicitly
        elide = self.parent.hash_placed and n > 1
        # Order survives the elided passthrough's stable compact, letting
        # the reduce run presorted (no sort at all in reduce-of-reduce).
        elide_sorted = elide and self.parent.key_sorted
        # Fuse any pending narrow chain above the exchange into its own
        # program: the map/filter work rides the exchange launch instead
        # of materializing an intermediate block (one launch saved + no
        # intermediate HBM traffic; the sizing histogram recomputes the
        # chain — narrow work is cheap VPU math by construction). Fusion
        # only applies when a real exchange sizes itself from a histogram
        # of post-chain rows: elided and single-shard paths size from raw
        # counts, so a fused FILTER would leave them permanently
        # oversized — those materialize the parent as before.
        chain, root = (_narrow_chain(self.parent) if n > 1 and not elide
                       else ([], self.parent))
        chain = _detached_chain(chain)  # cached program must not pin nodes
        blk = root.block_spec()  # we register our own pending entry
        in_names = list(blk.cols)
        names = [nm for nm, _ in self.parent._schema()]
        this = _detach(self)  # _segment_reduce state without the node
        # Wide int64 adds track signed overflow through the whole exchange
        # (the capacity-flag pattern applied to arithmetic): an injected
        # _SOVF column rides pre-combine -> exchange -> merge, collapses
        # to one per-shard flag fetched with the counts, and a set flag
        # routes to the host-exact fold (see _host_exact_fold).
        track_sovf = self._op == "add" and bool(
            block_lib.wide_value_pairs(names))

        def build(slot, out_cap):
            exchange, x_tok = ((kernels.bucket_exchange, _X_ELIDED)
                               if elide else
                               self._resolve_exchange((blk,), slot,
                                                      out_cap))

            def prog_fn(counts, *col_arrays):
                cols = dict(zip(in_names, col_arrays))
                cols, count = _apply_chain(chain, cols, counts[0])
                if track_sovf:
                    cols[_SOVF] = jnp.zeros(cols[KEY].shape[0], jnp.int32)
                if n > 1 and not elide:
                    # 2-sort exchange: ONE multi-key sort (bucket major,
                    # key minor) feeds both the presorted map-side combine
                    # (reference: dependency.rs:176-223) and a pregrouped
                    # exchange — vs the 3 sorts of sort-for-combine +
                    # group-by-bucket + reduce-side sort.
                    capacity = cols[KEY].shape[0]
                    mask = kernels.valid_mask(capacity, count)
                    bucket = _bucket_cols(cols, n)
                    bucket = jnp.where(mask, bucket, n)
                    cols, bucket = kernels.bucket_key_sort(
                        cols, bucket, KEY, lo_name=_lo_of(cols))
                    cols, count = this._segment_reduce(
                        cols, count, presorted=True)
                    # compact kept (bucket, key) order; re-derive the
                    # combiner rows' buckets from their keys (hash is cheap
                    # and deterministic).
                    bucket = _bucket_cols(cols, n)
                    cols, count, overflow = exchange(
                        cols, count, bucket, n, slot, out_cap,
                        pregrouped=True,
                    )
                elif not elide:
                    bucket = jnp.zeros_like(cols[KEY])
                    cols, count, overflow = exchange(
                        cols, count, bucket, n, slot, out_cap)
                else:
                    capacity = cols[KEY].shape[0]
                    cols, count, overflow = kernels.passthrough_exchange(
                        cols, count, capacity, out_cap
                    )
                # reduce-side merge (reference: shuffled_rdd.rs:149-170)
                cols, count = this._segment_reduce(
                    cols, count, presorted=elide_sorted)
                res = (count.reshape(1),)
                if track_sovf:
                    m = kernels.valid_mask(cols[_SOVF].shape[0], count)
                    sovf = jnp.any(jnp.where(m, cols[_SOVF], 0) != 0)
                    res += (sovf.reshape(1).astype(jnp.int32),)
                return res + tuple(
                    cols[nm] for nm in names
                ) + (overflow.reshape(1),)

            key = ("rbk", self.mesh, tuple(in_names), tuple(names),
                   _chain_fp(chain), n, slot, out_cap, elide, elide_sorted,
                   self.exchange_mode, x_tok, self._op or _fp(self._func),
                   track_sovf)
            prog = _cached_program(
                key,
                lambda: _shard_program(
                    self.mesh, prog_fn, 1 + len(in_names),
                    (_SPEC,) * (2 + track_sovf + len(names)),
                ),
            )
            return prog, (blk.counts, *[blk.cols[nm] for nm in in_names])

        # Elided: rows stay put, so capacities are known a priori (no
        # sizing pass, no overflow possible): tight when the parent's
        # counts are already host-known, else the parent's capacity —
        # never a fetch. Slot is unused by the passthrough.
        self._elided = elide
        # sovf rides the (counts, overflow) transfer; deferred launches
        # re-check it at settlement via validate.
        extra_n = int(track_sovf)
        self._fetch_extra_outs = extra_n
        validate = ((lambda head: not bool(np.any(np.asarray(head[1]))))
                    if track_sovf else None)
        if elide:
            outs, out_cap = self._run_exchange(
                build, lambda: blk.counts_np,
                fixed_caps=(0, _elide_out_cap(blk)),
                validate=validate,
            )
        else:
            outs, out_cap = self._run_exchange(
                build, lambda: blk.counts_np,
                make_hists=lambda: ([self._hash_histogram(blk, chain)],
                                    None),
                hint_key=self._hint_key(),
                validate=validate,
            )
        counts, col_arrays = outs[0], outs[1 + extra_n:]
        extra = self._last_extra_host
        if track_sovf and extra and np.any(np.asarray(extra[0])):
            # Blocking path saw the flag inline (the deferred path
            # reaches here via _settle_pending's repair rerun).
            return self._host_exact_fold()
        return self._attach_pending(Block(
            cols=dict(zip(names, col_arrays)), counts=counts,
            capacity=out_cap, mesh=self.mesh,
            counts_host=self._last_counts_host))


class _GroupByKeyRDD(_ExchangeRDD):
    """Exchange + local sort; block holds key-sorted runs per shard."""

    hash_placed = True  # output rows live on shard hash(key) % n
    key_sorted = True   # the whole point of the grouped block

    def __init__(self, parent: DenseRDD):
        super().__init__(parent.context, parent.mesh, [parent])
        self.parent = parent

    def _schema(self):
        return self.parent._schema()

    def _fp_extra(self):
        return (self.exchange_mode,)

    def _materialize(self) -> Block:
        n = self.mesh.size
        self.parent._settle_placement()  # materialized truth, explicitly
        elide = self.parent.hash_placed and n > 1  # rows already placed
        elide_sorted = elide and self.parent.key_sorted
        # Fused only on the real-exchange path (see reduce: elided/1-shard
        # sizing uses raw counts, which a fused filter would inflate).
        chain, root = (_narrow_chain(self.parent) if n > 1 and not elide
                       else ([], self.parent))
        chain = _detached_chain(chain)  # cached program must not pin nodes
        blk = root.block_spec()  # we register our own pending entry
        in_names = list(blk.cols)
        names = [nm for nm, _ in self.parent._schema()]

        def build(slot, out_cap):
            exchange, x_tok = ((kernels.bucket_exchange, _X_ELIDED)
                               if elide else
                               self._resolve_exchange((blk,), slot,
                                                      out_cap))

            def prog_fn(counts, *col_arrays):
                cols = dict(zip(in_names, col_arrays))
                cols, count = _apply_chain(chain, cols, counts[0])
                if elide:
                    cols, count, overflow = kernels.passthrough_exchange(
                        cols, count, cols[KEY].shape[0], out_cap
                    )
                else:
                    bucket = (_bucket_cols(cols, n)
                              if n > 1 else jnp.zeros_like(cols[KEY]))
                    cols, count, overflow = exchange(
                        cols, count, bucket, n, slot, out_cap)
                if not elide_sorted:  # already sorted rows skip the sort
                    cols = kernels.sort_by_column(cols, count, KEY,
                                                  lo_name=_lo_of(cols))
                return (count.reshape(1),) + tuple(
                    cols[nm] for nm in names
                ) + (overflow.reshape(1),)

            key = ("gbk", self.mesh, tuple(in_names), tuple(names),
                   _chain_fp(chain), n, slot, out_cap, elide,
                   elide_sorted, self.exchange_mode, x_tok)
            prog = _cached_program(
                key,
                lambda: _shard_program(
                    self.mesh, prog_fn, 1 + len(in_names),
                    (_SPEC,) * (2 + len(names)),
                ),
            )
            return prog, (blk.counts, *[blk.cols[nm] for nm in in_names])

        self._elided = elide
        if elide:
            outs, out_cap = self._run_exchange(
                build, lambda: blk.counts_np,
                fixed_caps=(0, _elide_out_cap(blk)),
            )
        else:
            outs, out_cap = self._run_exchange(
                build, lambda: blk.counts_np,
                make_hists=lambda: ([self._hash_histogram(blk, chain)],
                                    None),
                hint_key=self._hint_key(),
                moved=[(blk, chain)] if n > 1 else (),
            )
        counts, col_arrays = outs[0], outs[1:]
        return self._attach_pending(Block(
            cols=dict(zip(names, col_arrays)), counts=counts,
            capacity=out_cap, mesh=self.mesh,
            counts_host=self._last_counts_host))

    def collect_grouped(self):
        """Columnar grouped collect: (keys, offsets, values) numpy arrays,
        where group i's values are values[offsets[i]:offsets[i+1]] — the
        ragged result WITHOUT per-key Python lists (group_by_key's scale
        face; reference aggregator.rs:33-53 builds Vecs instead). Shards are
        key-sorted and hash-disjoint, so boundaries fall out of one
        vectorized pass over the concatenated rows."""
        cols = self.block().to_numpy()
        return _grouped_columnar(cols[KEY], cols[VALUE])

    def collect(self) -> list:
        # keys are sorted within each shard; shards don't overlap (hash
        # partitioned), so grouping is a single pass per shard run.
        cols = self.block().to_numpy()
        with spans.span("pivot"):
            return list(_sorted_runs(cols[KEY], cols[VALUE]))

    def compute(self, split: Split, task_context=None):
        rows = self.block().shard_rows(split.index)
        yield from _sorted_runs(rows[KEY], rows[VALUE])


class _JoinRDD(_ExchangeRDD):
    """Device sort-merge join with full duplicate-key semantics (dup x dup
    product, reference pair_rdd.rs:104-121) — no host fallback on the dense
    path. Output expansion beyond the exchange capacity is reported exactly
    by the kernel and rerun once at the right capacity. A hash-placed side
    (e.g. a reduce_by_key output) skips its exchange entirely."""

    hash_placed = True  # joined rows stay on their key's shard
    key_sorted = True   # output follows the left sort order

    def __init__(self, left: DenseRDD, right: DenseRDD,
                 outer: bool = False, fill_value=0):
        super().__init__(left.context, left.mesh, [left, right])
        self.left = left
        self.right = right
        self.outer = outer
        self.fill_value = fill_value

    def _fp_extra(self):
        # repr() keeps NaN fills hint-stable (nan != nan would make every
        # hint lookup miss and leak a store entry per run).
        return (self.outer, repr(self.fill_value), self.exchange_mode)

    @staticmethod
    def _side_value_names(schema):
        """Value-column names of one side in schema order — VALUE plus its
        wide low word when the side carries int64 values."""
        return [nm for nm, _ in schema if nm not in (KEY, KEY_LO)]

    def _schema(self):
        ls = dict(self.left._schema())
        key_schema = ((KEY, ls[KEY]),)
        if KEY_LO in ls:
            key_schema += ((KEY_LO, ls[KEY_LO]),)
        out = key_schema
        for prefix, side in (("lv", self.left), ("rv", self.right)):
            for nm, dt in side._schema():
                if nm in (KEY, KEY_LO):
                    continue
                out += ((_join_rename(nm, prefix), dt),)
        return out

    def _dicts(self):
        # KEY: both sides were unified by _align_keys before construction
        # (or never diverged), so the left side's key dictionary IS the
        # shared one. Values: each side's dictionary follows its column
        # through the lv/rv rename.
        out = {}
        ld, rd = self.left._dicts(), self.right._dicts()
        if KEY in ld:
            out[KEY] = ld[KEY]
        for prefix, side_d, side in (("lv", ld, self.left),
                                     ("rv", rd, self.right)):
            for nm, _dt in side._schema():
                if nm in (KEY, KEY_LO) or nm not in side_d:
                    continue
                out[_join_rename(nm, prefix)] = side_d[nm]
        return out

    def _materialize(self) -> Block:
        n = self.mesh.size
        # Per-side exchange elision: a hash-placed side's rows are already
        # on their key's shard (reduce/group/join outputs), so only the
        # other side moves — the north-star reduced.join(table) pipeline
        # pays ONE collective instead of two.
        self.left._settle_placement()   # materialized truth, explicitly
        self.right._settle_placement()
        l_elide = self.left.hash_placed and n > 1
        r_elide = self.right.hash_placed and n > 1
        # Pending narrow chains fuse into the join program (same
        # rematerialization trade as reduce/group) — only on sides whose
        # exchange sizes from a post-chain histogram; elided/1-shard
        # sides size from raw counts and materialize as before.
        l_chain, l_root = (_narrow_chain(self.left)
                           if n > 1 and not l_elide else ([], self.left))
        r_chain, r_root = (_narrow_chain(self.right)
                           if n > 1 and not r_elide else ([], self.right))
        # cached program must not pin nodes
        l_chain = _detached_chain(l_chain)
        r_chain = _detached_chain(r_chain)
        outer, fill_value = self.outer, self.fill_value
        lblk = l_root.block_spec()  # we register our own pending entry
        rblk = r_root.block_spec()
        l_in = list(lblk.cols)
        r_in = list(rblk.cols)
        # Key layout is aligned by _align_keys before a _JoinRDD is built:
        # both sides carry the same key columns (single, or (KEY, KEY_LO)).
        lschema = dict(self.left._schema())
        key_names = [KEY] + ([KEY_LO] if KEY_LO in lschema else [])
        lo_name = KEY_LO if KEY_LO in lschema else None
        l_val_names = self._side_value_names(self.left._schema())
        r_val_names = self._side_value_names(self.right._schema())
        n_vals = len(l_val_names) + len(r_val_names)
        # Sortedness survives only the elided (stable passthrough) path.
        l_sorted = l_elide and self.left.key_sorted
        r_sorted = r_elide and self.right.key_sorted
        join_cap_override: List[Optional[int]] = [None]
        join_cap_used: List[int] = [0]
        n_l = 1 + len(l_in)  # counts + left root columns

        def one_side(cols, count, elide, slot_pair, out_cap, exchange):
            if elide:
                return kernels.passthrough_exchange(
                    cols, count, cols[KEY].shape[0], out_cap
                )
            bucket = (_bucket_cols(cols, n)
                      if n > 1 else jnp.zeros_like(cols[KEY]))
            return exchange(cols, count, bucket, n, slot_pair, out_cap)

        def build(slot_pair, out_cap):
            join_cap = join_cap_override[0] or out_cap
            join_cap_used[0] = join_cap
            if l_elide and r_elide:
                exchange, x_tok = kernels.bucket_exchange, _X_ELIDED
            else:
                moving = [b for b, el in ((lblk, l_elide), (rblk, r_elide))
                          if not el]
                exchange, x_tok = self._resolve_exchange(
                    moving, slot_pair, out_cap)

            def prog_fn(*args):
                lc, *lkv = args[:n_l]
                rc, *rkv = args[n_l:]
                lcols, lcount = _apply_chain(
                    l_chain, dict(zip(l_in, lkv)), lc[0]
                )
                rcols, rcount = _apply_chain(
                    r_chain, dict(zip(r_in, rkv)), rc[0]
                )
                lcols, lcount, lof = one_side(
                    lcols, lcount, l_elide, slot_pair, out_cap, exchange
                )
                rcols, rcount, rof = one_side(
                    rcols, rcount, r_elide, slot_pair, out_cap, exchange
                )
                joined, jcount, jtotal = kernels.merge_join_expand(
                    lcols, lcount, rcols, rcount, KEY, join_cap,
                    outer=outer, fill_value=fill_value,
                    left_sorted=l_sorted, right_sorted=r_sorted,
                    lo_name=lo_name,
                )
                return (
                    jcount.reshape(1), jtotal.reshape(1),
                ) + tuple(joined[nm] for nm in key_names) + tuple(
                    joined[nm] for nm in l_val_names
                ) + tuple(
                    joined[f"r_{nm}"] for nm in r_val_names
                ) + ((lof | rof).reshape(1),)

            prog = _cached_program(
                ("join", self.mesh, n, tuple(key_names), tuple(l_in),
                 tuple(r_in), _chain_fp(l_chain), _chain_fp(r_chain),
                 slot_pair, out_cap,
                 join_cap, l_elide, r_elide, l_sorted, r_sorted,
                 self.exchange_mode, x_tok, self.outer,
                 repr(self.fill_value)),
                lambda: _shard_program(
                    self.mesh, prog_fn, 2 + len(l_in) + len(r_in),
                    (_SPEC,) * (3 + len(key_names) + n_vals)),
            )
            return prog, (
                lblk.counts, *[lblk.cols[nm] for nm in l_in],
                rblk.counts, *[rblk.cols[nm] for nm in r_in],
            )

        counts_fn = lambda: np.concatenate([lblk.counts_np, rblk.counts_np])
        self._elided = (l_elide, r_elide)
        self._fetch_extra_outs = 1  # jtotals rides the counts transfer

        def make_hists():
            # Blocking path only (post-settle), so counts_np is safe/free.
            hs = [
                np.diag(lblk.counts_np) if l_elide
                else self._hash_histogram(lblk, l_chain),
                np.diag(rblk.counts_np) if r_elide
                else self._hash_histogram(rblk, r_chain),
            ]
            # Elided (diag) sides never send: keep them out of slot sizing.
            return hs, [h for h, el in zip(hs, (l_elide, r_elide))
                        if not el]

        hint = self._hint_key()
        # The dup x dup product size is also hint-memoized: without it, a
        # join whose product exceeds the exchange-sized cap would repeat
        # its full-launch resize on every warm rerun.
        hint_store = self.context.__dict__.setdefault(
            "_dense_capacity_hints", {})
        jc_key = (hint, "join_cap")
        if jc_key in hint_store:
            join_cap_override[0] = hint_store[jc_key]

        def validate(head):
            """Deferred-mode product checks (the blocking path's inline
            logic below, recast for _settle_pending)."""
            jtot = int(head[1].max(initial=0))
            if jtot >= 2**31 - 1:
                raise VegaError(
                    "dense join product exceeds 2^31 rows on one shard — "
                    "cannot materialize; filter or pre-aggregate the "
                    "heavy keys"
                )
            if jtot > join_cap_used[0]:
                # Stash the exact product cap for the settle-repair rerun.
                hint_store[jc_key] = _cap_round(jtot)
                return False
            return True

        def on_success(_head):
            if join_cap_override[0]:
                hint_store.pop(jc_key, None)  # move-to-end (recency)
                hint_store[jc_key] = join_cap_override[0]
                while len(hint_store) > 4096:
                    hint_store.pop(next(iter(hint_store)))

        # One shard moves nothing, and an elided side stays where it is.
        moved = [side for side, elide in (((lblk, l_chain), l_elide),
                                          ((rblk, r_chain), r_elide))
                 if n > 1 and not elide]
        outs, _ = self._run_exchange(build, counts_fn,
                                     make_hists=make_hists,
                                     hint_key=hint, validate=validate,
                                     on_success=on_success, moved=moved)
        if "_deferred_entry" not in self.__dict__:
            # Blocking path: run the same product checks the deferred
            # entry runs at settlement (ONE policy, validate above). On a
            # cap miss, validate stashed the exact product cap under
            # jc_key; ONE resized rerun is guaranteed to fit (the kernel
            # reported the exact size — no geometric-growth walk).
            if not validate([None, self._last_extra_host[0]]):
                join_cap_override[0] = hint_store[jc_key]
                outs, _ = self._run_exchange(build, counts_fn,
                                             make_hists=make_hists,
                                             hint_key=hint,
                                             validate=validate,
                                             on_success=on_success,
                                             moved=moved)
            if "_deferred_entry" not in self.__dict__ \
                    and join_cap_override[0]:
                on_success(None)
        jcounts = outs[0]
        key_arrays = outs[2:2 + len(key_names)]
        val_arrays = outs[2 + len(key_names):2 + len(key_names) + n_vals]
        out_names = ([_join_rename(nm, "lv") for nm in l_val_names]
                     + [_join_rename(nm, "rv") for nm in r_val_names])
        cols = dict(zip(key_names, key_arrays))
        cols.update(dict(zip(out_names, val_arrays)))
        return self._attach_pending(Block(
            cols=cols,
            counts=jcounts, capacity=join_cap_used[0], mesh=self.mesh,
            counts_host=self._last_counts_host,
        ))

    @staticmethod
    def _rows(cols: dict):
        # to_numpy/shard_rows decode wide (lv, lv.lo) pairs to int64
        # before this zip, so lv/rv are single columns again. Nested zips
        # and no generator expression: a Python frame a row gives every
        # collection CPython 3.12+ schedules an eval breaker to run at
        # (19,118 in a 6.7M-row collect(), half its time: PERF.md PR 29);
        # list() over C-level iterators pays one, after the last row.
        return zip(cols[KEY].tolist(),
                   zip(cols["lv"].tolist(), cols["rv"].tolist()))

    def collect(self) -> list:
        cols = self.block().to_numpy()
        with spans.span("pivot"):
            return list(self._rows(cols))

    def count(self) -> int:
        return self.block().num_rows

    def compute(self, split: Split, task_context=None):
        yield from self._rows(self.block().shard_rows(split.index))


class _SortByKeyRDD(_ExchangeRDD):
    def __init__(self, parent: DenseRDD, ascending: bool, sample_size: int):
        super().__init__(parent.context, parent.mesh, [parent])
        self.parent = parent
        self.ascending = ascending
        self.sample_size = sample_size

    def _fp_extra(self):
        return (self.ascending, self.sample_size, self.exchange_mode)

    def _schema(self):
        return self.parent._schema()

    def _materialize(self) -> Block:
        n = self.mesh.size
        # Fused only on the multi-shard path (1-shard sizing uses raw
        # counts; see reduce). The range exchange itself never elides.
        chain, root = (_narrow_chain(self.parent) if n > 1
                       else ([], self.parent))
        chain = _detached_chain(chain)  # cached program must not pin nodes
        blk = root.block()
        in_names = list(blk.cols)
        names = [nm for nm, _ in self.parent._schema()]
        lo_name = _lo_of(names)
        composite = lo_name is not None
        # Sampler inputs: key columns only when no chain is fused (one
        # universal compile across value schemas, like the histograms).
        samp_in = (in_names if chain
                   else [KEY] + ([KEY_LO] if composite else []))

        # Bound sampling: ONE device program applies the fused chain and
        # gathers a strided sample per shard into a fixed [n_shards, 2m]
        # buffer, fetched with the post-chain shard counts in a single
        # transfer — the per-shard host slicing this replaces cost one
        # driver<->device round trip PER SHARD. Post-chain counts also
        # size the exchange exactly when the chain filters rows.
        m = max(1, self.sample_size // max(1, blk.n_shards))
        samp_cap = blk.capacity  # plain int: samp_fn must not pin the Block

        @spans.stage("sample")
        def samp_fn(counts_arg, *col_arrays):
            cols, count = _apply_chain(
                chain, dict(zip(samp_in, col_arrays)), counts_arg[0]
            )
            keycols = ((cols[KEY], cols[lo_name]) if composite
                       else (cols[KEY],))
            stride = jnp.maximum(jnp.int32(1), count // jnp.int32(m))
            pos = jnp.clip(lax.iota(jnp.int32, 2 * m) * stride,
                           0, max(samp_cap - 1, 0))
            return (count.reshape(1),) + tuple(
                jnp.take(kc, pos).reshape(1, -1) for kc in keycols
            )

        samp_prog = _cached_program(
            ("sortsamp", self.mesh, m, blk.capacity, composite,
             tuple(samp_in), _chain_fp(chain)),
            lambda: _shard_program(
                self.mesh, samp_fn, 1 + len(samp_in),
                (_SPEC,) * (2 + composite),
            ),
        )
        samp_out = mesh_lib.host_get(
            samp_prog(blk.counts, *[blk.cols[nm] for nm in samp_in])
        )
        counts_host = np.asarray(samp_out[0]).reshape(-1)
        samp_hi = np.asarray(samp_out[1]).reshape(blk.n_shards, 2 * m)
        if composite:
            samp_lo = np.asarray(samp_out[2]).reshape(blk.n_shards, 2 * m)
        samples = []
        for s in range(blk.n_shards):
            c = int(counts_host[s])
            if c == 0:
                continue
            stride = max(1, c // m)
            n_valid = min(2 * m, -(-c // stride))
            keys = samp_hi[s, :n_valid]
            if composite:
                keys = block_lib.decode_i64(keys, samp_lo[s, :n_valid])
            samples.append(keys)
        if samples:
            allk = np.sort(np.concatenate(samples))
            if not self.ascending:
                allk = allk[::-1]
            idx = [int(len(allk) * i / n) for i in range(1, n)]
            bounds = allk[idx] if len(allk) else np.array([], allk.dtype)
        elif composite:
            bounds = np.zeros((n - 1,), np.int64)
        else:
            bounds = np.zeros((n - 1,),
                              np.dtype(dict(self.parent._schema())[KEY]))
        repl = mesh_lib.replicated_spec(self.mesh)
        if composite:
            bounds_hi, bounds_lo = block_lib.encode_i64(bounds)
            bounds_dev = mesh_lib.host_put(bounds_hi, repl)
            bounds_lo_dev = mesh_lib.host_put(bounds_lo, repl)
        else:
            bounds_dev = mesh_lib.host_put(bounds, repl)
            bounds_lo_dev = None
        ascending = self.ascending

        def build(slot, out_cap):
            exchange, x_tok = self._resolve_exchange((blk,), slot, out_cap)

            def prog_fn(*args):
                if composite:
                    bnds, bnds_lo, counts, *col_arrays = args
                else:
                    (bnds, counts, *col_arrays), bnds_lo = args, None
                cols, count = _apply_chain(
                    chain, dict(zip(in_names, col_arrays)), counts[0]
                )
                keys = cols[KEY]
                if n == 1:
                    bucket = jnp.zeros_like(keys, shape=keys.shape).astype(jnp.int32)
                else:
                    bucket = kernels.range_bucket(
                        bnds, keys, ascending, bounds_lo=bnds_lo,
                        keys_lo=cols.get(lo_name) if composite else None,
                    )
                cols, count, overflow = exchange(
                    cols, count, bucket, n, slot, out_cap)
                cols = kernels.sort_by_column(
                    cols, count, KEY, descending=not ascending,
                    lo_name=lo_name)
                return (count.reshape(1),) + tuple(
                    cols[nm] for nm in names
                ) + (overflow.reshape(1),)

            key = ("sort", self.mesh, tuple(in_names), tuple(names),
                   _chain_fp(chain), n, slot, out_cap,
                   ascending, self.exchange_mode, x_tok)
            prog = _cached_program(
                key,
                lambda: _shard_program(
                    self.mesh, prog_fn,
                    (_REPL,) * (1 + composite)
                    + (_SPEC,) * (1 + len(in_names)),
                    (_SPEC,) * (2 + len(names)),
                ),
            )
            dev_bounds = ((bounds_dev, bounds_lo_dev) if composite
                          else (bounds_dev,))
            return prog, (*dev_bounds, blk.counts,
                          *[blk.cols[nm] for nm in in_names])

        outs, out_cap = self._run_exchange(
            build, counts_host,
            make_hists=lambda: ([self._range_histogram(
                blk, bounds_dev, ascending, bounds_lo_dev,
                chain=chain)], None),
            # Bounds are data-derived: same data -> same bounds, and a
            # changed distribution changes the bounds, so they belong in
            # the hint identity (with the post-chain counts the sampling
            # already fetched).
            hint_key=self._hint_key(counts_host.tobytes(),
                                    bounds.tobytes()),
            moved=[(blk, chain)] if n > 1 else (),
        )
        counts, col_arrays = outs[0], outs[1:]
        return self._attach_pending(Block(
            cols=dict(zip(names, col_arrays)), counts=counts,
            capacity=out_cap, mesh=self.mesh,
            counts_host=self._last_counts_host))


class _CartesianDenseRDD(DenseRDD):
    """Device cross product: right side replicated, each shard
    ragged-expands its left rows against all right rows (m = rtotal per
    valid left row -> ragged_expand slot ownership). Parents materialize
    at construction: the product-size budget gate needs real counts, and
    an over-budget product must fall back to the host tier's lazy
    cartesian BEFORE a node type is fixed."""

    def __init__(self, left: DenseRDD, right: DenseRDD, budget: int):
        lblk = left.block()
        rblk = right.block()
        r_total = rblk.num_rows
        l_counts = lblk.counts_np
        max_l = int(l_counts.max()) if l_counts.size else 0
        out_cap = block_lib._round_capacity(max(max_l * max(r_total, 1), 1))
        row_bytes = sum(c.dtype.itemsize for c in lblk.cols.values()) + \
            sum(c.dtype.itemsize for c in rblk.cols.values())
        if out_cap * row_bytes * 3 > budget:
            raise _NotTraceable(
                f"cartesian product (~{out_cap} rows/shard) exceeds the "
                "HBM budget — host tier streams it lazily instead"
            )
        super().__init__(left.context, left.mesh, [left, right])
        self.left = left
        self.right = right
        self._r_total = r_total
        self._out_cap = out_cap

    def _schema(self):
        # Canonical (KEY, VALUE) so the product is a pair RDD on BOTH
        # tiers: host cartesian's (x, y) tuples are pairs, and the dense
        # result must accept the same downstream pair ops.
        ldt = dict(self.left._schema())[VALUE]
        rdt = dict(self.right._schema())[VALUE]
        return ((KEY, ldt), (VALUE, rdt))

    def _materialize(self) -> Block:
        lblk = self.left.block()
        rblk = self.right.block()
        n = self.mesh.size
        r_total, out_cap = self._r_total, self._out_cap
        if r_total == 0:
            # Empty right side: the product is empty; build it directly
            # (a zero-length replicated operand cannot be gathered from).
            schema = dict(self._schema())
            return block_lib.from_numpy(
                {KEY: np.zeros(0, schema[KEY]),
                 VALUE: np.zeros(0, schema[VALUE])},
                self.mesh,
            )
        rvals_host = rblk.to_numpy()[VALUE]
        rvals = mesh_lib.host_put(rvals_host,
                               mesh_lib.replicated_spec(self.mesh))

        def prog_fn(rv, counts, lvals):
            cap = lvals.shape[0]
            m = jnp.where(kernels.valid_mask(cap, counts[0]),
                          jnp.int32(r_total), 0)
            owner, off, total = kernels.ragged_expand(m, out_cap)
            a = jnp.take(lvals, owner)
            b = jnp.take(rv, jnp.clip(off, 0, max(r_total - 1, 0)))
            return total.reshape(1), a, b

        prog = _cached_program(
            ("cart", self.mesh, n, lblk.capacity, r_total, out_cap),
            lambda: _shard_program(self.mesh, prog_fn,
                                   (_REPL, _SPEC, _SPEC), (_SPEC,) * 3),
        )
        counts, a, b = prog(rvals, lblk.counts, lblk.cols[VALUE])
        return Block(cols={KEY: a, VALUE: b}, counts=counts,
                     capacity=out_cap, mesh=self.mesh)


class _SampleRDD(_NarrowRDD):
    """Per-shard Bernoulli sampling with a threefry stream folded by shard id
    (deterministic per (seed, shard))."""

    def __init__(self, parent: DenseRDD, fraction: float, seed: int):
        super().__init__(parent, parent._schema())
        self._fraction = float(fraction)
        self._seed = int(seed)
        self._user_fn = ("sample", self._fraction, self._seed)

    def _shard_fn(self, cols, count):
        cap = next(iter(cols.values())).shape[0]
        # Per-shard stream: fold the shard's first-row global position in.
        shard_tag = count * 0 + lax.axis_index(mesh_lib.SHARD_AXIS)
        key = jax.random.fold_in(jax.random.PRNGKey(self._seed), shard_tag)
        u = jax.random.uniform(key, (cap,))
        keep = (u < self._fraction) & kernels.valid_mask(cap, count)
        return kernels.compact(cols, keep, cap)


def _grouped_columnar(keys: np.ndarray, vals: np.ndarray):
    """(group_keys, offsets, values) from key-sorted runs: group i's values
    are values[offsets[i]:offsets[i+1]]. Pure vectorized numpy — no per-row
    or per-key Python. Rows from different shards never share a key (hash
    partitioning), so a key change marks every group boundary including
    shard boundaries."""
    if len(keys) == 0:
        return keys, np.zeros(1, dtype=np.int64), vals
    starts = np.concatenate(
        [[0], np.flatnonzero(keys[1:] != keys[:-1]) + 1]
    ).astype(np.int64)
    offsets = np.concatenate([starts, [len(keys)]])
    return keys[starts], offsets, vals


def _sorted_runs(keys: np.ndarray, vals: np.ndarray):
    """(key, [values]) pairs from a key-sorted run (shared by group_by_key
    collect/compute and cogroup) — the host-facing view of
    _grouped_columnar; per-GROUP (not per-row) Python cost."""
    group_keys, offsets, values = _grouped_columnar(keys, vals)
    for i, k in enumerate(group_keys.tolist()):
        yield k, values[offsets[i]:offsets[i + 1]].tolist()


class _DenseCoGroupRDD(RDD):
    """Host-facing view over two device-grouped blocks: each side runs the
    dense group-by-key exchange (same hash -> same shard), and compute()
    merges the two sorted runs per shard into (k, (l_values, r_values)).

    Because this is a plain RDD with a partitioner-consistent layout, every
    host pair op (join variants, flat_map_values, ...) composes on top."""

    def __init__(self, left: DenseRDD, right: DenseRDD):
        from vega_tpu.dependency import OneToOneDependency

        self.left_grouped = _GroupByKeyRDD(left)
        self.right_grouped = _GroupByKeyRDD(right)
        super().__init__(left.context, deps=[
            OneToOneDependency(self.left_grouped),
            OneToOneDependency(self.right_grouped),
        ])
        self.mesh = left.mesh

    @property
    def num_partitions(self) -> int:
        return self.mesh.size

    def compute(self, split: Split, task_context=None):
        # Columnar alignment: both sides are key-sorted runs, so the merge
        # is two vectorized searchsorted passes; Python cost is per GROUP
        # (the unavoidable host-facing (k, ([lvs], [rvs])) assembly), never
        # per row.
        yield from self._group_rows(
            self.left_grouped.block().shard_rows(split.index),
            self.right_grouped.block().shard_rows(split.index))

    @staticmethod
    def _group_rows(lrows: dict, rrows: dict):
        lk, loff, lv = _grouped_columnar(lrows[KEY], lrows[VALUE])
        rk, roff, rv = _grouped_columnar(rrows[KEY], rrows[VALUE])

        union = np.union1d(lk, rk)
        li = np.searchsorted(lk, union)
        ri = np.searchsorted(rk, union)
        has_l = np.isin(union, lk, assume_unique=True)
        has_r = np.isin(union, rk, assume_unique=True)
        for j, k in enumerate(union.tolist()):
            lvs = (lv[loff[li[j]]:loff[li[j] + 1]].tolist()
                   if has_l[j] else [])
            rvs = (rv[roff[ri[j]]:roff[ri[j] + 1]].tolist()
                   if has_r[j] else [])
            yield (k, (lvs, rvs))

    def collect(self) -> list:
        out = []
        for s in range(self.num_partitions):
            lrows = self.left_grouped.block().shard_rows(s)
            rrows = self.right_grouped.block().shard_rows(s)
            with spans.span("pivot"):
                out.extend(self._group_rows(lrows, rrows))
        return out

    def collect_grouped(self):
        """Columnar cogroup: (keys, l_offsets, l_values, r_offsets,
        r_values) — group i's left values are
        l_values[l_offsets[i]:l_offsets[i+1]] (resp. right). No per-row or
        per-key Python: keys are hash-disjoint across shards and sorted
        within one, so each shard's two sides align with one union +
        searchsorted pass and value arrays concatenate untouched."""
        def expand_offsets(gk, goff, union):
            # gk is a subset of the sorted union, so one scatter places
            # each group's length at its union slot.
            lengths = np.zeros(len(union), dtype=np.int64)
            lengths[np.searchsorted(union, gk)] = goff[1:] - goff[:-1]
            return np.concatenate([[0], np.cumsum(lengths)])

        # One device gather per side (counts fetched once, columns whole);
        # shard boundaries are then host-side splits — no per-shard
        # device round-trips.
        lblk = self.left_grouped.block()
        rblk = self.right_grouped.block()
        l_counts = lblk.counts_np
        r_counts = rblk.counts_np
        lall = lblk.to_numpy()
        rall = rblk.to_numpy()

        def shard_parts(all_cols, counts):
            splits = np.cumsum(counts)[:-1]
            return (np.split(all_cols[KEY], splits),
                    np.split(all_cols[VALUE], splits))

        lk_s, lv_s = shard_parts(lall, l_counts)
        rk_s, rv_s = shard_parts(rall, r_counts)

        keys_parts, lv_parts, rv_parts = [], [], []
        lo_parts, ro_parts = [np.zeros(1, np.int64)], [np.zeros(1, np.int64)]
        l_base = r_base = 0
        for s in range(self.num_partitions):
            lk, loff, lv = _grouped_columnar(lk_s[s], lv_s[s])
            rk, roff, rv = _grouped_columnar(rk_s[s], rv_s[s])
            union = np.union1d(lk, rk)
            if not len(union):
                continue
            keys_parts.append(union)
            lo = expand_offsets(lk, loff, union)
            ro = expand_offsets(rk, roff, union)
            lo_parts.append(lo[1:] + l_base)
            ro_parts.append(ro[1:] + r_base)
            l_base += lo[-1]
            r_base += ro[-1]
            lv_parts.append(lv)
            rv_parts.append(rv)
        if not keys_parts:
            zero = np.zeros(1, np.int64)
            return (lall[KEY][:0], zero, lall[VALUE][:0],
                    zero, rall[VALUE][:0])
        return (np.concatenate(keys_parts),
                np.concatenate(lo_parts), np.concatenate(lv_parts),
                np.concatenate(ro_parts), np.concatenate(rv_parts))


class _DenseUnionRDD(DenseRDD):
    """Per-shard concatenation of two same-schema dense RDDs."""

    def __init__(self, first: DenseRDD, second: DenseRDD):
        super().__init__(first.context, first.mesh, [first, second])
        self.first = first
        self.second = second

    @property
    def hash_placed(self) -> bool:
        # Same placement function on both sides -> concat preserves it.
        return self.first.hash_placed and self.second.hash_placed

    def _settle_placement(self) -> None:
        self.first._settle_placement()
        self.second._settle_placement()

    def _schema(self):
        return self.first._schema()

    def _materialize(self) -> Block:
        a = self.first.block()
        b = self.second.block()
        names = [n for n, _ in self._schema()]
        concat_cap = a.capacity + b.capacity
        # Size the output from VALID counts when both sides already know
        # them on host (block() settled them; no fetch here, ever) —
        # capacity-sum sizing made the streamed reduce's accumulator
        # union grow its capacity geometrically: each chunk's elided
        # merge inherited cap(acc)+cap(partial), so the accumulator
        # DOUBLED per chunk at constant key count (16->32->64->128 MiB
        # at 1M keys; round-5 stream_1b profiling). Known counts also
        # ride out on the Block so downstream elided exchanges
        # (_elide_out_cap) size tightly instead of falling back to
        # capacity.
        counts_host = None
        if a.counts_host is not None and b.counts_host is not None:
            counts_host = (np.asarray(a.counts_host)
                           + np.asarray(b.counts_host))
            out_cap = block_lib._round_capacity(
                max(int(counts_host.max()), 1))
        else:
            out_cap = block_lib._round_capacity(concat_cap)
        cap_a = a.capacity  # plain int: the closure must not pin the Block

        def shard_concat(ac, bc, *cols):
            half = len(names)
            a_cols = dict(zip(names, cols[:half]))
            b_cols = dict(zip(names, cols[half:]))
            a_count, b_count = ac[0], bc[0]
            # Concatenate at full width, then compact into the (possibly
            # smaller, counts-sized) output capacity.
            out = {name: jnp.concatenate([a_cols[name], b_cols[name]])
                   for name in names}
            # mark validity: rows [0,a_count) and [cap_a, cap_a+b_count)
            idx = lax.iota(jnp.int32, concat_cap)
            keep = (idx < a_count) | (
                (idx >= cap_a) & (idx < cap_a + b_count)
            )
            return kernels.compact(out, keep, out_cap) + tuple()

        def prog_fn(ac, bc, *cols):
            out, count = shard_concat(ac, bc, *cols)
            return (count.reshape(1),) + tuple(out[n] for n in names)

        prog = _cached_program(
            ("dense_union", self.mesh, tuple(names), a.capacity, b.capacity,
             out_cap),
            lambda: _shard_program(
                self.mesh, prog_fn, 2 + 2 * len(names),
                (_SPEC,) * (1 + len(names)),
            ),
        )
        outs = prog(a.counts, b.counts,
                    *[a.cols[n] for n in names], *[b.cols[n] for n in names])
        counts, col_arrays = outs[0], outs[1:]
        return Block(cols=dict(zip(names, col_arrays)), counts=counts,
                     capacity=out_cap, mesh=self.mesh,
                     counts_host=counts_host)


def _infer_named_op(func) -> Optional[str]:
    """Sound monoid recognition shared with the host tier (exact identities
    only — see vega_tpu/rdd/pair.py:_infer_named_op). Unrecognized
    associative functions still run correctly via the segmented
    associative-scan path; this only selects the faster XLA segment op."""
    from vega_tpu.rdd.pair import _infer_named_op as _host_infer

    return _host_infer(func)
