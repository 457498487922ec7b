"""Driver entry point (reference: src/context.rs).

Owns RDD/shuffle id counters (context.rs:398-404), RDD constructors
(make_rdd/parallelize/range/read_source/union, context.rs:406-455,537-539) and
job runners (run_job/run_approximate_job, context.rs:457-524). Deployment mode
selects the task backend: local thread pool, distributed executor fleet
(vega_tpu/distributed), with the device tier layered on top for numeric RDDs
(vega_tpu/tpu).
"""

from __future__ import annotations

import itertools
import logging
import threading
import time
from typing import Any, Callable, List, Optional, Sequence

from vega_tpu.cache_tracker import CacheTracker
from vega_tpu.env import Configuration, DeploymentMode, Env
from vega_tpu.errors import VegaError
from vega_tpu.map_output_tracker import MapOutputTracker
from vega_tpu.partial.partial_result import PartialResult
from vega_tpu.rdd.base import RDD
from vega_tpu.scheduler.dag import DAGScheduler
from vega_tpu.scheduler.events import LiveListenerBus, MetricsListener
from vega_tpu.scheduler.jobserver import JobFuture, JobServer
from vega_tpu.scheduler.local_backend import LocalBackend

log = logging.getLogger("vega_tpu")


import contextlib
from vega_tpu.lint.sync_witness import assert_role, named_lock


@contextlib.contextmanager
def _profile_trace(log_dir: str):
    import jax

    from vega_tpu.tpu import spans

    jax.profiler.start_trace(log_dir)
    spans.new_session()
    try:
        yield
    finally:
        jax.profiler.stop_trace()


_active_context_lock = named_lock("context._active_context_lock")
_active_context: Optional["Context"] = None


class Context:
    def __init__(self, mode: str | DeploymentMode = "local",
                 conf: Optional[Configuration] = None,
                 multihost: Optional[dict] = None, **conf_overrides):
        global _active_context
        self._stopped = False
        # Claim the active slot atomically with the liveness check (a
        # check-then-register race would let two threads both pass and the
        # second Env.reset clobber the first context's shuffles — the
        # exact corruption this guard exists to prevent).
        with _active_context_lock:
            if _active_context is not None and not _active_context._stopped:
                raise VegaError(
                    "a Context is already active in this process — the Env "
                    "(shuffle store, trackers) is a process singleton like "
                    "the reference's (env.rs:38-40), so a second Context "
                    "would silently break the first one's shuffles. Call "
                    ".stop() on it — reachable via Context.active() if the "
                    "variable was lost — or use `with Context(...)`."
                )
            _active_context = self
        try:
            if isinstance(mode, str):
                mode = DeploymentMode(mode)
            conf = conf or Configuration.from_environ()
            conf.deployment_mode = mode
            for key, value in conf_overrides.items():
                if not hasattr(conf, key):
                    raise TypeError(f"unknown configuration field: {key}")
                setattr(conf, key, value)
            self.conf = conf
            if multihost is not None:
                # Join the jax.distributed global mesh BEFORE any backend
                # touch: every process runs this same driver program and
                # the dense tier then executes SPMD over all processes'
                # devices (the DCN analogue of the reference's multi-host
                # executor fleet, context.rs:209-303). Keys: coordinator,
                # num_processes, process_id (each defaultable from the
                # JAX_* env vars — see tpu/mesh.init_multihost).
                from vega_tpu.tpu import mesh as _mesh_lib

                _mesh_lib.ensure_multihost(**multihost)
            env = Env.reset(conf, is_driver=True)
            env.map_output_tracker = MapOutputTracker()
            env.cache_tracker = CacheTracker()
            self._log_handler = None

            self._next_rdd_id = itertools.count(0)
            self._next_shuffle_id = itertools.count(0)

            self.bus = LiveListenerBus()
            self.metrics = MetricsListener()
            self.bus.add_listener(self.metrics)
            self.bus.start()
            # Storage tiering observability: the tiered cache and shuffle
            # store post BlockSpilled/BlockPromoted onto the scheduler
            # event bus (executors have no bus; they keep counters that
            # surface through the shuffle server's `status`).
            env.cache.event_sink = self.bus.post
            env.shuffle_store.event_sink = self.bus.post
            # Fetch-pipeline observability: driver-side reduce tasks post
            # ShuffleFetchCompleted per stream (round trips / bytes /
            # overlap); executor fetches keep fetcher-local counters.
            env.fetch_event_sink = self.bus.post

            if mode is DeploymentMode.LOCAL:
                self._backend = LocalBackend()
            else:
                from vega_tpu.distributed.backend import DistributedBackend

                self._backend = DistributedBackend(conf)
            self.scheduler = DAGScheduler(self._backend, self.bus)
            # Multi-job front door (scheduler/jobserver.py): every action
            # — blocking or async — routes through it, so fair-scheduling
            # pools, quotas, and cancellation apply uniformly. Jobs run
            # concurrently, each on its own event-loop thread.
            self.job_server = JobServer(self.scheduler, conf)
            # Elastic serving plane (scheduler/elastic.py): the
            # autoscaler exists for any fleet-shaped backend so manual
            # decommission and fleet_status work even with the control
            # loop off; the loop itself only runs under elastic_enabled.
            self.elastic = None
            if hasattr(self._backend, "fleet_snapshot"):
                from vega_tpu.scheduler.elastic import ElasticController

                self.elastic = ElasticController(
                    self._backend, self.job_server.arbiter,
                    self.scheduler, conf, self.bus)
                if getattr(conf, "elastic_enabled", False):
                    self.elastic.start()
            # Thread-local submission properties (Spark's
            # setLocalProperty): "pool" selects the scheduling pool for
            # jobs submitted from this thread.
            self._local_props = threading.local()
            # Lazily created micro-batch streaming plane
            # (vega_tpu/streaming/): one per Context, like the job server.
            self._streaming = None
            # Attach last: a failed backend init must not leak a file
            # handler on the process-global logger.
            from vega_tpu.env import attach_session_logger

            self._prev_logger_level = log.level
            self._log_handler = attach_session_logger(env, "driver")
        except BaseException:
            with _active_context_lock:
                if _active_context is self:
                    _active_context = None
            raise

    @staticmethod
    def active() -> Optional["Context"]:
        """The live Context of this process, if any — the recovery handle
        when the creating variable was lost (Context.active().stop())."""
        with _active_context_lock:
            return _active_context

    # ------------------------------------------------------------------ ids
    def new_rdd_id(self) -> int:
        """Reference: context.rs:398-400."""
        return next(self._next_rdd_id)

    def new_shuffle_id(self) -> int:
        """Reference: context.rs:402-404."""
        return next(self._next_shuffle_id)

    # ----------------------------------------------------------- constructors
    def parallelize(self, data: Sequence, num_slices: Optional[int] = None) -> RDD:
        """Reference: context.rs:406-420 (make_rdd/parallelize)."""
        from vega_tpu.rdd.narrow import ParallelCollectionRDD

        n = num_slices or self.default_parallelism
        return ParallelCollectionRDD(self, data, n)

    make_rdd = parallelize

    def range(self, start: int, stop: Optional[int] = None, step: int = 1,
              num_slices: Optional[int] = None) -> RDD:
        """Reference: context.rs:422-442. Lazy: slices of a Python range are
        ranges, so no materialization happens until compute."""
        if stop is None:
            start, stop = 0, start
        return self.parallelize(range(start, stop, step), num_slices)

    def union(self, rdds: List[RDD]) -> RDD:
        """Reference: context.rs:537-539."""
        from vega_tpu.rdd.union import UnionRDD

        return UnionRDD(self, rdds)

    def empty_rdd(self) -> RDD:
        return self.parallelize([], 1)

    def read_source(self, config, decoder: Optional[Callable] = None) -> RDD:
        """Reference: context.rs:445-455 + src/io/local_file_reader.rs."""
        rdd = config.make_reader(self)
        if decoder is not None:
            rdd = rdd.map(decoder)
        return rdd

    def text_file(self, path: str, num_partitions: Optional[int] = None) -> RDD:
        from vega_tpu.io.readers import TextFileReaderConfig

        return self.read_source(
            TextFileReaderConfig(path, num_partitions or self.default_parallelism)
        )

    def whole_text_files(self, path: str) -> RDD:
        from vega_tpu.io.readers import WholeFileReaderConfig

        return self.read_source(WholeFileReaderConfig(path))

    def parquet_file(self, path: str, columns: Optional[List[str]] = None,
                     num_partitions: Optional[int] = None) -> RDD:
        from vega_tpu.io.readers import ParquetColumnReader

        return self.read_source(
            ParquetColumnReader(path, columns,
                                num_partitions or self.default_parallelism)
        )

    # ------------------------------------------------------------ DataFrame
    def read_parquet(self, path: str, columns: Optional[List[str]] = None,
                     num_partitions: Optional[int] = None):
        """Parquet -> DataFrame (vega_tpu/frame): the expression/verb API
        whose planner pushes column pruning and supported predicates into
        ParquetColumnReader and fuses narrow verb chains into one SPMD
        program per stage on the device tier. `columns=` pre-prunes at
        the entry point; the planner prunes further from the query. For
        the raw columnar-block RDD, use parquet_file()."""
        from vega_tpu.frame.api import DataFrame

        return DataFrame.from_parquet(self, path, columns, num_partitions)

    def create_frame(self, columns: Optional[dict] = None,
                     num_partitions: Optional[int] = None, **kwcolumns):
        """In-memory columns -> DataFrame (dict and/or keywords), the
        frame-layer sibling of dense_from_columns."""
        from vega_tpu.frame.api import DataFrame

        data = dict(columns or {})
        for name, c in kwcolumns.items():
            if name in data:
                raise VegaError(f"duplicate column {name!r}")
            data[name] = c
        return DataFrame.from_columns(self, data, num_partitions)

    # Device-tier sources (vega_tpu/tpu): numeric RDDs whose partitions are
    # arrays and whose ops lower to XLA.
    def dense_range(self, n: int, num_partitions: Optional[int] = None,
                    dtype=None, chunk_rows: Optional[int] = None):
        """Device iota source; auto-streams in chunks when block bytes
        times the exchange footprint (~6x) exceed
        Configuration.dense_hbm_budget (see tpu/stream.py)."""
        from vega_tpu.tpu.dense_rdd import dense_range

        return dense_range(self, n, num_partitions or self.default_parallelism,
                           dtype, chunk_rows=chunk_rows)

    def dense_from_numpy(self, *columns, num_partitions: Optional[int] = None):
        from vega_tpu.tpu.dense_rdd import dense_from_numpy

        return dense_from_numpy(
            self, columns, num_partitions or self.default_parallelism
        )

    def dense_from_columns(self, columns: Optional[dict] = None,
                           key: Optional[str] = None, **kwcolumns):
        """Named multi-column dense source (see tpu.dense_rdd.dense_from_columns)."""
        from vega_tpu.tpu.dense_rdd import dense_from_columns

        return dense_from_columns(self, columns, key=key, **kwcolumns)

    def dense_load_npz(self, path: str, chunk_rows: Optional[int] = None):
        """Reload a DenseRDD persisted with save_npz (re-sharded onto the
        current mesh); auto-streams in chunks when block bytes times the
        exchange footprint (~6x) exceed the HBM budget."""
        from vega_tpu.tpu.dense_rdd import dense_load_npz

        return dense_load_npz(self, path, chunk_rows=chunk_rows)

    def dense_hbm_in_use(self) -> int:
        """Tracked device-resident bytes of materialized dense
        intermediates. Intermediates above Configuration.dense_hbm_budget
        are LRU-evicted (lineage recomputes them on next access); sources
        are gated at creation by the streaming planner instead. See the
        lifetime note in tpu/dense_rdd.py."""
        from vega_tpu.tpu.dense_rdd import dense_hbm_in_use

        return dense_hbm_in_use(self)

    def profiler(self, log_dir: str):
        """JAX profiler trace over a block of work (the tracing subsystem
        the reference never built — SURVEY.md §5 'Tracing: none'). View with
        TensorBoard or xprof.

            with ctx.profiler("/tmp/trace"):
                rdd.reduce_by_key(op="add").collect()
            ctx.metrics_summary()["dense_spans"]["session"]

        While the session runs, the dense tier names its host work in the
        profile, on the clock of the device's own operations, and tallies
        it by the same names (vega_tpu/tpu/spans.py): `vega:launch <kind>`
        (dispatch of one shard program), `vega:fetch` (a blocking
        device->host round trip), `vega:put` (host->device), `vega:decode`
        (slicing and decoding a fetched block), `vega:pivot` (columns to
        Python rows) and `vega:fingerprint` (pickling a closure for a
        program-cache key). Off, they cost one flag check each.
        """
        return _profile_trace(log_dir)

    def broadcast(self, value: Any):
        """Driver-side broadcast variable (absent from the reference; Spark
        parity). Local mode shares by reference; distributed mode ships once
        per executor and caches in the BROADCAST key space."""
        from vega_tpu.broadcast import Broadcast

        return Broadcast(self, value)

    # ------------------------------------------------------------------ jobs
    def set_local_property(self, key: str, value) -> None:
        """Thread-local job-submission property (Spark parity). The one
        the scheduler reads is ``"pool"``: jobs submitted from this
        thread land in that fair-scheduling pool. ``None`` clears."""
        props = getattr(self._local_props, "props", None)
        if props is None:
            props = self._local_props.props = {}
        if value is None:
            props.pop(key, None)
        else:
            props[key] = value

    def get_local_property(self, key: str, default=None):
        props = getattr(self._local_props, "props", None)
        return default if props is None else props.get(key, default)

    def set_pool(self, name: str, weight: int = 1,
                 max_concurrent_tasks: Optional[int] = None,
                 max_queued: Optional[int] = None):
        """Declare/configure a scheduling pool (weight skews the fair
        share; max_concurrent_tasks is a hard per-pool in-flight quota;
        max_queued bounds ADMISSION — in-flight jobs of the pool beyond
        it are rejected or blocked per Configuration.admission_mode).
        Select it per thread with ``set_local_property("pool", name)`` or
        per job with ``submit_job(..., pool=name)``."""
        return self.job_server.set_pool(name, weight, max_concurrent_tasks,
                                        max_queued)

    def submit_job(self, rdd: RDD, func: Callable,
                   partitions: Optional[List[int]] = None,
                   pool: Optional[str] = None,
                   transform: Optional[Callable[[list], Any]] = None
                   ) -> JobFuture:
        """Asynchronous job submission: returns a JobFuture immediately;
        the job runs on its own event-loop thread, concurrently with any
        other in-flight jobs, arbitrated by the fair scheduler. `func`
        runs per partition; `transform` (optional) folds the list of
        partition results into the future's final value."""
        self._check_alive()
        if pool is None:
            pool = self.get_local_property("pool")
        return self.job_server.submit(rdd, func, partitions, pool=pool,
                                      transform=transform)

    def run_job(self, rdd: RDD, func: Callable,
                partitions: Optional[List[int]] = None) -> list:
        """Reference: context.rs:457-473. Blocking actions are submit +
        result() on the job server, so pools/quotas/cancellation apply to
        them exactly as to async submissions."""
        self._check_alive()
        if partitions is not None and not partitions:
            return []
        future = self.submit_job(rdd, func, partitions)
        try:
            return future.result()
        except BaseException:
            # The calling thread is unwinding — KeyboardInterrupt in a
            # REPL, most commonly. Pre-PR-7 the event loop ran on THIS
            # thread, so the job died with its caller; preserve that by
            # cancelling the would-be-orphaned job instead of leaving it
            # holding arbiter slots and pool quota to completion. A
            # no-op when the exception IS the job's own error re-raise
            # (the future is already settled; cancel returns False).
            future.cancel("blocking caller interrupted")
            raise

    def run_approximate_job(self, rdd: RDD, func: Callable, evaluator,
                            timeout_s: float) -> PartialResult:
        """Reference: context.rs:510-524 + approximate_action_listener.rs."""
        self._check_alive()
        future = self.job_server.submit(
            rdd, func, list(range(rdd.num_partitions)),
            pool=self.get_local_property("pool"),
            on_task_success=evaluator.merge,
        )
        start = time.time()
        try:
            future.result(timeout_s)
        except TimeoutError:
            # Deadline hit: return the current estimate, deliver the final
            # value when the background job drains (reference:
            # approximate_action_listener.rs:58-111).
            result = PartialResult(evaluator.current_result(), is_final=False)

            def finisher(fut: JobFuture):
                exc = fut.exception()
                if exc is not None:
                    result.set_failure(exc)
                else:
                    result.set_final_value(evaluator.current_result())

            future.add_done_callback(finisher)
            return result
        except BaseException as exc:  # noqa: BLE001 — folded into the result
            result = PartialResult(None, is_final=False)
            result.set_failure(exc)
            return result
        log.debug("approximate job finished in %.3fs", time.time() - start)
        return PartialResult(evaluator.current_result(), is_final=True)

    # ------------------------------------------------------------- streaming
    def streaming(self, batch_interval_s: Optional[float] = None,
                  checkpoint_dir: Optional[str] = None):
        """The Context's micro-batch streaming plane (one per Context,
        created on first use; vega_tpu/streaming/). Interval/checkpoint
        overrides apply only to the creating call."""
        self._check_alive()
        if self._streaming is None:
            from vega_tpu.streaming.context import StreamingContext

            self._streaming = StreamingContext(
                self, batch_interval_s=batch_interval_s,
                checkpoint_dir=checkpoint_dir)
        return self._streaming

    def stream_from_generator(self, fn, **kwargs):
        """DStream over an offset-addressed generator: fn(offset) ->
        record | None. Deterministic + picklable fn = fully replayable
        (the exactly-once reference source)."""
        return self.streaming(**kwargs).generator_stream(fn)

    def stream_from_file_tail(self, path: str, **kwargs):
        """DStream tailing an append-only line file (byte offsets)."""
        return self.streaming(**kwargs).file_tail_stream(path)

    def stream_from_socket(self, host: str, port: int, **kwargs):
        """DStream over line-delimited TCP; every read is bounded by
        stream_socket_timeout_s."""
        return self.streaming(**kwargs).socket_stream(host, port)

    # ----------------------------------------------------------------- admin
    @property
    def default_parallelism(self) -> int:
        return max(2, self._backend.parallelism)

    def metrics_summary(self) -> dict:
        """The event bus's counters, and under "dense_spans" what the
        dense tier's host side did: "session", the spans tallied under the
        newest profiler session (see `profiler`), "programs", the shard
        programs minted by kind with the host seconds of their first
        calls, and "program_stages", each kind's compiled instructions by
        the stage that wrote them (spans.program_stages: what a device
        profile's operations are joined with)."""
        if not self.bus.flush():
            log.warning("event bus flush timed out; metrics may lag")
        from vega_tpu.tpu import spans  # imports no jax

        summary = self.metrics.summary()
        summary["dense_spans"] = {"session": spans.session(),
                                  "programs": spans.programs(),
                                  "program_stages": spans.program_stages()}
        return summary

    def fleet_status(self) -> dict:
        """One view of the serving plane: fleet membership/occupancy
        (per-executor in-flight), the arbiter's running/queued depths
        (global and per pool), per-pool admission in-flight vs bounds,
        and the elastic controller's state. Works in local mode too —
        the fleet section is just empty there."""
        backend = self._backend
        return {
            "fleet": backend.fleet_snapshot()
            if hasattr(backend, "fleet_snapshot") else [],
            "scheduler": self.job_server.arbiter.stats(),
            "admission": self.job_server.admission_status(),
            "elastic": self.elastic.status() if self.elastic is not None
            else {"enabled": False},
            "pool_latency": self.metrics.pool_latency(),
            "streaming": self._streaming.status()
            if self._streaming is not None else {"active": False},
        }

    def storage_status(self) -> dict:
        """Tier occupancy + spill/promote counters of this process's block
        stores (cache + shuffle). bench.py embeds this in its detail so
        HBM/RSS numbers can attribute spill cost."""
        env = Env.get()
        return {
            "cache": env.cache.status(),
            "shuffle": env.shuffle_store.status(),
        }

    def stop(self) -> None:
        """Reference: context.rs:131-144 (drop/cleanup)."""
        assert_role()  # driver teardown — never from a confined thread
        global _active_context
        if self._stopped:
            return
        self._stopped = True
        # Streaming stops FIRST: its batch loop submits jobs and its
        # receivers write the cache — both must quiesce before the job
        # plane and stores they ride on wind down.
        if self._streaming is not None:
            try:
                self._streaming.stop()
            except Exception:  # noqa: BLE001 — teardown must finish
                log.warning("streaming stop failed", exc_info=True)
        # The autoscaler goes first: a control loop mid-decision must not
        # spawn or decommission against a backend that is tearing down
        # (teardown=True also aborts any mid-ladder decommission).
        if self.elastic is not None:
            self.elastic.stop(teardown=True)
        # Wind the job plane down first: cancel in-flight jobs and settle
        # their futures (nobody stays parked on result()) BEFORE the
        # backend and stores those jobs might still be touching go away.
        self.job_server.stop()
        self.scheduler.stop()
        env = Env.get()
        env.shuffle_store.close()  # clears both tiers + removes spill dir
        env.cache.close()
        from vega_tpu.env import detach_session_logger

        detach_session_logger(self._log_handler, self.conf.log_cleanup)
        self._log_handler = None
        log.setLevel(self._prev_logger_level)
        with _active_context_lock:
            if _active_context is self:
                _active_context = None

    def _check_alive(self):
        if self._stopped:
            raise RuntimeError("Context is stopped")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # -------------------------------------------------------------- pickling
    # RDD lineages hold a Context reference; tasks serialize lineages. The
    # Context itself must not travel (it owns threads and sockets) — ship a
    # handle that rebinds to the process-active context, mirroring the
    # reference's weak Context ref inside RddVals (rdd/rdd.rs:54-76).
    def __reduce__(self):
        return (_deserialize_context, ())


class _StubContext:
    """Context stand-in inside executor processes: id counters only."""

    def __init__(self):
        self._next_rdd_id = itertools.count(1 << 40)
        self._next_shuffle_id = itertools.count(1 << 40)

    def new_rdd_id(self):
        return next(self._next_rdd_id)

    def new_shuffle_id(self):
        return next(self._next_shuffle_id)

    def run_job(self, *_a, **_k):
        raise RuntimeError("run_job is driver-only; executors compute partitions")

    def __reduce__(self):
        return (_deserialize_context, ())


_stub_context: Optional[_StubContext] = None


def _deserialize_context():
    global _stub_context
    with _active_context_lock:
        if _active_context is not None:
            return _active_context
    if _stub_context is None:
        _stub_context = _StubContext()
    return _stub_context
