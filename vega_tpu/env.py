"""Process-global environment: config + lazily-started services.

Reference: src/env.rs. The reference holds a lazy singleton bundling the tokio
runtime, map-output tracker, shuffle manager and cache (env.rs:38-96) plus a
Configuration read from VEGA_* env vars / a worker-local config.toml
(env.rs:131-293). vega_tpu keeps the same shape: `Env.get()` is the process
singleton; configuration comes from VEGA_TPU_* env vars with the same field
set (deployment_mode, local_ip, local_dir, log_level, shuffle port, ...).
"""

from __future__ import annotations

import dataclasses
import enum
import logging
import os
import tempfile
import uuid
from typing import Optional

from vega_tpu.lint.sync_witness import assert_role, named_lock

log = logging.getLogger("vega_tpu")


class DeploymentMode(enum.Enum):
    """Reference: src/env.rs:146-149."""

    LOCAL = "local"
    DISTRIBUTED = "distributed"


@dataclasses.dataclass
class Configuration:
    """Reference: src/env.rs:162-272 (field-for-field, TPU additions at end)."""

    deployment_mode: DeploymentMode = DeploymentMode.LOCAL
    local_ip: str = "127.0.0.1"
    local_dir: str = dataclasses.field(
        default_factory=lambda: os.path.join(tempfile.gettempdir(), "vega-tpu")
    )
    log_level: str = "WARNING"
    log_cleanup: bool = True
    shuffle_service_port: Optional[int] = None
    slave_deployment: bool = False
    slave_port: Optional[int] = None
    # --- vega_tpu additions ---
    # Worker threads for the local scheduler's task pool.
    num_workers: int = dataclasses.field(
        default_factory=lambda: os.cpu_count() or 4
    )
    # Initial executor count for distributed mode (None -> hosts file if
    # configured, else 2 — the backend's historical default). The elastic
    # plane starts here and moves the fleet between
    # elastic_min/max_executors.
    num_executors: Optional[int] = None
    # Round-trip tasks through serialization even in local mode, like the
    # reference does (local_scheduler.rs:345-351): catches unserializable
    # closures early. Costs wall time; disable for pure-local perf runs.
    serialize_tasks_locally: bool = False
    # Cache capacity in bytes for BoundedMemoryCache (reference hardcodes
    # 2000MB at cache.rs:29; we make it configurable and actually evict).
    cache_capacity_bytes: int = 2_000 * 1024 * 1024
    # Tiered block store (vega_tpu/store): spill directory root. None ->
    # <local_dir>/session-<id>/spill, i.e. rooted at VEGA_TPU_LOCAL_DIR —
    # per-process (so per-executor) and removed on shutdown.
    spill_dir: Optional[str] = None
    # Shuffle store memory budget: total in-RAM bucket bytes before the
    # oldest buckets spill to disk (the reference pins every bucket in
    # process memory forever — env.rs:19; large shuffles simply OOM'd).
    shuffle_memory_budget: int = 1 << 30
    # Individual buckets larger than this go straight to disk.
    shuffle_spill_threshold: int = 64 * 1024 * 1024
    # Scheduler timeouts (reference: distributed_scheduler.rs:87-88).
    resubmit_timeout_s: float = 2.0
    poll_timeout_s: float = 0.05
    # Max task retries before failing the job (reference plumbs max_failures
    # but never enforces it, local_scheduler.rs:29,57 — we enforce it).
    max_failures: int = 4
    # Multi-job task arbitration (scheduler/jobserver.py): "fifo"
    # dispatches ready tasks of all concurrent jobs in global submission
    # order (the reference's effective behavior — one long job's backlog
    # gates every later job); "fair" shares backend slots across pools by
    # weight, and across jobs within a pool by fewest-running-first, so
    # short interactive jobs are not starved by a long batch job.
    # Switchable at runtime via ctx.job_server.set_scheduler_mode(...).
    scheduler_mode: str = "fifo"
    # Locality-aware task placement (distributed mode). > 0 turns the
    # plane ON: the DAG scheduler computes reduce-side preferred
    # locations (push-plan pre-merge owner / pull-plan biggest-bytes
    # server) and _pick_executor scores candidates
    # PROCESS_LOCAL > HOST_LOCAL > ANY, breaking ties by fewest in-flight
    # tasks; a task whose only preferred executors are TEMPORARILY down
    # (a respawn in flight or budgeted) waits up to this many seconds
    # before settling for a worse tier — permanently dead, blacklisted or
    # speculation-excluded preferred executors demote immediately, so the
    # wait can never starve a task. 0 turns the whole plane off and
    # reproduces the legacy round-robin + first-match placement.
    locality_wait_s: float = 0.3
    # --- executor fault tolerance (distributed mode) ---
    # Worker -> driver heartbeat period. Must be well under
    # executor_liveness_timeout_s or healthy workers get reaped.
    heartbeat_interval_s: float = 2.0
    # A registered executor whose last heartbeat is older than this is
    # declared lost: its map outputs are unregistered (generation bump),
    # its in-flight dispatches are failed over, and ExecutorLost is
    # emitted. Detects wedged-but-alive workers, not just dead sockets.
    executor_liveness_timeout_s: float = 30.0
    # Reaper sweep period (driver-side liveness thread).
    executor_reap_interval_s: float = 5.0
    # Dead local/ssh workers are respawned up to this many times per slot
    # with exponential backoff; 0 disables respawn.
    executor_max_restarts: int = 3
    # Base respawn delay; attempt k waits backoff * 2**k.
    executor_restart_backoff_s: float = 1.0
    # Executors accumulating this many dispatch failures are skipped by
    # _pick_executor while any non-blacklisted executor is alive (repeat
    # offenders stop eating task attempts).
    executor_blacklist_threshold: int = 5
    # Transient shuffle-fetch retry: a dropped connection is retried in
    # place this many times (with linear backoff fetch_retry_interval_s)
    # before escalating to FetchFailedError and a stage resubmission.
    # A server answering "missing" escalates immediately (not transient).
    fetch_retries: int = 3
    fetch_retry_interval_s: float = 0.2
    # Pipelined shuffle fetch (shuffle/fetcher.py): batched `get_many`
    # requests — ONE round trip per (reducer, server) instead of one per
    # bucket — answered as a stream the reducer merges while later
    # buckets are still on the wire. 0/false falls back to the per-bucket
    # `get` protocol (same pipeline, M round trips).
    fetch_batch_enabled: bool = True
    # Bound on the fetch pipeline's bucket queue: at most this many
    # fetched-but-unmerged buckets are resident per reduce task (producer
    # threads block past it — backpressure IS the reducer's peak-memory
    # bound; the old path materialized the entire List[bytes]).
    fetch_queue_buckets: int = 32
    # --- task dispatch plane ---
    # Deduplicated dispatch: tasks ship as a tiny header plus a
    # stage-level binary (the shared (rdd, func | shuffle_dep) closure,
    # cloudpickled once per stage, content-hashed, sent to each executor
    # on first use only — a worker lacking the hash answers `need_binary`
    # and gets it re-shipped inline, so correctness never depends on
    # driver bookkeeping). Results return with protocol-5 out-of-band
    # buffers (zero-copy numpy). 0/false keeps the legacy
    # one-envelope-per-task protocol live (A/B and fallback; the
    # reference's only shape, serialized_data.capnp).
    task_binary_dedup: bool = True
    # Bound on the executor-side LRU of *deserialized* stage binaries
    # (one lineage unpickle per stage per executor, not per task). An
    # evicted hash recovers via the need_binary re-ship.
    task_binary_cache_entries: int = 32
    # Dense-tier shuffle collective. "auto" (default) routes every
    # exchange launch through the collective-aware planner
    # (tpu/exchange_plan.py): one-shot "all_to_all" when its estimated
    # per-shard transient peak fits dense_hbm_budget, the blocked
    # "staged" program (K sub-rounds of peer groups over shifted
    # ppermutes, K chosen so the estimate fits) when it doesn't, "ring"
    # (single bounded buffer, n-1 rounds — the minimum possible peak)
    # when no larger group fits. Explicit "all_to_all" / "ring" /
    # "staged" force that program per run. See tpu/ring.py and
    # tpu/exchange_plan.py.
    dense_exchange: str = "auto"
    # Cluster membership file for distributed mode (reference: ~/hosts.conf,
    # src/hosts.rs); None -> VEGA_TPU_HOSTS_FILE -> ~/hosts.conf -> local.
    hosts_file: Optional[str] = None
    # Speculative execution (straggler mitigation; the reference has none):
    # once a quorum of a stage's tasks has finished (speculation_quorum
    # fraction of its submitted tasks), a pending task that has run longer
    # than max(speculation_min_s, speculation_multiplier * median task
    # duration) gets ONE duplicate attempt launched — on a different,
    # non-blacklisted executor in distributed mode. First completion wins
    # (dedup by (stage_id, partition)); the loser is cancelled best-effort
    # via the `cancel_task` protocol message. NOTE: like task retries,
    # this gives at-least-once semantics for user side effects (for_each
    # etc.) — framework-owned writes (save_as_text_file, shuffle buckets)
    # are duplicate-safe.
    speculation_enabled: bool = False
    speculation_multiplier: float = 3.0
    speculation_min_s: float = 1.0
    # Fraction of a stage's tasks that must have COMPLETED before any of
    # its stragglers are eligible for speculation (the median is garbage
    # on two data points).
    speculation_quorum: float = 0.75
    # Replicated shuffle writes (the data-side redundancy of
    # arXiv:1802.03049): each map task's buckets are written to this many
    # executors' stores (1 = primary only). Reducers treat the extra
    # locations as failover targets — a dead or slow server's undelivered
    # buckets are re-requested from a replica mid-stream, with no stage
    # resubmission and no map recompute.
    shuffle_replication: int = 1
    # Shuffle plan (PR 8, Exoshuffle map-side push as a policy over the
    # existing store/fetch primitives — never a fork of the plane):
    #   "pull" (default) — the PR 4 pipeline: map outputs park locally,
    #     reducers batch-fetch them after the whole map stage registered.
    #   "push" — map tasks additionally push each finished bucket to its
    #     reducer's OWNING server (rotation over the live peer list);
    #     that server pre-merges mergeable buckets into the existing
    #     MergeState machinery as they arrive, and reducers start from
    #     ONE mostly-merged blob, pulling only the stragglers that never
    #     arrived — the shuffle barrier becomes a map/reduce pipeline.
    # Push is strictly additive: the local bucket row and its registered
    # locations are byte-identical to the pull plan, so any push failure
    # (dead peer, fleet churn, overflow) silently degrades to pull.
    shuffle_plan: str = "pull"
    # When > 0 and every bucket requested from a server has at least one
    # replica location, the batched get_many round runs under this socket
    # deadline with no in-place retries: a server unresponsive past it
    # fails over to the replicas instead of gating the reduce task on the
    # slowest source. 0 keeps the normal fetch_retries behavior.
    fetch_slow_server_s: float = 0.0
    # Coded shuffle (third redundancy-ladder leg, arXiv:1802.03049 via
    # shuffle/coding.py): "none" (default) | "xor" | "rs" | "rs(k,m)".
    # Map tasks ship each bucket row ONCE (compressed) to a parity
    # server, which folds rotation groups of up to `coding_group_k`
    # same-shuffle rows — at most one per origin server, so any single
    # server loss is decodable — into parity buckets: one XOR unit, or
    # `coding_parity_m` Reed–Solomon units (any ≤m losses decode). On a
    # dead server the fetch path RECONSTRUCTS missing buckets from the
    # surviving members plus parity instead of resubmitting the map
    # stage: replica-grade recovery at ~(1/group)× storage instead of
    # (k-1)×. Composes with shuffle_replication (replica failover is
    # tried first) and shuffle_plan=push; degradation ladder stays total
    # (coded -> replica -> FetchFailed -> resubmit).
    shuffle_coding: str = "none"
    coding_group_k: int = 4
    coding_parity_m: int = 1
    # Dense-tier HBM budget in bytes (per chip). Sources stream through
    # the mesh in chunks (tpu/stream.py) when estimated block bytes times
    # the exchange footprint factor (~6: operand + sorted copy + send
    # slots + received block) exceed this — i.e. resident execution is
    # kept only while block_bytes * 6 <= budget. Default 4 GiB:
    # conservative for a 16 GiB v5e chip once XLA workspace and a second
    # live block are accounted for.
    dense_hbm_budget: int = 4 << 30
    # --- elastic serving plane (scheduler/elastic.py; distributed mode) ---
    # Master switch for the autoscaler control loop: the driver samples
    # load signals (arbiter queue depth, per-pool backlog, per-executor
    # in-flight watermarks) every elastic_decision_interval_s and
    # spawns/decommissions executors between the min/max bounds. Off by
    # default: the fleet stays exactly as spawned (the reference sizes
    # it once at context.rs launch time and never revisits).
    elastic_enabled: bool = False
    # Fleet bounds the autoscaler may move between. The initial fleet is
    # num_executors/hosts as before; scale-down never drains below min,
    # scale-up never spawns past max.
    elastic_min_executors: int = 1
    elastic_max_executors: int = 8
    # Scale UP when (running + queued tasks) per live executor SLOT
    # (num_workers slots per executor) holds above this watermark for a
    # full decision interval. 1.0 = grow as soon as the fleet is more
    # than fully subscribed for an interval.
    elastic_scale_up_threshold: float = 2.0
    # Scale DOWN (graceful decommission of one executor per decision)
    # when fleet occupancy — running tasks / total slots — holds BELOW
    # this fraction for a full decision interval with nothing queued.
    elastic_scale_down_threshold: float = 0.25
    # Sampling period of the control loop; a watermark must hold for one
    # full interval (two consecutive samples) before the loop acts, so a
    # single bursty sample never flaps the fleet.
    elastic_decision_interval_s: float = 1.0
    # Graceful decommission: how long the victim may take to drain its
    # in-flight tasks before the drain escalates to the PR 2
    # executor-lost path (socket teardown, output unregistration, task
    # failover) instead of waiting forever on a wedged victim.
    decommission_timeout_s: float = 10.0
    # Admission control (scheduler/jobserver.py): maximum jobs a pool may
    # have in flight (submitted, not yet settled) before submit_job stops
    # admitting more — the bound that replaces unbounded queueing at the
    # multi-tenant front door. 0 = unbounded (legacy behavior).
    # Per-pool overrides via ctx.set_pool(..., max_queued=N).
    pool_max_queued: int = 0
    # What a full pool does to the submitter: "reject" raises the typed
    # JobRejectedError immediately; "block" parks the submitting thread
    # until a job of that pool settles (backpressure).
    admission_mode: str = "reject"
    # Dispatch-failure blacklists age out: an executor whose last
    # transport failure is older than this many seconds has its
    # consecutive-failure count forgiven, so a recovered-but-once-flaky
    # executor rejoins _pick_executor rotation instead of staying
    # advisory-deprioritized forever. 0 disables decay (legacy).
    blacklist_decay_s: float = 60.0
    # --- device-tier string columns (tpu/dict_encoding.py) ---
    # Master switch for dictionary-encoded string columns on the device
    # tier: string columns become int32 code columns plus a sorted
    # dictionary sidecar on the Block (codes ARE rank codes, so order
    # ops need no extra pass), unified across blocks before keyed binary
    # ops and decoded only at the collect boundary. False keeps the
    # pre-PR-20 behavior — string data raises at the block boundary and
    # the caller degrades to the host tier (the forced-host leg of
    # benchmarks/strings_ab.py sets this).
    dense_dict_enabled: bool = True
    # Starting capacity (entries) of the padded dictionary tables staged
    # into the cross-block unification remap program. A REAL capacity,
    # same contract as exchange capacities: a code at or past the staged
    # table sets the device overflow flag and the driver retries with
    # doubled capacity (tests shrink this to exercise the retry path).
    dense_dict_capacity: int = 65536
    # --- micro-batch streaming (vega_tpu/streaming/) ---
    # Discretization interval: how often the streaming context snapshots
    # receiver blocks into one micro-batch and submits its output jobs.
    stream_batch_interval_s: float = 0.5
    # Receivers cut a block (land it in the tiered store and queue it for
    # the next batch) at this many records; a batch tick also flushes the
    # partial block so low-rate streams still make progress.
    stream_block_max_records: int = 10_000
    # Backpressure bound: maximum receiver blocks landed but not yet
    # consumed by a completed batch. At the bound the receiver applies
    # stream_backpressure_mode instead of queueing without limit.
    stream_queue_max_blocks: int = 64
    # What a full block queue does to ingest: "block" parks the receiver
    # until a batch drains blocks (lossless; the socket source's peer
    # sees TCP backpressure); "shed" drops the newest block while still
    # advancing source offsets (lossy by design — counted and surfaced,
    # mirroring jobserver admission_mode reject/block).
    stream_backpressure_mode: str = "block"
    # Fair-scheduler pool streaming batches are submitted into, and its
    # weight vs the default batch pool (set via ctx.set_pool at streaming
    # start) — the isolation that keeps a heavy batch tenant from
    # starving the stream.
    stream_pool: str = "streaming"
    stream_pool_weight: int = 4
    # StorageLevel for receiver blocks in the tiered store. The default
    # keeps blocks replayable across memory pressure (eviction demotes to
    # disk instead of dropping — a failed batch must recompute from
    # stored blocks, never from the wire).
    stream_storage_level: str = "memory_and_disk"
    # Socket source read timeout: every recv on the streaming socket
    # carries this bound (VG012/VG015 — no unbounded waits), so a silent
    # peer never wedges the receiver thread past it.
    stream_socket_timeout_s: float = 5.0
    # Where stateful streams write their (batch_id, offsets, state)
    # commit records + checkpointed state parts. Empty = under the
    # session work dir (wiped with the session; set it to survive a
    # driver restart).
    stream_checkpoint_dir: str = ""

    @staticmethod
    def from_environ(environ=None) -> "Configuration":
        env = os.environ if environ is None else environ
        cfg = Configuration()
        pref = "VEGA_TPU_"
        if env.get(pref + "DEPLOYMENT_MODE"):
            cfg.deployment_mode = DeploymentMode(env[pref + "DEPLOYMENT_MODE"])
        for name in ("LOCAL_IP", "LOCAL_DIR", "LOG_LEVEL", "DENSE_EXCHANGE",
                     "HOSTS_FILE", "SPILL_DIR",
                     "SCHEDULER_MODE", "SHUFFLE_PLAN", "SHUFFLE_CODING",
                     "ADMISSION_MODE",
                     "STREAM_BACKPRESSURE_MODE", "STREAM_POOL",
                     "STREAM_STORAGE_LEVEL", "STREAM_CHECKPOINT_DIR"):
            if env.get(pref + name):
                setattr(cfg, name.lower(), env[pref + name])
        for name in ("SHUFFLE_SERVICE_PORT", "SLAVE_PORT", "NUM_WORKERS",
                     "NUM_EXECUTORS",
                     "CACHE_CAPACITY_BYTES", "MAX_FAILURES",
                     "DENSE_HBM_BUDGET", "SHUFFLE_MEMORY_BUDGET",
                     "SHUFFLE_SPILL_THRESHOLD", "DENSE_DICT_CAPACITY",
                     "EXECUTOR_MAX_RESTARTS",
                     "EXECUTOR_BLACKLIST_THRESHOLD", "FETCH_RETRIES",
                     "FETCH_QUEUE_BUCKETS", "TASK_BINARY_CACHE_ENTRIES",
                     "SHUFFLE_REPLICATION", "CODING_GROUP_K",
                     "CODING_PARITY_M", "ELASTIC_MIN_EXECUTORS",
                     "ELASTIC_MAX_EXECUTORS", "POOL_MAX_QUEUED",
                     "STREAM_BLOCK_MAX_RECORDS", "STREAM_QUEUE_MAX_BLOCKS",
                     "STREAM_POOL_WEIGHT"):
            if env.get(pref + name):
                setattr(cfg, name.lower(), int(env[pref + name]))
        for name in ("LOG_CLEANUP", "SLAVE_DEPLOYMENT", "SERIALIZE_TASKS_LOCALLY",
                     "SPECULATION_ENABLED", "FETCH_BATCH_ENABLED",
                     "TASK_BINARY_DEDUP", "ELASTIC_ENABLED",
                     "DENSE_DICT_ENABLED"):
            if env.get(pref + name):
                setattr(cfg, name.lower(), env[pref + name].lower() in ("1", "true"))
        for name in ("RESUBMIT_TIMEOUT_S", "POLL_TIMEOUT_S",
                     "SPECULATION_MULTIPLIER", "SPECULATION_MIN_S",
                     "SPECULATION_QUORUM",
                     "HEARTBEAT_INTERVAL_S", "EXECUTOR_LIVENESS_TIMEOUT_S",
                     "EXECUTOR_REAP_INTERVAL_S", "EXECUTOR_RESTART_BACKOFF_S",
                     "FETCH_RETRY_INTERVAL_S", "FETCH_SLOW_SERVER_S",
                     "LOCALITY_WAIT_S", "ELASTIC_SCALE_UP_THRESHOLD",
                     "ELASTIC_SCALE_DOWN_THRESHOLD",
                     "ELASTIC_DECISION_INTERVAL_S", "DECOMMISSION_TIMEOUT_S",
                     "BLACKLIST_DECAY_S", "STREAM_BATCH_INTERVAL_S",
                     "STREAM_SOCKET_TIMEOUT_S"):
            if env.get(pref + name):
                setattr(cfg, name.lower(), float(env[pref + name]))
        return cfg


def normalize_log_level(level) -> int:
    """'info'/'INFO'/20 -> 20; invalid values fall back to WARNING instead
    of crashing startup."""
    if isinstance(level, int):
        return level
    resolved = logging.getLevelName(str(level).upper())
    return resolved if isinstance(resolved, int) else logging.WARNING


def attach_session_logger(env: "Env", role: str):
    """Per-session log file (reference: simplelog combined file+terminal
    logger — ns-driver.log / ns-executor.log, context.rs:542-564). Returns
    the handler (caller owns detach/cleanup) or None when the directory is
    unwritable. Never *raises* the logger threshold: an application that
    configured more verbose logging keeps it."""
    try:
        path = os.path.join(env.work_dir(), f"{role}.log")
        handler = logging.FileHandler(path)
    except OSError:
        return None
    handler.setFormatter(logging.Formatter(
        "%(asctime)s %(levelname)s %(name)s %(message)s"
    ))
    level = normalize_log_level(env.conf.log_level)
    handler.setLevel(level)
    log.addHandler(handler)
    if level < log.getEffectiveLevel():
        log.setLevel(level)
    return handler


def detach_session_logger(handler, cleanup: bool) -> None:
    if handler is None:
        return
    log.removeHandler(handler)
    path = handler.baseFilename
    handler.close()
    if cleanup:
        try:
            os.unlink(path)
        except OSError:
            pass


class Env:
    """Lazy process singleton (reference: src/env.rs:38-96).

    Bundles the shuffle store, map-output tracker client/server, cache, and
    cache tracker. Services start on first access, exactly like the
    reference's once_cell pattern.
    """

    _instance: Optional["Env"] = None
    _lock = named_lock("env.Env._lock")

    def __init__(self, conf: Optional[Configuration] = None, is_driver: bool = True):
        from vega_tpu.cache import BoundedMemoryCache
        from vega_tpu.shuffle.store import ShuffleStore
        from vega_tpu.store import DiskStore, TieredCache

        self.conf = conf or Configuration.from_environ()
        self.is_driver = is_driver
        self.session_id = uuid.uuid4().hex[:12]
        # Spill root (paths only — DiskStore mkdirs lazily on first write,
        # so constructing an Env touches no filesystem). Always suffixed
        # with the per-process session id, INCLUDING under an explicit
        # VEGA_TPU_SPILL_DIR: driver and executors share that env var, and
        # a bare shared root would let one process's shutdown rmtree
        # delete every other live executor's disk-resident blocks.
        base = self.conf.spill_dir or os.path.join(self.conf.local_dir,
                                                   "spill")
        spill_root = os.path.join(base, f"session-{self.session_id}")
        self.shuffle_store = ShuffleStore(
            spill_dir=os.path.join(spill_root, "shuffle"),
            spill_threshold=self.conf.shuffle_spill_threshold,
            memory_budget=self.conf.shuffle_memory_budget,
        )
        self.cache = TieredCache(
            BoundedMemoryCache(self.conf.cache_capacity_bytes),
            DiskStore(os.path.join(spill_root, "cache")),
        )
        self.map_output_tracker = None  # set by Context/Executor at startup
        self.cache_tracker = None
        self.shuffle_server = None  # distributed mode only
        self.executor_id: Optional[str] = None
        # Set by the Context to LiveListenerBus.post (driver-side): the
        # shuffle fetcher posts ShuffleFetchCompleted per reduce stream.
        # Executors keep process-local counters only (fetcher.stats).
        self.fetch_event_sink = None

    @classmethod
    def get(cls) -> "Env":
        if cls._instance is None:
            with cls._lock:
                if cls._instance is None:
                    cls._instance = Env()
        return cls._instance

    @classmethod
    def reset(cls, conf: Optional[Configuration] = None, is_driver: bool = True) -> "Env":
        """Replace the singleton (tests / worker bootstrap)."""
        # Worker bootstrap calls this on the worker process's MAIN thread
        # (un-noted -> passes); a task-handler or receiver thread doing it
        # would corrupt every concurrent task's view of the Env.
        assert_role()
        with cls._lock:
            cls._instance = Env(conf, is_driver)
        return cls._instance

    def work_dir(self) -> str:
        d = os.path.join(self.conf.local_dir, f"session-{self.session_id}")
        os.makedirs(d, exist_ok=True)
        return d
