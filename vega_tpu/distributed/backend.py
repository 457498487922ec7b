"""Distributed task backend: driver side.

Reference: src/scheduler/distributed_scheduler.rs — submit_task opens a TCP
connection to an executor, writes the framed task, and awaits the result on
the same socket (:382-445), choosing executors round-robin with a pinned-host
seek (:447-469), retrying connects 5x with backoff (:434-441).

vega_tpu keeps that dispatch shape, but deduplicates the payload: the
reference writes the WHOLE serialized task — lineage and closure — per
task (its one-field capnp envelope, serialized_data.capnp), so an
N-partition stage pays N lineage pickles on the GIL-bound driver. Here the
stage binary is pickled once per stage (scheduler/task.py StageBinary) and
shipped to each executor on first use only; per-task dispatch carries a
tiny header. Per-executor known-hash sets are advisory — a worker that
lacks the hash answers `need_binary` and the binary re-ships inline on the
same connection (protocol.py task_v2 grammar), so respawns and cache
evictions self-heal. Results return as protocol-5 out-of-band buffer
frames (zero-copy numpy). `task_binary_dedup=0` keeps the legacy
one-envelope-per-task protocol live for A/B and fallback
(benchmarks/dispatch_ab.py measures both legs).

It also adds the executor fault tolerance the reference lacks (SURVEY.md
§5 failure detection — its executor loss is 'retry connect 5x then
panic'):

  * a dead socket marks the executor lost and re-dispatches its task;
  * a **liveness reaper** thread sweeps worker heartbeats
    (DriverService.workers last_seen): a wedged-but-alive executor is
    declared lost within executor_liveness_timeout_s — its map outputs are
    unregistered (tracker generation bump, so reducers refetch), its
    in-flight dispatch sockets are torn down (the blocked dispatch threads
    fail over to survivors), and ExecutorLost reaches the scheduler bus;
  * **worker respawn**: dead local/ssh workers are relaunched with capped
    restarts and exponential backoff (ExecutorRestarted on the bus), and
    per-executor dispatch-failure counts blacklist repeat offenders from
    _pick_executor.

Deployment: local workers are spawned as subprocesses (the docker-compose
testing-cluster analogue, reference docker/testing_cluster.sh); remote hosts
listed in Configuration/hosts file are launched over ssh like the
reference's scp+ssh bootstrap (context.rs:209-303) but shipping only the
`python -m vega_tpu.distributed.worker` command, not a binary.
"""

from __future__ import annotations

import itertools
import logging
import os
import socket
import subprocess
import sys
import threading
import time
from typing import Callable, Dict, List, Optional, Set

from vega_tpu import serialization
from vega_tpu.distributed import protocol
from vega_tpu.distributed.driver_service import DriverService
from vega_tpu.env import Env
from vega_tpu.errors import NetworkError, TaskError
from vega_tpu.scheduler import events as ev
from vega_tpu.scheduler.dag import TaskBackend
from vega_tpu.scheduler.task import Task, TaskEndEvent
from vega_tpu.lint.sync_witness import (
    assert_role,
    named_lock,
    note_thread_role,
)

log = logging.getLogger("vega_tpu")


def _weighted_scale_host(weights: Dict[str, int],
                         live_by_host: Dict[str, int]) -> str:
    """Capacity-weighted scale-up placement: choose the host whose
    occupancy-per-capacity ((live + 1) / weight, counting the slot being
    placed) is lowest, tiebreaking toward the bigger box, then by name
    for determinism. Starting empty, a weight-3 host receives the first
    three slots before a weight-1 host receives its first; at equal
    weights this degrades to the old even rotation."""
    if not weights:
        return "127.0.0.1"
    return min(
        weights,
        key=lambda h: ((live_by_host.get(h, 0) + 1) / weights[h],
                       -weights[h], h),
    )


class _Executor:
    def __init__(self, executor_id: str, task_uri: str, host: str,
                 process: Optional[subprocess.Popen] = None,
                 restarts: int = 0):
        self.executor_id = executor_id
        self.task_uri = task_uri
        self.host = host
        self.process = process
        self.restarts = restarts  # respawn incarnation of this slot
        # This slot's shuffle-server URI, lazily resolved from the
        # worker's registration (DriverService.workers) the first time
        # the locality scorer needs it. A respawn binds a fresh port, but
        # it also replaces this _Executor object — never stale.
        self.shuffle_uri: Optional[str] = None
        self.alive = True
        self.reaped = False      # declared lost; never resurrects
        self.respawning = False  # a replacement launch is in flight
        # Graceful decommission (scheduler/elastic.py): a draining slot
        # takes no new placements, leaves the peer registry, and never
        # respawns — it is on its way OUT, not failed.
        self.draining = False
        self.failures = 0        # dispatch/transport failures (blacklist)
        self.last_failure_at = 0.0  # blacklist decay clock
        self.lost_at = 0.0       # when the reaper declared it lost
        self.sockets: Set[socket.socket] = set()  # in-flight dispatches


class DistributedBackend(TaskBackend):
    def __init__(self, conf, num_executors: Optional[int] = None,
                 hosts: Optional[List[str]] = None):
        env = Env.get()
        self.service = DriverService(
            env.map_output_tracker, env.cache_tracker,
            liveness_timeout_s=conf.executor_liveness_timeout_s,
        )
        env.shuffle_server = None  # driver serves no shuffle data
        self.conf = conf
        self._executors: Dict[str, _Executor] = {}
        # Per-executor-ID sets of stage-binary hashes believed delivered.
        # Keyed by executor_id (NOT the _Executor object) so a respawned
        # slot inherits its predecessor's — deliberately stale — set: the
        # wire-level need_binary recovery is what keeps that correct, and
        # the chaos suite drives exactly that staleness.
        self._known_hashes: Dict[str, Set[str]] = {}
        self._rr = itertools.count(0)
        # task_id -> executor_id currently running it (set per dispatch
        # attempt, dropped when the dispatch thread finishes): the target
        # map for cancel_task — the losing copy of a speculated pair.
        self._running_on: Dict[int, str] = {}
        self._lock = named_lock("distributed.backend.DistributedBackend._lock")
        self._stopped = False
        self._stop_event = threading.Event()
        # The scheduler (or any observer) plugs in here: bus.post for
        # ExecutorLost/ExecutorRestarted, plus structured callbacks so the
        # DAG scheduler can scrub Stage.output_locs on loss.
        self.event_sink: Optional[Callable] = None
        self._executor_lost_listeners: List[Callable] = []
        if hosts is None:
            # Cluster membership from a hosts file ONLY when explicitly
            # configured (conf.hosts_file / VEGA_TPU_HOSTS_FILE) — a stray
            # ~/hosts.conf must not silently override num_executors.
            explicit = getattr(conf, "hosts_file", None) or \
                os.environ.get("VEGA_TPU_HOSTS_FILE")
            if explicit:
                from vega_tpu.hosts import Hosts

                if not os.path.exists(explicit):
                    raise NetworkError(
                        f"configured hosts file does not exist: {explicit}"
                    )
                hosts = Hosts.load(explicit).slaves or None
        n = num_executors or getattr(conf, "num_executors", None) or 2
        local_hosts = hosts or ["127.0.0.1"] * n
        # Elastic scale-up (scheduler/elastic.py): fresh slots get the
        # next never-used index. Placement honors per-host CAPACITY
        # weights — a hosts-file `host:N` entry appears N times in
        # local_hosts, so the multiplicity IS the capacity signal: new
        # slots land where occupancy-per-capacity is lowest (bigger boxes
        # first), not on a uniform rotation that fills a laptop as fast
        # as a 64-core box.
        self._slot_ids = itertools.count(len(local_hosts))
        self._host_weights: Dict[str, int] = {}
        for h in local_hosts:
            self._host_weights[h] = self._host_weights.get(h, 0) + 1
        self._spawn_workers(local_hosts)
        self._reaper = threading.Thread(
            target=self._reaper_loop, name="executor-reaper", daemon=True
        )
        self._reaper.start()

    # ------------------------------------------------------------- lifecycle
    def add_executor_lost_listener(self, callback: Callable) -> None:
        """callback(executor_id, host, shuffle_uri, reason) — fired once per
        lost executor, from the reaper thread."""
        self._executor_lost_listeners.append(callback)

    @staticmethod
    def _worker_knobs(conf, incarnation: int = 0) -> Dict[str, str]:
        """Every Configuration knob that WORKER-SIDE code reads
        (worker.py, shuffle_server.py, shuffle/), as VEGA_TPU_* env vars.
        The single source for both the spawned-subprocess environment and
        the ssh `env K=V` command line, so the two launch paths cannot
        drift — and the list vegalint VG010 checks worker-side reads
        against: a knob read on the worker side but missing here is
        silently stuck at its default in every executor."""
        return {
            "VEGA_TPU_DEPLOYMENT_MODE": "distributed",
            "VEGA_TPU_HEARTBEAT_INTERVAL_S": str(conf.heartbeat_interval_s),
            "VEGA_TPU_FETCH_RETRIES": str(conf.fetch_retries),
            "VEGA_TPU_FETCH_RETRY_INTERVAL_S": str(
                conf.fetch_retry_interval_s),
            "VEGA_TPU_FETCH_BATCH_ENABLED":
                "1" if conf.fetch_batch_enabled else "0",
            "VEGA_TPU_FETCH_QUEUE_BUCKETS": str(conf.fetch_queue_buckets),
            "VEGA_TPU_TASK_BINARY_DEDUP":
                "1" if conf.task_binary_dedup else "0",
            "VEGA_TPU_TASK_BINARY_CACHE_ENTRIES": str(
                conf.task_binary_cache_entries),
            # Straggler plane: map tasks replicate buckets, reduce
            # tasks fail slow/dead servers over to the replicas.
            "VEGA_TPU_SHUFFLE_REPLICATION": str(conf.shuffle_replication),
            "VEGA_TPU_FETCH_SLOW_SERVER_S": str(conf.fetch_slow_server_s),
            # Coded shuffle: map tasks fold bucket rows into peer-held
            # parity groups; reducers reconstruct lost buckets from the
            # survivors + parity (shuffle/coding.py).
            "VEGA_TPU_SHUFFLE_CODING": str(
                getattr(conf, "shuffle_coding", "none")),
            "VEGA_TPU_CODING_GROUP_K": str(conf.coding_group_k),
            "VEGA_TPU_CODING_PARITY_M": str(conf.coding_parity_m),
            # Device-tier string columns: a worker that rebuilds a dense
            # source from shipped host rows (host->dense round trips in
            # executor closures) must agree with the driver on whether
            # strings dictionary-encode and at what starting table
            # capacity — a mismatch would flip a worker onto the host
            # path the driver planned on device.
            "VEGA_TPU_DENSE_DICT_ENABLED":
                "1" if getattr(conf, "dense_dict_enabled", True) else "0",
            "VEGA_TPU_DENSE_DICT_CAPACITY": str(
                getattr(conf, "dense_dict_capacity", 65536)),
            # Push plan: map tasks push buckets to their reducer's
            # owning server; reducers read the pre-merged blob first.
            "VEGA_TPU_SHUFFLE_PLAN": str(
                getattr(conf, "shuffle_plan", "pull")),
            # The worker sizes its shuffle store AND its pre-merge
            # accumulator cap (a quarter of it) from this; unpropagated,
            # a driver-side budget override never reached the fleet.
            "VEGA_TPU_SHUFFLE_MEMORY_BUDGET": str(
                conf.shuffle_memory_budget),
            # Locality plane: driver-side placement policy, but workers
            # carry it so nested tooling (benchmarks, diagnostics) sees
            # the same switch the driver scheduled under.
            "VEGA_TPU_LOCALITY_WAIT_S": str(conf.locality_wait_s),
            # Elastic serving plane: driver-side policy knobs (the control
            # loop, admission bounds, blacklist decay), carried like
            # LOCALITY_WAIT_S so nested tooling in workers sees the same
            # switches the driver scheduled under.
            "VEGA_TPU_ELASTIC_ENABLED":
                "1" if getattr(conf, "elastic_enabled", False) else "0",
            "VEGA_TPU_ELASTIC_MIN_EXECUTORS": str(
                conf.elastic_min_executors),
            "VEGA_TPU_ELASTIC_MAX_EXECUTORS": str(
                conf.elastic_max_executors),
            "VEGA_TPU_ELASTIC_SCALE_UP_THRESHOLD": str(
                conf.elastic_scale_up_threshold),
            "VEGA_TPU_ELASTIC_SCALE_DOWN_THRESHOLD": str(
                conf.elastic_scale_down_threshold),
            "VEGA_TPU_ELASTIC_DECISION_INTERVAL_S": str(
                conf.elastic_decision_interval_s),
            "VEGA_TPU_DECOMMISSION_TIMEOUT_S": str(
                conf.decommission_timeout_s),
            "VEGA_TPU_POOL_MAX_QUEUED": str(conf.pool_max_queued),
            "VEGA_TPU_ADMISSION_MODE": str(conf.admission_mode),
            "VEGA_TPU_BLACKLIST_DECAY_S": str(conf.blacklist_decay_s),
            # Respawned incarnations disarm one-shot fault injections
            # (faults.py): a chaos-killed slot comes back healthy.
            "VEGA_TPU_FAULT_INCARNATION": str(incarnation),
        }

    def _launch(self, executor_id: str, host: str,
                incarnation: int = 0) -> subprocess.Popen:
        knobs = self._worker_knobs(self.conf, incarnation)
        if host in ("127.0.0.1", "localhost"):
            cmd = [
                sys.executable, "-m", "vega_tpu.distributed.worker",
                "--driver", self.service.uri,
                "--executor-id", executor_id,
                "--log-level", str(self.conf.log_level),
            ]
            # Workers are host-tier compute: keep them off the TPU.
            # Propagate the driver's logging/workdir config plus the
            # worker-side knobs so Context(...)-level overrides reach the
            # fleet, not just env-var-configured runs. (Logging/workdir
            # stay local-spawn-only: a remote host has its own fs.)
            worker_env = dict(
                os.environ, JAX_PLATFORMS="cpu",
                VEGA_TPU_LOG_LEVEL=str(self.conf.log_level),
                VEGA_TPU_LOG_CLEANUP="true" if self.conf.log_cleanup else "false",
                VEGA_TPU_LOCAL_DIR=self.conf.local_dir,
                **knobs,
            )
            return subprocess.Popen(
                cmd, env=worker_env, stdout=subprocess.PIPE,
                stderr=subprocess.DEVNULL, text=True,
            )
        # ssh launch (reference: context.rs:237-288) — assumes the
        # package is importable on the remote host. Popen env only reaches
        # the local ssh client, so the knobs ride the remote command line
        # (`env K=V ...`) — a remote worker heartbeating at a default
        # slower than the driver's liveness bound would be reaped while
        # healthy.
        cmd = [
            "ssh", host, "env",
            *[f"{k}={v}" for k, v in sorted(knobs.items())],
            sys.executable, "-m",
            "vega_tpu.distributed.worker",
            "--driver", self.service.uri,
            "--executor-id", executor_id,
            "--host", host,
            "--log-level", str(self.conf.log_level),
        ]
        return subprocess.Popen(
            cmd, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, text=True,
        )

    @staticmethod
    def _wait_ready(executor_id: str, proc: subprocess.Popen,
                    deadline: float) -> str:
        """Readiness with a real deadline: readline() blocks indefinitely,
        so read on a helper thread and join with the remaining time budget —
        a silent-but-alive worker (hung import, ssh prompt) fails loudly
        instead of hanging the driver."""
        box: Dict[str, str] = {}

        def reader():
            while True:
                line = proc.stdout.readline() if proc.stdout else ""
                if not line:
                    return
                if line.startswith("VEGA_WORKER_READY"):
                    box["line"] = line
                    return

        t = threading.Thread(target=reader, daemon=True)
        t.start()
        t.join(max(0.1, deadline - time.time()))
        if "line" not in box:
            if proc.poll() is not None:
                raise NetworkError(
                    f"worker {executor_id} exited during startup"
                )
            proc.kill()
            raise NetworkError(f"worker {executor_id} never became ready")
        return box["line"]

    @staticmethod
    def _confirm_task_port(executor_id: str, task_uri: str) -> None:
        """READY only proves the worker PRINTED; ping the task port before
        marking the slot live, so a worker whose server thread died
        between bind and serve (or whose READY line raced a crash) fails
        the launch loudly instead of eating its first max_failures worth
        of dispatches. Raises NetworkError on no (or wrong) answer."""
        host, port = protocol.parse_uri(task_uri)
        got = protocol.request(host, port, "ping", timeout=5.0)
        if got != executor_id:
            raise NetworkError(
                f"worker {executor_id} task port answered ping as {got!r}")

    @staticmethod
    def _drain_stdout(executor_id: str, proc: subprocess.Popen) -> None:
        """Keep reading the worker's stdout after READY. The PIPE buffer is
        ~64 KB: a chatty worker (user print()s in tasks) would otherwise
        block on a full pipe mid-task — a silent wedge."""
        def drain():
            try:
                while True:
                    line = proc.stdout.readline() if proc.stdout else ""
                    if not line:
                        return
                    log.debug("[%s stdout] %s", executor_id, line.rstrip())
            except (OSError, ValueError):
                pass

        threading.Thread(target=drain, daemon=True,
                         name=f"drain-{executor_id}").start()

    def _spawn_workers(self, hosts: List[str]) -> None:
        procs = []
        for i, host in enumerate(hosts):
            executor_id = f"exec-{i}"
            procs.append((executor_id, host, self._launch(executor_id, host)))

        deadline = time.time() + 30.0
        for executor_id, host, proc in procs:
            line = self._wait_ready(executor_id, proc, deadline)
            _tag, wid, task_uri = line.split()
            try:
                self._confirm_task_port(wid, task_uri)
            except NetworkError:
                proc.kill()  # READY-but-unserving: don't leak the process
                raise
            with self._lock:
                self._executors[wid] = _Executor(wid, task_uri, host, proc)
            self._drain_stdout(wid, proc)
        log.info("distributed backend up: %d executors", len(self._executors))

    def stop(self) -> None:
        self._stopped = True
        self._stop_event.set()
        with self._lock:
            executors = list(self._executors.values())
        for ex in executors:
            self._shutdown_worker(ex)
        if self._reaper.is_alive():
            self._reaper.join(timeout=2.0)
        self.service.stop()

    # --------------------------------------------------------------- liveness
    def _reaper_loop(self) -> None:
        """Driver-side liveness sweep: workers heartbeat into
        DriverService.workers; this thread is the thing that finally READS
        last_seen (the reference stored it and never looked)."""
        note_thread_role("reaper")
        while not self._stop_event.wait(self.conf.executor_reap_interval_s):
            try:
                self._sweep()
            except Exception:  # noqa: BLE001 — the reaper must survive
                log.exception("liveness sweep failed")

    def _sweep(self) -> None:
        live = self.service.live_workers()
        with self._lock:
            suspects = [ex for ex in self._executors.values() if not ex.reaped]
        for ex in suspects:
            if ex.process is not None and ex.process.poll() is not None:
                self._mark_lost(ex, "process exited")
            elif ex.executor_id in self.service.workers \
                    and ex.executor_id not in live:
                self._mark_lost(ex, "heartbeat timeout")
        if not self._stopped:
            self._maybe_respawn()

    def _mark_lost(self, ex: _Executor, reason: str) -> None:
        with self._lock:
            if ex.reaped:
                return
            ex.reaped = True
            ex.alive = False
            ex.lost_at = time.time()
            inflight = list(ex.sockets)
        log.warning("executor %s lost (%s); failing over its in-flight "
                    "tasks", ex.executor_id, reason)
        info = self.service.workers.get(ex.executor_id) or {}
        shuffle_uri = info.get("shuffle_uri")
        # A wedged-but-alive local worker holds its port and its half of
        # every open socket: kill it so the slot can respawn cleanly.
        if ex.process is not None and ex.process.poll() is None:
            ex.process.kill()
        # For ssh slots that Popen is only the LOCAL ssh client — the
        # remote worker survives it and would collide with a respawned
        # incarnation under the same executor_id. Best-effort remote kill
        # by the pid the worker registered, off-thread (the reaper must
        # not block on a dead host's ssh timeout).
        if ex.host not in ("127.0.0.1", "localhost") and info.get("pid"):
            def remote_kill(host=ex.host, pid=info["pid"]):
                try:
                    subprocess.run(["ssh", host, "kill", "-9", str(pid)],
                                   timeout=15.0,
                                   stdout=subprocess.DEVNULL,
                                   stderr=subprocess.DEVNULL)
                except (OSError, subprocess.TimeoutExpired):
                    pass
            threading.Thread(target=remote_kill, daemon=True,
                             name=f"remote-kill-{ex.executor_id}").start()
        # Unblock dispatch threads parked in recv() on this executor; their
        # NetworkError path re-dispatches to survivors.
        for sock in inflight:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        # Invalidate its map outputs: generation bump -> reducers refetch;
        # the DAG scheduler listener scrubs Stage.output_locs so the holes
        # are recomputed on resubmission.
        tracker = self.service.map_output_tracker
        removed = 0
        if shuffle_uri and hasattr(tracker, "unregister_server_outputs"):
            removed = tracker.unregister_server_outputs(shuffle_uri)
        if removed:
            log.info("unregistered %d map outputs of lost executor %s",
                     removed, ex.executor_id)
        for callback in list(self._executor_lost_listeners):
            try:
                callback(ex.executor_id, ex.host, shuffle_uri, reason)
            except Exception:  # noqa: BLE001 — observers must not kill the reaper
                log.exception("executor-lost listener raised")
        sink = self.event_sink
        if sink is not None:
            sink(ev.ExecutorLost(executor_id=ex.executor_id, host=ex.host,
                                 reason=reason))

    # ---------------------------------------------------------------- respawn
    def _respawn_possible(self) -> bool:
        """Any dead slot with restart budget left (or a respawn already in
        flight)? Dispatchers with zero live executors wait on this instead
        of burning max_failures in milliseconds while a worker boots. A
        slot the dispatcher marked dead but the reaper has not swept yet
        (reaped=False) counts too — the sweep that will respawn it is at
        most executor_reap_interval_s away."""
        with self._lock:
            return any(not ex.alive and ex.process is not None
                       and not ex.draining
                       and (ex.respawning
                            or ex.restarts < self.conf.executor_max_restarts)
                       for ex in self._executors.values())

    def _maybe_respawn(self) -> None:
        with self._lock:
            # Draining slots never respawn: they are being retired on
            # purpose (elastic scale-down), not recovered.
            dead = [ex for ex in self._executors.values()
                    if ex.reaped and ex.process is not None
                    and not ex.respawning and not ex.draining]
        for ex in dead:
            if self._stop_event.is_set():
                return
            if ex.restarts >= self.conf.executor_max_restarts:
                continue
            backoff = self.conf.executor_restart_backoff_s * (2 ** ex.restarts)
            if time.time() - ex.lost_at < backoff:
                continue
            with self._lock:
                if ex.respawning:
                    continue
                ex.respawning = True
            # Off the reaper thread: a replacement that hangs before READY
            # would otherwise suspend liveness detection for every OTHER
            # executor for up to the 30s readiness deadline.
            threading.Thread(target=self._respawn, args=(ex,), daemon=True,
                             name=f"respawn-{ex.executor_id}").start()

    def _respawn(self, ex: _Executor) -> None:
        if self._stop_event.is_set():
            ex.respawning = False
            return
        attempt = ex.restarts + 1
        log.warning("respawning executor %s (restart %d/%d)",
                    ex.executor_id, attempt, self.conf.executor_max_restarts)
        try:
            proc = self._launch(ex.executor_id, ex.host, incarnation=attempt)
            line = self._wait_ready(ex.executor_id, proc, time.time() + 30.0)
            _tag, wid, task_uri = line.split()
            try:
                self._confirm_task_port(wid, task_uri)
            except NetworkError:
                proc.kill()  # READY-but-unserving: don't leak the process
                raise
        except (NetworkError, ValueError) as e:
            log.warning("respawn of %s failed: %s", ex.executor_id, e)
            # Count the failed attempt so backoff keeps growing and the
            # restart cap still binds.
            ex.restarts = attempt
            ex.lost_at = time.time()
            ex.respawning = False
            return
        fresh = _Executor(wid, task_uri, ex.host, proc, restarts=attempt)
        with self._lock:
            if self._stopped:
                # stop() raced us while we waited for readiness: the fleet
                # it snapshotted is already down — don't leak a live worker
                # past the Context's lifetime.
                proc.kill()
                ex.respawning = False
                return
            self._executors[wid] = fresh
            ex.respawning = False
        self._drain_stdout(wid, proc)
        sink = self.event_sink
        if sink is not None:
            sink(ev.ExecutorRestarted(executor_id=wid, host=ex.host,
                                      attempt=attempt))

    # ----------------------------------------------------------- elastic fleet
    def add_executor(self) -> str:
        """Scale-up: spawn ONE brand-new executor slot mid-run (the PR 2
        `_launch` path — readiness-gated, task-port-confirmed, stdout-
        drained), register it, and announce `ExecutorAdded` on the bus.
        The new slot enters `_pick_executor` rotation the moment it lands
        in `_executors`. Raises NetworkError if the worker never becomes
        ready — the caller (the elastic control loop) logs and retries on
        a later decision tick."""
        assert_role("elastic")  # fleet mutation: driver-side control only
        with self._lock:
            if self._stopped:
                raise NetworkError("backend is stopped; cannot scale up")
            idx = next(self._slot_ids)
            live_by_host: Dict[str, int] = {}
            for ex in self._executors.values():
                if ex.alive and not ex.draining:
                    live_by_host[ex.host] = live_by_host.get(ex.host, 0) + 1
        executor_id = f"exec-{idx}"
        host = _weighted_scale_host(self._host_weights, live_by_host)
        proc = self._launch(executor_id, host)
        line = self._wait_ready(executor_id, proc, time.time() + 30.0)
        _tag, wid, task_uri = line.split()
        try:
            self._confirm_task_port(wid, task_uri)
        except NetworkError:
            proc.kill()  # READY-but-unserving: don't leak the process
            raise
        with self._lock:
            if self._stopped:
                proc.kill()  # stop() raced the launch: don't leak
                raise NetworkError("backend stopped during scale-up")
            self._executors[wid] = _Executor(wid, task_uri, host, proc)
            fleet = len([e for e in self._executors.values()
                         if e.alive and not e.draining])
        self._drain_stdout(wid, proc)
        log.info("elastic scale-up: %s on %s (fleet now %d)", wid, host,
                 fleet)
        sink = self.event_sink
        if sink is not None:
            sink(ev.ExecutorAdded(executor_id=wid, host=host,
                                  fleet_size=fleet))
        return wid

    def claim_decommission(self, executor_id: str,
                           min_live: int = 0) -> str:
        """Atomically claim a slot for decommission. Returns "ok" (the
        slot is now draining: no new placements, out of the shuffle-peer
        registry, never respawned), "unknown", "claimed" (a racing
        decommission already holds it — two callers can never both run
        the ladder), or "floor" (retiring this LIVE slot would leave
        fewer than `min_live` alive non-draining executors). The floor
        check and the claim share ONE lock acquisition, so concurrent
        decommissions of DIFFERENT victims cannot jointly shrink the
        fleet below the floor either."""
        with self._lock:
            ex = self._executors.get(executor_id)
            if ex is None:
                return "unknown"
            if ex.draining:
                return "claimed"
            if ex.alive:
                live = len([e for e in self._executors.values()
                            if e.alive and not e.draining])
                if live - 1 < min_live:
                    return "floor"
            ex.draining = True
        self.service.set_draining(executor_id, True)
        return "ok"

    def release_decommission(self, executor_id: str) -> None:
        """Drop a decommission claim (abandoned/failed ladder): the slot
        re-enters placement and the peer registry. No-op for a slot the
        ladder already reaped."""
        with self._lock:
            ex = self._executors.get(executor_id)
            if ex is None:
                return
            ex.draining = False
        self.service.set_draining(executor_id, False)

    @staticmethod
    def _shutdown_worker(ex: _Executor, graceful: bool = True) -> None:
        """One worker's shutdown handshake + process reap (shared by
        stop() and remove_executor so the two cannot drift)."""
        if graceful:
            try:
                host, port = protocol.parse_uri(ex.task_uri)
                with protocol.connect(host, port, timeout=2.0) as sock:
                    protocol.send_msg(sock, "shutdown")
                    protocol.recv_msg(sock)
            except NetworkError:
                pass  # fall through to the process reap below
        if ex.process is not None:
            try:
                ex.process.wait(timeout=5.0)
            except subprocess.TimeoutExpired:
                ex.process.kill()

    def remove_executor(self, executor_id: str, graceful: bool = True) -> None:
        """Reap a decommissioned slot: drop it from the executor table and
        the worker registry FIRST (so the liveness reaper never sees its
        exit as a loss — `reaped` is also set under the same lock, which
        covers a sweep that snapshotted the victim BEFORE this pop and
        would otherwise _mark_lost its graceful exit mid-tick), then shut
        the process down — gracefully when the worker is healthy, straight
        kill after a forced escalation. Also clears the slot's advisory
        state (known-hash set, blacklist count dies with the _Executor
        object) so a future slot under a fresh id starts clean."""
        assert_role("elastic")  # fleet mutation: driver-side control only
        with self._lock:
            ex = self._executors.pop(executor_id, None)
            self._known_hashes.pop(executor_id, None)
            if ex is not None:
                ex.draining = True
                ex.alive = False
                ex.reaped = True  # _mark_lost's guard: never a "loss"
        if ex is None:
            return
        self.service.unregister_worker(executor_id)
        self._shutdown_worker(ex, graceful=graceful)

    def declare_lost(self, executor_id: str, reason: str) -> None:
        """Escalation entry for the elastic decommission ladder: a victim
        that wedged mid-drain is handed to the PR 2 executor-lost path
        (socket teardown, output unregistration, listener scrub,
        ExecutorLost on the bus)."""
        with self._lock:
            ex = self._executors.get(executor_id)
        if ex is not None:
            self._mark_lost(ex, reason)

    def executor_inflight(self) -> Dict[str, int]:
        """Live per-executor in-flight dispatch counts (from the cancel-
        routing map): the elastic loop's occupancy watermark and the
        decommission drain gate."""
        with self._lock:
            counts: Dict[str, int] = {}
            for eid in self._running_on.values():
                counts[eid] = counts.get(eid, 0) + 1
            return counts

    def fleet_snapshot(self) -> List[dict]:
        """One row per slot (id/host/state/in-flight/restarts) for
        ctx.fleet_status() and the elastic controller's decisions."""
        inflight = self.executor_inflight()
        with self._lock:
            return [{
                "executor_id": ex.executor_id,
                "host": ex.host,
                "alive": ex.alive,
                "draining": ex.draining,
                "restarts": ex.restarts,
                "inflight": inflight.get(ex.executor_id, 0),
            } for ex in self._executors.values()]

    # -------------------------------------------------------------- dispatch
    @property
    def parallelism(self) -> int:
        # Draining slots are excluded: the arbiter must stop feeding a
        # fleet slice that takes no new placements, or queued tasks park
        # against capacity that will never serve them.
        with self._lock:
            n = max(1, len([e for e in self._executors.values()
                            if e.alive and not e.draining]))
        return n * self.conf.num_workers

    # Locality-tier names, indexed by score (0 is best): PROCESS_LOCAL
    # (executor-id or shuffle-server-URI match — the task's preferred data
    # lives in that very process), HOST_LOCAL (host match), ANY.
    _TIER_NAMES = ("process", "host", "any")

    def shuffle_peer_uris(self) -> List[str]:
        """Live, non-draining workers' shuffle-server URIs — the same
        registry `list_shuffle_peers` serves the map/reduce planes, so the
        DAG scheduler's push-owner computation (dag._reduce_side_prefs)
        rotates over the same peer set the mappers push along. A draining
        slot leaves this set the moment decommission starts: no new
        replica or pre-merge state lands on the node being retired."""
        return [info["shuffle_uri"]
                for wid, info in self.service.live_workers().items()
                if info.get("shuffle_uri")
                and wid not in self.service.draining]

    def _effective_failures(self, ex: _Executor, now: float) -> int:
        """Consecutive dispatch-failure count with time decay
        (blacklist_decay_s): a count whose LAST failure is older than the
        decay window is forgiven, so a recovered-but-once-flaky executor
        rejoins rotation instead of staying advisory-deprioritized
        forever. 0 disables decay. Caller holds self._lock."""
        decay = float(getattr(self.conf, "blacklist_decay_s", 0.0) or 0.0)
        if decay > 0 and ex.failures \
                and now - ex.last_failure_at >= decay:
            log.info("blacklist decay: forgiving %d stale failures of %s",
                     ex.failures, ex.executor_id)
            ex.failures = 0
        return ex.failures

    def _match_tier(self, executor: _Executor, locs) -> int:
        """0 PROCESS_LOCAL, 1 HOST_LOCAL, 2 ANY for `executor` against a
        task's preferred locations (which may name executor ids — cache
        tracker entries — hosts, or shuffle-server URIs from the
        reduce-side preference)."""
        if not locs:
            return 2
        if executor.executor_id in locs:
            return 0
        uri = executor.shuffle_uri
        if uri is None:
            info = self.service.workers.get(executor.executor_id)
            uri = executor.shuffle_uri = (info or {}).get("shuffle_uri")
        if uri and uri in locs:
            return 0
        if executor.host in locs:
            return 1
        return 2

    def _recoverable_better_tier_locked(self, locs, best_tier: int,
                                        exclude) -> bool:
        """Could waiting improve this task's locality tier? True only for
        a TEMPORARILY-down preferred executor: a dead slot with respawn
        budget (or a respawn already in flight) whose HOST matches `locs`
        while the task currently only scores ANY. Host-level data —
        pinned-host files, host-resident disk — survives a process
        respawn, so that wait can genuinely be repaid; PROCESS-level
        matches never qualify, because the data they name died with the
        process (a respawn keeps the executor id but starts with an
        empty cache, and binds a fresh shuffle server holding none of
        the pushed state) — waiting would add latency for zero possible
        win. Blacklisted, speculation-excluded, or restart-exhausted
        slots never qualify either: the delay wait must demote
        immediately rather than starve. Caller holds self._lock."""
        if best_tier <= 1:
            return False  # already host-local or better
        now = time.time()
        for ex in self._executors.values():
            if ex.alive or ex.process is None or ex.draining:
                continue
            if not (ex.respawning
                    or ex.restarts < self.conf.executor_max_restarts):
                continue
            if ex.executor_id in exclude:
                continue
            if self._effective_failures(ex, now) >= \
                    self.conf.executor_blacklist_threshold:
                continue
            if ex.host in locs:
                return True
        return False

    def _pick_executor(self, task: Task) -> _Executor:
        return self._pick_executor_scored(task)[0]

    def _pick_executor_scored(self, task: Task):
        """One placement decision: (executor, locality_tier, improvable).

        Eligibility is unchanged from the pre-locality dispatch path:
        speculative duplicates must land on a different executor than the
        straggling original (task.exclude_executors) and never on a
        blacklisted one — no eligible executor skips the launch (raises;
        the DAG ignores the failure since the original still runs) rather
        than relaxing; ordinary tasks keep the advisory blacklist (better
        flaky than none).

        Placement among the eligible:
          * locality_wait_s <= 0 — the legacy round-robin + first-match
            seek (reference: distributed_scheduler.rs:447-469),
            byte-for-byte, except that the seek now also compares
            e.host: the locs _get_preferred_locs returns are hosts (and
            executor ids), so the old id-only soft branch made host-level
            locality from the cache tracker and pinned-host RDDs dead in
            distributed mode. Reports no tier ("" — the histogram stays
            empty, placement is unmeasured).
          * locality_wait_s > 0 — candidates are scored
            PROCESS_LOCAL > HOST_LOCAL > ANY, ties broken by fewest
            in-flight tasks (then round-robin), instead of first-match.
            `improvable` tells the caller whether waiting could yield a
            better tier (see _pick_with_locality_wait)."""
        speculative = bool(getattr(task, "speculative", False))
        exclude = getattr(task, "exclude_executors", None) or ()
        locs = getattr(task, "preferred_locs", None) or ()
        wait_s = float(getattr(self.conf, "locality_wait_s", 0.0) or 0.0)
        with self._lock:
            now = time.time()
            alive = [e for e in self._executors.values() if e.alive]
            if not alive:
                raise NetworkError("no live executors")
            # Draining slots (graceful decommission in progress) take no
            # new placements — unless they are ALL that's left, in which
            # case stranding the task would be worse than one more task
            # on a leaving node.
            active = [e for e in alive if not e.draining]
            if active:
                alive = active
            threshold = self.conf.executor_blacklist_threshold
            if exclude:
                eligible = [e for e in alive
                            if e.executor_id not in exclude]
                if eligible or speculative:
                    alive = eligible  # advisory for ordinary retries only
            if speculative:
                alive = [e for e in alive
                         if self._effective_failures(e, now) < threshold]
                if not alive:
                    raise NetworkError(
                        "no eligible executor for speculative attempt "
                        f"(excluded={set(exclude) or '{}'})"
                    )
            else:
                clean = [e for e in alive
                         if self._effective_failures(e, now) < threshold]
                if clean:
                    alive = clean  # blacklist advisory: better flaky than none
            if wait_s <= 0:
                # Pinned seek and soft-locality seek (both now compare
                # e.host as well as e.executor_id). Round-robin AMONG the
                # matches, not first-match: on a fleet with several
                # executors per host (the standard local spawn — every
                # executor is 127.0.0.1), a host-named preference matches
                # them all, and first-match would funnel every such task
                # onto dict-order executor 0 instead of spreading.
                if locs:
                    matches = [e for e in alive
                               if e.executor_id in locs or e.host in locs]
                    if matches:
                        return (matches[next(self._rr) % len(matches)],
                                "", False)
                return alive[next(self._rr) % len(alive)], "", False
            tiers = [(self._match_tier(e, locs), e) for e in alive]
            best = min(t for t, _ in tiers)
            cands = [e for t, e in tiers if t == best]
            # Tie-break: fewest in-flight dispatches first (live load,
            # from the cancel-routing map), then round-robin so equally
            # loaded executors still spread.
            running: Dict[str, int] = {}
            for eid in self._running_on.values():
                running[eid] = running.get(eid, 0) + 1
            least = min(running.get(e.executor_id, 0) for e in cands)
            cands = [e for e in cands
                     if running.get(e.executor_id, 0) == least]
            chosen = cands[next(self._rr) % len(cands)]
            improvable = bool(locs) and best > 0 and \
                self._recoverable_better_tier_locked(locs, best, exclude)
            return chosen, self._TIER_NAMES[best], improvable

    def _pick_with_locality_wait(self, task: Task):
        """(executor, tier): the bounded delay wait. A task whose best
        achievable tier could still improve — a HOST it prefers has its
        only executor down with a respawn in flight or budgeted
        (_recoverable_better_tier_locked) — re-picks every 50ms for up
        to locality_wait_s before settling for the worse tier.
        Never starves: permanently-dead/blacklisted/excluded preferences
        report not-improvable and settle immediately, speculative
        duplicates never wait (they ARE the latency mitigation), and the
        deadline is absolute from the first pick."""
        deadline = None
        while True:
            executor, tier, improvable = self._pick_executor_scored(task)
            if not improvable or bool(getattr(task, "speculative", False)):
                return executor, tier
            now = time.time()
            if deadline is None:
                deadline = now + float(self.conf.locality_wait_s)
            elif now >= deadline:
                log.info("locality wait expired for %s; settling for %s "
                         "tier on %s", task, tier, executor.executor_id)
                return executor, tier
            time.sleep(min(0.05, max(0.001, deadline - now)))

    @property
    def preserialize_stage_binaries(self) -> bool:
        # Deduplicated dispatch wants the stage binary pickled once at
        # submit_missing_tasks time (off the per-task path); the legacy
        # leg pickles whole tasks below and never touches it.
        return bool(self.conf.task_binary_dedup)

    def cancel_task(self, task_id: int) -> None:
        """Best-effort cancel of a running attempt (the losing copy of a
        speculated pair): one `cancel_task` message to the executor that
        holds it, fired from a throwaway thread so the DAG event loop
        never blocks on a wedged worker's connect timeout. Correctness
        never depends on delivery — completions are deduped driver-side."""
        with self._lock:
            executor_id = self._running_on.get(task_id)
            ex = self._executors.get(executor_id) if executor_id else None
        if ex is None or not ex.alive:
            return

        def _send(uri=ex.task_uri):
            try:
                host, port = protocol.parse_uri(uri)
                with protocol.connect(host, port, timeout=5.0) as sock:
                    protocol.send_msg(sock, "cancel_task", task_id)
                    protocol.recv_msg(sock)
            except NetworkError:
                pass  # loser keeps running; its completion is ignored

        threading.Thread(target=_send, daemon=True,
                         name=f"cancel-{task_id}").start()

    def worker_stats(self) -> Dict[str, dict]:
        """Process-local counters of every live worker (fetcher/push
        totals — the worker-side numbers the driver event bus cannot
        see), one `worker_stats` round trip per executor, issued in
        PARALLEL so one wedged worker bounds the whole call at the single
        5s probe deadline instead of 5s per dead peer. The deadline
        covers the WHOLE round (connect AND reply — a wedged-but-
        accepting worker must not park the probe on the 120s IO_TIMEOUT),
        and the returned dict is a post-join snapshot so a straggling
        probe thread can never mutate it under the caller's iteration.
        Observability for tests and benchmarks/locality_ab.py: an
        unreachable worker is simply omitted."""
        with self._lock:
            executors = [e for e in self._executors.values() if e.alive]
        out: Dict[str, dict] = {}
        out_lock = threading.Lock()

        def probe(ex: _Executor) -> None:
            try:
                host, port = protocol.parse_uri(ex.task_uri)
                with protocol.connect(host, port, timeout=5.0) as sock:
                    sock.settimeout(5.0)  # whole-round probe deadline
                    protocol.send_msg(sock, "worker_stats")
                    reply_type, reply = protocol.recv_msg(sock)
                if reply_type != "ok":
                    raise NetworkError(
                        f"worker_stats refused: {reply_type!r}")
            except NetworkError:
                log.debug("worker_stats probe of %s failed",
                          ex.executor_id, exc_info=True)
                return
            with out_lock:
                out[ex.executor_id] = reply

        threads = [threading.Thread(target=probe, args=(ex,), daemon=True,
                                    name=f"worker-stats-{ex.executor_id}")
                   for ex in executors]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=6.0)
        with out_lock:
            return dict(out)

    def submit(self, task: Task, callback: Callable[[TaskEndEvent], None]) -> None:
        binary = task.stage_binary
        dedup = bool(self.conf.task_binary_dedup) and binary is not None
        if dedup:
            # Only the tiny header is serialized on the submit caller's
            # thread (the DAG event loop); the stage binary was pickled
            # once per stage at submit_missing_tasks time.
            header_payload = serialization.dumps(task.header())
            payload = None
            # Byte counters accumulate per WIRE SEND in _send_task (not
            # per serialization) so a redispatch after a dead executor
            # counts the same way on both legs — keeps the A/B
            # driver-bytes comparison apples-to-apples under retries.
            stats = {"mode": "v2", "header_bytes": 0,
                     "binary_bytes": 0, "binaries_shipped": 0,
                     "need_binary": 0, "cache_hit": 0, "result_bytes": 0}
        else:
            # Legacy one-envelope-per-task protocol (the reference's only
            # shape, serialized_data.capnp): whole lineage per task.
            header_payload = None
            payload = serialization.dumps(task)
            stats = {"mode": "legacy", "task_bytes": 0,
                     "result_bytes": 0}

        def dispatch():
            try:
                _dispatch_loop()
            except BaseException as exc:  # noqa: BLE001 — a dead dispatch
                # thread would hang the job; always deliver an event.
                log.exception("dispatch for %s failed", task)
                callback(TaskEndEvent(task=task, success=False, error=exc,
                                      dispatch=stats))
            finally:
                with self._lock:
                    self._running_on.pop(task.task_id, None)

        def _send_task(sock: socket.socket, executor: _Executor) -> None:
            if not dedup:
                protocol.send_msg(sock, "task", payload)
                stats["task_bytes"] += len(payload)
                return
            sha = binary.sha
            with self._lock:
                known = self._known_hashes.setdefault(
                    executor.executor_id, set())
                if len(known) > 4096:
                    # Unbounded growth guard (a hash per stage, forever).
                    # Clearing is always safe: the worst case is one
                    # redundant re-ship per (stage, executor).
                    known.clear()
                ship = sha not in known
                if ship:
                    # Optimistically marked BEFORE the send so the other
                    # 63 dispatch threads of this stage ride the cache
                    # instead of all shipping the binary; if this send
                    # dies the worker-side need_binary reply heals it.
                    known.add(sha)
            # Coalesced into ONE write on the warm path (TWO when the
            # binary ships — its possibly-multi-MB payload goes in its own
            # sendall rather than paying a join copy): the byte stream is
            # identical to the per-frame sends, but a TCP_NODELAY socket
            # otherwise emits ~6 small segments per task on exactly the
            # hot path this plane exists to slim down.
            frames = [protocol.encode_msg("task_v2", sha),
                      serialization.frame_bytes(header_payload)]
            stats["header_bytes"] += len(header_payload)
            if ship:
                payload_bytes = binary.payload
                frames.append(protocol.encode_msg("binary", sha))
                frames.append(serialization.frame_prefix(len(payload_bytes)))
                protocol.send_raw(sock, b"".join(frames))
                protocol.send_raw(sock, payload_bytes)
                stats["binaries_shipped"] += 1
                stats["binary_bytes"] += len(payload_bytes)
            else:
                frames.append(protocol.encode_msg("binary_cached", sha))
                protocol.send_raw(sock, b"".join(frames))

        def _recv_result(sock: socket.socket):
            reply_type, meta = protocol.recv_msg(sock)
            while reply_type == "need_binary":
                # Worker lacks the hash (fresh respawn, cache eviction,
                # chaos drop): re-ship inline on this same connection —
                # correctness never depends on the known-hash bookkeeping.
                protocol.send_msg(sock, "binary", binary.sha)
                protocol.send_bytes(sock, binary.payload)
                stats["need_binary"] += 1
                stats["binaries_shipped"] += 1
                stats["binary_bytes"] += len(binary.payload)
                reply_type, meta = protocol.recv_msg(sock)
            if reply_type != "result":
                raise NetworkError(f"bad reply {reply_type}")
            if meta is None:
                # Legacy reply: one pickled frame.
                reply = protocol.recv_bytes(sock)
                stats["result_bytes"] += len(reply)
                return serialization.loads(reply)
            # Dedup reply: pickle header + `meta` out-of-band buffer
            # frames received into writable bytearrays (zero-copy numpy).
            head = protocol.recv_bytes(sock)
            buffers = [protocol.recv_buffer(sock) for _ in range(meta)]
            stats["result_bytes"] += len(head) + sum(len(b) for b in buffers)
            if dedup and stats["need_binary"] == 0 \
                    and not stats["binaries_shipped"]:
                stats["cache_hit"] = 1
            return serialization.loads_oob(head, buffers)

        def _dispatch_loop():
            attempts = 0
            # Total momentary loss (every executor dead at once) must not
            # burn max_failures in milliseconds while a respawn that WOULD
            # recover the fleet is still booting: wait out the restart
            # budget before declaring the task undispatchable.
            no_executor_deadline = None
            while True:
                try:
                    executor, tier = self._pick_with_locality_wait(task)
                except NetworkError as e:
                    if task.speculative:
                        # A duplicate with nowhere eligible to run is a
                        # skipped launch, not a task failure worth waiting
                        # on: the original is still running and the DAG
                        # ignores this event while it lives.
                        callback(TaskEndEvent(task=task, success=False,
                                              error=e, dispatch=stats))
                        return
                    if not self._stopped and self._respawn_possible():
                        if no_executor_deadline is None:
                            conf = self.conf
                            budget = sum(
                                conf.executor_restart_backoff_s * (2 ** k)
                                for k in range(conf.executor_max_restarts)
                            ) + 35.0  # + readiness deadline headroom
                            no_executor_deadline = time.time() + budget
                        if time.time() < no_executor_deadline:
                            time.sleep(0.25)
                            continue
                    callback(TaskEndEvent(task=task, success=False, error=e,
                                          dispatch=stats))
                    return
                no_executor_deadline = None
                # Where this attempt runs: the speculation sweep reads
                # dispatched_to to exclude the straggler's executor from
                # its duplicate; cancel_task resolves task_id through
                # _running_on to reach the right worker.
                task.dispatched_to = executor.executor_id
                with self._lock:
                    self._running_on[task.task_id] = executor.executor_id
                try:
                    host, port = protocol.parse_uri(executor.task_uri)
                    with protocol.connect(host, port) as sock:
                        # Register with the executor so the liveness reaper
                        # can shut this socket down and unblock us if the
                        # executor wedges (alive but silent) mid-task. The
                        # reaped check and the add share one lock acquisition
                        # with _mark_lost's snapshot: a socket is either in
                        # the snapshot (shut down by the reaper) or refused
                        # here — never silently parked on a dead executor.
                        with self._lock:
                            if executor.reaped:
                                raise NetworkError(
                                    f"executor {executor.executor_id} was "
                                    "reaped while connecting"
                                )
                            executor.sockets.add(sock)
                        try:
                            _send_task(sock, executor)
                            # The result wait is unbounded: tasks may
                            # legitimately run for hours. Executor death is
                            # detected by the OS (socket reset; keepalive
                            # covers remote hosts) or by the reaper — not
                            # by an arbitrary IO timeout.
                            # vegalint: ignore[VG012] — deliberately unbounded: tasks may run for hours; executor death unblocks via the reaper's socket shutdown / OS keepalive
                            sock.settimeout(None)
                            sock.setsockopt(socket.SOL_SOCKET,
                                            socket.SO_KEEPALIVE, 1)
                            status, *rest = _recv_result(sock)
                        finally:
                            with self._lock:
                                executor.sockets.discard(sock)
                    # Transport round-trip succeeded (whatever the task's
                    # own outcome): the executor is healthy — clear its
                    # blacklist count so only CONSECUTIVE transport
                    # failures blacklist it, not a lifetime's worth of
                    # recovered blips.
                    with self._lock:
                        executor.failures = 0
                    if status == "success":
                        result, duration = rest
                        callback(TaskEndEvent(task=task, success=True,
                                              result=result,
                                              duration_s=duration,
                                              dispatch=stats,
                                              executor=executor.executor_id,
                                              locality=tier))
                    else:
                        exc, remote_tb = rest
                        if not isinstance(exc, BaseException):
                            exc = TaskError(repr(exc), remote_traceback=remote_tb)
                        callback(TaskEndEvent(task=task, success=False,
                                              error=exc, dispatch=stats,
                                              executor=executor.executor_id,
                                              locality=tier))
                    return
                except NetworkError as e:
                    # Executor lost: mark dead, re-dispatch elsewhere
                    # (the failure-detection the reference lacks).
                    attempts += 1
                    log.warning("executor %s unreachable (%s); re-dispatching",
                                executor.executor_id, e)
                    with self._lock:
                        executor.failures += 1
                        executor.last_failure_at = time.time()
                        if executor.reaped:
                            executor.alive = False  # never resurrect
                        else:
                            executor.alive = executor.process is not None and \
                                executor.process.poll() is None
                    if attempts >= 3 + len(self._executors):
                        callback(TaskEndEvent(task=task, success=False,
                                              error=e, dispatch=stats))
                        return
                    time.sleep(0.1 * attempts)

        threading.Thread(target=dispatch, daemon=True,
                         name=f"dispatch-{task.task_id}").start()
