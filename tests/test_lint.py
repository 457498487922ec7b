"""vegalint self-tests: every rule VG001–VG008 fires on its fixture and
stays silent on the corrected form; pragma suppression requires a
justification; reporters stay machine-readable; and the runtime
sync-witness (the dynamic half of VG003) catches inversions a static
pass cannot see.

Fixtures are written into tmp trees that mimic the repo layout, because
several rules scope by path (vega_tpu/tpu/..., distributed/, ...).
"""

import json
import textwrap
import threading

import pytest

from vega_tpu.lint.engine import render_json, render_text, run_lint
from vega_tpu.lint.sync_witness import (
    LockOrderError,
    WitnessLock,
    WitnessRLock,
    named_lock,
    witness,
)


def _lint(tmp_path, relpath, src, select=None):
    p = tmp_path / relpath
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
    return run_lint([str(tmp_path)], select=select)


def _rules(result):
    return [f.rule for f in result.findings]


# ---------------------------------------------------------------- VG001
def test_vg001_fires_on_raw_shard_map_spellings(tmp_path):
    res = _lint(tmp_path, "vega_tpu/tpu/newop.py", """\
        import jax
        from jax import lax
        from jax.experimental.shard_map import shard_map as smap

        def f(fn, mesh):
            g = jax.shard_map(fn, mesh=mesh)
            with jax.enable_x64():  # fine: no wrapper to go through
                pass
            return lax.platform_dependent(tpu=fn, default=fn)
        """, select=["VG001"])
    assert _rules(res).count("VG001") == 2  # the import + jax.shard_map
    assert all(f.path.endswith("newop.py") for f in res.findings)


def test_vg001_silent_on_compat_shim_and_inside_compat(tmp_path):
    clean = _lint(tmp_path, "vega_tpu/tpu/newop.py", """\
        from vega_tpu.tpu import compat

        def f(fn, mesh):
            return compat.shard_map(fn, mesh=mesh)
        """, select=["VG001"])
    assert not clean.findings
    # compat.py itself is the one place allowed to touch the raw surface
    exempt = _lint(tmp_path, "vega_tpu/tpu/compat.py", """\
        import jax
        shard_map = jax.shard_map
        """, select=["VG001"])
    assert not exempt.findings


# ---------------------------------------------------------------- VG002
def test_vg002_fires_on_import_time_probe(tmp_path):
    res = _lint(tmp_path, "vega_tpu/newmod.py", """\
        import jax
        N = len(jax.devices())
        """, select=["VG002"])
    assert _rules(res) == ["VG002"]


def test_vg002_fires_on_module_level_call_to_probing_local_fn(tmp_path):
    res = _lint(tmp_path, "vega_tpu/newmod.py", """\
        import jax

        def probe():
            return jax.default_backend()

        BACKEND = probe()
        """, select=["VG002"])
    assert _rules(res) == ["VG002"]
    assert res.findings[0].line == 6


def test_vg002_fires_in_else_of_main_guard(tmp_path):
    # the else branch of a __main__ guard is exactly what runs on import
    res = _lint(tmp_path, "vega_tpu/newmod.py", """\
        import jax

        if __name__ == "__main__":
            pass
        else:
            N = len(jax.devices())
        """, select=["VG002"])
    assert _rules(res) == ["VG002"]


def test_vg002_silent_inside_functions_and_main_guard(tmp_path):
    res = _lint(tmp_path, "vega_tpu/newmod.py", """\
        import jax

        def backend():
            return jax.default_backend()

        if __name__ == "__main__":
            print(jax.devices())
        """, select=["VG002"])
    assert not res.findings


# ---------------------------------------------------------------- VG003
def test_vg003_fires_on_lock_order_cycle(tmp_path):
    res = _lint(tmp_path, "vega_tpu/newmod.py", """\
        import threading

        a_lock = threading.Lock()
        b_lock = threading.Lock()

        def forward():
            with a_lock:
                with b_lock:
                    pass

        def backward():
            with b_lock:
                with a_lock:
                    pass
        """, select=["VG003"])
    assert _rules(res) == ["VG003"]
    assert "cycle" in res.findings[0].message


def test_vg003_silent_on_consistent_order(tmp_path):
    res = _lint(tmp_path, "vega_tpu/newmod.py", """\
        import threading

        a_lock = threading.Lock()
        b_lock = threading.Lock()

        def one():
            with a_lock:
                with b_lock:
                    pass

        def two():
            with a_lock:
                with b_lock:
                    pass
        """, select=["VG003"])
    assert not res.findings


def test_vg003_fires_on_blocking_call_under_cache_lock(tmp_path):
    res = _lint(tmp_path, "vega_tpu/newcache.py", """\
        import threading
        import jax

        class ThingCache:
            def __init__(self):
                self._lock = threading.Lock()

            def read(self, arr):
                with self._lock:
                    return jax.device_get(arr)
        """, select=["VG003"])
    assert _rules(res) == ["VG003"]
    assert "device_get" in res.findings[0].message


def test_vg003_one_call_hop_and_nested_def_exclusion(tmp_path):
    res = _lint(tmp_path, "vega_tpu/newcache.py", """\
        import threading
        import jax

        class ThingStore:
            def __init__(self):
                self._lock = threading.Lock()

            def _fetch(self, arr):
                return jax.device_get(arr)

            def read(self, arr):
                with self._lock:
                    # a callback DEFINED under the lock runs later: clean
                    def later():
                        return arr.result()
                    return later
        """, select=["VG003"])
    assert not res.findings  # _fetch not called under the lock; def is ok


def test_vg003_detects_self_deadlock_on_nonreentrant_lock(tmp_path):
    res = _lint(tmp_path, "vega_tpu/newmod.py", """\
        import threading

        big_lock = threading.Lock()

        def recurse():
            with big_lock:
                with big_lock:
                    pass
        """, select=["VG003"])
    assert _rules(res) == ["VG003"]
    assert "self-deadlock" in res.findings[0].message


def test_vg003_reentrant_lock_reacquire_is_clean(tmp_path):
    res = _lint(tmp_path, "vega_tpu/newmod.py", """\
        import threading

        big_lock = threading.RLock()

        def recurse():
            with big_lock:
                with big_lock:
                    pass
        """, select=["VG003"])
    assert not res.findings


# ---------------------------------------------------------------- VG004
def test_vg004_fires_on_materializing_reader(tmp_path):
    res = _lint(tmp_path, "vega_tpu/tpu/newrdd.py", """\
        class Node:
            @property
            def hash_placed(self):
                self._settle_placement()
                return self._hash_placed

            @property
            def key_sorted(self):
                return self.block().sorted
        """, select=["VG004"])
    assert _rules(res) == ["VG004", "VG004"]


def test_vg004_silent_on_pure_reader(tmp_path):
    res = _lint(tmp_path, "vega_tpu/tpu/newrdd.py", """\
        class Node:
            @property
            def hash_placed(self):
                return self.parent.hash_placed

            @property
            def key_sorted(self):
                return False
        """, select=["VG004"])
    assert not res.findings


# ---------------------------------------------------------------- VG005
def test_vg005_fires_on_blind_broad_except(tmp_path):
    res = _lint(tmp_path, "vega_tpu/distributed/newsvc.py", """\
        def dispatch(sock):
            try:
                return sock.recv(4)
            except Exception:
                return None
        """, select=["VG005"])
    assert _rules(res) == ["VG005"]


def test_vg005_silent_when_logged_or_reraised(tmp_path):
    res = _lint(tmp_path, "vega_tpu/shuffle/newfetch.py", """\
        import logging

        log = logging.getLogger("vega_tpu")

        def a(sock):
            try:
                return sock.recv(4)
            except Exception:
                log.exception("recv failed")
                return None

        def b(sock):
            try:
                return sock.recv(4)
            except Exception as exc:
                raise VegaError("fetch failed") from exc
        """, select=["VG005"])
    assert not res.findings


def test_vg005_out_of_scope_dirs_ignored(tmp_path):
    res = _lint(tmp_path, "vega_tpu/io/newreader.py", """\
        def parse(s):
            try:
                return int(s)
            except Exception:
                return None
        """, select=["VG005"])
    assert not res.findings


# ---------------------------------------------------------------- VG006
def test_vg006_fires_in_traced_module(tmp_path):
    res = _lint(tmp_path, "vega_tpu/tpu/kernels.py", """\
        import jax.numpy as jnp

        def shard_op(col, count):
            n = int(jnp.sum(col))
            hits = jnp.nonzero(col)[0]
            return col.max().item(), n, hits
        """, select=["VG006"])
    assert _rules(res) == ["VG006", "VG006", "VG006"]


def test_vg006_fires_on_fn_passed_to_shard_program(tmp_path):
    res = _lint(tmp_path, "vega_tpu/tpu/newrdd.py", """\
        import jax.numpy as jnp

        def plan(mesh):
            def step(col, count):
                return jnp.unique(col)

            return _shard_program(mesh, step, 2, None)
        """, select=["VG006"])
    assert _rules(res) == ["VG006"]


def test_vg006_silent_on_static_size_and_host_code(tmp_path):
    res = _lint(tmp_path, "vega_tpu/tpu/kernels.py", """\
        import jax.numpy as jnp

        def shard_op(col, capacity):
            hits = jnp.nonzero(col, size=capacity, fill_value=0)[0]
            return hits

        def shard_op2(col, n):
            for _ in range(max(1, int(n).bit_length())):
                col = col * 2
            return col
        """, select=["VG006"])
    assert not res.findings
    # host-side driver code in a non-traced function: .item() is fine
    host = _lint(tmp_path, "vega_tpu/tpu/newrdd.py", """\
        import numpy as np

        def collect_scalar(partials):
            return np.asarray(partials).sum().item()
        """, select=["VG006"])
    assert not host.findings


# ---------------------------------------------------------------- VG007
def test_vg007_fires_on_shared_pool_submit_then_wait(tmp_path):
    res = _lint(tmp_path, "vega_tpu/scheduler/newsched.py", """\
        class Backend:
            def run_sync(self, task):
                fut = self._pool.submit(task.run)
                return fut.result()
        """, select=["VG007"])
    assert _rules(res) == ["VG007"]


def test_vg007_silent_on_local_pool_or_timeout(tmp_path):
    res = _lint(tmp_path, "vega_tpu/scheduler/newsched.py", """\
        from concurrent.futures import ThreadPoolExecutor

        def run_batch(tasks):
            with ThreadPoolExecutor(2) as tp:
                futs = [tp.submit(t) for t in tasks]
                return [f.result() for f in futs]

        class Backend:
            def run_bounded(self, task, conf):
                fut = self._pool.submit(task.run)
                return fut.result(timeout=conf.poll_timeout_s)
        """, select=["VG007"])
    assert not res.findings


# ---------------------------------------------------------------- VG008
def test_vg008_fires_on_direct_scheduler_entry(tmp_path):
    res = _lint(tmp_path, "vega_tpu/tpu/newplane.py", """\
        def run_now(self, rdd, func):
            return self.scheduler.run_job(rdd, func)

        def run_listener(scheduler, rdd, func, parts, cb):
            return scheduler.run_job_with_listener(rdd, func, parts, cb)

        def run_inner(self, rdd, func, parts):
            return self.sched._run_job_inner(rdd, func, parts, None)
        """, select=["VG008"])
    assert _rules(res) == ["VG008", "VG008", "VG008"]
    assert "job server" in res.findings[0].message


def test_vg008_silent_on_context_facade_and_allowed_files(tmp_path):
    # Context.run_job (the facade that DOES route through the job server)
    # stays legal everywhere.
    res = _lint(tmp_path, "vega_tpu/tpu/newplane.py", """\
        def run_via_facade(ctx, rdd, func):
            return ctx.run_job(rdd, func)

        def run_via_context_attr(self, rdd, func):
            return self.context.run_job(rdd, func)
        """, select=["VG008"])
    assert not res.findings
    # The allowed locations themselves: the facade, the rdd actions, and
    # the job server may touch the scheduler entries directly.
    for allowed in ("vega_tpu/context.py", "vega_tpu/rdd/newact.py",
                    "vega_tpu/scheduler/jobserver.py"):
        res = _lint(tmp_path, allowed, """\
            def drive(self, rdd, func, parts, job):
                return self.scheduler._run_job_inner(rdd, func, parts,
                                                     None, job=job)
            """, select=["VG008"])
        assert not res.findings, allowed


# ------------------------------------------------------------- pragmas
def test_pragma_suppresses_with_justification(tmp_path):
    res = _lint(tmp_path, "vega_tpu/newmod.py", """\
        import jax

        # vegalint: ignore[VG002] — init happens under the bench watchdog
        N = len(jax.devices())
        """)
    assert not res.findings
    assert [f.rule for f in res.suppressed] == ["VG002"]
    assert "watchdog" in res.suppressed[0].justification


def test_pragma_same_line_and_star(tmp_path):
    res = _lint(tmp_path, "vega_tpu/newmod.py", """\
        import jax

        N = len(jax.devices())  # vegalint: ignore[*] — fixture exercising same-line star
        """)
    assert not res.findings
    assert len(res.suppressed) == 1


def test_pragma_without_justification_is_vg000(tmp_path):
    res = _lint(tmp_path, "vega_tpu/newmod.py", """\
        import jax

        # vegalint: ignore[VG002]
        N = len(jax.devices())
        """)
    assert _rules(res) == ["VG000"]
    assert "justification" in res.findings[0].message
    assert [f.rule for f in res.suppressed] == ["VG002"]


def test_unused_and_unknown_pragmas_are_vg000(tmp_path):
    res = _lint(tmp_path, "vega_tpu/newmod.py", """\
        def fine():
            return 1  # vegalint: ignore[VG001] — nothing fires here

        def typo():
            return 2  # vegalint: ignore[VG999] — no such rule
        """)
    assert _rules(res) == ["VG000", "VG000"]


def test_pragma_in_docstring_is_not_a_pragma(tmp_path):
    res = _lint(tmp_path, "vega_tpu/newmod.py", '''\
        """Docs may say # vegalint: ignore[VG001] without being one."""
        ''')
    assert not res.findings


# ----------------------------------------------------------- reporters
def test_json_reporter_is_machine_readable(tmp_path):
    res = _lint(tmp_path, "vega_tpu/distributed/newsvc.py", """\
        def f(sock):
            try:
                return sock.recv(4)
            except Exception:
                return None
        """, select=["VG005"])
    doc = json.loads(render_json(res))
    assert doc["ok"] is False
    assert doc["by_rule"] == {"VG005": 1}
    (finding,) = doc["findings"]
    assert finding["rule"] == "VG005"
    assert finding["line"] == 4
    assert finding["path"].endswith("newsvc.py")
    assert "vegalint:" in render_text(res)


def test_nonexistent_path_fails_the_gate(tmp_path):
    # a typo'd path must not make the invariant gate pass vacuously
    res = run_lint([str(tmp_path / "no_such_dir")])
    assert res.errors and not res.ok
    txt = tmp_path / "not_python.txt"
    txt.write_text("x")
    res = run_lint([str(txt)])
    assert res.errors and not res.ok


def test_unknown_select_rule_id_raises(tmp_path):
    with pytest.raises(ValueError, match="VG999"):
        run_lint([str(tmp_path)], select=["VG999"])


def test_syntax_error_reported_not_crash(tmp_path):
    p = tmp_path / "vega_tpu" / "broken.py"
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text("def oops(:\n")
    res = run_lint([str(tmp_path)])
    assert res.errors and not res.ok


# -------------------------------------------------- runtime sync witness
@pytest.fixture()
def fresh_witness():
    w = witness()
    saved = (dict(w._edges), list(w.inversions),
             dict(w.roles_observed), list(w.role_violations))
    w._edges.clear()
    w.inversions.clear()
    w.roles_observed.clear()
    w.role_violations.clear()
    yield w
    w._edges.clear()
    w.inversions.clear()
    w.roles_observed.clear()
    w.role_violations.clear()
    w._edges.update(saved[0])
    w.inversions.extend(saved[1])
    w.roles_observed.update(saved[2])
    w.role_violations.extend(saved[3])


def test_witness_records_order_and_raises_on_inversion(fresh_witness):
    a = WitnessLock("test.a")
    b = WitnessLock("test.b")
    with a:
        with b:
            pass
    with pytest.raises(LockOrderError, match="inversion"):
        with b:
            with a:
                pass
    # the swallowed-raise backstop still sees it
    assert fresh_witness.inversions
    with pytest.raises(LockOrderError):
        from vega_tpu.lint.sync_witness import check_clean

        check_clean()


def test_witness_inversion_seen_across_threads(fresh_witness):
    a = WitnessLock("test.a")
    b = WitnessLock("test.b")

    def forward():
        with a:
            with b:
                pass

    t = threading.Thread(target=forward)
    t.start()
    t.join()
    caught = []

    def backward():
        try:
            with b:
                with a:
                    pass
        except LockOrderError as exc:
            caught.append(exc)

    t2 = threading.Thread(target=backward)
    t2.start()
    t2.join()
    assert caught, "inversion across threads must raise"


def test_witness_self_deadlock_and_reentrant(fresh_witness):
    lk = WitnessLock("test.plain")
    with lk:
        with pytest.raises(LockOrderError, match="self-deadlock"):
            lk.acquire()
    rl = WitnessRLock("test.re")
    with rl:
        with rl:
            pass  # recursive acquisition of an RLock is legal


def test_named_lock_plain_unless_enabled(monkeypatch):
    monkeypatch.delenv("VEGA_TPU_DEBUG_SYNC", raising=False)
    assert isinstance(named_lock("test.x"), type(threading.Lock()))
    monkeypatch.setenv("VEGA_TPU_DEBUG_SYNC", "1")
    assert isinstance(named_lock("test.x"), WitnessLock)
    assert isinstance(named_lock("test.x", reentrant=True), WitnessRLock)


def test_repo_sweep_is_clean_and_fast():
    """The acceptance gate, as a test: zero unsuppressed findings over the
    real tree (full index pass + every rule, call graph included), every
    suppression justified, and the CACHED sweep — what scripts/lint.sh
    pays on every run after the first — under 2s (the vegalint v3
    budget: the call graph combines from cached per-file extracts, so
    adding it must not regress the warm path). The first run may be cold
    (rules changed, fresh container) and is asserted for correctness
    only; the timed run must be served almost entirely from the
    mtime-keyed record cache."""
    import os
    import time

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = [os.path.join(root, "vega_tpu"),
             os.path.join(root, "tests"),
             os.path.join(root, "bench.py")]
    res = run_lint(paths)  # warms the cache if rules/files changed
    assert res.ok, "\n".join(f.render() for f in res.findings)
    assert all(f.justification for f in res.suppressed)
    t0 = time.time()
    warm = run_lint(paths)
    elapsed = time.time() - t0
    assert warm.ok
    assert warm.cache_hits == warm.files, \
        f"expected a fully cached sweep, got {warm.cache_hits}/{warm.files}"
    assert elapsed < 2, f"cached lint took {elapsed:.1f}s, budget is 2s"


# ---------------------------------------------------------------- VG009
def test_vg009_fires_on_unmatched_send_and_dead_arm(tmp_path):
    res = _lint(tmp_path, "vega_tpu/distributed/newproto.py", """\
        from vega_tpu.distributed import protocol

        def client(sock):
            protocol.send_msg(sock, "frob", 1)

        def handler(sock):
            msg_type, payload = protocol.recv_msg(sock)
            if msg_type == "defrob":
                protocol.send_msg(sock, "frob_done", None)
        """, select=["VG009"])
    msgs = sorted(f.message for f in res.findings)
    assert _rules(res) == ["VG009"] * 3
    assert any("'frob' is sent but no dispatch arm" in m for m in msgs)
    assert any("'frob_done' is sent but no dispatch arm" in m
               for m in msgs)
    assert any("arm for 'defrob' has no send site" in m for m in msgs)


def test_vg009_silent_on_matched_grammar(tmp_path):
    res = _lint(tmp_path, "vega_tpu/distributed/newproto.py", """\
        from vega_tpu.distributed import protocol

        def client(sock):
            protocol.send_msg(sock, "frob", 1)
            reply_type, _ = protocol.recv_msg(sock)
            if reply_type == "frob_done":
                return True

        def handler(sock):
            msg_type, payload = protocol.recv_msg(sock)
            if msg_type == "frob":
                protocol.send_msg(sock, "frob_done", None)
        """, select=["VG009"])
    assert not res.findings


# ---------------------------------------------------------------- VG010
_VG010_ENV_PY = """\
    import dataclasses

    @dataclasses.dataclass
    class Configuration:
        frob_interval_s: float = 1.0
        safe_knob: int = 3
    """


def test_vg010_fires_on_unpropagated_worker_read_and_typo(tmp_path):
    (tmp_path / "vega_tpu").mkdir(parents=True, exist_ok=True)
    _lint(tmp_path, "vega_tpu/env.py", _VG010_ENV_PY, select=["VG010"])
    _lint(tmp_path, "vega_tpu/distributed/backend.py", """\
        def launch(conf):
            return {"VEGA_TPU_" "SAFE_KNOB": str(conf.safe_knob)}
        """, select=["VG010"])
    res = _lint(tmp_path, "vega_tpu/distributed/worker.py", """\
        import os

        def serve(conf):
            period = conf.frob_interval_s       # read, not propagated
            typo = os.environ.get("VEGA_TPU_" "FROB_INTRVAL_S")
            return period, typo
        """, select=["VG010"])
    msgs = sorted(f.message for f in res.findings)
    assert _rules(res) == ["VG010", "VG010"]
    assert any("Configuration.frob_interval_s" in m
               and "not in backend.py's worker propagation list" in m
               for m in msgs)
    # (typo'd name assembled at runtime so the real-tree sweep does not
    # flag this assert line itself)
    assert any(("VEGA_TPU_FROB_" + "INTRVAL_S") in m
               and "resolves to no Configuration field" in m for m in msgs)


def test_vg010_silent_when_propagated_and_resolvable(tmp_path):
    (tmp_path / "vega_tpu").mkdir(parents=True, exist_ok=True)
    _lint(tmp_path, "vega_tpu/env.py", _VG010_ENV_PY, select=["VG010"])
    _lint(tmp_path, "vega_tpu/distributed/backend.py", """\
        def launch(conf):
            return {
                "VEGA_TPU_" "FROB_INTERVAL_S": str(conf.frob_interval_s),
                "VEGA_TPU_" "SAFE_KNOB": str(conf.safe_knob),
            }
        """, select=["VG010"])
    res = _lint(tmp_path, "vega_tpu/distributed/worker.py", """\
        import os

        def serve(conf):
            period = conf.frob_interval_s
            ok = os.environ.get("VEGA_TPU_" "SAFE_KNOB")
            return period, ok
        """, select=["VG010"])
    assert not res.findings


# ---------------------------------------------------------------- VG011
_VG011_EVENTS_PY = """\
    import dataclasses

    @dataclasses.dataclass
    class Event:
        time: float = 0.0

    @dataclasses.dataclass
    class FrobDone(Event):
        frob_id: int = -1
        wall_s: float = 0.0

    @dataclasses.dataclass
    class FrobLost(Event):
        frob_id: int = -1

    class MetricsListener:
        def on_event(self, event):
            if isinstance(event, FrobDone):
                self.total = getattr(self, "total", 0) + event.wall_s
    """


def test_vg011_fires_on_misspelled_read_and_unaggregated_emit(tmp_path):
    _lint(tmp_path, "vega_tpu/scheduler/events.py", _VG011_EVENTS_PY,
          select=["VG011"])
    res = _lint(tmp_path, "vega_tpu/scheduler/newlistener.py", """\
        from vega_tpu.scheduler.events import FrobDone, FrobLost

        class Watcher:
            def on_event(self, event):
                if isinstance(event, FrobDone):
                    print(event.walls_s)        # misspelled field
                print(event.no_such_field)      # on no event class

        def emit(bus, fid):
            bus.post(FrobLost(frob_id=fid))     # never aggregated
        """, select=["VG011"])
    msgs = sorted(f.message for f in res.findings)
    assert _rules(res) == ["VG011"] * 3
    assert any("event.walls_s" in m and "FrobDone" in m for m in msgs)
    assert any("event.no_such_field" in m and "any event class" in m
               for m in msgs)
    assert any("FrobLost is emitted but MetricsListener never" in m
               for m in msgs)


def test_vg011_silent_on_conforming_listener(tmp_path):
    _lint(tmp_path, "vega_tpu/scheduler/events.py", _VG011_EVENTS_PY,
          select=["VG011"])
    res = _lint(tmp_path, "vega_tpu/scheduler/newlistener.py", """\
        from vega_tpu.scheduler.events import FrobDone

        class Watcher:
            def on_event(self, event):
                if isinstance(event, FrobDone):
                    print(event.frob_id, event.wall_s, event.time)
                print(event.time)

        def emit(bus, fid):
            bus.post(FrobDone(frob_id=fid))     # aggregated
        """, select=["VG011"])
    assert not res.findings


# ---------------------------------------------------------------- VG012
def test_vg012_fires_on_unbounded_socket_ops(tmp_path):
    res = _lint(tmp_path, "vega_tpu/distributed/newio.py", """\
        import socket

        def fetch(sock, fut):
            sock.settimeout(None)
            data = sock.recv(4096)
            peer = socket.create_connection(("h", 1))
            return data, fut.result()
        """, select=["VG012"])
    assert _rules(res) == ["VG012"] * 4


def test_vg012_silent_on_deadlined_ops_and_out_of_scope(tmp_path):
    res = _lint(tmp_path, "vega_tpu/distributed/newio.py", """\
        import socket

        def fetch(sock, fut, deadline_s):
            sock.settimeout(deadline_s)
            peer = socket.create_connection(("h", 1), timeout=deadline_s)
            return fut.result(timeout=deadline_s)
        """, select=["VG012"])
    assert not res.findings
    out = _lint(tmp_path, "vega_tpu/scheduler/newsched2.py", """\
        def wait(fut):
            return fut.result()
        """, select=["VG012"])
    assert not out.findings  # scheduler/ is VG007's turf, not VG012's


# ---------------------------------------------------------------- VG013
def test_vg013_fires_on_materializing_calls_in_frame_planning(tmp_path):
    res = _lint(tmp_path, "vega_tpu/frame/newplanner.py", """\
        def lower(node, rdd):
            rows = rdd.collect()
            blk = node.block()
            counts = blk.counts_np
            return rows, counts
        """, select=["VG013"])
    assert _rules(res) == ["VG013"] * 3  # collect, block, counts_np


def test_vg013_silent_on_lazy_planning_and_in_api(tmp_path):
    # Pure lineage building in planner code: no findings.
    clean = _lint(tmp_path, "vega_tpu/frame/newplanner.py", """\
        def lower(node, exprs):
            staged = node.reduce_by_key(op="add")
            return staged.sort_by_key(ascending=True)
        """, select=["VG013"])
    assert not clean.findings
    # The SAME materializing calls in the action surface (api.py) are
    # the sanctioned route.
    api = _lint(tmp_path, "vega_tpu/frame/api.py", """\
        def collect_columns(compiled):
            return compiled.rdd.collect_arrays()
        """, select=["VG013"])
    assert not api.findings
    # And outside vega_tpu/frame/ the rule has no opinion.
    out = _lint(tmp_path, "vega_tpu/tpu/newthing.py", """\
        def read(rdd):
            return rdd.collect()
        """, select=["VG013"])
    assert not out.findings


def test_vg013_fires_in_real_tree_shape(tmp_path):
    """A materializing call added to the real planner module layout must
    produce exactly one VG013 finding."""
    base = run_lint([str(tmp_path)], select=["VG013"])
    assert not base.findings
    p = tmp_path / "vega_tpu" / "frame" / "planner.py"
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent("""\
        def _lower_device(ctx, plan):
            node = make_source(ctx, plan)
            node.block()  # materializes at plan-build time
            return node
        """))
    res = run_lint([str(tmp_path)], select=["VG013"])
    assert _rules(res) == ["VG013"]


# ---------------------------------------------------------------- VG014
def test_vg014_fires_on_contract_violations(tmp_path):
    # Missing the n_shards==1 passthrough gate.
    res = _lint(tmp_path, "vega_tpu/tpu/newx.py", """\
        def shiny_exchange(cols, count, bucket, n_shards, slot_capacity,
                           out_capacity):
            return cols, count, False
        """, select=["VG014"])
    assert _rules(res) == ["VG014"]
    assert "single-shard gate" in res.findings[0].message
    # Gate present but a return site breaks the triple contract
    # (run_lint sweeps the whole tmp tree, so filter to this fixture).
    res = _lint(tmp_path, "vega_tpu/tpu/newx2.py", """\
        def lossy_exchange(cols, count, bucket, n_shards, slot_capacity,
                           out_capacity):
            if n_shards == 1:
                return passthrough_exchange(cols, count, 4, out_capacity)
            return cols, count
        """, select=["VG014"])
    f2 = [f for f in res.findings if "newx2" in f.path]
    assert [f.rule for f in f2] == ["VG014"]
    assert "3-tuple" in f2[0].message


def test_vg014_silent_on_conforming_and_exempt_shapes(tmp_path):
    # Conforming implementation: gate + triple returns + delegation.
    clean = _lint(tmp_path, "vega_tpu/tpu/newx3.py", """\
        def blocked_exchange(cols, count, bucket, n_shards, slot_capacity,
                             out_capacity, group=1):
            if n_shards == 1:
                return passthrough_exchange(cols, count, 4, out_capacity)
            if group == 1:
                return ring_exchange(cols, count, bucket, n_shards,
                                     slot_capacity, out_capacity)
            return cols, count, False
        """, select=["VG014"])
    assert not clean.findings
    # Exempt: no bucket/n_shards signature (the planner shape), private
    # helpers, and anything outside vega_tpu/tpu/.
    exempt = _lint(tmp_path, "vega_tpu/tpu/newx4.py", """\
        def plan_some_exchange(n_shards, capacity, slot_capacity):
            return capacity

        def _inner_exchange(cols, count, bucket, n_shards):
            return cols
        """, select=["VG014"])
    assert not exempt.findings
    out = _lint(tmp_path, "vega_tpu/other/newx5.py", """\
        def weird_exchange(cols, count, bucket, n_shards):
            return cols
        """, select=["VG014"])
    assert not out.findings


# ---------------------------------------------------------------- VG015
def test_vg015_fires_on_state_mutation_outside_commit_api(tmp_path):
    res = _lint(tmp_path, "vega_tpu/streaming/rogue.py", """\
        from vega_tpu.rdd.checkpoint import CheckpointRDD, CommitLog

        def hack(store, rdd):
            store._state["k"] = 1
            store.last_committed_batch = 7
            log = CommitLog("/tmp/x")
            CheckpointRDD.write(rdd, "/tmp/y")
        """, select=["VG015"])
    assert _rules(res) == ["VG015"] * 4
    msgs = " ".join(f.message for f in res.findings)
    assert "StateStore.apply_batch" in msgs
    assert "CommitLog minted" in msgs
    assert "CheckpointRDD.write" in msgs


def test_vg015_silent_in_state_py_and_outside_streaming(tmp_path):
    # state.py itself IS the commit API — exempt.
    exempt = _lint(tmp_path, "vega_tpu/streaming/state.py", """\
        class StateStore:
            def __init__(self):
                self._state = {}
                self.last_committed_batch = -1
        """, select=["VG015"])
    assert not exempt.findings
    # Reads of state (Load context) and calls into the commit API are fine.
    clean = _lint(tmp_path, "vega_tpu/streaming/ctx2.py", """\
        def tick(store, batch_id, offsets, updates):
            frontier = store.last_committed_batch
            return store.apply_batch(batch_id, offsets, updates)
        """, select=["VG015"])
    assert not clean.findings
    # Outside streaming/ the rule does not apply.
    out = _lint(tmp_path, "vega_tpu/other/free.py", """\
        class Thing:
            def __init__(self):
                self._state = {}
        """, select=["VG015"])
    assert not out.findings


def test_vg012_covers_streaming_receivers(tmp_path):
    # PR 16 extended VG012's directory index into streaming/: raw socket
    # reads in a receiver must carry deadlines.
    res = _lint(tmp_path, "vega_tpu/streaming/badrecv.py", """\
        def pull(sock):
            return sock.recv(4096)
        """, select=["VG012"])
    assert _rules(res) == ["VG012"]


# ---------------------------------------------------------------- VG020
def test_vg020_fires_on_object_dtype_in_device_tier(tmp_path):
    res = _lint(tmp_path, "vega_tpu/tpu/badcol.py", """\
        import numpy as np

        def build(xs, col):
            a = np.array(xs, dtype=object)
            b = np.empty(len(xs), np.object_)
            c = col.astype("O")
            d = np.full((3,), 0, dtype="object")
            ufn = np.frompyfunc(str, 1, 1)
            return a, b, c, d, ufn
        """, select=["VG020"])
    assert _rules(res) == ["VG020"] * 5
    assert "dictionary codes" in res.findings[0].message


def test_vg020_silent_on_clean_dtypes_dict_encoding_and_host_tier(tmp_path):
    clean = _lint(tmp_path, "vega_tpu/tpu/goodcol.py", """\
        import numpy as np

        def build(xs, col):
            a = np.array(xs, dtype=np.int32)
            b = col.astype(np.int64)
            c = np.full((3,), "O")  # fill VALUE, not a dtype
            return a, b, c
        """, select=["VG020"])
    assert not clean.findings
    # dict_encoding.py is the sanctioned host-side consumer of object
    # arrays — exempt; so is anything outside vega_tpu/tpu/.
    exempt = _lint(tmp_path, "vega_tpu/tpu/dict_encoding.py", """\
        import numpy as np

        def normalize(src):
            return src.astype(object)
        """, select=["VG020"])
    assert not exempt.findings
    host = _lint(tmp_path, "vega_tpu/rdd/rows.py", """\
        import numpy as np

        def pivot(rows):
            return np.array(rows, dtype=object)
        """, select=["VG020"])
    assert not host.findings


# ---------------------------- mutation self-tests against the real tree
import os as _os
import shutil as _shutil

_REPO = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))


def _copy_real(tmp_path, *relpaths):
    for rel in relpaths:
        dst = tmp_path / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        _shutil.copy(_os.path.join(_REPO, rel), dst)


def _mutate(tmp_path, rel, old, new, count=1):
    p = tmp_path / rel
    src = p.read_text()
    assert src.count(old) >= count, f"mutation anchor missing in {rel}"
    p.write_text(src.replace(old, new, count))


def test_vg009_mutation_removed_push_merged_arm(tmp_path):
    """Deleting the live push_merged dispatch arm from the real
    shuffle_server must produce exactly one VG009 finding."""
    files = ("vega_tpu/distributed/protocol.py",
             "vega_tpu/distributed/shuffle_server.py")
    _copy_real(tmp_path, *files)
    base = run_lint([str(tmp_path)], select=["VG009"])
    assert not base.findings, [f.render() for f in base.findings]
    src = (tmp_path / files[1]).read_text()
    start = src.index('elif msg_type == "push_merged":')
    end = src.index('elif msg_type == "get_merged":')
    (tmp_path / files[1]).write_text(src[:start] + src[end:])
    res = run_lint([str(tmp_path)], select=["VG009"])
    assert len(res.findings) == 1
    assert "push_merged" in res.findings[0].message
    assert "sent but no dispatch arm" in res.findings[0].message


def test_vg010_mutation_dropped_knob_from_propagation(tmp_path):
    """Dropping fetch_slow_server_s from the real worker propagation list
    must produce exactly one VG010 finding."""
    files = ("vega_tpu/env.py", "vega_tpu/faults.py",
             "vega_tpu/distributed/backend.py",
             "vega_tpu/distributed/worker.py",
             "vega_tpu/distributed/shuffle_server.py",
             "vega_tpu/shuffle/fetcher.py")
    _copy_real(tmp_path, *files)
    base = run_lint([str(tmp_path)], select=["VG010"])
    assert not base.findings, [f.render() for f in base.findings]
    _mutate(tmp_path, "vega_tpu/distributed/backend.py",
            '"VEGA_TPU_FETCH_SLOW_SERVER_S": str(conf.fetch_slow_server_s),',
            "")
    res = run_lint([str(tmp_path)], select=["VG010"])
    assert len(res.findings) == 1
    assert "fetch_slow_server_s" in res.findings[0].message
    assert "not in backend.py's worker propagation list" \
        in res.findings[0].message


def test_vg011_mutation_renamed_event_field_read(tmp_path):
    """Misspelling an event attribute in the real MetricsListener must
    produce exactly one VG011 finding."""
    _copy_real(tmp_path, "vega_tpu/scheduler/events.py")
    base = run_lint([str(tmp_path)], select=["VG011"])
    assert not base.findings, [f.render() for f in base.findings]
    _mutate(tmp_path, "vega_tpu/scheduler/events.py",
            "self.total_task_time_s += event.duration_s",
            "self.total_task_time_s += event.durations")
    res = run_lint([str(tmp_path)], select=["VG011"])
    assert len(res.findings) == 1
    assert "event.durations" in res.findings[0].message
    assert "TaskEnd" in res.findings[0].message


def test_vg012_mutation_stripped_socket_deadline(tmp_path):
    """Replacing the push plane's socket deadline with settimeout(None)
    in the real shuffle_server must produce exactly one VG012 finding."""
    _copy_real(tmp_path, "vega_tpu/distributed/shuffle_server.py")
    base = run_lint([str(tmp_path)], select=["VG012"])
    assert not base.findings, [f.render() for f in base.findings]
    _mutate(tmp_path, "vega_tpu/distributed/shuffle_server.py",
            "sock.settimeout(deadline_s)", "sock.settimeout(None)")
    res = run_lint([str(tmp_path)], select=["VG012"])
    assert len(res.findings) == 1
    assert "settimeout(None)" in res.findings[0].message


# ----------------------------------------------- VG000 staleness upgrade
def test_stale_pragma_reports_orphaned_justification(tmp_path):
    res = _lint(tmp_path, "vega_tpu/newmod.py", """\
        def fine():
            return 1  # vegalint: ignore[VG002] — probe guarded by the bench watchdog
        """)
    assert _rules(res) == ["VG000"]
    msg = res.findings[0].message
    assert "suppresses nothing" in msg
    assert "orphaned justification" in msg
    assert "probe guarded by the bench watchdog" in msg


# ------------------------------------------------------ JSON schema + CLI
def test_json_schema_is_stable_and_carries_pragma_state(tmp_path):
    res = _lint(tmp_path, "vega_tpu/newmod.py", """\
        import jax

        N = len(jax.devices())  # vegalint: ignore[VG002] — fixture: suppressed finding for the schema test
        M = len(jax.local_devices())
        """, select=["VG002"])
    doc = json.loads(render_json(res))
    # Schema 2 (vegalint v3): finding shape unchanged from schema 1; the
    # bump marks the --explain-role document sharing the version number.
    assert doc["schema"] == 2
    assert set(doc) >= {"ok", "files", "findings", "suppressed",
                        "errors", "by_rule", "cache_hits"}
    (finding,) = doc["findings"]
    assert set(finding) >= {"rule", "path", "line", "col", "message",
                            "suppressed", "pragma"}
    assert finding["pragma"] == "none"
    (supp,) = doc["suppressed"]
    assert supp["pragma"] == "justified"
    assert "schema test" in supp["justification"]


def test_cli_json_out_writes_artifact(tmp_path):
    from vega_tpu.lint.__main__ import main

    target = tmp_path / "vega_tpu" / "clean.py"
    target.parent.mkdir(parents=True)
    target.write_text("x = 1\n")
    artifact = tmp_path / "vegalint.json"
    rc = main([str(target), "--output", "json",
               "--json-out", str(artifact), "--no-cache"])
    assert rc == 0
    doc = json.loads(artifact.read_text())
    assert doc["ok"] is True and doc["schema"] == 2


# ------------------------------------------------------------ result cache
def test_result_cache_hits_and_invalidation(tmp_path, monkeypatch):
    monkeypatch.setenv("VEGA_TPU_LINT_CACHE", str(tmp_path / "cache.pkl"))
    target = tmp_path / "vega_tpu" / "mod.py"
    target.parent.mkdir(parents=True)
    target.write_text("import jax\nN = len(jax.devices())\n")
    cold = run_lint([str(target)], select=["VG002"])
    assert _rules(cold) == ["VG002"] and cold.cache_hits == 0
    warm = run_lint([str(target)], select=["VG002"])
    assert _rules(warm) == ["VG002"] and warm.cache_hits == 1
    # same cache serves a different --select subset (records hold every
    # rule's output)
    other = run_lint([str(target)], select=["VG001"])
    assert not other.findings and other.cache_hits == 1
    # a content change invalidates by mtime/size: the finding disappears
    target.write_text("import jax\n\ndef n():\n    return jax.devices()\n")
    fixed = run_lint([str(target)], select=["VG002"])
    assert not fixed.findings and fixed.cache_hits == 0


def test_cache_never_leaks_suppression_state(tmp_path, monkeypatch):
    monkeypatch.setenv("VEGA_TPU_LINT_CACHE", str(tmp_path / "cache.pkl"))
    target = tmp_path / "vega_tpu" / "mod.py"
    target.parent.mkdir(parents=True)
    target.write_text(
        "import jax\n"
        "# vegalint: ignore[VG002] — fixture: cache suppression roundtrip\n"
        "N = len(jax.devices())\n")
    first = run_lint([str(target)])
    second = run_lint([str(target)])
    for res in (first, second):
        assert not res.findings
        assert [f.rule for f in res.suppressed] == ["VG002"]
        assert res.suppressed[0].suppressed is True


# ------------------------------------- VG016–VG019: thread-role dataflow
def test_vg016_fires_through_the_call_graph(tmp_path):
    """A blocking op two call hops below a latency-critical role entry
    fires, with the witness path in the message."""
    res = _lint(tmp_path, "vega_tpu/scheduler/elastic.py", """\
        class ElasticController:
            def _loop(self):
                self._decide()

            def _decide(self):
                self._drain_all()

            def _drain_all(self):
                for t in self.threads:
                    t.join()
        """, select=["VG016"])
    assert _rules(res) == ["VG016"]
    msg = res.findings[0].message
    assert "join() without timeout" in msg
    assert "'elastic'" in msg
    assert "ElasticController._loop" in msg \
        and "ElasticController._drain_all" in msg


def test_vg016_spawn_boundary_ends_the_role(tmp_path):
    """Thread(target=...) offload is the sanctioned escape hatch: the
    blocking op inside the spawned target must NOT inherit the role."""
    res = _lint(tmp_path, "vega_tpu/scheduler/elastic.py", """\
        import threading

        class ElasticController:
            def _loop(self):
                threading.Thread(target=self._kill, daemon=True).start()

            def _kill(self):
                self.proc.wait()
        """, select=["VG016"])
    assert not res.findings


def test_vg016_silent_on_bounded_waits(tmp_path):
    res = _lint(tmp_path, "vega_tpu/scheduler/elastic.py", """\
        class ElasticController:
            def _loop(self):
                self._decide()

            def _decide(self):
                for t in self.threads:
                    t.join(timeout=45.0)
                self.future.result(timeout=10.0)
        """, select=["VG016"])
    assert not res.findings


def test_vg016_unreachable_blocking_op_is_silent(tmp_path):
    """The same blocking op with no path from a critical role stays
    silent — the rule is reachability, not lexical presence."""
    res = _lint(tmp_path, "vega_tpu/scheduler/helpers.py", """\
        def drain_all(threads):
            for t in threads:
                t.join()
        """, select=["VG016"])
    assert not res.findings


def test_vg017_fires_on_driver_handle_capture(tmp_path):
    res = _lint(tmp_path, "vega_tpu/rdd/newop.py", """\
        def bad(rdd, owner):
            sched = owner.scheduler
            return rdd.map(lambda x: (sched, x))
        """, select=["VG017"])
    assert _rules(res) == ["VG017"]
    assert "'sched'" in res.findings[0].message
    assert "driver handle" in res.findings[0].message


def test_vg017_fires_on_env_and_lock_captures(tmp_path):
    res = _lint(tmp_path, "vega_tpu/rdd/newop.py", """\
        import threading

        from vega_tpu.env import Env

        def bad_env(rdd):
            env = Env.get()
            return rdd.filter(lambda x: env is not None)

        def bad_lock(rdd):
            mu = threading.Lock()

            def body(it):
                with mu:
                    yield from it

            return rdd.map_partitions(body)
        """, select=["VG017"])
    assert _rules(res) == ["VG017", "VG017"]
    msgs = " | ".join(f.message for f in res.findings)
    assert "Env singleton" in msgs and "a lock" in msgs


def test_vg017_silent_on_plain_data_captures(tmp_path):
    res = _lint(tmp_path, "vega_tpu/rdd/newop.py", """\
        def good(rdd, n):
            scale = n * 2
            return rdd.map(lambda x: x * scale)
        """, select=["VG017"])
    assert not res.findings


def test_vg018_fires_on_unreleased_socket(tmp_path):
    res = _lint(tmp_path, "vega_tpu/distributed/newio.py", """\
        import socket

        def bad(host, port):
            s = socket.create_connection((host, port), timeout=5.0)
            s.sendall(b"ping")
            s.close()
        """, select=["VG018"])
    assert _rules(res) == ["VG018"]
    assert "'s'" in res.findings[0].message
    assert "try-finally" in res.findings[0].message


def test_vg018_silent_on_released_or_transferred_handles(tmp_path):
    res = _lint(tmp_path, "vega_tpu/distributed/newio.py", """\
        import socket
        from contextlib import closing

        def finally_release(host, port):
            s = socket.create_connection((host, port), timeout=5.0)
            try:
                s.sendall(b"ping")
            finally:
                s.close()

        def closing_release(host, port):
            with closing(socket.create_connection((host, port),
                                                  timeout=5.0)) as s:
                s.sendall(b"ping")

        def ownership_transfer(host, port):
            s = socket.create_connection((host, port), timeout=5.0)
            return s

        def stored_transfer(pool, host, port):
            s = socket.create_connection((host, port), timeout=5.0)
            pool.register(s)
        """, select=["VG018"])
    assert not res.findings


def test_vg018_scoped_to_cross_process_dirs(tmp_path):
    res = _lint(tmp_path, "vega_tpu/rdd/newio.py", """\
        import socket

        def bad(host, port):
            s = socket.create_connection((host, port), timeout=5.0)
            s.sendall(b"ping")
        """, select=["VG018"])
    assert not res.findings


def test_vg019_fires_on_annotated_driver_only_reachable(tmp_path):
    res = _lint(tmp_path, "vega_tpu/distributed/worker.py", """\
        class _TaskHandler:
            def handle(self):
                self._bootstrap()

            def _bootstrap(self):
                reset_mesh()

        # vegalint: role[driver-only]
        def reset_mesh():
            pass
        """, select=["VG019"])
    assert _rules(res) == ["VG019"]
    msg = res.findings[0].message
    assert "'worker-task'" in msg and "role[driver-only] annotation" in msg
    assert "_TaskHandler.handle" in msg


def test_vg019_silent_when_unreachable_from_confined_roles(tmp_path):
    res = _lint(tmp_path, "vega_tpu/distributed/worker.py", """\
        class _TaskHandler:
            def handle(self):
                pass

        # vegalint: role[driver-only]
        def reset_mesh():
            pass

        def driver_entry():
            reset_mesh()
        """, select=["VG019"])
    assert not res.findings


def test_role_map_and_seeds_resolve_against_real_tree():
    """Drift protection: every declared role entry and driver-only seed
    must resolve to a real def in the real tree — a rename would
    otherwise silently stop propagating that role."""
    import os

    from vega_tpu.lint import callgraph
    from vega_tpu.lint.engine import gather_extracts

    root = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    records = gather_extracts([os.path.join(root, "vega_tpu")],
                              "callgraph")
    g = callgraph.build_graph(records)
    missing = []
    for role, spec in callgraph.ROLES.items():
        for entry in spec["entries"]:
            if entry not in g.defs:
                missing.append(f"{role}: {entry}")
    for seed in callgraph.DRIVER_ONLY_SEEDS:
        if seed not in g.defs:
            missing.append(f"driver-only seed: {seed}")
    assert not missing, f"role map entries without a real def: {missing}"
    # The propagation itself must be live: the reaper's sweep helper is
    # one hop below its entry.
    roles, _parent = callgraph.propagate_roles(g)
    assert "reaper" in roles.get(
        "vega_tpu.distributed.backend.DistributedBackend._sweep", ())


# --------------------------------------------- runtime role witness
def test_role_witness_confined_violation(fresh_witness):
    """A confined-role thread reaching a driver-only assert_role fails
    with the call path; the record survives a swallowed raise."""
    from vega_tpu.lint.sync_witness import RoleError

    outcome = []

    def body():
        fresh_witness.note_role("stream-receiver")
        try:
            fresh_witness.check_role(())
        except RoleError as exc:
            outcome.append(str(exc))

    t = threading.Thread(target=body, name="stream-recv-99")
    t.start()
    t.join()
    assert outcome and "stream-receiver" in outcome[0]
    assert fresh_witness.stats()["role_violations"]
    from vega_tpu.lint.sync_witness import check_clean

    with pytest.raises(RoleError):
        check_clean()


def test_role_witness_allowed_and_unconfined_pass(fresh_witness):
    def elastic_body():
        fresh_witness.note_role("elastic")
        fresh_witness.check_role(("elastic",))  # explicitly allowed
        fresh_witness.check_role(())  # unconfined role: always passes

    t = threading.Thread(target=elastic_body, name="elastic-controller")
    t.start()
    t.join()
    # un-noted thread (this one) always passes
    fresh_witness.check_role(())
    assert not fresh_witness.stats()["role_violations"]


def test_role_witness_thread_name_cross_check(fresh_witness):
    """The static map's thread prefix is checked against the OBSERVED
    thread name — a mismatch is a map/runtime disagreement."""
    from vega_tpu.lint.sync_witness import RoleError

    outcome = []

    def body():
        try:
            fresh_witness.note_role("reaper")
        except RoleError as exc:
            outcome.append(str(exc))

    t = threading.Thread(target=body, name="not-the-reaper")
    t.start()
    t.join()
    assert outcome and "disagree" in outcome[0]
    assert fresh_witness.stats()["role_violations"]


def test_role_witness_unknown_role_rejected(fresh_witness):
    from vega_tpu.lint.sync_witness import RoleError

    with pytest.raises(RoleError, match="not in the declared role map"):
        fresh_witness.note_role("no-such-role")


def test_role_witness_noop_when_disabled(monkeypatch):
    from vega_tpu.lint import sync_witness

    monkeypatch.delenv("VEGA_TPU_DEBUG_SYNC", raising=False)
    sync_witness.note_thread_role("no-such-role")  # no-op, no raise
    assert sync_witness.current_role() is None
    sync_witness.assert_role()  # no-op


# ----------------------------------------------- --explain-role / --changed
def test_cli_explain_role_text_and_json(tmp_path, capsys):
    from vega_tpu.lint.__main__ import main

    p = tmp_path / "vega_tpu" / "scheduler" / "elastic.py"
    p.parent.mkdir(parents=True)
    p.write_text(textwrap.dedent("""\
        class ElasticController:
            def _loop(self):
                self._decide()

            def _decide(self):
                pass
        """))
    rc = main([str(tmp_path), "--explain-role",
               "ElasticController._decide", "--no-cache"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "elastic:" in out and "_loop" in out and "_decide" in out
    rc = main([str(tmp_path), "--explain-role",
               "ElasticController._decide", "--output", "json",
               "--no-cache"])
    doc = json.loads(capsys.readouterr().out)
    assert doc["schema"] == 2
    assert doc["query"] == "ElasticController._decide"
    (match,) = doc["matches"]
    assert match["roles"]["elastic"][0].endswith("._loop")
    # no match: usage-style exit code 2
    rc = main([str(tmp_path), "--explain-role", "nope", "--no-cache"])
    capsys.readouterr()
    assert rc == 2


def test_cli_changed_mode(tmp_path, monkeypatch, capsys):
    """--changed: instant pass when nothing moved; narrow per-file run
    for a non-graph change; full-sweep fallback when vega_tpu/ changed."""
    import time as _time

    from vega_tpu.lint.__main__ import main

    monkeypatch.setenv("VEGA_TPU_LINT_CACHE", str(tmp_path / "cache.pkl"))
    mod = tmp_path / "tree" / "vega_tpu" / "mod.py"
    mod.parent.mkdir(parents=True)
    mod.write_text("x = 1\n")
    t = tmp_path / "tree" / "tests" / "test_mod.py"
    t.parent.mkdir(parents=True)
    t.write_text("y = 2\n")
    paths = [str(tmp_path / "tree")]
    # no stamp yet: --changed falls back to the full sweep
    assert main(paths + ["--changed"]) == 0
    assert '"files": 0' not in capsys.readouterr().out
    # the clean full sweep armed the stamp; nothing changed -> 0 files
    assert main(paths + ["--changed"]) == 0
    assert "0 file(s)" in capsys.readouterr().out
    # a test-file change -> narrow run on just that file
    _time.sleep(0.01)
    t.write_text("y = 3\n")
    assert main(paths + ["--changed"]) == 0
    assert "1 file(s)" in capsys.readouterr().out
    # a vega_tpu/ change -> graph inputs moved -> full sweep again
    _time.sleep(0.01)
    mod.write_text("x = 2\n")
    assert main(paths + ["--changed"]) == 0
    assert "2 file(s)" in capsys.readouterr().out


# ------------------------- seeded-defect mutation tests (VG016–VG019)
def test_vg016_mutation_deleted_elastic_join_timeout(tmp_path):
    """Stripping the scale-up join timeout in the real elastic controller
    must produce exactly one VG016 finding on the elastic role."""
    _copy_real(tmp_path, "vega_tpu/scheduler/elastic.py")
    base = run_lint([str(tmp_path)], select=["VG016"])
    assert not base.findings, [f.render() for f in base.findings]
    _mutate(tmp_path, "vega_tpu/scheduler/elastic.py",
            "t.join(timeout=45.0)", "t.join()")
    res = run_lint([str(tmp_path)], select=["VG016"])
    assert len(res.findings) == 1
    msg = res.findings[0].message
    assert "join() without timeout" in msg and "'elastic'" in msg
    assert "_scale_up" in msg


def test_vg017_mutation_captured_scheduler_in_count(tmp_path):
    """Capturing a driver scheduler handle into the real RDD.count
    closure must produce exactly one VG017 finding."""
    _copy_real(tmp_path, "vega_tpu/rdd/base.py")
    base = run_lint([str(tmp_path)], select=["VG017"])
    assert not base.findings, [f.render() for f in base.findings]
    _mutate(tmp_path, "vega_tpu/rdd/base.py",
            "counts = self.map_partitions("
            "lambda it: iter([sum(1 for _ in it)])).collect()",
            "sched = self.context.scheduler\n"
            "        counts = self.map_partitions("
            "lambda it: iter([sum(1 for _ in it) if sched else 0]))"
            ".collect()")
    res = run_lint([str(tmp_path)], select=["VG017"])
    assert len(res.findings) == 1
    assert "'sched'" in res.findings[0].message
    assert "driver handle" in res.findings[0].message


def test_vg018_mutation_leaked_probe_socket(tmp_path):
    """Opening the streaming socket source via a local temp that is
    neither closed nor stored must produce exactly one VG018 finding."""
    _copy_real(tmp_path, "vega_tpu/streaming/source.py")
    base = run_lint([str(tmp_path)], select=["VG018"])
    assert not base.findings, [f.render() for f in base.findings]
    _mutate(tmp_path, "vega_tpu/streaming/source.py",
            "self._sock = socket.create_connection(\n"
            "            (self.host, self.port), timeout=self.timeout_s)\n"
            "        self._sock.settimeout(self.timeout_s)\n"
            "        self._file = self._sock.makefile(\"rb\")",
            "sock = socket.create_connection(\n"
            "            (self.host, self.port), timeout=self.timeout_s)\n"
            "        sock.settimeout(self.timeout_s)\n"
            "        self._file = sock.makefile(\"rb\")")
    res = run_lint([str(tmp_path)], select=["VG018"])
    assert len(res.findings) == 1
    assert "'sock'" in res.findings[0].message


def test_vg019_mutation_env_reset_from_task_handler(tmp_path):
    """Calling Env.reset from the real worker task handler must produce
    exactly one VG019 finding (the worker BOOTSTRAP call in
    Worker.__init__ stays legal — main thread, not a task thread)."""
    _copy_real(tmp_path, "vega_tpu/distributed/worker.py",
               "vega_tpu/env.py")
    base = run_lint([str(tmp_path)], select=["VG019"])
    assert not base.findings, [f.render() for f in base.findings]
    _mutate(tmp_path, "vega_tpu/distributed/worker.py",
            "worker: Worker = self.server.worker"
            "  # type: ignore[attr-defined]",
            "worker: Worker = self.server.worker"
            "  # type: ignore[attr-defined]\n"
            "        Env.reset(worker.conf, is_driver=False)")
    res = run_lint([str(tmp_path)], select=["VG019"])
    assert len(res.findings) == 1
    msg = res.findings[0].message
    assert "Env.reset" in msg and "'worker-task'" in msg
    assert "_TaskHandler.handle" in msg


def test_vg010_mutation_dropped_coding_knob(tmp_path):
    """PR 19 (coded shuffle): dropping the coding_group_k propagation
    entry from the real worker knob dict must produce exactly one VG010
    finding — workers would otherwise group parity members under the
    DEFAULT k while the driver plans recovery under the configured one."""
    files = ("vega_tpu/env.py", "vega_tpu/faults.py",
             "vega_tpu/distributed/backend.py",
             "vega_tpu/distributed/worker.py",
             "vega_tpu/distributed/shuffle_server.py",
             "vega_tpu/shuffle/fetcher.py",
             "vega_tpu/shuffle/coding.py")
    _copy_real(tmp_path, *files)
    base = run_lint([str(tmp_path)], select=["VG010"])
    assert not base.findings, [f.render() for f in base.findings]
    _mutate(tmp_path, "vega_tpu/distributed/backend.py",
            '"VEGA_TPU_CODING_GROUP_K": str(conf.coding_group_k),', "")
    res = run_lint([str(tmp_path)], select=["VG010"])
    assert len(res.findings) == 1
    assert "coding_group_k" in res.findings[0].message
    assert "not in backend.py's worker propagation list" \
        in res.findings[0].message
