"""Host spans and counters of the dense tier, on the jax profiler's switch.

`with span("fetch"): ...` costs one `TraceAnnotation.is_enabled()` while no
profiler session runs, and records nothing. While one runs — whoever started
it: `ctx.profiler(dir)` or a bare `jax.profiler.start_trace` — the span is a
`jax.profiler.TraceAnnotation("vega:<name> <kind>")`, so it lies in the profile
on the clock the device planes share, and it adds to a process-wide session
tally that `session()` (and `ctx.metrics_summary()["dense_spans"]`) returns:

    {name: {"count", "seconds", "bytes", "by_kind": {kind: {...}}}}

The tally restarts when a span first sees a session it had not seen before,
and stops growing when the session ends: after `stop_trace` it holds what ran
under that session. Two sessions with no span between them read as one,
unless `new_session()` is called (as `ctx.profiler` does).

Spans are FLAT: none opens inside another on the same thread, so their
seconds add up without counting anything twice and a profile reader that
names an idle gap by the span covering it has one candidate. `nested()`
counts the entries that broke this; a tier-1 test holds it at 0. There is no
span around a whole action, `_materialize` or `_run_exchange` for that reason.

The span names: `launch <kind>` (host dispatch of one shard program), `fetch`
(one blocking device->host round trip), `put` (host->device), `decode` (shard
slicing, concatenation, the int64 and dictionary decodes of a fetched block),
`pivot` (columns -> Python row objects), `fingerprint` (pickling a closure
for a program-cache key).

Counters: `count(name, n=1)` adds `n` to a count-only entry of the same tally
(seconds and bytes stay 0; no annotation, nothing to nest). `exchange` (one a
call of `_run_exchange`), `exchange_round` (one a launch of its program: an
overflow launches again with grown capacities) and `exchange_repair` (one a
block `_settle_pending` rebuilt after a speculative launch overflowed);
`exchange_rows` and `exchange_slots` (once a launch has succeeded, for each
side that moved and whose row count the host already held: the rows it put
into the exchange over all shards, and the `n_shards x out_cap` receive slots
the program held for it; their quotient is how full the exchange ran);
`exchange_plan_rounds` (once a launch across shards, the rounds of the plan
`_resolve_exchange` gave it: 1 the one-shot `all_to_all`, `ceil((n - 1) /
group)` for `staged`, `n - 1` for `ring`, so a traced window says which
collective program ran; one shard plans nothing and adds nothing).

Programs are rare (2-3 a run), so `programs()` is always recorded:
{kind: {"mints", "first_call_s"}}, the second the host seconds of each minted
program's first call (trace, lower, compile or persistent-cache load,
dispatch).

Importing this module does not import jax (`ctx.metrics_summary()` reads the
tallies for host-only jobs too): the first span binds the profiler's switch.
"""

import threading
import time

_lock = threading.Lock()
_depth = threading.local()  # .n: spans open on this thread
_live = False  # a span has seen the profiler session that is running
_nested = 0
_session: dict = {}
_programs: dict = {}


def _enabled() -> bool:
    """`TraceAnnotation.is_enabled`, which takes this name's place at the
    first call."""
    global _enabled, TraceAnnotation
    from jax.profiler import TraceAnnotation

    _enabled = TraceAnnotation.is_enabled
    return _enabled()


def _add(acc: dict, seconds: float, nbytes: int) -> None:
    acc["count"] += 1
    acc["seconds"] += seconds
    acc["bytes"] += nbytes


def _zero() -> dict:
    return {"count": 0, "seconds": 0.0, "bytes": 0}


def _entry(name: str) -> dict:
    """The session tally's entry `name`, made if absent; under `_lock`."""
    acc = _session.get(name)
    if acc is None:
        acc = _session[name] = dict(_zero(), by_kind={})
    return acc


class span:
    """`with span(name, kind) as sp:`; `sp.on` says whether a session is
    recording, and `sp.nbytes` may be set before the block ends (on a hot
    path, count it under `if sp.on`)."""

    __slots__ = ("name", "kind", "nbytes", "on", "_ann", "_t0")

    def __init__(self, name: str, kind: str = "", nbytes: int = 0):
        self.name = name
        self.kind = kind
        self.nbytes = nbytes
        self.on = False

    def __enter__(self):
        global _live, _nested
        if not _enabled():
            if _live:
                _live = False
            return self
        self.on = True
        depth = getattr(_depth, "n", 0)
        _depth.n = depth + 1
        if depth or not _live:
            with _lock:
                if depth:
                    _nested += 1
                if not _live:
                    _session.clear()
                    _live = True
        self._ann = TraceAnnotation(
            f"vega:{self.name} {self.kind}" if self.kind
            else "vega:" + self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if not self.on:
            return False
        seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        _depth.n -= 1
        with _lock:
            acc = _entry(self.name)
            _add(acc, seconds, self.nbytes)
            if self.kind:
                _add(acc["by_kind"].setdefault(self.kind, _zero()),
                     seconds, self.nbytes)
        return False


def count(name: str, n: int = 1) -> None:
    """Add `n` to the count-only entry `name` of the session tally; off, one
    `_enabled()` check and nothing recorded."""
    global _live
    if not _enabled():
        if _live:
            _live = False
        return
    with _lock:
        if not _live:
            _session.clear()
            _live = True
        _entry(name)["count"] += n


def new_session() -> None:
    """The next span under a profiler session starts an empty tally."""
    global _live
    with _lock:
        _live = False


def session() -> dict:
    """A copy of the tally of the newest profiler session."""
    with _lock:
        return {name: dict(acc, by_kind={k: dict(v) for k, v in
                                         acc["by_kind"].items()})
                for name, acc in _session.items()}


def nested() -> int:
    """Spans that opened inside another on their thread, since process
    start: 0, or the spans are not flat."""
    return _nested


def program_minted(kind: str) -> None:
    with _lock:
        _programs.setdefault(kind, {"mints": 0, "first_call_s": 0.0})[
            "mints"] += 1


def program_first_call(kind: str, seconds: float) -> None:
    with _lock:
        _programs[kind]["first_call_s"] += seconds


def programs() -> dict:
    """A copy of {kind: {"mints", "first_call_s"}} since process start."""
    with _lock:
        return {kind: dict(p) for kind, p in _programs.items()}


def program_mints() -> int:
    with _lock:
        return sum(p["mints"] for p in _programs.values())
