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
(one blocking device->host round trip), `put` (host->device), `decode` (each shard's
valid rows written once into a result column allocated at its full length,
an int64's two words joined in that pass, the dictionary decode of a fetched
block), `pivot` (columns -> Python row objects), `fingerprint` (pickling a closure
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

Stages name the device side. `with stage("key_sort"): ...` inside a shard
program's traced function is `jax.named_scope("vega.key_sort")`: it exists
while jax traces and costs a call nothing; every instruction of the compiled
program then carries the scope in its `op_name` metadata, which is what XProf's
op view shows and what `program_stages()` reads back. STAGES is the closed list;
the innermost `vega.` scope of an instruction is its stage:

  narrow            a fused narrow chain's body (`dense_rdd._apply_chain`)
  named_reduce      `kernels.masked_reduce`
  key_sort          `kernels.sort_carrying` (so `sort_by_column`,
                    `bucket_key_sort`, the join's sorts) and `topk_rows`' sort
  segment_reduce    `segment_reduce_named`, `segment_reduce_sorted` (their
                    compaction and `_segment_totals_blocked` included)
  merge_join        `merge_ranks`, `ragged_expand`, `merge_join_expand`
  exchange_group    rows to buckets: `hash32`, `hash32_pair`, `range_bucket`
                    (`searchsorted2`), `_group_by_bucket`, `pregrouped_group`,
                    the Pallas bucket kernels, the sizing histograms
  exchange_send     the send buffers of `bucket_exchange` (`slot_rows`, the
                    take through them, the zeroing of empty slots) and
                    `ring.staged_exchange`'s `take_slot`
  exchange_wire     the `lax.all_to_all`s and `lax.ppermute`s
  exchange_compact  the received rows' `compact` (`bucket_exchange`), the
                    staged program's `append_round`, and the single-shard
                    or elided `passthrough_exchange` (a slice or pad and a
                    select: it may fuse into its consumer and leave nothing)
  topk              the selection of `take_ordered` / `top` (`lax.top_k`, the
                    slice of the sorted rows)
  sample            `sort_by_key`'s strided key sample (`sortsamp`)

`program_stages()` is the table: for every minted program the instructions of
its compiled text, each with the stage its metadata names. The first call of a
minted program leaves `prog.lower(*args)` here (`program_lowered`); the table
is parsed from `lowered.compile().as_text()` when first asked for, which jax's
own caches serve without compiling anything, and kept.

Importing this module does not import jax (`ctx.metrics_summary()` reads the
tallies for host-only jobs too): the first span binds the profiler's switch.
"""

import contextlib
import re
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


# ---------------------------------------------------------------------------
# stages: the device side, by the program's own names
# ---------------------------------------------------------------------------

STAGES = ("narrow", "named_reduce", "key_sort", "segment_reduce",
          "merge_join", "exchange_group", "exchange_send", "exchange_wire",
          "exchange_compact", "topk", "sample")

_STAGE_OF = re.compile(r"vega\.([a-z_]+)")
_INSTRUCTION = re.compile(r"\s+(?:ROOT )?%?([\w.\-]+) = (.*)")
_COMPUTATION = re.compile(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_CALLED = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
# operations the device never runs as a step of their own
_NO_STEP = ("parameter", "constant", "get-tuple-element", "tuple")

_stages_lock = threading.Lock()
_lowered: dict = {}  # kind -> [Lowered or None, a minted program each]
_stage_tables: dict = {}  # kind -> the parsed table, see program_stages()


class stage(contextlib.ContextDecorator):
    """`with stage(name):` around the code of one stage inside a traced
    shard program, or `@stage(name)` on a kernel that is one stage:
    `jax.named_scope("vega." + name)`, a new one each time it is entered
    (a decorated kernel may be traced on two threads at once). A name that
    STAGES does not list is refused, so the table's stages stay a closed
    list."""

    __slots__ = ("name", "_scope")

    def __init__(self, name: str):
        if name not in STAGES:
            raise ValueError(f"no stage {name!r}: spans.STAGES has {STAGES}")
        self.name = name

    def _recreate_cm(self):
        return stage(self.name)

    def __enter__(self):
        from jax import named_scope

        self._scope = named_scope("vega." + self.name)
        self._scope.__enter__()
        return self

    def __exit__(self, *exc):
        return self._scope.__exit__(*exc)


_ENTERS = re.compile(r'^\s*(with |@)(?:spans\.)?stage\("([a-z_]+)"\)|'
                     r"^\s*def (\w+)\(", re.M)
_placement = None


def stage_placement() -> str:
    """Eight hex digits over where this package's source enters which stage:
    file by file, each `with stage(..)` with the `def` it stands in and each
    `@stage(..)` with the `def` it stands on. `dense_rdd._shard_program`
    puts it into the name of every shard program, so that it is part of the
    program's key in jax's persistent compile cache. That key leaves
    metadata out, and an executable found there carries the scopes of
    whichever trace first compiled it: a tree that moved, added or renamed a
    scope would read its stage table off the older tree's executable. With
    the placement in the name it compiles its own, once."""
    global _placement
    if _placement is None:
        import hashlib
        import os

        here = os.path.dirname(os.path.abspath(__file__))
        found = []
        try:
            names = sorted(n for n in os.listdir(here) if n.endswith(".py"))
        except OSError:  # no source tree to read: one name for all
            names = []
        for name in names:
            with open(os.path.join(here, name), encoding="utf-8") as f:
                text = f.read()
            inside, pending = "", []
            for how, entered, defined in _ENTERS.findall(text):
                if defined:
                    found += [(name, defined, st) for st in pending]
                    inside, pending = defined, []
                elif how == "@":
                    pending.append(entered)
                else:
                    found.append((name, inside, entered))
        _placement = hashlib.sha1(repr(found).encode()).hexdigest()[:8]
    return _placement


def program_lowered(kind: str, lowered) -> None:
    """Keep what a minted program's first call lowered (`prog.lower(*args)`,
    or None where it could not be lowered again) for program_stages()."""
    with _lock:
        _lowered.setdefault(kind, []).append(lowered)


def forget_lowered() -> None:
    """Drop the lowerings not parsed yet (each keeps its executable alive):
    for whoever frees every compiled program, as the test suite does between
    modules. Their programs then read as an empty table."""
    with _lock:
        _lowered.clear()


def _fields(rest: str) -> tuple:
    """(result shape, opcode, fusion kind, operand names) of an instruction,
    from the text after its ` = `: `dtype[dims]` of the result (a tuple's
    first), the opcode, `kind=` of a fusion ("" otherwise) and the names in
    the opcode's parentheses. A profile names a device operation by the same
    text, its metadata left out and each operand's shape put before its
    name, so both give the same four."""
    rest = rest.split(", metadata={", 1)[0].split(", backend_config=", 1)[0]
    code = _OPCODE.search(" " + rest)
    operands = ()
    if code:
        depth, start = 0, code.end() - 2  # the opcode's own parenthesis
        for at in range(start, len(rest)):
            depth += {"(": 1, ")": -1}.get(rest[at], 0)
            if depth == 0:
                operands = tuple(_OPERAND.findall(rest[start:at]))
                break
    return (rest.split("{", 1)[0].split(" ", 1)[0].lstrip("("),
            code.group(1) if code else "",
            rest.split("kind=", 1)[1].split(",", 1)[0]
            if "kind=" in rest else "",
            operands)


def instruction_key(text: str) -> str:
    """`name shape opcode kind(operand, ...)` of one HLO instruction, given
    as its line in a compiled program's text or as the name a device
    profile gives its event: what a profile's reader joins the two by. The
    operands make it a program's own: two programs may both have a
    `fusion.3 f32[67108864] fusion kCustom`, but not over the same inputs.
    A name that is no instruction is its own key."""
    found = _INSTRUCTION.match(" " + text.strip())
    if not found:
        return text
    return _key(found.group(1), *_fields(found.group(2)))


def _key(op: str, shape: str, code: str, kind: str, operands: tuple) -> str:
    return " ".join(w for w in (op, shape, code, kind) if w) \
        + "(" + ",".join(operands) + ")"


def parse_stages(hlo_text: str) -> list:
    """The instructions of a compiled program's text as [{"op", "shape",
    "opcode", "kind", "stage", "key"}, ...]: name, result `dtype[dims]` (a
    tuple's first), opcode, a fusion's kind ("" otherwise), the innermost
    `vega.<stage>` scope of its `op_name` (None where there is none, or one
    STAGES does not list), and instruction_key() of the line. The bodies of
    fusions and the reducers and comparators an instruction applies are left
    out: the device runs the instruction that calls them, under its own
    name; a fusion carries its root's metadata."""
    lines = hlo_text.splitlines()
    inner = set()
    for line in lines:
        if " fusion(" in line or "to_apply=" in line:
            inner.update(_CALLED.findall(line.split(", metadata={", 1)[0]))
    rows, skip = [], False
    for line in lines:
        if not line.startswith(" "):
            head = _COMPUTATION.match(line)
            if head:
                skip = head.group(1) in inner
            continue
        found = None if skip else _INSTRUCTION.match(line)
        if not found:
            continue
        op, rest = found.groups()
        shape, code, kind, operands = _fields(rest)
        if code in _NO_STEP:
            continue
        meta = rest.partition(", metadata={")[2]
        name = meta.split('op_name="', 1)[1].split('"', 1)[0] \
            if 'op_name="' in meta else ""
        scopes = _STAGE_OF.findall(name)
        rows.append({
            "op": op, "shape": shape, "opcode": code, "kind": kind,
            "stage": scopes[-1] if scopes and scopes[-1] in STAGES else None,
            "key": _key(op, shape, code, kind, operands),
        })
    return rows


def _table_of(lowered) -> dict:
    """One minted program's {"ops", and the compiler's "temp_bytes",
    "argument_bytes", "output_bytes" where the backend gives them}. Nothing
    here compiles: `lowered` is the lowering the program's first call made,
    and its `compile()` hands back the executable that call built."""
    try:
        compiled = lowered.compile()
        table = {"ops": parse_stages(compiled.as_text())}
    except Exception:  # noqa: BLE001 — no table is no error
        return {"ops": []}
    try:
        mem = compiled.memory_analysis()
        table.update(temp_bytes=int(mem.temp_size_in_bytes),
                     argument_bytes=int(mem.argument_size_in_bytes),
                     output_bytes=int(mem.output_size_in_bytes))
    except Exception:  # noqa: BLE001 — a backend without the analysis
        pass
    return table


def program_stages() -> dict:
    """{kind: {"programs": n, "parse_s", "ops": [{"op", "shape", "opcode",
    "kind", "stage", "key"}, ...], "temp_bytes", "argument_bytes",
    "output_bytes"}}:
    for each kind of minted program the instructions of its compiled text
    with their stage (parse_stages), over every program of the kind whose
    first call has returned (`programs` of them; a row two of them share is
    listed once), the host seconds reading them took, and the compiler's
    memory analysis of the newest that has one (absent where the backend
    gives none). Built when first asked for and then kept; asking compiles
    nothing and mints nothing. A program whose lowering could not be kept
    adds no rows."""
    with _stages_lock:
        with _lock:
            fresh = dict(_lowered)
            _lowered.clear()
        for kind, lows in fresh.items():
            acc = _stage_tables.setdefault(
                kind, {"programs": 0, "parse_s": 0.0, "ops": []})
            seen = {(row["key"], row["stage"]) for row in acc["ops"]}
            for lowered in lows:
                t0 = time.perf_counter()
                table = _table_of(lowered)  # of None: no rows
                acc["parse_s"] += time.perf_counter() - t0
                acc["programs"] += 1
                for row in table.pop("ops"):
                    if (row["key"], row["stage"]) not in seen:
                        seen.add((row["key"], row["stage"]))
                        acc["ops"].append(row)
                acc.update(table)
        return {kind: dict(acc, ops=list(acc["ops"]))
                for kind, acc in _stage_tables.items()}
