"""Device seconds by the program's own stages: the join of a traced window's
device operations with the stage tables the program keeps of its minted
shard programs (`vega_tpu.tpu.spans.program_stages()`: for each program the
instructions of its compiled text, each with the `vega.<stage>` scope its
metadata carries and the key `spans.instruction_key` gives its line).

A profile names a device operation by its instruction's text, so the program's
own `instruction_key` gives an event the key of its table row: instruction
name, result shape, opcode, a fusion's kind and the names of its operands
(`trace_reduce.short_name`'s four words are not enough: two programs of one
run both have a `sort.14 s32[67108864] sort`, over different inputs). An
operation is *staged* where every table row of its key names one and the
same stage. It is *unstaged* where no table has it, where its row has no
scope, or where two programs' tables still give the key different stages
(the profile does not say which program ran an operation, and the reader
does not guess). Seconds are the operations' self seconds over the traced
window, averaged over the device planes: `obs["events"]` reduced the way
`trace_reduce.reduce_events` reduces it to `obs["trace"]["ops"]`, before
that shortens the names (`self_seconds`; a test holds the two sums equal).
Staged and unstaged seconds add up to all the self seconds of the window.

The per-stage readers in metrics/ load this file by path and share one join
a run. A program without `program_stages` (a tree from before the stages)
gives nothing, never 0. What the join found goes to standard error, with
the seconds reading the tables took and the compiles jax was asked for
meanwhile (none: a table is read off the executable the run already built).
"""

import importlib.util
import os
import sys
import time


def _trace_reduce():
    """perfbench/trace_reduce.py, loaded by path as run.py loads it."""
    name = "perfbench_trace_reduce"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "trace_reduce.py"))
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


def self_seconds(events: dict) -> dict:
    """{event name: self seconds in the traced window, averaged over the
    device planes}: what reduce_events sums into `ops`, by the whole name."""
    tr = _trace_reduce()
    windows = [h for h in events["host"] if h[0] == tr.SPAN_WINDOW]
    if not windows or not events["devices"]:
        return {}
    w0 = min(h[1] for h in windows)
    w1 = max(h[1] + h[2] for h in windows)
    seconds = {}
    for plane in events["devices"].values():
        clipped = [[n, max(s, w0), min(s + d, w1) - max(s, w0)]
                   for n, s, d in plane if s + d > w0 and s < w1]
        for name, sec in tr._self_times(clipped).items():
            seconds[name] = seconds.get(name, 0.0) \
                + sec / len(events["devices"])
    return seconds


def stage_by_key(tables: dict) -> dict:
    """{key: stage} over every program's table; None where a row has no
    stage or two rows of one key disagree."""
    found = {}
    for table in tables.values():
        for row in table["ops"]:
            if found.setdefault(row["key"], row["stage"]) != row["stage"]:
                found[row["key"]] = None
    return found


def join(seconds: dict, tables: dict, key_of) -> dict:
    """{"stages": {stage: self seconds}, "unstaged_s", "total_s",
    "unstaged_ops": [[key, seconds], ...] longest first} for the window's
    operations `seconds` ({event name: self seconds}), `key_of` the
    program's instruction_key."""
    stage_of = stage_by_key(tables)
    stages, unstaged = {}, []
    for name, sec in seconds.items():
        key = key_of(name)
        stage = stage_of.get(key)
        if stage is None:
            unstaged.append([key, sec])
        else:
            stages[stage] = stages.get(stage, 0.0) + sec
    unstaged.sort(key=lambda op: -op[1])
    return {"stages": stages,
            "unstaged_s": sum(s for _k, s in unstaged),
            "total_s": sum(seconds.values()),
            "unstaged_ops": unstaged}


def program_tables():
    """(spans.program_stages(), spans.instruction_key) of the program under
    test, or None where it has none. Says on standard error what asking
    cost."""
    try:
        from vega_tpu.tpu import spans
        import jax.monitoring as monitoring
    except ImportError:
        return None
    if not hasattr(spans, "program_stages"):
        return None
    compiles = []

    def on_duration(event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    monitoring.register_event_duration_secs_listener(on_duration)
    t = time.perf_counter()
    tables = spans.program_stages()
    print(f"[perfbench stage_ops] stage tables of {len(tables)} kinds, "
          f"{sum(len(t_['ops']) for t_ in tables.values())} instructions, "
          f"read in {sum(t_.get('parse_s', 0.0) for t_ in tables.values()):.4f}s"
          f" when first asked for; this ask {time.perf_counter() - t:.4f}s, "
          f"compiles asked of jax meanwhile: {len(compiles)}",
          file=sys.stderr, flush=True)
    return tables, spans.instruction_key


_last = {"events": None, "join": None}  # the newest run's join, shared


def of_run(obs: dict):
    """The join for this run's traced window, or None where there is no
    trace or no table."""
    events = obs.get("events")
    if not obs.get("trace") or not events:
        return None
    if _last["events"] is not events:
        found = program_tables()
        t = time.perf_counter()
        joined = join(self_seconds(events), *found) if found else None
        _last.update(events=events, join=joined)
        if joined:
            by_time = sorted(joined["stages"].items(), key=lambda kv: -kv[1])
            print(f"[perfbench stage_ops] reduced and joined in "
                  f"{time.perf_counter() - t:.4f}s: "
                  + ", ".join(f"{k} {v:.6f}s" for k, v in by_time)
                  + f"; unstaged {joined['unstaged_s']:.6f}s of "
                  f"{joined['total_s']:.6f}s, the longest "
                  f"{joined['unstaged_ops'][:12]}",
                  file=sys.stderr, flush=True)
    return _last["join"]


def seconds_per_action(obs: dict, stage: str):
    """Self seconds of `stage` per completed action of the traced window;
    None where the window ran no operation of it."""
    joined = of_run(obs)
    if not joined or not obs.get("actions"):
        return None
    seconds = joined["stages"].get(stage, 0.0)
    return seconds / obs["actions"] if seconds > 0 else None


def unstaged_share(obs: dict):
    """100 x unstaged self seconds / all self seconds of the window."""
    joined = of_run(obs)
    if not joined or not joined["total_s"]:
        return None
    return 100.0 * joined["unstaged_s"] / joined["total_s"]
