"""The dense tier's host spans (vega_tpu/tpu/spans.py): off they record
nothing; under a jax profiler session they tally launches, fetches, decodes
and pivots by name, never nest, and start over with each session."""

import re
import threading

import numpy as np
import pytest

from test_dense_zipf import on_devices  # noqa: F401


@pytest.fixture()
def dctx():
    import vega_tpu as v

    context = v.Context("local", num_workers=2)
    yield context
    context.stop()


def _ind(v):
    return (v >= 3).astype("int32")


N, KEYS = 6000, 50


def _sources(ctx):
    keys = (np.arange(N, dtype=np.int64) * 7) % KEYS
    vals = (np.arange(N, dtype=np.float64) * 3) % 11
    return (ctx.dense_from_numpy(keys, vals),
            ctx.dense_from_numpy(np.arange(KEYS, dtype=np.int64),
                                 np.arange(KEYS, dtype=np.float64)))


LINEAGES = {
    "scan": lambda p, t: p.map_values(_ind).values_dense().sum(),
    "reduce_join_collect":
        lambda p, t: sorted(p.reduce_by_key(op="add").join(t).collect()),
    "collect_pairs": lambda p, t: sorted(p.collect()),
    "collect_values": lambda p, t: sorted(p.values_dense().collect()),
    "group_by_key_collect":
        lambda p, t: sorted((k, sorted(vs))
                            for k, vs in p.group_by_key().collect()),
    "cogroup_collect":
        lambda p, t: sorted((k, (sorted(a), sorted(b)))
                            for k, (a, b) in p.cogroup(t).collect()),
    "sort_collect_arrays":
        lambda p, t: [c.tolist() for c in
                      p.sort_by_key().collect_arrays().values()],
    "take_ordered": lambda p, t: p.take_ordered(7),
    "top_values": lambda p, t: p.values_dense().top(5),
}


@pytest.mark.parametrize("name", ["scan", "reduce_join_collect"])
def test_off_records_nothing(dctx, name):
    from vega_tpu.tpu import spans

    pairs, table = _sources(dctx)
    before = spans.session()
    LINEAGES[name](pairs, table)
    with spans.span("fetch", nbytes=8) as sp:
        assert sp.on is False
    assert spans.session() == before
    assert spans.nested() == 0


def test_on_tallies_a_scan(dctx, session):
    from vega_tpu.tpu import dense_rdd, spans

    pairs, table = _sources(dctx)
    expected = LINEAGES["scan"](pairs, table)  # warm: mints two programs
    mints = dense_rdd.program_mints()
    with session:
        assert LINEAGES["scan"](pairs, table) == expected
    tally = spans.session()
    assert tally["launch"]["count"] == 2
    assert {k: v["count"] for k, v in tally["launch"]["by_kind"].items()} \
        == {"narrow": 1, "named_reduce": 1}
    assert tally["fetch"]["count"] == 1 and tally["fetch"]["bytes"] > 0
    assert tally["launch"]["seconds"] > 0 and tally["fetch"]["seconds"] > 0
    assert "decode" not in tally and "pivot" not in tally
    # wrapping a program at mint mints nothing more
    assert dense_rdd.program_mints() == mints
    # once the session has ended the tally stops growing
    LINEAGES["scan"](pairs, table)
    assert spans.session() == tally


def test_on_tallies_decode_and_pivot(dctx, session):
    from vega_tpu.tpu import spans

    pairs, table = _sources(dctx)
    with session:
        rows = pairs.reduce_by_key(op="add").join(table).collect()
    tally = spans.session()
    assert len(rows) == KEYS
    assert tally["decode"]["seconds"] > 0 and tally["decode"]["bytes"] > 0
    assert tally["pivot"]["seconds"] > 0 and tally["pivot"]["count"] == 1
    assert sorted(tally["pivot"]) == ["by_kind", "bytes", "count", "seconds"]
    assert tally["fetch"]["count"] >= 1 and tally["launch"]["count"] >= 2


def test_collect_arrays_is_one_fetch_across_shards(dctx, on_devices, session):
    """to_numpy reads every shard's own buffer of every column in ONE
    host_get: a 4-shard block is one `fetch` span (one blocking round trip)
    of the block's whole bytes, and one `decode` of its valid rows."""
    from vega_tpu.tpu import spans

    on_devices(4)
    keys = (np.arange(N, dtype=np.int64) * 2654435761) % (2**40)
    vals = np.arange(N, dtype=np.float64)
    pairs = dctx.dense_from_numpy(keys, vals)
    blk = pairs.block()
    assert blk.n_shards == 4 and sorted(blk.cols) == ["k", "k.lo", "v"]
    with session:
        cols = pairs.collect_arrays()
    assert np.array_equal(cols["k"], keys)
    assert np.array_equal(cols["v"], vals.astype(np.float32))
    tally = spans.session()
    assert tally["fetch"]["count"] == 1
    assert tally["fetch"]["bytes"] == blk.nbytes
    assert tally["decode"]["count"] == 1
    assert tally["decode"]["bytes"] == N * (8 + 4)
    assert "launch" not in tally and spans.nested() == 0


@pytest.mark.parametrize("name", sorted(LINEAGES))
def test_spans_are_flat_and_change_no_result(dctx, session, name):
    from vega_tpu.tpu import spans

    pairs, table = _sources(dctx)
    expected = LINEAGES[name](pairs, table)
    with session:
        got = LINEAGES[name](pairs, table)
    assert got == expected
    assert spans.nested() == 0
    tally = spans.session()
    assert tally["fetch"]["count"] >= 1  # a source's collect launches none
    spanned = sum(acc["seconds"] for acc in tally.values())
    assert 0 < spanned < 60


def test_a_nested_span_is_counted(session):
    """The counter the flatness tests hold at 0 does count."""
    from vega_tpu.tpu import spans

    before = spans.nested()
    try:
        with session:
            with spans.span("decode"):
                with spans.span("pivot"):
                    pass
        assert spans.nested() == before + 1
    finally:
        spans._nested = before


def test_depth_is_per_thread(session):
    from vega_tpu.tpu import spans

    inside, release = threading.Event(), threading.Event()

    def hold():
        with spans.span("decode"):
            inside.set()
            release.wait(10)

    spans.new_session()  # the last test's session may have had no span after it
    with session:
        t = threading.Thread(target=hold)
        t.start()
        assert inside.wait(10)
        with spans.span("pivot"):  # another thread's span is open
            pass
        release.set()
        t.join(10)
    assert spans.nested() == 0
    assert spans.session()["decode"]["count"] == 1
    assert spans.session()["pivot"]["count"] == 1


@pytest.mark.parametrize("how", ["start_trace", "ctx.profiler"])
def test_a_second_session_starts_empty(dctx, session, tmp_path, how):
    from vega_tpu.tpu import spans

    pairs, table = _sources(dctx)
    LINEAGES["reduce_join_collect"](pairs, table)
    LINEAGES["scan"](pairs, table)
    if how == "start_trace":
        with session:
            LINEAGES["reduce_join_collect"](pairs, table)
        assert "pivot" in spans.session()
        LINEAGES["scan"](pairs, table)  # a span sees that no session runs
        with session:
            LINEAGES["scan"](pairs, table)
    else:  # back to back, no span between: profiler() marks the new one
        with dctx.profiler(str(tmp_path / "a")):
            LINEAGES["reduce_join_collect"](pairs, table)
        assert "pivot" in spans.session()
        with dctx.profiler(str(tmp_path / "b")):
            LINEAGES["scan"](pairs, table)
    tally = spans.session()
    assert sorted(tally) == ["fetch", "fingerprint", "launch"]
    assert tally["launch"]["count"] == 2 and tally["fetch"]["count"] == 1


def test_programs_table(dctx):
    from vega_tpu.tpu import dense_rdd, spans

    pairs, _table = _sources(dctx)
    before = spans.programs()
    salt = dense_rdd.program_mints() + 12345

    def fresh(v):  # a closure no other test has fingerprinted
        return v + salt

    pairs.map_values(fresh).values_dense().sum()
    after = spans.programs()
    assert after["narrow"]["mints"] == before.get(
        "narrow", {"mints": 0})["mints"] + 1
    assert after["narrow"]["first_call_s"] > before.get(
        "narrow", {"first_call_s": 0.0})["first_call_s"]
    assert all(p["mints"] >= 1 and p["first_call_s"] > 0
               for p in after.values())
    assert dense_rdd.program_mints() == sum(
        p["mints"] for p in after.values()) == salt - 12345 + 1 + (
            after["named_reduce"]["mints"]
            - before.get("named_reduce", {"mints": 0})["mints"])


def test_first_call_is_timed_once_under_threads():
    """Two task threads that both make a minted program's first call: one
    of them is timed, and each call reaches the program."""
    from vega_tpu.tpu import dense_rdd, spans

    kind = "test_first_call_once"
    inside, release = threading.Event(), threading.Event()
    calls = []

    def prog(x):
        calls.append(x)
        inside.set()
        release.wait(10)
        return x

    launch = dense_rdd._spanned_program(kind, prog)
    spans.program_minted(kind)
    try:
        t = threading.Thread(target=launch, args=(1,))
        t.start()
        assert inside.wait(10)  # the first call is in flight
        threading.Timer(0.05, release.set).start()
        assert launch(2) == 2  # untimed: the first is taken
        t.join(10)
        assert sorted(calls) == [1, 2]
        first_call_s = spans.programs()[kind]["first_call_s"]
        assert first_call_s > 0
        launch(3)
        assert spans.programs()[kind]["first_call_s"] == first_call_s
        # a program that cannot be lowered again: counted, no rows, no error
        table = spans.program_stages()[kind]
        assert (table["programs"], table["ops"]) == (1, [])
    finally:
        spans._programs.pop(kind)
        spans._stage_tables.pop(kind)


def test_host_only_summary_imports_no_jax():
    """`metrics_summary()["dense_spans"]` of a job that never touched the
    dense tier is empty, and reading it does not import jax."""
    import subprocess
    import sys

    code = (
        "import sys, vega_tpu as v\n"
        "with v.Context('local', num_workers=2) as ctx:\n"
        "    assert ctx.parallelize(range(10), 2).map(lambda x: x + 1)"
        ".collect()[-1] == 10\n"
        "    dense = ctx.metrics_summary()['dense_spans']\n"
        "assert dense == {'session': {}, 'programs': {},"
        " 'program_stages': {}}, dense\n"
        "assert 'vega_tpu.tpu.spans' in sys.modules\n"
        "assert 'jax' not in sys.modules\n")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


def test_metrics_summary_has_all_three(dctx, session):
    from vega_tpu.tpu import spans

    pairs, table = _sources(dctx)
    with session:
        LINEAGES["scan"](pairs, table)
    dense = dctx.metrics_summary()["dense_spans"]
    assert dense == {"session": spans.session(),
                     "programs": spans.programs(),
                     "program_stages": spans.program_stages()}
    assert "narrow" in {row["stage"] for row in
                        dense["program_stages"]["narrow"]["ops"]}
    assert dense["session"]["launch"]["count"] == 2
    assert dense["programs"]["narrow"]["mints"] >= 1
    # copies: an edit does not reach the tally
    dense["session"]["launch"]["count"] = 99
    assert spans.session()["launch"]["count"] == 2


def test_exchange_stage_duration_is_monotonic(dctx):
    """StageCompleted.duration_s of a dense exchange comes from
    time.perf_counter: never negative, and within the action's wall."""
    import time

    from vega_tpu.scheduler import events as ev

    seen = []

    class Listener(ev.Listener):
        def on_event(self, event):
            if isinstance(event, ev.StageCompleted):
                seen.append(event.duration_s)

    dctx.bus.add_listener(Listener())
    pairs, _table = _sources(dctx)
    t0 = time.perf_counter()
    pairs.reduce_by_key(op="add").collect()
    wall = time.perf_counter() - t0
    dctx.bus.flush()
    assert seen and all(0 <= d <= wall for d in seen)


@pytest.mark.parametrize("devices, exchange, rounds", [
    (4, None, 1),  # the planner's one-shot all_to_all
    (4, "ring", 3),  # the mesh's n - 1
    (8, "ring", 7),
    (4, "staged", 1),  # forced: the largest group that fits, one round here
    (1, None, None),  # one shard plans nothing and counts nothing
])
def test_exchange_plan_rounds_an_action(dctx, on_devices, session, devices,
                                        exchange, rounds):
    """`exchange_plan_rounds` adds the resolved plan's rounds once a launch:
    a sort's action reads which collective program ran; cold (sized by the
    histogram) and warm (hinted, the launch deferred) alike."""
    from vega_tpu.tpu import spans

    on_devices(devices)
    keys = (np.arange(N, dtype=np.int64) * 2654435761) % (2**40)
    pairs = dctx.dense_from_numpy(keys, np.arange(N, dtype=np.float64))
    for _run in ("cold", "warm"):
        spans.new_session()
        with session:
            node = pairs.sort_by_key(exchange=exchange)
            cols = node.collect_arrays()
        assert np.array_equal(cols["k"], np.sort(keys))
        tally = spans.session()
        assert tally["exchange"]["count"] == 1
        if rounds is None:
            assert "exchange_plan_rounds" not in tally
            assert node._exchange_plan is None
        else:
            assert tally["exchange_plan_rounds"] == {
                "count": rounds, "seconds": 0.0, "bytes": 0, "by_kind": {}}
            assert node._exchange_plan.rounds == rounds
        assert spans.nested() == 0
    # off, the counter records nothing
    before = spans.session()
    pairs.sort_by_key(exchange=exchange).collect_arrays()
    assert spans.session() == before


# ---- stages: the device side by the program's own names --------------------

HLO = """\
HloModule jit_prog_fn.5ef7b579, entry_computation_layout={(s32[8]{0})->s32[8]{0}}

%fused_computation (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  ROOT %add.1 = s32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(f)/vega.narrow/add"}
}

%region_0.1 (a: s32[], b: s32[]) -> pred[] {
  %a = s32[] parameter(0)
  %b = s32[] parameter(1)
  ROOT %lt = pred[] compare(%a, %b), direction=LT, metadata={op_name="jit(f)/vega.key_sort/sort"}
}

%body (p: (s32[8])) -> (s32[8]) {
  %p = (s32[8]{0}) parameter(0)
  %gte = s32[8]{0} get-tuple-element(%p), index=0
  %copy.3 = s32[8]{0:T(1024)} copy(%gte), metadata={op_name="jit(f)/while/body/vega.exchange_send/copy"}
  ROOT %tuple.2 = (s32[8]{0}) tuple(%copy.3)
}

ENTRY %main.9 (x: s32[8]) -> s32[8] {
  %x = s32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion.1 = s32[8]{0:T(1024)} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/vega.segment_reduce/vega.key_sort/add" stack_frame_id=3}, backend_config={"kind":"x"}
  %sort.2 = (s32[8]{0}, s32[8]{0}) sort(%fusion.1, %x), dimensions={0}, is_stable=true, to_apply=%region_0.1, metadata={op_name="jit(f)/vega.exchange_compact/scatter"}
  %while.4 = (s32[8]{0}) while(%sort.2), condition=%cond, body=%body
  %bitcast.7 = s32[8]{0} bitcast(%x), metadata={op_name="jit(f)/vega.not_a_stage/reshape"}
  ROOT %copy.5 = s32[8]{0} copy(%bitcast.7), metadata={op_name="jit(f)/reshape"}
}
"""


def test_parse_stages_reads_a_compiled_text():
    """The innermost `vega.` scope is the stage; fusion bodies and applied
    comparators are left out, a while's body is not; parameters and tuples
    are no steps; a scope STAGES does not list is no stage; the four words
    are what trace_reduce.short_name keeps of the same line."""
    from vega_tpu.tpu import spans

    rows = {row["op"]: row for row in spans.parse_stages(HLO)}
    assert sorted(rows) == ["bitcast.7", "copy.3", "copy.5", "fusion.1",
                            "sort.2", "while.4"]
    assert rows["fusion.1"] == {"op": "fusion.1", "shape": "s32[8]",
                                "opcode": "fusion", "kind": "kLoop",
                                "stage": "key_sort",
                                "key": "fusion.1 s32[8] fusion kLoop(x)"}
    assert rows["sort.2"] == {"op": "sort.2", "shape": "s32[8]",
                              "opcode": "sort", "kind": "",
                              "stage": "exchange_compact",
                              "key": "sort.2 s32[8] sort(fusion.1,x)"}
    assert rows["copy.3"]["stage"] == "exchange_send"
    # a profile names the event by the same text, operand shapes included
    assert spans.instruction_key(
        "%sort.2 = (s32[8]{0:T(1024)}, s32[8]{0:T(1024)}) sort("
        "s32[8]{0:T(1024)} %fusion.1, s32[8]{0:T(1024)} %x), dimensions={0}"
    ) == rows["sort.2"]["key"]
    assert [rows[op]["stage"] for op in ("while.4", "bitcast.7", "copy.5")] \
        == [None, None, None]


def test_an_unlisted_stage_is_refused():
    from vega_tpu.tpu import spans

    with pytest.raises(ValueError, match="no stage 'shuffle'"):
        spans.stage("shuffle")
    with pytest.raises(ValueError):
        spans.stage("vega.key_sort")
    assert isinstance(spans.stage_placement(), str) \
        and len(spans.stage_placement()) == 8


@pytest.fixture()
def fresh_programs(monkeypatch):
    """Every program of the test is minted anew and the stage tables hold
    those programs alone."""
    from vega_tpu.tpu import dense_rdd, spans

    monkeypatch.setattr(dense_rdd, "_PROGRAM_CACHE", {})
    monkeypatch.setattr(spans, "_lowered", {})
    monkeypatch.setattr(spans, "_stage_tables", {})
    return spans


class _Compiles:
    """Counts the compiles asked of jax while it is `on`."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.on, self.count = False, 0
        monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, _secs, **_kw):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


PIPELINES = {
    # devices, the stages its programs must name
    "reduce_join": (4, {"key_sort", "segment_reduce", "merge_join",
                        "exchange_group", "exchange_send", "exchange_wire",
                        "exchange_compact"}),
    "sort_take_ordered": (4, {"sample", "exchange_group", "exchange_send",
                              "exchange_wire", "exchange_compact",
                              "key_sort", "topk"}),
    "filter_count": (4, {"narrow", "named_reduce"}),
    # no "exchange_compact": a one-shard passthrough moves no row, and the
    # select that is all of it may fuse into its consumer
    "one_shard": (1, {"key_sort", "segment_reduce", "merge_join", "topk"}),
    "ring": (4, {"exchange_group", "exchange_send", "exchange_wire",
                 "exchange_compact", "key_sort"}),
}


def _run_pipeline(name, pairs, table):
    if name in ("reduce_join", "one_shard"):
        out = sorted(pairs.reduce_by_key(op="add").join(table).collect())
        assert len(out) == KEYS
    if name in ("sort_take_ordered", "one_shard"):
        cols = pairs.sort_by_key().collect_arrays()
        assert np.array_equal(cols["k"], np.sort(cols["k"]))
        assert pairs.take_ordered(5) == sorted(pairs.collect())[:5]
    if name == "filter_count":
        assert pairs.filter(lambda kv: kv[1] >= 3).count() > 0
        assert LINEAGES["scan"](pairs, table) > 0
    if name == "ring":
        cols = pairs.sort_by_key(exchange="ring").collect_arrays()
        assert np.array_equal(cols["k"], np.sort(cols["k"]))


@pytest.mark.parametrize("name", sorted(PIPELINES))
def test_stage_tables_of_a_pipeline(dctx, on_devices, fresh_programs, name):
    """Every stage a pipeline reaches is named in some minted program's
    table, no instruction has a stage outside STAGES, and asking for the
    tables compiles nothing and mints nothing."""
    from vega_tpu.tpu import dense_rdd

    spans = fresh_programs
    devices, reached = PIPELINES[name]
    on_devices(devices)
    pairs, table = _sources(dctx)
    before = spans.programs()
    _run_pipeline(name, pairs, table)
    minted = {kind for kind, p in spans.programs().items()
              if p["mints"] > before.get(kind, {"mints": 0})["mints"]}
    mints = dense_rdd.program_mints()
    compiles = _Compiles()
    compiles.on = True
    tables = spans.program_stages()
    again = spans.program_stages()
    compiles.on = False
    assert compiles.count == 0
    assert dense_rdd.program_mints() == mints
    assert again == tables
    assert set(tables) == minted  # every minted program left its lowering
    seen = {row["stage"] for t in tables.values() for row in t["ops"]}
    assert reached <= seen, reached - seen
    assert seen - {None} <= set(spans.STAGES)
    for kind, t in tables.items():
        assert t["programs"] >= 1 and t["ops"] and t["parse_s"] > 0, kind
        assert {"temp_bytes", "argument_bytes", "output_bytes"} <= set(t)
        assert all(sorted(row) == ["key", "kind", "op", "opcode", "shape",
                                   "stage"] for row in t["ops"])
        assert len({row["key"] for row in t["ops"]}) == len(t["ops"])
    assert spans.nested() == 0
    assert dctx.metrics_summary()["dense_spans"]["program_stages"] == tables


def _opcodes_by_stage(ops):
    by_stage = {}
    for row in ops:
        by_stage.setdefault(row["stage"], set()).add(row["opcode"])
    return by_stage


def test_a_scatter_and_its_compaction_keep_their_stage(dctx, on_devices,
                                                       fresh_programs):
    """The segment reduce's key compaction is the segment reduce's, the
    received rows' is the exchange's: `compact` itself carries no scope."""
    on_devices(4)
    pairs, _table = _sources(dctx)
    pairs.reduce_by_key(op="add").collect()
    by_stage = _opcodes_by_stage(fresh_programs.program_stages()["rbk"]["ops"])
    assert "sort" in by_stage["key_sort"]
    assert by_stage["segment_reduce"] and by_stage["exchange_compact"]


def test_no_scatter_where_no_row_moves(dctx, on_devices, fresh_programs):
    """On one shard every exchange is the passthrough: a slice or a pad and
    a select a column. Nothing under `exchange_compact` scatters or sorts
    (the chip runs a 64Mi-slot scatter as a sort and a kCustom fusion), in
    the compiled tables and, since the CPU's compiler hides a scatter inside
    a loop fusion, in what was traced."""
    on_devices(1)
    pairs, table = _sources(dctx)
    assert len(pairs.reduce_by_key(op="add").join(table).collect()) == KEYS
    cols = pairs.sort_by_key().collect_arrays()
    assert np.array_equal(cols["k"], np.sort(cols["k"]))
    spans = fresh_programs
    traced = {kind: list(lows) for kind, lows in spans._lowered.items()}
    tables = spans.program_stages()
    for kind in ("rbk", "join", "sort"):
        moved = [row["key"] for row in tables[kind]["ops"]
                 if row["stage"] == "exchange_compact"
                 and (row["opcode"] in ("scatter", "sort")
                      or row["kind"] == "kCustom")]
        assert moved == [], (kind, moved)
        assert traced[kind], kind
        for lowered in traced[kind]:
            names = re.findall(r"vega\.exchange_compact/([\w()]+)",
                               lowered.as_text(debug_info=True))
            assert names, kind  # the passthrough was traced, under its stage
            assert not [n for n in names
                        if re.search("scatter|sort|cumsum", n)], (kind, names)


def test_only_the_first_call_lowers_again():
    """The first call of a minted program leaves its lowering; later calls
    take the plain path (the `first` lock is spent). A `lower` that raises
    leaves an empty table and no error."""
    from vega_tpu.tpu import dense_rdd, spans

    lowered = []

    class Prog:
        def __init__(self, fail):
            self.fail = fail

        def __call__(self, x):
            return x + 1

        def lower(self, *args):
            lowered.append(args)
            if self.fail:
                raise RuntimeError("cannot lower")
            return "a lowering that cannot be compiled"

    for kind, fail in (("test_lowers_once", False), ("test_lower_fails", True)):
        launch = dense_rdd._spanned_program(kind, Prog(fail))
        spans.program_minted(kind)
        try:
            assert [launch(1), launch(2), launch(3)] == [2, 3, 4]
            assert lowered == [(1,)]
            table = spans.program_stages()[kind]
            assert (table["programs"], table["ops"]) == (1, [])
        finally:
            lowered.clear()
            spans._programs.pop(kind)
            spans._stage_tables.pop(kind)


def test_put_counts_the_bytes_of_a_value_without_nbytes(dctx, session):
    from vega_tpu.tpu import mesh as mesh_lib
    from vega_tpu.tpu import spans

    repl = mesh_lib.replicated_spec(mesh_lib.default_mesh())
    spans.new_session()
    with session:
        mesh_lib.host_put([1, 2, 3], repl)
        mesh_lib.host_put(np.arange(5, dtype=np.int32), repl)
        mesh_lib.host_put(np.zeros(0, np.int32), repl)
    put = spans.session()["put"]
    assert put["count"] == 3
    assert put["bytes"] == np.asarray([1, 2, 3]).nbytes + 20
