"""The per-stage readers (perfbench/stage_ops.py and the metrics that load
it): the join of a window's operations with hand-written stage tables, what
counts as unstaged, `None` where there is nothing to read, a table row's key
against its event's, and the manifest's entries."""

import os
import sys

import pytest

from conftest import BENCH, ROOT, load

STAGE_METRICS = {
    "exchange_send_s_per_action": "exchange_send",
    "exchange_group_s_per_action": "exchange_group",
    "exchange_compact_s_per_action": "exchange_compact",
    "key_sort_s_per_action": "key_sort",
    "segment_reduce_s_per_action": "segment_reduce",
    "merge_join_s_per_action": "merge_join",
}
FOUR = ["agg_join_256m_4chip.batch", "agg_join_256m_zipf_4chip.batch",
        "sort_256m_4chip.batch"]
BATCH = ["agg_join_64m.batch", "sort_64m.batch", "agg_join_64m_zipf.batch"] \
    + FOUR
AGG = [c for c in BATCH if c.startswith("agg_join")]
LISTED = {
    "exchange_send_s_per_action": FOUR,
    "exchange_group_s_per_action": FOUR,
    "exchange_compact_s_per_action": BATCH,
    "key_sort_s_per_action": BATCH,
    "segment_reduce_s_per_action": AGG,
    "merge_join_s_per_action": AGG,
    "device_unstaged_share": BATCH + ["agg_join_64m.scan"],
    "exchange_plan_peak_over_compiled": FOUR,
}


def line(op, shape, opcode, operands, kind=""):
    """An instruction as a profile names its event: each operand's shape
    before its name, no metadata."""
    return (f"%{op} = {shape}{{0:T(1024)}} {opcode}("
            + ", ".join(f"s32[64]{{0:T(1024)}} %{o}" for o in operands) + ")"
            + (f", kind={kind}, calls=%fused_computation.1" if kind else ""))


def row(op, shape, opcode, operands, stage, kind=""):
    key = " ".join(w for w in (op, shape, opcode, kind) if w) \
        + "(" + ",".join(operands) + ")"
    return {"op": op, "shape": shape, "opcode": opcode, "kind": kind,
            "stage": stage, "key": key}


# two programs' tables, as spans.program_stages() gives them
TABLES = {
    "rbk": {"programs": 1, "temp_bytes": 600, "argument_bytes": 100,
            "output_bytes": 200, "ops": [
                row("sort.8", "s32[64]", "sort", ["k.1", "v.1"], "key_sort"),
                row("fusion.3", "f32[64]", "fusion", ["sort.15"],
                    "segment_reduce", "kCustom"),
                row("sort.15", "s32[64]", "sort", ["ids", "v.2"],
                    "segment_reduce"),
                # the two programs' sort.14 differ in their operands
                row("sort.14", "s32[64]", "sort", ["ids", "k.2"],
                    "segment_reduce"),
                row("fusion.1", "s32[64]", "fusion", ["p.1"],
                    "exchange_compact", "kCustom"),
                row("all_to_all.2", "s32[4,1,16]", "all-to-all", ["buf"],
                    "exchange_wire"),
                row("copy.4", "s32[1]", "copy", ["n"], None)]},
    "join": {"programs": 2, "ops": [
        row("sort.8", "s32[64]", "sort", ["k.1", "v.1"], "key_sort"),
        row("sort.14", "s32[64]", "sort", ["idx", "lv"], "exchange_compact"),
        # one name, one input, two stages: nobody can tell
        row("fusion.1", "s32[64]", "fusion", ["p.1"], "merge_join",
            "kCustom"),
        row("fusion.9", "s32[32]", "fusion", ["li"], "merge_join",
            "kCustom")]},
}
# the traced window: one plane, back to back, a while around its body
PLANE = [[line("fusion.3", "f32[64]", "fusion", ["sort.15"], "kCustom"), 1.0, 0.5],
         [line("sort.8", "s32[64]", "sort", ["k.1", "v.1"]), 1.5, 0.4],
         [line("fusion.1", "s32[64]", "fusion", ["p.1"], "kCustom"), 1.9, 0.3],
         [line("sort.15", "s32[64]", "sort", ["ids", "v.2"]), 2.2, 0.2],
         [line("sort.14", "s32[64]", "sort", ["ids", "k.2"]), 2.4, 0.15],
         [line("sort.14", "s32[64]", "sort", ["idx", "lv"]), 2.55, 0.25],
         ["%while.7 = (s32[]{:T(128)}) while((s32[]{:T(128)}) %t), "
          "condition=%c, body=%b", 3.0, 0.12],
         [line("fusion.9", "s32[32]", "fusion", ["li"], "kCustom"), 3.01, 0.1],
         [line("copy.4", "s32[1]", "copy", ["n"]), 3.2, 0.05]]
EVENTS = {"host": [["perfbench:window", 0.0, 10.0]],
          "devices": {"/device:TPU:0": PLANE}}
TOTAL = 0.5 + 0.4 + 0.3 + 0.2 + 0.15 + 0.25 + 0.12 + 0.05


@pytest.fixture()
def stage_ops(monkeypatch):
    monkeypatch.delitem(sys.modules, "perfbench_stage_ops", raising=False)
    return load(os.path.join(BENCH, "stage_ops.py"))


@pytest.fixture()
def spans():
    sys.path.insert(0, ROOT)
    try:
        from vega_tpu.tpu import spans as mod
    finally:
        sys.path.remove(ROOT)
    return mod


def test_self_seconds_are_the_trace_reductions(stage_ops):
    """Self seconds by the whole event name add up to reduce_events' `ops`,
    name for name once shortened, on a hand-made window and on the recorded
    chip trace."""
    import json

    tr = load(os.path.join(BENCH, "trace_reduce.py"))
    with open(os.path.join(BENCH, "tests", "trace_chip_scan.json")) as f:
        recorded = json.load(f)["events"]
    for events in (EVENTS, recorded):
        mine = {}
        for name, sec in stage_ops.self_seconds(events).items():
            mine[tr.short_name(name)] = mine.get(tr.short_name(name), 0.0) + sec
        theirs = {}
        for name, _cat, sec, _runs in tr.reduce_events(events)["ops"]:
            theirs[name] = theirs.get(name, 0.0) + sec
        assert mine == pytest.approx(theirs)
    assert stage_ops.self_seconds({"host": [], "devices": {}}) == {}


def test_join_adds_up(stage_ops, spans):
    seconds = stage_ops.self_seconds(EVENTS)
    joined = stage_ops.join(seconds, TABLES, spans.instruction_key)
    # the while holds fusion.9: its self time is what is left of it
    assert joined["stages"] == {
        "segment_reduce": pytest.approx(0.5 + 0.2 + 0.15),
        "key_sort": pytest.approx(0.4),
        "exchange_compact": pytest.approx(0.25),
        "merge_join": pytest.approx(0.1)}
    # fusion.1 is exchange_compact in one program and merge_join in the
    # other, over the same input; no table has while.7; copy.4 has no scope
    assert [(k.split("(")[0], pytest.approx(s))
            for k, s in joined["unstaged_ops"]] == [
        ("fusion.1 s32[64] fusion kCustom", 0.3),
        ("copy.4 s32[1] copy", 0.05), ("while.7 s32[] while", 0.02)]
    assert joined["unstaged_s"] == pytest.approx(0.37)
    assert sum(joined["stages"].values()) + joined["unstaged_s"] \
        == pytest.approx(joined["total_s"]) == pytest.approx(TOTAL)


def test_a_key_two_programs_stage_differently_is_unstaged(stage_ops):
    by_key = stage_ops.stage_by_key(TABLES)
    assert by_key["fusion.1 s32[64] fusion kCustom(p.1)"] is None
    assert by_key["sort.8 s32[64] sort(k.1,v.1)"] == "key_sort"
    # one name over different operands is two keys
    assert by_key["sort.14 s32[64] sort(ids,k.2)"] == "segment_reduce"
    assert by_key["sort.14 s32[64] sort(idx,lv)"] == "exchange_compact"
    # whichever table comes first
    assert stage_ops.stage_by_key(dict(reversed(TABLES.items()))) == by_key


@pytest.fixture()
def read(stage_ops, spans, monkeypatch):
    """The readers over TABLES in spans.program_stages()'s place."""
    monkeypatch.setattr(spans, "program_stages", lambda: TABLES,
                        raising=False)
    return {name: load(os.path.join(BENCH, "metrics", name + ".py")).read
            for name in LISTED}


def obs_of(events, actions=2):
    return {"actions": actions, "events": events,
            "trace": {"ops": [["x", "other", 1.0, 1]]} if events else None}


def test_readers(read):
    obs = obs_of(EVENTS)
    assert read["segment_reduce_s_per_action"](obs) == pytest.approx(0.425)
    assert read["key_sort_s_per_action"](obs) == pytest.approx(0.2)
    assert read["merge_join_s_per_action"](obs) == pytest.approx(0.05)
    assert read["exchange_compact_s_per_action"](obs) == pytest.approx(0.125)
    assert read["device_unstaged_share"](obs) \
        == pytest.approx(100 * 0.37 / TOTAL)
    # the window ran no operation of these stages: nothing, never 0
    assert read["exchange_send_s_per_action"](obs) is None
    assert read["exchange_group_s_per_action"](obs) is None
    for name in STAGE_METRICS:
        assert read[name](obs_of(None)) is None  # no trace
        assert read[name](obs_of(EVENTS, actions=0)) is None
    assert read["device_unstaged_share"](obs_of(None)) is None
    # one join a run: a second run's operations are joined anew
    other = dict(EVENTS, devices={"/device:TPU:0": PLANE[1:2]})
    assert read["key_sort_s_per_action"](obs_of(other)) \
        == pytest.approx(0.2)
    assert read["device_unstaged_share"](obs_of(other)) == 0.0


def test_a_program_without_stage_tables_gives_nothing(read, spans,
                                                      monkeypatch):
    """The parent commit's spans.py has no program_stages; a tree from
    before PR 28 has no spans.py: None, and nothing raised."""
    monkeypatch.delattr(spans, "program_stages")
    for name in LISTED:
        assert read[name](obs_of(EVENTS)) is None
    import vega_tpu.tpu

    monkeypatch.delattr(vega_tpu.tpu, "spans")
    monkeypatch.setitem(sys.modules, "vega_tpu.tpu.spans", None)
    for name in LISTED:
        assert read[name](obs_of(dict(EVENTS))) is None


def test_plan_peak_over_compiled(read, spans, monkeypatch):
    from vega_tpu.tpu import exchange_plan

    plan = exchange_plan.plan_exchange(
        n_shards=4, capacity=64, slot_capacity=16, out_capacity=64,
        row_bytes=8, budget_bytes=1 << 30, mode="all_to_all")
    monkeypatch.setattr(exchange_plan, "_LAST_PLAN", plan)
    # `rbk` is the one program with an exchange_wire operation and a
    # memory analysis: 600 + 200 bytes
    assert read["exchange_plan_peak_over_compiled"](obs_of(EVENTS)) \
        == plan.est_peak_bytes / 800
    monkeypatch.setattr(exchange_plan, "_LAST_PLAN", None)  # one chip
    assert read["exchange_plan_peak_over_compiled"](obs_of(EVENTS)) is None
    monkeypatch.setattr(exchange_plan, "_LAST_PLAN", plan)
    monkeypatch.setattr(spans, "program_stages", lambda: {
        "rbk": {k: v for k, v in TABLES["rbk"].items()
                if not k.endswith("_bytes")}})  # no memory analysis
    assert read["exchange_plan_peak_over_compiled"](obs_of(EVENTS)) is None


# (a compiled text's line, the name a profile gives the event, the key)
LINES = [
    ("%fusion.62 = s32[8388608]{0:T(1024)} fusion(%p.1, %bitcast.7), "
     "kind=kCustom, calls=%fused_computation.3",
     "%fusion.62 = s32[8388608]{0:T(1024)} fusion(s32[8]{0:T(1024)} %p.1, "
     "s32[8388608,1]{1,0:T(8,128)} %bitcast.7), kind=kCustom, "
     "calls=%fused_computation.3",
     "fusion.62 s32[8388608] fusion kCustom(p.1,bitcast.7)"),
    ("%sort.14 = (s32[67108864]{0:T(1024)}, f32[67108864]{0:T(1024)}) "
     "sort(%a, %lo.1), dimensions={0}, is_stable=true, to_apply=%region_1.2",
     "%sort.14 = (s32[67108864]{0:T(1024)}, f32[67108864]{0:T(1024)}) "
     "sort(s32[67108864]{0:T(1024)} %a, f32[67108864]{0:T(1024)} %lo.1), "
     "dimensions={0}, is_stable=true, to_apply=%region_1.2",
     "sort.14 s32[67108864] sort(a,lo.1)"),
    ("%select_reduce_fusion = s32[]{:T(128)} fusion(%vals.1, %bitcast.2), "
     "kind=kLoop, calls=%fused_computation",
     "%select_reduce_fusion = s32[]{:T(128)} fusion(s32[67108864]{0:T(1024)}"
     " %vals.1, s32[]{:T(128)} %bitcast.2), kind=kLoop, "
     "calls=%fused_computation",
     "select_reduce_fusion s32[] fusion kLoop(vals.1,bitcast.2)"),
    ("%while.4 = (u32[]{:T(128)}, s32[8]{0}) while(%tuple.41), "
     "condition=%wide.cond.1, body=%wide.body.1.sunk",
     "%while.4 = (u32[]{:T(128)}, s32[8]{0}) while((u32[]{:T(128)}, "
     "s32[8]{0}) %tuple.41), condition=%wide.cond.1, "
     "body=%wide.body.1.sunk",
     "while.4 u32[] while(tuple.41)"),
    ("%partition_pos_pallas.1 = s32[524288,128]{1,0:T(8,128)} custom-call("
     "%s, %b), custom_call_target=\"tpu_custom_call\"",
     "%partition_pos_pallas.1 = s32[524288,128]{1,0:T(8,128)} custom-call("
     "s32[1,128]{1,0} %s, s32[524288,128]{1,0} %b), "
     "custom_call_target=\"tpu_custom_call\"",
     "partition_pos_pallas.1 s32[524288,128] custom-call(s,b)"),
    ("%iota.6 = s32[67108864]{0:T(1024)} iota(), iota_dimension=0",
     "%iota.6 = s32[67108864]{0:T(1024)} iota(), iota_dimension=0",
     "iota.6 s32[67108864] iota()"),
]


@pytest.mark.parametrize("text, event, key", LINES)
def test_a_table_row_and_its_event_have_one_key(spans, text, event, key):
    """spans.parse_stages keys a compiled program's line as
    spans.instruction_key keys the profile's event of that instruction
    (the same text, less its metadata, each operand's shape before its
    name); the first three words are trace_reduce.short_name's."""
    tr = load(os.path.join(BENCH, "trace_reduce.py"))
    hlo = ("ENTRY %main.1 (p: s32[8]) -> s32[8] {\n  " + text
           + ', metadata={op_name="jit(f)/vega.key_sort/x" kind=odd}, '
           'backend_config={"a":"(b)"}\n}\n')
    (parsed,) = spans.parse_stages(hlo)
    assert parsed["key"] == spans.instruction_key(event) == key
    assert key.split("(")[0] == tr.short_name(event)
    assert parsed["stage"] == "key_sort"
    assert spans.instruction_key("ThunkExecutor::Execute") \
        == "ThunkExecutor::Execute"


@pytest.mark.parametrize("name", sorted(LISTED))
def test_manifest_lists(manifest, name):
    cells = {w["name"] for w in manifest["workloads"]}
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert not [c for c in LISTED[name] if c not in entry["workloads"]]
    assert not [c for c in entry["workloads"] if c not in cells]
    assert entry["moves"] == "rows_per_s_chip" and entry["better"] == "lower"
    if name == "exchange_plan_peak_over_compiled":
        assert (entry["source"], entry["layer"], entry["unit"]) == (
            "program_counter", "plan and schedule", "ratio")
    else:
        assert (entry["source"], entry["layer"]) == ("device_trace",
                                                     "shard programs")
    assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))
