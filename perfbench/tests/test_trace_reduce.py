"""The reduction from trace events to busy time, top operations and idle
gaps, on a small recorded list of events kept beside this file."""

import json
import os

from conftest import BENCH, HERE, load


def test_known_trace():
    tr = load(os.path.join(BENCH, "trace_reduce.py"))
    with open(os.path.join(HERE, "trace_small.json")) as f:
        rec = json.load(f)
    got = tr.reduce_events(rec["events"])
    want = rec["expect"]
    assert abs(got["window_s"] - want["window_s"]) < 1e-9
    assert abs(got["busy_s"] - want["busy_s"]) < 1e-9
    assert got["device_ops"][0][0] == want["top_op"]
    assert abs(got["device_ops"][0][1] - want["top_op_s"]) < 1e-9
    # every operation, not the ten longest: name, category, self seconds, runs
    assert got["ops"] == [["sort.5", "sort", 3.0, 1], ["while.2", "while", 1.0, 1],
                          ["fusion.9", "other fusion", 1.0, 1],
                          ["copy.1", "copy", 0.5, 1]]
    assert got["idle_gaps"][0][0].startswith(want["longest_gap_during"])
    assert abs(got["idle_gaps"][0][1] - want["gap_s"]) < 1e-9
    assert "2 gaps, the longest 3.000000 s" in got["idle_gaps"][0][0]
    assert got["idle_gaps"][1] == [
        "perfbench:build lineage > python (1 gaps, the longest 0.500000 s)", 0.5]


def test_recorded_chip_trace():
    """The first 20 ms of a traced scan window from the chip: busy time
    against a count on a 100 ns grid, the top operation and the gaps."""
    tr = load(os.path.join(BENCH, "trace_reduce.py"))
    with open(os.path.join(HERE, "trace_chip_scan.json")) as f:
        rec = json.load(f)
    got = tr.reduce_events(rec["events"])
    assert abs(got["window_s"] - rec["expect_window_s"]) < 1e-9
    assert abs(got["busy_s"] - rec["expect_busy_grid_s"]) < 2e-6
    assert got["device_ops"][0][0].startswith(rec["expect_top_op_prefix"])
    assert got["idle_gaps"][0][0].startswith(
        "perfbench:action call > np.asarray(jax.Array)")
    idle = sum(sec for _what, sec in got["idle_gaps"])
    assert abs(idle - (got["window_s"] - got["busy_s"])) < 1e-5


def test_nothing_to_read_gives_nothing():
    tr = load(os.path.join(BENCH, "trace_reduce.py"))
    assert tr.reduce_events({"devices": {}, "host": []}) is None
    assert tr.reduce_events({"devices": {"/device:TPU:0": []},
                             "host": [["perfbench:window", 0.0, 1.0]]}) is None


def test_categories():
    tr = load(os.path.join(BENCH, "trace_reduce.py"))
    hlo = ("%fusion.62 = s32[8388608]{0:T(1024)} fusion(s32[8388608]{0:T(1024)S(1)} "
           "%get-tuple-element.271, s32[8388608]{0:T(1024)} %fusion.61), "
           "kind=kCustom, calls=%fused_computation.3.clone")
    assert tr.short_name(hlo) == "fusion.62 s32[8388608] fusion kCustom"
    assert tr.category(hlo) == "gather-scatter fusion"
    sort = ("%sort.12 = (s32[67108864]{0:T(1024)}, f32[67108864]{0:T(1024)}) "
            "sort(s32[67108864]{0:T(1024)} %fusion.1, f32[67108864]{0:T(1024)} %c), "
            "dimensions={0}, is_stable=true")
    assert tr.short_name(sort) == "sort.12 s32[67108864] sort"
    assert tr.category(sort) == "sort"
    assert tr.category("%compare_convert_fusion = s32[64]{0} fusion(f32[64]{0} %a), "
                       "kind=kLoop, calls=%fc") == "other fusion"
    assert tr.category("%while.2 = (s32[]) while((s32[]) %t), body=%b") == "while"
    assert tr.category("%custom-call.7 = s32[8]{0} custom-call(s32[8]{0} %a)") == "custom call"
    assert tr.category("%all-to-all.1 = s32[8]{0} all-to-all(s32[8]{0} %a)") == "collective"
    assert tr.category("%copy.4 = s32[8]{0} copy(s32[8]{0} %a)") == "copy"
    assert tr.category("sort.5") == "sort" and tr.category("fusion.9") == "other fusion"
