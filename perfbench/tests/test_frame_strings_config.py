"""frame_strings: the files state the deployment (sizes, `reduced`, source,
the cell and its four metrics), the reference against a row-by-row count,
the rehearsal is `correct` on the CPU, the control and a planted fault (a
join of raw, un-unified codes) are caught, a frame that falls to the host
tier or reads a file in an action fails it, and the four readers on made-up
tallies and traces."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, ROOT, load

NAME = "frame_strings"
METRICS = ("frame_plan_s_per_action", "dict_unify_s_per_action",
           "dict_remap_s_per_action", "dict_remap_hbm_roofline")
SOURCE = ("AMPLab Big Data Benchmark Query 3 (3C), the join task of Pavlo et "
          "al. SIGMOD '09 §4.3: Rankings and UserVisits schemas, 155M:18M "
          "rows a node; tables cached in memory")


@pytest.fixture(scope="module", autouse=True)
def program():
    """The program under test importable, as the harness makes it."""
    sys.path.insert(0, ROOT)
    yield
    sys.path.remove(ROOT)


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def mod():
    return load(os.path.join(BENCH, "configs", NAME + ".py"))


def test_the_files_state_the_deployment(cfg, manifest):
    assert cfg["source"] == SOURCE and len(SOURCE) <= 200
    assert (cfg["uservisits_rows"], cfg["rankings_rows"]) == (2**26, 7_793_287)
    assert cfg["rankings_rows"] == 2**26 * 18 // 155  # Pavlo's ratio
    assert (cfg["unranked_urls"], cfg["source_ips"]) == (2**20, 2**22)
    assert cfg["reduced"] == ["uservisits_rows", "rankings_rows"]
    assert cfg["source_rows_per_node"] == {"UserVisits": 155_000_000,
                                           "Rankings": 18_000_000}
    assert len(cfg["schema"]["UserVisits"]) == 9 \
        and len(cfg["schema"]["Rankings"]) == 3
    assert cfg["resident_bytes"] == {"UserVisits": 9 * 4 * 2**26,
                                     "Rankings": 3 * 4 * 7_793_287}
    assert cfg["avg_page_rank_rel_limit"] == 2.0 ** -21
    assert {"exact", "device_tier", "cached"} <= set(cfg["guarantees"])
    assert {"destURL", "pageURL", "sourceIP", "visitDate", "adRevenue",
            "pools", "window_3c"} <= set(cfg["assumed"])
    entry = next(c for c in manifest["configs"] if c["name"] == NAME)
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert entry["file"] == "perfbench/configs/" + NAME + ".json"
    cells = [w for w in manifest["workloads"] if w["config"] == NAME]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] \
        == [(NAME + ".batch", "batch", 1)]
    assert cells[0]["why"] == (
        "1 client, closed loop: AMPLab Q3C via frames on cached 2^26-row "
        "UserVisits ⋈ 7.8M Rankings; string-coded join and group keys, "
        "dictionary remap, date filter, float-key sort")
    with open(os.path.join(BENCH, "workloads", NAME + ".batch.json")) as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients"], mix["actions"], mix["compare_sample"]) \
        == ("closed", 1, [{"name": "amplab_q3", "weight": 1}], 1)
    listed = {m["name"]: m for m in manifest["per_layer"]}
    for m in METRICS:
        assert listed[m]["workloads"] == [NAME + ".batch"], m
        assert listed[m]["moves"] == "rows_per_s_chip", m
        assert os.path.exists(os.path.join(BENCH, "metrics", m + ".py"))
    assert listed["dict_remap_hbm_roofline"]["unit"] == "%"


def test_least_bytes_hand_count(cfg, mod):
    act = mod.actions(cfg)["amplab_q3"]
    full = mod.sizes(cfg, 1, False)
    assert act.rows_read(full) == 2**26
    # four UserVisits and two Rankings columns of 4 B read, one 12 B row out
    assert act.least_bytes(full, cfg) == 2**26 * 16 + 7_793_287 * 8 + 12 \
        == cfg["least_bytes"]


@pytest.fixture(scope="module")
def data(cfg, mod):
    return mod.make_data(2**31 + 9, cfg, mod.sizes(cfg, 1, True))


def test_the_data_follows_the_laws(cfg, mod, data):
    size = mod.sizes(cfg, 1, True)
    again = mod.make_data(2**31 + 9, cfg, size)
    for key in ("url_id", "ip_id", "day", "revenue", "page_rank"):
        assert np.array_equal(data[key], again[key]), key
    n, r = size["uservisits_rows"], size["rankings_rows"]
    assert len(data["url_id"]) == n and len(data["page_rank"]) == r
    ranked = np.count_nonzero(data["url_id"] < r) / n
    assert 0.88 < ranked < 0.92
    kept = np.count_nonzero((data["day"] >= data["window"][0])
                            & (data["day"] <= data["window"][1])) / n
    assert 0.73 < kept < 0.77 and data["window"] == [3652, 14610]
    assert set(np.unique(data["revenue"])) <= set(range(1000))
    ips = data["ip_pool"].to_pylist()
    assert len(set(ips)) == len(ips) == size["source_ips"]
    assert all(len(ip.split(".")) == 4 for ip in ips[:50])
    files = sorted(os.listdir(data["uv_dir"]))
    assert len(files) == cfg["part_files"]["UserVisits"]
    assert mod.fed_bytes(data) > 0


def test_the_reference_against_a_row_count(cfg, mod, data):
    act = mod.actions(cfg)["amplab_q3"]
    ref = act.reference(data)
    lo, hi = data["window"]
    sums = {}
    for u, ip, d, rev in zip(data["url_id"][:4000], data["ip_id"][:4000],
                             data["day"][:4000], data["revenue"][:4000]):
        if lo <= d <= hi and u < data["ranked"]:
            sums[ip] = sums.get(ip, 0.0) + rev
    part = dict(data, url_id=data["url_id"][:4000], ip_id=data["ip_id"][:4000],
                day=data["day"][:4000], revenue=data["revenue"][:4000])
    small = act.reference(part)
    names = data["ip_pool"].to_pylist()
    assert dict(zip(small["ip"].tolist(), small["revenue"].tolist())) \
        == {names[i]: s for i, s in sums.items()}
    assert ref["top"] == ref["revenue"].max()
    assert np.all(ref["ip"][:-1] < ref["ip"][1:])


def _answer(ref):
    """The answer a correct program gives, in the program's widths."""
    order = np.argsort(-ref["revenue"], kind="stable")
    return {"sourceIP": ref["ip"][order],
            "totalRevenue": ref["revenue"][order].astype(np.float32),
            "avgPageRank": ref["avg_page_rank"][order].astype(np.float32)}


def test_the_control_is_caught(cfg, mod, data):
    act = mod.actions(cfg)["amplab_q3"]
    ref = act.reference(data)
    act.whole = _answer(ref)
    top = int(np.argmax(ref["revenue"]))
    good = act.answer([(ref["ip"][top], 1.0, ref["revenue"][top])])
    assert all(v <= lim for v, lim in act.compare(good, ref).values())
    caught = {k for k, (v, lim) in act.compare(
        act.controls(data)["bfloat16_revenue"], ref).items() if v > lim}
    assert "whole_revenue_err" in caught
    # a row that is not of the maximum revenue is wrong
    low = int(np.argmin(ref["revenue"]))
    bad = act.answer([(ref["ip"][low], 1.0, ref["revenue"][low])])
    assert act.compare(bad, ref)["top_row_wrong"][0] == 1


def _run(*args):
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", NAME + ".batch",
         *args], cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)


def test_the_cell_rehearses():
    p = _run("--seed", "2147484001", "--seconds", "0.5", "--trace", "0",
             "--rehearse", "--control", "1")
    assert p.returncode == 0, p.stderr[-3000:]
    assert "platform: cpu" in p.stdout and "correct True" in p.stdout
    assert '"metrics"' not in p.stdout and "PASSED AS CORRECT" not in p.stderr
    assert "in-window mints 0 compiles 0" in p.stderr
    assert "compared whole_revenue_err: 0.0 (limit 0)" in p.stderr
    assert "compared top_row_wrong: 0 (limit 0)" in p.stderr
    assert "control bfloat16_revenue: not correct" in p.stderr


def _program(cfg, mod, data):
    import vega_tpu as v

    ctx = v.Context("local")
    return ctx, mod.feed(ctx, data), mod.actions(cfg)["amplab_q3"]


def test_a_join_of_raw_codes_is_caught(cfg, mod, data, monkeypatch):
    """The planted fault: each side's own URL codes joined without the
    unification onto one dictionary."""
    ctx, src, act = _program(cfg, mod, data)
    try:
        from vega_tpu.tpu import dense_rdd

        monkeypatch.setattr(dense_rdd, "_unify_dict_cols",
                            lambda a, b, names: (a, b))
        result = act.call(act.build(src))
        numbers = act.compare(act.answer(result), act.reference(data))
    finally:
        ctx.stop()
    assert numbers["whole_ips_wrong"][0] > 0 or numbers["top_row_wrong"][0] > 0 \
        or numbers.get("whole_revenue_err", (0, 0))[0] > 0


def test_an_action_that_leaves_the_device_or_reads_a_file_fails(cfg, mod, data,
                                                                monkeypatch):
    ctx, src, act = _program(cfg, mod, data)
    try:
        from vega_tpu.frame import planner
        from vega_tpu.io import readers

        assert act.call(act.build(src))  # set-up reads the files
        nodes = act.build(src)
        monkeypatch.setattr(readers, "_FILES_READ", [10**6])
        with pytest.raises(RuntimeError, match="cached"):
            act.call(nodes)
        monkeypatch.setattr(planner, "_lower_device", _refuse)
        with pytest.raises(RuntimeError, match="device_tier"):
            act.build(src)
    finally:
        ctx.stop()


def _refuse(*_args, **_kw):
    from vega_tpu.frame.physical import HostFallback

    raise HostFallback("refused")


def _tally(**entries):
    return {k: {"count": c, "seconds": s, "bytes": 0, "by_kind": {}}
            for k, (c, s) in entries.items()}


def test_the_readers(monkeypatch):
    read = {m: load(os.path.join(BENCH, "metrics", m + ".py")).read
            for m in METRICS}
    bytes_of = load(os.path.join(BENCH, "metrics",
                                 "dict_remap_hbm_roofline.py")).least_bytes
    from vega_tpu.tpu import spans

    monkeypatch.delitem(sys.modules, "perfbench_stage_ops", raising=False)
    table = {"dict_remap": {"programs": 2, "ops": [
        {"op": "gather.3", "shape": "s32[64]", "opcode": "gather", "kind": "",
         "stage": "dict_remap", "key": "gather.3 s32[64] gather(t,c)"}]}}
    monkeypatch.setattr(spans, "program_stages", lambda: table, raising=False)
    plane = [["%gather.3 = s32[64]{0} gather(s32[64]{0} %t, s32[64]{0} %c)",
              1.0, 0.001]]
    obs = {"actions": 2, "chips": 1, "trace": {"ops": []},
           "peaks": {"hbm_bytes_per_s": 819e9},
           "events": {"host": [["perfbench:window", 0.0, 10.0]],
                      "devices": {"/device:TPU:0": plane}}}
    monkeypatch.setattr(spans, "session", lambda: _tally(
        frame_plan=(2, 0.3), dict_unify=(2, 0.002),
        dict_remap_rows=(2 * 50_000_000, 0.0),
        dict_remap_table_entries=(4 * 8_000_000, 0.0)))
    assert read["frame_plan_s_per_action"](obs) == pytest.approx(0.15)
    assert read["dict_unify_s_per_action"](obs) == pytest.approx(0.001)
    assert read["dict_remap_s_per_action"](obs) == pytest.approx(0.0005)
    assert bytes_of(10, 3) == 92
    want = 100 * bytes_of(50_000_000, 16_000_000) / 819e9 / 0.0005
    assert read["dict_remap_hbm_roofline"](obs) == pytest.approx(want)
    # nothing to read gives nothing, never 0: no spans or counters and no
    # stage of the name in the program's tables (the parent commit), no
    # remap in the trace, or no action
    monkeypatch.setattr(spans, "session", lambda: _tally(launch=(9, 0.1)))
    monkeypatch.setattr(spans, "program_stages", lambda: {"join": {
        "programs": 1, "ops": [dict(table["dict_remap"]["ops"][0],
                                    stage="merge_join")]}}, raising=False)
    monkeypatch.delitem(sys.modules, "perfbench_stage_ops", raising=False)
    for m in METRICS:
        assert read[m](dict(obs, events=dict(obs["events"]))) is None, m
    monkeypatch.setattr(spans, "program_stages", lambda: table, raising=False)
    monkeypatch.setattr(spans, "session", lambda: _tally(
        frame_plan=(2, 0.3), dict_unify=(2, 0.002),
        dict_remap_rows=(10, 0.0), dict_remap_table_entries=(4, 0.0)))
    bare = dict(obs, events=dict(obs["events"], devices={"/device:TPU:0": []}))
    monkeypatch.delitem(sys.modules, "perfbench_stage_ops", raising=False)
    assert read["dict_remap_s_per_action"](bare) is None
    assert read["dict_remap_hbm_roofline"](bare) is None
    for m in METRICS:
        assert read[m](dict(obs, actions=0)) is None, m
