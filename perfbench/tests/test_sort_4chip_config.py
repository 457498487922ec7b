"""sort_256m_4chip: the file states what ISSUE 36 asks and differs from
sort_64m's in the mesh alone, the .py holds no function of its own, the
sizes are four chips' rows, the new metric's reader on a made-up tally, and
the cell's rehearsal on the CPU mesh of four."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, ROOT, load

NAME = "sort_256m_4chip"
ACTION = "sort_collect_take"


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _json("configs", NAME + ".json")


@pytest.fixture(scope="module")
def mod():
    return load(os.path.join(BENCH, "configs", NAME + ".py"))


def test_the_file_states_what_the_issue_asks(cfg, manifest):
    one = _json("configs", "sort_64m.json")
    for key in ("schema", "row_bytes", "rows_per_chip", "key_range",
                "key_distribution", "fact_value_range", "take",
                "resident_row_bytes", "guarantees", "rehearse"):
        assert cfg[key] == one[key], key  # no width, range or law moved
    assert set(cfg["assumed"]) == set(one["assumed"])
    assert cfg["chips"] == 4 and cfg["rows_per_chip"] * 4 == 268_435_456
    assert cfg["row_bytes"] == 16 and cfg["resident_row_bytes"] == 12
    assert cfg["reduced"] == ["rows_per_chip", "chips"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert cfg["source"] == (
        "this repo's BASELINE.md config 5 (sort_by_key + take_ordered, 1B "
        "i64 keys on a v5e-8; upstream src/rdd/rdd.rs:1124-1153), "
        "GraySort-style uniform keys; cut to 4 chips at sort_64m's per-chip "
        "size")
    entry = next(c for c in manifest["configs"] if c["name"] == NAME)
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert entry["file"] == "perfbench/configs/" + NAME + ".json"
    cell = [w for w in manifest["workloads"] if w["config"] == NAME]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cell] \
        == [(NAME + ".batch", "batch", 4)]
    assert _json("workloads", NAME + ".batch.json") \
        == _json("workloads", "sort_64m.batch.json") \
        == {"loop": "closed", "clients": 1,
            "actions": [{"name": ACTION, "weight": 1}],
            "compare_sample": 1, "trace_seconds": 10, "warmup_max": 6}
    rounds = next(m for m in manifest["per_layer"]
                  if m["name"] == "exchange_rounds_per_action")
    assert rounds == {"name": "exchange_rounds_per_action", "unit": "count",
                      "better": "lower", "source": "program_counter",
                      "layer": "plan and schedule", "moves": "rows_per_s_chip",
                      "workloads": [NAME + ".batch"]}
    assert manifest["per_layer"][-1] == rounds
    assert manifest["workloads"][-1] == cell[0]
    assert manifest["configs"][-1] == entry
    four = [w["name"] for w in manifest["workloads"] if w["chips"] == 4]
    assert len(manifest["workloads"]) == 7 and len(four) == 3


def test_the_py_holds_no_function_of_its_own(mod):
    import ast

    with open(os.path.join(BENCH, "configs", NAME + ".py")) as f:
        src = f.read()
    tree = ast.parse(src)
    assert not [n for n in ast.walk(tree)
                if isinstance(n, (ast.FunctionDef, ast.ClassDef, ast.Lambda))]
    assert "import vega_tpu" not in src and "from vega_tpu" not in src
    assert "import jax" not in src
    one = load(os.path.join(BENCH, "configs", "sort_64m.py"))
    for name in ("sizes", "make_data", "feed", "fed_bytes", "actions"):
        theirs, ours = getattr(one, name), getattr(mod, name)
        assert ours.__code__.co_code == theirs.__code__.co_code
        assert ours.__code__.co_filename.endswith("sort_64m.py")


def test_sizes_are_four_chips_rows(cfg, mod):
    assert mod.sizes(cfg, 4, False) == {
        "rows": 268_435_456, "take": 1000,
        "key_range": [-9223372036854775807, 9223372036854775807]}
    assert mod.sizes(cfg, 4, True) == {
        "rows": 32768, "take": 1000,
        "key_range": [-8796093022208, 8796093022208]}
    one = _json("configs", "sort_64m.json")
    for rehearse in (False, True):
        assert mod.sizes(cfg, 4, rehearse)["rows"] \
            == 4 * mod.sizes(one, 1, rehearse)["rows"]
    act = mod.actions(cfg)[ACTION]
    full = mod.sizes(cfg, 4, False)
    assert act.rows_read(full) == 268_435_456
    # three passes over 12-byte rows and the take's 1,000
    assert act.least_bytes(full, cfg) == (3 * 268_435_456 + 1000) * 12


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_reference_passes_and_the_control_fails(cfg, mod, seed):
    size = mod.sizes(cfg, 4, True)
    data = mod.make_data(seed, cfg, size)
    again = mod.make_data(seed, cfg, size)
    assert all(np.array_equal(data[k], again[k]) for k in data)
    assert data["keys"].dtype == np.int64 and data["vals"].dtype == np.float64
    assert np.abs(data["keys"]).max() >= 2**31  # wide keys at toy size too
    act = mod.actions(cfg)[ACTION]
    ref = act.reference(data)
    assert np.array_equal(ref["k"], np.sort(data["keys"]))
    same = act.compare(ref, ref)
    assert same == {"sorted_keys_wrong": (0, 0), "sorted_values_wrong": (0, 0),
                    "take_keys_wrong": (0, 0), "take_values_wrong": (0, 0)}
    control = act.controls(data)
    assert list(control) == ["int32_high_word_keys"]
    caught = {k for k, (v, lim) in act.compare(
        control["int32_high_word_keys"], ref).items() if v > lim}
    assert "sorted_keys_wrong" in caught
    # a row lost is caught by its count
    lost = dict(ref, k=ref["k"][1:], v=ref["v"][1:])
    assert act.compare(lost, ref)["sorted_keys_wrong"] == (1, 0)


def test_exchange_rounds_per_action_reader(monkeypatch):
    read = load(os.path.join(BENCH, "metrics",
                             "exchange_rounds_per_action.py")).read
    sys.path.insert(0, ROOT)
    try:
        from vega_tpu.tpu import spans
    finally:
        sys.path.remove(ROOT)

    def tally(**counts):
        return {k: {"count": v, "seconds": 0.0, "bytes": 0, "by_kind": {}}
                for k, v in counts.items()}

    # two actions, one one-shot exchange each
    monkeypatch.setattr(spans, "session", lambda: tally(
        exchange=2, exchange_round=2, exchange_plan_rounds=2))
    assert read({"actions": 2}) == 1.0
    # one of them staged in groups of two on four shards
    monkeypatch.setattr(spans, "session", lambda: tally(
        exchange=2, exchange_round=2, exchange_plan_rounds=3))
    assert read({"actions": 2}) == 1.5
    # a program without the counter (the parent commit): nothing, never 0
    monkeypatch.setattr(spans, "session", lambda: tally(
        launch=6, exchange=2, exchange_round=2))
    assert read({"actions": 2}) is None
    monkeypatch.setattr(spans, "session", lambda: {})
    assert read({"actions": 2}) is None
    monkeypatch.setattr(spans, "session", lambda: tally(exchange_plan_rounds=2))
    assert read({"actions": 0}) is None


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_rehearses(trace):
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", NAME + ".batch",
         "--seed", "2147484036", "--seconds", "0.5", "--trace", trace,
         "--rehearse", "--control", "1"], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "platform: cpu" in p.stdout and "correct True" in p.stdout
    assert "devices: 4" in p.stderr and "chips: 4" in p.stderr
    assert '"metrics"' not in p.stdout and "PASSED AS CORRECT" not in p.stderr
    assert "control int32_high_word_keys: not correct" in p.stderr
    assert "in-window mints 0 compiles 0" in p.stderr
    for name in ("sorted_keys_wrong", "sorted_values_wrong",
                 "take_keys_wrong", "take_values_wrong"):
        assert f"compared {name}: 0 (limit 0)" in p.stderr
    assert "correct: True" in p.stderr
    if trace == "1":
        assert '"exchange_rounds_per_action": {"value": 1.0, ' in p.stderr
        assert '"programs_minted": {"value": 4' in p.stderr
