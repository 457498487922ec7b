"""BENCHMARK.json against the contract's characters, lengths and cross
references, and every file it names."""

import json
import os
import re

import pytest

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    # a full check of 24 cells fits the driver's 43200 s
    runs = 2 + 14 * 24
    assert runs * (manifest["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_lines(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
            lines = ["why", "layer"] + (["source"] if group == "configs" else [])
            for key in lines:
                if key in entry:
                    assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] \
                        and "\t" not in entry[key], (entry["name"], key)
    assert len(names) == len(set(names))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16


def test_cross_references(manifest):
    cells = {w["name"]: w for w in manifest["workloads"]}
    configs = {c["name"] for c in manifest["configs"]}
    assert {w["config"] for w in cells.values()} == configs
    pairs = [(w["config"], w["traffic"]) for w in cells.values()]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(1, len(cells) // 2)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]

    def reports(metric, cell):
        return "workloads" not in metric or cell in metric["workloads"]

    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert set(m.get("workloads", [])) <= set(cells), m["name"]
    for cell in cells:
        assert sum(reports(m, cell) for m in manifest["end_to_end"]) >= 2
        assert any(reports(m, cell) for m in manifest["per_layer"])
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        # every cell that reports the per-layer metric reports what it moves
        for cell in cells:
            if reports(m, cell):
                assert reports(e2e[m["moves"]], cell), (m["name"], cell)


def test_every_file_is_there(manifest):
    assert manifest["paths"] == ["perfbench"]
    for word in manifest["command"]:
        assert not word.startswith("/") and ".." not in word
    for c in manifest["configs"]:
        assert c["file"].startswith("perfbench/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert os.path.exists(os.path.join(ROOT, c["file"][:-5] + ".py"))
    for w in manifest["workloads"]:
        with open(os.path.join(BENCH, "workloads", w["name"] + ".json")) as f:
            mix = json.load(f)
        assert (mix["loop"], mix["clients"]) == ("closed", 1) and mix["actions"]
    for m in manifest["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics", m["name"] + ".py"))
    # the end-to-end metrics are the harness's own
    assert {m["name"] for m in manifest["end_to_end"]} <= {
        "rows_per_s_chip", "action_p95_s", "setup_s"}


def test_peaks_lookup():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert peaks["TPU v5 lite"]["hbm_bytes"] == 16e9
    assert "TPU v4" not in peaks and "cpu" not in peaks


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_schedule_is_the_same_work_for_every_seed(seed):
    from conftest import load

    traffic = load(os.path.join(BENCH, "traffic.py"))
    mix = {"actions": [{"name": "a", "weight": 3}, {"name": "b", "weight": 1}]}
    gen = traffic.schedule(mix, seed)
    for _ in range(5):
        assert sorted(next(gen) for _ in range(4)) == ["a", "a", "a", "b"]
    sample = traffic.Sample(2, seed)
    for i in range(50):
        sample.offer(i, "a", i)
    kept = [i for i, _n, _r in sample.items()]
    assert len(kept) in (2, 3) and kept[-1] == 49 and kept == sorted(set(kept))
