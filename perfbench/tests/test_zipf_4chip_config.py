"""agg_join_256m_zipf_4chip: the file states what ISSUE 34 asks, the hot keys
are the deployment's and not the seed's, the comparison passes the reference
and fails the control by both sums, the least bytes against a hand count, the
fill metric's reader on a made-up tally, and the cell's rehearsal on the CPU
mesh of four."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, ROOT, load

NAME = "agg_join_256m_zipf_4chip"
ACTION = "join_product_reduce"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def mod():
    return load(os.path.join(BENCH, "configs", NAME + ".py"))


def test_the_file_states_what_the_issue_asks(cfg, manifest):
    four = json.load(open(os.path.join(BENCH, "configs",
                                       "agg_join_256m_4chip.json")))
    zipf = json.load(open(os.path.join(BENCH, "configs",
                                       "agg_join_64m_zipf.json")))
    for key in ("schema", "row_bytes", "chips", "rows_per_chip",
                "keys_per_chip", "fact_value_range", "table_value_range",
                "resident_row_bytes", "reduced", "rehearse"):
        assert cfg[key] == four[key], key  # no width, range or ratio moved
    for key in ("key_distribution", "zipf_s", "hot_sum_rel_limit"):
        assert cfg[key] == zipf[key], key
    assert cfg["chips"] == 4 and cfg["zipf_s"] == 1.1
    assert cfg["rows_per_chip"] * 4 == 268_435_456
    assert cfg["keys_per_chip"] * 4 == 26_843_544
    assert cfg["hot_sum_rel_limit"] == 2.0 ** -18
    assert cfg["keys_present_min_share"] == 0.40
    assert cfg["key_permutation_seed"] == 34
    assert cfg["reduced"] == ["rows_per_chip", "keys_per_chip", "chips"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert set(cfg["assumed"]) == {
        "zipf_s", "key_permutation_seed", "fact_value_range",
        "table_value_range", "hot_sum_rel_limit", "keys_present_min_share"}
    assert {"keys", "cold_sums", "hot_sums", "resident", "device_tier"} \
        <= set(cfg["guarantees"])
    assert cfg["source"] == (
        "rajasekarv/vega examples/join.rs + examples/group_by.rs (join, "
        "map_values, reduce_by_key), this repo's BASELINE.md north star cut "
        "to 4 chips; keys by YCSB's ZipfianGenerator (Gray et al. '94), s 1.1")
    entry = next(c for c in manifest["configs"] if c["name"] == NAME)
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    cell = [w for w in manifest["workloads"] if w["config"] == NAME]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cell] \
        == [(NAME + ".batch", "batch", 4)]
    mix = json.load(open(os.path.join(BENCH, "workloads", NAME + ".batch.json")))
    assert mix == {"loop": "closed", "clients": 1,
                   "actions": [{"name": ACTION, "weight": 1}],
                   "compare_sample": 1, "trace_seconds": 10, "warmup_max": 6}
    fill = next(m for m in manifest["per_layer"]
                if m["name"] == "exchange_fill_share")
    assert fill == {"name": "exchange_fill_share", "unit": "%",
                    "better": "higher", "source": "program_counter",
                    "layer": "plan and schedule", "moves": "rows_per_s_chip",
                    "workloads": [NAME + ".batch"]}


def test_two_seeds_give_the_same_hot_keys_and_different_rows(cfg, mod):
    """1M rows over 100k keys: which keys are hot comes from
    `key_permutation_seed`, their rows and every value from --seed; the same
    seed gives the same data."""
    size = {"rows": 1_000_000, "keys": 100_000}
    a = mod.make_data(1, cfg, size)
    b = mod.make_data(2**31 + 5, cfg, size)
    again = mod.make_data(1, cfg, size)
    assert all(np.array_equal(a[k], again[k]) for k in a)
    assert a["keys"].dtype == np.int64 and a["vals"].dtype == np.float64
    assert not np.array_equal(a["keys"], b["keys"])
    assert not np.array_equal(a["vals"], b["vals"])
    assert not np.array_equal(a["tvals"], b["tvals"])
    ca = np.bincount(a["keys"], minlength=size["keys"])
    cb = np.bincount(b["keys"], minlength=size["keys"])
    perm = np.random.default_rng(34).permutation(size["keys"])
    # the ten hottest keys, in order, are the permutation's first ten
    assert np.argsort(-ca, kind="stable")[:10].tolist() == perm[:10].tolist()
    assert np.argsort(-cb, kind="stable")[:10].tolist() == perm[:10].tolist()
    assert sorted(perm[:10].tolist()) != list(range(10))
    p = np.arange(1, size["keys"] + 1, dtype=np.float64) ** -cfg["zipf_s"]
    p /= p.sum()
    assert abs(ca.max() / size["rows"] - p[0]) < 0.002
    assert abs(np.sort(ca)[-10:].sum() / size["rows"] - p[:10].sum()) < 0.003
    assert set(np.unique(a["vals"])) <= set(range(1009))
    assert set(np.unique(a["tvals"])) <= set(range(1000))
    # another permutation seed moves the hot keys
    other = mod.make_data(1, dict(cfg, key_permutation_seed=35), size)
    assert int(np.argmax(np.bincount(other["keys"]))) != int(perm[0])


def test_the_law_at_the_cells_size(cfg):
    """The .json's numbers, from the law alone (no rows drawn)."""
    rows, keys = cfg["rows_per_chip"] * 4, cfg["keys_per_chip"] * 4
    p = np.arange(1, keys + 1, dtype=np.float64) ** -cfg["zipf_s"]
    p /= p.sum()
    assert abs(p[0] - 0.1139) < 0.0001 and abs(p[:10].sum() - 0.305) < 0.001
    present = float(np.sum(-np.expm1(rows * np.log1p(-p)))) / keys
    assert abs(present - 0.455) < 0.002
    assert present > cfg["keys_present_min_share"] + 0.05
    assert 3000 < np.count_nonzero(rows * p > 4096) < 3600


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 5])
def test_reference_passes_and_the_control_fails_by_both_sums(cfg, mod, seed):
    """At rehearsal size (32,768 rows over 3,276 keys on four chips)."""
    size = mod.sizes(cfg, 4, True)
    assert size == {"rows": 32768, "keys": 3276}
    data = mod.make_data(seed, cfg, size)
    act = mod.actions(cfg)[ACTION]
    ref = act.reference(data)
    # plain numpy, in one piece
    prod = data["vals"] * data["tvals"][data["keys"]]
    sums = np.bincount(data["keys"], weights=prod, minlength=size["keys"])
    present = np.flatnonzero(np.bincount(data["keys"], minlength=size["keys"]))
    assert np.array_equal(ref["k"], present)
    assert np.array_equal(ref["v"], sums[present])
    assert (ref["v"] >= 2.0 ** 24).sum() >= 20  # this size has hot keys
    assert len(ref["k"]) >= int(size["keys"] * cfg["keys_present_min_share"])
    same = act.compare(ref, ref)
    assert set(same) == {"keys_wrong", "sum_max_abs_err_exact_keys",
                         "sum_max_rel_err_hot_keys"}
    assert all(v <= lim for v, lim in same.values())
    assert same["sum_max_rel_err_hot_keys"] == (0.0, 2.0 ** -18)
    control = act.controls(data)
    assert list(control) == ["bfloat16_products"]
    caught = {k for k, (v, lim) in act.compare(
        control["bfloat16_products"], ref).items() if v > lim}
    assert caught == {"sum_max_abs_err_exact_keys", "sum_max_rel_err_hot_keys"}
    # a hot sum off by 1e-5 fails, by that number alone; a cold one off by 1
    i = int(np.argmax(ref["v"]))
    off = dict(ref, v=ref["v"].copy())
    off["v"][i] *= 1 + 1e-5
    assert [k for k, (v, lim) in act.compare(off, ref).items() if v > lim] \
        == ["sum_max_rel_err_hot_keys"]
    j = int(np.argmin(ref["v"]))
    off = dict(ref, v=ref["v"].copy())
    off["v"][j] += 1.0
    assert [k for k, (v, lim) in act.compare(off, ref).items() if v > lim] \
        == ["sum_max_abs_err_exact_keys"]
    # a key lost, or one too many, is caught before any sum is looked at
    lost = {k: v[1:] for k, v in ref.items()}
    assert act.compare(lost, ref) == {"keys_wrong": (1, 0)}
    # the answer of the timed path: shard after shard, float32, any order
    shuffled = np.random.default_rng(seed).permutation(len(ref["k"]))
    got = act.answer({"k": ref["k"][shuffled].astype(np.int32),
                      "v": ref["v"][shuffled]})
    assert got["k"].dtype == np.int64 and got["v"].dtype == np.float64
    assert all(v <= lim for v, lim in act.compare(got, ref).values())


def test_least_bytes_hand_count(cfg, mod):
    act = mod.actions(cfg)[ACTION]
    toy = {"resident_row_bytes": 8, "keys_present_min_share": 0.40}
    # 1000 fact rows x 8 B + 20 table rows x 8 B read; at least 8 of the 20
    # keys draw a row: 8 result rows x 8 B (int32 key, float32 sum) written
    assert act.least_bytes({"rows": 1000, "keys": 20}, toy) == 8000 + 160 + 64
    assert act.rows_read({"rows": 1000, "keys": 20}) == 1000
    full = mod.sizes(cfg, 4, False)
    assert full == {"rows": 268_435_456, "keys": 26_843_544}
    assert act.least_bytes(full, cfg) \
        == (268_435_456 + 26_843_544) * 8 + 10_737_417 * 8


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "configs", NAME + ".py")) as f:
        src = f.read()
    assert "import vega_tpu" not in src and "from vega_tpu" not in src
    assert "import jax" not in src


def test_exchange_fill_share_reader(monkeypatch):
    read = load(os.path.join(BENCH, "metrics", "exchange_fill_share.py")).read
    sys.path.insert(0, ROOT)
    try:
        from vega_tpu.tpu import spans
    finally:
        sys.path.remove(ROOT)

    def tally(**counts):
        return {k: {"count": v, "seconds": 0.0, "bytes": 0, "by_kind": {}}
                for k, v in counts.items()}

    obs = {"actions": 2}
    # two sides, four shards, an out_cap of 100: 295 rows in 800 slots
    monkeypatch.setattr(spans, "session", lambda: tally(
        exchange=2, exchange_round=2, exchange_rows=295, exchange_slots=800))
    assert read(obs) == 36.875
    # a program without the counters (the parent commit): nothing, never 0
    monkeypatch.setattr(spans, "session", lambda: tally(
        launch=4, exchange=2, exchange_round=2))
    assert read(obs) is None
    monkeypatch.setattr(spans, "session", lambda: {})
    assert read(obs) is None
    monkeypatch.setattr(spans, "session", lambda: tally(exchange_rows=0,
                                                        exchange_slots=0))
    assert read(obs) is None


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_rehearses(trace):
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", NAME + ".batch",
         "--seed", "2147483999", "--seconds", "0.5", "--trace", trace,
         "--rehearse", "--control", "1"], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "platform: cpu" in p.stdout and "correct True" in p.stdout
    assert "devices: 4" in p.stderr and "chips: 4" in p.stderr
    assert '"metrics"' not in p.stdout and "PASSED AS CORRECT" not in p.stderr
    assert "control bfloat16_products: not correct" in p.stderr
    assert "in-window mints 0 compiles 0" in p.stderr
    assert "compared keys_wrong: 0 (limit 0)" in p.stderr
    assert "compared sum_max_abs_err_exact_keys: 0.0 (limit 0)" in p.stderr
    assert "(limit 3.8146" in p.stderr and "correct: True" in p.stderr
    if trace == "1":
        assert '"exchange_fill_share": {"value": ' in p.stderr
        assert '"programs_minted": {"value": 4' in p.stderr
