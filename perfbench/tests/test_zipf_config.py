"""agg_join_64m_zipf: the data is what the configuration says, the
comparison passes the reference and fails the control by the numbers it
should, the least bytes against a hand count, the retry metric's reader on a
made-up tally, and the cell's rehearsal."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, ROOT, load

NAME = "agg_join_64m_zipf"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def mod():
    return load(os.path.join(BENCH, "configs", NAME + ".py"))


def _present_expected(rows, keys, s):
    p = np.arange(1, keys + 1, dtype=np.float64) ** -s
    p /= p.sum()
    return float(np.sum(-np.expm1(rows * np.log1p(-p))))


def test_the_file_states_what_the_issue_asks(cfg, manifest):
    uniform = json.load(open(os.path.join(BENCH, "configs", "agg_join_64m.json")))
    for key in ("schema", "row_bytes", "rows_per_chip", "keys_per_chip",
                "fact_value_range", "table_value_range", "resident_row_bytes",
                "reduced", "rehearse"):
        assert cfg[key] == uniform[key], key  # no width, range or ratio moved
    assert cfg["key_distribution"] == "zipf" and cfg["zipf_s"] == 1.1
    assert cfg["hot_sum_rel_limit"] == 2.0 ** -18
    assert {"zipf_s", "rank_to_key_permutation", "fact_value_range",
            "table_value_range", "hot_sum_rel_limit"} <= set(cfg["assumed"])
    assert {"cold_sums", "hot_sums", "resident", "device_tier"} \
        <= set(cfg["guarantees"])
    cell = [w for w in manifest["workloads"] if w["config"] == NAME]
    assert [(w["name"], w["chips"]) for w in cell] == [(NAME + ".batch", 1)]


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_data_follows_the_law(cfg, mod, seed):
    """1M rows over 100k keys: the hottest key's share is p_1, the hot keys
    are not the small integers, and the same seed gives the same data."""
    size = {"rows": 1_000_000, "keys": 100_000}
    a, b = mod.make_data(seed, cfg, size), mod.make_data(seed, cfg, size)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["keys"], mod.make_data(seed + 1, cfg, size)["keys"])
    assert a["keys"].dtype == np.int64 and a["vals"].dtype == np.float64
    counts = np.bincount(a["keys"], minlength=size["keys"])
    p = np.arange(1, size["keys"] + 1, dtype=np.float64) ** -cfg["zipf_s"]
    p /= p.sum()
    by_rank = np.sort(counts)[::-1]
    assert abs(by_rank[0] / size["rows"] - p[0]) < 0.002
    assert abs(by_rank[:10].sum() / size["rows"] - p[:10].sum()) < 0.003
    assert sorted(np.argsort(counts)[-10:]) != list(range(10))  # permuted
    present = np.count_nonzero(counts)
    assert abs(present - _present_expected(**size, s=cfg["zipf_s"])) < 1500
    assert set(np.unique(a["vals"])) <= set(range(1009))


def test_reference_passes_and_the_control_fails_by_both_sums(cfg, mod):
    size = {"rows": 1_000_000, "keys": 100_000}
    data = mod.make_data(7, cfg, size)
    act = mod.actions(cfg)["reduce_join_collect"]
    ref = act.reference(data)
    assert (ref["lv"] >= 2.0 ** 24).sum() >= 3  # this size has hot keys
    same = act.compare(ref, ref)
    assert set(same) == {"join_keys_wrong", "table_values_wrong",
                         "sum_max_abs_err_exact_keys", "sum_max_rel_err_hot_keys"}
    assert all(v <= lim for v, lim in same.values())
    assert same["sum_max_rel_err_hot_keys"][1] == 2.0 ** -18
    caught = {k: v for k, (v, lim) in act.compare(
        act.controls(data)["bfloat16_sums"], ref).items() if v > lim}
    assert set(caught) == {"sum_max_abs_err_exact_keys",
                           "sum_max_rel_err_hot_keys"}
    # a hot sum off by 1e-5 fails, by that number alone; a cold one off by 1
    i = int(np.argmax(ref["lv"]))
    off = dict(ref, lv=ref["lv"].copy())
    off["lv"][i] *= 1 + 1e-5
    assert [k for k, (v, lim) in act.compare(off, ref).items() if v > lim] \
        == ["sum_max_rel_err_hot_keys"]
    j = int(np.argmin(ref["lv"]))
    off = dict(ref, lv=ref["lv"].copy())
    off["lv"][j] += 1.0
    assert [k for k, (v, lim) in act.compare(off, ref).items() if v > lim] \
        == ["sum_max_abs_err_exact_keys"]
    # a key lost from the join is caught before any sum is looked at
    lost = {k: v[1:] for k, v in ref.items()}
    assert act.compare(lost, ref) == {"join_keys_wrong": (1, 0)}


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 5])
def test_at_rehearsal_size_the_exact_keys_catch_the_control(cfg, mod, seed):
    size = mod.sizes(cfg, 1, True)
    data = mod.make_data(seed, cfg, size)
    act = mod.actions(cfg)["reduce_join_collect"]
    ref = act.reference(data)
    assert all(v <= lim for v, lim in act.compare(ref, ref).values())
    assert act.compare(ref, ref)["sum_max_rel_err_hot_keys"][0] == 0.0
    numbers = act.compare(act.controls(data)["bfloat16_sums"], ref)
    assert numbers["sum_max_abs_err_exact_keys"][0] > 0
    # the stated share of keys that draw a row is a lower bound here too
    assert len(ref["k"]) >= int(size["keys"] * cfg["keys_present_min_share"])


def test_least_bytes_hand_count(cfg, mod):
    act = mod.actions(cfg)["reduce_join_collect"]
    toy = {"resident_row_bytes": 8, "keys_present_min_share": 0.45}
    # 1000 fact rows x 8 B + 20 table rows x 8 B read; at least 9 of the 20
    # keys draw a row: 9 result rows x 12 B written
    assert act.least_bytes({"rows": 1000, "keys": 20}, toy) == 8000 + 160 + 108
    assert act.rows_read({"rows": 1000, "keys": 20}) == 1000
    # a true lower bound at the cell's size: 3.34M keys are expected to draw
    # a row (the count spreads by about a thousand), 0.45 x 6,710,886 is 3.02M
    full = mod.sizes(cfg, 1, False)
    expected = _present_expected(full["rows"], full["keys"], cfg["zipf_s"])
    assert 3_300_000 < expected < 3_400_000
    assert int(full["keys"] * cfg["keys_present_min_share"]) < expected - 100_000
    assert act.least_bytes(full, cfg) == (67108864 + 6710886) * 8 + 3019898 * 12


def test_exchange_retries_reader(monkeypatch):
    read = load(os.path.join(BENCH, "metrics",
                             "exchange_retries_per_action.py")).read
    sys.path.insert(0, ROOT)
    try:
        from vega_tpu.tpu import spans
    finally:
        sys.path.remove(ROOT)

    def tally(**counts):
        return {k: {"count": v, "seconds": 0.0, "bytes": 0, "by_kind": {}}
                for k, v in counts.items()}

    obs = {"actions": 2}
    monkeypatch.setattr(spans, "session", lambda: tally(
        launch=4, exchange=4, exchange_round=4))
    assert read(obs) == 0.0
    # one exchange launched three times, and one block rebuilt (whose
    # rebuild is an exchange and a round of its own)
    monkeypatch.setattr(spans, "session", lambda: tally(
        exchange=5, exchange_round=7, exchange_repair=1))
    assert read(obs) == 1.5
    # a program without the counters (the parent commit), or no action
    monkeypatch.setattr(spans, "session", lambda: tally(launch=4, fetch=2))
    assert read(obs) is None
    monkeypatch.setattr(spans, "session", lambda: tally(exchange=1))
    assert read({"actions": 0}) is None


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
    assert '"metrics"' not in p.stdout and "PASSED AS CORRECT" not in p.stderr
    assert "in-window mints 0 compiles 0" in p.stderr
    assert "compared sum_max_abs_err_exact_keys: 0.0 (limit 0)" in p.stderr
    assert "compared sum_max_rel_err_hot_keys: 0.0 (limit 3.8146" in p.stderr
    if trace == "1":
        assert '"exchange_retries_per_action": {"value": 0.0' in p.stderr
