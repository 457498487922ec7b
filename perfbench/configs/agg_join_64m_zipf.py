"""agg_join_64m_zipf — agg_join_64m's job on keys drawn from a bounded Zipf
law: the data, the plain numpy reference, its control, the comparison that
decides `correct`, and the least bytes the action must move.

What the uniform configuration shares (sizes, the feed, how the action is
built, called and read back, the bfloat16 control) is taken
from agg_join_64m.py, loaded by path as the harness loads a configuration.
The reference is this file's own and imports nothing of vega_tpu: it sees
the host arrays `make_data` drew from the seed.

One key here holds an eighth of the rows and its sum passes 2^31, so "every
sum equals the reference" cannot hold in the float32 the program narrows a
float64 to. The comparison splits the keys by their reference sum: under
2^24 the sum is exact in float32 whatever the order of additions (limit 0);
from 2^24 on it is held to `hot_sum_rel_limit`, relative.
"""

import importlib.util
import os

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "perfbench_configs_agg_join_64m_shared_by_zipf",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "agg_join_64m.py"))
_uniform = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_uniform)

sizes = _uniform.sizes
feed = _uniform.feed
fed_bytes = _uniform.fed_bytes

EXACT_BELOW = float(2 ** 24)  # float32 holds every whole number up to here


def make_data(seed: int, cfg: dict, size: dict) -> dict:
    """(int64 key, float64 value) rows whose keys follow p_i ∝ i^-s over the
    table's keys, by the inverse CDF (YCSB's ZipfianGenerator, after Gray et
    al.); a seeded permutation takes a rank to its key, so the hot keys are
    not the small integers."""
    rng = np.random.default_rng(seed)
    rows, keys = size["rows"], size["keys"]
    cdf = np.cumsum(np.arange(1, keys + 1, dtype=np.float64) ** -cfg["zipf_s"])
    cdf /= cdf[-1]
    # cdf[-1] is 1.0 and random() stays under it: a rank is at most keys - 1
    rank = np.searchsorted(cdf, rng.random(rows), side="right")
    perm = rng.permutation(keys).astype(np.int64)
    lo, hi = cfg["fact_value_range"]
    tlo, thi = cfg["table_value_range"]
    return {
        "keys": perm[rank],
        "vals": rng.integers(lo, hi, rows).astype(np.float64),
        "tkeys": np.arange(keys, dtype=np.int64),
        "tvals": rng.integers(tlo, thi, keys).astype(np.float64),
    }


class ReduceJoinCollect(_uniform.ReduceJoinCollect):
    """pairs.reduce_by_key(op="add").join(table).collect() — built, called
    and read back as agg_join_64m's, whose control `bfloat16_sums` (this
    reference with values and sums through bfloat16) it keeps; referenced
    and compared here."""

    def __init__(self, cfg: dict):
        self.hot_limit = float(cfg["hot_sum_rel_limit"])

    def least_bytes(self, size: dict, cfg: dict) -> int:
        """Read every fact row once (key and value) and every table row once
        at `resident_row_bytes`; write one 12-byte (key, sum, table value)
        row for each key that drew a row, of which `keys_present_min_share`
        of the table's keys is a lower bound."""
        row = cfg["resident_row_bytes"]
        present = int(size["keys"] * cfg["keys_present_min_share"])
        return size["rows"] * row + size["keys"] * row + present * 12

    def reference(self, data: dict, _vals=None) -> dict:
        vals = data["vals"] if _vals is None else _vals
        n_keys = len(data["tkeys"])
        sums = np.bincount(data["keys"], weights=vals, minlength=n_keys)
        present = np.bincount(data["keys"], minlength=n_keys) > 0
        return {"k": np.flatnonzero(present).astype(np.int64),
                "lv": sums[present],  # float64: whole numbers, exact
                "rv": data["tvals"][present]}

    def compare(self, got: dict, ref: dict) -> dict:
        """name -> (number, limit)."""
        if len(got["k"]) != len(ref["k"]) or not np.array_equal(got["k"], ref["k"]):
            wrong = len(np.setxor1d(got["k"], ref["k"])) or abs(
                len(got["k"]) - len(ref["k"])) or 1  # duplicates
            return {"join_keys_wrong": (int(wrong), 0)}
        err = np.abs(got["lv"] - ref["lv"])
        hot = ref["lv"] >= EXACT_BELOW
        return {
            "join_keys_wrong": (0, 0),
            "table_values_wrong": (int(np.count_nonzero(got["rv"] != ref["rv"])), 0),
            "sum_max_abs_err_exact_keys": (float(np.max(err[~hot], initial=0.0)), 0),
            "sum_max_rel_err_hot_keys": (
                float(np.max(err[hot] / ref["lv"][hot], initial=0.0)),
                self.hot_limit),
        }


def actions(cfg: dict) -> dict:
    return {"reduce_join_collect": ReduceJoinCollect(cfg)}
