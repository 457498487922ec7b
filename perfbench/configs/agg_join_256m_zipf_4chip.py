"""agg_join_256m_zipf_4chip — a star join before the aggregate, on Zipf keys
over four chips: the data, the action, its plain numpy reference, its
control, the comparison that decides `correct`, and the least bytes the
action must move.

    joined = pairs.join(table)
    prod   = joined.map_values(lambda vw: vw[0] * vw[1])
    out    = prod.reduce_by_key(op="add")
    out.collect_arrays()

No map-side combine runs before the join's exchange, so every raw fact row
crosses the all_to_all to its key's chip and the chip that owns the hottest
keys receives a third more than its share.

What the other configurations share (sizes, the feed, `_bf16`) is taken from
agg_join_64m.py, loaded by path as the harness loads a configuration. The
Zipf draw is agg_join_64m_zipf.py's law (YCSB's ZipfianGenerator: the inverse
of the float64 CDF) with one difference the .json states: the rank -> key
permutation comes from the constant `key_permutation_seed`, not from --seed,
so which keys are hot, and which chip they hash to, is the deployment's and
the same in every run. The reference imports nothing of vega_tpu: it sees the
host arrays `make_data` drew.
"""

import importlib.util
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "perfbench_configs_agg_join_64m_shared_by_zipf_4chip",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "agg_join_64m.py"))
_uniform = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_uniform)

sizes = _uniform.sizes
feed = _uniform.feed
fed_bytes = _uniform.fed_bytes
_bf16 = _uniform._bf16

EXACT_BELOW = float(2 ** 24)  # float32 holds every whole number up to here
CHUNK = 1 << 24  # rows drawn, and referenced, at a time


def make_data(seed: int, cfg: dict, size: dict) -> dict:
    """(int64 key, float64 value) rows whose keys follow p_i ∝ i^-s over the
    table's keys, by the inverse CDF; the rows' ranks and every value from
    `seed`, the rank -> key permutation from the configuration's constant."""
    rng = np.random.default_rng(seed)
    rows, keys = size["rows"], size["keys"]
    cdf = np.cumsum(np.arange(1, keys + 1, dtype=np.float64) ** -cfg["zipf_s"])
    cdf /= cdf[-1]
    perm = np.random.default_rng(cfg["key_permutation_seed"]).permutation(
        keys).astype(np.int64)
    u = rng.random(rows)
    fact_keys = np.empty(rows, np.int64)

    def rank_to_key(i: int) -> None:
        # cdf[-1] is 1.0 and random() stays under it: a rank is at most keys - 1
        fact_keys[i:i + CHUNK] = perm[
            np.searchsorted(cdf, u[i:i + CHUNK], side="right")]

    # the binary searches are four fifths of the draw and numpy runs them
    # with the interpreter's lock released: a chunk a thread, same keys
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(rank_to_key, range(0, rows, CHUNK)))
    del u
    lo, hi = cfg["fact_value_range"]
    tlo, thi = cfg["table_value_range"]
    return {
        "keys": fact_keys,
        "vals": rng.integers(lo, hi, rows).astype(np.float64),
        "tkeys": np.arange(keys, dtype=np.int64),
        "tvals": rng.integers(tlo, thi, keys).astype(np.float64),
    }


def product(vw):
    """One module-level function, so that one program serves every action:
    the joined pair (lv, rv) -> lv * rv, on either tier."""
    return vw[0] * vw[1]


def _sums_of_products(data: dict, rounded=None) -> np.ndarray:
    """Per key, the float64 sum of value x table value over its rows, in
    chunks of rows; `rounded` is applied to each product first."""
    n_keys = len(data["tkeys"])
    sums = np.zeros(n_keys, np.float64)
    for i in range(0, len(data["keys"]), CHUNK):
        k = data["keys"][i:i + CHUNK]
        prod = data["vals"][i:i + CHUNK] * data["tvals"][k]
        if rounded is not None:
            prod = rounded(prod).astype(np.float64)
        sums += np.bincount(k, weights=prod, minlength=n_keys)
    return sums


def _keys_present(data: dict) -> np.ndarray:
    return np.flatnonzero(
        np.bincount(data["keys"], minlength=len(data["tkeys"])))


class JoinProductReduce:
    """pairs.join(table).map_values(product).reduce_by_key(op="add")
    .collect_arrays()"""

    def __init__(self, cfg: dict):
        self.hot_limit = float(cfg["hot_sum_rel_limit"])

    def build(self, src: dict) -> dict:
        joined = src["pairs"].join(src["table"])
        prod = joined.map_values(product)
        return {"joined": joined, "prod": prod,
                "out": prod.reduce_by_key(op="add")}

    def call(self, nodes: dict):
        return nodes["out"].collect_arrays()

    def rows_read(self, size: dict) -> int:
        return size["rows"]

    def least_bytes(self, size: dict, cfg: dict) -> int:
        """Read every fact row once (key and value) and every table row once
        at `resident_row_bytes`; write one 8-byte (key, sum) row for each key
        that drew a row, of which `keys_present_min_share` of the table's
        keys is a lower bound."""
        row = cfg["resident_row_bytes"]
        present = int(size["keys"] * cfg["keys_present_min_share"])
        return size["rows"] * row + size["keys"] * row + present * 8

    def reference(self, data: dict) -> dict:
        """Every product is a whole number under 2^20 and every sum under
        2^45: float64 is exact, in any order."""
        present = _keys_present(data)
        return {"k": present.astype(np.int64),
                "v": _sums_of_products(data)[present]}

    def controls(self, data: dict) -> dict:
        """The reference in the nearest precision below float32: every
        product and every key's sum through bfloat16."""
        present = _keys_present(data)
        sums = _sums_of_products(data, rounded=_bf16)[present]
        return {"bfloat16_products": {
            "k": present.astype(np.int64),
            "v": _bf16(sums).astype(np.float64)}}

    def answer(self, result) -> dict:
        """The timed path's result (the columns `k` and `v` of
        collect_arrays(), shard after shard) in key order."""
        order = np.argsort(result["k"], kind="stable")
        return {"k": result["k"][order].astype(np.int64),
                "v": result["v"][order].astype(np.float64)}

    def compare(self, got: dict, ref: dict) -> dict:
        """name -> (number, limit)."""
        if len(got["k"]) != len(ref["k"]) or not np.array_equal(got["k"], ref["k"]):
            wrong = len(np.setxor1d(got["k"], ref["k"])) or abs(
                len(got["k"]) - len(ref["k"])) or 1  # duplicates
            return {"keys_wrong": (int(wrong), 0)}
        err = np.abs(got["v"] - ref["v"])
        hot = ref["v"] >= EXACT_BELOW
        return {
            "keys_wrong": (0, 0),
            "sum_max_abs_err_exact_keys": (float(np.max(err[~hot], initial=0.0)), 0),
            "sum_max_rel_err_hot_keys": (
                float(np.max(err[hot] / ref["v"][hot], initial=0.0)),
                self.hot_limit),
        }


def actions(cfg: dict) -> dict:
    return {"join_product_reduce": JoinProductReduce(cfg)}
