"""agg_join_64m — the data, the resident sources, the actions by name, the
plain numpy reference of each action, its control, the comparison that
decides `correct`, and the least bytes each action must move.

The reference imports nothing of vega_tpu and takes nothing it has made: it
sees the host arrays that `make_data` drew from the seed. Only `feed` and an
action's `build` / `call` touch the program.
"""

import numpy as np


def sizes(cfg: dict, chips: int, rehearse: bool) -> dict:
    per = cfg["rehearse"] if rehearse else cfg
    return {"rows": per["rows_per_chip"] * chips,
            "keys": per["keys_per_chip"] * chips}


def make_data(seed: int, cfg: dict, size: dict) -> dict:
    """(int64 key, float64 value) rows, the source's widths. Whatever the
    program narrows on the way to the device is its own doing."""
    rng = np.random.default_rng(seed)
    lo, hi = cfg["fact_value_range"]
    tlo, thi = cfg["table_value_range"]
    return {
        "keys": rng.integers(0, size["keys"], size["rows"], dtype=np.int64),
        "vals": rng.integers(lo, hi, size["rows"]).astype(np.float64),
        "tkeys": np.arange(size["keys"], dtype=np.int64),
        "tvals": rng.integers(tlo, thi, size["keys"]).astype(np.float64),
    }


def feed(ctx, data: dict) -> dict:
    """The resident sources. The fact table is fed first: the harness times
    this call for `feed_gbytes_per_s`."""
    return {"pairs": ctx.dense_from_numpy(data["keys"], data["vals"]),
            "table": ctx.dense_from_numpy(data["tkeys"], data["tvals"])}


def fed_bytes(data: dict) -> int:
    return sum(a.nbytes for a in data.values())


def _bf16(a: np.ndarray) -> np.ndarray:
    """float32 -> bfloat16 -> float32, round to nearest even, in numpy."""
    bits = a.astype(np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32)


class ReduceJoinCollect:
    """pairs.reduce_by_key(op="add").join(table).collect()"""

    def build(self, src: dict) -> dict:
        reduced = src["pairs"].reduce_by_key(op="add")
        return {"reduced": reduced, "joined": reduced.join(src["table"])}

    def call(self, nodes: dict):
        return nodes["joined"].collect()

    def rows_read(self, size: dict) -> int:
        return size["rows"]

    def least_bytes(self, size: dict, cfg: dict) -> int:
        """Read every fact row once (key and value) and every table row once,
        at the narrowest widths that hold them exactly (int32 key, float32
        value: `resident_row_bytes`); write one (key, sum, table value) row
        a key, 12 bytes."""
        row = cfg["resident_row_bytes"]
        return size["rows"] * row + size["keys"] * row + size["keys"] * 12

    def reference(self, data: dict, _vals=None) -> dict:
        vals = data["vals"] if _vals is None else _vals
        n_keys = len(data["tkeys"])
        sums = np.bincount(data["keys"], weights=vals, minlength=n_keys)
        present = np.bincount(data["keys"], minlength=n_keys) > 0
        return {"k": np.flatnonzero(present).astype(np.int64),
                "lv": sums[present],  # float64: whole numbers, exact
                "rv": data["tvals"][present]}

    def controls(self, data: dict) -> dict:
        """The reference in the nearest precision below float32: values and
        the per-key sums in bfloat16."""
        ref = self.reference(data, _vals=_bf16(data["vals"]))
        ref["lv"] = _bf16(ref["lv"]).astype(np.float64)
        return {"bfloat16_sums": ref}

    def answer(self, result) -> dict:
        """The timed path's result (a list of (k, (sum, table value)) rows)
        as columns in key order."""
        n = len(result)
        k = np.fromiter((r[0] for r in result), np.int64, n)
        lv = np.fromiter((r[1][0] for r in result), np.float64, n)
        rv = np.fromiter((r[1][1] for r in result), np.float64, n)
        order = np.argsort(k, kind="stable")
        return {"k": k[order], "lv": lv[order], "rv": rv[order]}

    def compare(self, got: dict, ref: dict) -> dict:
        """name -> (number, limit). Every comparison is exact: limit 0."""
        if len(got["k"]) != len(ref["k"]) or not np.array_equal(got["k"], ref["k"]):
            wrong = len(np.setxor1d(got["k"], ref["k"])) or abs(
                len(got["k"]) - len(ref["k"])) or 1  # duplicates
            return {"join_keys_wrong": (int(wrong), 0)}
        return {
            "join_keys_wrong": (0, 0),
            "sum_max_abs_err": (float(np.max(np.abs(got["lv"] - ref["lv"]),
                                             initial=0.0)), 0),
            "table_values_wrong": (int(np.count_nonzero(got["rv"] != ref["rv"])), 0),
        }


def ind(v):
    """One module-level function, so that one program serves every scan."""
    return (v >= 504).astype("int32")


class CountWhere:
    """SELECT COUNT(*) FROM pairs WHERE v >= 504, as one narrow program and a
    named reduce: pairs.map_values(ind).values_dense().sum()"""

    def build(self, src: dict) -> dict:
        return {"flags": src["pairs"].map_values(ind).values_dense()}

    def call(self, nodes: dict):
        return nodes["flags"].sum()

    def rows_read(self, size: dict) -> int:
        return size["rows"]

    def least_bytes(self, size: dict, cfg: dict) -> int:
        """Read every fact row's value once (4 bytes as float32, which holds
        it exactly); the result is 4 bytes."""
        return size["rows"] * 4 + 4

    def reference(self, data: dict) -> dict:
        return {"count": int(np.count_nonzero(data["vals"] >= 504))}

    def controls(self, data: dict) -> dict:
        """Values in bfloat16: 503 rounds to 504 and is counted."""
        return {"bfloat16_values": {
            "count": int(np.count_nonzero(_bf16(data["vals"]) >= 504))}}

    def answer(self, result) -> dict:
        return {"count": int(result)}

    def compare(self, got: dict, ref: dict) -> dict:
        return {"count_abs_err": (abs(got["count"] - ref["count"]), 0)}


def actions(cfg: dict) -> dict:
    return {"reduce_join_collect": ReduceJoinCollect(),
            "count_where": CountWhere()}
