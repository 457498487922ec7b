"""sort_64m — the data, the resident source, its action, the plain numpy
reference, its controls, the comparison that decides `correct`, and the
least bytes the action must move.

The reference imports nothing of vega_tpu and takes nothing it has made.
"""

import numpy as np


def sizes(cfg: dict, chips: int, rehearse: bool) -> dict:
    per = cfg["rehearse"] if rehearse else cfg
    return {"rows": per["rows_per_chip"] * chips, "take": cfg["take"],
            "key_range": per.get("key_range", cfg["key_range"])}


def make_data(seed: int, cfg: dict, size: dict) -> dict:
    """(int64 key, float64 value) rows, the source's widths, keys over the
    int64 range. How the program holds them on the device is its own doing."""
    rng = np.random.default_rng(seed)
    klo, khi = size["key_range"]
    lo, hi = cfg["fact_value_range"]
    return {
        "keys": rng.integers(klo, khi, size["rows"], dtype=np.int64),
        "vals": rng.integers(lo, hi, size["rows"]).astype(np.float64),
    }


def feed(ctx, data: dict) -> dict:
    return {"pairs": ctx.dense_from_numpy(data["keys"], data["vals"])}


def fed_bytes(data: dict) -> int:
    return sum(a.nbytes for a in data.values())


def _sorted_answer(keys, vals, order, take: int) -> dict:
    skeys, svals = keys[order], vals[order]
    # take_ordered orders pairs like host tuples: key, then value
    n = min(take, len(skeys))
    head = np.flatnonzero(skeys <= skeys[n - 1])
    head = head[np.lexsort((svals[head], skeys[head]))][:n]
    return {"k": skeys, "v": svals,
            "take_k": skeys[head].astype(np.int64),
            "take_v": svals[head].astype(np.float64)}


class SortCollectTake:
    """pairs.sort_by_key().collect_arrays(), then pairs.take_ordered(1000)"""

    def __init__(self, take: int):
        self.take = take

    def build(self, src: dict) -> dict:
        return {"pairs": src["pairs"], "sorted": src["pairs"].sort_by_key()}

    def call(self, nodes: dict):
        return {"arrays": nodes["sorted"].collect_arrays(),
                "take": nodes["pairs"].take_ordered(self.take)}

    def rows_read(self, size: dict) -> int:
        return size["rows"]

    def least_bytes(self, size: dict, cfg: dict) -> int:
        """The sort reads every row once and writes every row once; the take
        reads every row once more and writes `take` rows; each at the
        narrowest widths that hold a row exactly (an 8-byte key, a float32
        value: `resident_row_bytes`)."""
        return (3 * size["rows"] + size["take"]) * cfg["resident_row_bytes"]

    def reference(self, data: dict) -> dict:
        order = np.argsort(data["keys"], kind="stable")
        return _sorted_answer(data["keys"], data["vals"], order, self.take)

    def controls(self, data: dict) -> dict:
        """The step that would tempt, int32 for int64: the keys ordered by
        their high 32 bits alone, the one word a TPU compares natively."""
        keys, vals = data["keys"], data["vals"]
        by_high = np.argsort(keys >> 32, kind="stable")
        return {"int32_high_word_keys":
                _sorted_answer(keys, vals, by_high, self.take)}

    def answer(self, result) -> dict:
        take = result["take"]
        return {"k": result["arrays"]["k"], "v": result["arrays"]["v"],
                "take_k": np.array([r[0] for r in take], np.int64),
                "take_v": np.array([r[1] for r in take], np.float64)}

    def compare(self, got: dict, ref: dict) -> dict:
        """name -> (number, limit). Every comparison is exact: limit 0."""
        out = {}
        for name, a, b in (("sorted_keys_wrong", got["k"], ref["k"]),
                           ("sorted_values_wrong", got["v"], ref["v"]),
                           ("take_keys_wrong", got["take_k"], ref["take_k"]),
                           ("take_values_wrong", got["take_v"], ref["take_v"])):
            if a.shape != b.shape:
                out[name] = (max(1, abs(len(a) - len(b))), 0)
            else:
                out[name] = (int(np.count_nonzero(a != b)), 0)
        return out


def actions(cfg: dict) -> dict:
    return {"sort_collect_take": SortCollectTake(cfg["take"])}
