"""frame_strings — AMPLab Big Data Benchmark Query 3 (3C), Pavlo et al.'s join
task, through the frame API over cached parquet tables, on one chip:

    uv = ctx.read_parquet(uv_dir).cache()          # set-up
    rk = ctx.read_parquet(rk_dir).cache()
    (uv.filter((col("visitDate") >= date(1980, 1, 1))
               & (col("visitDate") <= date(2010, 1, 1)))
       .select("sourceIP", "destURL", "adRevenue")
       .join(rk.select(destURL=col("pageURL"), pageRank=col("pageRank")),
             on="destURL")
       .group_by("sourceIP")
       .agg(F.mean("pageRank", "avgPageRank"), F.sum("adRevenue", "totalRevenue"))
       .sort("totalRevenue", ascending=False).limit(1)
       .collect())                                 # each action

The data is drawn from the seed as integer ids (a URL's index in one pool of
distinct strings, an IP's in another, a day, a revenue) and written as
parquet part files in set-up, the string columns as dictionary columns over
the pools; the files are deleted at exit. The plain reference sees only the
ids and imports nothing of vega_tpu: the date mask, then `url_id < R` (the
ranked URLs are the pool's first R), `np.bincount` of revenue, of the
visited URL's pageRank and of ones by IP, and the IP strings looked up at
the end.
"""

import atexit
import datetime
import os
import shutil
import tempfile
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LOWER = ord("a")


def sizes(cfg: dict, chips: int, rehearse: bool) -> dict:
    per = cfg["rehearse"] if rehearse else cfg
    return {k: per[k] for k in ("uservisits_rows", "rankings_rows",
                                "unranked_urls", "source_ips")}


def _strings(rng, n: int, lo: int, hi: int, prefix: bool = False):
    """n lowercase strings of uniform lengths in [lo, hi] as one arrow
    string array, built from one buffer of random letters; with `prefix`
    the first characters spell each string's index in base 26, so no two
    are equal."""
    import pyarrow as pa

    lens = rng.integers(lo, hi + 1, n)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(lens, out=offsets[1:])
    data = rng.integers(LOWER, LOWER + 26, int(offsets[-1]), dtype=np.uint8)
    if prefix:
        digits, left = 0, max(n - 1, 1)
        while left:
            left //= 26
            digits += 1
        if digits > lo:
            raise ValueError(f"{n} distinct strings need {digits} letters")
        idx = np.arange(n, dtype=np.int64)
        for j in range(digits):
            data[offsets[:-1] + j] = LOWER + (idx // 26 ** j) % 26
    return pa.StringArray.from_buffers(
        n, pa.py_buffer(offsets.astype(np.int32)), pa.py_buffer(data))


def _dotted_quads(rng, n: int):
    """n distinct IPv4 addresses as dotted-quad strings: an odd multiplier
    is a bijection of the 32-bit integers."""
    import pyarrow as pa
    import pyarrow.compute as pc

    a = int(rng.integers(0, 1 << 31)) * 2 + 1
    b = int(rng.integers(0, 1 << 32))
    ip = (np.arange(n, dtype=np.uint64) * np.uint64(a) + np.uint64(b)) \
        & np.uint64(0xFFFFFFFF)
    octet = pa.array([str(i) for i in range(256)])
    parts = [octet.take(pa.array(((ip >> np.uint64(s)) & np.uint64(255))
                                 .astype(np.int32))) for s in (24, 16, 8, 0)]
    return pc.binary_join_element_wise(*parts, ".")


def make_data(seed: int, cfg: dict, size: dict) -> dict:
    """The ids from the seed (each a generator of its own), the parquet
    files over them, and what the reference needs: the ids, revenues,
    pageRanks and the IP pool."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    n, r = size["uservisits_rows"], size["rankings_rows"]
    pool, ips = r + size["unranked_urls"], size["source_ips"]
    g = [np.random.default_rng(s)
         for s in np.random.SeedSequence(seed).spawn(12)]
    urls = _strings(g[0], pool, *cfg["url_length"], prefix=True)
    ip_pool = _dotted_quads(g[1], ips)
    ranked = g[2].random(n, dtype=np.float32) < cfg["ranked_share"]
    url_id = np.where(ranked, g[3].integers(0, r, n, dtype=np.int32),
                      g[4].integers(r, pool, n, dtype=np.int32))
    del ranked
    ip_id = g[5].integers(0, ips, n, dtype=np.int32)
    day = g[6].integers(*cfg["visit_days"], n, dtype=np.int32)
    revenue = g[7].integers(*cfg["ad_revenue_range"], n,
                            dtype=np.int16).astype(np.float64)
    page_rank = g[8].integers(*cfg["page_rank_range"], r, dtype=np.int32)
    dlo, dhi = cfg["duration_range"]
    others = {}
    for i, (name, spec) in enumerate(cfg["pools"].items()):
        sub = np.random.default_rng(g[9].integers(0, 1 << 62) + i)
        others[name] = pa.DictionaryArray.from_arrays(
            pa.array(sub.integers(0, spec["entries"], n, dtype=np.int16)),
            _strings(sub, spec["entries"], *spec["length"], prefix=True))
    uv = pa.table({
        "sourceIP": pa.DictionaryArray.from_arrays(pa.array(ip_id), ip_pool),
        "destURL": pa.DictionaryArray.from_arrays(pa.array(url_id), urls),
        "visitDate": pa.array(day).cast(pa.date32()),
        "adRevenue": pa.array(revenue),
        "userAgent": others["userAgent"],
        "countryCode": others["countryCode"],
        "languageCode": others["languageCode"],
        "searchWord": others["searchWord"],
        "duration": pa.array(g[10].integers(dlo, dhi, n, dtype=np.int32)),
    })
    rk = pa.table({
        "pageURL": urls.slice(0, r),
        "pageRank": pa.array(page_rank),
        "avgDuration": pa.array(g[11].integers(dlo, dhi, r, dtype=np.int32)),
    })
    os.makedirs(os.path.join(ROOT, ".perfbench"), exist_ok=True)
    top = tempfile.mkdtemp(prefix="frame_strings_",
                           dir=os.path.join(ROOT, ".perfbench"))
    atexit.register(shutil.rmtree, top, True)
    jobs = []
    for name, table, parts in (("uservisits", uv,
                                cfg["part_files"]["UserVisits"]),
                               ("rankings", rk, cfg["part_files"]["Rankings"])):
        os.makedirs(os.path.join(top, name))
        per = -(-table.num_rows // parts)
        for p in range(parts):
            piece = table.slice(p * per, per)
            jobs.append((piece, os.path.join(top, name, f"part-{p:02d}.parquet")))

    def write(job) -> None:
        piece, path = job
        pq.write_table(piece, path, row_group_size=max(piece.num_rows, 1),
                       compression="none",
                       use_dictionary=[c for c in piece.column_names
                                       if c != "pageURL"])

    with ThreadPoolExecutor(len(jobs)) as pool_:
        list(pool_.map(write, jobs))
    del jobs, uv, rk, urls, others
    pa.default_memory_pool().release_unused()  # the writers' buffers
    return {"uv_dir": os.path.join(top, "uservisits"),
            "rk_dir": os.path.join(top, "rankings"),
            "url_id": url_id, "ip_id": ip_id, "day": day,
            "revenue": revenue, "page_rank": page_rank, "ip_pool": ip_pool,
            "ranked": r, "window": [
                (datetime.date.fromisoformat(d) - datetime.date(1970, 1, 1)).days
                for d in cfg["window_3c"]],
            "dense_hbm_budget": cfg["dense_hbm_budget"]}


def fed_bytes(data: dict) -> int:
    """The parquet files the set-up reads."""
    return sum(os.path.getsize(os.path.join(d, f))
               for d in (data["uv_dir"], data["rk_dir"])
               for f in os.listdir(d))


class Sources(dict):
    """The cached frames by name. `values()` is what the harness waits on:
    each cached frame's resident node, whose block the first read builds."""

    def values(self):
        return [frame._compiled().rdd for frame in super().values()]


def feed(ctx, data: dict) -> Sources:
    """Both tables, read once and cached on the device. The HBM LRU's
    budget is the deployment's (`assumed`)."""
    from vega_tpu.env import Env

    Env.get().conf.dense_hbm_budget = data["dense_hbm_budget"]
    return Sources(uv=ctx.read_parquet(data["uv_dir"]).cache(),
                   rk=ctx.read_parquet(data["rk_dir"]).cache())


def q3(uv, rk, limit=1):
    """AMPLab Query 3 (3C) over the two frames, as a user writes it."""
    from vega_tpu.frame.expr import F, col

    q = (uv.filter((col("visitDate") >= datetime.date(1980, 1, 1))
                   & (col("visitDate") <= datetime.date(2010, 1, 1)))
         .select("sourceIP", "destURL", "adRevenue")
         .join(rk.select(destURL=col("pageURL"), pageRank=col("pageRank")),
               on="destURL")
         .group_by("sourceIP")
         .agg(F.mean("pageRank", "avgPageRank"),
              F.sum("adRevenue", "totalRevenue"))
         .sort("totalRevenue", ascending=False))
    return q if limit is None else q.limit(limit)


def _bf16(a: np.ndarray) -> np.ndarray:
    """float32 -> bfloat16 -> float32, round to nearest even, in numpy."""
    bits = a.astype(np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32)


def _nodes(rdd) -> list:
    """Every node of a compiled lineage."""
    seen, todo = [], [rdd]
    while todo:
        node = todo.pop()
        if any(node is s for s in seen):
            continue
        seen.append(node)
        todo.extend(getattr(node, "_dense_parents", ()) or ())
    return seen


class AmplabQ3:
    """The query with LIMIT 1, compiled in `build` and collected in `call`.
    Its first build, in set-up, also collects the same plan without the
    limit, whole, for the comparison."""

    def __init__(self, rel_limit: float):
        self.rel_limit = rel_limit
        self.whole = None

    def build(self, src: dict) -> dict:
        from vega_tpu.frame import planner
        from vega_tpu.io import readers

        if self.whole is None:
            self.whole = q3(src["uv"], src["rk"], limit=None).collect_columns()
        before = planner.fallback_count()
        frame = q3(src["uv"], src["rk"])
        compiled = frame._compiled()
        if planner.fallback_count() != before or compiled.kind != "device":
            raise RuntimeError("the frame planner fell back to the host tier "
                               f"(guarantee `device_tier`): "
                               f"{planner.last_fallback()}")
        nodes = {f"node{i}": n for i, n in enumerate(_nodes(compiled.rdd))}
        self._pending = (frame, compiled, readers.parquet_files_read())
        return nodes

    def call(self, nodes: dict):
        from vega_tpu.io import readers

        frame, compiled, files = self._pending
        self._pending = None
        rows = frame._collect(compiled)
        if readers.parquet_files_read() != files:
            raise RuntimeError("an action read parquet files (guarantee "
                               "`cached`)")
        return rows

    def rows_read(self, size: dict) -> int:
        return size["uservisits_rows"]

    def least_bytes(self, size: dict, cfg: dict) -> int:
        """Read once what the query uses at the device's widths: four
        UserVisits columns (visitDate, sourceIP, destURL, adRevenue) and two
        Rankings columns (pageURL, pageRank), 4 bytes each; write the one
        result row (sourceIP code, totalRevenue, avgPageRank), 12 bytes."""
        return (16 * size["uservisits_rows"] + 8 * size["rankings_rows"]
                + 12)

    def reference(self, data: dict, rounded=None) -> dict:
        """Per IP over the visits in 3C's window to a ranked URL: the sum of
        revenue (each value first through `rounded`, and the sums after,
        where given), of the URL's pageRank, and the count; the IPs that
        have any, as strings, with their sums and mean pageRank."""
        lo, hi = data["window"]
        day, url_id = data["day"], data["url_id"]
        sel = (day >= lo) & (day <= hi) & (url_id < data["ranked"])
        ip = data["ip_id"][sel]
        rev = data["revenue"][sel]
        if rounded is not None:
            rev = rounded(rev).astype(np.float64)
        n_ips = len(data["ip_pool"])
        revenue = np.bincount(ip, weights=rev, minlength=n_ips)
        if rounded is not None:
            revenue = rounded(revenue).astype(np.float64)
        ranks = np.bincount(ip, weights=data["page_rank"][url_id[sel]],
                            minlength=n_ips)
        count = np.bincount(ip, minlength=n_ips)
        has = np.flatnonzero(count)
        names = data["ip_pool"].take(has).to_numpy(zero_copy_only=False) \
            .astype(np.str_)
        order = np.argsort(names, kind="stable")
        return {"ip": names[order], "revenue": revenue[has][order],
                "avg_page_rank": (ranks[has] / count[has])[order],
                "top": float(revenue.max(initial=0.0))}

    def controls(self, data: dict) -> dict:
        """The answer of the reference in the nearest precision below
        float32: revenue and its per-IP sums in bfloat16."""
        ref = self.reference(data, rounded=_bf16)
        top = int(np.argmax(ref["revenue"]))
        return {"bfloat16_revenue": {
            "row": (ref["ip"][top], ref["avg_page_rank"][top],
                    ref["revenue"][top]),
            "ip": ref["ip"], "revenue": ref["revenue"],
            "avg_page_rank": ref["avg_page_rank"],
            "revenue_down": np.sort(ref["revenue"])[::-1]}}

    def answer(self, result) -> dict:
        """The window's row, and the set-up's whole result by IP string."""
        whole = self.whole
        order = np.argsort(whole["sourceIP"], kind="stable")
        return {"row": result[0] if result else None,
                "ip": whole["sourceIP"][order],
                "revenue": whole["totalRevenue"][order].astype(np.float64),
                "avg_page_rank": whole["avgPageRank"][order]
                .astype(np.float64),
                "revenue_down": whole["totalRevenue"].astype(np.float64)}

    def compare(self, got: dict, ref: dict) -> dict:
        """name -> (number, limit): the row against the reference's maximum
        (limit 0), the whole result's IPs and revenues (limit 0), its mean
        pageRank (relative, `avg_page_rank_rel_limit`) and its order."""
        out = {}
        row = got["row"]
        if row is None:
            out["top_row_wrong"] = (1, 0)
        else:
            ip, _avg, revenue = row
            i = np.searchsorted(ref["ip"], ip)
            found = i < len(ref["ip"]) and ref["ip"][i] == ip
            out["top_row_wrong"] = (int(not found or ref["revenue"][i]
                                        != ref["top"]), 0)
            out["top_revenue_err"] = (abs(float(revenue) - ref["top"]), 0)
        if len(got["ip"]) != len(ref["ip"]) \
                or not np.array_equal(got["ip"], ref["ip"]):
            wrong = len(np.setxor1d(got["ip"], ref["ip"])) or abs(
                len(got["ip"]) - len(ref["ip"])) or 1
            out["whole_ips_wrong"] = (int(wrong), 0)
            return out
        out["whole_ips_wrong"] = (0, 0)
        out["whole_revenue_err"] = (float(np.max(np.abs(
            got["revenue"] - ref["revenue"]), initial=0.0)), 0)
        out["avg_page_rank_rel_err"] = (float(np.max(np.abs(
            got["avg_page_rank"] - ref["avg_page_rank"])
            / ref["avg_page_rank"], initial=0.0)), self.rel_limit)
        down = got["revenue_down"]
        out["revenue_increases"] = (int(np.count_nonzero(down[1:]
                                                         > down[:-1])), 0)
        return out


def actions(cfg: dict) -> dict:
    """The one action. A program whose frames have no cache() cannot run
    this deployment: it says so here, before any data is drawn."""
    from vega_tpu.frame.api import DataFrame

    if not hasattr(DataFrame, "cache"):
        raise SystemExit("perfbench: frame_strings needs DataFrame.cache(); "
                         "this program has none. No result.")
    return {"amplab_q3": AmplabQ3(cfg["avg_page_rank_rel_limit"])}
