"""AMPLab Query 3 (Pavlo et al.'s join task) through the frame API on the
device tier, and what it needs of the frame layer: joins that carry several
value columns a side, date columns and day literals, DataFrame.cache(), the
arrow string dictionaries (rank codes, host bytes about the strings') and a
dictionary unification whose remap table stages whole."""

import datetime
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import vega_tpu as v
from vega_tpu.frame import F, col
from vega_tpu.frame import planner
from vega_tpu.io import readers
from vega_tpu.tpu import dict_encoding, spans

EPOCH = datetime.date(1970, 1, 1)
WINDOW = (datetime.date(1980, 1, 1), datetime.date(2010, 1, 1))


def _words(rng, n, lo=6, hi=30, tag=""):
    """n distinct lowercase strings (a base-26 index spelled first)."""
    out = []
    for i in range(n):
        head, j = "", i
        for _ in range(4):
            head += chr(97 + j % 26)
            j //= 26
        tail = rng.integers(97, 123, rng.integers(lo, hi + 1) - 4)
        out.append(tag + head + bytes(tail.astype(np.uint8)).decode())
    return out


def _amplab_tables(tmp_path, seed=7, visits=6000, ranked=700, unranked=120,
                   ips=300):
    """Seeded UserVisits and Rankings as parquet part files, in the
    benchmark's laws at a small size, and the ids behind them."""
    rng = np.random.default_rng(seed)
    urls = _words(rng, ranked + unranked, 20, 40)
    ip_pool = [f"10.{i // 256}.{i % 256}.{(i * 7) % 256}" for i in range(ips)]
    url_id = np.where(rng.random(visits) < 0.9,
                      rng.integers(0, ranked, visits),
                      rng.integers(ranked, ranked + unranked, visits))
    ip_id = rng.integers(0, ips, visits)
    day = rng.integers(0, 14610, visits).astype(np.int32)
    revenue = rng.integers(0, 1000, visits).astype(np.float64)
    page_rank = rng.integers(1, 1000, ranked).astype(np.int32)
    uv = pa.table({
        "sourceIP": pa.DictionaryArray.from_arrays(
            pa.array(ip_id.astype(np.int32)), pa.array(ip_pool)),
        "destURL": pa.DictionaryArray.from_arrays(
            pa.array(url_id.astype(np.int32)), pa.array(urls)),
        "visitDate": pa.array(day).cast(pa.date32()),
        "adRevenue": pa.array(revenue),
        "userAgent": pa.array([f"agent{i % 5}" for i in range(visits)]),
        "duration": pa.array(rng.integers(1, 100, visits).astype(np.int32)),
    })
    rk = pa.table({"pageURL": pa.array(urls[:ranked]),
                   "pageRank": pa.array(page_rank),
                   "avgDuration": pa.array(np.arange(ranked,
                                                     dtype=np.int32) % 50)})
    dirs = {}
    for name, table, parts in (("uv", uv, 3), ("rk", rk, 2)):
        d = tmp_path / name
        os.makedirs(d)
        per = -(-table.num_rows // parts)
        for p in range(parts):
            pq.write_table(table.slice(p * per, per),
                           str(d / f"part-{p}.parquet"))
        dirs[name] = str(d)
    ids = dict(url_id=url_id, ip_id=ip_id, day=day, revenue=revenue,
               page_rank=page_rank, ranked=ranked, ip_pool=ip_pool)
    return dirs, ids


def _q3(uv, rk, limit=None):
    q = (uv.filter((col("visitDate") >= WINDOW[0])
                   & (col("visitDate") <= WINDOW[1]))
         .select("sourceIP", "destURL", "adRevenue")
         .join(rk.select(destURL=col("pageURL"), pageRank=col("pageRank")),
               on="destURL")
         .group_by("sourceIP")
         .agg(F.mean("pageRank", "avgPageRank"),
              F.sum("adRevenue", "totalRevenue"))
         .sort("totalRevenue", ascending=False))
    return q if limit is None else q.limit(limit)


def _q3_reference(ids):
    lo, hi = ((d - EPOCH).days for d in WINDOW)
    sel = (ids["day"] >= lo) & (ids["day"] <= hi) \
        & (ids["url_id"] < ids["ranked"])
    ip = ids["ip_id"][sel]
    n = len(ids["ip_pool"])
    revenue = np.bincount(ip, weights=ids["revenue"][sel], minlength=n)
    ranks = np.bincount(ip, weights=ids["page_rank"][ids["url_id"][sel]],
                        minlength=n)
    count = np.bincount(ip, minlength=n)
    return {ids["ip_pool"][i]: (revenue[i], ranks[i] / count[i])
            for i in np.flatnonzero(count)}


@pytest.fixture()
def ctx():
    context = v.Context("local", num_workers=2)
    yield context
    context.stop()


def test_amplab_q3_over_cached_parquet_on_the_device(ctx, tmp_path):
    dirs, ids = _amplab_tables(tmp_path)
    uv = ctx.read_parquet(dirs["uv"]).cache()
    rk = ctx.read_parquet(dirs["rk"]).cache()
    base = planner.fallback_count()
    whole = _q3(uv, rk)
    assert "device tier" in whole.explain()
    got = whole.collect_columns()
    ref = _q3_reference(ids)
    assert sorted(got["sourceIP"].tolist()) == sorted(ref)
    for ip, avg, total in zip(got["sourceIP"], got["avgPageRank"],
                              got["totalRevenue"]):
        assert total == ref[ip][0]
        assert abs(avg - ref[ip][1]) <= 2.0 ** -21 * ref[ip][1]
    assert np.all(np.diff(got["totalRevenue"]) <= 0)
    top = max(r for r, _a in ref.values())
    (row,) = _q3(uv, rk, limit=1).collect()
    assert ref[row[0]][0] == top == row[2]
    assert planner.fallback_count() == base


def test_a_cached_frame_reads_its_files_once(ctx, tmp_path):
    dirs, ids = _amplab_tables(tmp_path, seed=11, visits=3000)
    uv = ctx.read_parquet(dirs["uv"]).cache()
    rk = ctx.read_parquet(dirs["rk"]).cache()
    first = _q3(uv, rk, limit=3).collect()
    read = readers.parquet_files_read()
    for _ in range(3):
        assert _q3(uv, rk, limit=3).collect() == first
    assert readers.parquet_files_read() == read
    # the same resident node at every compile, and an uncached frame
    # reads its files again each action
    assert uv._compiled().rdd is uv._compiled().rdd
    plain = ctx.read_parquet(dirs["rk"])
    plain.count()
    plain.count()
    assert readers.parquet_files_read() == read + 4
    # the host tier caches its lineage as well, with the same rows
    host = sorted(_q3(uv, rk).hint(tier="host").collect())
    assert [r[0] for r in host] == sorted(r[0] for r in
                                          _q3(uv, rk).collect())


def test_cache_after_limit_is_refused(ctx):
    from vega_tpu.errors import VegaError

    with pytest.raises(VegaError, match="before limit"):
        ctx.create_frame(k=np.arange(4)).limit(2).cache()


@pytest.mark.parametrize("lit_kind", ["date", "datetime64"])
@pytest.mark.parametrize("source", ["numpy", "parquet"])
def test_date_columns_and_day_literals(ctx, tmp_path, source, lit_kind):
    days = np.array([0, 3651, 3652, 9000, 14609, 14610, 20000])
    dates = days.astype("datetime64[D]")
    vals = np.arange(len(days), dtype=np.int64)
    if source == "numpy":
        frame = ctx.create_frame(d=dates, x=vals)
    else:
        path = str(tmp_path / "d.parquet")
        pq.write_table(pa.table({"d": pa.array(days.astype(np.int32))
                                 .cast(pa.date32()),
                                 "x": pa.array(vals)}), path)
        frame = ctx.read_parquet(path)
    lo, hi = WINDOW
    if lit_kind == "datetime64":
        lo, hi = np.datetime64(lo, "D"), np.datetime64(hi, "D")
    q = frame.filter((col("d") >= lo) & (col("d") <= hi)).sort("x")
    base = planner.fallback_count()
    assert "device tier" in q.explain()
    got = q.collect_columns()
    assert got["d"].dtype == np.dtype("datetime64[D]")
    assert got["d"].tolist() == dates[2:6].tolist()
    assert got["x"].tolist() == [2, 3, 4, 5]
    assert planner.fallback_count() == base
    # rows carry datetime.date, on either tier
    assert q.collect() == q.hint(tier="host").collect() == [
        (EPOCH + datetime.timedelta(days=int(d)), int(x))
        for d, x in zip(days[2:6], vals[2:6])]
    kept = frame.filter(col("d") >= lo)
    assert sorted(kept.to_rdd().collect()) \
        == sorted(kept.hint(tier="host").to_rdd().collect())
    # min/max of a date is a date; arithmetic on one runs on the host tier
    g = frame.group_by("x").agg(F.max("d", "last"))
    assert "device tier" in g.explain()
    assert g.sort("x").collect_columns()["last"].tolist() == dates.tolist()
    shifted = frame.select(e=col("d") > col("x"))
    assert "host tier" in shifted.explain()


def test_a_day_literal_prunes_row_groups(ctx, tmp_path):
    path = str(tmp_path / "days.parquet")
    days = np.concatenate([np.arange(0, 100), np.arange(10000, 10100)])
    pq.write_table(pa.table({"d": pa.array(days.astype(np.int32))
                             .cast(pa.date32()),
                             "x": pa.array(np.arange(200))}), path,
                   row_group_size=100)
    q = ctx.read_parquet(path).filter(col("d") >= datetime.date(1990, 1, 1))
    assert "d>=datetime.date(1990, 1, 1)" in q.explain()
    batches = list(readers.iter_parquet_batches(
        [path], ["x"], [("d", ">=", datetime.date(1990, 1, 1))]))
    assert sum(len(b["x"]) for b in batches) == 100
    assert q.collect_columns()["x"].tolist() == list(range(100, 200))


JOINS = {
    "2x1": (["a", "b"], ["c"]),
    "2x2": (["a", "b"], ["c", "e"]),
    "1x0": (["a"], []),
}


@pytest.mark.parametrize("keys", ["strings", "ints"])
@pytest.mark.parametrize("shape", sorted(JOINS))
def test_joins_carry_every_value_column(ctx, keys, shape):
    lcols, rcols = JOINS[shape]
    rng = np.random.default_rng(3)
    lk = rng.integers(0, 40, 500)
    rk = np.arange(10, 60)
    if keys == "strings":
        lk = np.array([f"key{k:03d}" for k in lk])
        rk = np.array([f"key{k:03d}" for k in rk])

    def column(name, n):
        # on string keys a string value column on each side too
        if keys == "strings" and name in ("b", "e"):
            return np.array([f"s{i % 7}" for i in range(n)])
        return rng.integers(0, 100, n)

    left = ctx.create_frame(k=lk, **{c: column(c, 500) for c in lcols})
    right = ctx.create_frame(k=rk, **{c: column(c, 50) for c in rcols})
    base = planner.fallback_count()
    q = left.join(right, on="k")
    assert "device tier" in q.explain()
    dev = sorted(q.collect())
    assert planner.fallback_count() == base
    assert dev == sorted(q.hint(tier="host").collect())
    assert len(dev) == sum(1 for k in lk if k in set(rk.tolist()))
    if rcols:
        outer = left.join(right, on="k", how="left", fill_value=-1)
        assert sorted(outer.collect()) == sorted(
            outer.hint(tier="host").collect())


def test_unification_past_eight_doublings_stages_once():
    """dense_dict_capacity=2 stages from 128 entries; 8 doublings reach
    32,768, short of these 40,000-entry dictionaries. The table stages
    whole at once and the join is exact."""
    from vega_tpu.tpu.dense_rdd import _DictUnifyRDD

    ctx = v.Context("local", num_workers=2, dense_dict_capacity=2)
    try:
        n = 40_000
        left = ctx.create_frame(k=np.array([f"u{i:06d}" for i in range(n)]),
                                x=np.arange(n))
        right = ctx.create_frame(
            k=np.array([f"u{i:06d}" for i in range(n // 2, n // 2 + n)]),
            y=np.arange(n) * 3)
        q = left.join(right, on="k")
        got = q.collect_columns()
        assert len(got["k"]) == n // 2
        assert np.array_equal(got["y"], (got["x"] - n // 2) * 3)
        rdd = q._compiled().rdd
        rdd.collect_arrays()
        todo, unify = [rdd], []
        while todo:
            node = todo.pop()
            unify += [node] if isinstance(node, _DictUnifyRDD) else []
            todo.extend(getattr(node, "_dense_parents", ()))
        assert unify and all(u._table_capacity == 65536 for u in unify)
    finally:
        ctx.stop()


def test_a_string_sort_stays_lexicographic(ctx):
    words = np.array(["b", "a", "Z", "é", "ab", "aa", "z", "ä", "", "bb"] * 3)
    frame = ctx.create_frame(w=words, x=np.arange(len(words)))
    got = frame.sort("w").collect_columns()["w"].tolist()
    assert got == sorted(words.tolist())
    codes, values = dict_encoding.encode_array(words)
    assert values.tolist() == sorted(set(words.tolist()))
    assert np.array_equal(values[codes], words)
    desc = ctx.dense_from_numpy(words, np.arange(len(words))).sort_by_key(
        ascending=False).collect()
    assert [k for k, _v in desc] == sorted(words.tolist(), reverse=True)


def test_a_dictionary_holds_about_its_strings_bytes():
    rng = np.random.default_rng(5)
    words = _words(rng, 20_000, 20, 100)
    _codes, values = dict_encoding.encode_array(np.array(words * 2))
    string_bytes = sum(len(w.encode()) for w in words)
    assert len(values) == len(words)
    assert string_bytes <= values.nbytes <= 1.1 * string_bytes \
        + 8 * (len(words) + 1)
    # the fixed-width form it replaces: 4 bytes a character, longest wide
    assert np.array(words).nbytes > 3 * values.nbytes


def test_a_merge_runs_once_per_pair_of_dictionaries():
    _c, a = dict_encoding.encode_array(np.array(["x", "b", "q"]))
    _c, b = dict_encoding.encode_array(np.array(["b", "c"]))
    first = dict_encoding.merge_dicts(a, b)
    assert dict_encoding.merge_dicts(a, b) is first
    merged, lmap, rmap = first
    assert merged.tolist() == ["b", "c", "q", "x"]
    assert lmap.tolist() == [0, 2, 3] and rmap.tolist() == [0, 1]


def test_spans_of_a_frame_action(ctx, tmp_path, session):
    dirs, _ids = _amplab_tables(tmp_path, seed=13, visits=2000)
    uv = ctx.read_parquet(dirs["uv"]).cache()
    rk = ctx.read_parquet(dirs["rk"]).cache()
    nested = spans.nested()
    session.start()
    _q3(uv, rk, limit=1).collect()
    first = spans.session()
    _q3(uv, rk, limit=1).collect()
    tally = spans.session()
    session.stop()
    assert spans.nested() == nested
    # set-up's reads built every dictionary once; the second action built
    # none, merged nothing new, and remapped both sides again
    assert tally["dict_build"]["count"] == first["dict_build"]["count"] >= 2
    assert tally["frame_plan"]["count"] == 2
    assert tally["dict_unify"]["count"] == 2
    assert tally["dict_remap_rows"]["count"] \
        == 2 * first["dict_remap_rows"]["count"] > 0
    assert tally["dict_remap_table_entries"]["count"] > 0
    assert "frame_fallbacks" not in tally
    session.start()
    ctx.create_frame(k=np.arange(4)).filter(col("k") > 1).collect()
    ctx.create_frame(s=np.array(["a", "b"])).select(
        t=col("s") + col("s")).collect()
    assert spans.session()["frame_fallbacks"]["count"] == 1
    session.stop()
    assert spans.nested() == nested
