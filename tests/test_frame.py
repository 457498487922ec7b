"""DataFrame layer (vega_tpu/frame): host-vs-device parity for every
verb, whole-stage fusion (ONE program per narrow stage, by mint count),
parquet column/predicate pushdown (reader-level pruning proof), the
silent host-tier fallback for untraceable expressions, and the satellite
reader regression (non-parquet dir -> crisp VegaError)."""

import math
import os

import numpy as np
import pytest

import vega_tpu as v
from vega_tpu.errors import VegaError
from vega_tpu.frame import F, col, lit, udf


def _rows_close(a, b):
    """Row-list equality with float tolerance (device float32 vs host
    float64 reductions may differ in the last ulp)."""
    assert len(a) == len(b), (a, b)
    for ra, rb in zip(a, b):
        assert len(ra) == len(rb), (ra, rb)
        for xa, xb in zip(ra, rb):
            if isinstance(xa, float) or isinstance(xb, float):
                assert math.isclose(xa, xb, rel_tol=1e-6, abs_tol=1e-6), \
                    (ra, rb)
            else:
                assert xa == xb, (ra, rb)


def _parity(frame, sort_key=None):
    """Collect the SAME logical plan on both tiers; rows must match."""
    dev = frame.hint(tier="device").collect()
    host = frame.hint(tier="host").collect()
    if sort_key is not None:
        dev = sorted(dev, key=sort_key)
        host = sorted(host, key=sort_key)
    _rows_close(dev, host)
    return dev


def _frame(ctx, n=60):
    return ctx.create_frame(
        k=(np.arange(n) * 7) % 5,
        x=np.arange(n),
        y=(np.arange(n) * 3) % 11,
    )


# ------------------------------------------------------------ verb parity


def test_select_parity(ctx):
    rows = _parity(_frame(ctx).select("k", "y"), sort_key=lambda r: r)
    assert rows[0] == (0, 0) and len(rows) == 60


def test_select_computed_and_rename_parity(ctx):
    q = _frame(ctx).select("k", total=col("x") + col("y") * 2)
    assert q.columns == ["k", "total"]
    _parity(q, sort_key=lambda r: r)
    _parity(_frame(ctx).rename({"x": "ex"}).select("ex"),
            sort_key=lambda r: r)


def test_filter_parity(ctx):
    q = _frame(ctx).filter((col("x") > 10) & (col("y") != 3))
    rows = _parity(q, sort_key=lambda r: r)
    assert all(r[1] > 10 and r[2] != 3 for r in rows)


def test_with_column_parity(ctx):
    q = _frame(ctx).with_column("z", col("x") * 2 - col("y"))
    rows = _parity(q, sort_key=lambda r: r)
    assert all(r[3] == r[1] * 2 - r[2] for r in rows)


def test_with_column_literal_broadcast_parity(ctx):
    _parity(_frame(ctx).with_column("one", lit(1)).select("k", "one"),
            sort_key=lambda r: r)


def test_group_by_agg_named_op_parity(ctx):
    # Uniform monoid -> named-op segment reduce on device.
    q = _frame(ctx).group_by("k").agg(F.sum("x"), F.sum("y"))
    assert "named-op 'add'" in q.explain()
    rows = _parity(q, sort_key=lambda r: r[0])
    exp = {}
    for i in range(60):
        e = exp.setdefault((i * 7) % 5, [0, 0])
        e[0] += i
        e[1] += (i * 3) % 11
    assert rows == sorted((k, sx, sy) for k, (sx, sy) in exp.items())


def test_group_by_agg_mixed_ops_tuple_combiner_parity(ctx):
    # Mixed monoids -> ONE exchange with a traced tuple combiner.
    q = _frame(ctx).group_by("k").agg(F.sum("x"), F.min("y"), F.max("y"),
                                      F.count(), F.mean("x"))
    assert "tuple combiner" in q.explain()
    _parity(q, sort_key=lambda r: r[0])


def test_group_by_agg_expression_input_parity(ctx):
    q = _frame(ctx).group_by("k").agg(F.sum(col("x") * 2 + 1, "s2"))
    _parity(q, sort_key=lambda r: r[0])


def test_join_inner_parity(ctx):
    a = _frame(ctx).group_by("k").agg(F.sum("x", "sx"))
    b = (_frame(ctx, 30).filter(col("x") % 2 == 0)
         .group_by("k").agg(F.sum("y", "sy")))
    q = a.join(b, on="k")
    _parity(q, sort_key=lambda r: r[0])


def test_join_left_outer_fill_parity(ctx):
    a = _frame(ctx).group_by("k").agg(F.sum("x", "sx"))
    b = (_frame(ctx).filter(col("k") < 3)
         .group_by("k").agg(F.count("c")))
    q = a.join(b, on="k", how="left", fill_value=-1)
    rows = _parity(q, sort_key=lambda r: r[0])
    assert [r[2] for r in rows if r[0] >= 3] == [-1, -1]


def test_sort_and_limit_parity_exact_order(ctx):
    q = (_frame(ctx).select("x", "k").sort("x", ascending=False))
    dev = q.hint(tier="device").collect()
    host = q.hint(tier="host").collect()
    assert dev == host  # global order, not just set equality
    assert dev[0][0] == 59
    lim = q.limit(7)
    assert lim.hint(tier="device").collect() \
        == lim.hint(tier="host").collect()
    assert len(lim.collect()) == 7
    assert lim.count() == 7
    assert q.take(3) == dev[:3]


def test_multi_stage_pipeline_parity(ctx):
    q = (_frame(ctx)
         .filter(col("x") < 50)
         .with_column("z", col("x") + col("y"))
         .group_by("k").agg(F.sum("z", "sz"), F.count("n"))
         .with_column("avgish", col("sz") // col("n"))
         .filter(col("n") > 2)
         .sort("k"))
    dev = q.hint(tier="device").collect()
    host = q.hint(tier="host").collect()
    assert dev == host


# -------------------------------------------------- two-tier fallback


def test_untraceable_udf_falls_back_silently_with_identical_results(ctx):
    table = {i: i * 100 for i in range(5)}

    def lookup(kk):  # Python dict access: no jax trace can exist
        return table[int(kk)]

    q = (_frame(ctx)
         .with_column("m", udf(lookup, col("k")))
         .select("k", "m")
         .sort("k"))
    # auto tier compiles (silently) on the host — no error surfaced.
    assert "host tier" in q.explain()
    rows = q.collect()
    assert rows == q.hint(tier="host").collect()
    assert all(m == k * 100 for k, m in rows)


def test_traceable_udf_stays_on_device(ctx):
    import jax.numpy as jnp

    q = _frame(ctx).with_column("m", udf(lambda c: jnp.abs(c - 5),
                                         col("x")))
    assert "host tier" not in q.explain()
    _parity(q, sort_key=lambda r: r)


def test_tier_device_forced_raises_on_untraceable(ctx):
    q = _frame(ctx).with_column("m", udf(lambda kk: {0: 1}.get(int(kk), 0),
                                         col("k")))
    with pytest.raises(VegaError, match="no device lowering"):
        q.hint(tier="device").collect()


def test_object_dtype_source_falls_back_silently(ctx):
    # A GENUINELY mixed object column has no device form; an all-string
    # object column does (dictionary encoding) and is covered below.
    df = ctx.create_frame(k=np.array([1, 2, 1]),
                          s=np.array(["a", 2, None], dtype=object))
    q = df.filter(col("k") == 1).select("s")
    assert "host tier" in q.explain()
    assert sorted(q.collect(), key=repr) == [("a",), (None,)]


def test_all_string_object_column_devices(ctx):
    # Object columns whose every element is a str dictionary-encode onto
    # the device tier (the pandas/pyarrow pivot shape).
    df = ctx.create_frame(k=np.array([1, 2, 1]),
                          s=np.array(["a", "b", "c"], dtype=object))
    q = df.filter(col("k") == 1).select("s")
    assert "device tier" in q.explain()
    assert sorted(q.collect()) == [("a",), ("c",)]


def test_string_group_key_and_join_compile_to_device(ctx):
    # String group keys / join keys / sort keys ride dictionary codes on
    # the device tier now (PR 20) — same rows as the host path, and the
    # fallback counter proves no silent demotion happened.
    from vega_tpu.frame import planner

    names = np.array(["ada", "bob", "ada", "cy", "bob", "ada"],
                     dtype=object)
    df = ctx.create_frame(name=names, x=np.arange(6))
    g = df.group_by("name").agg(F.sum("x", "sx"), F.count("n")).sort("name")
    base = planner.fallback_count()
    assert "device tier" in g.explain()
    assert g.collect() == [("ada", 0 + 2 + 5, 3), ("bob", 1 + 4, 2),
                           ("cy", 3, 1)]
    assert g.count() == 3
    assert planner.fallback_count() == base
    rows = sorted(df.select("name", "x").to_rdd().collect())
    assert rows[0] == ("ada", 0)
    dims = ctx.create_frame(name=np.array(["ada", "cy"], dtype=object),
                            w=np.array([10, 20]))
    j = g.select("name", "sx").join(dims, on="name").sort("name")
    assert "device tier" in j.explain()
    assert j.collect() == [("ada", 7, 10), ("cy", 3, 20)]


def test_wide_join_falls_back_to_host(ctx):
    # >1 value column per side: the device join carries them all (it fell
    # back to the host tier before the join took named columns); the host
    # tier, asked for, gives the same rows.
    a = ctx.create_frame(k=np.arange(6) % 3, x=np.arange(6),
                         y=np.arange(6) * 2)
    b = ctx.create_frame(k=np.arange(3), z=np.arange(3) * 5)
    q = a.join(b, on="k").sort("k")
    assert "device tier" in q.explain()
    rows = q.collect()
    want = sorted((k, x, 2 * x, 5 * k) for x, k in enumerate(
        np.arange(6) % 3))
    assert sorted(rows) == want and [r[0] for r in rows] == sorted(
        r[0] for r in want)
    assert sorted(q.hint(tier="host").collect()) == want


# -------------------------------------------------- whole-stage fusion


def test_fused_stage_mints_exactly_one_program(ctx):
    from vega_tpu.tpu import dense_rdd as dr

    # Unique literals -> unique program-cache keys (no warm hits).
    salt = len(dr._PROGRAM_CACHE) + 131
    q = (_frame(ctx)
         .select("k", "x")
         .filter(col("x") < salt)
         .with_column("z", col("x") * salt + 1))
    before = dr.program_mints()
    q.collect_columns()
    assert dr.program_mints() - before == 1
    # Warm rerun of the IDENTICAL pipeline: zero new programs.
    q2 = (_frame(ctx)
          .select("k", "x")
          .filter(col("x") < salt)
          .with_column("z", col("x") * salt + 1))
    before = dr.program_mints()
    q2.collect_columns()
    assert dr.program_mints() - before == 0


def test_unfused_hint_mints_one_program_per_verb(ctx):
    from vega_tpu.tpu import dense_rdd as dr

    salt = len(dr._PROGRAM_CACHE) + 977
    q = (_frame(ctx)
         .select("k", "x")
         .filter(col("x") < salt)
         .with_column("z", col("x") * salt + 3)
         .hint(fuse=False))
    before = dr.program_mints()
    fused_cols = q.hint(fuse=True).collect_columns()
    fused_mints = dr.program_mints() - before
    before = dr.program_mints()
    unfused_cols = q.collect_columns()
    unfused_mints = dr.program_mints() - before
    assert fused_mints == 1
    assert unfused_mints >= 3  # one per verb
    for nm in fused_cols:
        np.testing.assert_array_equal(fused_cols[nm], unfused_cols[nm])


# -------------------------------------------------- parquet pushdown


@pytest.fixture()
def parquet_dir(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    n = 1000
    table = pa.table({f"c{i}": np.arange(n) * (i + 1) for i in range(6)})
    pq.write_table(table, str(tmp_path / "part0.parquet"),
                   row_group_size=100)
    return str(tmp_path)


def test_column_pruning_reaches_the_reader(ctx, parquet_dir):
    from vega_tpu.io.readers import (discover_parquet_files,
                                     iter_parquet_batches)

    q = ctx.read_parquet(parquet_dir).select("c0", "c3")
    assert "cols=[c0,c3]" in q.explain()
    # Reader-level proof: a 6-column file queried for 2 materializes
    # only 2 — every block leaving the reader has exactly those keys.
    blocks = list(iter_parquet_batches(
        discover_parquet_files(parquet_dir), ["c0", "c3"]))
    assert blocks and all(sorted(b) == ["c0", "c3"] for b in blocks)
    # And the device plan's source block carries exactly 2 columns.
    compiled = q.hint(tier="device")._compiled()
    assert len(compiled.rdd._schema()) == 2
    _parity(q, sort_key=lambda r: r)


def test_predicate_pushdown_into_scan_and_rowgroup_skip(ctx, parquet_dir):
    from vega_tpu.io.readers import (discover_parquet_files,
                                     iter_parquet_batches)

    q = (ctx.read_parquet(parquet_dir)
         .filter(col("c0") < 100)
         .select("c0", "c2"))
    assert "c0<100" in q.explain()  # conjunct landed in the scan
    rows = _parity(q, sort_key=lambda r: r)
    assert len(rows) == 100
    # Reader-level: the predicate prunes ROWS inside the reader (row-group
    # statistics skip 9 of 10 groups; the survivor is mask-filtered).
    blocks = list(iter_parquet_batches(
        discover_parquet_files(parquet_dir), ["c0"], [("c0", "<", 100)]))
    assert sum(len(b["c0"]) for b in blocks) == 100


def test_predicate_on_pruned_output_column(ctx, parquet_dir):
    # Filter column read for the mask, dropped from the output.
    q = ctx.read_parquet(parquet_dir).filter(col("c5") > 4000).select("c1")
    rows = _parity(q, sort_key=lambda r: r)
    assert len(rows) == sum(1 for i in range(1000) if i * 6 > 4000)


def test_pushdown_disabled_reads_everything(ctx, parquet_dir):
    q = (ctx.read_parquet(parquet_dir).select("c0", "c3")
         .hint(pushdown=False))
    compiled = q.hint(tier="device")._compiled()
    # Unpruned scan: all 6 columns reach the SOURCE block (the select
    # then projects them away in-stage).
    node = compiled.rdd
    while node._dense_parents:
        node = node._dense_parents[0]
    assert len(node._schema()) == 6
    _parity(q, sort_key=lambda r: r)


def test_read_parquet_columns_wrapper(ctx, parquet_dir):
    q = ctx.read_parquet(parquet_dir, columns=["c1", "c4"])
    assert q.columns == ["c1", "c4"]
    rows = q.sort("c1").limit(3).collect()
    assert rows == [(0, 0), (2, 5), (4, 10)]  # c1 = 2i, c4 = 5i
    with pytest.raises(VegaError, match="unknown column"):
        ctx.read_parquet(parquet_dir, columns=["nope"])
    # parquet_file keeps returning the raw block RDD.
    blocks = ctx.parquet_file(parquet_dir, columns=["c0"]).collect()
    assert all(sorted(b) == ["c0"] for b in blocks)


def test_parquet_string_group_join_sort_on_device(ctx, tmp_path):
    """PR 20: parquet string columns ride pyarrow dictionary pages
    (codes + dictionary, no object-array pivot) onto the device tier —
    group/agg, sort, and join on the string key compile to device with
    host-tier parity."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    n = 300
    words = [f"w{i % 7:02d}" for i in range(n)]
    pq.write_table(pa.table({"w": words, "x": np.arange(n)}),
                   str(tmp_path / "p.parquet"), row_group_size=64)
    q = (ctx.read_parquet(str(tmp_path)).group_by("w")
         .agg(F.sum("x", "sx"), F.count("cnt")).sort("w"))
    assert "device tier" in q.explain()
    rows = _parity(q)
    assert [r[0] for r in rows] == sorted(set(words))
    exp = {}
    for w, x in zip(words, range(n)):
        exp[w] = exp.get(w, 0) + x
    assert {r[0]: r[1] for r in rows} == exp

    dims = ctx.create_frame(w=np.array([f"w{i:02d}" for i in range(3, 10)],
                                       dtype=object),
                            z=np.arange(7))
    j = (ctx.read_parquet(str(tmp_path)).group_by("w")
         .agg(F.sum("x", "sx")).join(dims, on="w").sort("w"))
    assert "device tier" in j.explain()
    _parity(j)


def test_parquet_string_nulls_fall_back_correctly(ctx, tmp_path):
    """A nullable string column has no code slot for null — the reader's
    row-group null statistics gate it to the host tier, which preserves
    None exactly."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    pq.write_table(
        pa.table({"w": ["a", None, "b", "a"], "x": [1, 2, 3, 4]}),
        str(tmp_path / "p.parquet"))
    q = ctx.read_parquet(str(tmp_path)).select("w", "x")
    assert "host tier" in q.explain()
    assert sorted(q.collect(), key=repr) == sorted(
        [("a", 1), (None, 2), ("b", 3), ("a", 4)], key=repr)


def test_frame_string_sort_parity_and_filter_fallback(ctx):
    """Dedicated string-sort leg (rank codes ARE sort order), plus the
    counted fallback for a string-literal filter — comparisons compute
    on codes, so the planner must demote them, visibly."""
    from vega_tpu.frame import planner

    names = np.array(["pear", "apple", "fig", "apple", "date"],
                     dtype=object)
    df = ctx.create_frame(name=names, x=np.arange(5))
    q = df.select("name", "x").sort("name")
    assert "device tier" in q.explain()
    rows = _parity(q)
    assert [r[0] for r in rows] == sorted(names.tolist())

    base = planner.fallback_count()
    f = df.filter(col("name") == lit("apple")).select("x")
    assert "host tier" in f.explain()
    assert sorted(f.collect()) == [(1,), (3,)]
    assert planner.fallback_count() > base
    assert "string" in (planner.last_fallback() or "")


def test_parquet_dir_without_parquet_files_raises_crisply(ctx, tmp_path):
    d = tmp_path / "csvs"
    d.mkdir()
    for nm in ("a.csv", "b.csv"):
        (d / nm).write_text("x,y\n1,2\n")
    with pytest.raises(VegaError) as excinfo:
        ctx.read_parquet(str(d)).collect()
    assert str(d) in str(excinfo.value)
    assert "a.csv" in str(excinfo.value)
    # Same crisp error through the raw reader RDD route.
    with pytest.raises(VegaError):
        ctx.parquet_file(str(d)).collect()
    # An EMPTY match errors too (never a silent empty result).
    with pytest.raises(VegaError, match="matches no files"):
        ctx.read_parquet(str(tmp_path / "nothing" / "*.parquet")).collect()


def test_explicit_file_without_extension_still_reads(ctx, tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    p = str(tmp_path / "data_no_ext")
    pq.write_table(pa.table({"a": np.arange(5)}), p)
    assert ctx.read_parquet(p).count() == 5


def test_int64_beyond_int32_parquet_falls_back_to_host(ctx, tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    p = str(tmp_path / "wide.parquet")
    pq.write_table(pa.table({"k": np.array([1, 2, 3]),
                             "big": np.array([2**40, 2, 3])}), p)
    q = ctx.read_parquet(p).select("k", "big").sort("k")
    assert "host tier" in q.explain()
    assert q.collect() == [(1, 2**40), (2, 2), (3, 3)]


# -------------------------------------------------- API contract edges


def test_api_errors(ctx):
    df = _frame(ctx)
    with pytest.raises(VegaError, match="unknown column"):
        df.select("nope")
    with pytest.raises(VegaError, match="filter"):
        df.select("k").filter(col("x") > 0)
    with pytest.raises(VegaError, match="group key"):
        df.group_by("nope")
    with pytest.raises(VegaError, match="terminal"):
        df.limit(3).select("k")
    with pytest.raises(VegaError, match="terminal"):
        # a limited frame as the join's RIGHT side is just as terminal
        df.group_by("k").agg(F.sum("x", "s")).join(
            df.group_by("k").agg(F.sum("y", "t")).limit(2), on="k")
    with pytest.raises(VegaError, match="unknown hint"):
        df.hint(warp_speed=True)
    with pytest.raises(VegaError, match="valid values"):
        df.hint(tier="Device")  # typo'd value must not demote to auto
    with pytest.raises(VegaError, match="valid values"):
        df.hint(exchange="rnig")
    with pytest.raises(VegaError, match="takes a bool"):
        df.hint(fuse="yes")
    with pytest.raises(VegaError, match="rename"):
        df.rename({"nope": "x2"})
    with pytest.raises(VegaError, match="duplicate"):
        df.group_by("k").agg(F.sum("x", "s"), F.sum("y", "s"))
    with pytest.raises(VegaError, match="collide"):
        df.join(_frame(ctx), on="k")  # x/y collide


def test_reserved_block_names_are_sanitized(ctx):
    # A frame column literally named "k" (the canonical KEY) must not
    # fabricate a pair layout, and ".lo"-suffixed names must not be
    # consumed as wide low words.
    df = ctx.create_frame({"k": np.arange(8) % 3, "v.lo": np.arange(8)})
    rows = _parity(df.filter(col("v.lo") > 2), sort_key=lambda r: r)
    assert len(rows) == 5


def test_to_rdd_hands_back_row_tuples(ctx):
    q = _frame(ctx).select("k", "x").filter(col("x") < 5)
    rows = sorted(q.to_rdd().collect())
    assert rows == sorted(((i * 7) % 5, i) for i in range(5))
    # host plan to_rdd too
    rows_h = sorted(q.hint(tier="host").to_rdd().collect())
    assert rows_h == rows


def test_collect_columns_shapes(ctx):
    cols = _frame(ctx).group_by("k").agg(F.count("n")).collect_columns()
    assert sorted(cols) == ["k", "n"]
    assert int(np.asarray(cols["n"]).sum()) == 60


def test_exchange_hint_ring(ctx):
    q = (_frame(ctx).group_by("k").agg(F.sum("x", "s"))
         .hint(exchange="ring").sort("k"))
    assert q.collect() == (_frame(ctx).group_by("k")
                           .agg(F.sum("x", "s")).sort("k").collect())


def test_literal_only_select_keeps_row_count(ctx):
    # Pruning must not drop the scan to zero columns when the projection
    # references none — the row COUNT is still live data.
    q = ctx.create_frame(k=np.arange(5)).select(c=lit(7))
    assert q.collect() == [(7,)] * 5
    assert q.count() == 5
    assert q.hint(tier="host").collect() == [(7,)] * 5
    assert q.hint(pushdown=False).collect() == [(7,)] * 5


def test_float_predicates_stay_residual(ctx, tmp_path):
    # A reader-side f64 compare can disagree with the device stage's
    # narrowed-f32 compare, so float conjuncts must NOT push into the
    # scan: pushdown on/off must be unobservable per tier.
    import pyarrow as pa
    import pyarrow.parquet as pq

    edge = float(np.float32(0.15)) + 1e-12  # f64 > 0.15, f32 == 0.15
    p = str(tmp_path / "f.parquet")
    pq.write_table(pa.table({"i": np.arange(3),
                             "f": np.array([edge, 0.5, 0.9])}), p)
    q = ctx.read_parquet(p).filter(col("f") > 0.15).select("i")
    assert "f>" not in q.explain()  # stayed a residual in-plan filter
    assert q.collect() == q.hint(pushdown=False).collect()
    # Integer conjuncts still push.
    q2 = ctx.read_parquet(p).filter(col("i") >= 1).select("i")
    assert "i>=1" in q2.explain()
    assert q2.collect() == q2.hint(pushdown=False).collect()


def test_udf_scalar_first_arg_host_fallback(ctx):
    # The per-element host fallback must size its loop from the first
    # ARRAY argument — a literal first arg must not shrink the column.
    table = {i: i + 1 for i in range(100)}

    def add_base(base, v):  # dict access on v: never vectorizes
        return base + table[int(v)]

    q = (ctx.create_frame(x=np.arange(4))
         .with_column("m", udf(add_base, lit(10), col("x")))
         .sort("x"))
    assert "host tier" in q.explain()
    assert q.collect() == [(i, 10 + i + 1) for i in range(4)]


def test_to_rdd_honors_limit(ctx):
    q = _frame(ctx).select("x").sort("x").limit(3)
    assert sorted(q.to_rdd().collect()) == [(0,), (1,), (2,)]
    assert sorted(q.hint(tier="host").to_rdd().collect()) \
        == [(0,), (1,), (2,)]


def test_shuffle_plan_hint_applies_and_restores(ctx):
    from vega_tpu.env import Env

    conf = Env.get().conf
    saved = conf.shuffle_plan
    q = (_frame(ctx).group_by("k").agg(F.sum("x", "s"))
         .hint(tier="host", shuffle_plan="push").sort("k"))
    rows = q.collect()
    assert conf.shuffle_plan == saved  # restored after the action
    assert rows == (_frame(ctx).group_by("k").agg(F.sum("x", "s"))
                    .sort("k").collect())
