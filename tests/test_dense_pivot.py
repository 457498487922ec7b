"""The collect() pivot: rows come out of C-level iterators equal, in value
and in Python type, to the per-row expressions they replace, and no Python
frame a row lets the cyclic collector in while they are made."""

import gc
import sys

import numpy as np
import pytest

BIG = 1 << 40
N, KEYS = 4000, 37


@pytest.fixture()
def dctx():
    import vega_tpu as v

    context = v.Context("local", num_workers=2)
    yield context
    context.stop()


def _pairs(ctx):
    keys = (np.arange(N, dtype=np.int64) * 7) % KEYS
    vals = (np.arange(N, dtype=np.float64) * 3) % 11
    return ctx.dense_from_numpy(keys, vals)


def _table(ctx, wide: bool):
    vals = np.arange(KEYS, dtype=np.int64) * 5
    return ctx.dense_from_numpy(np.arange(KEYS, dtype=np.int64),
                                vals + BIG if wide else vals)


def _wide_rows(ctx):
    return ctx.dense_from_columns(
        {"k": np.arange(N, dtype=np.int32) % KEYS,
         "a": np.arange(N, dtype=np.float32) % 13,
         "b": np.arange(N, dtype=np.int32) * 3}, key="k")


def _strings(ctx):
    keys = np.array([f"k{(i * 7) % KEYS:02d}" for i in range(N)])
    return ctx.dense_from_numpy(keys, np.arange(N, dtype=np.int32) % 11)


def _by_row(cols: dict) -> list:
    """Rows built one at a time, by Python bytecode, from numpy scalars."""
    names = list(cols)
    return [tuple(cols[nm][i].item() for nm in names)
            for i in range(len(cols[names[0]]))]


def _values_only(ctx):
    rdd = _pairs(ctx).values_dense()
    return rdd.collect(), [row[0] for row in _by_row(rdd.collect_arrays())]


def _pair_rows(ctx):
    rdd = _pairs(ctx)
    return rdd.collect(), _by_row(rdd.collect_arrays())


def _wide(ctx):
    rdd = _wide_rows(ctx)
    return rdd.collect(), _by_row(rdd.collect_arrays())


def _join(ctx, wide: bool):
    rdd = _pairs(ctx).reduce_by_key(op="add").join(_table(ctx, wide))
    cols = rdd.collect_arrays()
    # the generator expression _JoinRDD._rows was
    return rdd.collect(), list(
        (k, (lv, rv)) for k, lv, rv in zip(
            cols["k"].tolist(), cols["lv"].tolist(), cols["rv"].tolist()))


def _group_by_key(ctx):
    rdd = _pairs(ctx).group_by_key()
    keys, offs, vals = rdd.collect_grouped()
    return rdd.collect(), [
        (keys[i].item(), [v.item() for v in vals[offs[i]:offs[i + 1]]])
        for i in range(len(keys))]


def _cogroup(ctx):
    rdd = _pairs(ctx).cogroup(_table(ctx, wide=True))
    keys, loff, lv, roff, rv = rdd.collect_grouped()
    return rdd.collect(), [
        (keys[i].item(), ([v.item() for v in lv[loff[i]:loff[i + 1]]],
                          [v.item() for v in rv[roff[i]:roff[i + 1]]]))
        for i in range(len(keys))]


def _ordered(build, n: int, largest: bool):
    """take_ordered / top against Python's sort of the by-row rows."""
    def shape(ctx):
        rdd = build(ctx)
        rows = _by_row(rdd.collect_arrays())
        if len(rows[0]) == 1:
            rows = [row[0] for row in rows]
        got = rdd.top(n) if largest else rdd.take_ordered(n)
        return got, sorted(rows, reverse=largest)[:n]
    return shape


SHAPES = {
    "values_only": _values_only,
    "pairs": _pair_rows,
    "wide_rows": _wide,
    "join": lambda ctx: _join(ctx, wide=False),
    "join_int64_wide_values": lambda ctx: _join(ctx, wide=True),
    "group_by_key": _group_by_key,
    "cogroup": _cogroup,
    "take_ordered_pairs": _ordered(_pairs, 9, largest=False),
    "top_pairs": _ordered(_pairs, 9, largest=True),
    "take_ordered_wide_rows": _ordered(_wide_rows, 5, largest=False),
    "top_int64_wide_pairs": _ordered(lambda ctx: _table(ctx, True), 4,
                                     largest=True),
    "take_ordered_keyless_int64": _ordered(
        lambda ctx: ctx.dense_from_numpy(
            np.arange(50, dtype=np.int64)[::-1] + BIG), 6, largest=False),
    "take_ordered_string_keys": _ordered(_strings, 7, largest=False),
    "take_ordered_values": _ordered(
        lambda ctx: _pairs(ctx).values_dense(), 5, largest=False),
    "top_values": _ordered(lambda ctx: _pairs(ctx).values_dense(), 5,
                           largest=True),
}


def _types(x):
    if isinstance(x, (list, tuple)):
        return (type(x), [_types(e) for e in x])
    return type(x)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_pivot_rows_equal_the_per_row_expression(dctx, session, shape):
    from vega_tpu.tpu import spans

    with session:
        got, expected = SHAPES[shape](dctx)
    assert len(got) > 0
    assert got == expected
    assert _types(got) == _types(expected)
    tally = spans.session()
    assert tally["pivot"]["count"] >= 1
    assert spans.nested() == 0


# the shapes whose collect() makes tuples of columns (_pivot_rows)
TUPLE_SHAPES = ["join", "join_int64_wide_values", "pairs", "wide_rows"]


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_pivot_rows_without_the_native_module(dctx, session, monkeypatch,
                                              shape):
    """With no compiled module every pivot takes tolist() and zip: the same
    rows and types, counted under pivot_python_rows."""
    from vega_tpu import native
    from vega_tpu.tpu import spans

    monkeypatch.setattr(native, "get", lambda: None)
    spans.new_session()
    with session:
        got, expected = SHAPES[shape](dctx)
    assert got == expected
    assert _types(got) == _types(expected)
    tally = spans.session()
    assert "pivot_native_rows" not in tally
    if shape in TUPLE_SHAPES:
        assert tally["pivot_python_rows"]["count"] == len(got)
    else:
        assert "pivot_python_rows" not in tally


@pytest.mark.parametrize("shape", TUPLE_SHAPES)
def test_pivot_rows_are_native_where_the_module_loads(dctx, session, shape):
    from vega_tpu import native
    from vega_tpu.tpu import spans

    if native.get() is None:
        pytest.skip("native build unavailable")
    spans.new_session()
    with session:
        got, _expected = SHAPES[shape](dctx)
    tally = spans.session()
    assert tally["pivot_native_rows"]["count"] == len(got) > 0
    assert "pivot_python_rows" not in tally
    assert not any(gc.is_tracked(row) for row in got)


def test_join_rows_are_c_level_iterators():
    """No generator frame a row: `_rows` hands `compute` (and `collect`
    where the native builder is absent) a zip of a zip."""
    from vega_tpu.tpu.dense_rdd import _JoinRDD

    cols = {"k": np.array([3, 4], np.int64),
            "lv": np.array([BIG, 2], np.int64),
            "rv": np.array([0.5, 1.5], np.float32)}
    rows = _JoinRDD._rows(cols)
    assert type(rows) is zip
    assert list(rows) == [(3, (BIG, 0.5)), (4, (2, 1.5))]


@pytest.mark.skipif(sys.version_info < (3, 12),
                    reason="before 3.12 a collection runs at the allocation")
def test_a_200k_row_join_pivot_lets_one_collection_in(dctx, monkeypatch):
    """400,000 tuples are 571 young generations' worth. The generator
    expression ran them all, one at the eval breaker of every 350th row;
    list(zip(...)) reaches an eval breaker once, after the last row."""
    from vega_tpu.tpu import spans

    n = 200_000
    keys = np.arange(n, dtype=np.int64)
    left = dctx.dense_from_numpy(keys, keys % 1009)
    right = dctx.dense_from_numpy(keys, keys % 7)
    stops, marks = [], []

    def on_gc(phase, _info):
        if phase == "stop":
            stops.append(None)

    class marked(spans.span):
        __slots__ = ()

        def __enter__(self):
            if self.name == "pivot":
                marks.append(len(stops))
            return super().__enter__()

        def __exit__(self, *exc):
            out = super().__exit__(*exc)
            if self.name == "pivot":
                marks.append(len(stops))
            return out

    monkeypatch.setattr(spans, "span", marked)
    was = gc.isenabled()
    gc.enable()
    gc.callbacks.append(on_gc)
    try:
        rows = left.join(right).collect()
    finally:
        gc.callbacks.remove(on_gc)
        if not was:
            gc.disable()
    assert sorted(rows) == [(k, (k % 1009, k % 7)) for k in range(n)]
    assert len(marks) == 2
    assert marks[1] - marks[0] <= 1
