"""`join(...).map_values(f)` over the joined block's two columns stays on the
device: f is traced on the pair (lv, rv), as the host tier calls it on
(k, (lv, rv)) rows. The action is the benchmark's
(`agg_join_256m_zipf_4chip.batch`): fact.join(table) -> product of the two
value columns -> reduce_by_key(add) -> collect_arrays(), on Zipf keys against
numpy in float64 under the cell's three limits. Then every fallback the
two-tier contract names, the one exchange of an action on four devices, and
the exchange's fill counters (`exchange_rows`, `exchange_slots`)."""

import numpy as np
import pytest

import vega_tpu as v
from test_dense_zipf import dctx, on_devices, zipf_keys  # noqa: F401

HOT_LIMIT = 2.0 ** -18  # relative, for a key whose sum float32 cannot hold
EXACT_BELOW = 2.0 ** 24


def product(vw):
    return vw[0] * vw[1]


def _sources(ctx, rows, n_keys, seed, zipf=True):
    rng = np.random.default_rng(seed)
    keys = (zipf_keys(rng, rows, n_keys) if zipf
            else rng.integers(0, n_keys, rows, dtype=np.int64))
    vals = rng.integers(0, 1009, rows).astype(np.float64)
    tvals = rng.integers(0, 1000, n_keys).astype(np.float64)
    return (keys, vals, tvals, ctx.dense_from_numpy(keys, vals),
            ctx.dense_from_numpy(np.arange(n_keys, dtype=np.int64), tvals))


def _under_the_three_limits(cols, keys, vals, tvals) -> int:
    """The cell's comparison: the keys returned are the keys present, a sum
    under 2^24 is exact, any other is within 2^-18; how many were hot."""
    assert set(cols) == {"k", "v"}
    order = np.argsort(cols["k"], kind="stable")
    k, got = cols["k"][order], cols["v"][order].astype(np.float64)
    ref = np.bincount(keys, weights=vals * tvals[keys], minlength=len(tvals))
    present = np.flatnonzero(np.bincount(keys, minlength=len(tvals)))
    assert np.array_equal(k, present)  # keys_wrong 0
    ref = ref[present]
    hot = ref >= EXACT_BELOW
    assert np.array_equal(got[~hot], ref[~hot])  # exact keys: limit 0
    assert np.max(np.abs(got[hot] - ref[hot]) / ref[hot],
                  initial=0.0) <= HOT_LIMIT
    return int(hot.sum())


@pytest.mark.parametrize("devices", [1, 4])
def test_join_product_reduce_on_zipf_keys(dctx, on_devices, devices):
    """1M rows over 100k keys: the hottest key has 120,000 rows, a product
    is up to a million, so thousands of sums pass 2^24 (a 70-row key's
    already does) and the hottest is 3e10."""
    from vega_tpu.tpu.dense_rdd import DenseRDD

    on_devices(devices)
    rows, n_keys = 1_000_000, 100_000
    keys, vals, tvals, pairs, table = _sources(dctx, rows, n_keys, 34)
    tasks = dctx.metrics_summary()["tasks"]
    joined = pairs.join(table)
    prod = joined.map_values(product)
    out = prod.reduce_by_key(op="add")
    cols = out.collect_arrays()
    assert all(isinstance(node, DenseRDD) for node in (joined, prod, out))
    assert dctx.metrics_summary()["tasks"] == tasks  # no host-tier task
    hot = _under_the_three_limits(cols, keys, vals, tvals)
    assert hot > 1000 and np.bincount(keys).max() > 100_000


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("outer", [False, True])
def test_the_same_closure_gives_the_same_rows_on_both_tiers(
        dctx, on_devices, devices, outer):
    """The joined map on the device against the same closure through
    .to_rdd() (the host tier calls it on (k, (lv, rv)) rows); an inner join
    and a left outer join, whose unmatched rows carry the fill."""
    from vega_tpu.tpu.dense_rdd import _MapValuesRDD

    on_devices(devices)
    rng = np.random.default_rng(35)
    lk = rng.integers(0, 60, 3000, dtype=np.int64)
    lv = rng.integers(0, 1009, 3000).astype(np.float64)
    left = dctx.dense_from_numpy(lk, lv)
    right = dctx.dense_from_numpy(np.arange(40, dtype=np.int64),
                                  rng.integers(0, 1000, 40).astype(np.float64))

    def joined():
        return (left.left_outer_join(right, fill_value=7.0) if outer
                else left.join(right))

    f = lambda vw: vw[0] * vw[1] + vw[1]  # noqa: E731
    dev = joined().map_values(f)
    assert isinstance(dev, _MapValuesRDD)
    assert [nm for nm, _ in dev._schema()] == ["k", "v"]
    assert dev.hash_placed and dev.key_sorted
    host = joined().to_rdd().map_values(f)
    assert not isinstance(host, _MapValuesRDD)
    got = sorted(dev.collect())
    assert got == sorted(host.collect())
    assert len(got) == (3000 if outer else np.count_nonzero(lk < 40))


def _untraceable(vw):
    return float(vw[0]) * 2 if vw[1] > 3 else -1.0


def _returns_a_pair(vw):
    return (vw[1], vw[0])


@pytest.mark.parametrize("case", ["untraceable", "tuple", "wide_int64_side",
                                  "string_side"])
def test_fallbacks_give_the_host_tiers_answer(dctx, case):
    """What the device cannot trace falls back to the host tier silently,
    with the answer the host tier gives: a closure that branches on a value
    or returns a pair, an int64 side beyond int32 (two words on the device),
    a string side (dictionary codes on the device)."""
    from vega_tpu.tpu.dense_rdd import DenseRDD, _JoinRDD

    rng = np.random.default_rng(36)
    lk = rng.integers(0, 50, 1000, dtype=np.int64)
    lv = rng.integers(0, 1009, 1000).astype(np.float64)
    rk = np.arange(50, dtype=np.int64)
    rv = rng.integers(0, 1000, 50).astype(np.float64)
    f = {"untraceable": _untraceable, "tuple": _returns_a_pair}.get(case)
    if case == "wide_int64_side":
        lv = rng.integers(2 ** 40, 2 ** 41, 1000).astype(np.int64)
        f = lambda vw: vw[0] - int(vw[1])  # noqa: E731
    elif case == "string_side":
        rv = np.array([f"name{i:02d}" for i in range(50)])
        f = lambda vw: f"{vw[1]}:{vw[0]:.0f}"  # noqa: E731
    joined = dctx.dense_from_numpy(lk, lv).join(dctx.dense_from_numpy(rk, rv))
    assert isinstance(joined, _JoinRDD)
    mapped = joined.map_values(f)
    assert not isinstance(mapped, DenseRDD)
    rv_of = dict(zip(rk.tolist(), rv.tolist()))
    expect = sorted((k, f((x, rv_of[k]))) for k, x in zip(lk.tolist(),
                                                          lv.tolist()))
    assert sorted(mapped.collect()) == expect


@pytest.mark.parametrize("names", [("a", "b"), ("lv", "rv")])
def test_named_two_column_blocks_still_raise(dctx, names):
    """A named block has no host (k, v) row form whatever its columns are
    called: only a join's own output maps over the pair."""
    blk = dctx.dense_from_columns(
        {"k": np.arange(10), names[0]: np.arange(10.0),
         names[1]: np.arange(10.0)}, key="k")
    with pytest.raises(v.VegaError, match="exactly one value column"):
        blk.map_values(product)


# ---- one exchange an action, and how full it ran ----------------------------

ROWS, KEYS = 200_000, 20_000


def _fill(tally):
    return tuple(tally.get(name, {"count": 0})["count"]
                 for name in ("exchange_rows", "exchange_slots"))


def test_the_reduce_after_the_joined_map_moves_nothing(dctx, on_devices,
                                                       session):
    """Four devices: the join's all_to_all is the action's one collective.
    The joined map keeps placement and order, so the reduce's exchange is a
    passthrough (the tally's `exchange` counts it: a call of _run_exchange)
    and plans no collective; cold (histogram-sized) and warm (hinted, the
    launch settled at the fetch) alike."""
    from vega_tpu.tpu import exchange_plan, spans

    on_devices(4)
    keys, vals, tvals, pairs, table = _sources(dctx, ROWS, KEYS, 37)
    for run in ("cold", "warm"):
        planned = sum(exchange_plan.plan_counters().values())
        spans.new_session()
        with session:
            out = pairs.join(table).map_values(product).reduce_by_key(op="add")
            cols = out.collect_arrays()
        assert out._elided is True
        assert sum(exchange_plan.plan_counters().values()) - planned == 1
        tally = spans.session()
        assert tally["exchange"]["count"] == tally["exchange_round"]["count"] == 2
        assert "exchange_repair" not in tally
        # the join's two sides, and nothing for the passthrough
        rows, slots = _fill(tally)
        assert rows == ROWS + KEYS and slots % (2 * 4) == 0
        assert sorted(tally["launch"]["by_kind"]) == (
            ["hash_hist", "join", "narrow", "rbk"] if run == "cold"
            else ["join", "narrow", "rbk"])
        _under_the_three_limits(cols, keys, vals, tvals)


@pytest.mark.parametrize("path", ["blocking", "deferred"])
def test_fill_counters_of_a_join_whose_sides_both_move(dctx, on_devices,
                                                       session, path):
    """rows = the two sides' sizes, slots = 2 sides x 4 shards x out_cap;
    the first run sizes from histograms and fetches (blocking), the second
    launches on the learned hint and is counted when it settles."""
    from vega_tpu.tpu import spans

    on_devices(4)
    _keys, _vals, _tvals, pairs, table = _sources(dctx, ROWS, KEYS, 38)
    if path == "deferred":
        assert pairs.join(table).count() == ROWS  # learns the hint
    joined = pairs.join(table)
    spans.new_session()
    with session:
        assert joined.count() == ROWS
    assert joined._last_attempts == 1
    assert ("_last_counts_host" in joined.__dict__
            and (joined._last_counts_host is None) == (path == "deferred"))
    _slot, out_cap = dctx.__dict__["_dense_capacity_hints"][joined._hint_key()]
    assert _fill(spans.session()) == (ROWS + KEYS, 2 * 4 * out_cap)
    # uniform-ish sides of unequal size in one capacity: under half full
    assert out_cap >= ROWS / 4 and ROWS + KEYS < 2 * 4 * out_cap


@pytest.mark.parametrize("path", ["blocking", "deferred"])
def test_fill_counters_of_a_join_with_one_side_elided(dctx, on_devices,
                                                      session, path):
    """reduced.join(table): the reduce's output is hash-placed and stays;
    only the table's rows and slots are counted. The reduce's own exchange
    sends combiner rows whose number the host does not hold: not counted."""
    from vega_tpu.tpu import spans

    on_devices(4)
    keys, _vals, _tvals, pairs, table = _sources(dctx, ROWS, KEYS, 39,
                                                 zipf=False)
    present = len(np.unique(keys))
    reduced = pairs.reduce_by_key(op="add")
    assert reduced.count() == present
    if path == "deferred":
        assert reduced.join(table).count() == present
    joined = reduced.join(table)
    spans.new_session()
    with session:
        assert joined.count() == present
    assert joined._elided == (True, False)
    _slot, out_cap = dctx.__dict__["_dense_capacity_hints"][joined._hint_key()]
    assert _fill(spans.session()) == (KEYS, 4 * out_cap)


def test_a_side_whose_rows_the_host_does_not_hold_is_not_counted(
        dctx, on_devices, session):
    """A filter fused into the join's program: the rows it lets through are
    known on the device alone, so that side adds neither rows nor slots and
    no transfer is made to find out."""
    from vega_tpu.tpu import spans

    on_devices(4)
    _keys, vals, _tvals, pairs, table = _sources(dctx, ROWS, KEYS, 40)
    spans.new_session()
    with session:
        n = pairs.filter(lambda kv: kv[1] >= 504).join(table).count()
    assert n == np.count_nonzero(vals >= 504)
    tally = spans.session()
    rows, slots = _fill(tally)
    assert rows == KEYS and slots > 0 and slots % 4 == 0
    # a histogram a side and the launch's own (counts, overflow)
    assert tally["fetch"]["count"] == 3


def test_no_fill_entry_with_the_profiler_off(dctx, on_devices):
    from vega_tpu.tpu import spans

    on_devices(4)
    _keys, _vals, _tvals, pairs, table = _sources(dctx, ROWS, KEYS, 41)
    before = spans.session()
    assert pairs.join(table).count() == ROWS
    assert pairs.join(table).count() == ROWS  # deferred, settled by count()
    assert spans.session() == before
    spans.count("exchange_rows", 5)
    assert spans.session() == before


def test_count_adds_n(session):
    from vega_tpu.tpu import spans

    spans.new_session()
    with session:
        spans.count("exchange_rows", 5)
        spans.count("exchange_rows", 7)
        spans.count("exchange")
    tally = spans.session()
    assert tally["exchange_rows"] == {"count": 12, "seconds": 0.0, "bytes": 0,
                                      "by_kind": {}}
    assert tally["exchange"]["count"] == 1
