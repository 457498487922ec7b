"""The sort across shards against the benchmark's plain reference
(`perfbench/configs/sort_64m.py`: a stable `np.argsort`, numpy alone): the
action of `sort_256m_4chip.batch`, `sort_by_key().collect_arrays()` then
`take_ordered(n)`, on the CPU mesh at 4 and 8 shards, exactly, on seeded data
that the chip-sized data cannot hold: the whole int64 range with its extremes,
duplicate keys that straddle a bound and come from different shards, a
descending sort, every key equal, a shard with no rows, more shards than
distinct keys. Then the cell's exchange plans at the planner's edge."""

import numpy as np
import pytest

from test_dense_zipf import dctx, on_devices  # noqa: F401

I64 = np.iinfo(np.int64)
ROWS = 24_000


def _full_range(rng):
    keys = rng.integers(I64.min, I64.max, ROWS, dtype=np.int64, endpoint=True)
    keys[rng.choice(ROWS, 6, replace=False)] = [I64.min, I64.max, I64.min + 1,
                                                I64.max - 1, 0, -1]
    return keys


def _straddling_duplicates(rng):
    # 40 wide keys, 600 rows each, every key on every shard: whatever the
    # three (or seven) sampled bounds are, each is one of these keys
    distinct = rng.integers(I64.min, I64.max, 40, dtype=np.int64)
    return distinct[rng.integers(0, 40, ROWS)]


def _all_equal(rng):
    return np.full(ROWS, 2**40 + 7, np.int64)


def _few_keys(rng):
    # three distinct keys on four or eight shards: most destinations are empty
    return np.array([-2**45, 5, 2**50], np.int64)[rng.integers(0, 3, ROWS)]


def _narrow_duplicates(rng):
    # int32-range keys: the one-word key path through the same exchange
    return rng.integers(-50, 50, ROWS, dtype=np.int64)


CASES = {"full_range": _full_range, "straddling": _straddling_duplicates,
         "all_equal": _all_equal, "few_keys": _few_keys,
         "narrow": _narrow_duplicates}


def _reference(keys, vals, ascending=True, take=50):
    """sort_64m.py's reference: a stable argsort of the keys (descending: of
    their bitwise complement, which reverses int64 order and keeps equal
    keys in the order of the source), and the `take` least (key, value)."""
    order = np.argsort(keys if ascending else ~keys, kind="stable")
    head = np.lexsort((vals, keys))[:min(take, len(keys))]
    return keys[order], vals[order], list(zip(keys[head].tolist(),
                                              vals[head].tolist()))


def _check(pairs, keys, vals, ascending=True):
    from vega_tpu.tpu.dense_rdd import DenseRDD

    ctx = pairs.context
    tasks = ctx.metrics_summary()["tasks"]
    node = pairs.sort_by_key(ascending=ascending)
    cols = node.collect_arrays()
    taken = pairs.take_ordered(50)
    assert isinstance(node, DenseRDD)
    assert ctx.metrics_summary()["tasks"] == tasks  # no host-tier task
    k, v, head = _reference(keys, vals, ascending)
    assert cols["k"].dtype == (np.int64 if np.abs(keys).max() >= 2**31
                               else np.int32)  # keys that fit are narrowed
    assert np.array_equal(cols["k"], k)  # sorted_keys_wrong 0
    assert np.array_equal(cols["v"], v)  # sorted_values_wrong 0: stable
    assert taken == head  # take_keys_wrong, take_values_wrong 0
    return node


@pytest.mark.parametrize("devices", [4, 8])
@pytest.mark.parametrize("case", sorted(CASES))
def test_sort_collect_take_equals_the_reference(dctx, on_devices, devices, case):
    """The value is the row's place in the source, so a value out of place
    among equal keys shows an unstable exchange or sort."""
    on_devices(devices)
    rng = np.random.default_rng(36)
    keys = CASES[case](rng)
    vals = np.arange(ROWS, dtype=np.float64)
    node = _check(dctx.dense_from_numpy(keys, vals), keys, vals)
    # the histogram sized the launch: one round, whatever the skew
    assert node._last_attempts == 1
    assert node._exchange_plan.program == "all_to_all"


@pytest.mark.parametrize("devices", [4, 8])
@pytest.mark.parametrize("case", ["full_range", "straddling"])
def test_descending_sort_equals_the_reference(dctx, on_devices, devices, case):
    on_devices(devices)
    rng = np.random.default_rng(37)
    keys = CASES[case](rng)
    vals = np.arange(ROWS, dtype=np.float64)
    _check(dctx.dense_from_numpy(keys, vals), keys, vals, ascending=False)


@pytest.mark.parametrize("devices", [4, 8])
def test_every_key_equal_goes_to_one_destination_in_one_round(
        dctx, on_devices, devices):
    """One destination takes every row: the histogram sizes `out_cap` to all
    of them before the launch, so nothing overflows and nothing is retried,
    cold or warm."""
    on_devices(devices)
    keys = _all_equal(None)
    vals = np.arange(ROWS, dtype=np.float64)
    pairs = dctx.dense_from_numpy(keys, vals)
    for _run in ("cold", "warm"):
        node = pairs.sort_by_key()
        blk = node.block()
        assert node._last_attempts == 1
        assert blk.capacity >= ROWS
        assert sorted(blk.counts_np.tolist()) == [0] * (devices - 1) + [ROWS]
        assert np.array_equal(node.collect_arrays()["v"], vals)


@pytest.mark.parametrize("devices", [4, 8])
@pytest.mark.parametrize("how", ["wide_short_source", "narrow_filtered"])
def test_a_shard_with_no_rows(dctx, on_devices, devices, how):
    """A shard that samples nothing and sends nothing. Wide keys: a source of
    (devices - 1) x per rows leaves the last shard empty (a filter over int64
    keys has no device form). Narrow keys: the second shard is filtered empty
    before the sort, and the filter fuses into the sampler and the exchange."""
    on_devices(devices)
    rng = np.random.default_rng(38)
    if how == "wide_short_source":
        per = devices - 1  # ceil((devices - 1) * per / devices) == per
        rows = (devices - 1) * per
        keys = _full_range(rng)[:rows]
        vals = np.arange(rows, dtype=np.float64)
        pairs = dctx.dense_from_numpy(keys, vals)
        empty = devices - 1
    else:
        per = ROWS // devices
        keys = _narrow_duplicates(rng)
        vals = np.arange(ROWS, dtype=np.float64)
        kept = (vals < per) | (vals >= 2 * per)
        pairs = dctx.dense_from_numpy(keys, vals).filter(
            lambda kv: (kv[1] < per) | (kv[1] >= 2 * per))
        keys, vals = keys[kept], vals[kept]
        empty = 1
    assert pairs.block().counts_np.tolist()[empty] == 0
    _check(pairs, keys, vals)


@pytest.mark.parametrize("devices", [4, 8])
def test_fewer_rows_than_shards(dctx, on_devices, devices):
    on_devices(devices)
    keys = np.array([2**40, I64.min, 2**40], np.int64)
    vals = np.array([0.0, 1.0, 2.0])
    node = dctx.dense_from_numpy(keys, vals).sort_by_key()
    cols = node.collect_arrays()
    assert cols["k"].tolist() == [I64.min, 2**40, 2**40]
    assert cols["v"].tolist() == [1.0, 0.0, 2.0]


# `sort_256m_4chip.batch`: 64Mi rows of 12 bytes a shard on four shards under
# the default 4 GiB `dense_hbm_budget`. The sampled bounds give `slot` 17-18Mi
# and `out_cap` 65-71Mi by the seed (ISSUE 36's table): these are its corners.
@pytest.mark.parametrize("slot_mi, out_mi, program, group, rounds, share", [
    (17, 65, "all_to_all", 3, 1, 0.964),
    (17, 68, "all_to_all", 3, 1, 0.973),
    (18, 69, "all_to_all", 3, 1, 0.999),
    (18, 70, "staged", 2, 2, 0.896),
    (18, 71, "staged", 2, 2, 0.899),
])
def test_the_four_chip_sort_cells_plan_at_the_budget_edge(
        slot_mi, out_mi, program, group, rounds, share):
    """A change to the planner's model or to the default budget shows up here
    as the cell's plan moving."""
    from vega_tpu.env import Configuration
    from vega_tpu.tpu import exchange_plan

    budget = Configuration().dense_hbm_budget
    assert budget == 4 << 30
    plan = exchange_plan.plan_exchange(4, 64 << 20, slot_mi << 20,
                                       out_mi << 20, 12, budget)
    assert (plan.program, plan.group, plan.rounds) == (program, group, rounds)
    assert plan.fits
    assert round(plan.est_peak_bytes / budget, 3) == share
    assert plan.cache_token() == (program, group)
