"""Zipf-skewed keys through the dense tier: one key holds an eighth of the
rows, so a float `add` that takes a key's rows in turn drifts past 2^-18
where uniform keys (10 rows a key) hide the accumulator. The action is the
benchmark's (`agg_join_64m_zipf.batch`): reduce_by_key(add).join(table)
.collect(), against numpy in float64. Then the exchange's three counters
(`spans.count`): no retry on a histogram-sized run, a repair where a
capacity hint learned on uniform keys meets skewed keys of the same sizes."""

import numpy as np
import pytest

HOT_LIMIT = 2.0 ** -18  # relative, for a key whose sum float32 cannot hold
EXACT_BELOW = 2.0 ** 24


def zipf_keys(rng, rows, keys, s=1.1):
    cdf = np.cumsum(np.arange(1, keys + 1, dtype=np.float64) ** -s)
    cdf /= cdf[-1]
    rank = np.searchsorted(cdf, rng.random(rows), side="right")
    return rng.permutation(keys).astype(np.int64)[rank]


@pytest.fixture()
def on_devices():
    """`on_devices(n)`: the default mesh holds the first n devices until the
    test ends."""
    from vega_tpu.tpu import mesh as mesh_lib

    saved = mesh_lib.default_mesh()

    def use(n):
        mesh_lib.set_default_mesh(mesh_lib.make_mesh(n))

    yield use
    mesh_lib.set_default_mesh(saved)


@pytest.fixture()
def dctx():
    import vega_tpu as v

    context = v.Context("local", num_workers=2)
    yield context
    context.stop()


def _reference(keys, vals, tvals):
    n_keys = len(tvals)
    sums = np.bincount(keys, weights=vals, minlength=n_keys)
    present = np.bincount(keys, minlength=n_keys) > 0
    return np.flatnonzero(present), sums[present], tvals[present]


def _columns(rows):
    k = np.fromiter((r[0] for r in rows), np.int64, len(rows))
    lv = np.fromiter((r[1][0] for r in rows), np.float64, len(rows))
    rv = np.fromiter((r[1][1] for r in rows), np.float64, len(rows))
    order = np.argsort(k, kind="stable")
    return k[order], lv[order], rv[order]


@pytest.mark.parametrize("devices", [1, 4])
def test_reduce_join_collect_on_zipf_keys(dctx, on_devices, devices):
    """4M rows over 400k keys: the hottest key has 510,000 rows and a dozen
    sums pass 2^24. Adding in turn reads 8e-6 on one device here."""
    from vega_tpu.tpu.dense_rdd import DenseRDD

    on_devices(devices)
    rows, n_keys = 4_000_000, 400_000
    rng = np.random.default_rng(30)
    keys = zipf_keys(rng, rows, n_keys)
    vals = rng.integers(0, 1009, rows).astype(np.float64)
    tvals = rng.integers(0, 1000, n_keys).astype(np.float64)
    pairs = dctx.dense_from_numpy(keys, vals)
    table = dctx.dense_from_numpy(np.arange(n_keys, dtype=np.int64), tvals)
    tasks = dctx.metrics_summary()["tasks"]
    reduced = pairs.reduce_by_key(op="add")
    joined = reduced.join(table)
    k, lv, rv = _columns(joined.collect())
    assert isinstance(reduced, DenseRDD) and isinstance(joined, DenseRDD)
    assert dctx.metrics_summary()["tasks"] == tasks  # no host-tier task
    rk, rlv, rrv = _reference(keys, vals, tvals)
    assert np.array_equal(k, rk) and np.array_equal(rv, rrv)
    hot = rlv >= EXACT_BELOW
    assert hot.sum() >= 8 and np.bincount(keys).max() > 400_000
    assert np.array_equal(lv[~hot], rlv[~hot])
    assert np.max(np.abs(lv[hot] - rlv[hot]) / rlv[hot]) <= HOT_LIMIT


def _one_long_segment(rng, long_rows=2_000_000, short_keys=20_000):
    """Sorted keys: ten rows a key, and key 7 with `long_rows` more."""
    keys = np.sort(np.concatenate([
        np.repeat(np.arange(short_keys, dtype=np.int32), 10),
        np.full(long_rows, 7, np.int32)]))
    vals = rng.integers(0, 1009, len(keys)).astype(np.float32)
    return keys, vals


@pytest.mark.parametrize("add", ["blocked", "in_turn"])
def test_segment_reduce_named_over_a_long_segment(monkeypatch, add):
    """One 2M-row segment among 10-row segments, `segment_reduce_named`
    alone. `in_turn` is the control: with the blocked path out of reach the
    scatter-add alone is over the limit, so the limit can fail."""
    import jax
    import jax.numpy as jnp

    from vega_tpu.tpu import kernels

    if add == "in_turn":
        monkeypatch.setattr(kernels, "LONG_RUN_ROWS", 1 << 40)
    keys, vals = _one_long_segment(np.random.default_rng(31))
    rows = len(keys)
    cap = -(-rows // (1 << 20)) << 20
    pad = cap - rows
    cols = {"k": jnp.asarray(np.pad(keys, (0, pad))),
            "v": jnp.asarray(np.pad(vals, (0, pad), constant_values=5.0))}
    out, n = jax.jit(lambda c, cnt: kernels.segment_reduce_named(
        c, cnt, "k", "add", presorted=True))(cols, jnp.int32(rows))
    n = int(n)
    ref = np.bincount(keys, weights=vals.astype(np.float64))
    assert n == len(ref)
    assert np.array_equal(np.asarray(out["k"])[:n], np.arange(n))
    got = np.asarray(out["v"])[:n].astype(np.float64)
    cold = np.arange(n) != 7
    assert np.array_equal(got[cold], ref[cold])
    rel = abs(got[7] - ref[7]) / ref[7]
    assert (rel <= HOT_LIMIT) == (add == "blocked"), rel


@pytest.mark.parametrize("wide", [False, True])
@pytest.mark.parametrize("cap,rows", [(1024, 1000), (1024, 1024), (1024, 0),
                                      (1024, 1), (1000, 900), (8192, 5000)])
def test_blocked_add_at_every_edge(monkeypatch, cap, rows, wide):
    """The blocked path at a block of 64 rows: a full shard, an empty one,
    one row, a capacity that is no multiple of the block, junk past `count`,
    a two-word key, an int column beside the float one."""
    import jax
    import jax.numpy as jnp

    from vega_tpu.tpu import kernels

    monkeypatch.setattr(kernels, "LONG_RUN_ROWS", 64)
    rng = np.random.default_rng(cap + rows)
    k = rng.integers(0, 50, rows).astype(np.int32)
    k[: rows // 2] = 3  # a run longer than the block
    k = rng.permutation(k)
    lo = rng.integers(0, 2, rows).astype(np.int32) if wide \
        else np.zeros(rows, np.int32)
    v = rng.integers(0, 1009, rows).astype(np.float32)
    pad = cap - rows
    cols = {"k": jnp.asarray(np.pad(k, (0, pad))),
            "v": jnp.asarray(np.pad(v, (0, pad), constant_values=9.0)),
            "i": jnp.asarray(np.pad(v.astype(np.int32), (0, pad),
                                    constant_values=9))}
    if wide:
        cols["lo"] = jnp.asarray(np.pad(lo, (0, pad)))
    out, n = jax.jit(lambda c, cnt: kernels.segment_reduce_named(
        c, cnt, "k", "add", lo_name="lo" if wide else None))(
            cols, jnp.int32(rows))
    n = int(n)
    uniq, inv = np.unique(k.astype(np.int64) * 2 + lo, return_inverse=True)
    ref = np.bincount(inv, weights=v.astype(np.float64), minlength=len(uniq))
    assert n == len(uniq)
    assert np.array_equal(np.asarray(out["k"])[:n], uniq // 2)
    assert np.array_equal(np.asarray(out["v"])[:n], ref)  # all under 2^24
    assert np.array_equal(np.asarray(out["i"])[:n], ref.astype(np.int32))


# ---- the exchange's counters ------------------------------------------------

ROWS, KEYS = 200_000, 20_000


def _small_sources(ctx, keys, seed=32):
    rng = np.random.default_rng(seed)
    vals = rng.integers(0, 1009, len(keys)).astype(np.float64)
    tvals = rng.integers(0, 1000, KEYS).astype(np.float64)
    return (keys, vals, tvals, ctx.dense_from_numpy(keys, vals),
            ctx.dense_from_numpy(np.arange(KEYS, dtype=np.int64), tvals))


def _counts(tally):
    return {name: tally.get(name, {"count": 0})["count"]
            for name in ("exchange", "exchange_round", "exchange_repair")}


def test_no_retry_on_a_histogram_sized_run(dctx, on_devices, session):
    from vega_tpu.tpu import spans

    on_devices(4)
    keys = zipf_keys(np.random.default_rng(33), ROWS, KEYS)
    keys, vals, tvals, pairs, table = _small_sources(dctx, keys)
    with session:
        rows = pairs.reduce_by_key(op="add").join(table).collect()
    k, lv, rv = _columns(rows)
    rk, rlv, rrv = _reference(keys, vals, tvals)
    assert np.array_equal(k, rk) and np.array_equal(rv, rrv)
    assert np.array_equal(lv, rlv)  # every sum under 2^24 at this size
    counts = _counts(spans.session())
    assert counts["exchange"] >= 2  # the reduce's and the join's
    assert counts["exchange_round"] == counts["exchange"]
    assert counts["exchange_repair"] == 0
    tally = dctx.metrics_summary()["dense_spans"]["session"]
    assert tally["exchange"] == {"count": counts["exchange"], "seconds": 0.0,
                                 "bytes": 0, "by_kind": {}}


def test_a_hint_learned_on_uniform_keys_meets_zipf_keys(dctx, on_devices,
                                                        session):
    """`fact.join(table)` sends every fact row to its key's shard. The
    capacities a run over uniform keys learned are too small for the shard
    that owns the hot key of the same number of Zipf rows: the speculative
    launch overflows, settlement repairs it, and the counters show it."""
    from vega_tpu.tpu import spans

    on_devices(4)
    rng = np.random.default_rng(34)
    uniform = rng.integers(0, KEYS, ROWS, dtype=np.int64)
    _, _, _, upairs, utable = _small_sources(dctx, uniform)
    assert upairs.join(utable).count() == ROWS  # learns the hints
    hints = dctx.__dict__["_dense_capacity_hints"]
    keys, vals, tvals, pairs, table = _small_sources(
        dctx, zipf_keys(rng, ROWS, KEYS))
    joined = pairs.join(table)
    # same lineage, same leaf counts: the hint key is the uniform run's
    learned = hints[joined._hint_key()]
    with session:
        rows = joined.collect()
    assert hints[joined._hint_key()] > learned  # the repair's capacities
    counts = _counts(spans.session())
    assert counts["exchange_round"] - counts["exchange"] \
        + counts["exchange_repair"] >= 1, counts
    k, lv, rv = _columns(rows)
    order = np.argsort(keys, kind="stable")
    assert np.array_equal(k, keys[order])
    assert np.array_equal(np.sort(lv), np.sort(vals))
    assert np.array_equal(rv, tvals[keys[order]])


def test_count_off_costs_no_tally_entry():
    from vega_tpu.tpu import spans

    before = spans.session()
    spans.count("exchange")
    spans.count("never_seen")
    assert spans.session() == before
    assert "never_seen" not in spans.session()
