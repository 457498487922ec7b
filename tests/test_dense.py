"""Device-tier tests: DenseRDD ops on an 8-virtual-device CPU mesh, with
host-tier parity asserts — the CPU-vs-TPU "identical results" oracle that
BASELINE.md requires. Mirrors the reference's per-op golden-test strategy
(SURVEY.md §4) applied to the XLA execution path."""

import numpy as np
import pytest

import vega_tpu as v


@pytest.fixture()
def dctx():
    import vega_tpu as v

    context = v.Context("local", num_workers=2)
    yield context
    context.stop()


def host_expected_reduce_by_key(pairs, fn):
    out = {}
    for k, x in pairs:
        out[k] = fn(out[k], x) if k in out else x
    return out


def test_dense_range_count_sum(dctx):
    r = dctx.dense_range(10_000)
    assert r.count() == 10_000
    assert r.sum() == sum(range(10_000))
    assert r.min() == 0
    assert r.max() == 9_999


def test_dense_map_filter(dctx):
    r = dctx.dense_range(1_000)
    assert r.map(lambda x: x * 3).sum() == 3 * sum(range(1_000))
    kept = r.filter(lambda x: x % 5 == 0)
    assert kept.count() == 200
    assert sorted(kept.collect()) == list(range(0, 1_000, 5))


def test_dense_map_chain_fuses(dctx):
    # narrow chain: one program, correct composition
    r = dctx.dense_range(500).map(lambda x: x + 1).map(lambda x: x * 2).filter(
        lambda x: x % 4 == 0
    )
    expected = [(_x + 1) * 2 for _x in range(500) if (_x + 1) * 2 % 4 == 0]
    assert sorted(r.collect()) == sorted(expected)


def test_dense_reduce_by_key_parity(dctx):
    n, k = 5_000, 37
    pairs = [(i % k, i) for i in range(n)]
    # device
    dev = dict(
        dctx.dense_range(n).map(lambda x: (x % k, x))
        .reduce_by_key(lambda a, b: a + b).collect()
    )
    # host tier — the parity oracle
    host = dict(
        dctx.parallelize(pairs, 8).reduce_by_key(lambda a, b: a + b, 8).collect()
    )
    assert dev == host


def test_dense_reduce_by_key_named_ops(dctx):
    n, k = 2_000, 11
    base = dctx.dense_range(n).map(lambda x: (x % k, x))
    mins = dict(base.reduce_by_key(op="min").collect())
    maxs = dict(base.reduce_by_key(op="max").collect())
    assert mins == {i: i for i in range(k)}
    assert maxs == {i: max(x for x in range(n) if x % k == i) for i in range(k)}


def test_dense_reduce_by_key_generic_scan(dctx):
    """Non-monoid-named combiner goes through the segmented scan.
    f(a,b) = a + b + a*b is associative+commutative ((1+a)(1+b)-1) but not a
    named op, so it exercises the associative-scan path."""
    n, k = 40, 13
    f = lambda a, b: a + b + a * b
    dev = dict(
        dctx.dense_range(n).map(lambda x: (x % k, x)).reduce_by_key(f).collect()
    )
    host = host_expected_reduce_by_key([(i % k, i) for i in range(n)], f)
    assert dev == host


def test_dense_group_by_key(dctx):
    n, k = 3_000, 13
    grouped = dict(
        dctx.dense_range(n).map(lambda x: (x % k, x)).group_by_key().collect()
    )
    assert set(grouped) == set(range(k))
    for key in range(k):
        assert sorted(grouped[key]) == [x for x in range(n) if x % k == key]


def test_dense_join_parity(dctx):
    rng = np.random.RandomState(42)
    lk = rng.randint(0, 100, size=2_000)
    lv = rng.rand(2_000).astype(np.float32)
    rk = np.arange(100)
    rv = rng.rand(100).astype(np.float32)
    dev = sorted(
        dctx.dense_from_numpy(lk, lv).join(dctx.dense_from_numpy(rk, rv)).collect()
    )
    host = sorted(
        dctx.parallelize(list(zip(lk.tolist(), lv.tolist())), 8)
        .join(dctx.parallelize(list(zip(rk.tolist(), rv.tolist())), 4))
        .collect()
    )
    assert len(dev) == len(host) == 2_000
    for (dk, (dl, dr)), (hk, (hl, hr)) in zip(dev, host):
        assert dk == hk
        assert dl == pytest.approx(hl)
        assert dr == pytest.approx(hr)


def test_dense_sort_by_key(dctx):
    rng = np.random.RandomState(7)
    keys = rng.permutation(5_000)
    vals = keys * 2
    result = dctx.dense_from_numpy(keys, vals).sort_by_key().collect()
    assert [k for k, _ in result] == sorted(keys.tolist())
    assert all(vv == kk * 2 for kk, vv in result)
    desc = dctx.dense_from_numpy(keys, vals).sort_by_key(ascending=False).collect()
    assert [k for k, _ in desc] == sorted(keys.tolist(), reverse=True)


def test_dense_distinct(dctx):
    data = np.array([1, 5, 1, 2, 5, 5, 9], dtype=np.int32)
    assert sorted(dctx.dense_from_numpy(data).distinct().collect()) == [1, 2, 5, 9]


def test_dense_generic_reduce(dctx):
    import jax.numpy as jnp

    r = dctx.dense_range(1_000).map(lambda x: x + 1)
    assert r.reduce(jnp.maximum) == 1_000
    assert r.reduce(lambda a, b: a + b) == sum(range(1, 1_001))


def test_dense_reduce_empty(dctx):
    empty = dctx.dense_range(100).filter(lambda x: x < 0)
    with pytest.raises(v.VegaError):
        empty.reduce(lambda a, b: a + b)


def test_dense_host_fallback_map(dctx):
    """Untraceable closure falls back to the host tier transparently."""
    r = dctx.dense_range(100).map(lambda x: f"item-{int(x)}")
    from vega_tpu.tpu.dense_rdd import DenseRDD

    assert not isinstance(r, DenseRDD)
    assert r.take(2) == ["item-0", "item-1"]


def test_dense_host_interop_cogroup(dctx):
    """Dense RDD cogroups with a host RDD via the interop path."""
    dense = dctx.dense_range(20).map(lambda x: (x % 4, x))
    host = dctx.parallelize([(i, f"h{i}") for i in range(4)], 2)
    grouped = dict(dense.cogroup(host).collect())
    assert sorted(grouped[1][0]) == [x for x in range(20) if x % 4 == 1]
    assert grouped[1][1] == ["h1"]


def test_dense_map_values(dctx):
    r = dctx.dense_range(100).map(lambda x: (x % 5, x)).map_values(
        lambda x: x * 10
    )
    dev = dict(r.reduce_by_key(op="add").collect())
    assert dev == {
        k: sum(x * 10 for x in range(100) if x % 5 == k) for k in range(5)
    }


def test_dense_skew_overflow_retry(dctx):
    """All rows on one key: exchange capacity must grow and still succeed."""
    n = 4_000
    dev = dict(
        dctx.dense_range(n).map(lambda x: (x * 0, x)).reduce_by_key(op="add").collect()
    )
    assert dev == {0: sum(range(n))}


def test_dense_join_duplicate_keys_on_device(dctx):
    """Dup keys on either side run the full dup x dup product ON DEVICE
    (merge_join_expand) — no host fallback (reference pair_rdd.rs:104-121
    semantics)."""
    left = dctx.dense_from_numpy(np.array([1, 2]), np.array([5, 6]))
    right = dctx.dense_from_numpy(np.array([1, 1, 2]), np.array([10, 20, 30]))
    j = left.join(right)
    assert sorted(j.collect()) == [(1, (5, 10)), (1, (5, 20)), (2, (6, 30))]
    assert j.count() == 3


def test_dense_join_dup_parity_randomized(dctx):
    """Randomized dup x dup join parity: dense result must equal the host
    tier's join on the same data (inner and left-outer)."""
    rng = np.random.RandomState(42)
    lk = rng.randint(0, 40, 3000).astype(np.int32)
    lv = rng.randint(0, 1000, 3000).astype(np.int32)
    rk = rng.randint(20, 60, 500).astype(np.int32)  # partial key overlap
    rv = rng.randint(0, 1000, 500).astype(np.int32)

    dense = dctx.dense_from_numpy(lk, lv).join(
        dctx.dense_from_numpy(rk, rv))
    host = dctx.parallelize(list(zip(lk.tolist(), lv.tolist())), 4).join(
        dctx.parallelize(list(zip(rk.tolist(), rv.tolist())), 4))
    assert sorted(dense.collect()) == sorted(host.collect())

    douter = dctx.dense_from_numpy(lk, lv).left_outer_join(
        dctx.dense_from_numpy(rk, rv), fill_value=-1)
    houter = dctx.parallelize(list(zip(lk.tolist(), lv.tolist())), 4) \
        .cogroup(dctx.parallelize(list(zip(rk.tolist(), rv.tolist())), 4)) \
        .flat_map_values(lambda g: [(a, b) for a in g[0] for b in g[1]]
                         if g[1] else [(a, -1) for a in g[0]])
    assert sorted(douter.collect()) == sorted(houter.collect())


def test_dense_join_expansion_overflow_retries(dctx):
    """A join whose dup x dup product far exceeds the input row counts must
    trigger the expansion-overflow retry and still return exact results."""
    lk = np.zeros(300, dtype=np.int32)  # all same key
    rk = np.zeros(300, dtype=np.int32)  # 300 x 300 = 90k output rows
    j = dctx.dense_from_numpy(lk, np.arange(300, dtype=np.int32)).join(
        dctx.dense_from_numpy(rk, np.arange(300, dtype=np.int32)))
    assert j.count() == 90_000


def test_dense_take(dctx):
    r = dctx.dense_range(1_000)
    assert r.take(5) == [0, 1, 2, 3, 4]


def test_dense_float_aggregation_close(dctx):
    """Float32 sums: device vs host within tolerance (summation order
    differs; BASELINE parity for floats is tolerance-specified,
    SURVEY.md §7 hard part 4)."""
    rng = np.random.RandomState(3)
    vals = rng.rand(10_000).astype(np.float32)
    keys = rng.randint(0, 50, size=10_000)
    dev = dict(
        dctx.dense_from_numpy(keys, vals).reduce_by_key(op="add").collect()
    )
    host = {}
    for k, x in zip(keys.tolist(), vals.tolist()):
        host[k] = host.get(k, 0.0) + x
    assert set(dev) == set(host)
    for k in host:
        assert dev[k] == pytest.approx(host[k], rel=1e-3)


def test_program_cache_reuse(dctx):
    from vega_tpu.tpu.dense_rdd import _PROGRAM_CACHE

    def run():
        return dict(
            dctx.dense_range(1_000).map(lambda x: (x % 3, x))
            .reduce_by_key(op="add").collect()
        )

    r1 = run()
    size_after_first = len(_PROGRAM_CACHE)
    r2 = run()
    assert r1 == r2
    # The first WARM run may add exactly one program: the speculative
    # dense-key table plan only activates once the key range is known
    # (learned by the cold run). Steady state compiles nothing new.
    size_after_warm = len(_PROGRAM_CACHE)
    assert size_after_warm <= size_after_first + 1
    r3 = run()
    assert r3 == r1
    assert len(_PROGRAM_CACHE) == size_after_warm


def test_dense_topk_actions(dctx):
    r = dctx.dense_range(5_000)
    assert r.top(3) == [4999, 4998, 4997]
    assert r.take_ordered(4) == [0, 1, 2, 3]
    # pair / custom key falls back to host semantics
    pairs = dctx.dense_range(100).map(lambda x: (x % 5, x))
    assert pairs.top(1, key=lambda kv: kv[1])[0][1] == 99


def test_dense_stats_histogram(dctx):
    r = dctx.dense_range(1_000)
    s = r.stats()
    assert s["count"] == 1_000
    assert s["mean"] == pytest.approx(499.5)
    assert s["min"] == 0.0 and s["max"] == 999.0
    edges, counts = r.histogram(4)
    assert sum(counts) == 1_000
    assert counts == [250, 250, 250, 250]


def test_dense_sample(dctx):
    r = dctx.dense_range(10_000)
    s = r.sample(False, 0.2, seed=7)
    c = s.count()
    assert 1_700 < c < 2_300
    # deterministic per seed
    assert s.count() == c
    s2 = dctx.dense_range(10_000).sample(False, 0.2, seed=7)
    assert s2.count() == c


def test_dense_union(dctx):
    a = dctx.dense_range(100)
    b = dctx.dense_range(50).map(lambda x: x + 1_000)
    u = a.union(b)
    assert u.count() == 150
    got = sorted(u.collect())
    assert got[:100] == list(range(100))
    assert got[100:] == list(range(1_000, 1_050))
    # unioned data flows through a shuffle correctly
    tot = dict(u.map(lambda x: (x % 2, x)).reduce_by_key(op="add").collect())
    expected = {0: sum(x for x in got if x % 2 == 0),
                1: sum(x for x in got if x % 2 == 1)}
    assert tot == expected


def test_dense_count_by_value(dctx):
    r = dctx.dense_from_numpy(np.array([5, 5, 7, 9, 9, 9], dtype=np.int32))
    assert r.count_by_value() == {5: 2, 7: 1, 9: 3}


def test_dense_pair_take_ordered_top(dctx):
    rng = np.random.default_rng(11)
    # duplicate keys force the value tiebreak at the cutoff — the case
    # where key-only ordering would diverge from host tuple ordering
    ks = rng.integers(0, 40, size=600).astype(np.int32)
    vs = rng.integers(-1000, 1000, size=600).astype(np.int32)
    pairs = list(zip(ks.tolist(), vs.tolist()))
    host = dctx.parallelize(pairs, 4)
    dev = dctx.dense_from_numpy(ks, vs)
    assert dev.take_ordered(7) == host.take_ordered(7)
    assert dev.top(7) == host.top(7)
    assert dev.take_ordered(0) == []
    assert dev.take_ordered(10_000) == host.take_ordered(10_000)

    # float values
    fvs = rng.standard_normal(600).astype(np.float32)
    fdev = dctx.dense_from_numpy(ks, fvs)
    fhost = dctx.parallelize(list(zip(ks.tolist(), fvs.tolist())), 4)
    assert fdev.take_ordered(9) == fhost.take_ordered(9)
    assert fdev.top(9) == fhost.top(9)

    # int64 (hi, lo) keys order as true int64, not as encoded words
    big = rng.integers(-(1 << 45), 1 << 45, size=300, dtype=np.int64)
    wdev = dctx.dense_from_numpy(big, np.arange(300, dtype=np.int32))
    whost = dctx.parallelize(
        list(zip(big.tolist(), range(300))), 4)
    assert wdev.take_ordered(5) == whost.take_ordered(5)
    assert wdev.top(5) == whost.top(5)

    # multi-column blocks: natural element order == schema-tuple order,
    # so take_ordered(n) agrees with sorted(collect())[:n] (key column
    # sits wherever the schema put it — here last)
    m = dctx.dense_from_columns(
        {"a": vs, "b": fvs, "k": ks}, key="k")
    assert m.take_ordered(6) == sorted(m.collect())[:6]
    assert m.top(6) == sorted(m.collect(), reverse=True)[:6]


def test_dense_wide_int64_values(dctx):
    """int64 VALUES on device via the wide (v, v.lo) encoding: named
    reduces use carry/lex combines; shuffles/joins/groups/sorts carry the
    pair opaquely; host-facing reads decode; traced closures fall back."""
    BIG = 1 << 40
    ks = np.array([3, 1, 3, 2, 1, 3], dtype=np.int32)
    vs = BIG + np.array([10, 20, 30, 40, 50, 60], dtype=np.int64)
    pairs = list(zip(ks.tolist(), vs.tolist()))
    d = dctx.dense_from_numpy(ks, vs)
    assert sorted(d.collect()) == sorted(pairs)

    exp_add, exp_min, groups = {}, {}, {}
    for k, x in pairs:
        exp_add[k] = exp_add.get(k, 0) + x
        exp_min[k] = min(exp_min.get(k, x), x)
        groups.setdefault(k, []).append(x)
    red = d.reduce_by_key(op="add")
    assert dict(red.collect()) == exp_add
    assert dict(d.reduce_by_key(op="min").collect()) == exp_min

    # carry across the 32-bit boundary
    cd = dctx.dense_from_numpy(
        np.array([1, 1, 2, 2], dtype=np.int32),
        np.array([0xFFFFFFFF, 1, 2**33, 2**33], dtype=np.int64))
    assert dict(cd.reduce_by_key(op="add").collect()) == \
        {1: 0x100000000, 2: 2**34}

    # joins carry wide values on either side
    table = dctx.dense_from_numpy(np.array([1, 2, 3], dtype=np.int32),
                                  np.array([7, 8, 9], dtype=np.int32))
    tv = {1: 7, 2: 8, 3: 9}
    assert sorted(red.join(table).collect()) == \
        sorted((k, (exp_add[k], tv[k])) for k in exp_add)
    assert sorted(table.join(red).collect()) == \
        sorted((k, (tv[k], exp_add[k])) for k in exp_add)
    # outer join with a wide right side takes the host path (exact fill)
    loj = dict(table.left_outer_join(red, fill_value=-1).collect())
    assert loj[1] == (7, exp_add[1]) and len(loj) == 3

    # traced closures see no row form -> silent host fallback, exact int64
    assert dict(d.reduce_by_key(lambda a, b: a + b).collect()) == exp_add
    assert sorted(d.map_values(lambda x: x - BIG).collect()) == \
        sorted((k, x - BIG) for k, x in pairs)

    # group/sort/take_ordered/count
    g = d.group_by_key()
    assert {k: sorted(v) for k, v in dict(g.collect()).items()} == \
        {k: sorted(v) for k, v in groups.items()}
    _gk, _offs, gv = g.collect_grouped()
    assert gv.dtype == np.int64
    assert d.sort_by_key().take(3) == sorted(pairs)[:3]
    assert d.take_ordered(3) == sorted(pairs)[:3]
    wide_both = dctx.dense_from_numpy(vs, vs)  # wide key AND wide value
    assert wide_both.top(2) == sorted(zip(vs.tolist(), vs.tolist()),
                                      reverse=True)[:2]
    assert dict(d.count_by_key_dense().collect()) == {1: 2, 2: 1, 3: 3}

    # multi-column: wide + narrow columns reduce in one program
    m = dctx.dense_from_columns(
        {"k2": ks, "w": vs, "x": ks.astype(np.float32)}, key="k2")
    arrs = m.reduce_by_key(op="add").collect_arrays()
    keyname = "k" if "k" in arrs else "k2"
    assert dict(zip(arrs[keyname].tolist(), arrs["w"].tolist())) == exp_add
    # select keeps the wide partner
    assert sorted(m.select("w").collect_arrays()["w"].tolist()) == \
        sorted(vs.tolist())
    # prod over wide values: crisp error (no device path, overflow-bound)
    with pytest.raises(v.errors.VegaError):
        d.reduce_by_key(op="prod")

    # streamed chunks keep one schema even when a chunk's range fits int32
    from vega_tpu.tpu.stream import streamed_columns
    sr = streamed_columns(dctx, {"k": ks, "v": vs}, chunk_rows=2)
    assert dict(sr.reduce_by_key(op="add").collect()) == exp_add

    # the ".lo" suffix is reserved
    with pytest.raises(v.errors.VegaError):
        dctx.dense_from_columns({"a.lo": ks, "k3": ks}, key="k3")
    # selecting an orphaned low word would silently vanish data: crisp
    with pytest.raises(v.errors.VegaError):
        m.select("w.lo")

    # combine_by_key over wide values: exact host fallback (a traced
    # create_combiner would see only the hi word)
    import operator

    got = dict(d.combine_by_key(
        lambda x: x, operator.add, operator.add).collect())
    assert got == exp_add
    # a multiplication CLOSURE (inferred op='prod') falls back silently,
    # exact even past int64 (the native codec rejects overflow and the
    # Python path folds bignums)
    exp_prod = {}
    for k, x in pairs:
        exp_prod[k] = exp_prod.get(k, 1) * x
    assert dict(d.reduce_by_key(lambda a, b: a * b).collect()) == exp_prod
    # dense left_outer_join against a HOST-tier other still works
    h = dctx.parallelize([(1, 7)], 2)
    loj = d.left_outer_join(h, fill_value=-1).collect()
    assert len(loj) == len(pairs) and (1, (BIG + 20, 7)) in loj


def test_dense_count_by_key_variants(dctx):
    # pair block: (k, count) pairs, host parity
    ks = np.array([3, 1, 3, 2, 3, 1], dtype=np.int32)
    vs = np.arange(6, dtype=np.float32)
    pair = dctx.dense_from_numpy(ks, vs)
    expected = {1: 2, 2: 1, 3: 3}
    assert dict(pair.count_by_key_dense().collect()) == expected
    host = dctx.parallelize(list(zip(ks.tolist(), vs.tolist())), 3)
    assert dict(host.map(lambda kv: (kv[0], 1))
                .reduce_by_key(lambda a, b: a + b, 3).collect()) == expected

    # key-only block (no value column): counting a bare key column works
    key_only = dctx.dense_from_columns({"word": ks}, key="word")
    assert dict(key_only.count_by_key_dense().collect()) == expected

    # multi-column block: value columns drop, counts stay per-key
    multi = dctx.dense_from_columns(
        {"k": ks, "a": vs, "b": vs * 2}, key="k")
    assert dict(multi.count_by_key_dense().collect()) == expected

    # int64 (hi, lo) keys: the synthesized ones column rides the wide key
    big = (1 << 40) + np.array([3, 1, 3, 2, 3, 1], dtype=np.int64)
    wide = dctx.dense_from_numpy(big, vs)
    got = dict(wide.count_by_key_dense().collect())
    assert got == {(1 << 40) + k: c for k, c in expected.items()}


def test_dense_cogroup(dctx):
    a = dctx.dense_from_numpy(np.array([1, 1, 2, 3], dtype=np.int32),
                              np.array([10, 11, 20, 30], dtype=np.int32))
    b = dctx.dense_from_numpy(np.array([1, 4], dtype=np.int32),
                              np.array([100, 400], dtype=np.int32))
    grouped = dict(a.cogroup(b).collect())
    assert sorted(grouped[1][0]) == [10, 11]
    assert grouped[1][1] == [100]
    assert grouped[2] == ([20], [])
    assert grouped[4] == ([], [400])
    # host ops compose on top of the dense cogroup
    joined = sorted(
        a.cogroup(b).flat_map_values(
            lambda g: [(l, r) for l in g[0] for r in g[1]]
        ).collect()
    )
    assert joined == [(1, (10, 100)), (1, (11, 100))]


def test_dense_cogroup_parity_with_host(dctx):
    rng = np.random.RandomState(5)
    ak, av = rng.randint(0, 30, 500), rng.randint(0, 1000, 500)
    bk, bv = rng.randint(0, 30, 300), rng.randint(0, 1000, 300)
    dev = {
        k: (sorted(l), sorted(r))
        for k, (l, r) in dctx.dense_from_numpy(ak, av)
        .cogroup(dctx.dense_from_numpy(bk, bv)).collect()
    }
    host = {
        k: (sorted(l), sorted(r))
        for k, (l, r) in dctx.parallelize(list(zip(ak.tolist(), av.tolist())), 4)
        .cogroup(dctx.parallelize(list(zip(bk.tolist(), bv.tolist())), 4))
        .collect()
    }
    assert dev == host


def test_dense_multi_column(dctx):
    """Named multi-column blocks: one reduce_by_key aggregates every value
    column per key in a single program."""
    rng = np.random.RandomState(2)
    n = 1_000
    ip = rng.randint(0, 20, n).astype(np.int32)
    rdd = dctx.dense_from_columns(
        key="ip", ip=ip,
        bytes=np.ones(n, dtype=np.int32) * 10,
        packets=np.ones(n, dtype=np.int32),
    )
    assert set(rdd.columns) == {"k", "bytes", "packets"}
    per_key = rdd.reduce_by_key(op="add")
    arrays = per_key.collect_arrays()
    assert len(arrays["k"]) == 20
    by_key = dict(zip(arrays["k"].tolist(), arrays["bytes"].tolist()))
    counts = dict(zip(arrays["k"].tolist(), arrays["packets"].tolist()))
    for k in range(20):
        expected_n = int((ip == k).sum())
        assert counts[k] == expected_n
        assert by_key[k] == expected_n * 10
    # select projects columns (narrow)
    proj = per_key.select("k", "bytes")
    assert set(proj.columns) == {"k", "bytes"}
    with pytest.raises(v.VegaError):
        per_key.select("nope")


def test_dense_profiler_hook(dctx, tmp_path):
    with dctx.profiler(str(tmp_path / "trace")):
        dctx.dense_range(1_000).sum()
    import os
    assert os.path.exists(tmp_path / "trace")


def test_dense_map_expand(dctx):
    import jax.numpy as jnp

    r = dctx.dense_range(100).map_expand(
        lambda x: jnp.stack([x, x + 1000]), 2
    )
    got = sorted(r.collect())
    expected = sorted(list(range(100)) + [x + 1000 for x in range(100)])
    assert got == expected
    # pair output
    kv = dctx.dense_range(50).map_expand(
        lambda x: (jnp.stack([x % 3, x % 3]), jnp.stack([x, x * 2])), 2
    )
    agg = dict(kv.reduce_by_key(op="add").collect())
    expected2 = {}
    for x in range(50):
        expected2[x % 3] = expected2.get(x % 3, 0) + x + x * 2
    assert agg == expected2


def test_dense_zip_and_index(dctx):
    a = dctx.dense_range(100)
    b = dctx.dense_range(100).map(lambda x: x * 2)
    z = a.zip(b)
    assert sorted(z.collect()) == [(x, 2 * x) for x in range(100)]
    wi = dctx.dense_range(64).zip_with_index()
    pairs = wi.collect()
    assert sorted(pairs) == sorted((v, i) for i, v in enumerate(
        [x for s in range(8) for x in range(s * 8, s * 8 + 8)]
    ))
    # indices are a permutation of 0..63 and value==index for range input
    assert sorted(i for _v, i in pairs) == list(range(64))


def test_dense_zip_mismatch_raises(dctx):
    a = dctx.dense_range(100)
    b = dctx.dense_range(37)
    with pytest.raises(v.VegaError):
        a.zip(b).collect()


def test_dense_save_load_npz(dctx, tmp_path):
    """Dense persistence round-trip, including across a reduce."""
    path = str(tmp_path / "block.npz")
    agg = dctx.dense_range(1_000).map(lambda x: (x % 10, x)).reduce_by_key(op="add")
    agg.save_npz(path)
    reloaded = dctx.dense_load_npz(path)
    assert sorted(reloaded.collect()) == sorted(agg.collect())
    # reloaded block is a source: flows through further device ops
    doubled = dict(reloaded.map_values(lambda x: x * 2)
                   .reduce_by_key(op="add").collect())
    assert doubled == {k: 2 * val for k, val in agg.collect()}


def test_dense_left_outer_join(dctx):
    left = dctx.dense_from_numpy(np.array([1, 2, 3, 4], dtype=np.int32),
                                 np.array([10, 20, 30, 40], dtype=np.int32))
    right = dctx.dense_from_numpy(np.array([2, 4], dtype=np.int32),
                                  np.array([200, 400], dtype=np.int32))
    j = sorted(left.left_outer_join(right, fill_value=-1).collect())
    assert j == [(1, (10, -1)), (2, (20, 200)), (3, (30, -1)), (4, (40, 400))]
    # dup right -> cogroup fallback keeps outer semantics
    dup = dctx.dense_from_numpy(np.array([2, 2], dtype=np.int32),
                                np.array([5, 6], dtype=np.int32))
    j2 = sorted(left.left_outer_join(dup, fill_value=0).collect())
    assert j2 == [(1, (10, 0)), (2, (20, 5)), (2, (20, 6)),
                  (3, (30, 0)), (4, (40, 0))]


def test_dense_int64_values_fall_back_keys_stay_dense(dctx):
    """int64 beyond int32 range stays DENSE on both sides of a pair: keys
    AND values ride the wide (name, name.lo) two-column encoding (named
    reduces use device carry arithmetic; traced binops fall back but the
    source stays dense). Keyless bare int64 single columns stay dense
    too (test_keyless_int64_stays_dense)."""
    from vega_tpu.tpu.block import KEY_LO
    from vega_tpu.tpu.dense_rdd import DenseRDD

    big_vals = dctx.dense_from_numpy(
        np.array([1, 2, 1], dtype=np.int64),
        np.array([2**40, 2, 3], dtype=np.int64),
    )
    assert isinstance(big_vals, DenseRDD)
    assert "v.lo" in big_vals.columns
    got = dict(big_vals.reduce_by_key(lambda a, b: a + b, 2).collect())
    assert got == {1: 2**40 + 3, 2: 2}  # exact int64 sums (host fallback)
    got = dict(big_vals.reduce_by_key(op="add").collect())
    assert got == {1: 2**40 + 3, 2: 2}  # device carry arithmetic
    bare = dctx.dense_from_numpy(np.array([2**40, 2, 3], dtype=np.int64))
    assert isinstance(bare, DenseRDD)  # keyless wide: stays dense now
    assert bare.reduce(lambda a, b: a + b) == 2**40 + 5  # host fold, exact
    # int64 keys beyond int32 range: composite encoding, still a DenseRDD
    big_keys = dctx.dense_from_numpy(
        np.array([2**40, 1, 2**40], dtype=np.int64),
        np.array([1, 2, 3], dtype=np.int32),
    )
    assert isinstance(big_keys, DenseRDD)
    assert KEY_LO in big_keys.columns
    got = dict(big_keys.reduce_by_key(op="add").collect())
    assert got == {2**40: 4, 1: 2}  # exact int64 keys
    # in-range int64 narrows safely and stays dense (single-column key)
    r = dctx.dense_from_numpy(np.array([5, 6], dtype=np.int64),
                              np.array([50, 60], dtype=np.int64))
    assert isinstance(r, DenseRDD)
    assert KEY_LO not in r.columns
    assert sorted(r.collect()) == [(5, 50), (6, 60)]


def _i64_fixture(seed=0, n=3000):
    rng = np.random.RandomState(seed)
    keys = (rng.randint(-5, 5, size=n).astype(np.int64) * 3_000_000_000
            + rng.randint(0, 3, size=n))
    vals = rng.randint(0, 1000, size=n).astype(np.int32)
    return keys, vals


def test_dense_int64_key_roundtrip_and_encoding(dctx):
    """encode/decode is exact and order-preserving at the numpy level and
    through a block round trip."""
    from vega_tpu.tpu import block as block_lib

    edge = np.array([-2**63, -2**32 - 1, -2**32, -1, 0, 1, 2**31,
                     2**32, 2**40 + 7, 2**63 - 1], dtype=np.int64)
    hi, lo = block_lib.encode_i64(edge)
    assert hi.dtype == np.int32 and lo.dtype == np.int32
    np.testing.assert_array_equal(block_lib.decode_i64(hi, lo), edge)
    # lexicographic (hi, lo-signed) order == int64 order
    order = np.lexsort((lo, hi))
    np.testing.assert_array_equal(edge[order], np.sort(edge))

    keys, vals = _i64_fixture()
    d = dctx.dense_from_numpy(keys, vals)
    got = d.collect()
    np.testing.assert_array_equal(
        np.array([k for k, _ in got], np.int64), keys
    )


def _decode_i64_reference(hi, lo):
    """The plain reference: block.decode_i64 as it stood before it wrote
    each word once (five whole-column temporaries)."""
    lo_u = (np.asarray(lo).view(np.uint32)
            ^ np.uint32(0x80000000)).astype(np.int64)
    return (np.asarray(hi).astype(np.int64) << 32) | lo_u


_I64_MIN, _I64_MAX = -2**63, 2**63 - 1
_DECODE_VALUES = {
    "empty": [],
    "one_row": [-3_000_000_007],
    "int64_min_max": [_I64_MIN, _I64_MAX, _I64_MIN + 1, _I64_MAX - 1],
    # true low words 0x7fffffff / 0x80000000 / 0xffffffff / 0: both sides
    # of the stored word's flipped sign bit, under both signs of the high
    "low_word_sign_bit": [2**31 - 1, 2**31, 2**32 - 1, 2**32, 0, -1,
                          -2**31, -2**31 - 1, 5 * 2**32 + 2**31,
                          -5 * 2**32 + 2**31 - 1],
    # more than one chunk of the join, and not a multiple of it
    "three_chunks": None,
}


def _decode_values(name):
    if name == "three_chunks":
        from vega_tpu.tpu import block as block_lib

        n = 2 * block_lib._DECODE_CHUNK_ROWS + 12_345
        return np.random.RandomState(7).randint(
            _I64_MIN, _I64_MAX, size=n, dtype=np.int64)
    return np.array(_DECODE_VALUES[name], dtype=np.int64)


@pytest.mark.parametrize("inputs", ["contiguous", "strided", "read_only",
                                    "uint32_lo", "lists_of_rows"])
@pytest.mark.parametrize("values", sorted(_DECODE_VALUES))
def test_decode_i64_equals_the_plain_formula(values, inputs):
    """One pass, no 64-bit temporaries: the same int64s as the formula it
    replaced, for every input the formula took."""
    from vega_tpu.tpu import block as block_lib

    want = _decode_values(values)
    hi, lo = block_lib.encode_i64(want)
    if inputs == "strided":  # every other element of a wider buffer
        wide_hi = np.full(2 * len(hi) + 1, 99, np.int32)
        wide_lo = np.full(2 * len(lo) + 1, 99, np.int32)
        wide_hi[1::2], wide_lo[1::2] = hi, lo
        hi, lo = wide_hi[1::2], wide_lo[1::2]
        assert len(hi) < 2 or not hi.flags.c_contiguous
    elif inputs == "read_only":  # what jax hands back from a fetch
        hi.flags.writeable = lo.flags.writeable = False
    elif inputs == "uint32_lo":
        lo = lo.view(np.uint32)
    elif inputs == "lists_of_rows":  # [rows, d]: a wide value column
        if len(hi) % 2:
            hi, lo, want = hi[:-1], lo[:-1], want[:-1]
        hi, lo, want = (a.reshape(-1, 2) for a in (hi, lo, want))
    before = (hi.copy(), lo.copy())
    got = block_lib.decode_i64(hi, lo)
    assert got.dtype == np.int64 and got.shape == want.shape
    assert got.flags.c_contiguous and got.flags.writeable
    np.testing.assert_array_equal(got, _decode_i64_reference(hi, lo))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(hi, before[0])  # inputs untouched
    np.testing.assert_array_equal(lo, before[1])
    # out=: a slice of a larger result is filled in place, nothing beside it
    larger = np.full((len(hi) + 5,) + hi.shape[1:], 42, np.int64)
    dst = larger[3:3 + len(hi)]
    assert block_lib.decode_i64(hi, lo, out=dst) is dst
    np.testing.assert_array_equal(larger[3:3 + len(hi)], want)
    assert (larger[:3] == 42).all() and (larger[3 + len(hi):] == 42).all()


_TO_NUMPY_COUNTS = {
    "1_shard": [77],
    "1_shard_full": [128],
    "2_shards_first_empty": [0, 100],
    "2_shards_no_rows": [0, 0],
    "8_shards_uneven": [3, 0, 128, 1, 0, 50, 127, 9],
}


def _uneven_block(counts, backing, cap=128):
    """(block, reference columns): a wide key, a plain float, a wide value,
    a dictionary column and a [rows, 3] column, `counts[s]` valid rows in
    shard s of `cap` and a sentinel in every padding row. `backing`:
    "device" (a shard a device, the mesh's layout), "one_device" (jax
    columns that are not laid out a shard a device: fetched whole) or
    "numpy" (a host-tier block behind _HostMeshStub)."""
    import jax.numpy as jnp

    from vega_tpu.tpu import block as block_lib
    from vega_tpu.tpu import mesh as mesh_lib
    from vega_tpu.tpu.dense_rdd import _HostMeshStub

    counts = np.asarray(counts, np.int32)
    n_shards, total = len(counts), int(counts.sum())
    rng = np.random.RandomState(n_shards)
    words = np.array(["ash", "birch", "cedar", "fir", "oak"])
    ref = {
        "k": rng.randint(_I64_MIN, _I64_MAX, size=total, dtype=np.int64),
        "v": rng.rand(total).astype(np.float32),
        "w": rng.randint(-2**40, 2**40, size=total, dtype=np.int64),
        "s": words[rng.randint(0, len(words), size=total)],
        "m": rng.randint(-9, 9, size=(total, 3)).astype(np.int32),
    }
    k_hi, k_lo = block_lib.encode_i64(ref["k"])
    w_hi, w_lo = block_lib.encode_i64(ref["w"])
    stored = {"k": k_hi, "k.lo": k_lo, "v": ref["v"], "w": w_hi,
              "w.lo": w_lo,
              "s": np.searchsorted(words, ref["s"]).astype(np.int32),
              "m": ref["m"]}
    mesh = (mesh_lib.make_mesh(n_shards) if backing == "device"
            else _HostMeshStub(n_shards))
    cols, at = {}, np.concatenate([[0], np.cumsum(counts)])
    for name, src in stored.items():
        dst = np.full((n_shards * cap,) + src.shape[1:], -7, src.dtype)
        for s, c in enumerate(counts):
            dst[s * cap:s * cap + c] = src[at[s]:at[s + 1]]
        cols[name] = (mesh_lib.host_put(dst, mesh_lib.shard_spec(mesh))
                      if backing == "device"
                      else jnp.asarray(dst) if backing == "one_device"
                      else dst)
    blk = block_lib.Block(cols=cols, counts=counts, capacity=cap, mesh=mesh,
                          counts_host=counts, dicts={"s": words})
    return blk, ref


@pytest.mark.parametrize("backing", ["device", "one_device", "numpy"])
@pytest.mark.parametrize("counts", sorted(_TO_NUMPY_COUNTS))
def test_block_to_numpy_fills_each_column_once(counts, backing):
    """Valid rows only, shard order, schema order; int64 for a wide pair,
    strings for a dictionary column, the column's own dtype otherwise; fresh
    arrays that alias neither the block's columns nor each other's call."""
    blk, ref = _uneven_block(_TO_NUMPY_COUNTS[counts], backing)
    got = blk.to_numpy()
    assert list(got) == ["k", "v", "w", "s", "m"]
    for name, want in ref.items():
        assert got[name].dtype == want.dtype and got[name].shape == want.shape
        assert got[name].flags.c_contiguous and got[name].flags.writeable
        assert got[name].flags.owndata
        np.testing.assert_array_equal(got[name], want)
        if backing == "numpy":
            assert not np.shares_memory(got[name], blk.cols[name])
    # shard_rows reads the same rows a shard at a time
    for name, want in ref.items():
        parts = [blk.shard_rows(s)[name] for s in range(blk.n_shards)]
        np.testing.assert_array_equal(np.concatenate(parts), want)
    # a caller that scribbles on its result changes no later read
    for name in ("k", "v", "w", "m"):
        got[name][...] = 0
    again = blk.to_numpy()
    for name, want in ref.items():
        np.testing.assert_array_equal(again[name], want)


@pytest.mark.parametrize("n_shards", [1, 8])
def test_block_to_numpy_makes_no_whole_column_temporary(n_shards):
    """The mechanism, pinned: a 1Mi-row wide-key block comes back at a peak
    of its own result's bytes (a quarter of room; the slice lists,
    np.concatenate and the five-temporary int64 join it replaced peak at
    three times that, by this test on the parent). On the CPU mesh a
    fetched column is a view of the device's buffer, which tracemalloc does
    not count."""
    import tracemalloc

    from vega_tpu.tpu import block as block_lib
    from vega_tpu.tpu import mesh as mesh_lib

    n = 1 << 20
    rng = np.random.RandomState(3)
    keys = rng.randint(_I64_MIN, _I64_MAX, size=n, dtype=np.int64)
    vals = rng.rand(n).astype(np.float32)
    blk = block_lib.from_numpy({"k": keys, "v": vals},
                               mesh_lib.make_mesh(n_shards))
    assert "k.lo" in blk.cols
    blk.to_numpy()  # counts and the fetch are the block's from here on
    tracemalloc.start()
    try:
        base = tracemalloc.get_traced_memory()[0]
        got = blk.to_numpy()
        peak = tracemalloc.get_traced_memory()[1] - base
    finally:
        tracemalloc.stop()
    returned = sum(col.nbytes for col in got.values())
    assert returned == n * (8 + 4)
    np.testing.assert_array_equal(got["k"], keys)
    np.testing.assert_array_equal(got["v"], vals)
    assert peak <= 1.25 * returned, (peak, returned)


def _fill_case_block(name, cap=2048):
    """(block, reference columns) for one case of the threaded fill: host
    columns of the valid rows, shard after shard, stored as a device block
    a shard a device (an int64 column as its two words), a sentinel in every
    padding row."""
    from vega_tpu.tpu import block as block_lib
    from vega_tpu.tpu import mesh as mesh_lib

    rng = np.random.RandomState(len(name))
    counts = {"four_shards_uneven": [700, 0, 333, 2048],
              "dict_beside_numeric": [900, 41, 0, 1200]}.get(name, [1500])
    n = sum(counts)
    wide = rng.randint(_I64_MIN, _I64_MAX, size=n, dtype=np.int64)
    wide[:4] = [_I64_MIN, -1, 2**31, _I64_MAX]  # negative, beyond int32
    floats = rng.rand(n).astype(np.float32)
    floats[:3] = [np.nan, -0.0, np.inf]  # a copy keeps every bit
    words = np.array(["ash", "birch", "cedar", "fir", "oak"])
    ref = {
        "wide_key": {"k": wide},
        "wide_value": {"k": rng.randint(-50, 50, n).astype(np.int32),
                       "w": rng.randint(-2**40, 2**40, n, dtype=np.int64)},
        "plain_columns": {"k": rng.randint(-2**31, 2**31 - 1,
                                           n).astype(np.int32),
                          "v": floats,
                          "m": rng.randint(-9, 9, (n, 3)).astype(np.int32)},
        "four_shards_uneven": {"k": wide, "v": floats,
                               "m": rng.rand(n, 2).astype(np.float32)},
        "dict_beside_numeric": {"k": wide,
                                "s": words[rng.randint(0, 5, n)],
                                "v": floats},
    }[name]
    stored = {}
    for col, src in ref.items():
        if src.dtype == np.int64:
            stored[col], stored[col + ".lo"] = block_lib.encode_i64(src)
        elif src.dtype.kind == "U":
            stored[col] = np.searchsorted(words, src).astype(np.int32)
        else:
            stored[col] = src
    mesh = mesh_lib.make_mesh(len(counts))
    cols, at = {}, np.concatenate([[0], np.cumsum(counts)])
    for col, src in stored.items():
        dst = np.full((len(counts) * cap,) + src.shape[1:], -7, src.dtype)
        for s, c in enumerate(counts):
            dst[s * cap:s * cap + c] = src[at[s]:at[s + 1]]
        cols[col] = mesh_lib.host_put(dst, mesh_lib.shard_spec(mesh))
    counts = np.asarray(counts, np.int32)
    blk = block_lib.Block(cols=cols, counts=counts, capacity=cap, mesh=mesh,
                          counts_host=counts,
                          dicts={"s": words} if "s" in ref else None)
    return blk, ref


def _to_numpy_on_threads(blk, monkeypatch, threshold):
    """blk.to_numpy() with the threaded fill's threshold at `threshold`
    bytes, and for each run of rows whether the calling thread wrote it
    ("caller") or the name of the thread that did."""
    import threading

    from vega_tpu.tpu import block as block_lib

    seen, fill, caller = [], block_lib._fill, threading.current_thread()

    def spy(run):
        t = threading.current_thread()
        seen.append("caller" if t is caller else t.name)
        fill(run)

    with monkeypatch.context() as m:
        m.setattr(block_lib, "_THREADED_FILL_BYTES", threshold)
        m.setattr(block_lib, "_fill", spy)
        return blk.to_numpy(), seen


def _assert_same_bytes(got, want):
    assert list(got) == list(want)
    for name in want:
        assert got[name].dtype == want[name].dtype, name
        assert got[name].shape == want[name].shape, name
        assert got[name].tobytes() == want[name].tobytes(), name


@pytest.mark.parametrize("case", ["wide_key", "wide_value", "plain_columns",
                                  "four_shards_uneven",
                                  "dict_beside_numeric"])
def test_block_to_numpy_threaded_fill_is_byte_identical(case, monkeypatch):
    """Above the threshold to_numpy writes runs of rows on the decode pool,
    cut inside shards (runs of about 1 KB here, decode_i64's chunks of 64
    rows inside them): the same bytes as the inline loop and as the host
    columns the block was built from, on the pool's threads only."""
    from vega_tpu.tpu import block as block_lib

    blk, ref = _fill_case_block(case)
    inline, on = _to_numpy_on_threads(blk, monkeypatch, 1 << 62)
    assert set(on) == {"caller"} and len(on) == len(ref) * sum(
        1 for c in blk.counts_np if c)
    monkeypatch.setattr(block_lib, "_FILL_TASK_BYTES", 1000)
    monkeypatch.setattr(block_lib, "_DECODE_CHUNK_ROWS", 64)
    threaded, on = _to_numpy_on_threads(blk, monkeypatch, 1)
    assert len(on) > 2 * len(inline)
    assert all(name.startswith("vega-decode") for name in on)
    _assert_same_bytes(threaded, inline)
    _assert_same_bytes(threaded, ref)


@pytest.mark.parametrize("side", ["just_below", "at"])
def test_block_to_numpy_threshold_decides_the_path(side, monkeypatch):
    """A result one byte under the threshold is filled inline on the calling
    thread; one of exactly the threshold's bytes on the pool; the bytes are
    the same."""
    blk, ref = _fill_case_block("four_shards_uneven")
    nbytes = sum(a.nbytes for a in ref.values())
    got, on = _to_numpy_on_threads(
        blk, monkeypatch, nbytes + 1 if side == "just_below" else nbytes)
    if side == "just_below":
        assert set(on) == {"caller"}
    else:
        assert on and all(name.startswith("vega-decode") for name in on)
    _assert_same_bytes(got, ref)


def test_block_to_numpy_threaded_from_many_callers(monkeypatch):
    """More callers than the pool has threads, with a short switch interval:
    the lazily made pool is made once, and each caller's threaded fill comes
    back whole while the others share the pool."""
    import sys
    import threading

    from vega_tpu.tpu import block as block_lib

    blocks = [_fill_case_block(case) for case in
              ("wide_key", "four_shards_uneven", "dict_beside_numeric")]
    made, pool_cls = [], block_lib.ThreadPoolExecutor

    def counted(*args, **kwargs):
        made.append(pool_cls(*args, **kwargs))
        return made[-1]

    monkeypatch.setattr(block_lib, "_fill_pool", None)
    monkeypatch.setattr(block_lib, "ThreadPoolExecutor", counted)
    monkeypatch.setattr(block_lib, "_THREADED_FILL_BYTES", 1)
    monkeypatch.setattr(block_lib, "_FILL_TASK_BYTES", 1000)
    wrong = []

    def caller(i):
        blk, ref = blocks[i % len(blocks)]
        for _ in range(3):
            try:
                _assert_same_bytes(blk.to_numpy(), ref)
            except AssertionError as e:
                wrong.append((i, e))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=caller, args=(i,))
                   for i in range(3 * block_lib._FILL_THREADS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
        for pool in made:
            pool.shutdown()
    assert not any(t.is_alive() for t in threads)
    assert wrong == []
    assert len(made) == 1


def test_dense_int64_key_reduce_group_parity(dctx):
    keys, vals = _i64_fixture(1)
    d = dctx.dense_from_numpy(keys, vals)
    host = host_expected_reduce_by_key(
        zip(keys.tolist(), vals.tolist()), lambda a, b: a + b
    )
    assert dict(d.reduce_by_key(op="add").collect()) == host
    grouped = {k: sorted(vs) for k, vs in d.group_by_key().collect()}
    hostg = {}
    for k, x in zip(keys.tolist(), vals.tolist()):
        hostg.setdefault(k, []).append(x)
    assert grouped == {k: sorted(vs) for k, vs in hostg.items()}


def test_dense_int64_key_join_and_sort_parity(dctx):
    keys, vals = _i64_fixture(2, n=2000)
    d = dctx.dense_from_numpy(keys, vals)
    reduced = d.reduce_by_key(op="add")
    host = host_expected_reduce_by_key(
        zip(keys.tolist(), vals.tolist()), lambda a, b: a + b
    )
    table_keys = np.unique(keys)[::2]
    table = dctx.dense_from_numpy(
        table_keys, np.arange(len(table_keys), dtype=np.int32)
    )
    got = sorted(reduced.join(table).collect())
    exp = sorted(
        (int(k), (host[int(k)], i)) for i, k in enumerate(table_keys)
    )
    assert got == exp
    # sample sort over int64 keys, both directions
    s = d.sort_by_key()
    assert [k for k, _ in s.collect()] == sorted(keys.tolist())
    s_desc = d.sort_by_key(ascending=False)
    assert [k for k, _ in s_desc.collect()] == sorted(keys.tolist(),
                                                      reverse=True)


def test_dense_int64_key_mixed_width_join_widens(dctx):
    """Joining an int64-keyed side with an int32-keyed side widens the
    narrow side on device (same logical key -> same shard); float keys
    against int64 keys take the host path (Python equality semantics)."""
    from vega_tpu.tpu.dense_rdd import DenseRDD, _JoinRDD

    fact = dctx.dense_from_numpy(
        np.array([0, -7, 2**40, 2**40], dtype=np.int64),
        np.array([1.0, 2.0, 3.0, 4.0], dtype=np.float32),
    )
    t32 = dctx.dense_from_numpy(
        np.array([0, -7, 9], dtype=np.int32),
        np.array([10.0, 20.0, 90.0], dtype=np.float32),
    )
    j = fact.join(t32)
    assert isinstance(j, _JoinRDD)
    assert sorted(j.collect()) == [(-7, (2.0, 20.0)), (0, (1.0, 10.0))]
    # reversed orientation widens the other side
    j2 = t32.join(fact)
    assert isinstance(j2, _JoinRDD)
    assert sorted(j2.collect()) == [(-7, (20.0, 2.0)), (0, (10.0, 1.0))]
    # float-keyed side cannot widen: host path, still correct
    tf = dctx.dense_from_numpy(np.array([0.0, 2.0], dtype=np.float32),
                               np.array([5.0, 6.0], dtype=np.float32))
    j3 = fact.join(tf)
    assert not isinstance(j3, DenseRDD)
    assert sorted(j3.collect()) == [(0, (1.0, 5.0))]


def test_dense_int64_key_cogroup_and_outer_join(dctx):
    fact = dctx.dense_from_numpy(
        np.array([2**40, 2**40, 5], dtype=np.int64),
        np.array([1, 2, 3], dtype=np.int32),
    )
    other = dctx.dense_from_numpy(
        np.array([2**40, -2**40], dtype=np.int64),
        np.array([7, 8], dtype=np.int32),
    )
    cg = dict(fact.cogroup(other).collect())
    assert cg[2**40] == ([1, 2], [7])
    assert cg[5] == ([3], [])
    assert cg[-2**40] == ([], [8])
    lo = sorted(fact.left_outer_join(other, fill_value=0).collect())
    assert lo == [(5, (3, 0)), (2**40, (1, 7)), (2**40, (2, 7))]


def test_dense_int64_key_row_closures_fall_back(dctx):
    """Row-wise closures over int64-keyed blocks have no device form (the
    int64 scalar is untraceable without x64) — they silently take the host
    tier with decoded keys; map_values stays on device."""
    from vega_tpu.tpu.dense_rdd import DenseRDD, _MapValuesRDD

    keys = np.array([2**40, 1, 2**40], dtype=np.int64)
    d = dctx.dense_from_numpy(keys, np.array([1, 2, 3], dtype=np.int32))
    m = d.map(lambda kv: (kv[0], kv[1] * 10))
    assert not isinstance(m, DenseRDD)
    assert sorted(m.collect()) == [(1, 20), (2**40, 10), (2**40, 30)]
    mv = d.map_values(lambda x: x * 10)
    assert isinstance(mv, _MapValuesRDD)
    assert sorted(mv.collect()) == [(1, 20), (2**40, 10), (2**40, 30)]
    # keys over the composite block decode on the host tier
    assert sorted(mv.keys().collect()) == [1, 2**40, 2**40]


def test_dense_int64_key_save_load_npz(dctx, tmp_path):
    keys, vals = _i64_fixture(3, n=500)
    d = dctx.dense_from_numpy(keys, vals)
    p = str(tmp_path / "i64.npz")
    d.save_npz(p)
    loaded = dctx.dense_load_npz(p)
    assert sorted(loaded.collect()) == sorted(zip(keys.tolist(),
                                                  vals.tolist()))


def test_histogram_sizing_no_retries_under_skew(ctx):
    """Exchange capacities come from a one-pass destination histogram, so
    even a fully-skewed key distribution (every row to one reducer) runs in
    ONE attempt — no overflow -> grow -> recompile loop (the round-1 jit
    thrash hazard)."""
    skewed = ctx.dense_range(8192).map(lambda x: (x * 0, x))
    node = skewed.reduce_by_key(op="add")
    assert dict(node.collect()) == {0: sum(range(8192))}
    assert node._last_attempts == 1

    # 90/10 mixed skew through a join as well.
    keys = np.where(np.arange(4096) % 10 == 0, np.arange(4096) % 7, 0)
    left = ctx.dense_from_numpy(keys.astype(np.int32),
                                np.ones(4096, dtype=np.int32))
    right = ctx.dense_from_numpy(np.arange(7, dtype=np.int32),
                                 np.arange(7, dtype=np.int32) * 2)
    j = left.reduce_by_key(op="add").join(right)
    assert j.count() == len(set(keys.tolist()))
    assert j._last_attempts == 1

    srt = ctx.dense_from_numpy(keys.astype(np.int32),
                               keys.astype(np.int32)).sort_by_key()
    sk = [k for k, _ in srt.collect()]
    assert sk == sorted(keys.tolist())
    assert srt._last_attempts == 1


def test_collect_grouped_columnar_parity(ctx):
    """collect_grouped returns (keys, offsets, values) arrays whose groups
    match the host tier's group_by_key exactly."""
    n, k = 20_000, 113
    grouped = ctx.dense_range(n).map(lambda x: (x % k, x)).group_by_key()
    keys, offsets, values = grouped.collect_grouped()
    assert len(keys) == k
    assert offsets[0] == 0 and offsets[-1] == n
    host = dict(
        ctx.range(n, num_slices=8).map(lambda x: (x % k, x))
        .group_by_key(8).collect()
    )
    for i, key in enumerate(keys.tolist()):
        got = sorted(values[offsets[i]:offsets[i + 1]].tolist())
        assert got == sorted(host[key]), f"group {key} mismatch"

    # cogroup over the same machinery (columnar merge path)
    other = ctx.dense_range(500).map(lambda x: (x % 7, x * 10))
    cg = dict(ctx.dense_range(300).map(lambda x: (x % 5, x))
              .cogroup(other).collect())
    for key, (lvs, rvs) in cg.items():
        assert sorted(lvs) == [x for x in range(300) if x % 5 == key]
        assert sorted(rvs) == [x * 10 for x in range(500) if x % 7 == key]


def test_flat_map_ragged_device(dctx):
    """Variable-arity flat_map on device: each row x emits x % 4 copies of
    itself (bounded by 3) — parity vs the host flat_map."""
    import jax.numpy as jnp

    def emit(x):
        n = x % 4  # 0..3 outputs
        return jnp.full((3,), x), n

    from vega_tpu.tpu.dense_rdd import DenseRDD

    r = dctx.dense_range(2_000).flat_map_ragged(emit, 3)
    assert isinstance(r, DenseRDD), "must stay on device"
    got = sorted(r.collect())
    exp = sorted(x for x in range(2_000) for _ in range(x % 4))
    assert got == exp

    # pair output feeds the shuffle ops directly
    def emit_kv(x):
        ks = jnp.stack([x % 7, x % 7])
        vs = jnp.stack([x, x * 0 + 1])
        return (ks, vs), jnp.int32(2)

    kv = dctx.dense_range(1_000).flat_map_ragged(emit_kv, 2)
    red = dict(kv.reduce_by_key(op="add").collect())
    exp_red = {}
    for x in range(1_000):
        exp_red[x % 7] = exp_red.get(x % 7, 0) + x + 1
    assert red == exp_red


def test_flat_map_ragged_untraceable_falls_back(dctx):
    """An untraceable ragged closure degrades to the host flat_map with
    identical results."""
    def emit(x):
        n = int(x) % 3  # int() breaks tracing
        import numpy as _np

        return _np.full(2, int(x)), min(n, 2)

    from vega_tpu.tpu.dense_rdd import DenseRDD

    r = dctx.dense_range(300).flat_map_ragged(emit, 2)
    assert not isinstance(r, DenseRDD)
    got = sorted(r.collect())
    exp = sorted(x for x in range(300) for _ in range(min(x % 3, 2)))
    assert got == exp


def test_expansion_nodes_chain_with_narrow_ops(dctx):
    """Narrow ops AFTER a capacity-changing expansion node must
    materialize the expansion via its own program, not fuse through it
    (chain-break regression: map/filter after flat_map_ragged/map_expand
    used to hit NotImplementedError)."""
    import jax.numpy as jnp

    def emit(x):
        return jnp.full((3,), x), x % 4

    r = (dctx.dense_range(500).flat_map_ragged(emit, 3)
         .map(lambda x: x + 1).filter(lambda x: x % 2 == 0))
    exp = sorted(x + 1 for x in range(500) for _ in range(x % 4)
                 if (x + 1) % 2 == 0)
    assert sorted(r.collect()) == exp

    m = dctx.dense_range(100).map_expand(
        lambda x: jnp.stack([x, x + 1000]), 2
    ).map(lambda x: x * 2)
    exp_m = sorted(x * 2 for pair in ((y, y + 1000) for y in range(100))
                   for x in pair)
    assert sorted(m.collect()) == exp_m


def test_dense_combine_by_key_family(dctx):
    """combine_by_key stays on device for scalar traceable combiners and
    matches host results; fold/aggregate_by_key keep host semantics (zero
    once per key per partition) by delegating to the host tier."""
    from vega_tpu.tpu.dense_rdd import DenseRDD

    n, k = 5_000, 23
    kv = dctx.dense_range(n).map(lambda x: (x % k, (x % 100) * 1.0))
    host_kv = dctx.parallelize(
        [(x % k, (x % 100) * 1.0) for x in range(n)], 8)
    # sum of squares per key
    cbk = kv.combine_by_key(lambda v: v * v, lambda c, v: c + v * v,
                            lambda a, b: a + b)
    assert isinstance(cbk, DenseRDD)
    got = dict(cbk.collect())
    host = dict(host_kv.combine_by_key(lambda v: v * v,
                                       lambda c, v: c + v * v,
                                       lambda a, b: a + b, 8).collect())
    for key in host:
        assert got[key] == pytest.approx(host[key], rel=1e-6)

    # fold/aggregate: host-tier semantics, host-tier execution — including
    # the zero-per-key-per-partition behavior for non-neutral zeros
    # (dense shards and the 8-slice host rdd hold identical contiguous
    # ranges, so results match exactly).
    agg = dict(kv.aggregate_by_key(10.0, lambda a, v: a + v,
                                   lambda a, b: a + b).collect())
    hagg = dict(host_kv.aggregate_by_key(10.0, lambda a, v: a + v,
                                         lambda a, b: a + b, 8).collect())
    assert agg == hagg
    fold = dict(kv.fold_by_key(10.0, lambda a, v: a + v).collect())
    hfold = dict(host_kv.fold_by_key(10.0, lambda a, v: a + v, 8).collect())
    assert fold == hfold


def test_dense_combine_by_key_untraceable_falls_back(dctx):
    from vega_tpu.tpu.dense_rdd import DenseRDD

    kv = dctx.dense_range(200).map(lambda x: (x % 5, x))
    r = kv.combine_by_key(lambda v: [int(v)], lambda c, v: c + [int(v)],
                          lambda a, b: a + b)
    assert not isinstance(r, DenseRDD)
    got = {key: sorted(vals) for key, vals in r.collect()}
    assert got[2] == list(range(2, 200, 5))


def test_dense_untraceable_reduce_falls_back_once(dctx):
    """Regression: an untraceable reduce_by_key on a dense RDD must fall
    back to ONE host shuffle node, not recurse through the overridden
    combine_by_key building hundreds of identity wrappers."""
    kv = dctx.dense_range(300).map(lambda x: (x % 3, x))
    r = kv.reduce_by_key(lambda a, b: max(int(a), int(b)))
    depth = 0
    node = r
    while node.get_dependencies():
        node = node.get_dependencies()[0].rdd
        depth += 1
        assert depth < 10, "lineage blew up — fallback recursion returned"
    assert depth >= 1, "walk must actually traverse the lineage"
    assert dict(r.collect()) == {c: max(range(c, 300, 3)) for c in range(3)}


def test_expansion_nodes_chain_with_narrow_ops(dctx):
    """Narrow ops AFTER a capacity-changing expansion node must
    materialize the expansion via its own program, not fuse through it
    (chain-break regression: map/filter after flat_map_ragged/map_expand
    used to hit NotImplementedError)."""
    import jax.numpy as jnp

    def emit(x):
        return jnp.full((3,), x), x % 4

    r = (dctx.dense_range(500).flat_map_ragged(emit, 3)
         .map(lambda x: x + 1).filter(lambda x: x % 2 == 0))
    exp = sorted(x + 1 for x in range(500) for _ in range(x % 4)
                 if (x + 1) % 2 == 0)
    assert sorted(r.collect()) == exp

    m = dctx.dense_range(100).map_expand(
        lambda x: jnp.stack([x, x + 1000]), 2
    ).map(lambda x: x * 2)
    exp_m = sorted(x * 2 for pair in ((y, y + 1000) for y in range(100))
                   for x in pair)
    assert sorted(m.collect()) == exp_m


def test_dense_combine_by_key_family(dctx):
    """combine_by_key / aggregate_by_key / fold_by_key stay on device for
    scalar traceable combiners and match host results."""
    from vega_tpu.tpu.dense_rdd import DenseRDD

    n, k = 5_000, 23
    kv = dctx.dense_range(n).map(lambda x: (x % k, (x % 100) * 1.0))
    # sum of squares per key
    cbk = kv.combine_by_key(lambda v: v * v, lambda c, v: c + v * v,
                            lambda a, b: a + b)
    assert isinstance(cbk, DenseRDD)
    got = dict(cbk.collect())
    host = dict(
        dctx.parallelize([(x % k, (x % 100) * 1.0) for x in range(n)], 8)
        .combine_by_key(lambda v: v * v, lambda c, v: c + v * v,
                        lambda a, b: a + b, 8).collect()
    )
    import pytest as _pt
    for key in host:
        assert got[key] == _pt.approx(host[key], rel=1e-6)

    agg = dict(kv.aggregate_by_key(0.0, lambda a, v: a + v,
                                   lambda a, b: a + b).collect())
    fold = dict(kv.fold_by_key(0.0, lambda a, v: a + v).collect())
    ref = {}
    for x in range(n):
        ref[x % k] = ref.get(x % k, 0.0) + (x % 100) * 1.0
    for key, val in ref.items():
        assert agg[key] == _pt.approx(val)
        assert fold[key] == _pt.approx(val)


def test_dense_combine_by_key_untraceable_falls_back(dctx):
    from vega_tpu.tpu.dense_rdd import DenseRDD

    kv = dctx.dense_range(200).map(lambda x: (x % 5, x))
    r = kv.combine_by_key(lambda v: [int(v)], lambda c, v: c + [int(v)],
                          lambda a, b: a + b)
    assert not isinstance(r, DenseRDD)
    got = {key: sorted(vals) for key, vals in r.collect()}
    assert got[2] == list(range(2, 200, 5))


def test_hash_placed_propagation_and_elision(dctx):
    """hash_placed propagates through key-preserving ops and resets on
    key-rewriting ones; elided shuffles match un-elided results exactly."""
    kv = dctx.dense_range(10_000).map(lambda x: (x % 50, x))
    assert not kv.hash_placed
    reduced = kv.reduce_by_key(op="add")
    # A bare property read is PURE (round-4 advisor): unmaterialized it
    # answers a conservative False and does NOT launch the exchange.
    assert not reduced.hash_placed
    assert reduced._block is None
    # Planners get the materialized truth via the explicit settle.
    reduced._settle_placement()
    assert reduced.hash_placed
    assert reduced.map_values(lambda v: v * 2).hash_placed
    assert reduced.filter(lambda p: p[1] > 0).hash_placed
    assert not reduced.map(lambda p: (p[1], p[0])).hash_placed  # key rewrite

    # reduce-of-reduce: second reduce elides its exchange; results must
    # equal a fresh single reduce — and the elision must actually RUN
    # (the _elided flag guards against the optimization silently dying)
    rr_node = reduced.map_values(lambda v: v).reduce_by_key(op="add")
    again = dict(rr_node.collect())
    base_node = kv.reduce_by_key(op="add")
    base = dict(base_node.collect())
    assert again == base
    assert rr_node._elided is True
    assert base_node._elided is False

    # group_by_key over placed data
    g_node = reduced.group_by_key()
    g = dict(g_node.collect())
    assert all(g[key] == [base[key]] for key in base)
    assert g_node._elided is True

    # join with a placed left side (the north-star shape): one collective
    table = dctx.dense_from_numpy(np.arange(50, dtype=np.int32),
                                  np.arange(50, dtype=np.int32) * 7)
    j_node = reduced.join(table)
    j = dict(j_node.collect())
    assert j == {key: (base[key], key * 7) for key in base}
    assert j_node._elided == (True, False)
    # join of two placed sides: zero collectives
    both = reduced.join(kv.map_values(lambda v: v * 0).reduce_by_key(op="add"))
    assert dict(both.collect()) == {key: (base[key], 0) for key in base}
    assert both._elided == (True, True)


def test_key_sorted_propagation_skips_sorts(dctx):
    """key_sorted propagates with hash_placed; sorted-elided pipelines
    still produce exact results (the skipped sorts were redundant)."""
    kv = dctx.dense_range(20_000).map(lambda x: (x % 101, x))
    reduced = kv.reduce_by_key(op="add")
    reduced._settle_placement()  # property reads are pure (conservative)
    assert reduced.key_sorted and reduced.map_values(lambda v: v).key_sorted
    assert not kv.key_sorted

    base = dict(reduced.collect())
    # reduce-of-reduce with presorted segment reduce
    rr = dict(reduced.map_values(lambda v: v).reduce_by_key(op="min")
              .collect())
    assert rr == base  # single-row segments: min == value

    # MULTI-row presorted segments: a group_by_key output (duplicate keys
    # in sorted runs) feeds reduce_by_key, exercising the presorted
    # boundary detection over real segments.
    grouped = kv.group_by_key()
    assert grouped.key_sorted
    regrouped = dict(grouped.reduce_by_key(op="add").collect())
    full = {}
    for x in range(20_000):
        full[x % 101] = full.get(x % 101, 0) + x
    assert regrouped == full
    # sorted-elided group_by (sort skipped)
    g = dict(reduced.group_by_key().collect())
    assert {key: vals[0] for key, vals in g.items()} == base
    # sorted-elided join on both sides (both sorts skipped)
    other = kv.map_values(lambda v: v * 2).reduce_by_key(op="add")
    j = dict(reduced.join(other).collect())
    assert j == {key: (base[key], 2 * base[key]) for key in base}


def test_dense_multicolumn_tuple_combiner(dctx):
    """reduce_by_key with a tuple-valued traced binop over a multi-column
    block: streaming mean/variance components stay on device."""
    import jax.numpy as jnp

    rng = np.random.RandomState(3)
    keys = rng.randint(0, 20, 5_000).astype(np.int32)
    x = rng.rand(5_000).astype(np.float32)
    blk = dctx.dense_from_columns(
        {"k": keys, "s": x, "ss": x * x,
         "cnt": np.ones(5_000, np.float32)}, key="k",
    )

    def comb(a, b):
        return (a[0] + b[0], a[1] + b[1], a[2] + b[2])

    got = blk.reduce_by_key(comb)
    from vega_tpu.tpu.dense_rdd import DenseRDD

    assert isinstance(got, DenseRDD)
    cols = got.collect_arrays()
    by_key = {int(k_): (s, ss, c) for k_, s, ss, c in zip(
        cols["k"], cols["s"], cols["ss"], cols["cnt"])}
    for k_ in range(20):
        sel = x[keys == k_]
        s, ss, c = by_key[k_]
        assert c == len(sel)
        assert s == pytest.approx(float(sel.sum()), rel=1e-4)
        mean = s / c
        var = ss / c - mean * mean
        assert var == pytest.approx(float(sel.var()), rel=1e-3, abs=1e-5)

    # Arity mismatch on a multi-column block has no host fallback form:
    # it must raise crisply, never feed the host tier tuples it can't fold.
    def bad(a, b):
        return a[0] + b[0]  # scalar, not a 3-tuple

    with pytest.raises(v.VegaError, match="tuple binop"):
        blk.reduce_by_key(bad)


def test_dense_map_values_multicolumn_rejected(dctx):
    blk = dctx.dense_from_columns({"k": np.arange(10), "a": np.arange(10),
                                   "b": np.arange(10)}, key="k")
    with pytest.raises(v.VegaError, match="exactly one value column"):
        blk.map_values(lambda x: x)


def test_single_named_value_column_ops(dctx):
    """A block with one value column under a non-canonical name works with
    map_values and traced reduce_by_key on device."""
    from vega_tpu.tpu.dense_rdd import DenseRDD

    blk = dctx.dense_from_columns(
        {"k": (np.arange(1000) % 9).astype(np.int32),
         "s": np.arange(1000, dtype=np.int32)}, key="k")
    mapped = blk.map_values(lambda x: x * 2)
    assert isinstance(mapped, DenseRDD)
    red = mapped.reduce_by_key(lambda a, b: a + b)
    assert isinstance(red, DenseRDD)
    cols = red.collect_arrays()
    got = dict(zip(cols["k"].tolist(), cols["s"].tolist()))
    assert got == {key: 2 * sum(range(key, 1000, 9)) for key in range(9)}

    # untraceable binop on a named block: crisp error, not silent garbage
    with pytest.raises(v.VegaError, match="traceable binop"):
        blk.reduce_by_key(lambda a, b: max(int(a), int(b)))


def test_dtype_changing_binop_keeps_schema_truthful(dctx):
    """A binop that changes the value dtype cannot run on device (the
    block schema would lie); on canonical (k, v) blocks it falls back to
    the host tier with correct (retyped) results."""
    from vega_tpu.tpu.dense_rdd import DenseRDD

    kv = dctx.dense_range(100).map(lambda x: (x % 5, x))
    # int -> float promotion; associative, and sums stay exact in float,
    # so the result is order-independent and host-comparable.
    r = kv.reduce_by_key(lambda a, b: a + b + 0.0)
    assert not isinstance(r, DenseRDD)  # host fallback
    assert dict(r.collect()) == {
        key: float(sum(range(key, 100, 5))) for key in range(5)
    }


def test_cogroup_collect_grouped_columnar(dctx):
    """Columnar cogroup result matches the per-group collect() exactly."""
    left = dctx.dense_range(4_000).map(lambda x: (x % 60, x))
    right = dctx.dense_range(900).map(lambda x: (x % 75, x * 10))
    cg = left.cogroup(right)
    keys, lo, lv, ro, rv = cg.collect_grouped()
    assert lo[-1] == 4_000 and ro[-1] == 900
    ref = dict(cg.collect())
    assert len(keys) == len(ref)
    for i, key in enumerate(keys.tolist()):
        lvs, rvs = ref[key]
        assert sorted(lv[lo[i]:lo[i + 1]].tolist()) == sorted(lvs)
        assert sorted(rv[ro[i]:ro[i + 1]].tolist()) == sorted(rvs)


def test_dense_cartesian_parity_and_budget_gate(dctx):
    """Device cartesian (BASELINE config 4) matches the host tier; an
    over-budget product degrades to the lazy host cartesian."""
    from vega_tpu.tpu.dense_rdd import DenseRDD, _CartesianDenseRDD

    a = dctx.dense_range(300)
    b = dctx.dense_from_numpy(np.array([10, 20, 30], dtype=np.int32))
    cart = a.cartesian(b)
    assert isinstance(cart, _CartesianDenseRDD)
    got = sorted(cart.collect())
    exp = sorted((x, y) for x in range(300) for y in (10, 20, 30))
    assert got == exp
    assert cart.count() == 900

    # pair ops compose on the device product (canonical (KEY, VALUE))
    red = dict(cart.reduce_by_key(op="add").collect())
    assert red == {x: 60 for x in range(300)}

    # over-budget: operands stay RESIDENT (10 MB budget) but the ~300 MB
    # product trips the gate inside _CartesianDenseRDD -> lazy host path
    from vega_tpu.env import Env

    old = Env.get().conf.dense_hbm_budget
    Env.get().conf.dense_hbm_budget = 10 << 20
    try:
        left = dctx.dense_range(10_000)
        assert isinstance(left, DenseRDD)  # resident, gate actually runs
        big = left.cartesian(dctx.dense_range(10_000))
        assert not isinstance(big, DenseRDD)
        assert big.take(2) == [(0, 0), (0, 1)]
    finally:
        Env.get().conf.dense_hbm_budget = old

    # empty right side
    empty = dctx.dense_range(50).cartesian(
        dctx.dense_range(100).filter(lambda x: x < 0))
    assert empty.count() == 0


def test_dense_from_columns_int64_keys_stay_dense(dctx):
    """int64 KEYS stay on device via the two-column encoding — both the
    canonical (key, value) face and named/multi-column blocks; int64
    VALUES on named blocks keep the crisp error (no host row form)."""
    from vega_tpu.tpu.dense_rdd import DenseRDD

    r = dctx.dense_from_columns({"k": [2**40, 2**40, 1], "v": [1, 2, 3]},
                                key="k")
    assert isinstance(r, DenseRDD)
    assert dict(r.reduce_by_key(op="add").collect()) == {2**40: 3, 1: 3}
    multi = dctx.dense_from_columns({"k": [2**40, 1], "x": [1, 2],
                                     "y": [2, 4]}, key="k")
    assert isinstance(multi, DenseRDD)
    got = multi.reduce_by_key(op="add")
    arrays = got.collect_arrays()
    by_key = dict(zip(arrays["k"].tolist(),
                      zip(arrays["x"].tolist(), arrays["y"].tolist())))
    assert by_key == {2**40: (1, 2), 1: (2, 4)}
    # int64 VALUE columns on named blocks ride the wide encoding and
    # reduce on device with carry arithmetic (previously a crisp error)
    wv = dctx.dense_from_columns({"k": [1, 1, 2], "x": [2**40, 5, 7],
                                  "y": [2, 3, 4]}, key="k")
    assert isinstance(wv, DenseRDD)
    arrays = wv.reduce_by_key(op="add").collect_arrays()
    by_key = dict(zip(arrays["k"].tolist(),
                      zip(arrays["x"].tolist(), arrays["y"].tolist())))
    assert by_key == {1: (2**40 + 5, 5), 2: (7, 4)}


def test_dense_intersection_subtract(dctx):
    """Set ops compose on device and match the host tier exactly."""
    from vega_tpu.tpu.dense_rdd import DenseRDD

    a_vals = [1, 2, 2, 3, 5, 8, 8, 13]
    b_vals = [2, 3, 21, 34]
    a = dctx.dense_from_numpy(np.array(a_vals, dtype=np.int32))
    b = dctx.dense_from_numpy(np.array(b_vals, dtype=np.int32))

    inter = a.intersection(b)
    assert isinstance(inter, DenseRDD)
    assert sorted(inter.collect()) == [2, 3]

    sub = a.subtract(b)
    assert isinstance(sub, DenseRDD)
    assert sorted(sub.collect()) == [1, 5, 8, 8, 13]  # dups preserved

    host_a = dctx.parallelize(a_vals, 3)
    host_b = dctx.parallelize(b_vals, 2)
    assert sorted(inter.collect()) == sorted(host_a.intersection(host_b).collect())
    assert sorted(sub.collect()) == sorted(host_a.subtract(host_b).collect())


def test_dense_set_ops_dtype_mismatch_falls_back(dctx):
    """int32 vs float32 operands hash differently on device but compare
    equal on the host — mismatched dtypes must take the host path."""
    from vega_tpu.tpu.dense_rdd import DenseRDD

    a = dctx.dense_from_numpy(np.array([1, 2, 3, 100], dtype=np.int32))
    b = dctx.dense_from_numpy(np.array([2.0, 3.0, 7.0], dtype=np.float32))
    inter = a.intersection(b)
    assert not isinstance(inter, DenseRDD)
    assert sorted(inter.collect()) == [2, 3]
    sub = a.subtract(b)
    assert not isinstance(sub, DenseRDD)
    assert sorted(sub.collect()) == [1, 100]


def test_dense_from_columns_rejects_reserved_lo_name(dctx):
    """A user column named 'k.lo' would be silently consumed as the low
    word of a composite key — reject it crisply."""
    with pytest.raises(v.VegaError):
        dctx.dense_from_columns(
            {"k": np.array([1, 2], np.int32),
             "k.lo": np.array([5, 6], np.int32)}, key="k",
        )


def test_capacity_hints_skip_histogram_on_rerun(dctx, monkeypatch):
    """A structurally identical second pipeline over same-count inputs
    reuses the memoized exchange capacities: no sizing-histogram device
    pass (one driver<->device round trip saved per exchange)."""
    from vega_tpu.tpu import dense_rdd as dr

    calls = {"n": 0}
    real = dr._ExchangeRDD._hash_histogram

    def counting(self, blk, chain=()):
        calls["n"] += 1
        return real(self, blk, chain)

    monkeypatch.setattr(dr._ExchangeRDD, "_hash_histogram", counting)

    def pipeline():
        kv = dctx.dense_range(4_000).map(lambda x: (x % 97, x))
        red = kv.reduce_by_key(op="add")
        table = dctx.dense_from_numpy(
            np.arange(97, dtype=np.int32), np.arange(97, dtype=np.int32)
        )
        return dict(red.join(table).collect())

    first = pipeline()
    n_first = calls["n"]
    assert n_first > 0  # cold run sized via histograms
    second = pipeline()
    assert second == first
    assert calls["n"] == n_first  # warm run: zero histogram passes
    assert dctx._dense_capacity_hints  # hints recorded


def test_capacity_hint_overflow_falls_back_to_histogram(dctx):
    """A stale/bogus hint (e.g. the key distribution changed under equal
    counts) must not break anything: the overflow flag triggers the exact
    histogram path and results stay correct."""
    n_keys = 2_000  # ~250 combiners per shard >> the poisoned capacity
    kv = dctx.dense_range(3_000).map(lambda x: (x % n_keys, x))
    node = kv.reduce_by_key(op="add")
    # Poison the hint store for this exact lineage+sizes with capacities
    # too small for the real distribution, then materialize. The hinted
    # launch runs SPECULATIVELY (no blocking overflow fetch); the first
    # host read settles the flag and repairs through the histogram path.
    key = node._hint_key()
    dctx.__dict__.setdefault("_dense_capacity_hints", {})[key] = (128, 128)
    got = dict(node.collect())
    assert got == {k: sum(x for x in range(3_000) if x % n_keys == k)
                   for k in range(n_keys)}
    # the bad hint was replaced by working capacities
    assert dctx._dense_capacity_hints[key] != (128, 128)
    # and nothing is left pending after settlement
    assert not dctx.__dict__.get("_dense_pending")


def test_narrow_chain_fuses_into_exchange(dctx):
    """A pending map/filter chain above reduce/group rides the exchange
    program: the intermediate narrow block is never materialized (one
    launch instead of two, no intermediate HBM block)."""
    kv = dctx.dense_range(10_000).map(lambda x: (x % 50, x))
    red = kv.reduce_by_key(op="add")
    got = dict(red.collect())
    assert got == {k: sum(x for x in range(10_000) if x % 50 == k)
                   for k in range(50)}
    assert kv._block is None  # fused, not materialized

    kv2 = dctx.dense_range(1_000).map(lambda x: (x % 7, x)).filter(
        lambda kv: kv[1] % 2 == 0
    )
    grouped = dict(kv2.group_by_key().collect())
    assert grouped == {
        k: [x for x in range(0, 1_000, 2) if x % 7 == k] for k in range(7)
    }
    assert kv2._block is None

    # a chain shared with another consumer materializes for that consumer
    # and the exchange then uses the materialized block as its root
    kv3 = dctx.dense_range(1_000).map(lambda x: (x % 3, x))
    assert kv3.count() == 1_000  # materializes kv3
    assert kv3._block is not None
    assert dict(kv3.reduce_by_key(op="min").collect()) == {0: 0, 1: 1, 2: 2}


def test_narrow_chain_fuses_into_join_and_sort(dctx):
    """Chain fusion covers join sides and sort_by_key (sampling included):
    the narrow parents never materialize and results match the host
    tier — including a fused FILTER, whose post-chain counts drive the
    sort's stride/validity math."""
    lk = dctx.dense_range(5_000).map(lambda x: (x % 100, x))
    rk = dctx.dense_range(100).map(lambda x: (x, x * 2))
    j = lk.join(rk)
    got = sorted(j.collect())
    exp = sorted((x % 100, (x, (x % 100) * 2)) for x in range(5_000))
    assert got == exp
    assert lk._block is None and rk._block is None  # fused

    sk = (dctx.dense_range(10_000).map(lambda x: (x * 7919 % 10_000, x))
          .filter(lambda kv: kv[0] % 2 == 0))
    s = sk.sort_by_key()
    keys = [k for k, _ in s.collect()]
    assert keys == sorted(k for k in (x * 7919 % 10_000
                                      for x in range(10_000)) if k % 2 == 0)
    assert sk._block is None  # fused through sampling + exchange


def test_named_multicolumn_join_rejected_crisply(dctx):
    """Named/multi-column pair blocks must not reach the lv/rv join (its
    output contract is (k, (lv, rv)) rows) NOR the host cogroup fallback
    (no host row form) — crisp VegaError on every join-family op."""
    named = dctx.dense_from_columns(
        {"k": np.arange(20, dtype=np.int32) % 5,
         "avg": np.arange(20, dtype=np.float32),
         "cnt": np.ones(20, dtype=np.int32)}, key="k")
    canon = dctx.dense_from_numpy(np.arange(5, dtype=np.int32),
                                  np.arange(5, dtype=np.int32) * 2)
    for op in ("join", "left_outer_join", "cogroup"):
        with pytest.raises(v.VegaError, match="named/multi-column"):
            getattr(named, op)(canon)
        with pytest.raises(v.VegaError, match="named/multi-column"):
            getattr(canon, op)(named)


def test_rename_bridges_named_to_canonical(dctx):
    """rename({'w': 'v'}) re-opens the canonical-layout paths (join,
    map_values host fallback) for blocks built with user column names."""
    from vega_tpu.tpu.dense_rdd import DenseRDD, _JoinRDD

    ks = np.arange(20, dtype=np.int32) % 5
    ws = np.arange(20, dtype=np.float32)
    named = dctx.dense_from_columns({"k": ks, "w": ws}, key="k")
    canon = named.rename({"w": "v"})
    assert isinstance(canon, DenseRDD)
    assert {nm for nm, _ in canon._schema()} == {"k", "v"}
    table = dctx.dense_from_numpy(np.arange(5, dtype=np.int32),
                                  np.arange(5, dtype=np.int32) * 10)
    j = canon.join(table)
    assert isinstance(j, _JoinRDD)
    exp = sorted((int(k), (float(w), int(k) * 10)) for k, w in zip(ks, ws))
    assert sorted(j.collect()) == exp

    # wide int64 pair travels with the rename, then decodes on host reads
    big = (np.arange(20).astype(np.int64) << 40) + 7
    wide = dctx.dense_from_columns({"k": ks, "w": big}, key="k")
    rn = wide.rename({"w": "v"})
    assert {nm for nm, _ in rn._schema()} == {"k", "v", "v.lo"}
    assert sorted(rn.collect()) == sorted(zip(ks.tolist(), big.tolist()))

    # guard rails
    with pytest.raises(v.VegaError, match="no such column"):
        named.rename({"zz": "v"})
    with pytest.raises(v.VegaError, match="key columns"):
        named.rename({"k": "v"})
    with pytest.raises(v.VegaError, match="key columns"):
        named.rename({"w": "k"})  # fabricating a pair from values
    with pytest.raises(v.VegaError, match="reserved"):
        named.rename({"w": "x.lo"})
    two = dctx.dense_from_columns({"k": ks, "a": ws, "b": ws}, key="k")
    with pytest.raises(v.VegaError, match="collide"):
        two.rename({"a": "b"})


def test_map_values_wide_named_column_errors_logically(dctx):
    """A single NAMED wide int64 column raises naming ONE logical column
    (never leaking .lo as a phantom second column); multi-column messages
    list logical names only."""
    ks = np.arange(10, dtype=np.int32)
    big = (np.arange(10).astype(np.int64) << 40)
    one = dctx.dense_from_columns({"k": ks, "w": big}, key="k")
    with pytest.raises(v.VegaError, match="wide int64 column 'w'"):
        one.map_values(lambda x: x + 1)
    # canonical wide layout still silently host-falls-back
    canon = one.rename({"w": "v"})
    got = dict(canon.map_values(lambda x: x + 1).collect())
    assert got == {int(k): int(b) + 1 for k, b in zip(ks, big)}
    multi = dctx.dense_from_columns(
        {"k": ks, "w": big, "x": ks.astype(np.float32)}, key="k")
    with pytest.raises(v.VegaError) as ei:
        multi.map_values(lambda x: x)
    assert ".lo" not in str(ei.value)


def test_warm_rerun_defers_overflow_to_settlement(dctx):
    """A warm rerun of the same pipeline shape launches speculatively: the
    exchange skips its blocking overflow fetch, the block carries a settle
    hook, and the first host read verifies + commits in one transfer."""
    import numpy as np

    def build():
        kv = dctx.dense_range(20_000).map(lambda x: (x % 500, x * 1.0))
        red = kv.reduce_by_key(op="add")
        table = dctx.dense_from_numpy(np.arange(500, dtype=np.int32),
                                      np.arange(500, dtype=np.float32))
        return red, red.join(table)

    red1, j1 = build()
    assert j1.count() == 500  # cold: blocking, seeds hints
    red2, j2 = build()
    blk = j2.block_spec()  # warm: hinted -> speculative
    assert blk.settle is not None, "warm join should defer its fetch"
    assert blk.counts_host is None
    assert red2._last_attempts == 1
    pending = dctx.__dict__.get("_dense_pending")
    assert pending, "reduce + join entries should be pending"
    assert j2.count() == 500  # settles everything
    assert blk.settle is None and blk.counts_host is not None
    assert not dctx.__dict__.get("_dense_pending")
    assert sorted(j2.collect()) == sorted(j1.collect())


def test_failed_speculation_repairs_downstream_consumers(dctx):
    """Poisoning the REDUCE hint makes the join consume capacity-truncated
    data; settlement must detect the upstream overflow and rebuild both
    stages (in registration order) before any host read sees results."""
    import numpy as np

    def build():
        kv = dctx.dense_range(30_000).map(lambda x: (x % 3_000, x * 1.0))
        red = kv.reduce_by_key(op="add")
        table = dctx.dense_from_numpy(np.arange(3_000, dtype=np.int32),
                                      np.arange(3_000, dtype=np.float32))
        return red, red.join(table)

    red1, j1 = build()
    expected = sorted(j1.collect())  # cold run = oracle, seeds hints
    red2, j2 = build()
    # Poison the reduce's capacities so its speculative launch overflows.
    dctx._dense_capacity_hints[red2._hint_key()] = (128, 128)
    got = sorted(j2.collect())
    assert got == expected
    assert not dctx.__dict__.get("_dense_pending")
    # the poisoned hint was replaced by working capacities
    assert dctx._dense_capacity_hints[red2._hint_key()] != (128, 128)


def test_settlement_midway_error_requeues_failed_entries(dctx):
    """A later entry's validator raising mid-settlement must put entries
    ALREADY triaged as failed (an earlier overflowed speculation) back on
    the backlog too — the next read repairs them rather than silently
    serving capacity-truncated data (round-3 advisor finding)."""
    import numpy as np

    def build_a():
        kv = dctx.dense_range(20_000).map(lambda x: (x % 2_000, x * 1.0))
        return kv.reduce_by_key(op="add")

    def build_b():
        kv = dctx.dense_range(24_000).map(lambda x: (x % 500, x * 1.0))
        return kv.reduce_by_key(op="add")

    exp_a = dict(build_a().collect())  # cold oracles, seed hints
    exp_b = dict(build_b().collect())
    a2, b2 = build_a(), build_b()
    assert a2._hint_key() != b2._hint_key()
    # Poison A so its warm (speculative) launch overflows.
    dctx._dense_capacity_hints[a2._hint_key()] = (64, 64)
    a2.block_spec()
    b2.block_spec()
    pending = dctx.__dict__.get("_dense_pending")
    assert pending and [e["rdd"] for e in pending] == [a2, b2]
    # Give B a validator that dies mid-settlement (after A was triaged
    # into the failed list but before its repair ran).
    for e in pending:
        if e["rdd"] is b2:
            e["validate"] = lambda head: (_ for _ in ()).throw(
                RuntimeError("transient settlement failure"))
    with pytest.raises(RuntimeError, match="transient settlement"):
        a2.count()
    # Every uncommitted entry is back on the backlog — including A,
    # which had already been moved to the failed list.
    pend = dctx.__dict__.get("_dense_pending")
    assert any(e["rdd"] is a2 for e in pend)
    assert any(e["rdd"] is b2 for e in pend)
    # Clear the injected fault; the next read settles and repairs A.
    for e in pend:
        if e["rdd"] is b2:
            e["validate"] = None
    assert dict(a2.collect()) == exp_a
    assert dict(b2.collect()) == exp_b
    assert not dctx.__dict__.get("_dense_pending")


def test_wide_sum_overflow_detected_and_raises(dctx):
    """reduce_by_key(op='add') over wide int64 values whose exact total
    exceeds int64 must raise crisply (device flags the wrap, the
    host-exact fold confirms non-representability) — never silently
    wrap like numpy."""
    from vega_tpu.tpu.dense_rdd import DenseRDD

    keys = np.array([1, 1, 1, 2], dtype=np.int64)
    vals = np.array([2**62, 2**62, 2**62, 5], dtype=np.int64)
    r = dctx.dense_from_numpy(keys, vals)
    assert isinstance(r, DenseRDD)
    with pytest.raises(v.VegaError, match="int64 range"):
        r.reduce_by_key(op="add").collect()
    # the host tier keeps exact bignums for the same data
    host = dctx.parallelize(list(zip(keys.tolist(), vals.tolist())))
    exact = dict(host.reduce_by_key(lambda a, b: a + b).collect())
    assert exact == {1: 3 * 2**62, 2: 5}


def test_wide_sum_in_range_unflagged_and_exact(dctx):
    """Wide sums whose totals fit int64 stay dense and exact (clean
    flags prove mod-2^64 == exact), including near-boundary totals."""
    keys = np.array([7, 7, 8, 8], dtype=np.int64)
    vals = np.array([2**62, 2**62 - 1, -2**62, -2**62 + 1], dtype=np.int64)
    r = dctx.dense_from_numpy(keys, vals).reduce_by_key(op="add")
    assert dict(r.collect()) == {7: 2**63 - 1, 8: -2**63 + 1}
    assert r.hash_placed  # no fold happened


def test_host_exact_fold_rebuilds_schema_and_resets_placement(dctx):
    """_host_exact_fold: exact totals, schema-faithful wide re-encoding,
    narrow int columns wrap like the device, placement/order flags reset
    so downstream exchanges skip elision."""
    from vega_tpu.tpu import block as block_lib
    from vega_tpu.tpu.dense_rdd import _ReduceByKeyRDD

    k = np.array([2**40, 2**40, 3], dtype=np.int64)
    wide_v = np.array([2**62, -2**61, 2**35], dtype=np.int64)
    narrow_v = np.array([2**30, 2**30, 7], dtype=np.int64)  # sum wraps i32
    src = dctx.dense_from_columns(
        {"k": k, "w": wide_v, "m": narrow_v}, key="k")
    node = _ReduceByKeyRDD(src, op="add", func=None)
    blk = node._host_exact_fold()
    assert node._host_folded
    assert not node.hash_placed and not node.key_sorted
    got = blk.to_numpy()
    by_key = {kk: (w, m) for kk, w, m in
              zip(got["k"].tolist(), got["w"].tolist(), got["m"].tolist())}
    # wide column: exact int64 totals
    assert by_key[2**40][0] == 2**62 - 2**61
    assert by_key[3][0] == 2**35
    # narrow column wraps to int32 exactly like the device would:
    # 2^30 + 2^30 = 2^31 -> two's-complement -2^31
    assert by_key[2**40][1] == -2**31
    assert by_key[3][1] == 7
    # schema kept the wide pair encoding
    assert block_lib.lo_of("w") in blk.cols
    # downstream keyed exchange over the folded node: placement reset
    # means a REAL exchange (no elision over stale placement) and the
    # re-reduce of the already-reduced rows reproduces the same totals
    node._block = blk  # what the settle-repair path installs
    again = node.reduce_by_key(op="add")
    got2 = again.block().to_numpy()
    by_key2 = {kk: (w, m) for kk, w, m in
               zip(got2["k"].tolist(), got2["w"].tolist(),
                   got2["m"].tolist())}
    assert by_key2 == by_key
    assert not getattr(again, "_elided", True)


def test_keyless_int64_stays_dense(dctx):
    """Keyless bare int64 single columns get the wide
    (VALUE, VALUE.lo) encoding instead of degrading to the host tier.
    Named reductions fold on device; order ops sort the pair; closures
    and structure-changing ops fall back with exact decoded rows."""
    from vega_tpu.tpu.dense_rdd import DenseRDD

    data = [2**40, -2**35, 7, 2**62, -2**40, 0, 2**40]
    arr = np.array(data, dtype=np.int64)
    r = dctx.dense_from_numpy(arr)
    assert isinstance(r, DenseRDD)
    assert "v.lo" in r.columns

    # device folds, exact
    assert r.count() == len(data)
    assert r.sum() == sum(data)
    assert r.min() == min(data)
    assert r.max() == max(data)
    assert r.mean() == sum(data) / len(data)
    # collect/take decode transparently
    assert r.collect() == data
    assert sorted(r.take(3)) == sorted(data[:3])
    # device order ops over the wide pair
    assert r.take_ordered(3) == sorted(data)[:3]
    assert r.top(3) == sorted(data, reverse=True)[:3]
    # closures fall back to the host tier with decoded int64s
    assert r.map(lambda x: x % 97).count() == len(data)
    assert r.filter(lambda x: x > 0).count() == sum(1 for x in data if x > 0)
    assert r.reduce(lambda a, b: a + b) == sum(data)
    # host-fallback aggregations stay exact
    assert r.count_by_value()[2**40] == 2
    assert r.stats()["count"] == len(data)
    edges, hist = r.histogram([-2**63, 0, 2**63 - 1])
    assert sum(hist) == len(data)
    assert r.zip_with_index().collect() == [(x, i) for i, x in
                                            enumerate(data)]


def test_keyless_int64_sum_overflow_exact(dctx):
    """A keyless wide sum whose partials wrap int64 comes back as the
    EXACT Python bignum (actions have host-return semantics; the sticky
    device flag routes to a driver refold)."""
    arr = np.array([2**62, 2**62, 2**62], dtype=np.int64)
    r = dctx.dense_from_numpy(arr)
    assert r.sum() == 3 * 2**62  # > int64 max, exact bignum
    mixed = np.array([2**62, 2**62, -2**62, 5], dtype=np.int64)
    assert dctx.dense_from_numpy(mixed).sum() == 2**62 + 5


def test_values_dense_keeps_wide_pair_on_device(dctx):
    """values_dense() over a wide-valued pair block yields a keyless wide
    DenseRDD (no host detour) whose folds run on device."""
    from vega_tpu.tpu.dense_rdd import DenseRDD

    r = dctx.dense_from_numpy(np.array([1, 2, 1], dtype=np.int32),
                              np.array([2**40, 5, 2**41], dtype=np.int64))
    vals = r.values_dense()
    assert isinstance(vals, DenseRDD)
    assert vals.sum() == 2**40 + 2**41 + 5
    assert vals.max() == 2**41


@pytest.mark.parametrize("area", ["named_op", "traced_combiner",
                                  "wide_int64_values", "downstream_join"])
def test_rbk_plan_matches_python(dctx, area):
    """The reduce exchange's one plan (fused (bucket, key) sort ->
    presorted combine -> pregrouped exchange -> merge) against a Python
    fold, over the four areas it serves: named ops, traced combiners,
    wide int64 values, and a downstream join over its output."""
    if area == "traced_combiner":
        got = dict(dctx.dense_range(10_000)
                   .map(lambda x: (x % 53, x * 1.0))
                   .reduce_by_key(lambda a, b: a + b).collect())
        assert got == {k: sum(float(x) for x in range(k, 10_000, 53))
                       for k in range(53)}
        return
    if area == "wide_int64_values":  # the sovf column rides the plan too
        wide = dctx.dense_from_numpy(
            np.array([1, 1, 2], dtype=np.int64),
            np.array([2**40, 2**41, 7], dtype=np.int64))
        assert dict(wide.reduce_by_key(op="add").collect()) == {
            1: 2**40 + 2**41, 2: 7}
        return
    exp = {k: sum(range(k, 50_000, 997)) for k in range(997)}
    r = (dctx.dense_range(50_000).map(lambda x: (x % 997, x))
         .reduce_by_key(op="add"))
    if area == "named_op":
        assert dict(r.collect()) == exp
        assert r.hash_placed and r.key_sorted
        return
    # the plan's hash-placed output lets the join elide its left exchange
    table = dctx.dense_from_numpy(np.arange(997, dtype=np.int32),
                                  np.arange(997, dtype=np.int32))
    j = r.join(table)
    assert dict(j.collect()) == {k: (exp[k], k) for k in range(997)}
    assert j._elided == (True, False)


def test_exchange_grouping_with_pallas_partition_ranks(dctx, monkeypatch):
    """An exchange that groups its rows by bucket (group_by_key: no
    pre-combine, so _group_by_bucket runs) computes identical results
    when the counting partition's ranks come from the Pallas kernel
    (interpret mode here; on TPU the dispatcher selects it at
    lowering)."""
    from vega_tpu.tpu import dense_rdd as dr
    from vega_tpu.tpu import pallas_kernels

    calls = []

    def pallas_ranks(bucket, n_bins, starts, prefer_low_memory=False):
        calls.append(n_bins)
        return pallas_kernels.partition_pos_pallas(bucket, n_bins, starts,
                                                   True)

    monkeypatch.setattr(dr, "_PROGRAM_CACHE", {})  # force re-trace
    monkeypatch.setattr(pallas_kernels, "partition_pos", pallas_ranks)
    g = (dctx.dense_range(30_000).map(lambda x: (x % 433, x))
         .group_by_key())
    got = {k: sorted(vs) for k, vs in g.collect()}
    assert calls, "the grouping never asked for partition ranks"
    assert got == {k: list(range(k, 30_000, 433)) for k in range(433)}


def test_sort_by_key_descending_int_min(dctx):
    """Regression: the descending range partitioner and per-shard sort
    must not negate keys — negation wraps INT32_MIN onto itself, landing
    the most negative key in the first (largest-keys) bucket."""
    r = dctx.dense_from_numpy(
        np.array([5, -2**31, 7, 0, -3], dtype=np.int32),
        np.array([1, 2, 3, 4, 5], dtype=np.int32))
    got = [k for k, _ in r.sort_by_key(ascending=False).collect()]
    assert got == [7, 5, 0, -3, -2**31]
    got_asc = [k for k, _ in r.sort_by_key().collect()]
    assert got_asc == [-2**31, -3, 0, 5, 7]


@pytest.mark.parametrize("action", ["take_ordered", "top"])
@pytest.mark.parametrize("kind", ["scalar", "pair", "float", "wide-pair"])
def test_take_ordered_top_match_sorted(dctx, kind, action):
    """take_ordered / top (the per-shard row sort + the driver's merge)
    against sorted() of the same rows, over value-only, pair, float and
    wide-int64 pair blocks."""
    rng = np.random.RandomState(12)
    vals32 = rng.randint(-10**6, 10**6, 5_000).astype(np.int32)
    keys32 = rng.randint(-500, 500, 5_000).astype(np.int32)
    flo = (rng.randn(5_000) * 100).astype(np.float32)
    wide = rng.randint(-2**50, 2**50, 3_000).astype(np.int64)
    wkeys = rng.randint(0, 100, 3_000).astype(np.int64)
    cols = {"scalar": (vals32,), "pair": (keys32, vals32),
            "float": (flo,), "wide-pair": (wkeys, wide)}[kind]
    rows = (cols[0].tolist() if len(cols) == 1
            else list(zip(*(c.tolist() for c in cols))))
    r = dctx.dense_from_numpy(*cols)
    if action == "take_ordered":
        assert r.take_ordered(9) == sorted(rows)[:9]
    else:
        assert r.top(9) == sorted(rows, reverse=True)[:9]


def test_multiproc_memo_resets_on_multihost_init(monkeypatch):
    """Regression: init_multihost must reset the
    single-vs-multi-process eviction-policy memo next to
    set_default_mesh(None) — a stop()+new-multihost-Context process would
    otherwise keep running the single-process LRU/weakref policy on a
    multi-process mesh."""
    from vega_tpu.tpu import dense_rdd as dr, mesh as mesh_lib

    # Pretend this process already resolved the policy single-process.
    monkeypatch.setattr(dr, "_lifetime_multiproc_memo", False)
    # jax.distributed cannot actually rendezvous here; stub it and
    # restore every module-global init_multihost mutates.
    monkeypatch.setattr(mesh_lib.jax.distributed, "initialize",
                        lambda **kw: None)
    monkeypatch.setattr(mesh_lib, "_multihost_settings", None)
    monkeypatch.setattr(mesh_lib, "_multihost_heartbeat_s", None)
    saved_mesh = mesh_lib._default_mesh
    try:
        mesh_lib.init_multihost(coordinator="127.0.0.1:0",
                                num_processes=1, process_id=0)
        assert dr._lifetime_multiproc_memo is None, \
            "init_multihost must invalidate the eviction-policy memo"
    finally:
        mesh_lib.set_default_mesh(saved_mesh)


def test_dense_spilled_block_parity(dctx):
    """Tiered-store acceptance: a persisted (MEMORY_AND_DISK) dense node
    whose block was demoted to disk under HBM pressure promotes back
    placement-identically — no lineage recompute (asserted by poisoning
    _materialize), results bit-identical to the host oracle, and the
    hash_placed claim of the reduce output stays true for downstream
    elision."""
    from vega_tpu.env import Env
    from vega_tpu.store import StorageLevel
    from vega_tpu.tpu import dense_rdd as dr

    n, k = 20_000, 100
    r = (dctx.dense_range(n).map(lambda x: (x % k, x))
         .reduce_by_key(op="add").persist(StorageLevel.MEMORY_AND_DISK))
    before = dict(r.collect())
    assert r._block is not None

    # force a demotion sweep at zero budget
    old = Env.get().conf.dense_hbm_budget
    Env.get().conf.dense_hbm_budget = 0
    try:
        dr._lifetime_evict(dctx)
    finally:
        Env.get().conf.dense_hbm_budget = old
    assert r._block is None, "budget sweep should evict the block"
    status = Env.get().cache.status()
    assert status["spilled_bytes"] > 0

    # recompute is forbidden: the next access must PROMOTE from disk
    r._materialize = lambda: (_ for _ in ()).throw(
        AssertionError("promoted access must not recompute lineage"))
    after = dict(r.collect())
    assert r._block is not None
    assert Env.get().cache.status()["promote_count"] > 0

    # host-tier parity oracle
    exp = host_expected_reduce_by_key(
        [(i % k, i) for i in range(n)], lambda a, b: a + b)
    assert before == exp
    assert after == exp

    # placement survives the round trip: a downstream keyed op over the
    # promoted block still elides its exchange (hash_placed invariant)
    assert r.hash_placed
    del r.__dict__["_materialize"]
    r2 = r.reduce_by_key(op="add")
    assert dict(r2.collect()) == exp

    # unpersist drops the disk snapshot too
    r.unpersist()
    assert not Env.get().cache.contains_raw(dr._dense_spill_key(r))


def test_dense_unspilled_eviction_still_recomputes(dctx):
    """Without a disk-tier storage level, eviction keeps the original
    recompute-over-spill behavior (and writes nothing to disk)."""
    from vega_tpu.env import Env
    from vega_tpu.store import StorageLevel
    from vega_tpu.tpu import dense_rdd as dr

    r = dctx.dense_range(10_000).map(lambda x: x * 3)
    total = r.sum()
    old = Env.get().conf.dense_hbm_budget
    Env.get().conf.dense_hbm_budget = 0
    try:
        dr._lifetime_evict(dctx)
    finally:
        Env.get().conf.dense_hbm_budget = old
    assert r._block is None
    assert not Env.get().cache.contains_raw(dr._dense_spill_key(r))
    assert r.sum() == total  # recompute-from-lineage transparency


# ---------------------------------------------------------------------------
# collective-aware exchange planner (PR 13)
# ---------------------------------------------------------------------------


def _budget(dctx, value):
    """Set dense_hbm_budget for the test body; returns the old value."""
    from vega_tpu.env import Env

    conf = Env.get().conf
    old = conf.dense_hbm_budget
    conf.dense_hbm_budget = value
    return conf, old


def test_exchange_planner_program_parity(dctx):
    """Acceptance: dense_exchange=auto resolves per launch through the
    cost model — under a deliberately small dense_hbm_budget the SAME
    named-reduce/group/join/sort pipelines run the staged (K>1 rounds)
    program fully on device, with estimated peak <= budget, results
    bit-identical to the one-shot leg, and plan records readable on the
    node and the module counters."""
    from vega_tpu.env import Env
    from vega_tpu.tpu import exchange_plan
    from vega_tpu.tpu.dense_rdd import DenseRDD

    conf = Env.get().conf
    assert conf.dense_exchange == "auto"  # the shipped default
    rng = np.random.RandomState(3)
    keys = rng.randint(0, 997, size=200_000).astype(np.int32)
    vals = rng.randint(0, 1 << 20, size=200_000).astype(np.int32)
    tk = np.arange(997, dtype=np.int32)
    tv = (tk * 7).astype(np.int32)
    # Unique sort keys: duplicate-key ties keep exchange ARRIVAL order,
    # which legitimately differs between collective programs (true of
    # ring vs all_to_all since PR 2) — uniqueness makes the sort leg's
    # bit-identical claim well-defined.
    skeys = rng.permutation(200_000).astype(np.int32)

    def pipelines():
        src = dctx.dense_from_numpy(keys, vals)
        nodes = {
            "rbk": src.reduce_by_key(op="add"),
            "gbk": src.group_by_key(),
            "join": src.join(dctx.dense_from_numpy(tk, tv)),
            "sort": dctx.dense_from_numpy(skeys, vals).sort_by_key(),
        }
        out = {
            "rbk": dict(nodes["rbk"].collect()),
            "gbk": {k: sorted(vs) for k, vs in nodes["gbk"].collect()},
            "join": sorted(nodes["join"].collect()),
            "sort": nodes["sort"].collect(),
        }
        return nodes, out

    # Leg A: forced one-shot all_to_all at the default budget.
    old_mode = conf.dense_exchange
    conf.dense_exchange = "all_to_all"
    try:
        nodes_a, leg_a = pipelines()
    finally:
        conf.dense_exchange = old_mode
    for node in nodes_a.values():
        assert node._exchange_plan.program == "all_to_all"

    # Leg B: auto under a budget the one-shot footprint busts (the
    # 200k-row operand block is 32768 rows/shard x 8 B; the one-shot's
    # [n, slot] buffers put its estimate ~1.31 MB/shard, and the join's
    # JOINT two-sided launch ~1.65 MB). 1.28 MB sits in the window where
    # every pipeline stages at K>1 rounds: below the one-shot estimate
    # and above the join's smallest multi-round staged estimate (g=2,
    # ~1.25 MB with the 3x staged slot coefficient).
    conf2, old = _budget(dctx, 1_280_000)
    exchange_plan.reset_plan_counters()
    try:
        nodes_b, leg_b = pipelines()
    finally:
        conf2.dense_hbm_budget = old
    assert leg_b == leg_a  # bit-identical across programs
    counters = exchange_plan.plan_counters()
    assert counters.get("staged", 0) >= 4, counters
    for name, node in nodes_b.items():
        assert isinstance(node, DenseRDD)  # completed on device
        plan = node._exchange_plan
        assert plan.program == "staged", (name, plan)
        assert plan.rounds > 1, (name, plan)
        assert plan.fits and plan.est_peak_bytes <= 1_280_000, (name, plan)

    # Host-tier truth for one pipeline (the standing parity oracle).
    host = host_expected_reduce_by_key(zip(keys.tolist(), vals.tolist()),
                                       lambda a, b: (a + b) & 0xFFFFFFFF)
    host = {k: ((s + 2**31) % 2**32) - 2**31 for k, s in host.items()}
    assert leg_b["rbk"] == host


def test_exchange_planner_ring_when_no_group_fits(dctx):
    """A budget below even the smallest staged group's estimate resolves
    to ring — the single-bounded-buffer extreme — and still completes
    with identical results (fits may be False: the planner bounds, it
    never refuses)."""
    from vega_tpu.tpu import exchange_plan

    rng = np.random.RandomState(4)
    keys = rng.randint(0, 500, size=120_000).astype(np.int32)
    vals = rng.randint(0, 1000, size=120_000).astype(np.int32)

    src = dctx.dense_from_numpy(keys, vals)
    expected = {k: sorted(vs) for k, vs in src.group_by_key().collect()}

    conf, old = _budget(dctx, 500_000)
    exchange_plan.reset_plan_counters()
    try:
        node = dctx.dense_from_numpy(keys, vals).group_by_key()
        got = {k: sorted(vs) for k, vs in node.collect()}
    finally:
        conf.dense_hbm_budget = old
    assert got == expected
    assert node._exchange_plan.program == "ring"
    assert exchange_plan.plan_counters().get("ring", 0) >= 1


def test_exchange_planner_overflow_retry_keeps_contract(dctx):
    """The staged plan keeps the grown-capacity retry contract: a
    poisoned (too-small) capacity hint overflows on round 0 and the
    retry — re-planned at the exact histogram capacities, crossing
    PROGRAMS mid-loop when the bigger buffers bust the budget — lands
    the correct result."""
    rng = np.random.RandomState(5)
    keys = rng.randint(0, 700, size=200_000).astype(np.int32)
    vals = rng.randint(0, 1000, size=200_000).astype(np.int32)
    src = dctx.dense_from_numpy(keys, vals)
    expected = {k: sorted(vs) for k, vs in src.group_by_key().collect()}

    node = dctx.dense_from_numpy(keys, vals).group_by_key()
    hint_store = dctx.__dict__.setdefault("_dense_capacity_hints", {})
    hint_store[node._hint_key()] = (64, 256)  # far too small: must flag
    conf, old = _budget(dctx, 1_100_000)
    dctx.__dict__["_dense_no_defer"] = True  # inline blocking retry loop
    try:
        got = {k: sorted(vs) for k, vs in node.collect()}
    finally:
        dctx.__dict__["_dense_no_defer"] = False
        conf.dense_hbm_budget = old
    assert got == expected
    assert node._last_attempts >= 2  # round 0 overflowed, retry landed
    # The retry's histogram-sized buffers bust the 1.1 MB budget on the
    # one-shot program, so the landing launch ran staged.
    assert node._exchange_plan.program == "staged"
    assert node._exchange_plan.rounds > 1


def test_exchange_planner_events_aggregated(dctx):
    """DenseExchangePlanned rides the bus into MetricsListener: program
    counts, staged round totals and the peak estimate are queryable from
    the driver (the bench.py `exchange_plans` detail)."""
    rng = np.random.RandomState(6)
    keys = rng.randint(0, 300, size=150_000).astype(np.int32)
    vals = np.ones(150_000, dtype=np.int32)
    conf, old = _budget(dctx, 1_100_000)
    try:
        node = dctx.dense_from_numpy(keys, vals).group_by_key()
        node.block()
    finally:
        conf.dense_hbm_budget = old
    xp = dctx.metrics_summary()["exchange_plans"]
    assert xp["staged"] >= 1
    assert xp["staged_rounds"] >= 2
    assert 0 < xp["max_est_peak_bytes"] <= 1_100_000
    assert xp["over_budget"] == 0


def test_gf256_accumulate_host_device_parity():
    """Coded shuffle's decode hot loop: the device kernel
    (kernels.gf256_accumulate) must be bit-identical to the numpy twin
    (coding._accumulate_np) — a divergence would decode shuffled buckets
    into silently-wrong bytes. Exercises XOR (all-ones coefficients),
    RS Cauchy coefficients, zero coefficients (masked members), and the
    explicit numpy-fallback path of coding.accumulate."""
    from vega_tpu.shuffle import coding
    from vega_tpu.tpu.kernels import gf256_accumulate

    rng = np.random.RandomState(11)
    for n, width in ((1, 17), (4, 256), (7, 1023)):
        blocks = rng.randint(0, 256, size=(n, width)).astype(np.uint8)
        for coeffs in (
                np.ones(n, dtype=np.uint8),  # xor scheme
                np.array([coding.coeff("rs", 0, i) for i in range(n)],
                         dtype=np.uint8),
                np.array([(0 if i % 2 else 143) for i in range(n)],
                         dtype=np.uint8),  # masked members
        ):
            host = coding._accumulate_np(blocks, coeffs)
            dev = np.asarray(gf256_accumulate(blocks, coeffs),
                             dtype=np.uint8)
            assert np.array_equal(host, dev)
            # The public entry agrees on both routes (device preferred
            # vs forced numpy fallback).
            assert np.array_equal(
                coding.accumulate(blocks, coeffs, prefer_device=True),
                host)
            assert np.array_equal(
                coding.accumulate(blocks, coeffs, prefer_device=False),
                host)


# ---------------------------------------------------------------- PR 20:
# device string columns — dictionary-encoded int32 codes + sidecar, with
# the host tier as the parity oracle for every op the encoding unlocks.


def _string_pairs(seed=0, n=600, nkeys=29):
    rng = np.random.RandomState(seed)
    keys = np.array([f"w{i:02d}" for i in rng.randint(0, nkeys, size=n)])
    vals = rng.randint(-100, 100, size=n).astype(np.int32)
    return keys, vals


def _lineage_nodes(rdd):
    """Every node reachable through parent/left/right links."""
    seen, todo = [], [rdd]
    while todo:
        node = todo.pop()
        if any(node is s for s in seen):
            continue
        seen.append(node)
        for attr in ("parent", "left", "right"):
            child = getattr(node, attr, None)
            if child is not None:
                todo.append(child)
    return seen


def test_dense_string_reduce_group_count_parity(dctx):
    from vega_tpu.tpu.dense_rdd import DenseRDD

    keys, vals = _string_pairs()
    dev = dctx.dense_from_numpy(keys, vals)
    host = dctx.parallelize(list(zip(keys.tolist(), vals.tolist())), 4)

    red = dev.reduce_by_key(lambda a, b: a + b)
    assert isinstance(red, DenseRDD)  # string keys must not fall back
    assert dict(red.collect()) == dict(
        host.reduce_by_key(lambda a, b: a + b, 4).collect())

    # Named min/max run on RANK codes (sorted dictionary), so the device
    # winner-by-code is the winner-by-string.
    for op, fn in (("min", min), ("max", max)):
        assert dict(dev.reduce_by_key(op=op).collect()) == dict(
            host.reduce_by_key(fn, 4).collect())

    dg = {k: sorted(vs) for k, vs in dev.group_by_key().collect()}
    hg = {k: sorted(vs) for k, vs in host.group_by_key(4).collect()}
    assert dg == hg

    assert dev.count_by_key() == host.count_by_key()


def test_dense_string_sort_distinct_topk_parity(dctx):
    from vega_tpu.tpu.dense_rdd import DenseRDD

    keys, vals = _string_pairs(seed=3)
    dev = dctx.dense_from_numpy(keys, vals)
    host = dctx.parallelize(list(zip(keys.tolist(), vals.tolist())), 4)

    srt = dev.sort_by_key()
    assert isinstance(srt, DenseRDD)
    assert [k for k, _ in srt.collect()] == sorted(keys.tolist())
    desc = dev.sort_by_key(ascending=False).collect()
    assert [k for k, _ in desc] == sorted(keys.tolist(), reverse=True)

    assert sorted(dev.distinct().collect()) == sorted(host.distinct().collect())

    # Single string column: distinct + count_by_value on codes.
    col = dctx.dense_from_numpy(keys)
    assert sorted(col.distinct().collect()) == sorted(set(keys.tolist()))
    assert col.count_by_value() == \
        dctx.parallelize(keys.tolist(), 4).count_by_value()

    assert dev.take_ordered(7) == sorted(zip(keys.tolist(), vals.tolist()))[:7]
    assert dev.top(5) == sorted(zip(keys.tolist(), vals.tolist()),
                                reverse=True)[:5]


def test_dense_string_join_cross_dict_parity(dctx):
    """Two sides built from DIFFERENT key sets carry different
    dictionaries: the join must unify them (host merge + device remap)
    and match the host result exactly, each side's table staged at the
    default dense_dict_capacity."""
    from vega_tpu.tpu.dense_rdd import _DictUnifyRDD, DenseRDD

    rng = np.random.RandomState(11)
    lk = np.array([f"k{i:02d}" for i in rng.randint(0, 40, size=300)])
    lv = rng.randint(0, 1000, size=300).astype(np.int32)
    rk = np.array([f"k{i:02d}" for i in range(20, 60)])
    rv = np.arange(40).astype(np.int32)

    j = dctx.dense_from_numpy(lk, lv).join(dctx.dense_from_numpy(rk, rv))
    assert isinstance(j, DenseRDD)
    unify = [n for n in _lineage_nodes(j) if isinstance(n, _DictUnifyRDD)]
    assert unify, "cross-dictionary join never planned a unification"
    dev = sorted(j.collect())
    host = sorted(
        dctx.parallelize(list(zip(lk.tolist(), lv.tolist())), 4)
        .join(dctx.parallelize(list(zip(rk.tolist(), rv.tolist())), 2))
        .collect())
    assert dev == host
    assert all(n._table_capacity == 65536 for n in unify)


def test_dense_string_dict_overflow_grows_capacity():
    """dense_dict_capacity=2 (the 128-entry floor) stages a table far
    smaller than the 300-entry merged dictionary: the capacity grows to the
    power of two that holds the whole table, at once (no overflow, no
    second launch), and the join is the exact host-tier join; a code
    outside its dictionary is still caught on the device."""
    from vega_tpu.errors import VegaError
    from vega_tpu.tpu.dense_rdd import _DictUnifyRDD

    ctx = v.Context("local", num_workers=2, dense_dict_capacity=2)
    try:
        lk = np.array([f"k{i:03d}" for i in range(200)])
        lv = np.arange(200).astype(np.int32)
        rk = np.array([f"k{i:03d}" for i in range(100, 300)])
        rv = (np.arange(200) * 7).astype(np.int32)
        j = ctx.dense_from_numpy(lk, lv).join(ctx.dense_from_numpy(rk, rv))
        dev = sorted(j.collect())
        host = sorted(
            ctx.parallelize(list(zip(lk.tolist(), lv.tolist())), 4)
            .join(ctx.parallelize(list(zip(rk.tolist(), rv.tolist())), 2))
            .collect())
        assert dev == host
        unify = [n for n in _lineage_nodes(j)
                 if isinstance(n, _DictUnifyRDD)]
        assert unify and all(n._table_capacity == 256 for n in unify)
        # the device's check: a side whose dictionary is cut below its
        # codes has codes outside it
        side = unify[0]
        side.unpersist()
        maps = side._unif.tables()[1 + side._side]
        maps["k"] = maps["k"][:10]
        with pytest.raises(VegaError, match="outside its dictionary"):
            side.block()
    finally:
        ctx.stop()


def test_rdd_dense_lifts_scalars_pairs_and_degrades(dctx):
    """RDD.dense(): int64 scalars take the (name, name.lo) wide encoding
    instead of degrading; string pairs dictionary-encode; mixed-object
    rows stay on the host tier silently; DenseRDD.dense() is identity."""
    from vega_tpu.tpu.dense_rdd import DenseRDD

    big = [2**40 + 3, -(2**35), 17, 2**33]
    d = dctx.parallelize(big, 2).dense()
    assert isinstance(d, DenseRDD)
    assert sorted(d.collect()) == sorted(big)
    assert d.sum() == sum(big)
    assert d.max() == max(big)

    p = dctx.parallelize([("b", 2), ("a", 1), ("b", 3)], 2).dense()
    assert isinstance(p, DenseRDD)
    assert sorted(p.reduce_by_key(lambda a, b: a + b).collect()) == \
        [("a", 1), ("b", 5)]
    assert p.dense() is p

    mixed = dctx.parallelize([1, "x", None], 2).dense()
    assert not isinstance(mixed, DenseRDD)
    assert sorted(mixed.collect(), key=repr) == ["x", 1, None]
