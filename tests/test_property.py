"""Randomized dense-vs-host parity: the CPU/TPU 'identical results' oracle
(BASELINE.md) exercised over randomized key distributions, sizes, and ops —
catches capacity-estimation and masking edge cases deterministic tests miss.
Seeds are fixed for reproducibility."""

import itertools

import numpy as np
import pytest

@pytest.mark.parametrize("seed,op", list(itertools.product(
    [0, 1, 2], ["add", "min", "max"]
)))
def test_random_reduce_by_key_parity(ctx, seed, op):
    rng = np.random.RandomState(seed)
    n = int(rng.randint(1, 30_000))
    n_keys = int(rng.randint(1, max(2, n)))
    keys = rng.randint(0, n_keys, size=n).astype(np.int32)
    vals = rng.randint(-1000, 1000, size=n).astype(np.int32)

    collected = ctx.dense_from_numpy(keys, vals).reduce_by_key(op=op).collect()
    py_op = {"add": lambda a, b: a + b, "min": min, "max": max}[op]
    host = {}
    for k, x in zip(keys.tolist(), vals.tolist()):
        host[k] = py_op(host[k], x) if k in host else x
    # No duplicate keys may survive the reduce (dict() would mask them).
    assert len(collected) == len(host)
    assert dict(collected) == host


@pytest.mark.parametrize("seed", [3, 4])
def test_random_join_parity(ctx, seed):
    rng = np.random.RandomState(seed)
    n_left = int(rng.randint(1, 10_000))
    n_right = int(rng.randint(1, 500))
    rkeys = rng.permutation(100_000)[:n_right].astype(np.int32)  # unique
    lkeys = rkeys[rng.randint(0, n_right, size=n_left)]
    # mix in some unmatched left keys
    miss = rng.randint(200_000, 300_000, size=max(1, n_left // 10)).astype(np.int32)
    lkeys = np.concatenate([lkeys, miss])
    lvals = rng.randint(0, 10**6, size=len(lkeys)).astype(np.int32)
    rvals = rng.randint(0, 10**6, size=n_right).astype(np.int32)

    dev = sorted(
        ctx.dense_from_numpy(lkeys, lvals)
        .join(ctx.dense_from_numpy(rkeys, rvals)).collect()
    )
    rmap = dict(zip(rkeys.tolist(), rvals.tolist()))
    host = sorted(
        (int(k), (int(x), rmap[int(k)]))
        for k, x in zip(lkeys, lvals) if int(k) in rmap
    )
    assert dev == host


@pytest.mark.parametrize("seed", [5, 6])
def test_random_sort_parity(ctx, seed):
    rng = np.random.RandomState(seed)
    n = int(rng.randint(2, 20_000))
    keys = rng.randint(-10**6, 10**6, size=n).astype(np.int32)
    vals = np.arange(n, dtype=np.int32)
    result = ctx.dense_from_numpy(keys, vals).sort_by_key().collect()
    assert [k for k, _ in result] == sorted(keys.tolist())


def test_random_skewed_distribution(ctx):
    """Zipf-ish skew: capacity estimation must survive heavy imbalance."""
    rng = np.random.RandomState(9)
    keys = (rng.zipf(1.5, size=20_000) % 1000).astype(np.int32)
    vals = np.ones(20_000, dtype=np.int32)
    collected = ctx.dense_from_numpy(keys, vals).reduce_by_key(op="add").collect()
    host = {}
    for k in keys.tolist():
        host[k] = host.get(k, 0) + 1
    assert len(collected) == len(host)
    assert dict(collected) == host


@pytest.mark.parametrize("seed", [10, 11, 12])
def test_random_dup_join_parity(ctx, seed):
    """Dup x dup joins over random key multisets: device == brute force."""
    from collections import defaultdict

    rng = np.random.RandomState(seed)
    n_left = int(rng.randint(1, 4_000))
    n_right = int(rng.randint(1, 800))
    key_space = int(rng.randint(1, 300))
    lk = rng.randint(0, key_space, n_left).astype(np.int32)
    rk = rng.randint(0, key_space, n_right).astype(np.int32)
    lv = rng.randint(0, 10**6, n_left).astype(np.int32)
    rv = rng.randint(0, 10**6, n_right).astype(np.int32)

    dev = sorted(ctx.dense_from_numpy(lk, lv)
                 .join(ctx.dense_from_numpy(rk, rv)).collect())
    rmap = defaultdict(list)
    for k, x in zip(rk.tolist(), rv.tolist()):
        rmap[k].append(x)
    brute = sorted((k, (a, b)) for k, a in zip(lk.tolist(), lv.tolist())
                   for b in rmap.get(k, []))
    assert dev == brute


@pytest.mark.parametrize("seed", [13, 14])
def test_random_streamed_reduce_parity(ctx, seed):
    """Streamed chunked reduce == resident reduce on random int data."""
    rng = np.random.RandomState(seed)
    n = int(rng.randint(5_000, 120_000))
    chunk = int(rng.randint(1_000, max(2_000, n // 3)))
    n_keys = int(rng.randint(1, 2_000))
    s = (ctx.dense_range(n, chunk_rows=chunk)
         .map(lambda x: (x % n_keys, x)).reduce_by_key(op="add")).collect()
    r = (ctx.dense_range(n)
         .map(lambda x: (x % n_keys, x)).reduce_by_key(op="add")).collect()
    # No duplicate keys may survive either reduce (dict() would mask them).
    assert len(s) == len(r) == min(n, n_keys)
    assert dict(s) == dict(r)


@pytest.mark.parametrize("seed", [15, 16])
def test_random_flat_map_ragged_parity(ctx, seed):
    """Random per-row arities: device expansion == python expansion."""
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    n = int(rng.randint(100, 20_000))
    mod = int(rng.randint(2, 7))
    cap = mod - 1  # max arity == capacity: exercises the full-slot boundary

    def emit(x):
        return jnp.full((cap,), x * 3), x % mod

    got = sorted(ctx.dense_range(n).flat_map_ragged(emit, cap).collect())
    exp = sorted(x * 3 for x in range(n) for _ in range(x % mod))
    assert got == exp


@pytest.mark.parametrize("seed", [17, 18])
def test_random_elided_chain_parity(ctx, seed):
    """Random chains over hash-placed data (elided shuffles) == host."""
    rng = np.random.RandomState(seed)
    n = int(rng.randint(2_000, 50_000))
    n_keys = int(rng.randint(1, 500))
    reduced = (ctx.dense_range(n).map(lambda x: (x % n_keys, x))
               .reduce_by_key(op="add"))
    dev_rows = (reduced.map_values(lambda s: s % 10_007)
                .reduce_by_key(op="max").collect())
    assert len(dev_rows) == min(n, n_keys)  # no duplicate keys survive
    dev = dict(dev_rows)
    host = {}
    for x in range(n):
        host[x % n_keys] = host.get(x % n_keys, 0) + x
    host = {k: s % 10_007 for k, s in host.items()}
    assert dev == host


@pytest.mark.parametrize("seed", [19, 20])
def test_random_set_ops_parity(ctx, seed):
    """Device intersection/subtract == host tier on random multisets."""
    rng = np.random.RandomState(seed)
    a = rng.randint(0, 400, int(rng.randint(10, 5_000))).astype(np.int32)
    b = rng.randint(200, 600, int(rng.randint(10, 2_000))).astype(np.int32)
    da, db = ctx.dense_from_numpy(a), ctx.dense_from_numpy(b)
    ha = ctx.parallelize(a.tolist(), 4)
    hb = ctx.parallelize(b.tolist(), 4)
    assert sorted(da.intersection(db).collect()) == \
        sorted(ha.intersection(hb).collect())
    assert sorted(da.subtract(db).collect()) == \
        sorted(ha.subtract(hb).collect())


@pytest.mark.parametrize("seed", [21])
def test_random_cartesian_parity(ctx, seed):
    rng = np.random.RandomState(seed)
    a = rng.randint(0, 1000, 400).astype(np.int32)
    b = rng.randint(0, 1000, 9).astype(np.int32)
    dev = sorted(ctx.dense_from_numpy(a).cartesian(
        ctx.dense_from_numpy(b)).collect())
    host = sorted(ctx.parallelize(a.tolist(), 4).cartesian(
        ctx.parallelize(b.tolist(), 2)).collect())
    assert dev == host


@pytest.mark.parametrize("seed", [30, 31, 32])
def test_random_reduce_sort_join_stack_parity(ctx, seed):
    """One block through the whole keyed stack — reduce, sort, and a
    reduce feeding a join — matches the host tier on random keyed data
    (negative keys included)."""
    rng = np.random.RandomState(seed)
    n = int(rng.randint(2_000, 20_000))
    keys = rng.randint(-500, 500, n).astype(np.int32)
    vals = rng.randint(-10**6, 10**6, n).astype(np.int32)
    dev = ctx.dense_from_numpy(keys, vals)
    host = ctx.parallelize(list(zip(keys.tolist(), vals.tolist())), 4)

    red = dev.reduce_by_key(op="add").collect()
    host_red = host.reduce_by_key(lambda a, b: a + b).collect()
    # length asserted too: dict() would mask a key surviving in two
    # shards with partial sums (the plan's most plausible failure)
    assert len(red) == len(host_red)
    assert dict(red) == dict(host_red)
    srt = dev.sort_by_key().collect()
    assert sorted(srt) == sorted(host.collect())
    assert [k for k, _ in srt] == sorted(keys.tolist())

    table_k = np.unique(keys)[:200].astype(np.int32)
    table_v = (table_k * 3).astype(np.int32)
    dj = (dev.reduce_by_key(op="min")
          .join(ctx.dense_from_numpy(table_k, table_v)).collect())
    hj = (host.reduce_by_key(lambda a, b: min(a, b))
          .join(ctx.parallelize(
              list(zip(table_k.tolist(), table_v.tolist())), 3))
          .collect())
    assert len(dj) == len(hj)
    assert dict(dj) == dict(hj)
