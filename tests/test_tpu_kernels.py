"""Device-kernel unit tests: pallas kernels (interpret mode on CPU), ring
vs all_to_all exchange parity, shard-local kernel correctness."""

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import vega_tpu as v
from vega_tpu.tpu import kernels
from vega_tpu.tpu.pallas_kernels import hash_bucket_pallas


def test_pallas_hash_matches_xla():
    """Pallas bucketing must be bit-identical to kernels.hash32 % n."""
    keys = jnp.asarray(np.random.RandomState(0).randint(-2**31, 2**31 - 1,
                                                        size=5000, dtype=np.int32))
    for n_buckets in (2, 8, 97):
        expected = (kernels.hash32(keys) % jnp.uint32(n_buckets)).astype(jnp.int32)
        got = hash_bucket_pallas(keys, n_buckets, interpret=True)
        assert jnp.array_equal(got, expected)


def test_pallas_hash_ragged_sizes():
    for n in (1, 127, 1024, 1025):
        keys = jnp.arange(n, dtype=jnp.int32)
        expected = (kernels.hash32(keys) % jnp.uint32(4)).astype(jnp.int32)
        got = hash_bucket_pallas(keys, 4, interpret=True)
        assert jnp.array_equal(got, expected)


@pytest.fixture()
def ring_ctx():
    context = v.Context("local", num_workers=2, dense_exchange="ring")
    yield context
    context.stop()


def test_ring_exchange_parity(ring_ctx):
    """Ring ppermute exchange produces the same results as all_to_all."""
    n, k = 20_000, 101
    got = dict(
        ring_ctx.dense_range(n).map(lambda x: (x % k, x))
        .reduce_by_key(op="add").collect()
    )
    expected = {}
    for x in range(n):
        expected[x % k] = expected.get(x % k, 0) + x
    assert got == expected


def test_ring_sort_and_join(ring_ctx):
    keys = np.random.RandomState(1).permutation(3000)
    srt = ring_ctx.dense_from_numpy(keys, keys).sort_by_key()
    sk = [kk for kk, _ in srt.collect()]
    assert sk == sorted(keys.tolist())

    left = ring_ctx.dense_from_numpy(np.arange(1000) % 100,
                                     np.arange(1000).astype(np.float32))
    right = ring_ctx.dense_from_numpy(np.arange(100), np.arange(100) * 2)
    assert left.join(right).count() == 1000


def test_ring_skew_overflow(ring_ctx):
    got = dict(
        ring_ctx.dense_range(4096).map(lambda x: (x * 0, x))
        .reduce_by_key(op="add").collect()
    )
    assert got == {0: sum(range(4096))}


def test_segment_reduce_kernels_direct():
    """Shard-local kernels outside shard_map: sorted-run reductions."""
    cols = {"k": jnp.asarray([3, 1, 2, 1, 3, 9], jnp.int32),
            "v": jnp.asarray([1.0, 2.0, 3.0, 4.0, 5.0, 6.0], jnp.float32)}
    out, n_seg = kernels.segment_reduce_named(cols, jnp.int32(6), "k", "add")
    got = {int(k): float(x) for k, x in
           zip(out["k"][:int(n_seg)], out["v"][:int(n_seg)])}
    assert got == {1: 6.0, 2: 3.0, 3: 6.0, 9: 6.0}

    combine = lambda a, b: {"v": a["v"] + b["v"]}
    out2, n2 = kernels.segment_reduce_sorted(cols, jnp.int32(6), "k", combine)
    got2 = {int(k): float(x) for k, x in
            zip(out2["k"][:int(n2)], out2["v"][:int(n2)])}
    assert got2 == got


_SEG_CAP = 40
_NP_REDUCE = {"add": np.add, "min": np.minimum, "max": np.maximum,
              "prod": np.multiply}


@functools.lru_cache(maxsize=None)
def _named_reduce_program(op, wide):
    return jax.jit(lambda cols, count: kernels.segment_reduce_named(
        cols, count, "k", op, presorted=True,
        lo_name="k.lo" if wide else None))


def _segment_case(op, wide, column, count, keys):
    """Sorted valid rows, then rows past `count` that hold anything: keys
    out of order and values no reduction may pick up. Values are whole
    numbers (prod: -1, 1, 2) so any order of a float reduction is exact."""
    rng = np.random.RandomState(
        zlib.crc32(repr((op, wide, column, count, keys)).encode()))
    if keys == "a_key_a_row":
        hi = rng.permutation(_SEG_CAP).astype(np.int32) - 7
    elif keys == "one_key":
        hi = np.full(_SEG_CAP, 5, np.int32)
    else:
        hi = rng.randint(-2, _SEG_CAP // 10 - 2, _SEG_CAP).astype(np.int32)
    lo = np.zeros(_SEG_CAP, np.int32)
    if wide and keys == "ten_rows_a_key":  # the low word splits a key's rows
        lo = rng.randint(-1, 1, _SEG_CAP).astype(np.int32)
    order = np.lexsort((lo[:count], hi[:count]))
    hi[:count], lo[:count] = hi[:count][order], lo[:count][order]
    shape = (_SEG_CAP, 3) if column == "float32_3wide" else (_SEG_CAP,)
    vals = rng.choice([-1, 1, 2], shape) if op == "prod" \
        else rng.randint(-50, 50, shape)
    vals = vals.astype(np.int32 if column == "int32" else np.float32)
    return hi, lo, vals


@pytest.mark.parametrize("keys", ["ten_rows_a_key", "a_key_a_row", "one_key"])
@pytest.mark.parametrize("count", [0, 1, _SEG_CAP - 1, _SEG_CAP],
                         ids=["empty", "one_row", "all_but_one", "full"])
@pytest.mark.parametrize("column", ["float32", "int32", "float32_3wide"])
@pytest.mark.parametrize("wide", [False, True], ids=["one_word", "two_word"])
@pytest.mark.parametrize("op", ["add", "min", "max", "prod"])
def test_segment_reduce_named_matches_numpy_group_by(op, wide, column, count,
                                                     keys):
    """Segment i's key and reduction are at row i, in key order, and every
    row from n_segments on is zero in every column: whatever the rows past
    `count` hold, and where min / max / prod leave their identity."""
    hi, lo, vals = _segment_case(op, wide, column, count, keys)
    cols = {"k": jnp.asarray(hi), "v": jnp.asarray(vals)}
    if wide:
        cols["k.lo"] = jnp.asarray(lo)
    out, n_seg = _named_reduce_program(op, wide)(cols, jnp.int32(count))
    out = {name: np.asarray(col) for name, col in out.items()}
    assert set(out) == set(cols)

    pairs = np.stack([hi[:count], lo[:count]], axis=1)
    starts = np.flatnonzero(
        np.r_[True, (pairs[1:] != pairs[:-1]).any(axis=1)][:count])
    n = len(starts)
    assert int(n_seg) == n
    np.testing.assert_array_equal(out["k"][:n], hi[:count][starts])
    if wide:
        np.testing.assert_array_equal(out["k.lo"][:n], lo[:count][starts])
    if n:
        want = _NP_REDUCE[op].reduceat(vals[:count], starts, axis=0,
                                       dtype=vals.dtype)
        np.testing.assert_array_equal(out["v"][:n], want)
    for name, col in out.items():
        assert col.shape == cols[name].shape and col.dtype == cols[name].dtype
        assert not col[n:].any(), name


def test_masked_reduce_ignores_invalid_rows():
    col = jnp.asarray([5.0, -2.0, 999.0, 999.0], jnp.float32)
    assert float(kernels.masked_reduce(col, jnp.int32(2), "add")) == 3.0
    assert float(kernels.masked_reduce(col, jnp.int32(2), "min")) == -2.0
    assert float(kernels.masked_reduce(col, jnp.int32(2), "max")) == 5.0


_PASS_COLUMNS = {
    # every slot holds something other than zero, past `count` too; the
    # floats carry a -0.0 and a NaN so that "bit for bit" means bits
    "int32": lambda cap: np.arange(1, cap + 1, dtype=np.int32) * -7,
    "float32": lambda cap: np.where(
        np.arange(cap) % 5 == 3, np.float32("nan"),
        np.where(np.arange(cap) % 5 == 1, np.float32(-0.0),
                 np.arange(1, cap + 1, dtype=np.float32) / 3)
    ).astype(np.float32),
    "float32_3wide": lambda cap: (
        np.arange(1, 3 * cap + 1, dtype=np.float32).reshape(cap, 3) - 0.5),
}
_PASS_COUNTS = {
    "zero": lambda cap, out: 0,
    "one": lambda cap, out: 1,
    "mid": lambda cap, out: min(cap, out) // 2,
    "capacity": lambda cap, out: cap,
    "past_out": lambda cap, out: out + 5,
}


@pytest.mark.parametrize("count", sorted(_PASS_COUNTS))
@pytest.mark.parametrize("column", sorted(_PASS_COLUMNS))
@pytest.mark.parametrize("capacity,out_capacity",
                         [(48, 64), (64, 64), (64, 48)],
                         ids=["pad", "same", "slice"])
def test_passthrough_exchange_is_compact_of_a_prefix(capacity, out_capacity,
                                                     column, count):
    """No row moves through a passthrough, so it scatters nothing; what it
    returns is still compact() of the prefix mask, bit for bit: the kept
    rows, zeros after them, the count, the overflow flag and the rows cut
    on overflow."""
    cols = {"k": jnp.asarray(_PASS_COLUMNS["int32"](capacity)),
            "v": jnp.asarray(_PASS_COLUMNS[column](capacity))}
    n = _PASS_COUNTS[count](capacity, out_capacity)

    def old(cols, n):
        out, new_count = kernels.compact(
            cols, kernels.valid_mask(capacity, n), out_capacity)
        return out, new_count, new_count > out_capacity

    want, want_count, want_overflow = jax.jit(old)(cols, jnp.int32(n))
    got, got_count, got_overflow = jax.jit(
        functools.partial(kernels.passthrough_exchange, capacity=capacity,
                          out_capacity=out_capacity))(cols, jnp.int32(n))
    assert int(got_count) == int(want_count) == min(n, capacity)
    assert got_count.dtype == want_count.dtype
    assert bool(got_overflow) == bool(want_overflow) \
        == (min(n, capacity) > out_capacity)
    for name in cols:
        assert got[name].shape == want[name].shape
        assert got[name].dtype == want[name].dtype
        assert np.asarray(got[name]).tobytes() \
            == np.asarray(want[name]).tobytes(), name


def test_group_by_bucket_branch_parity():
    """Counting-sort and argsort branches of _group_by_bucket must agree
    (grouped rows, counts, starts) — the argsort branch is otherwise
    unreachable on the 8-device test mesh."""
    from vega_tpu.tpu.kernels import _group_by_bucket

    rng = np.random.RandomState(3)
    n_shards = 8
    bucket = jnp.asarray(rng.randint(0, n_shards + 1, size=512, dtype=np.int32))
    cols = {"k": jnp.asarray(rng.randint(0, 100, 512, dtype=np.int32)),
            "v": jnp.asarray(rng.rand(512).astype(np.float32))}
    fast = _group_by_bucket(cols, bucket, n_shards, prefer_low_memory=False)
    slow = _group_by_bucket(cols, bucket, n_shards, prefer_low_memory=True)
    # valid (non-ghost) prefix must match exactly; ghost-bucket tail rows are
    # masked by callers, but the counting branch zero-fills dropped slots
    # only beyond capacity, so the full grouped arrays agree here too.
    n_valid = int(jnp.sum(bucket < n_shards))
    for name in cols:
        assert jnp.array_equal(fast[0][name][:n_valid], slow[0][name][:n_valid])
    assert jnp.array_equal(fast[1], slow[1])  # counts
    assert jnp.array_equal(fast[2], slow[2])  # starts


def test_bucket_key_sort_groups_and_sorts():
    """bucket_key_sort: one multi-key sort -> bucket-grouped rows with
    key-sorted runs, ghost (invalid) rows sunk to the end, row multiset
    preserved. This is the map side of the 2-sort exchange."""
    rng = np.random.RandomState(11)
    capacity, count, n_shards = 64, 41, 4
    keys = jnp.asarray(rng.randint(0, 30, capacity, dtype=np.int32))
    vals = jnp.asarray(rng.rand(capacity).astype(np.float32))
    iota = jnp.arange(capacity)
    bucket = jnp.where(iota < count, keys % n_shards, n_shards)
    cols = {"k": keys, "v": vals}

    out, sb = kernels.bucket_key_sort(cols, bucket, "k")

    sb = np.asarray(sb)
    ok = np.asarray(out["k"])
    assert np.all(sb[1:] >= sb[:-1]), "buckets must be grouped"
    assert np.all(sb[count:] == n_shards), "ghost rows must sink to the end"
    same = sb[1:] == sb[:-1]
    assert np.all(ok[1:][same] >= ok[:-1][same]), "key-sorted within bucket"
    got = sorted(zip(np.asarray(out["k"])[:count].tolist(),
                     np.asarray(out["v"])[:count].tolist()))
    exp = sorted(zip(np.asarray(keys)[:count].tolist(),
                     np.asarray(vals)[:count].tolist()))
    assert got == exp, "row multiset must be preserved"


def test_pregrouped_counts_match_group_by_bucket():
    """The pregrouped exchange's bincount shortcut must agree with
    _group_by_bucket's (counts, starts) on grouped input."""
    from vega_tpu.tpu.kernels import _group_by_bucket

    rng = np.random.RandomState(12)
    n_shards = 8
    bucket = jnp.sort(jnp.asarray(
        rng.randint(0, n_shards + 1, size=256, dtype=np.int32)))
    cols = {"k": jnp.arange(256, dtype=jnp.int32)}
    _, counts, starts = _group_by_bucket(cols, bucket, n_shards)
    counts_all = jnp.bincount(bucket, length=n_shards + 1)
    assert jnp.array_equal(counts_all[:n_shards], counts)
    assert jnp.array_equal(
        (jnp.cumsum(counts_all) - counts_all)[:n_shards], starts)


def test_searchsorted2_matches_numpy_lexicographic():
    """The two-word binary search must agree with numpy searchsorted over
    the decoded int64 keys, both sides."""
    from vega_tpu.tpu import block as block_lib

    rng = np.random.RandomState(7)
    ref = np.sort(rng.randint(-2**62, 2**62, size=257, dtype=np.int64))
    q = np.concatenate([
        ref[rng.randint(0, len(ref), size=100)],  # exact hits
        rng.randint(-2**62, 2**62, size=100, dtype=np.int64),
    ])
    rh, rl = block_lib.encode_i64(ref)
    qh, ql = block_lib.encode_i64(q)
    for side in ("left", "right"):
        got = kernels.searchsorted2(
            jnp.asarray(rh), jnp.asarray(rl),
            jnp.asarray(qh), jnp.asarray(ql), side,
        )
        np.testing.assert_array_equal(
            np.asarray(got), np.searchsorted(ref, q, side=side)
        )


def test_hash32_pair_distributes_over_low_word():
    """Keys differing only in the low word must spread over buckets (a
    hi-only hash would put every such key in one bucket)."""
    hi = jnp.zeros(4096, jnp.int32)
    lo = jnp.arange(4096, dtype=jnp.int32)
    buckets = (kernels.hash32_pair(hi, lo) % jnp.uint32(8)).astype(np.int32)
    counts = np.bincount(np.asarray(buckets), minlength=8)
    assert counts.min() > 4096 // 8 // 4  # roughly uniform


def test_wide_add_checked_overflow_predicate():
    """Signed-overflow detection over the wide encoding: equal-sign
    operands whose int64 sum wraps must flag; everything else must not."""
    from vega_tpu.tpu import block as block_lib

    cases = np.array([
        (2**62, 2**62),            # positive wrap
        (-2**62, -2**62 - 1),      # negative wrap
        (2**62, -2**62),           # mixed signs: never wraps
        (2**62, 2**62 - 1),        # max boundary: 2^63-1, fits
        (-2**63 + 1, -1),          # min boundary: -2^63, fits
        (-2**63, -1),              # below min: wraps
        (123, 456),                # small
        (0x7FFFFFFF, 1),           # low-word carry only, no int64 wrap
    ], dtype=np.int64)
    a, b = cases[:, 0], cases[:, 1]
    ah, al = block_lib.encode_i64(a)
    bh, bl = block_lib.encode_i64(b)
    rh, rl, ovf = kernels.wide_add_checked(
        jnp.asarray(ah), jnp.asarray(al), jnp.asarray(bh), jnp.asarray(bl))
    got = block_lib.decode_i64(np.asarray(rh), np.asarray(rl))
    exp_wrap = (a + b)  # numpy int64 wraps mod 2^64
    np.testing.assert_array_equal(got, exp_wrap)
    exact = a.astype(object) + b.astype(object)
    exp_ovf = np.array([v < -2**63 or v > 2**63 - 1 for v in exact])
    np.testing.assert_array_equal(np.asarray(ovf), exp_ovf)


def test_partition_pos_pallas_matches_xla_ranks():
    """The Pallas counting-partition rank kernel (interpret mode) is
    bit-identical to the XLA one-hot rank path for every row, including
    ghost-bucket rows and non-tile-aligned lengths."""
    from vega_tpu.tpu.pallas_kernels import partition_pos_pallas

    rng = np.random.RandomState(11)
    for n, k in ((1024, 8), (5000, 9), (130_000, 17), (777, 2)):
        bucket = rng.randint(0, k, size=n).astype(np.int32)
        counts = np.bincount(bucket, minlength=k)
        starts = np.cumsum(counts) - counts
        # XLA reference ranks
        one_hot = (bucket[:, None] == np.arange(k)[None, :]).astype(np.int32)
        rank = np.take_along_axis(np.cumsum(one_hot, axis=0),
                                  bucket[:, None], axis=1)[:, 0] - 1
        exp = starts[bucket] + rank
        got = partition_pos_pallas(
            jnp.asarray(bucket), k, jnp.asarray(starts.astype(np.int32)),
            True,  # interpret: no TPU here
        )
        np.testing.assert_array_equal(np.asarray(got), exp, err_msg=f"{n},{k}")


def test_partition_pos_pallas_lowers_for_tpu():
    """The rank kernel must pass Mosaic lowering offline (a kernel that
    only works in interpret mode would burn a chip run)."""
    import jax

    from vega_tpu.tpu.pallas_kernels import partition_pos_pallas

    bucket = jnp.zeros(4096, jnp.int32)
    starts = jnp.zeros(9, jnp.int32)
    exp = jax.export.export(
        jax.jit(lambda b, s: partition_pos_pallas(b, 9, s)),
        platforms=["tpu"],
    )(bucket, starts)
    assert "tpu_custom_call" in exp.mlir_module()


def _tag(n):
    return np.arange(n, dtype=np.int32)  # source position of every row


# Payload blocks for the sorts: what rides the sort as operands (1-D columns
# of any dtype and number) and what is gathered behind it (a column with
# more than one dimension).
_PAYLOADS = {
    "ties_payload": lambda rng, n: {"v": _tag(n)},
    "mixed_payloads": lambda rng, n: {
        "f": rng.randn(n).astype(np.float32), "i": _tag(n),
        "b": rng.rand(n) < 0.5, "u": rng.randint(0, 255, n).astype(np.uint8)},
    "eight_values": lambda rng, n: {
        f"v{j}": (_tag(n) * (j + 1)).astype(np.float32 if j % 2 else np.int32)
        for j in range(8)},
    "deep_column": lambda rng, n: {
        "v": _tag(n), "m": rng.randn(n, 3).astype(np.float32),
        "t": np.arange(n * 4, dtype=np.int32).reshape(n, 2, 2)},
}
_SORT_KEYSETS = ["int32", "float32", "wide", *_PAYLOADS,
                 "wide_eight_values_deep"]


def _sort_case(keyset, rng, n):
    """(cols, lo_name, host keys) for one key layout, duplicate keys
    included (the values then pin the stable order)."""
    from vega_tpu.tpu import block as block_lib
    from vega_tpu.tpu.block import KEY, KEY_LO, VALUE

    vals = rng.randint(0, 10**6, size=n).astype(np.int32)
    if keyset in _PAYLOADS:
        # five distinct keys over thousands of rows: only the payload tells
        # a key's rows apart, so every column pins the stable order
        host = rng.randint(0, 5, size=n).astype(np.int32)
        return {KEY: host, **_PAYLOADS[keyset](rng, n)}, None, host
    if keyset == "wide_eight_values_deep":
        host = rng.randint(-2**50, 2**50, size=n).astype(np.int64)
        host[: n // 2] = host[0] + np.arange(n // 2) % 3
        hi, lo = block_lib.encode_i64(host)
        payload = {**_PAYLOADS["eight_values"](rng, n),
                   **_PAYLOADS["deep_column"](rng, n)}
        return {KEY: hi, KEY_LO: lo, **payload}, KEY_LO, host
    if keyset == "int32":
        host = rng.randint(-100, 100, size=n).astype(np.int32)
        return {KEY: host, VALUE: vals}, None, host
    if keyset == "float32":
        host = (rng.randn(n) * 10).astype(np.float32)
        return {KEY: host, VALUE: vals}, None, host
    host = rng.randint(-2**50, 2**50, size=n).astype(np.int64)
    host[: n // 4] = host[0] + np.arange(n // 4) % 7  # duplicates too
    hi, lo = block_lib.encode_i64(host)
    return {KEY: hi, KEY_LO: lo, VALUE: vals}, KEY_LO, host


def _stable_order(host, descending):
    """numpy's stable order of the valid keys; descending flips the key
    without overflow (bitwise-not for ints, negation for floats), so ties
    keep their source order either way."""
    if descending:
        host = -host if host.dtype.kind == "f" else ~host
    return np.argsort(host, kind="stable")


def _assert_sorted_like(cols, out, order, count):
    for nm, col in cols.items():
        np.testing.assert_array_equal(
            np.asarray(out[nm])[:count], np.asarray(col)[:count][order],
            err_msg=nm)


@pytest.mark.parametrize("descending", [False, True],
                         ids=["ascending", "descending"])
@pytest.mark.parametrize("keyset", _SORT_KEYSETS)
def test_sort_by_column_matches_numpy_stable_argsort(keyset, descending):
    """sort_by_column against numpy on the valid prefix: every column
    follows the key's stable order (duplicates included), and the ghost
    rows sink behind it."""
    from vega_tpu.tpu.block import KEY

    n, count = 3_000, 2_700
    cols, lo_name, host = _sort_case(keyset, np.random.RandomState(4), n)
    out = kernels.sort_by_column(
        {nm: jnp.asarray(c) for nm, c in cols.items()}, jnp.int32(count),
        KEY, descending=descending, lo_name=lo_name)
    _assert_sorted_like(cols, out, _stable_order(host[:count], descending),
                        count)


def _edge_rows(kind):
    """(host keys, count, lo?) for the rows the permutation tests kept:
    the int32 and float extremes among the valid rows (they tie with the
    padding the ghosts are forced to), ghost rows holding keys that would
    sort first, a constant high word, and no valid row at all."""
    rng = np.random.RandomState(11)
    n, count = 5_000, 4_321
    if kind == "int32_extremes":
        host = rng.randint(-2**31, 2**31 - 1, size=n).astype(np.int32)
        host[: n // 4] = rng.randint(-50, 50, size=n // 4)  # duplicates
        host[0], host[1] = -2**31, 2**31 - 1
        host[7], host[9] = 2**31 - 1, -2**31
        host[count:] = -2**31  # ghosts must not lead an ascending sort
    elif kind == "float32_infinities":
        host = (rng.randn(n) * 100).astype(np.float32)
        host[3], host[4], host[5] = np.inf, -np.inf, np.inf
        host[count:] = -np.inf
    elif kind == "wide_constant_high_word":
        host = (2**40 + rng.randint(0, 1_000, size=n)).astype(np.int64)
    elif kind == "wide_full_range":
        host = rng.randint(-2**62, 2**62, size=n).astype(np.int64)
        host[0], host[1] = np.iinfo(np.int64).min, np.iinfo(np.int64).max
    elif kind == "int32_full":  # count == capacity: no ghost at all
        host = rng.randint(-2**31, 2**31 - 1, size=n).astype(np.int32)
        host[: n // 4] = rng.randint(-50, 50, size=n // 4)
        host[5], host[n - 1] = -2**31, 2**31 - 1
        count = n
    elif kind == "wide_full":
        host = rng.randint(-2**62, 2**62, size=n).astype(np.int64)
        host[: n // 4] = host[0] + np.arange(n // 4) % 5
        host[n - 1], host[n - 2] = (np.iinfo(np.int64).min,
                                    np.iinfo(np.int64).max)
        count = n
    elif kind == "wide_all_ghost":
        host = rng.randint(-2**62, 2**62, size=n).astype(np.int64)
        count = 0
    else:
        assert kind == "all_ghost"
        host = rng.randint(-50, 50, size=n).astype(np.int32)
        count = 0
    return host, count


@pytest.mark.parametrize("descending", [False, True],
                         ids=["ascending", "descending"])
@pytest.mark.parametrize("kind", [
    "int32_extremes", "float32_infinities", "wide_constant_high_word",
    "wide_full_range", "all_ghost", "int32_full", "wide_full",
    "wide_all_ghost"])
def test_sort_by_column_edge_rows(kind, descending):
    """Extreme keys tie with the ghosts' padding and must still come
    first (stability: valid rows sit at lower positions); the ghosts keep
    their own order behind them, so an all-ghost shard is the identity on
    every column that rides the sort. The ghosts' key words hold the
    padding the sort ordered them by (the docstring's promise)."""
    from vega_tpu.tpu import block as block_lib
    from vega_tpu.tpu.block import KEY, KEY_LO, VALUE

    host, count = _edge_rows(kind)
    n = host.shape[0]
    tag = np.arange(n, dtype=np.int32)  # source position of every row
    if host.dtype == np.int64:
        hi, lo = block_lib.encode_i64(host)
        cols, lo_name = {KEY: hi, KEY_LO: lo, VALUE: tag}, KEY_LO
    else:
        cols, lo_name = {KEY: host, VALUE: tag}, None
    out = kernels.sort_by_column(
        {nm: jnp.asarray(c) for nm, c in cols.items()}, jnp.int32(count),
        KEY, descending=descending, lo_name=lo_name)
    _assert_sorted_like(cols, out, _stable_order(host[:count], descending),
                        count)
    assert np.asarray(out[VALUE])[count:].tolist() == list(range(count, n))
    for nm in set(cols) - {VALUE}:
        word = np.asarray(out[nm])
        if word.dtype.kind == "f":
            pad = -np.inf if descending else np.inf
        else:
            info = np.iinfo(word.dtype)
            pad = info.min if descending else info.max
        assert (word[count:] == pad).all(), nm


def test_sort_by_column_descending_int_min():
    """Regression: descending int sorts must not negate the key —
    negation wraps INT32_MIN onto itself and sorts it FIRST instead of
    last."""
    from vega_tpu.tpu.block import KEY

    keys = np.array([5, -2**31, 7, 0], dtype=np.int32)
    out = kernels.sort_by_column({KEY: jnp.asarray(keys)},
                                 jnp.int32(4), KEY, descending=True)
    assert np.asarray(out[KEY]).tolist() == [7, 5, 0, -2**31]


@pytest.mark.parametrize("count", [3_500, 0, 4_000],
                         ids=["some_ghosts", "all_ghost", "no_ghost"])
@pytest.mark.parametrize("keyset", _SORT_KEYSETS)
def test_bucket_key_sort_matches_numpy_lexsort(keyset, count):
    """The fused (bucket major, key minor) sort against
    np.lexsort((key, bucket)) over every row, ghosts (bucket = n_shards)
    included: lexsort is stable, so the whole permutation is pinned and
    each column must follow it, the ghosts' keys too."""
    from vega_tpu.tpu.block import KEY

    n, n_shards = 4_000, 8
    cols, lo_name, host = _sort_case(keyset, np.random.RandomState(6), n)
    bucket = np.asarray(
        kernels.hash32(jnp.asarray(cols[KEY])) % jnp.uint32(n_shards)
    ).astype(np.int32)
    bucket[count:] = n_shards
    out, out_bucket = kernels.bucket_key_sort(
        {nm: jnp.asarray(c) for nm, c in cols.items()},
        jnp.asarray(bucket), KEY, lo_name=lo_name)
    order = np.lexsort((host, bucket))
    np.testing.assert_array_equal(np.asarray(out_bucket), bucket[order])
    for nm, col in cols.items():
        np.testing.assert_array_equal(np.asarray(out[nm]), col[order],
                                      err_msg=nm)


# ---------------------------------------------------------------------------
# merge join: ranks from one merge, slot owners from a scatter and a scan
# ---------------------------------------------------------------------------

I32_MAX = 2**31 - 1


def _padded(keys, capacity, fill=I32_MAX):
    out = np.full(capacity, fill, dtype=np.asarray(keys).dtype)
    out[:len(keys)] = keys
    return out


def _rank_case(name):
    """(left keys, lcap, right keys, rcap, wide) — valid rows only, sorted;
    the rest of each capacity is padding, as merge_join_expand hands it."""
    rng = np.random.RandomState(31)
    if name == "duplicates_both_sides":
        return (np.sort(rng.randint(0, 40, 200)), 256,
                np.sort(rng.randint(0, 40, 180)), 256, False)
    if name == "two_word_duplicates":
        pool = rng.randint(-2**62, 2**62, size=30, dtype=np.int64)
        # same high word, different low words too
        pool = np.concatenate([pool, pool[:10] + 3, pool[:10] - 2**31])
        return (np.sort(pool[rng.randint(0, 50, 220)]), 256,
                np.sort(pool[rng.randint(0, 50, 150)]), 256, True)
    if name == "empty_left":
        return (np.zeros(0, np.int64), 128,
                np.sort(rng.randint(0, 9, 100)), 128, False)
    if name == "empty_right":
        return (np.sort(rng.randint(0, 9, 100)), 128,
                np.zeros(0, np.int64), 128, False)
    if name == "two_word_empty_right":
        return (np.sort(rng.randint(-2**62, 2**62, 90, dtype=np.int64)), 128,
                np.zeros(0, np.int64), 128, True)
    if name == "counts_below_capacity":
        return (np.sort(rng.randint(-50, 50, 17)), 512,
                np.sort(rng.randint(-50, 50, 5)), 512, False)
    if name == "valid_key_equals_sentinel":
        return (np.array([3, 3, I32_MAX, I32_MAX]), 128,
                np.array([1, 3, I32_MAX, I32_MAX, I32_MAX]), 128, False)
    if name == "two_word_key_equals_sentinel":
        top = np.iinfo(np.int64).max
        return (np.array([5, top, top], np.int64), 128,
                np.array([5, 5, top], np.int64), 128, True)
    if name == "lcap_below_rcap":
        return (np.sort(rng.randint(0, 300, 100)), 128,
                np.sort(rng.randint(0, 300, 900)), 1024, False)
    if name == "lcap_above_rcap":
        return (np.sort(rng.randint(0, 300, 2000)), 2048,
                np.sort(rng.randint(0, 300, 100)), 128, False)
    raise KeyError(name)


_RANK_CASES = [
    "duplicates_both_sides", "two_word_duplicates", "empty_left",
    "empty_right", "two_word_empty_right", "counts_below_capacity",
    "valid_key_equals_sentinel", "two_word_key_equals_sentinel",
    "lcap_below_rcap", "lcap_above_rcap",
]


def _encode_side(keys, capacity, wide):
    """Device columns of one side, padded with the largest key."""
    from vega_tpu.tpu import block as block_lib

    if not wide:
        return (jnp.asarray(_padded(keys.astype(np.int32), capacity)),)
    hi, lo = block_lib.encode_i64(np.asarray(keys, np.int64))
    return (jnp.asarray(_padded(hi, capacity)),
            jnp.asarray(_padded(lo, capacity)))


@pytest.mark.parametrize("side", ["left", "right"])
@pytest.mark.parametrize("case", _RANK_CASES)
def test_merge_ranks_match_numpy_searchsorted(case, side):
    """One merge of the two sorted columns gives what a binary search per
    left row gave: np.searchsorted over the valid right keys, clipped by
    the right count as merge_join_expand clips it."""
    lk, lcap, rk, rcap, wide = _rank_case(case)
    lw = _encode_side(lk, lcap, wide)
    rw = _encode_side(rk, rcap, wide)
    lo, hi = kernels.merge_ranks(lw, rw)
    got = np.minimum(np.asarray(lo if side == "left" else hi), len(rk))
    assert got.shape == (lcap,) and got.dtype == np.int32
    want = np.searchsorted(rk, lk, side=side)
    np.testing.assert_array_equal(got[:len(lk)], want)
    # padding rows rank as the largest key does
    top = np.iinfo(np.int64).max if wide else I32_MAX
    assert (got[len(lk):] == np.searchsorted(rk, top, side=side)
            if side == "left" else got[len(lk):] == len(rk)).all()


def test_merge_ranks_float_keys_zero_signs():
    """-0.0 and 0.0 are one key to lax.sort and to jnp.searchsorted."""
    lk = np.array([-1.5, -0.0, 0.0, 2.0], np.float32)
    rk = np.array([-0.0, 0.0, 0.0, 2.0, 7.0], np.float32)
    lo, hi = kernels.merge_ranks(
        [jnp.asarray(_padded(lk, 128, np.inf))],
        [jnp.asarray(_padded(rk, 128, np.inf))])
    np.testing.assert_array_equal(np.asarray(lo)[:4], [0, 0, 0, 3])
    np.testing.assert_array_equal(np.asarray(hi)[:4], [0, 3, 3, 4])


_RAGGED_CASES = {
    "zeros_leading": ([0, 0, 3, 1, 2], 128),
    "zeros_interior": ([2, 0, 0, 1, 0, 4], 128),
    "zeros_trailing": ([1, 5, 0, 0, 0], 128),
    "all_zeros": ([0, 0, 0, 0], 128),
    "single_row": ([7], 128),
    "total_fills_capacity": ([64, 0, 64], 128),
    "total_above_capacity": ([100, 0, 100, 3], 128),
    "more_rows_than_slots": ([1, 0] * 300, 128),
}


@pytest.mark.parametrize("case", list(_RAGGED_CASES))
def test_ragged_expand_matches_numpy_repeat(case):
    counts, out_capacity = _RAGGED_CASES[case]
    counts = np.asarray(counts, np.int32)
    owner, offset, total = kernels.ragged_expand(
        jnp.asarray(counts), out_capacity)
    owner, offset = np.asarray(owner), np.asarray(offset)
    assert int(total) == counts.sum()  # exact, even past the capacity
    want_owner = np.repeat(np.arange(len(counts)), counts)
    starts = np.cumsum(counts) - counts
    want_offset = np.arange(len(want_owner)) - starts[want_owner]
    n = min(len(want_owner), out_capacity)
    np.testing.assert_array_equal(owner[:n], want_owner[:n])
    np.testing.assert_array_equal(offset[:n], want_offset[:n])
    # slots that are not output still name a row that exists
    assert owner.shape == (out_capacity,)
    assert ((owner >= 0) & (owner < len(counts))).all()


@pytest.mark.parametrize("counts", [
    [2**30, 2**30, 2**30],          # the running sum wraps at row 2
    [2**31 - 1, 1, 0, 5],           # the total itself wraps
], ids=["starts_wrap", "total_wraps"])
def test_ragged_expand_wrapped_sum_saturates(counts):
    owner, _, total = kernels.ragged_expand(
        jnp.asarray(np.asarray(counts, np.int32)), 128)
    assert int(total) == I32_MAX
    owner = np.asarray(owner)
    assert ((owner >= 0) & (owner < len(counts))).all()


def _numpy_join(lk, lv, rk, rv, outer, fill):
    """dup x dup reference: left rows in stable key order, each against its
    right matches in stable key order."""
    lorder = np.argsort(lk, kind="stable")
    rorder = np.argsort(rk, kind="stable")
    rks = rk[rorder]
    rows = []
    for i in lorder:
        a = np.searchsorted(rks, lk[i], "left")
        b = np.searchsorted(rks, lk[i], "right")
        for j in rorder[a:b]:
            rows.append((lk[i], lv[i], rv[j]))
        if outer and a == b:
            rows.append((lk[i], lv[i], fill))
    return rows


def _join_case(name):
    """(left keys, lcap, right keys, rcap, out_capacity, wide)."""
    rng = np.random.RandomState(131)
    if name == "duplicates_both_sides":
        return (rng.randint(0, 30, 60), 128, rng.randint(0, 40, 50), 128,
                512, False)
    if name == "two_word_keys":
        pool = rng.randint(-2**62, 2**62, size=25, dtype=np.int64)
        pool = np.concatenate([pool, pool[:8] + 1])
        return (pool[rng.randint(0, 33, 70)], 128,
                pool[rng.randint(0, 30, 40)], 128, 1024, True)
    if name == "product_overflows_capacity":
        return (rng.randint(0, 4, 100), 128, rng.randint(0, 4, 90), 128,
                256, False)
    if name == "two_word_overflow":
        pool = rng.randint(-2**62, 2**62, size=3, dtype=np.int64)
        return (pool[rng.randint(0, 3, 64)], 128,
                pool[rng.randint(0, 3, 64)], 128, 128, True)
    if name == "empty_left":
        return (np.zeros(0, np.int64), 128, rng.randint(0, 9, 50), 128,
                128, False)
    if name == "empty_right":
        return (rng.randint(0, 9, 50), 128, np.zeros(0, np.int64), 128,
                128, False)
    if name == "valid_key_equals_sentinel":
        return (np.array([I32_MAX, 7, I32_MAX, 2]), 128,
                np.array([I32_MAX, 7, 7, I32_MAX, 1]), 128, 128, False)
    if name == "lcap_below_rcap":
        return (rng.randint(0, 200, 90), 128, rng.randint(0, 200, 700),
                1024, 2048, False)
    if name == "lcap_above_rcap":
        return (rng.randint(0, 60, 900), 1024, rng.randint(0, 60, 100), 128,
                2048, False)
    raise KeyError(name)


_JOIN_CASES = [
    "duplicates_both_sides", "two_word_keys", "product_overflows_capacity",
    "two_word_overflow", "empty_left", "empty_right",
    "valid_key_equals_sentinel", "lcap_below_rcap", "lcap_above_rcap",
]


@pytest.mark.parametrize("outer", [False, True], ids=["inner", "outer"])
@pytest.mark.parametrize("case", _JOIN_CASES)
def test_merge_join_expand_matches_numpy_product(case, outer):
    """Rows, their order (left sort order), count and the exact total —
    also where the product is larger than out_capacity, which is what the
    driver sizes its one retry from."""
    from vega_tpu.tpu import block as block_lib

    lk, lcap, rk, rcap, out_capacity, wide = _join_case(case)
    rng = np.random.RandomState(7)
    lv = rng.randint(0, 1000, len(lk)).astype(np.float32)
    rv = rng.randint(0, 1000, len(rk)).astype(np.float32)

    def side(keys, vals, capacity):
        # rows past the count hold garbage, as a block's padding may
        if wide:
            hi, lo = block_lib.encode_i64(np.asarray(keys, np.int64))
            cols = {"k": _padded(hi, capacity, -5),
                    "k.lo": _padded(lo, capacity, -5)}
        else:
            cols = {"k": _padded(keys.astype(np.int32), capacity, -5)}
        cols["v"] = _padded(vals, capacity, -1.0)
        return {n: jnp.asarray(c) for n, c in cols.items()}

    out, count, total = kernels.merge_join_expand(
        side(lk, lv, lcap), jnp.int32(len(lk)),
        side(rk, rv, rcap), jnp.int32(len(rk)),
        "k", out_capacity, outer=outer, fill_value=-7.0,
        lo_name="k.lo" if wide else None)
    want = _numpy_join(lk, lv, rk, rv, outer, np.float32(-7.0))
    assert int(total) == len(want)
    assert int(count) == min(len(want), out_capacity)
    n = int(count)
    keys = np.asarray(out["k"])[:n]
    if wide:
        keys = block_lib.decode_i64(keys, np.asarray(out["k.lo"])[:n])
    got = list(zip(keys.tolist(), np.asarray(out["v"])[:n].tolist(),
                   np.asarray(out["r_v"])[:n].tolist()))
    assert got == [(int(k), float(a), float(b)) for k, a, b in want[:n]]


def test_merge_join_expand_presorted_sides_agree():
    """left_sorted / right_sorted skip the sorts and nothing else."""
    rng = np.random.RandomState(5)
    lk = np.sort(rng.randint(0, 25, 80)).astype(np.int32)
    rk = np.sort(rng.randint(0, 25, 70)).astype(np.int32)
    left = {"k": jnp.asarray(_padded(lk, 128, 0)),
            "v": jnp.asarray(_padded(np.arange(80, dtype=np.float32), 128))}
    right = {"k": jnp.asarray(_padded(rk, 128, 0)),
             "v": jnp.asarray(_padded(np.arange(70, dtype=np.float32), 128))}
    a = kernels.merge_join_expand(left, jnp.int32(80), right, jnp.int32(70),
                                  "k", 512)
    b = kernels.merge_join_expand(left, jnp.int32(80), right, jnp.int32(70),
                                  "k", 512, left_sorted=True,
                                  right_sorted=True)
    assert int(a[1]) == int(b[1]) and int(a[2]) == int(b[2])
    n = int(a[1])
    for name in a[0]:
        np.testing.assert_array_equal(np.asarray(a[0][name])[:n],
                                      np.asarray(b[0][name])[:n])
