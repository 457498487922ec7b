"""Native C++ runtime tests: hash parity, codec round-trips, and
host-shuffle fast-path equivalence with the pure-Python path."""

import os

import numpy as np
import pytest

from vega_tpu import native
from vega_tpu.partitioner import HashPartitioner, splitmix64

nat = native.get()
pytestmark = pytest.mark.skipif(nat is None, reason="native build unavailable")


def test_hash_parity_with_python():
    """C++ splitmix64 bucketing must be bit-identical to HashPartitioner."""
    keys = np.array([0, 1, -1, 42, 2**40, -(2**40), 7_777_777], dtype=np.int64)
    got = np.frombuffer(nat.hash_i64(keys.tobytes(), 8), dtype=np.int64)
    part = HashPartitioner(8)
    expected = [part.get_partition(int(k)) for k in keys]
    assert got.tolist() == expected


def test_bucket_reduce_matches_python_dict():
    rows = [(i % 97, float(i)) for i in range(10_000)]
    blobs, all_int = nat.bucket_reduce_pairs(rows, 4, native.OP_ADD)
    assert all_int == 0
    merged = dict(nat.merge_encoded([(b, 0) for b in blobs if b], native.OP_ADD))
    expected = {}
    for k, x in rows:
        expected[k] = expected.get(k, 0.0) + x
    assert merged == pytest.approx(expected)
    # bucket placement honors the partitioner
    part = HashPartitioner(4)
    for b, blob in enumerate(blobs):
        for k, _v in nat.decode_pairs(blob, False):
            assert part.get_partition(k) == b


def test_int_value_round_trip():
    blobs, all_int = nat.bucket_reduce_pairs([(5, 2), (5, 3)], 2, native.OP_ADD)
    assert all_int == 1
    merged = nat.merge_encoded([(b, 1) for b in blobs if b], native.OP_ADD)
    assert merged == [(5, 5)]
    assert isinstance(merged[0][1], int)


def test_large_int_values_stay_exact():
    """int64 accumulation: sums beyond 2^53 must not round through double."""
    blobs, all_int = nat.bucket_reduce_pairs([(1, 2**60), (1, 3)], 1, native.OP_ADD)
    assert all_int == 1
    merged = nat.merge_encoded([(b, 1) for b in blobs], native.OP_ADD)
    assert merged == [(1, 2**60 + 3)]


def test_int_overflow_rejects_not_demotes():
    big = 2**62
    # map-side: integer accumulation overflowing int64 rejects the whole
    # call (None) — the caller redoes it on the exact Python path; double
    # demotion would silently round integer results
    assert nat.bucket_reduce_pairs(
        [(1, big), (1, big), (1, big)], 1, native.OP_ADD) is None
    # reduce-side: partials fit int64, the merge overflows -> None too
    blobs, all_int = nat.bucket_reduce_pairs([(1, big)], 1, native.OP_ADD)
    assert all_int == 1
    assert nat.merge_encoded(
        [(blobs[0], 1), (blobs[0], 1)], native.OP_ADD) is None
    # float inputs keep double semantics (no rejection)
    fblobs, f_int = nat.bucket_reduce_pairs(
        [(1, float(big)), (1, float(big))], 1, native.OP_ADD)
    assert f_int == 0
    merged = dict(nat.merge_encoded([(fblobs[0], 0)], native.OP_ADD))
    assert merged[1] == pytest.approx(2.0 * big, rel=1e-12)


def test_sound_monoid_inference():
    """Only exact identities are recognized; look-alikes are not."""
    import operator

    from vega_tpu.rdd.pair import _infer_named_op

    assert _infer_named_op(lambda a, b: a + b) == "add"
    assert _infer_named_op(lambda x, y: x + y) == "add"
    assert _infer_named_op(lambda a, b: a * b) == "prod"
    assert _infer_named_op(operator.add) == "add"
    assert _infer_named_op(min) == "min"
    assert _infer_named_op(max) == "max"
    # agrees with 'add' at any probe points, but is NOT add
    assert _infer_named_op(lambda x, y: min(x + y, 100)) is None
    cap = 100
    assert _infer_named_op(lambda x, y: min(x + y, cap)) is None
    assert _infer_named_op(lambda a, b: a - b) is None


def test_non_numeric_falls_back():
    assert nat.bucket_reduce_pairs([("key", 1)], 2, native.OP_ADD) is None
    assert nat.bucket_reduce_pairs([(1, "value")], 2, native.OP_ADD) is None
    assert nat.bucket_reduce_pairs([(1.5, 2.0)], 2, native.OP_ADD) is None
    assert nat.encode_pairs([object()]) is None


def test_encode_decode_round_trip():
    rows = [(1, 2.5), (-3, 4.0), (2**40, -1.0)]
    blob, is_int = nat.encode_pairs(rows)
    assert is_int == 0
    assert nat.decode_pairs(blob, False) == rows
    # pure-Python decoder agrees (heterogeneous-cluster fallback)
    assert native.decode_pairs_py(blob, False) == rows
    int_rows = [(7, 2**60), (8, -5)]
    blob, is_int = nat.encode_pairs(int_rows)
    assert is_int == 1
    assert nat.decode_pairs(blob, True) == int_rows
    assert native.decode_pairs_py(blob, True) == int_rows


def test_ops():
    for op, expected in ((native.OP_ADD, 7.0), (native.OP_MIN, 3.0),
                         (native.OP_MAX, 4.0), (native.OP_PROD, 12.0)):
        blobs, _ = nat.bucket_reduce_pairs([(1, 3.0), (1, 4.0)], 1, op)
        assert dict(nat.merge_encoded([(b, 0) for b in blobs], op)) == {1: expected}


def test_host_shuffle_native_path_equivalence(ctx):
    """reduce_by_key through the native fast path matches combine_by_key
    through the Python path, including key placement for downstream
    co-partitioned ops."""
    data = [(i % 50, float(i)) for i in range(5_000)]
    fast = ctx.parallelize(data, 4).reduce_by_key(lambda a, b: a + b, 4)
    slow = ctx.parallelize(data, 4).combine_by_key(
        lambda x: x, lambda a, b: a + b, lambda a, b: a + b, 4
    )
    assert dict(fast.collect()) == pytest.approx(dict(slow.collect()))
    # downstream narrow cogroup on the shuffled output still lines up
    joined = dict(fast.join(slow).collect())
    for k, (a, b) in joined.items():
        assert a == pytest.approx(b)


def test_mixed_numeric_and_python_partitions(ctx):
    """Partitions whose rows aren't numeric fall back per-partition; the
    reduce side merges native and pickled buckets together."""
    def make(idx, it):
        # partition 0 yields numpy int64 keys (not exact ints -> python path)
        for k, x in it:
            if idx == 0:
                yield (np.int64(k).item(), x)  # still int after .item()
            else:
                yield (k, x)

    data = [(i % 10, 1) for i in range(1_000)]
    rdd = ctx.parallelize(data, 3).map_partitions_with_index(make)
    result = dict(rdd.reduce_by_key(lambda a, b: a + b, 2).collect())
    assert result == {k: 100 for k in range(10)}


def test_native_group_path_parity(ctx):
    """group_by_key through the native raw-row path matches the pickle path
    and keeps order-insensitive content."""
    data = [(i % 23, float(i)) for i in range(4_000)]
    fast = dict(ctx.parallelize(data, 4).group_by_key(4).collect())
    expected = {}
    for k, x in data:
        expected.setdefault(k, []).append(x)
    assert set(fast) == set(expected)
    for k in expected:
        assert sorted(fast[k]) == sorted(expected[k])
    # non-numeric values use the pickle path transparently
    mixed = dict(
        ctx.parallelize([(1, "a"), (1, "b"), (2, "c")], 2).group_by_key(2).collect()
    )
    assert sorted(mixed[1]) == ["a", "b"]


def test_native_group_path_cogroup(ctx):
    """Cogroup's shuffled parents also ride the native group path."""
    a = ctx.parallelize([(i % 5, i) for i in range(100)], 3)
    b = ctx.parallelize([(i % 5, i * 10) for i in range(50)], 3)
    grouped = dict(a.cogroup(b).collect())
    for k in range(5):
        assert sorted(grouped[k][0]) == [x for x in range(100) if x % 5 == k]
        assert sorted(grouped[k][1]) == [x * 10 for x in range(50) if x % 5 == k]


def test_mixed_value_types_preserve_fidelity(ctx):
    """A partition mixing int and float values must keep per-value types
    (falls back to the pickle path rather than coercing ints to float)."""
    g = dict(ctx.parallelize([(1, 2), (1, 2.5)], 1).group_by_key(1).collect())
    assert 2 in g[1] and 2.5 in g[1]
    assert any(isinstance(x, int) for x in g[1])
    big = 2**60 + 1
    g2 = dict(ctx.parallelize([(1, big), (1, 0.5)], 1).group_by_key(1).collect())
    assert big in g2[1]  # no double rounding
    r = dict(ctx.parallelize([(1, 2), (1, 3), (2, 2.5)], 1)
             .reduce_by_key(lambda a, b: a + b, 1).collect())
    assert r[1] == 5 and isinstance(r[1], int)


def test_int64_overflow_rejects_to_exact_python(ctx):
    """int64 overflow during a native combine must NOT demote to double
    (silent rounding): both the map-side pre-combine and the reduce-side
    merge reject and redo on the exact Python bignum path."""
    big = 2**40
    got = dict(ctx.parallelize([(1, big), (1, big), (1, 8), (2, 5)], 2)
               .reduce_by_key(lambda a, b: a * b, 2).collect())
    assert got == {1: big * big * 8, 2: 5}
    assert all(isinstance(x, int) for x in got.values())
    # sums past int64 (map-side pre-combine overflow on one partition)
    gs = dict(ctx.parallelize([(1, 2**62)] * 3, 1)
              .reduce_by_key(lambda a, b: a + b, 1).collect())
    assert gs == {1: 3 * 2**62} and isinstance(gs[1], int)
    # reduce-side merge overflow: per-partition partials fit int64, the
    # cross-partition merge does not
    gm = dict(ctx.parallelize([(1, 2**62), (1, 2**62)], 2)
              .reduce_by_key(lambda a, b: a + b, 1).collect())
    assert gm == {1: 2**63} and isinstance(gm[1], int)


def test_loader_rebuilds_a_stale_shared_object(tmp_path, monkeypatch):
    """A binary absent, or older than native/vega_native.cpp (copied from
    another machine, left by an older checkout), is rebuilt, not imported.
    Runs on a copy of native/ so the library other processes have open is
    never touched."""
    import shutil

    real = native._native_dir()
    (tmp_path / "vega_tpu").mkdir()
    shutil.copytree(real, tmp_path / "native")
    monkeypatch.setattr(native, "_native_dir",
                        lambda: str(tmp_path / "native"))
    so = native._built_path()
    assert so.startswith(str(tmp_path))
    src = str(tmp_path / "native" / "vega_native.cpp")
    assert native._stale()  # absent
    assert native._try_build() and os.path.isfile(so)
    assert not native._stale()
    old = os.path.getmtime(src) - 60
    os.utime(so, (old, old))
    assert native._stale()  # older than its source: make rebuilds it


def test_loader_builds_before_importing_when_stale(monkeypatch):
    calls = []
    monkeypatch.setattr(native, "_native", None)
    monkeypatch.setattr(native, "_load_attempted", False)
    monkeypatch.setattr(native, "_stale", lambda: True)
    monkeypatch.setattr(native, "_try_build",
                        lambda: calls.append("build") or True)
    assert native.get() is not None
    assert calls == ["build"]


def test_failed_build_is_a_warning_with_the_compilers_words(monkeypatch,
                                                            caplog):
    import subprocess

    def boom(cmd, **kw):
        raise subprocess.CalledProcessError(2, cmd, stderr="error: boom")

    monkeypatch.setattr(subprocess, "run", boom)
    with caplog.at_level("WARNING", logger="vega_tpu"):
        assert native._try_build() is False
    assert any(r.levelname == "WARNING" and "error: boom" in r.getMessage()
               for r in caplog.records)
