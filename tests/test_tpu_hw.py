"""Real-TPU hardware test tier.

These run ONLY on the actual chip: `VEGA_TPU_HW_TESTS=1 python -m pytest
tests/test_tpu_hw.py -m tpu` on a machine whose jax finds a TPU (through
the builder's chip tool); under the normal CPU-mesh suite conftest skips
them. They validate exactly the paths whose behavior differs most between
the CPU emulation mesh and hardware: the Pallas kernels compiled by Mosaic
(interpret mode elsewhere), capacity sizing + overflow retry, speculative
settlement + repair, streaming under an HBM budget, and the wide int64
encoding on a device with no native int64.

The mesh is whatever the machine has: tests needing collectives (elision)
self-skip on one chip and run on a multi-chip host.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.tpu


@pytest.fixture(scope="module")
def hw_ctx():
    import jax

    if jax.devices()[0].platform != "tpu":
        pytest.skip("no TPU device")
    import vega_tpu as v

    context = v.Context("local", num_workers=2)
    yield context
    context.stop()


def _reduce_join(ctx, n, n_keys=991):
    # values are small integers in float32: per-key sums stay below 2^24,
    # so float32 accumulation is exact in any order
    kv = ctx.dense_range(n).map(
        lambda x, m=n_keys: (x % m, (x % 1009) * 1.0))
    red = kv.reduce_by_key(op="add")
    table = ctx.dense_from_numpy(np.arange(n_keys, dtype=np.int32),
                                 np.arange(n_keys, dtype=np.float32))
    return red, red.join(table)


def test_hw_parity_reduce_join(hw_ctx):
    """The north-star group_by+join stage computes the exact host answer
    on hardware (the CPU-vs-TPU oracle BASELINE.md requires)."""
    red, j = _reduce_join(hw_ctx, 200_000, 991)
    got = dict(j.collect())
    exp = {}
    for x in range(200_000):
        k = x % 991
        exp[k] = exp.get(k, 0.0) + (x % 1009) * 1.0
    assert set(got) == set(exp)
    for k in exp:
        s, t = got[k]
        assert s == exp[k] and t == float(k)


def test_hw_histogram_sizing_first_try(hw_ctx):
    """Cold exchanges size from the hash histogram and must not need an
    overflow retry on hardware (attempts == 1)."""
    kv = hw_ctx.dense_range(300_000).map(lambda x: (x % 1237, x))
    red = kv.reduce_by_key(op="add")
    assert dict(red.collect())[0] == sum(
        x for x in range(300_000) if x % 1237 == 0)
    assert red._last_attempts == 1


def test_hw_speculation_settles(hw_ctx):
    """Warm rerun defers the blocking (counts, overflow) fetch on the
    real chip; the first host read settles the backlog in one
    transfer with the right answer."""
    red1, j1 = _reduce_join(hw_ctx, 150_000, 991)
    exp = sorted(j1.collect())  # cold: seeds hints
    red2, j2 = _reduce_join(hw_ctx, 150_000, 991)
    blk = j2.block_spec()
    deferred = blk.settle is not None
    got = sorted(j2.collect())  # settles if deferred
    assert got == exp
    assert not hw_ctx.__dict__.get("_dense_pending")
    assert deferred, "warm rerun should have launched speculatively"


def test_hw_failed_speculation_repairs(hw_ctx):
    """A poisoned capacity hint makes the speculative launch overflow on
    hardware; settlement must detect it and repair to the exact answer."""
    red1, j1 = _reduce_join(hw_ctx, 120_000, 991)
    exp = sorted(j1.collect())
    red2, j2 = _reduce_join(hw_ctx, 120_000, 991)
    hw_ctx._dense_capacity_hints[red2._hint_key()] = (128, 128)
    got = sorted(j2.collect())
    assert got == exp
    assert not hw_ctx.__dict__.get("_dense_pending")
    assert hw_ctx._dense_capacity_hints[red2._hint_key()] != (128, 128)


def test_hw_overflow_retry_blocking(hw_ctx):
    """Blocking path: a wrong hinted capacity overflows on device and the
    retry loop recovers with grown capacities (attempts > 1)."""
    hw_ctx.__dict__["_dense_no_defer"] = True
    try:
        kv = hw_ctx.dense_range(100_000).map(lambda x: (x % 4093, x))
        red = kv.reduce_by_key(op="add")
        hw_ctx._dense_capacity_hints[red._hint_key()] = (64, 64)
        got = dict(red.collect())
        assert got[0] == sum(x for x in range(100_000) if x % 4093 == 0)
        assert red._last_attempts > 1
    finally:
        hw_ctx.__dict__.pop("_dense_no_defer", None)


def test_hw_streaming_under_budget(hw_ctx):
    """HBM-budgeted streaming on the real chip: the chunked source folds
    to the exact total without materializing whole."""
    from vega_tpu.env import Env
    from vega_tpu.tpu.stream import StreamedDenseRDD

    old = Env.get().conf.dense_hbm_budget
    Env.get().conf.dense_hbm_budget = 8 << 20  # 8 MiB
    try:
        big = hw_ctx.dense_range(10_000_000)
        assert isinstance(big, StreamedDenseRDD)
        red = big.map(lambda x: (x % 100_003, x)).reduce_by_key(op="add")
        got = dict(red.collect())
        assert got[1] == sum(
            x for x in range(10_000_000) if x % 100_003 == 1)
    finally:
        Env.get().conf.dense_hbm_budget = old


def test_hw_wide_int64(hw_ctx):
    """The wide (hi, lo) int64 encoding on hardware: keyed carry sums,
    keyless folds, order ops, and the overflow flag's exact takeover."""
    keys = np.array([2**40, 2**40, 7, -2**35], dtype=np.int64)
    vals = np.array([2**62, -2**61, 5, 2**35], dtype=np.int64)
    r = hw_ctx.dense_from_numpy(keys, vals)
    got = dict(r.reduce_by_key(op="add").collect())
    assert got == {2**40: 2**62 - 2**61, 7: 5, -2**35: 2**35}
    bare = hw_ctx.dense_from_numpy(vals)
    assert bare.sum() == int(2**62 - 2**61 + 5 + 2**35)
    assert bare.min() == -2**61 and bare.max() == 2**62
    assert bare.take_ordered(2) == sorted(vals.tolist())[:2]
    # exact bignum takeover when partials wrap
    over = hw_ctx.dense_from_numpy(
        np.array([2**62, 2**62, 2**62], dtype=np.int64))
    assert over.sum() == 3 * 2**62


def test_hw_sort_by_key(hw_ctx):
    """Distributed sample sort on hardware (BASELINE config 5 shape)."""
    n = 500_000
    kv = hw_ctx.dense_range(n).map(
        lambda x: ((x * 2654435761) % n, x))
    keys = [k for k, _ in kv.sort_by_key().take(1000)]
    assert keys == sorted(keys)
    assert len(keys) == 1000


def test_hw_elision_zero_collectives(hw_ctx):
    """Shuffle elision over hash-placed inputs (needs a multi-chip mesh:
    single-chip meshes never elide)."""
    from vega_tpu.tpu import mesh as mesh_lib

    if mesh_lib.default_mesh().size < 2:
        pytest.skip("elision needs a mesh of >= 2 devices")
    kv = hw_ctx.dense_range(100_000).map(lambda x: (x % 613, x))
    red1 = kv.reduce_by_key(op="add")
    red1.collect()
    red2 = red1.reduce_by_key(op="add")
    red2.collect()
    assert red2._elided


def _np_hash32(keys: np.ndarray) -> np.ndarray:
    """lowbias32 in numpy — the device-tier bucketing contract."""
    x = keys.astype(np.uint32)
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x7FEB352D)
    x ^= x >> np.uint32(15)
    x *= np.uint32(0x846CA68B)
    x ^= x >> np.uint32(16)
    return x


@pytest.mark.parametrize("n_buckets", [4, 7, 64])
def test_hw_hash_bucket_kernel(hw_ctx, n_buckets):
    """The Pallas hash+bucket kernel (compiled Mosaic, not interpret
    mode) is bit-identical to lowbias32 % n on the real chip — through
    the dispatching entry point the exchange programs call."""
    import jax
    import jax.numpy as jnp

    from vega_tpu.tpu import pallas_kernels as pk

    keys = np.random.RandomState(3).randint(
        -2**31, 2**31 - 1, size=300_001, dtype=np.int64).astype(np.int32)
    got = jax.jit(lambda k: pk.hash_bucket(k, n_buckets))(jnp.asarray(keys))
    exp = (_np_hash32(keys) % np.uint32(n_buckets)).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(got), exp)


@pytest.mark.parametrize("n_bins", [5, 16, 65, 256])
def test_hw_partition_rank_kernel(hw_ctx, n_bins):
    """The Pallas streaming histogram and counting-partition rank kernels
    compute XLA-identical counts and positions on the real chip (compiled
    Mosaic, not interpret mode), at the exchange's bucket range (mesh
    size + 1), the 65-bin gate, and 16 and 256 bins."""
    import jax.numpy as jnp

    from vega_tpu.tpu.pallas_kernels import (digit_hist_pallas,
                                             partition_pos_pallas)

    rng = np.random.RandomState(2)
    bucket = rng.randint(0, n_bins, size=200_003).astype(np.int32)
    counts = np.bincount(bucket, minlength=n_bins)
    starts = (np.cumsum(counts) - counts).astype(np.int32)
    # stable counting-partition positions == inverse of a stable argsort
    exp = np.empty(len(bucket), np.int32)
    exp[np.argsort(bucket, kind="stable")] = np.arange(len(bucket),
                                                       dtype=np.int32)
    hist = digit_hist_pallas(jnp.asarray(bucket), n_bins)
    np.testing.assert_array_equal(np.asarray(hist), counts)
    got = partition_pos_pallas(jnp.asarray(bucket), n_bins,
                               jnp.asarray(starts))
    np.testing.assert_array_equal(np.asarray(got), exp)
