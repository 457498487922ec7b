"""A/B microbench: 3-sort vs 2-sort exchange map side.

The reduce_by_key exchange's map side was restructured (round 2) from
  A) sort-by-key (pre-combine) + counting/argsort group-by-bucket
to
  B) ONE multi-key lax.sort (bucket major, key minor) feeding a presorted
     pre-combine + bincount-only pregrouped exchange.

The collective itself is identical, so this measures the map-side shard
program only — the part the restructuring changes — as plain jit on one
device (the real mesh's per-shard work).

Usage: python benchmarks/exchange_ab.py [rows] [n_keys] [n_shards]
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 4_000_000
    n_keys = int(sys.argv[2]) if len(sys.argv) > 2 else 1_000_000
    n_shards = int(sys.argv[3]) if len(sys.argv) > 3 else 8

    import jax
    import jax.numpy as jnp

    from vega_tpu.tpu import kernels
    from vega_tpu.tpu.block import KEY, VALUE
    from vega_tpu.tpu.pallas_kernels import hash_bucket

    rng = np.random.RandomState(0)
    keys = jnp.asarray(rng.randint(0, n_keys, size=rows, dtype=np.int32))
    vals = jnp.asarray(rng.rand(rows).astype(np.float32))
    count = jnp.int32(rows)

    def variant_a(keys, vals, count):
        """Old map side: pre-combine (sorts by key) + group-by-bucket."""
        cols = {KEY: keys, VALUE: vals}
        cols, c = kernels.segment_reduce_named(cols, count, KEY, "add",
                                               presorted=False)
        bucket = hash_bucket(cols[KEY], n_shards)
        mask = kernels.valid_mask(rows, c)
        bucket = jnp.where(mask, bucket, n_shards)
        grouped, counts_to, starts = kernels._group_by_bucket(
            cols, bucket, n_shards
        )
        return grouped[KEY], grouped[VALUE], counts_to, starts

    def variant_b(keys, vals, count):
        """New map side: one (bucket, key) sort + presorted pre-combine +
        bincount grouping."""
        cols = {KEY: keys, VALUE: vals}
        mask = kernels.valid_mask(rows, count)
        bucket = hash_bucket(keys, n_shards)
        bucket = jnp.where(mask, bucket, n_shards)
        cols, bucket = kernels.bucket_key_sort(cols, bucket, KEY)
        cols, c = kernels.segment_reduce_named(cols, count, KEY, "add",
                                               presorted=True)
        bucket = hash_bucket(cols[KEY], n_shards)
        bucket = jnp.where(kernels.valid_mask(rows, c), bucket, n_shards)
        counts_all = jnp.bincount(bucket, length=n_shards + 1)
        counts_to = counts_all[:n_shards]
        starts = (jnp.cumsum(counts_all) - counts_all)[:n_shards]
        return cols[KEY], cols[VALUE], counts_to, starts

    results = {}
    for name, fn in (("A_3sort", variant_a), ("B_2sort", variant_b)):
        jfn = jax.jit(fn)
        out = jfn(keys, vals, count)  # compile + warm
        jax.block_until_ready(out)
        t0 = time.time()
        n_iter = 5
        for _ in range(n_iter):
            out = jfn(keys, vals, count)
        jax.block_until_ready(out)
        dt = (time.time() - t0) / n_iter
        results[name] = dt
        print(f"{name}: {dt*1e3:.1f} ms  ({rows/dt/1e6:.1f} M rows/s)  "
              f"counts_sum={int(jnp.sum(out[2]))}")

    # Parity: both variants must route identical totals per bucket.
    ca = jax.jit(variant_a)(keys, vals, count)[2]
    cb = jax.jit(variant_b)(keys, vals, count)[2]
    assert jnp.array_equal(ca, cb), "per-bucket counts must match"
    print(f"backend={jax.default_backend()} speedup A/B = "
          f"{results['A_3sort']/results['B_2sort']:.2f}x")


if __name__ == "__main__":
    main()
