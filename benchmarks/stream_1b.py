"""BASELINE config 5 at full scale: 1B-row group_by+join on ONE chip.

The source streams through the mesh in HBM-budget-sized chunks
(vega_tpu/tpu/stream.py); reduce_by_key folds per-chunk combiner blocks
into an accumulator bounded by the key count, then joins a resident table.
Prints rows/sec and peak chunk bytes. Run on TPU; CPU works at reduced
scale via argv.

Usage: python benchmarks/stream_1b.py [rows] [n_keys] [chunk_rows]
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# VEGA_STREAM_1B_TPU=1 targets whatever device jax finds (the real chip on
# a TPU machine); anything else forces the 8-device CPU mesh.
if os.environ.get("VEGA_STREAM_1B_TPU") != "1":
    from _cpu_mesh import force_cpu_mesh

    force_cpu_mesh(8)


def main():
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000_000
    n_keys = int(sys.argv[2]) if len(sys.argv) > 2 else 1_000_000
    chunk = int(sys.argv[3]) if len(sys.argv) > 3 else None

    import vega_tpu as v

    ctx = v.Context("local")
    try:
        src = ctx.dense_range(rows, chunk_rows=chunk)
        from vega_tpu.tpu.stream import StreamedDenseRDD

        streamed = isinstance(src, StreamedDenseRDD)
        t0 = time.time()
        reduced = src.map(lambda x: (x % n_keys, x)).reduce_by_key(op="add")
        table = ctx.dense_from_numpy(
            np.arange(n_keys, dtype=np.int32),
            np.arange(n_keys, dtype=np.int32) * 2,
        )
        joined = reduced.join(table)
        count = joined.count()
        dt = time.time() - t0
        assert count == n_keys, f"expected {n_keys} joined rows, got {count}"

        import jax

        # The group_by+join number prints BEFORE the second full pass: a
        # timeout or assert in the take_ordered phase must not lose it.
        head = (f"backend={jax.default_backend()} streamed={streamed} "
                f"chunks={getattr(src, 'n_chunks', 1)} rows={rows} "
                f"keys={n_keys}")
        print(f"{head}: group_by+join {dt:.1f}s "
              f"({rows/dt/1e6:.1f} M rows/s)", flush=True)

        # BASELINE config 5's order statistic at full scale: streamed
        # take_ordered scans chunk by chunk (per-chunk device sort +
        # driver best-n merge) — no resident materialization.
        t1 = time.time()
        smallest = src.take_ordered(10)
        dt_to = time.time() - t1
        assert smallest == list(range(10)), smallest[:3]
        print(f"{head}: take_ordered {dt_to:.1f}s "
              f"({rows/max(dt_to, 1e-9)/1e6:.1f} M rows/s)", flush=True)
    finally:
        ctx.stop()


if __name__ == "__main__":
    sys.exit(main())
