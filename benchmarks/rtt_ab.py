"""A/B: speculative settlement's driver-RTT elimination (round-3 work).

A hinted (warm) exchange launches WITHOUT the blocking (counts, overflow)
fetch and settles the whole backlog in ONE transfer at the next genuine
host read. Every blocking fetch is a driver<->device round trip sitting
between otherwise async-pipelined device launches; what a CPU run can
honestly report is the COUNT of blocking device->host transfers per
pipeline run:

  A) cold run (no hints): every exchange pays its sizing histogram fetch
     and its (counts, overflow) fetch
  B) warm rerun (hinted): zero per-exchange fetches; one settlement
     transfer at the terminal read

Prints one JSON line with both counts and the wall times.
Usage: python benchmarks/rtt_ab.py [rows]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# VEGA_RTT_AB_TPU=1 runs on whatever device jax finds (the real chip on
# a TPU machine), where the warm/cold wall-time gap is the measured effect.
_TPU = os.environ.get("VEGA_RTT_AB_TPU") == "1"
if not _TPU:
    from _cpu_mesh import force_cpu_mesh  # noqa: E402

    force_cpu_mesh(8)


def main():
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 2_000_000

    import jax
    import numpy as np

    import vega_tpu as v
    from vega_tpu.tpu import mesh as mesh_lib

    counts = {"n": 0}
    orig = mesh_lib.host_get

    def counting_host_get(tree):
        counts["n"] += 1
        return orig(tree)

    def build(ctx):
        kv = ctx.dense_range(rows).map(lambda x: (x % 10_000, x * 1.0))
        red = kv.reduce_by_key(op="add")
        table = ctx.dense_from_numpy(np.arange(10_000, dtype=np.int32),
                                     np.arange(10_000, dtype=np.float32))
        return red.join(table)

    ctx = v.Context("local")
    try:
        mesh_lib.host_get = counting_host_get
        t0 = time.time()
        n0 = counts["n"]
        j1 = build(ctx)
        cold_rows = j1.count()
        cold_s = time.time() - t0
        cold_fetches = counts["n"] - n0

        t0 = time.time()
        n0 = counts["n"]
        j2 = build(ctx)
        warm_rows = j2.count()
        warm_s = time.time() - t0
        warm_fetches = counts["n"] - n0
        assert warm_rows == cold_rows

        # --- end-to-end settlement on/off (round-4 verdict item 5):
        # the same WARM pipeline with deferral force-disabled — every
        # exchange pays its blocking (counts, overflow) fetch again.
        # Both legs are warm (hints + jit caches hot), so the wall-clock
        # difference isolates what the ~400 lines of settlement
        # machinery actually buy end to end. Median of 3: single runs
        # on the 1-core sandbox are noisy.
        def timed_run(no_defer: bool):
            ctx.__dict__["_dense_no_defer"] = no_defer
            try:
                n0 = counts["n"]
                t0 = time.time()
                j = build(ctx)
                got = j.count()
                dt = time.time() - t0
                assert got == cold_rows
                return dt, counts["n"] - n0
            finally:
                ctx.__dict__["_dense_no_defer"] = False

        on_times, off_times = [], []
        on_fetches = off_fetches = 0
        for _ in range(3):
            dt, off_fetches = timed_run(no_defer=True)
            off_times.append(dt)
            dt, on_fetches = timed_run(no_defer=False)
            on_times.append(dt)
        on_med = sorted(on_times)[1]
        off_med = sorted(off_times)[1]
    finally:
        mesh_lib.host_get = orig
        ctx.stop()

    saved = cold_fetches - warm_fetches
    print(json.dumps({
        "bench": "rtt_ab",
        "rows": rows,
        "cold_fetches": cold_fetches,
        "warm_fetches": warm_fetches,
        "fetches_saved_per_run": saved,
        "cold_s": round(cold_s, 3),
        "warm_s": round(warm_s, 3),
        "settlement_e2e": {
            "warm_median_s_defer_on": round(on_med, 3),
            "warm_median_s_defer_off": round(off_med, 3),
            "fetches_defer_on": on_fetches,
            "fetches_defer_off": off_fetches,
            "runs": 3,
        },
        "backend": jax.devices()[0].platform,
    }))


if __name__ == "__main__":
    main()
