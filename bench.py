"""Benchmark: the BASELINE.md north-star workload — group_by + join rows/sec.

Workload (BASELINE.json configs 1+2): N (int32, float32) pairs with K distinct
keys -> reduce_by_key(add) -> inner join against a K-row table. The device
tier runs it as two fused SPMD programs (exchange + segment reduce; exchange +
merge join). The baseline is this framework's own host (pure-Python local
mode) tier running the SAME pipeline at the SAME scale (identical rows, keys,
and results) — the stand-in for the reference's local-mode CPU throughput
(the reference publishes no numbers, BASELINE.md).

One process, one touch of jax, ONE JSON line on stdout: {"metric", "value",
"unit", "vs_baseline", "platform", "device_kind", "n_devices", "detail"}.
The line names the device jax ran on, and the metric is called per-chip only
when that device is a TPU. Anything that fails prints the line with an
"error" field and exits non-zero — no fallback, no replay, no re-run.
"""

import json
import os
import sys
import time

import numpy as np


def device_pipeline(ctx, n_rows: int, n_keys: int):
    kv = ctx.dense_range(n_rows).map(lambda x: (x % n_keys, (x * 0.5)))
    reduced = kv.reduce_by_key(op="add")
    table = ctx.dense_from_numpy(
        np.arange(n_keys, dtype=np.int32),
        np.arange(n_keys, dtype=np.float32) * 2.0,
    )
    joined = reduced.join(table)
    return joined.count()


def host_pipeline(ctx, n_rows: int, n_keys: int, partitions: int = 8):
    kv = ctx.range(n_rows, num_slices=partitions).map(
        lambda x: (x % n_keys, x * 0.5)
    )
    reduced = kv.reduce_by_key(lambda a, b: a + b, partitions)
    table = ctx.parallelize(
        [(int(k), float(k) * 2.0) for k in range(n_keys)], partitions
    )
    return reduced.join(table).count()


def _leg_history_path():
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "docs", "BENCH_LEG_HISTORY.jsonl")


def _leg_history_compare_and_append(detail: dict) -> None:
    """Per-leg, per-round bench accounting (round-4 verdict: the r03->r04
    'improvement' 1.28x->1.48x was the HOST leg regressing 16% while the
    device leg also got slower — the ratio flattered a double regression
    and nothing tracked it). Each completed bench appends a row per leg;
    the most recent prior row at the same backend+scale yields leg deltas
    that go into the result detail, with a LOUD regression marker when
    either leg slowed >5%. Never costs the result line: I/O errors are
    reported on stderr and ignored."""
    try:
        entry = {
            "ts": time.strftime("%Y-%m-%d %H:%M:%S"),
            "backend": detail.get("backend"),
            "rows": detail.get("rows"),
            "device_seconds": detail.get("device_seconds"),
            "host_seconds": detail.get("host_seconds"),
            "host_rows_per_sec": detail.get("host_rows_per_sec"),
        }
        prior = None
        path = _leg_history_path()
        if os.path.exists(path):
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        row = json.loads(line)
                    except ValueError:
                        continue
                    if (row.get("backend") == entry["backend"]
                            and row.get("rows") == entry["rows"]):
                        prior = row  # last matching row wins
        if prior:
            deltas = {}
            for leg in ("device_seconds", "host_seconds"):
                old, new = prior.get(leg), entry.get(leg)
                if old and new:
                    pct = (new - old) / old * 100.0
                    deltas[leg.replace("_seconds", "_delta_pct")] = round(
                        pct, 1)
            if deltas:
                detail["legs_vs_prior"] = dict(deltas,
                                               prior_ts=prior.get("ts"))
                worst = max(deltas.values())
                if worst > 5.0:
                    detail["LEG_REGRESSION"] = (
                        f"a leg slowed {worst:.1f}% vs the prior run at "
                        "this backend+scale — the headline ratio cannot "
                        "be trusted until this is reproduced or "
                        "attributed")
        with open(path, "a") as f:
            f.write(json.dumps(entry) + "\n")
    except OSError as e:
        print(f"[bench] leg history failed (ignored): {e}", file=sys.stderr,
              flush=True)


def _median(reps):
    # Lower-middle on even lengths: a deadline-truncated 2-rep run must
    # not report the SLOWER rep as its "median".
    return sorted(reps)[(len(reps) - 1) // 2]


def measure(where: dict) -> dict:
    """Run the workload on whatever device jax has and return the result
    line; `where` is filled with the device as soon as jax names it. Raises
    on any failure — main() owns the error line."""
    import jax

    import vega_tpu as v

    t_start = time.time()
    budget = float(os.environ.get("VEGA_BENCH_TIMEOUT_S", "900"))
    deadline = t_start + budget
    devices = jax.devices()  # the one backend touch: the chip is ours now
    platform = devices[0].platform
    where.update(platform=platform, device_kind=devices[0].device_kind,
                 n_devices=len(devices))

    def _phase(msg):
        print(f"[bench {time.strftime('%H:%M:%S')}] {msg}",
              file=sys.stderr, flush=True)

    scale = float(os.environ.get("VEGA_BENCH_SCALE", "1.0"))
    n_rows = max(1000, int(20_000_000 * scale))
    n_keys = min(n_rows, max(1000, int(1_000_000 * scale)))

    ctx = v.Context("local")
    try:
        # Warmup on IDENTICAL shapes so program + jit caches make the
        # measured runs compile-free.
        _phase(f"device warmup ({n_rows:,} rows) on {where}")
        if device_pipeline(ctx, n_rows, n_keys) != n_keys:
            raise RuntimeError("device warmup returned a wrong row count")
        # Second warmup: speculative plans (the dense-key table reduce)
        # only activate on the run AFTER their key range was learned, so
        # one warmup would leave that plan's compile inside rep 1.
        if device_pipeline(ctx, n_rows, n_keys) != n_keys:
            raise RuntimeError("device warmup returned a wrong row count")
        # Median of up to 3 measured reps (deadline-guarded): the first
        # rep always completes; later reps only start while >25% of the
        # budget remains.
        dev_reps = []
        for rep in range(3):
            _phase(f"device measured run {rep + 1}")
            t0 = time.time()
            dev_count = device_pipeline(ctx, n_rows, n_keys)
            dev_reps.append(time.time() - t0)
            if dev_count != n_keys:
                raise RuntimeError("device run returned a wrong row count")
            if time.time() > deadline - 0.25 * budget:
                break
        dev_s = _median(dev_reps)
        dev_rows_per_s = n_rows / dev_s
        _phase(f"device done: median {dev_s:.3f}s over {len(dev_reps)}; "
               "host baseline next")

        # Host (CPU local-mode) baseline at the SAME scale as the device
        # run: same rows, same keys, identical results.
        host_reps = []
        for rep in range(3):
            t0 = time.time()
            host_count = host_pipeline(ctx, n_rows, n_keys)
            host_reps.append(time.time() - t0)
            if host_count != n_keys:
                raise RuntimeError("host run returned a wrong row count")
            if time.time() > deadline - 0.25 * budget:
                break
        host_s = _median(host_reps)
        host_rows_per_s = n_rows / host_s
        _phase(f"host done: median {host_s:.3f}s over {len(host_reps)}")

        # HBM-traffic lower bound for the pipeline: each of the n rows
        # (8 B as int32 key + f32 value) is touched by ~6 row-wide passes
        # (hash, multi-key sort r+w, exchange r+w, segment reduce) before
        # the key-bounded join. Real traffic is higher (sort is O(log n)
        # passes). A model figure, not a share of any device's peak.
        detail = {
            "backend": platform,
            "rows": n_rows,
            "keys": n_keys,
            "device_seconds": round(dev_s, 3),
            "host_seconds": round(host_s, 3),
            "host_rows_per_sec": round(host_rows_per_s),
            "hbm_gbps_lower_bound": round(n_rows * 8 * 6 / dev_s / 1e9, 1),
            "device_rep_seconds": [round(t, 3) for t in dev_reps],
            "host_rep_seconds": [round(t, 3) for t in host_reps],
        }
        metrics = ctx.metrics_summary()
        # Exchange planner records (DenseExchangePlanned): launches per
        # chosen collective program, staged round total, the largest
        # per-shard peak estimate, and launches even the ring program
        # could not bound under dense_hbm_budget.
        detail["exchange_plans"] = metrics.get("exchange_plans", {})
        # Tiered-store occupancy + spill/promote counters: attributes any
        # RSS/HBM movement to spill traffic (0 spills == fully resident).
        detail["storage"] = ctx.storage_status()
        # Host-plane counters, all zeros on a local in-process run but
        # always reported so a run under their knobs is attributable:
        # shuffle fetch (streams / buckets / round trips), push plan,
        # task dispatch (binaries shipped vs cache hits), speculation.
        detail["fetch"] = metrics.get("fetch", {})
        detail["shuffle_push"] = metrics.get("shuffle_push", {})
        detail["dispatch"] = metrics.get("dispatch", {})
        detail["speculation"] = metrics.get("speculation", {})
        # Locality plane: placement-tier histogram plus the push-plan
        # read-locality counters.
        detail["locality"] = {
            **metrics.get("locality", {}),
            "local_blob_reads": metrics.get("fetch", {}).get(
                "local_blob_reads", 0),
            "merged_rtts": metrics.get("fetch", {}).get("merged_rtts", 0),
        }
        # Job-server plane: the mode every bench action ran under plus
        # job-level accounting.
        detail["jobs"] = {
            "scheduler_mode": ctx.job_server.scheduler_mode,
            "jobs": metrics.get("jobs", 0),
            "jobs_cancelled": metrics.get("jobs_cancelled", 0),
            "task_failures": metrics.get("task_failures", 0),
        }
        _leg_history_compare_and_append(detail)
        return {
            "metric": _metric_name(platform),
            "value": round(dev_rows_per_s),
            "unit": "rows/sec",
            "vs_baseline": round(dev_rows_per_s / host_rows_per_s, 2),
            **where,
            "detail": detail,
        }
    finally:
        ctx.stop()


def _metric_name(platform: str) -> str:
    """Per-chip only when a chip ran it: a CPU number never goes under the
    chip's metric name."""
    what = ("reduce_by_key(add) + inner join; host tier measured at "
            "identical scale")
    if platform == "tpu":
        return f"group_by+join rows/sec/chip ({what})"
    return f"group_by+join rows/sec on {platform}, NOT a chip number ({what})"


def _failure_line(error: str) -> dict:
    return {"metric": "group_by+join rows/sec (nothing measured)",
            "value": 0, "unit": "rows/sec", "vs_baseline": 0.0,
            "error": error}


def main() -> int:
    where: dict = {}
    try:
        result = measure(where)
    except Exception as e:  # noqa: BLE001 — the boundary that owns the line
        import traceback

        traceback.print_exc()
        print(json.dumps({**_failure_line(f"{type(e).__name__}: {e}"),
                          **where}), flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


def _usage_line() -> int:
    """--help/--dryrun: honor the one-JSON-line contract without running
    the benchmark — and without importing jax, so this path never takes
    the chip. tests/test_entry_contract gates on it."""
    print(json.dumps({
        "metric": "bench dryrun (usage only, nothing measured)",
        "value": 0,
        "unit": "rows/sec",
        "vs_baseline": 0.0,
        "detail": {
            "usage": "python bench.py [--dryrun|--help|-h]",
            "env": {
                "VEGA_BENCH_SCALE": "workload scale, 1.0 = 20M rows / "
                                    "1M keys (default 1.0)",
                "VEGA_BENCH_TIMEOUT_S": "wall budget in seconds; later "
                                        "reps are skipped when it runs "
                                        "low (default 900)",
            },
            "contract": "bench.py prints exactly ONE JSON line on "
                        "stdout; a failure carries an 'error' field and "
                        "a non-zero exit status",
        },
    }))
    return 0


if __name__ == "__main__":
    if any(a in ("--dryrun", "--help", "-h") for a in sys.argv[1:]):
        sys.exit(_usage_line())
    sys.exit(main())
