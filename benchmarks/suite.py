"""BASELINE.md config matrix: every self-measured baseline config, host
tier vs device tier at IDENTICAL scale with result-parity asserts.

Configs (BASELINE.md "Self-measured baseline plan", reference workloads):
  1. group_by over (i64, f64) pairs            examples/group_by.rs
  2. two-RDD inner join, rows x keys           examples/join.rs
  3. reduce_by_key count over parquet input    examples/parquet_column_read.rs
  4. cogroup + cartesian                       co_grouped_rdd.rs / cartesian_rdd.rs
  5. sort_by_key + take_ordered, i64 keys      rdd.rs take_ordered
  6. cache spill round-trip                    (PR 1 tiered store)
  7. multi-job short-job p50, fifo vs fair     (PR 7 job server; host_s =
     fifo p50, device_s = fair p50 — CPU-only, see config docstring)

Prints ONE JSON line per config:
  {"config": N, "name": ..., "rows": ..., "host_s": ..., "device_s": ...,
   "device_vs_host": ..., "backend": ...}

Device runs are warmed on identical shapes first (program/jit caches make
the measured run compile-free), mirroring bench.py methodology. Scales
default to CPU-feasible sizes; pass --scale to grow them. run_configs
runs in-process: the chip belongs to one process, so a subprocess could not
see the chip its parent already holds.

Usage: python benchmarks/suite.py [--scale S] [--configs 1,2,5]
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BIG = 1 << 40  # pushes keys beyond int32 so the i64 (hi, lo) path is real


def _timed(fn):
    t0 = time.time()
    out = fn()
    return out, time.time() - t0


def _timed_warm(fn):
    """Time fn after ONE extra warm execution: speculative plans (the
    dense-key table reduce) only activate on the run AFTER their key
    range was learned, so a single warmup would leave that plan's
    compile inside the timed run."""
    fn()
    return _timed(fn)


def config1_group_by(ctx, scale, bank=None):
    """group_by over (i64, f64) pairs -> per-key group sizes."""
    n = int(4_000_000 * scale)
    k = max(1000, n // 40)
    keys = BIG + (np.arange(n, dtype=np.int64) * 2654435761 % k)
    vals = np.arange(n, dtype=np.float64) * 0.5

    dev = ctx.dense_from_numpy(keys, vals)
    warm = dev.group_by_key().collect_grouped()
    (gk, offs, _gv), dev_s = _timed_warm(
        lambda: ctx.dense_from_numpy(keys, vals).group_by_key()
        .collect_grouped())
    if bank:
        bank(n, dev_s)
    dev_sizes = dict(zip(np.asarray(gk).tolist(),
                         np.diff(np.asarray(offs)).tolist()))

    host_rdd = ctx.parallelize(list(zip(keys.tolist(), vals.tolist())), 8)
    host_out, host_s = _timed(
        lambda: dict(host_rdd.group_by_key(8).map_values(len).collect()))
    assert host_out == dev_sizes, "config1 host/device group sizes differ"
    return n, host_s, dev_s


def config2_join(ctx, scale, bank=None):
    """Inner join rows x keys (bench.py's join leg, join-only)."""
    n = int(4_000_000 * scale)
    k = max(1000, n // 10)
    lk = np.arange(n, dtype=np.int32) % k
    lv = np.arange(n, dtype=np.float32)
    rk = np.arange(k, dtype=np.int32)
    rv = rk.astype(np.float32) * 2.0

    left = ctx.dense_from_numpy(lk, lv)
    right = ctx.dense_from_numpy(rk, rv)
    warm = left.join(right).count()
    dev_n, dev_s = _timed_warm(
        lambda: ctx.dense_from_numpy(lk, lv)
        .join(ctx.dense_from_numpy(rk, rv)).count())
    if bank:
        bank(n, dev_s)

    hl = ctx.parallelize(list(zip(lk.tolist(), lv.tolist())), 8)
    hr = ctx.parallelize(list(zip(rk.tolist(), rv.tolist())), 8)
    host_n, host_s = _timed(lambda: hl.join(hr, 8).count())
    assert host_n == dev_n == n, (host_n, dev_n, n)
    return n, host_s, dev_s


def _parquet_fixture(scale):
    import pyarrow as pa
    import pyarrow.parquet as pq

    n = int(2_000_000 * scale)
    k = max(1000, n // 40)
    path = f"/tmp/vega_suite_pq_{n}"
    os.makedirs(path, exist_ok=True)
    f = os.path.join(path, "data.parquet")
    if not os.path.exists(f):
        ids = ((np.arange(n, dtype=np.uint64)
                * np.uint64(11400714819323198485)) % np.uint64(k)
               ).astype(np.int32)
        pq.write_table(pa.table({"word_id": ids}), f)
    return path, n


def config3_parquet_count(ctx, scale, bank=None):
    """Word-count (count per id) over a parquet column."""
    path, n = _parquet_fixture(scale)

    def dev_run():
        import pyarrow.parquet as pq
        import glob as g

        # Columnar all the way: arrow -> numpy -> device put. (to_pydict
        # materialized 2M Python ints and dominated the measured leg.)
        col = pq.read_table(g.glob(os.path.join(path, "*.parquet"))[0],
                            columns=["word_id"]).column("word_id")
        rdd = ctx.dense_from_columns(
            {"word_id": col.to_numpy().astype(np.int32, copy=False)},
            key="word_id")
        return dict(rdd.count_by_key_dense().collect())

    warm = dev_run()
    dev_out, dev_s = _timed_warm(dev_run)
    if bank:
        bank(n, dev_s)

    def host_run():
        # parquet_file yields columnar per-row-group dicts; the host word
        # count pivots them to (id, 1) rows, the device path never does.
        blocks = ctx.parquet_file(path, columns=["word_id"])
        pairs = blocks.flat_map(
            lambda blk: [(int(x), 1) for x in blk["word_id"]])
        return dict(pairs.reduce_by_key(lambda a, b: a + b, 8).collect())

    host_out, host_s = _timed(host_run)
    assert host_out == dev_out, "config3 parquet counts differ"
    return n, host_s, dev_s


def config4_cogroup_cartesian(ctx, scale, bank=None):
    """cogroup two pair-RDDs + a cartesian product, counted."""
    n = int(1_000_000 * scale)
    k = max(1000, n // 20)
    ak = np.arange(n, dtype=np.int32) % k
    av = np.arange(n, dtype=np.float32)
    bk = np.arange(n, dtype=np.int32) * 3 % k
    bv = np.arange(n, dtype=np.float32) * 2.0
    m = max(100, int(1500 * scale))  # cartesian side: m*m output rows
    cx = np.arange(m, dtype=np.int32)

    def dev_run():
        a = ctx.dense_from_numpy(ak, av)
        b = ctx.dense_from_numpy(bk, bv)
        groups = a.cogroup(b).count()
        cart = (ctx.dense_from_numpy(cx)
                .cartesian(ctx.dense_from_numpy(cx)).count())
        return groups, cart

    warm = dev_run()
    (dev_groups, dev_cart), dev_s = _timed_warm(dev_run)
    if bank:
        bank(n + m * m, dev_s)

    def host_run():
        a = ctx.parallelize(list(zip(ak.tolist(), av.tolist())), 8)
        b = ctx.parallelize(list(zip(bk.tolist(), bv.tolist())), 8)
        groups = a.cogroup(b, partitioner_or_num=8).count()
        cart = (ctx.parallelize(cx.tolist(), 4)
                .cartesian(ctx.parallelize(cx.tolist(), 4)).count())
        return groups, cart

    (host_groups, host_cart), host_s = _timed(host_run)
    assert (host_groups, host_cart) == (dev_groups, dev_cart)
    return n + m * m, host_s, dev_s


def config5_sort_take(ctx, scale, bank=None):
    """sort_by_key + take_ordered over i64-keyed pairs.

    Both tiers run identical logical ops end to end: the pair sort runs
    the distributed sort kernels; take_ordered(10) on the pair RDD runs
    the device per-shard masked row sort (host: BoundedPriorityQueue over
    tuples) — same tuple ordering, asserted identical."""
    n = int(4_000_000 * scale)
    rng = np.random.default_rng(7)
    keys = rng.integers(-(1 << 45), 1 << 45, size=n, dtype=np.int64)
    vals = rng.standard_normal(n).astype(np.float32)

    def dev_run():
        r = ctx.dense_from_numpy(keys, vals)
        first = r.sort_by_key().take(10)
        top = r.take_ordered(10)
        return first, top

    warm = dev_run()
    (dev_first, dev_top), dev_s = _timed_warm(dev_run)
    if bank:
        bank(n, dev_s)

    def host_run():
        r = ctx.parallelize(list(zip(keys.tolist(), vals.tolist())), 8)
        first = r.sort_by_key(True, 8).take(10)
        top = r.take_ordered(10)
        return first, top

    (host_first, host_top), host_s = _timed(host_run)
    assert [k for k, _ in host_first] == [k for k, _ in dev_first]
    # Selection only, no arithmetic: identical tuples bit for bit.
    assert host_top == dev_top
    return n, host_s, dev_s


def config6_spill_roundtrip(ctx, scale, bank=None):
    """Tiered-store spill leg: a MEMORY_AND_DISK-persisted host RDD ~4x
    the memory cap. "host_s" = cold build (compute + spill), "device_s" =
    warm re-action median of 3 (every memory miss served from the
    DiskStore, ZERO recomputes — asserted), so device_vs_host reads as
    the spilled-read speedup over recompute. Medians of 3 per the
    docs/BENCH_LEG_HISTORY.jsonl convention (single runs on this 1-core
    sandbox carry ~±15% noise)."""
    from vega_tpu.env import Env
    from vega_tpu.store import StorageLevel

    n = max(20_000, int(200_000 * scale))
    computes = []

    def work(x):
        computes.append(None)
        return (x * 2654435761) % 1_000_003

    rdd = ctx.parallelize(range(n), 8).map(work).persist(
        StorageLevel.MEMORY_AND_DISK)
    mem = Env.get().cache.memory
    old_cap = mem._capacity
    # cap at ~1/4 of the dataset's accounted size so most partitions spill
    mem.set_capacity(max(16_384, (n * 28) // 4))
    try:
        exp_sum, cold_s = _timed(lambda: sum(rdd.collect()))
        n_cold = len(computes)
        assert n_cold == n, "cold action must compute every row once"
        status = Env.get().cache.status()
        assert status["spill_count"] > 0, "cap below data size must spill"
        warm = []
        for _ in range(3):
            got, t = _timed(lambda: sum(rdd.collect()))
            assert got == exp_sum
            warm.append(t)
        assert len(computes) == n_cold, \
            "warm actions must be recompute-free (disk hits)"
        warm_s = sorted(warm)[1]
        if bank:
            bank(n, warm_s)
        return n, cold_s, warm_s
    finally:
        mem.set_capacity(old_cap)
        rdd.unpersist()


def config7_multijob_latency(ctx, scale=1.0, bank=None):
    """PR 7 job server: short-job p50 submit->done latency with one long
    batch job saturating the fleet, scheduler_mode=fifo (the reference-
    shaped global-order dispatch) vs fair (weighted pool shares). Reuses
    benchmarks/multijob_ab.py's interleaved solo/fifo/fair legs (medians
    of 3, results asserted identical across legs). Reported through the
    standard columns: host_s = fifo p50, device_s = fair p50, so
    device_vs_host reads as the fair-scheduling latency win. Pure
    sleep-bound scheduling work — no device leg, excluded from
    run_configs' default config set."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from multijob_ab import run_legs

    n_long = max(16, int(64 * scale))
    out = run_legs(ctx, n_long, 6)
    if bank:
        bank(n_long, out["fair_short_p50_s"])
    return n_long, out["fifo_short_p50_s"], out["fair_short_p50_s"]


def config8_shuffle_plan(ctx, scale=1.0, bank=None):
    """PR 8 push-based pre-merged shuffle: 16x16 native-add shuffle over
    4 cross-process workers, shuffle_plan=pull vs push (legs interleaved,
    medians of 3, asserted bit-identical by benchmarks/shuffle_plan_ab.py
    itself). Reported through the standard columns: host_s = pull
    end-to-end wall, device_s = push end-to-end wall, so device_vs_host
    reads as the push-plan win. Host-plane socket work — no device leg,
    excluded from run_configs' default config set."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from shuffle_plan_ab import run_legs

    rows = max(10_000, int(60_000 * scale))
    out = run_legs(rows, 16_384)
    assert out["bit_identical"], "push and pull legs diverged"
    if bank:
        bank(rows * out["mappers"], out["e2e_s"]["push"])
    return rows * out["mappers"], out["e2e_s"]["pull"], out["e2e_s"]["push"]


def config9_locality(ctx, scale=1.0, bank=None):
    """PR 10 locality plane: push-plan shuffle with locality-aware
    placement off vs on over a real 2-executor fleet
    (benchmarks/locality_ab.py: modeled get_merged RTT, phase-paired
    legs so the off leg measures the true placement-blind expectation,
    medians of 3, bit-identical asserted by the A/B itself). Runs in a
    SUBPROCESS: the A/B needs its own distributed Context and the Env is
    a process singleton — the suite's live Context cannot host a second
    fleet. Reported through the standard columns: host_s = locality-off
    e2e, device_s = locality-on e2e, so device_vs_host reads as the
    placement win. Host-plane socket work — no device leg, excluded from
    run_configs' default config set."""
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rows = max(500, int(2000 * scale))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "locality_ab.py"),
         str(rows)],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, f"locality_ab failed: {proc.stderr[-2000:]}"
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["bit_identical"], "locality legs diverged"
    assert out["owned_rtts_zero"], \
        "owner-placed reducers paid get_merged round trips"
    if bank:
        bank(rows * out["mappers"], out["e2e_s"]["on"])
    return rows * out["mappers"], out["e2e_s"]["off"], out["e2e_s"]["on"]


def config10_frame(ctx, scale=1.0, bank=None):
    """PR 11 DataFrame layer: filter->groupBy-sum->join->sort over a
    6-column parquet table (2 relevant columns), DataFrame WITHOUT
    fusion/pushdown vs WITH both (benchmarks/frame_ab.py; legs
    interleaved, medians of 3, all three legs — including a hand-written
    device RDD chain — asserted bit-identical by the A/B itself).
    Reported through the standard columns: host_s = unfused/unpruned
    DataFrame wall, device_s = fused+pushdown wall, so device_vs_host
    reads as the planner's win. Both legs run on the device tier, so
    this DOES belong in a chip run."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from frame_ab import run_legs

    rows = max(100_000, int(1_000_000 * scale))
    out = run_legs(ctx, rows, 4096)
    assert out["bit_identical"], "frame legs diverged"
    if bank:
        bank(rows, out["fused_s"])
    return rows, out["unfused_s"], out["fused_s"]


def config11_elastic(ctx, scale=1.0, bank=None):
    """PR 12 elastic serving plane: bursty short-job stream on a static
    max-size fleet vs an elastic min->max autoscaled fleet
    (benchmarks/elastic_ab.py: interleaved legs, medians of 3, per-job
    counts asserted by the A/B itself). Runs in a SUBPROCESS — the A/B
    spawns its own fresh fleets per leg and the Env is a process
    singleton. Reported through the standard columns: host_s = static
    short-job p50, device_s = elastic short-job p50, so device_vs_host
    reads as the latency COST of elasticity (want ~1.0x or better); the
    real win — executor-seconds — rides the emitted A/B line's
    exec_seconds_vs_static (accept <= 0.7). Host-plane scheduling work —
    no device leg, excluded from run_configs' default config set."""
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    jobs = max(8, int(20 * scale))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "elastic_ab.py"),
         str(jobs)],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, f"elastic_ab failed: {proc.stderr[-2000:]}"
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["results_ok"], "elastic legs returned wrong job results"
    assert out["exec_seconds_bounded"], (
        "elastic fleet burned more than 0.7x the static fleet's "
        f"executor-seconds: {out['executor_seconds']}")
    if bank:
        bank(jobs * out["bursts"], out["short_p50_s"]["elastic"])
    return (jobs * out["bursts"], out["short_p50_s"]["static"],
            out["short_p50_s"]["elastic"])


def config12_exchange_planner(ctx, scale=1.0, bank=None):
    """PR 13 collective-aware exchange planner: a reduce+sort pipeline
    whose one-shot all_to_all footprint busts a deliberately constrained
    dense_hbm_budget, one-shot vs planner-staged
    (benchmarks/exchange_planner_ab.py: interleaved legs, medians of 3,
    bit-identical + est-peak<=budget + streamed-sizing accepts asserted
    by the A/B itself). Runs in a SUBPROCESS — the A/B flips
    process-global dense_exchange/dense_hbm_budget config and the Env is
    a process singleton. Reported through the standard columns: host_s =
    one-shot warm wall, device_s = planned (staged) warm wall, so
    device_vs_host reads as the wall COST of bounding peak HBM (~1x is
    the hope on a real chip; the CPU proxy pays the extra append
    passes). Device-tier work: belongs in a chip run."""
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rows = max(100_000, int(400_000 * scale))
    proc = subprocess.run(
        [sys.executable,
         os.path.join(root, "benchmarks", "exchange_planner_ab.py"),
         str(rows)],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, \
        f"exchange_planner_ab failed: {proc.stderr[-2000:]}"
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    acc = out["accept"]
    assert acc["bit_identical"], "planner legs diverged"
    assert acc["staged_on_device"], \
        "constrained-budget exchange did not run the staged plan on device"
    assert acc["est_peak_le_budget"], \
        "staged plan's estimated peak exceeded the budget"
    assert acc["streamed_exact"], "streamed fold diverged at planner sizing"
    if bank:
        bank(rows, out["warm_s"]["planned"])
    return rows, out["warm_s"]["one_shot"], out["warm_s"]["planned"]


def config13_streaming(ctx, scale=1.0, bank=None):
    """PR 16 micro-batch streaming engine: an unbounded generator stream
    folding exactly-once state while a batch tenant hammers a sibling
    pool — stream alone vs weighted fair pool vs shared FIFO pool
    (benchmarks/streaming_ab.py: interleaved legs, medians of 3,
    exactly-once + bounded queue depth asserted by the A/B itself). Runs
    in a SUBPROCESS — each leg builds a fresh Context with different
    scheduler_mode/pool config and the Env is a process singleton.
    Reported through the standard columns: host_s = solo batch p50,
    device_s = fair-pool batch p50 under the tenant, so device_vs_host
    reads as the latency COST of multi-tenancy behind the fair arbiter
    (accept <= 1.3x; the FIFO contrast rides the emitted A/B line's
    fifo_p50_vs_solo). Host-plane scheduling work — no device leg,
    excluded from run_configs' default config set."""
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    run_s = max(2.0, 4.0 * scale)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(root, "benchmarks", "streaming_ab.py"), str(run_s)],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, f"streaming_ab failed: {proc.stderr[-2000:]}"
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["results_ok"], \
        "streaming legs lost exactly-once (state sum != committed frontier)"
    assert out["queue_bounded"], (
        "rate controller let the block queue past its bound: "
        f"{out['max_queue_depth']} > {out['queue_max_blocks']}")
    batches = out["batches"]["fair"] or 1
    if bank:
        bank(batches, out["batch_p50_s"]["fair"])
    return (batches, out["batch_p50_s"]["solo"], out["batch_p50_s"]["fair"])


def config14_coded(ctx, scale=1.0, bank=None):
    """PR 19 coded shuffle: equal-redundancy A/B — shuffle_replication=2
    (k full copies) vs shuffle_coding=xor k=4 (one compressed parity push
    into an origin-exclusive peer group) with one server SIGKILLed
    mid-reduce on a real 5-worker fleet (benchmarks/straggler_ab.py
    --coded: interleaved legs, medians of 3, bit-identical + zero map
    recompute asserted by the A/B itself). Runs in a SUBPROCESS — each
    (leg, rep) builds a fresh distributed Context and the Env is a
    process singleton. Reported through the standard columns: host_s =
    replica2 wall, device_s = coded wall, so device_vs_host reads as the
    wall COST of parity decode at failure time (accept: coded <= 1.25x
    replica AND <= 0.6x its storage+push bytes — both gates land in the
    emitted A/B line). Host-plane redundancy work — no device leg,
    excluded from run_configs' default config set."""
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    n_tasks = max(8, int(16 * scale))
    rows = max(500, int(2000 * scale))
    proc = subprocess.run(
        [sys.executable,
         os.path.join(root, "benchmarks", "straggler_ab.py"), "--coded",
         str(n_tasks), str(rows)],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, f"coded A/B failed: {proc.stderr[-2000:]}"
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["results_identical"], "coded legs diverged"
    assert out["map_recomputes"] == 0, \
        "a mid-reduce kill escalated to map recompute"
    assert out["bounded_wall_1_25x"], (
        f"coded wall {out['coded_wall_s']} > 1.25x replica "
        f"{out['replica2_wall_s']}")
    assert out["bounded_bytes_0_6x"], (
        f"coded bytes ratio {out['bytes_ratio']} > 0.6x replication=2")
    n = out["map_tasks"] * out["rows_per_map"]
    if bank:
        bank(n, out["coded_wall_s"])
    return (n, out["replica2_wall_s"], out["coded_wall_s"])


def config15_strings(ctx, scale=1.0, bank=None):
    """PR 20 device string columns: string-keyed groupBy-sum -> join ->
    sort over a parquet events table, device dictionary codes vs the
    forced-host object pivot (benchmarks/strings_ab.py run_legs:
    interleaved legs, medians of 3, bit-identical + zero planner
    fallbacks asserted by the A/B itself). Runs IN-PROCESS against the
    suite Context like config 10. Reported through the standard columns:
    host_s = forced-host wall, device_s = dictionary-code wall, so
    device_vs_host reads as the encoding's win (accept >= 1.5x on the
    CPU proxy). Both legs touch the device planner, so this DOES belong
    in a chip run."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from strings_ab import run_legs

    rows = max(50_000, int(300_000 * scale))
    out = run_legs(ctx, rows, 1024)
    assert out["bit_identical"], "string legs diverged"
    assert out["device_fallbacks"] == 0, "device leg silently demoted"
    assert out["accept_1_5x"], (
        f"device leg only {out['device_vs_host']}x the host leg")
    if bank:
        bank(rows, out["device_s"])
    return rows, out["host_s"], out["device_s"]


CONFIGS = {
    1: ("group_by (i64,f64)", config1_group_by),
    2: ("inner join", config2_join),
    3: ("parquet reduce_by_key count", config3_parquet_count),
    4: ("cogroup + cartesian", config4_cogroup_cartesian),
    5: ("sort_by_key + take_ordered i64", config5_sort_take),
    6: ("cache spill round-trip (recompute vs spilled read)",
        config6_spill_roundtrip),
    7: ("multi-job short-job p50, fifo vs fair", config7_multijob_latency),
    8: ("shuffle plan pull vs push e2e (16x16 native add)",
        config8_shuffle_plan),
    9: ("push-plan locality off vs on e2e (modeled get_merged RTT)",
        config9_locality),
    10: ("DataFrame fused+pushdown vs unfused (parquet analytics query)",
         config10_frame),
    11: ("elastic fleet vs static max fleet (bursty short-job p50 + "
         "executor-seconds)", config11_elastic),
    12: ("exchange planner one-shot vs staged under constrained HBM "
         "budget", config12_exchange_planner),
    13: ("micro-batch streaming solo vs fair-pool under batch tenant "
         "(batch p50 + exactly-once + bounded queue)", config13_streaming),
    14: ("coded shuffle equal-redundancy A/B, replication=2 vs xor "
         "parity under mid-reduce server kill", config14_coded),
    15: ("string-keyed groupBy-join-sort, device dictionary codes vs "
         "forced host pivot", config15_strings),
}


def run_configs(ctx, scale=1.0, configs=(1, 2, 3, 4, 5, 6), emit=print):
    """Run the matrix against an existing Context, emitting one JSON line
    per config as it completes — plus a partial "device leg done" line the
    moment each device measurement lands, BEFORE the slow host leg (so a
    caller under a time limit keeps the device number even if the limit
    hits mid-host-leg). Returns the full-config dicts."""
    import jax

    backend = jax.default_backend()
    results = []
    for c in configs:
        name, fn = CONFIGS[c]

        def bank(rows, dev_s, c=c, name=name):
            emit(json.dumps({
                "config": c, "name": name, "stage": "device-only",
                "rows": rows, "device_s": round(dev_s, 3),
                "backend": backend,
            }))

        fetch_before = ctx.metrics_summary().get("fetch", {})
        dispatch_before = ctx.metrics_summary().get("dispatch", {})
        spec_before = ctx.metrics_summary().get("speculation", {})
        rows, host_s, dev_s = fn(ctx, scale, bank)
        rec = {
            "config": c,
            "name": name,
            "rows": rows,
            "host_s": round(host_s, 3),
            "device_s": round(dev_s, 3),
            "device_vs_host": round(host_s / dev_s, 2) if dev_s else None,
            "backend": backend,
            # Per-config shuffle-fetch delta (streams/buckets/round trips/
            # overlap): attributes the pipelined-fetch contribution to each
            # leg instead of one cumulative blob at the end.
            "fetch": _fetch_delta(fetch_before,
                                  ctx.metrics_summary().get("fetch", {})),
            # Task-dispatch delta (same shape-preserving diff): binaries
            # shipped vs cache hits and driver-serialized bytes per leg.
            "dispatch": _fetch_delta(
                dispatch_before, ctx.metrics_summary().get("dispatch", {})),
            # Straggler-plane delta (zeros with speculation off — present
            # so a suite run under the knob attributes duplicate launches
            # and first-wins discards per leg).
            "speculation": _fetch_delta(
                spec_before, ctx.metrics_summary().get("speculation", {})),
        }
        emit(json.dumps(rec))
        results.append(rec)
    return results


def _fetch_delta(before: dict, after: dict) -> dict:
    return {k: (round(after.get(k, 0) - before.get(k, 0), 6)
                if isinstance(after.get(k, 0), float)
                else after.get(k, 0) - before.get(k, 0))
            for k in after}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    # Config 7 (multi-job fifo-vs-fair) runs from the command line but
    # stays out of run_configs' default tuple: its legs are sleep-bound
    # scheduling with no device relevance.
    ap.add_argument("--configs", type=str, default="1,2,3,4,5,6,7")
    args = ap.parse_args()

    import vega_tpu as v

    ctx = v.Context.active() or v.Context("local")
    try:
        run_configs(ctx, args.scale,
                    [int(x) for x in args.configs.split(",")],
                    emit=lambda line: print(line, flush=True))
    finally:
        if v.Context.active() is ctx:
            ctx.stop()


if __name__ == "__main__":
    sys.exit(main())
