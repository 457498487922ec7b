"""chip_smoke.py — the quickest proof that the device tier starts on the chip.

Drives the main path once through the entry points a user calls, on every
device jax reports (one file is both the 1-chip and the 4-chip run):

  reduce+join   v.Context("local") -> ctx.dense_from_numpy(keys, values)
                -> .reduce_by_key(op="add") -> .join(table) -> collect()
  sort+take     ctx.dense_from_numpy(keys, values) -> .sort_by_key()
                -> collect_arrays(), and .take_ordered(1000)

at 64Mi (int32, float32) rows and 4Mi distinct keys PER CHIP — 512 MiB of
input per chip, made from --seed. Values are small integers in float32, so
sums are exact and every check against the plain numpy reference
(np.bincount / np.argsort, computed outside the timed spans) is equality.
Each phase runs twice; cold and warm walls, the compile-cache directory and
peak device bytes are printed as set-up observations, never as metrics.

Exit status: 0 only when the platform is "tpu" and every check held. Any
exception or mismatch ends the run non-zero; nothing is caught. The last
stdout line of a passing run is
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

--rehearse runs the same code at a toy size on whatever platform jax has
(the CPU mesh in tests): it proves the script's own logic, says
`platform: cpu`, and prints no result line.

One process owns the chip: this script starts only `make -C native clean
all` (which never touches jax) and waits for it, so the native library the
run imports is built here from native/vega_native.cpp.
"""

import argparse
import gc
import json
import os
import subprocess
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROWS_PER_CHIP = 64 << 20
KEYS_PER_CHIP = 4 << 20
TAKE = 1000


def log(msg: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}", flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def make_data(seed: int, n_rows: int, n_keys: int):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, n_keys, n_rows, dtype=np.int32)
    vals = rng.integers(0, 8, n_rows, dtype=np.int32).astype(np.float32)
    tkeys = np.arange(n_keys, dtype=np.int32)
    tvals = rng.integers(0, 1000, n_keys, dtype=np.int32).astype(np.float32)
    return keys, vals, tkeys, tvals


def reference(keys, vals, tvals, n_keys: int) -> dict:
    """Plain numpy semantics of both phases, independent of vega_tpu."""
    sums = np.bincount(keys, weights=vals, minlength=n_keys)  # float64: exact
    present = np.bincount(keys, minlength=n_keys) > 0
    order = np.argsort(keys, kind="stable")
    skeys, svals = keys[order], vals[order]
    # take_ordered orders pairs like host tuples: key, then value
    head = np.flatnonzero(skeys <= skeys[min(TAKE, len(skeys)) - 1])
    head = head[np.lexsort((svals[head], skeys[head]))][:TAKE]
    return {
        "join_k": np.flatnonzero(present).astype(np.int32),
        "join_lv": sums[present].astype(np.float32),
        "join_rv": tvals[present],
        "sort_k": skeys, "sort_v": svals,
        "take": list(zip(skeys[head].tolist(), svals[head].tolist())),
    }


def record_programs(dense_rdd, recorded: list):
    """Wrap dense_rdd._shard_program (as tests/test_tpu_lowering.py does)
    so every program the pipeline builds is remembered with the abstract
    shapes of its first call — enough to re-lower it later without pinning
    device arrays."""
    import jax

    orig = dense_rdd._shard_program

    def wrapping(mesh, fn, in_specs, out_specs):
        prog = orig(mesh, fn, in_specs, out_specs)
        seen = []

        def wrapper(*args):
            if not seen:
                seen.append(True)
                recorded.append((prog, tuple(
                    jax.ShapeDtypeStruct(a.shape, a.dtype,
                                         sharding=getattr(a, "sharding",
                                                          None))
                    for a in args)))
            return prog(*args)

        return wrapper

    dense_rdd._shard_program = wrapping


def run_reduce_join(ctx, data, ref):
    keys, vals, tkeys, tvals = data
    t0 = time.time()
    pairs = ctx.dense_from_numpy(keys, vals)
    table = ctx.dense_from_numpy(tkeys, tvals)
    reduced = pairs.reduce_by_key(op="add")
    joined = reduced.join(table)
    rows = joined.collect()
    wall = time.time() - t0
    got = joined.collect_arrays()  # same memoized block, columnar
    order = np.argsort(got["k"], kind="stable")
    check(np.array_equal(got["k"][order], ref["join_k"]), "join keys differ")
    check(np.array_equal(got["lv"][order], ref["join_lv"]),
          "per-key sums differ from np.bincount")
    check(np.array_equal(got["rv"][order], ref["join_rv"]),
          "joined table values differ")
    check(len(rows) == len(ref["join_k"]), "collect() row count differs")
    for i in np.linspace(0, len(rows) - 1, 1000).astype(np.int64).tolist():
        k, (lv, rv) = rows[i]
        check((k, lv, rv) == (got["k"][i].item(), got["lv"][i].item(),
                              got["rv"][i].item()),
              f"collect() row {i} differs from its block")
    return wall, {"pairs": pairs, "table": table, "reduced": reduced,
                  "joined": joined}


def run_sort_take(ctx, data, ref):
    keys, vals, _tkeys, _tvals = data
    t0 = time.time()
    pairs = ctx.dense_from_numpy(keys, vals)
    srt = pairs.sort_by_key()
    got = srt.collect_arrays()
    top = pairs.take_ordered(TAKE)
    wall = time.time() - t0
    check(np.array_equal(got["k"], ref["sort_k"]),
          "sorted keys differ from np.argsort")
    check(np.array_equal(got["v"], ref["sort_v"]),
          "sorted values differ from a stable np.argsort")
    check(top == ref["take"], "take_ordered differs from the reference")
    return wall, {"pairs": pairs, "sorted": srt}


def check_on_device(nodes: dict, DenseRDD, devices) -> None:
    """Every node is device-tier, and its shards really live on every
    device — not all on device 0."""
    for name, node in nodes.items():
        check(isinstance(node, DenseRDD),
              f"{name} is a {type(node).__name__}, not a DenseRDD: the "
              "host tier served the pipeline")
        for col, arr in node.block().cols.items():
            check(len(arr.sharding.device_set) == len(devices),
                  f"{name}.{col} lives on {len(arr.sharding.device_set)} "
                  f"of {len(devices)} devices")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy size, any platform, prints no result line")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"platform: {device['platform']}  device_kind: {device['kind']}  "
        f"devices: {device['count']}")
    if device["platform"] != "tpu" and not args.rehearse:
        print("chip_smoke: jax found no TPU (platform "
              f"{device['platform']!r}); refusing to run on a fallback",
              file=sys.stderr)
        return 2
    if not args.rehearse:
        # Built from what git commits: never import a binary that rode
        # along from another machine. (A rehearsal leaves the library
        # alone — tests run it beside other processes that have it open.)
        # make never touches jax, so this child does not want the chip.
        subprocess.run(["make", "-C", os.path.join(HERE, "native"),
                        "clean", "all"], check=True, stdout=sys.stderr)

    import vega_tpu as v
    from vega_tpu.tpu import dense_rdd
    from vega_tpu.tpu import mesh as mesh_lib

    mesh = mesh_lib.default_mesh()  # all visible devices, as users get it
    n_dev = mesh.size
    check(n_dev == len(devices), "default mesh does not span every device")
    cache_dir = mesh_lib.ensure_compile_cache()
    log(f"compile cache: {cache_dir}")

    per_rows, per_keys = ((1 << 13, 1 << 9) if args.rehearse
                          else (ROWS_PER_CHIP, KEYS_PER_CHIP))
    n_rows, n_keys = per_rows * n_dev, per_keys * n_dev
    log(f"size: {n_rows} rows, {n_keys} distinct keys "
        f"({per_rows} rows and {per_keys} keys per chip, "
        f"{n_rows * 8 >> 20} MiB of input), seed {args.seed}")
    t0 = time.time()
    data = make_data(args.seed, n_rows, n_keys)
    ref = reference(data[0], data[1], data[3], n_keys)
    log(f"data and numpy reference ready in {time.time() - t0:.1f}s "
        "(host, untimed)")

    recorded: list = []
    record_programs(dense_rdd, recorded)
    walls = {}
    with v.Context("local") as ctx:
        for phase, run in (("reduce_join", run_reduce_join),
                           ("sort_take", run_sort_take)):
            for label in ("cold", "warm"):
                wall, nodes = run(ctx, data, ref)
                walls[f"{phase}_{label}_wall_s"] = round(wall, 3)
                log(f"{phase} {label} wall {wall:.3f}s — results equal "
                    "the numpy reference")
                check_on_device(nodes, dense_rdd.DenseRDD, devices)
                del nodes  # free this pass's blocks before the next feed
                gc.collect()
        summary = ctx.metrics_summary()

    # The device tier served it: dense stages launched, no host task ran.
    plans = summary["exchange_plans"]
    log(f"dense stages: {summary['stages']}  host tasks: "
        f"{summary['tasks']}  exchange_plans: {plans}")
    check(summary["tasks"] == 0, "host-tier tasks ran on the smoke path")
    check(summary["stages"] >= 6, "fewer dense stage launches than the "
          "two phases make (reduce, join, sort — twice)")
    exchange_hlo = []  # per all-to-all program: carries tpu_custom_call?
    if n_dev > 1:
        # reduce, join and sort each plan one exchange per cold pass (a
        # warm reduce may take a plan with no row exchange at all)
        check(plans["all_to_all"] >= 3 and plans["staged"] == 0
              and plans["ring"] == 0 and plans["over_budget"] == 0,
              f"planner left the one-shot all_to_all program: {plans}")
        # The compiled multi-chip programs carry the Mosaic kernels and a
        # real collective (the persistent cache serves these compiles).
        for prog, structs in recorded:
            lowered = prog.lower(*structs)
            if "all_to_all" not in lowered.as_text():
                continue  # not an exchange program: skip its compile
            text = lowered.compile().as_text()
            check("all-to-all" in text, "XLA compiled the all_to_all away")
            exchange_hlo.append("tpu_custom_call" in text)
        log(f"programs with an all-to-all: {len(exchange_hlo)}; carrying "
            f"tpu_custom_call: {sum(exchange_hlo)}")
        check(exchange_hlo, "no compiled program carries an all-to-all")
        if device["platform"] == "tpu":
            check(any(exchange_hlo), "no exchange program "
                  "carries tpu_custom_call: the Pallas kernels were not "
                  "compiled in")

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    log(f"peak_bytes_in_use per device: {peaks}")
    if n_dev > 1 and all(peaks):
        check(max(peaks) < 4 * min(peaks),
              f"device memory peaks are not of one order: {peaks}")
    cache_files = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) \
        else 0
    print(json.dumps({
        "observations": dict(
            walls, rows=n_rows, keys=n_keys, seed=args.seed,
            compile_cache_dir=cache_dir, compile_cache_files=cache_files,
            peak_bytes_in_use=peaks, dense_stages=summary["stages"],
            exchange_plans=plans, programs=len(recorded),
            all_to_all_programs=len(exchange_hlo)),
        "device": device, "claim": None}), flush=True)
    if args.rehearse:
        log(f"rehearsal passed on platform: {device['platform']} — not a "
            "chip result")
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
