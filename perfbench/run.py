"""perfbench/run.py — one run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which touches jax once. It refuses any platform but `tpu`,
makes the cell's data from the seed, feeds it to the device, warms up the
cell's own actions until one mints and compiles nothing, measures a closed
loop of whole actions for `--seconds`, compares a sample of the window's
results with the configuration's plain numpy reference, and prints one JSON
line last. Everything that belongs to one configuration, one traffic mix or
one per-layer metric is a file of its own, found by the name in
BENCHMARK.json: configs/<config>.json + .py, workloads/<cell>.json,
metrics/<metric>.py.

--rehearse runs the same code at a toy size on the CPU and prints no result
line: a number from a CPU never appears under a metric's name.
--control 1 also puts each of the configuration's controls (its reference
in a lower precision, or with a guarantee broken) in the program's place and
says on standard error whether the comparison caught it.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from contextlib import contextmanager  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SCRATCH = os.path.join(ROOT, ".perfbench")  # git-ignored run-time files


def log(msg: str) -> None:
    print(f"[perfbench {time.perf_counter() - T_START:8.3f}s] {msg}",
          file=sys.stderr, flush=True)


def load_module(path: str):
    name = "perfbench_" + re.sub(r"\W", "_", os.path.relpath(path, HERE)[:-3])
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def manifest_cell(workload: str):
    """The cell, its configuration entry and its metrics, from BENCHMARK.json."""
    man = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise SystemExit(f"perfbench: no cell {workload!r} in BENCHMARK.json "
                         f"(it has {sorted(cells)})")
    cell = cells[workload]
    config = next(c for c in man["configs"] if c["name"] == cell["config"])

    def of_cell(metrics):
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]

    return cell, config, of_cell(man["end_to_end"]), of_cell(man["per_layer"])


class Counters:
    """What the program and jax count, read from outside: shard programs
    minted (dense_rdd), compile requests and persistent-cache hits and
    misses (jax.monitoring), dense stage launches and host-tier tasks
    (ctx.metrics_summary)."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self, ctx) -> dict:
        from vega_tpu.tpu import dense_rdd

        summary = ctx.metrics_summary()
        return {"mints": dense_rdd.program_mints(), "compiles": self.compiles,
                "compile_s": self.compile_s, "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "stages": summary["stages"], "tasks": summary["tasks"]}

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        return {k: b[k] - a[k] for k in a}


def run_loop(actions, rows_read, sched, sources, seconds: float, sample,
             span) -> dict:
    """The closed loop: actions back to back, each on a new lineage, until
    `seconds` have passed; the one in flight is finished and counted."""
    from vega_tpu.tpu.dense_rdd import DenseRDD

    walls, rows, failed, attempted = [], 0, 0, 0
    pauses = []  # seconds of each Python garbage collection in the window

    def on_gc(phase, _info):
        if phase == "start":
            pauses.append(-time.perf_counter())
        elif pauses and pauses[-1] < 0:
            pauses[-1] += time.perf_counter()

    gc.callbacks.append(on_gc)
    t_open = t_close = time.perf_counter()
    while t_close - t_open < seconds:
        name = next(sched)
        action = actions[name]
        attempted += 1
        t0 = time.perf_counter()
        try:
            with span("build lineage"):
                nodes = action.build(sources)
            with span("action call"):
                result = action.call(nodes)
            t_close = time.perf_counter()
            if not all(isinstance(n, DenseRDD) for n in nodes.values()):
                raise RuntimeError("the host tier served a node of " + name)
        except Exception as e:  # noqa: BLE001 — a failed action is counted
            t_close = time.perf_counter()
            failed += 1
            log(f"action {attempted - 1} ({name}) failed: {e!r}")
            continue
        walls.append(t_close - t0)
        rows += rows_read[name]
        with span("keep result"):
            sample.offer(attempted - 1, name, result)
            del result, nodes
    gc.callbacks.remove(on_gc)
    return {"walls": walls, "rows": rows, "failed": failed,
            "attempted": attempted, "window_s": t_close - t_open,
            "gc_pauses": [p for p in pauses if p >= 0]}


def warm_up(actions, warm, sources, counters, ctx, mix: dict) -> bool:
    """Run the mix's actions until each has had a turn that minted no program
    and asked jax for no compile. False if `warmup_max` rounds do not get
    there."""
    distinct = len({a["name"] for a in mix["actions"]})
    clean = 0
    for i in range(int(mix["warmup_max"]) * distinct):
        before = counters.snapshot(ctx)
        t = time.perf_counter()
        name = next(warm)
        nodes = actions[name].build(sources)
        result = actions[name].call(nodes)
        del result, nodes
        d = Counters.delta(before, counters.snapshot(ctx))
        log(f"warm-up {i} ({name}): {time.perf_counter() - t:.3f}s  "
            f"mints {d['mints']}  compiles {d['compiles']} "
            f"({d['compile_s']:.1f}s; cache hits {d['cache_hits']}, "
            f"misses {d['cache_misses']})")
        clean = clean + 1 if d["mints"] == 0 and d["compiles"] == 0 else 0
        if clean >= distinct:
            return True
    return False


def compare_sample(actions, sample, data, with_controls: bool):
    """The worst of each number compared over the sampled results, as
    {name: {"value", "limit"}}; and, asked for, what each control failed."""
    compared, caught, refs = {}, {}, {}
    for _index, name, result in sample.items():
        action = actions[name]
        if name not in refs:
            refs[name] = action.reference(data)
        numbers = action.compare(action.answer(result), refs[name])
        for key, (value, limit) in numbers.items():
            if key not in compared or value > compared[key]["value"]:
                compared[key] = {"value": value, "limit": limit}
    if with_controls:
        for name, ref in refs.items():
            for cname, answer in actions[name].controls(data).items():
                caught[cname] = {
                    k: value for k, (value, limit)
                    in actions[name].compare(answer, ref).items() if value > limit}
                log(f"control {cname}: " + (f"not correct {caught[cname]}"
                                            if caught[cname] else "PASSED AS CORRECT"))
    return compared, caught


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell, config, e2e, per_layer = manifest_cell(args.workload)
    chips = cell["chips"]
    cfg = load_json(os.path.join(ROOT, config["file"]))
    cfg_mod = load_module(os.path.join(ROOT, config["file"][:-5] + ".py"))
    mix = load_json(os.path.join(HERE, "workloads", cell["name"] + ".json"))
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    traffic = load_module(os.path.join(HERE, "traffic.py"))
    if (mix["loop"], mix["clients"]) != ("closed", 1):
        raise SystemExit(f"perfbench: {cell['name']} asks for a "
                         f"{mix['loop']!r} loop of {mix['clients']} clients; "
                         "the generator drives one client in a closed loop")

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count={chips}")
    # One fixed cache directory inside the checkout (the path is part of the
    # cache's key), unless whoever started the run placed it; keep every
    # program, however quick its compile, so a second run compiles nothing.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": chips}
    log(f"platform: {device['platform']}  device_kind: {device['kind']}  "
        f"devices: {len(devices)}  cell: {cell['name']}  chips: {chips}")
    if not args.rehearse:
        if device["platform"] != "tpu" or len(devices) < chips:
            print(f"perfbench: {cell['name']} needs {chips} TPU chip(s); jax "
                  f"found {len(devices)} device(s) of platform "
                  f"{device['platform']!r}. No result.", file=sys.stderr)
            return 2
        if device["kind"] not in peaks:
            print(f"perfbench: no peaks for device_kind {device['kind']!r} in "
                  "perfbench/peaks.json. No result.", file=sys.stderr)
            return 2
    sys.path.insert(0, ROOT)
    try:
        import vega_tpu as v
        from vega_tpu.tpu import dense_rdd  # noqa: F401 — imported here, not in the feed's span
        from vega_tpu.tpu import mesh as mesh_lib
    except ImportError as e:
        print(f"perfbench: the system under test is not in this checkout "
              f"({e}). No result.", file=sys.stderr)
        return 3
    import numpy as np

    if len(devices) > chips:
        mesh_lib.set_default_mesh(mesh_lib.make_mesh(chips))
    counters = Counters()

    # ---- set-up: data from the seed, feed, warm-up -------------------------
    size = cfg_mod.sizes(cfg, chips, args.rehearse)
    actions = cfg_mod.actions(cfg)
    in_mix = [a["name"] for a in mix["actions"] for _ in range(int(a["weight"]))]
    t = time.perf_counter()
    data = cfg_mod.make_data(args.seed, cfg, size)
    log(f"data from seed {args.seed}: {size} in {time.perf_counter() - t:.3f}s")

    tracing = False

    @contextmanager
    def span(name: str):
        if tracing:
            with jax.profiler.TraceAnnotation("perfbench:" + name):
                yield
        else:
            yield

    ctx = v.Context("local")
    try:
        # the backend and the transfer path start before the feed's span
        jax.device_put(np.zeros(8, np.float32),
                       mesh_lib.default_mesh().devices.flat[0]).block_until_ready()
        t = time.perf_counter()
        sources = cfg_mod.feed(ctx, data)
        for src in sources.values():
            for col in src.block().cols.values():
                col.block_until_ready()
        feed = {"seconds": time.perf_counter() - t,
                "bytes": cfg_mod.fed_bytes(data)}
        log(f"fed {feed['bytes']} bytes in {feed['seconds']:.3f}s")
        if not warm_up(actions, traffic.schedule(mix, args.seed), sources,
                       counters, ctx, mix):
            print("perfbench: the cell's actions still mint or compile after "
                  f"{mix['warmup_max']} rounds of warm-up. No result.",
                  file=sys.stderr)
            return 4
        gc.collect()

        # ---- the measured window -----------------------------------------
        sample = traffic.Sample(int(mix["compare_sample"]), args.seed)
        seconds = args.seconds
        trace_dir = os.path.join(SCRATCH, "trace")
        if args.trace:
            # whole actions only, and no more than the mix's trace_seconds
            seconds = min(seconds, float(mix["trace_seconds"]))
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing = True
        before = counters.snapshot(ctx)
        setup_s = time.perf_counter() - T_START
        with span("window"):
            loop = run_loop(actions, {n: actions[n].rows_read(size) for n in in_mix},
                            traffic.schedule(mix, args.seed), sources, seconds,
                            sample, span)
        in_window = Counters.delta(before, counters.snapshot(ctx))
        if args.trace:
            tracing = False
            jax.profiler.stop_trace()
        total = counters.snapshot(ctx)
        peak_bytes = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                          for d in devices[:chips]), default=0)
        if in_window["tasks"]:
            log(f"{in_window['tasks']} host-tier tasks ran in the window: "
                "every action counts as failed")
            loop["failed"], loop["rows"], loop["walls"] = loop["attempted"], 0, []
        n_actions = len(loop["walls"])
        log(f"window: {loop['window_s']:.3f}s  actions {n_actions} of "
            f"{loop['attempted']}  failed {loop['failed']}  in-window mints "
            f"{in_window['mints']} compiles {in_window['compiles']}  peak bytes "
            f"{peak_bytes}")
        del sources
    finally:
        ctx.stop()

    # ---- compare, once the window has closed and the state is freed -------
    t = time.perf_counter()
    compared, controls_caught = compare_sample(actions, sample, data,
                                               bool(args.control))
    # nothing is minted or compiled inside the window: the warm-up saw to it
    compared["window_mints"] = {"value": in_window["mints"], "limit": 0}
    compared["window_compiles"] = {"value": in_window["compiles"], "limit": 0}
    correct = n_actions > 0 and len(compared) > 2 and all(
        c["value"] <= c["limit"] for c in compared.values())
    log(f"compared {len(sample.items())} results in {time.perf_counter() - t:.3f}s")

    # ---- metrics ----------------------------------------------------------
    values = {}
    trace = None
    if args.trace:
        tr = load_module(os.path.join(HERE, "trace_reduce.py"))
        # (a rehearsal reads XLA:CPU's worker threads in the device's place)
        where = ("/host:CPU", "tf_XLA") if args.rehearse else ()
        events = tr.read_xplane(tr.newest_xplane(trace_dir), *where)
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace = tr.reduce_events(events)
        obs = {
            "actions": n_actions, "action_walls": loop["walls"],
            "window": in_window, "total": total,
            "feed": feed, "trace": trace, "events": events,
            "peak_hbm_bytes": peak_bytes,
            "peaks": peaks.get(device["kind"]), "chips": chips,
            "least_bytes_per_action": statistics.mean(
                actions[n].least_bytes(size, cfg) for n in in_mix),
        }
        for m in per_layer:
            reader = load_module(os.path.join(
                HERE, "metrics", m["name"] + ".py"))
            value = reader.read(obs)
            if value is not None:
                values[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e_values = {
            "setup_s": setup_s,
            "rows_per_s_chip": loop["rows"] / loop["window_s"] / chips,
            "action_p95_s": (statistics.quantiles(loop["walls"], n=20,
                                                  method="inclusive")[18]
                             if n_actions >= 20 else None),
        }
        for m in e2e:
            if e2e_values.get(m["name"]) is not None:
                values[m["name"]] = {"value": e2e_values[m["name"]],
                                     "unit": m["unit"]}

    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    device["memory_peak_bytes"] = peak_bytes
    line = {"correct": correct, "attempted": loop["attempted"],
            "failed": loop["failed"], "metrics": values, "device": device}
    if trace is not None:
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    # actions of ten times the median or more: a stall shows here as one long
    # action or as many, and not in a percentile
    median_s = statistics.median(loop["walls"]) if n_actions else None
    slow = [w for w in loop["walls"] if w >= 10 * median_s]
    line["run"] = {
        "cell": cell["name"], "seed": args.seed, "seconds": args.seconds,
        "setup_s": setup_s, "window_s": loop["window_s"],
        "actions": n_actions,
        "action_s_median": median_s,
        "action_s_min": min(loop["walls"], default=None),
        "action_s_max": max(loop["walls"], default=None),
        "actions_s_sum": sum(loop["walls"]),
        "slow_actions": len(slow), "slow_actions_s_sum": sum(slow),
        "gc_pauses": len(loop["gc_pauses"]), "gc_s_sum": sum(loop["gc_pauses"]),
        "gc_s_max": max(loop["gc_pauses"], default=0.0),
        "window_mints": in_window["mints"],
        "window_compiles": in_window["compiles"],
        "run_compiles": total["compiles"], "run_compile_s": total["compile_s"],
        "cache_hits": total["cache_hits"], "cache_misses": total["cache_misses"],
        "results_compared": len(sample.items()),
    }
    if args.control:
        line["run"]["controls_caught"] = controls_caught
    line["compared"] = compared
    log("metrics: " + json.dumps(values))
    for key, c in compared.items():
        log(f"compared {key}: {c['value']} (limit {c['limit']})")
    log(f"correct: {correct}")
    if args.rehearse:
        print(f"platform: {device['platform']} — a rehearsal, not a result: "
              f"{n_actions} actions, correct {correct}")
        return 0 if correct else 5
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
