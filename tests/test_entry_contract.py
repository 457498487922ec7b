"""Standing CLAUDE.md contracts, finally guarded by tests:

- __graft_entry__.py's entry()/dryrun_multichip() must keep compiling
  (the driver dry-run-compiles them; a syntax/rename drift used to be
  caught only at driver time, far from the editing session);
- bench.py must keep printing exactly ONE JSON line on stdout — checked
  here on the cheap --dryrun/--help path, which must not import jax (so
  it never takes the chip), and on a forced failure, which must carry an
  "error" field and a non-zero exit status;
- bench.py is ONE process: no subprocess, no banked-result replay;
- chip_smoke.py refuses to run without a TPU, and its --rehearse mode
  proves the script's own logic on the CPU mesh.
"""

import ast
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _source(name: str) -> str:
    with open(os.path.join(ROOT, name), "r", encoding="utf-8") as f:
        return f.read()


def test_graft_entry_compiles_and_keeps_its_surface():
    src = _source("__graft_entry__.py")
    tree = ast.parse(src, filename="__graft_entry__.py")
    compile(tree, "__graft_entry__.py", "exec")  # full bytecode compile
    fns = {n.name: n for n in tree.body if isinstance(n, ast.FunctionDef)}
    assert "entry" in fns, "entry() contract function missing"
    assert "dryrun_multichip" in fns, "dryrun_multichip() missing"
    assert not fns["entry"].args.args, "entry() takes no arguments"
    assert [a.arg for a in fns["dryrun_multichip"].args.args] == \
        ["n_devices"], "dryrun_multichip(n_devices) signature drifted"
    # entry() must RETURN (fn, example_args) — a bare run would make the
    # driver's compile check execute the workload instead of lowering it.
    returns = [n for n in ast.walk(fns["entry"]) if isinstance(n, ast.Return)]
    assert returns, "entry() must return (fn, example_args)"


def test_bench_compiles_via_ast():
    compile(ast.parse(_source("bench.py"), filename="bench.py"),
            "bench.py", "exec")


def _run_bench(flag: str) -> dict:
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"), flag],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, f"bench.py {flag} printed {len(lines)} " \
        f"stdout lines, contract is exactly one: {lines!r}"
    row = json.loads(lines[0])  # must be valid JSON
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in row, f"JSON line missing {key!r}"
    return row


def test_bench_dryrun_prints_exactly_one_json_line():
    row = _run_bench("--dryrun")
    assert "usage" in row["detail"]


def test_bench_help_prints_exactly_one_json_line():
    _run_bench("--help")


def test_bench_dryrun_does_not_import_jax():
    # The cheap path must never touch the backend: initializing jax takes
    # the chip, and one process owns it (CLAUDE.md environment quirks).
    # Guard the guard: walk the statements executed before main() on the
    # --dryrun path — the module body up to the __main__ gate must not
    # import jax (bench imports it inside measure()).
    tree = ast.parse(_source("bench.py"))
    for node in tree.body:
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            names = [a.name for a in node.names]
            assert not any(n == "jax" or n.startswith("jax.")
                           for n in names), \
                "bench.py imports jax at module level — --dryrun would " \
                "take the chip"


def _imports(tree):
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module


def test_bench_is_one_process_with_no_bank_or_replay():
    # bench.py runs the workload once, in the process that owns the chip:
    # it never re-runs itself in a child, probes from a child, or replays
    # a stored result.
    src = _source("bench.py")
    tree = ast.parse(src)
    spawners = {"subprocess", "multiprocessing", "pty"}
    assert not spawners & {m.split(".")[0] for m in _imports(tree)}
    calls = {ast.unparse(n.func) for n in ast.walk(tree)
             if isinstance(n, ast.Call)}
    assert not {c for c in calls if c.startswith(("os.system", "os.exec",
                                                  "os.spawn", "os.fork",
                                                  "os.popen"))}
    names = {n.name for n in ast.walk(tree)
             if isinstance(n, (ast.FunctionDef, ast.ClassDef))} | \
        {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)}
    assert not {n for n in names if "bank" in n.lower()
                or "replay" in n.lower() or "fallback" in n.lower()}


def test_bench_forced_failure_is_one_error_line_and_nonzero():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py")],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "VEGA_BENCH_SCALE": "not-a-number"},
    )
    assert proc.returncode != 0
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, lines
    row = json.loads(lines[0])
    assert "error" in row and row["value"] == 0
    # the line names the device jax actually found
    assert row["platform"] == "cpu" and row["n_devices"] >= 1
    assert "device_kind" in row


def _run_chip_smoke(*flags):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *flags],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


def test_chip_smoke_refuses_to_run_without_a_tpu():
    proc = _run_chip_smoke()
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout  # no result line of any kind
    assert "platform: cpu" in proc.stdout


def test_chip_smoke_rehearsal_runs_the_pipeline_and_says_cpu():
    proc = _run_chip_smoke("--rehearse")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "platform: cpu" in proc.stdout
    assert "equal the numpy reference" in proc.stdout
    assert '"ok"' not in proc.stdout  # a rehearsal is not a chip result
    last = proc.stdout.strip().splitlines()[-1]
    assert "rehearsal passed on platform: cpu" in last


@pytest.mark.parametrize("placed", ["/some/dir", None])
def test_compile_cache_is_the_callers_or_the_checkouts(monkeypatch, placed):
    import jax

    from vega_tpu.tpu import mesh as mesh_lib

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    if placed:  # placed from outside: jax reads the variable itself
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        want, want_updates = placed, []
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(ROOT, ".jax_cache")
        want_updates = [("jax_compilation_cache_dir", want),
                        ("jax_persistent_cache_min_compile_time_secs", 0.5)]
    assert mesh_lib.ensure_compile_cache() == want
    assert updates == want_updates
