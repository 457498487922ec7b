"""Every cell's rehearsal passes, in a process of its own as the driver
starts it, traced and untraced, and prints no result line. Anywhere but on a
TPU the command itself exits non-zero with no result."""

import os
import subprocess
import sys

import pytest

from conftest import ROOT


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "perfbench/run.py", *args], cwd=ROOT,
                          env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal(manifest, trace):
    for cell in manifest["workloads"]:
        p = _run("--workload", cell["name"], "--seed", "2147483700",
                 "--seconds", "0.5", "--trace", trace, "--rehearse", "--control", "1")
        assert p.returncode == 0, p.stderr[-3000:]
        assert "platform: cpu" in p.stdout and "correct True" in p.stdout
        assert '"metrics"' not in p.stdout and "PASSED AS CORRECT" not in p.stderr
        assert "in-window mints 0 compiles 0" in p.stderr


def test_no_tpu_no_result():
    p = _run("--workload", "agg_join_64m.scan", "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "No result" in p.stderr


def test_alone_in_a_directory_no_result(tmp_path):
    """With only BENCHMARK.json and perfbench/ there is no system to test."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "perfbench/run.py", "--workload",
                        "sort_64m.batch", "--seed", "1", "--seconds", "1",
                        "--trace", "0", "--rehearse"], cwd=tmp_path,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "not in this checkout" in p.stderr
