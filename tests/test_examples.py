"""Examples stay runnable (the reference ships examples/ as its de-facto
acceptance suite; these run the fast ones end-to-end as subprocesses)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAST_EXAMPLES = ["make_rdd.py", "subtract.py", "file_read.py",
                 "columnar_analytics.py", "streamed_billion_rows.py",
                 "group_by.py", "join.py", "parquet_column_read.py",
                 "distributed_cluster.py",
                 "frame_analytics.py"]  # all ten ship runnable


@pytest.mark.parametrize("example", FAST_EXAMPLES)
def test_example_runs(example):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    result = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", example)],
        capture_output=True, text=True, timeout=180, env=env, cwd=REPO,
    )
    assert result.returncode == 0, result.stderr[-2000:]
    assert result.stdout.strip()
