"""``bench/run.py`` refuses to run without a TPU: it exits non-zero and
prints no result line. It never falls back to the CPU."""

import os
import subprocess
import sys

import bench_cases


def test_no_tpu_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, os.path.join(bench_cases.ROOT, "bench", "run.py"),
         "--workload", "cylon_paper.join.p1", "--seed", "3000000001",
         "--seconds", "1", "--trace", "0"],
        cwd=bench_cases.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert '"correct"' not in p.stdout
