"""The join cell at a tiny size on four CPU host devices, one worker each: a
sound run is correct, and runs with the exchange between chips left out or
half of the rows left out are not. Runs in a child process, which alone can
have four host devices."""

import json
import os
import subprocess
import sys

import bench_cases

CHILD = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src", sys.argv[1] + "/tests/bench"]
import jax
import bench_cases
from repro.core import api, operators
from repro.core.comm import collectives
from repro.core.dataframe import Table

def halve(fn):
    def wrapped(comm, left, *args, **kw):
        return fn(comm, Table(left.columns, left.nvalid // 2), *args, **kw)
    return wrapped

out = {}
for fault in ("sound", "no_exchange", "half_rows"):
    api._OP_CACHE = api._LRUCache()
    saved = collectives._all_to_all, operators.dist_join_shuffle
    if fault == "no_exchange":
        collectives._all_to_all = lambda x, axis: x
    if fault == "half_rows":
        operators.dist_join_shuffle = halve(operators.dist_join_shuffle)
    bench_cases.harness.enable_compile_cache = lambda: None
    r = bench_cases.run_tiny("cylon_paper.join.p1", 3_000_000_019, jax.devices()[:4],
                             workers=4)
    collectives._all_to_all, operators.dist_join_shuffle = saved
    out[fault] = {"correct": r["correct"], "failed": r["failed"], "count": r["device"]["count"],
                  "rows_diff": r["checks"]["rows_diff"]["value"]}
print(json.dumps(out))
"""


def test_four_device_faults():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    p = subprocess.run([sys.executable, "-c", CHILD, bench_cases.ROOT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["sound"] == {"correct": True, "failed": 0, "count": 4, "rows_diff": 0}
    for fault in ("no_exchange", "half_rows"):
        assert out[fault]["correct"] is False and out[fault]["rows_diff"] > 0, fault
