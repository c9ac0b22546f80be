"""The four-worker cell, ``cylon_paper_w4.join.p4``, by its own name at a
tiny size on four CPU host devices: a sound run is correct on four
workers, a run whose all-to-all leaves every block where it is is not, and
the shuffle's slot counters read the plan's padding, P x quota over the
capacity, at four workers and 1.0 for the one-worker cell. Runs in a child
process, which alone can have four host devices."""

import json
import os
import subprocess
import sys

import bench_cases

CHILD = r"""
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax
from bench import harness
from repro.core import api
from repro.core.comm import collectives
from repro.core.partition import default_quota
from repro.obs import metrics

harness.enable_compile_cache = lambda: None
ratio = harness.load_module(harness.BENCH / "metrics" / "shuffle.padded_slots_ratio.py")
ROWS = 3000

def run(cell, devices):
    api._OP_CACHE = api._LRUCache()
    metrics.registry().reset()
    config = {**harness.find_cell(cell).config, "rows_per_worker": ROWS}
    r = harness.run(cell, 3_000_000_041, 0.0, False, devices, time.perf_counter(),
                    config=config)
    return {"correct": r["correct"], "count": r["device"]["count"],
            "rows_diff": r["checks"]["rows_diff"]["value"],
            "checks_over": sorted(k for k, c in r["checks"].items()
                                  if c["value"] > c["limit"]),
            "ratio": ratio.read(None)}

out = {"sound": run("cylon_paper_w4.join.p4", jax.devices()[:4]),
       "p1": run("cylon_paper.join.p1", jax.devices()[:1]),
       "quota": default_quota(ROWS, 4)}
saved = collectives._all_to_all
collectives._all_to_all = lambda x, axis: x
out["no_exchange"] = run("cylon_paper_w4.join.p4", jax.devices()[:4])
collectives._all_to_all = saved
print(json.dumps(out))
"""


def test_four_worker_cell_runs_by_its_name():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    p = subprocess.run([sys.executable, "-c", CHILD, bench_cases.ROOT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    quota = out["quota"]
    assert quota == 2 * 3000 // 4 + 8
    assert out["sound"] == {"correct": True, "count": 4, "rows_diff": 0, "checks_over": [],
                            "ratio": 4 * quota / 3000}
    assert out["p1"]["correct"] is True and out["p1"]["ratio"] == 1.0
    bad = out["no_exchange"]
    assert bad["correct"] is False and bad["count"] == 4 and bad["rows_diff"] > 0
