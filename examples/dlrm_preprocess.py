"""DLRM-style preprocessing pipeline (the paper's motivating workload class:
TPC/DLRM preprocessing dominated by join/groupby — §6.3).

clicks x users join -> per-user aggregates -> quality filter -> rebalance,
each stage one of the paper's parallel patterns, with the planner choosing
strategies from sampled statistics.

Run:  PYTHONPATH=src python examples/dlrm_preprocess.py [--devices 8]
"""

import os
import sys

if "--devices" in sys.argv:
    n = sys.argv[sys.argv.index("--devices") + 1]
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core import DDF, DDFContext
from repro.core.patterns import sampled_cardinality
from repro.expr import col
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh


def main():
    mesh = make_host_mesh()
    ctx = DDFContext(mesh=mesh, axes=("data",))
    rng = np.random.default_rng(0)

    n_clicks, n_users = 80_000, 2_000
    clicks = {
        "user_id": rng.integers(0, n_users, n_clicks).astype(np.int32),
        "item_id": rng.integers(0, 10_000, n_clicks).astype(np.int32),
        "dwell_ms": rng.integers(10, 60_000, n_clicks).astype(np.int32),
    }
    users = {
        "user_id": np.arange(n_users, dtype=np.int32),
        "region": rng.integers(0, 40, n_users).astype(np.int32),
    }
    dclicks = DDF.from_numpy(clicks, ctx, capacity=2 * (n_clicks // ctx.nworkers + 1))
    dusers = DDF.from_numpy(users, ctx, capacity=2 * (n_users // ctx.nworkers + 1))

    # 1. enrich clicks with user features — users is small, so the cost
    #    model picks BROADCAST join (paper §5.3.7)
    joined, info = dclicks.join(dusers, on=("user_id",))
    print(f"join -> {joined.num_rows()} rows")

    # 2. per-user engagement aggregates — cardinality ~ n_users/n_clicks is
    #    low, so Combine-Shuffle-Reduce wins (paper §5.4.1)
    C = sampled_cardinality(clicks["user_id"][:5000])
    agg, _ = joined.groupby(("user_id",), {"dwell_ms": ("sum", "count", "mean")},
                            cardinality_hint=C)
    print(f"groupby (C-hat={C:.3f}, pre_combine={C < 0.5}) -> {agg.num_rows()} users")

    # 3. embarrassingly-parallel filter + 4. rebalance (partitioned I/O)
    active = agg.select(col("dwell_ms_count") >= 20, name="active")
    balanced, _ = active.rebalance()
    counts = np.asarray(balanced.counts)
    print(f"filter -> {active.num_rows()} active users; "
          f"rebalanced partitions: max-min={counts.max() - counts.min()}")

    # 5. global stats (Globally-Reduce)
    print(f"mean dwell over active users: {float(balanced.agg('dwell_ms_mean', 'mean')):.0f} ms")


if __name__ == "__main__":
    enable_compile_cache()
    main()
