"""Quickstart: the paper's Fig 2b program on the repro DDF engine.

    df1 = read_csv_dist(...); df2 = read_csv_dist(...)
    df_j = df1.merge(df2); df_s = df_j.sort_values(...); df_s.iloc[:10]

Run:  PYTHONPATH=src python examples/quickstart.py [--devices 8]
"""

import os
import sys

if "--devices" in sys.argv:
    n = sys.argv[sys.argv.index("--devices") + 1]
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core import DDF, DDFContext
from repro.data.synthetic import uniform_table
from repro.expr import col
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh


def main():
    # env = execution environment (paper's `env=env`)
    mesh = make_host_mesh()
    ctx = DDFContext(mesh=mesh, axes=("data",))
    print(f"workers: {ctx.nworkers}")

    # partitioned input (synthetic stands in for read_csv_dist)
    df1 = DDF.from_numpy(uniform_table(50_000, cardinality=0.9, seed=0), ctx)
    df2 = DDF.from_numpy(uniform_table(50_000, cardinality=0.9, seed=1), ctx)

    # join — the planner picks hash-shuffle vs broadcast from the cost model
    df_j, info = df1.join(df2, on=("c0",))
    print(f"join: {df_j.num_rows()} rows "
          f"(overflow={int(np.asarray(info.get('overflow_join', 0)).sum())})")

    # sort (sample-shuffle-compute) then global head(10)
    df_s, _ = df_j.sort_values("c1")
    top = df_s.head(10).to_numpy()
    print("top10 by c1:", top["c1"].tolist())

    # groupby (combine-shuffle-reduce) + global aggregate
    g, _ = df1.groupby(("c0",), {"c1": ("mean", "count")})
    print(f"groups: {g.num_rows()}, global mean(c1) = {float(df1.agg('c1', 'mean')):.1f}")

    # the same filter->join->groupby as ONE lazy plan over expression
    # operators (docs/EXPRESSIONS.md): the optimizer sees the whole
    # pipeline, pushes the predicate below the join shuffle, elides the
    # groupby shuffle (co-partition reuse) and compiles a single shard_map
    # program (docs/LAZY_PLANS.md)
    lz = (df1.lazy().select(col("c1") > 0.25)
          .join(df2.lazy(), on=("c0",), strategy="shuffle")
          .groupby(("c0",), [col("c1").count()]))
    print("lazy plan:")
    print(lz.explain())
    print(f"lazy groups: {lz.collect().num_rows()}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
