"""Eager vs lazy-optimized execution of a 4-op pipeline (ISSUE 2 tentpole).

Times ``select -> project -> join -> groupby`` over 8 host devices two ways:

- **eager**: today's per-op path — each method plans in isolation (blocking
  row-count syncs), jits one operator, and the groupby re-shuffles the join
  output it was already co-partitioned with;
- **lazy**: one logical plan through the optimizer — predicate/projection
  pushdown shrinks the shuffled bytes, the join->groupby shuffle is elided
  (co-partition reuse), the EP prefix fuses into the join stage, and the
  whole pipeline compiles into a single shard_map program.

A "lazy (plan-only)" variant runs the same plan with only the cost-model
planning pass (no rewrites) to separate whole-pipeline-compilation gains
from optimizer gains. Asserts the acceptance bar (>= 1.2x lazy-optimized
over eager, pushdown + elision visible in ``.explain()``) and writes
``BENCH_FUSION.json`` next to this file.
"""

import json
import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from benchmarks._util import emit, time_fn
from repro.core import DDF, DDFContext

N = 120_000          # rows per side
KEYS = N // 2        # ~2 matches per key


def make_tables(ctx):
    rng = np.random.default_rng(0)
    nw = ctx.nworkers
    cap = 2 * (-(-N // nw))
    L = {"k": rng.integers(0, KEYS, N).astype(np.int32),
         "v": rng.integers(0, 1000, N).astype(np.int32),
         "junk_a": rng.integers(0, 5, N).astype(np.int32),
         "junk_b": rng.integers(0, 5, N).astype(np.int32)}
    R = {"k": rng.integers(0, KEYS, N).astype(np.int32),
         "w": rng.integers(0, 1000, N).astype(np.int32),
         "junk_c": rng.integers(0, 5, N).astype(np.int32),
         "junk_d": rng.integers(0, 5, N).astype(np.int32)}
    return (DDF.from_numpy(L, ctx, capacity=cap),
            DDF.from_numpy(R, ctx, capacity=cap))


def _pred(c):
    return c["v"] % 2 == 0


# Join strategy is pinned to "shuffle" in BOTH modes so the comparison is
# apples-to-apples (and the explain demo shows the shuffle-join -> elided
# groupby co-partition reuse); the cost model still picks num_chunks.

def eager_pipeline(dl, dr):
    s = dl.select(_pred, name="even")
    p = s.project(["k", "v"])
    j, _ = p.join(dr, on=("k",), strategy="shuffle")   # own jit per op
    g, _ = j.groupby(("k",), {"v": ("sum", "count")})  # planner sync + reshuffle
    return g


def lazy_pipeline(dl, dr, level="all"):
    lz = (dl.lazy().select(_pred, name="even")
          .project(["k", "v"])
          .join(dr.lazy(), on=("k",), strategy="shuffle")
          .groupby(("k",), {"v": ("sum", "count")}))
    return lz.collect(level=level)


def main():
    nd = len(jax.devices())
    mesh = jax.make_mesh((nd,), ("data",))
    ctx = DDFContext(mesh=mesh, axes=("data",))
    dl, dr = make_tables(ctx)

    # acceptance: pushdown below the join shuffle + join->groupby elision
    lz = (dl.lazy().select(_pred, name="even").project(["k", "v"])
          .join(dr.lazy(), on=("k",), strategy="shuffle")
          .groupby(("k",), {"v": ("sum", "count")}))
    explain = lz.explain()
    print(explain, flush=True)
    assert explain.index("JOIN") < explain.index("PROJECT"), "no pushdown below join"
    assert "elide_shuffle" in explain, "groupby shuffle not elided"
    assert explain.strip().endswith("shuffles: 1"), "more than one shuffle"

    # correctness: lazy == eager before timing anything
    ref = eager_pipeline(dl, dr).to_numpy()
    got = lazy_pipeline(dl, dr).to_numpy()
    for k in ref:
        assert np.array_equal(ref[k], got[k]), k

    t_eager = time_fn(lambda: eager_pipeline(dl, dr).counts, repeat=5)
    t_lazy = time_fn(lambda: lazy_pipeline(dl, dr).counts, repeat=5)
    t_plan_only = time_fn(lambda: lazy_pipeline(dl, dr, level="plan-only").counts,
                          repeat=5)

    speedup = t_eager / t_lazy
    emit("fusion/eager_4op", t_eager, f"P={nd}")
    emit("fusion/lazy_plan_only_4op", t_plan_only,
         f"P={nd},speedup={t_eager / t_plan_only:.3f}")
    emit("fusion/lazy_optimized_4op", t_lazy, f"P={nd},speedup={speedup:.3f}")

    record = {
        "P": nd,
        "rows_per_side": N,
        "pipeline": "select -> project -> join -> groupby",
        "t_eager_s": t_eager,
        "t_lazy_plan_only_s": t_plan_only,
        "t_lazy_optimized_s": t_lazy,
        "speedup_lazy_over_eager": speedup,
        "speedup_plan_only_over_eager": t_eager / t_plan_only,
        "explain": explain.splitlines(),
    }
    out_path = os.path.join(os.path.dirname(__file__), "BENCH_FUSION.json")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
    assert speedup >= 1.2, f"lazy speedup {speedup:.2f}x below the 1.2x bar"
    print(f"lazy-optimized speedup over eager: {speedup:.2f}x "
          f"(plan-only: {t_eager / t_plan_only:.2f}x)", flush=True)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
