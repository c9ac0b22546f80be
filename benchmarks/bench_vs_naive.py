"""Paper Fig 12: our patterns vs state-of-the-art-style baselines.

Stand-ins for the systems the paper compares against (no Dask/Spark here):
- "serial-style"  — gather everything to worker 0, compute locally
  (the pandas-on-driver anti-pattern);
- "modin-style"   — broadcast-join ONLY (paper §5.3.7 notes Modin OOMs on
  same-order relations because of this);
- "cylon-style"   — our cost-model-selected pattern (shuffle-compute /
  combine-shuffle-reduce / sample-shuffle-compute).

Operators: join (shuffle-compute), groupby (combine-shuffle-reduce), sort
(sample-shuffle-compute) — the three the paper benchmarks."""

import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from benchmarks._util import emit, time_fn
from repro.core import DDF, DDFContext
from repro.data.synthetic import uniform_table


def main():
    nd = len(jax.devices())
    mesh = jax.make_mesh((nd,), ("data",))
    ctx = DDFContext(mesh=mesh, axes=("data",))
    n = 100_000
    cap = 2 * (n // nd + 1)
    L = DDF.from_numpy(uniform_table(n, 0.9, seed=1), ctx, capacity=cap)
    R = DDF.from_numpy(uniform_table(n, 0.9, seed=2), ctx, capacity=cap)

    # ---- join ----
    t = time_fn(lambda: L.join(R, on=("c0",), strategy="shuffle",
                               capacity=4 * cap)[0].counts)
    emit("fig12/join_cylon_style", t, f"P={nd}")
    t = time_fn(lambda: L.join(R, on=("c0",), strategy="broadcast",
                               capacity=4 * cap)[0].counts)
    emit("fig12/join_modin_style", t, "broadcast-only (OOM-prone at scale)")
    ln, rn = L.to_numpy(), R.to_numpy()  # gather-to-driver

    def serial_join():
        import collections
        idx = collections.defaultdict(list)
        for i, k in enumerate(rn["c0"]):
            idx[k].append(i)
        return sum(len(idx.get(k, ())) for k in ln["c0"])

    import time as _t
    t0 = _t.perf_counter()
    serial_join()
    emit("fig12/join_serial_style", _t.perf_counter() - t0, "driver-local python")

    # ---- groupby ----
    t = time_fn(lambda: L.groupby(("c0",), {"c1": ("sum",)}, pre_combine=True)[0].counts)
    emit("fig12/groupby_cylon_style", t, "combine-shuffle-reduce")
    t = time_fn(lambda: L.groupby(("c0",), {"c1": ("sum",)}, pre_combine=False)[0].counts)
    emit("fig12/groupby_shuffle_only", t, "no combine (C=0.9 worst case)")

    # ---- sort ----
    t = time_fn(lambda: L.sort_values("c1")[0].counts)
    emit("fig12/sort_cylon_style", t, "sample-shuffle-compute")
    t0 = _t.perf_counter()
    np.sort(ln["c1"])
    emit("fig12/sort_serial_style", _t.perf_counter() - t0, "driver numpy")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
