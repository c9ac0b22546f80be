"""Terasort-style distributed sort (Sample-Shuffle-Compute at its purest),
with pivots, partition sizes, and the cost-model's predicted vs measured
scaling printed.

Run:  PYTHONPATH=src python examples/distributed_sort.py --devices 8
"""

import os
import sys
import time

if "--devices" in sys.argv:
    n = sys.argv[sys.argv.index("--devices") + 1]
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core import DDF, DDFContext
from repro.core.cost_model import CostParams, pattern_cost
from repro.data.synthetic import uniform_table, zipf_table
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh


def main():
    mesh = make_host_mesh()
    ctx = DDFContext(mesh=mesh, axes=("data",))
    P = ctx.nworkers
    n = 200_000

    for name, data in (("uniform", uniform_table(n, 0.99, seed=3)),
                       ("zipf-skewed", zipf_table(n, a=1.3, seed=3))):
        d = DDF.from_numpy(data, ctx, capacity=4 * (n // P + 1))
        t0 = time.time()
        s, info = d.sort_values("c0")
        out = s.to_numpy()["c0"]
        dt = time.time() - t0
        assert np.array_equal(out, np.sort(data["c0"])), "sort mismatch!"
        counts = np.asarray(s.counts)
        skew = counts.max() / max(counts.mean(), 1)
        print(f"{name:12s}: {n} rows sorted in {dt:.2f}s on P={P}; "
              f"partition skew={skew:.2f} "
              f"(overflow={int(np.asarray(info['overflow_shuffle']).sum())})")

    est = pattern_cost("sample_shuffle_compute", P=P, n_rows=n / P, row_bytes=8,
                       params=CostParams())
    print(f"cost model estimate (host fabric): {est['total'] * 1e3:.2f} ms "
          f"[core={est['core'] * 1e3:.2f} aux={est['aux'] * 1e3:.2f} "
          f"comm={est['comm'] * 1e3:.2f}]")


if __name__ == "__main__":
    enable_compile_cache()
    main()
