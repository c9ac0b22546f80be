"""Paper Figs 10-11 + Table 5: Summit-style strong/weak scaling, measured at
P<=8 host devices and projected to Summit parallelisms (168..10752 cores)
with the calibrated cost model — the same extrapolation the paper's §6.1.1
performs analytically."""

import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from benchmarks._util import emit, time_fn
from repro.core import DDF, DDFContext
from repro.core.cost_model import CostParams, pattern_cost
from repro.core.comm.communicator import FabricProfile
from repro.data.synthetic import uniform_table


def main():
    nd = len(jax.devices())
    # --- weak scaling (Table 5 / Fig 11): fixed rows per worker ------------
    per_worker = 25_000  # scaled-down from the paper's 25M/worker
    throughputs = {}
    for p in (1, 2, 4, 8):
        if p > nd:
            continue
        devs = jax.devices()[:p]
        mesh = jax.sharding.Mesh(np.array(devs), ("data",))
        ctx = DDFContext(mesh=mesh, axes=("data",))
        n = per_worker * p
        cap = 2 * per_worker + 2
        L = DDF.from_numpy(uniform_table(n, 0.9, seed=1), ctx, capacity=cap)
        R = DDF.from_numpy(uniform_table(n, 0.9, seed=2), ctx, capacity=cap)
        t = time_fn(lambda: L.join(R, on=("c0",), strategy="shuffle",
                                   capacity=4 * cap)[0].counts)
        thr = 2 * n / t
        throughputs[p] = thr
        emit(f"table5/weak_P{p}", t, f"tuples_per_s={thr:.0f}")

    # --- strong scaling (Fig 10): fixed total ------------------------------
    total = 160_000
    for p in (1, 2, 4, 8):
        if p > nd:
            continue
        devs = jax.devices()[:p]
        mesh = jax.sharding.Mesh(np.array(devs), ("data",))
        ctx = DDFContext(mesh=mesh, axes=("data",))
        cap = 2 * (total // p + 1)
        L = DDF.from_numpy(uniform_table(total, 0.9, seed=1), ctx, capacity=cap)
        R = DDF.from_numpy(uniform_table(total, 0.9, seed=2), ctx, capacity=cap)
        t = time_fn(lambda: L.join(R, on=("c0",), strategy="shuffle",
                                   capacity=4 * cap)[0].counts)
        emit(f"fig10/strong_P{p}", t, f"rows={total}")

    # --- cost-model projection to Summit parallelisms (Fig 10b trend) -------
    # calibrate gamma from measured P=1 time; IB fabric like Summit
    if 1 in throughputs:
        ib = FabricProfile("ib", alpha_s=2e-6, beta_s_per_byte=1.0 / 5e9)
        params = CostParams(fabric=ib, gamma_s_per_row=2e-8)
        for p in (168, 672, 2688, 10752):
            c = pattern_cost("shuffle_compute", P=p, n_rows=50_000_000 / p * 2,
                             row_bytes=16, cardinality=0.9, core_op="sort_join",
                             params=params)
            emit(f"fig10/projected_P{p}", c["total"],
                 f"comm_frac={c['comm'] / c['total']:.2f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
