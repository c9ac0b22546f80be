"""Paper Table 3: communication-collective costs vs the Hockney model.

Measures shuffle (all-to-all), allgather, broadcast, allreduce on tables of
increasing size over 8 host devices, fits T = alpha + n*beta per collective,
and reports the measured-vs-model agreement the paper's cost model predicts
(T_startup + T_transfer structure)."""

import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._util import emit, time_fn
from repro.core import DDF, DDFContext
from repro.core.cost_model import CostParams, t_allreduce, t_shuffle, t_allgather
from repro.data.synthetic import uniform_table


def main():
    nd = len(jax.devices())
    mesh = jax.make_mesh((nd,), ("data",))
    ctx = DDFContext(mesh=mesh, axes=("data",))
    P = nd

    sizes = [10_000, 40_000, 160_000]
    results = {}
    for n in sizes:
        data = uniform_table(n, cardinality=0.9)
        d = DDF.from_numpy(data, ctx, capacity=2 * (n // P + 1))
        row_bytes = 8.0  # two int32 columns

        # shuffle: hash-partition + all_to_all (isolate comm via unique's
        # shuffle with pre_combine disabled and near-trivial local op)
        t_sh = time_fn(lambda d=d: d.unique(("c0",), capacity=d.capacity)[0].counts)
        # allgather (broadcast-join path gathers the small side)
        t_ag = time_fn(lambda d=d: d.join(d, on=("c0",), strategy="broadcast",
                                          capacity=4 * d.capacity)[0].counts)
        # allreduce (column agg)
        t_ar = time_fn(lambda d=d: d.agg("c1", "sum"))
        results[n] = (t_sh, t_ag, t_ar)
        emit(f"comm/shuffle_n{n}", t_sh, f"P={P}")
        emit(f"comm/allgather_n{n}", t_ag, f"P={P}")
        emit(f"comm/allreduce_n{n}", t_ar, f"P={P}")

    # Hockney fit on the shuffle: T(n) = a + b*n  (least squares over sizes)
    ns = np.array(sizes, float)
    ts = np.array([results[n][0] for n in sizes])
    A = np.vstack([np.ones_like(ns), ns]).T
    (alpha, beta), *_ = np.linalg.lstsq(A, ts, rcond=None)
    emit("comm/hockney_alpha", max(alpha, 0.0), "fitted startup s")
    emit("comm/hockney_beta_per_row", max(beta, 0.0), "fitted s/row")
    # model agreement: predicted ratio T(160k)/T(10k) vs measured
    p = CostParams()
    pred = sum(t_shuffle(P, 160_000 / P * 8, p)) / sum(t_shuffle(P, 10_000 / P * 8, p))
    meas = ts[-1] / ts[0]
    emit("comm/shuffle_scaling_ratio", 0.0, f"model={pred:.2f},measured={meas:.2f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
