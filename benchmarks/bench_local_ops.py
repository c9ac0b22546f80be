"""Paper Table 4: core local operator costs + complexity fits.

Times single-partition sort / join / groupby / unique / select across sizes
on one device and fits the per-row constant gamma used by the cost model
(CostParams.gamma_s_per_row)."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._util import emit, time_fn
from repro.core.dataframe import from_numpy
from repro.core.local_ops import local_groupby, local_join, local_sort, local_unique, select
from repro.data.synthetic import uniform_table


def main():
    sizes = [20_000, 80_000, 320_000]
    gammas = []
    for n in sizes:
        data = uniform_table(n, cardinality=0.9, seed=1)
        t = from_numpy(data)
        t2 = from_numpy(uniform_table(n, cardinality=0.9, seed=2))

        f_sort = jax.jit(lambda t: local_sort(t, ["c0"]).columns["c0"])
        ts = time_fn(f_sort, t)
        emit(f"local/sort_n{n}", ts, f"n_log_n_const={ts / (n * math.log2(n)):.3e}")

        f_join = jax.jit(lambda a, b: local_join(a, b, ["c0"], capacity=4 * n)[0].nvalid)
        tj = time_fn(f_join, t, t2)
        emit(f"local/join_n{n}", tj, f"per_row={tj / n:.3e}")

        f_gb = jax.jit(lambda t: local_groupby(t, ["c0"], {"c1": ("sum",)}).nvalid)
        tg = time_fn(f_gb, t)
        emit(f"local/groupby_n{n}", tg, f"per_row={tg / n:.3e}")

        f_uq = jax.jit(lambda t: local_unique(t, ["c0"]).nvalid)
        tu = time_fn(f_uq, t)
        emit(f"local/unique_n{n}", tu, f"per_row={tu / n:.3e}")

        f_sel = jax.jit(lambda t: select(t, lambda c: c["c1"] > 0).nvalid)
        tsel = time_fn(f_sel, t)
        emit(f"local/select_n{n}", tsel, f"per_row={tsel / n:.3e}")
        gammas.append(tsel / n)

    emit("local/gamma_s_per_row", float(np.median(gammas)),
         f"CostParams calibration gamma={float(np.median(gammas)):.3e}s/row")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
