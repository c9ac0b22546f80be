"""ISSUE 5: Pallas dataframe kernels vs the jnp hot paths + dispatch audit.

Three sections, written to ``benchmarks/BENCH_KERNELS.json`` (and the
shared ``name,us_per_call,derived`` CSV):

1. **per-kernel timings** — ``hash_partition`` and ``segment_reduce`` across
   sizes on the jnp path and the Pallas path (native on TPU; on this CPU
   container the Pallas path runs ``interpret=True``, which is a
   correctness mode, not a performance mode — the recorded speedup then
   documents *why* ``auto`` dispatch keeps CPU on jnp);
2. **parity** — both kernels asserted bit-identical between the two paths
   on every benchmarked size (integer data, so exactness is unconditional);
3. **dispatch audit** — for a grid of (kernel, rows, dtype, backend
   override), the decision ``registry.resolve`` makes is checked against
   the calibrated ``cost_model.kernel_params`` prediction.
"""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._util import emit, time_fn
from repro.core import cost_model
from repro.kernels import ops, ref, registry

SIZES = [8_192, 65_536, 262_144]
P = 64
NSEG_FRACTION = 16  # segments = rows / 16


def bench_hash_partition(results: dict) -> None:
    pallas_mode = "pallas" if ops.on_tpu() else "interpret"
    for n in SIZES:
        rng = np.random.default_rng(n)
        keys = jnp.asarray(rng.integers(0, 1 << 31, size=(n, 2)).astype(np.uint32))

        f_jnp = jax.jit(lambda k: ref.hash_partition_ref(k, P))
        f_pal = jax.jit(lambda k: ops.hash_partition(k, P, force=pallas_mode))
        t_jnp = time_fn(lambda k: f_jnp(k)[0], keys)
        t_pal = time_fn(lambda k: f_pal(k)[0], keys)

        dj, hj = f_jnp(keys)
        dp, hp = f_pal(keys)
        exact = bool(jnp.array_equal(dj, dp)) and bool(jnp.array_equal(hj, hp))
        assert exact, f"hash_partition parity failed at n={n}"

        speedup = t_jnp / t_pal
        emit(f"kernels/hash_partition_n{n}_jnp", t_jnp, f"per_row={t_jnp / n:.3e}")
        emit(f"kernels/hash_partition_n{n}_{pallas_mode}", t_pal,
             f"speedup_vs_jnp={speedup:.3f}x exact={exact}")
        results["hash_partition"].append(
            {"rows": n, "jnp_s": t_jnp, "pallas_s": t_pal,
             "pallas_mode": pallas_mode, "speedup": speedup, "exact": exact})


def bench_segment_reduce(results: dict) -> None:
    pallas_mode = "pallas" if ops.on_tpu() else "interpret"
    for n in SIZES:
        rng = np.random.default_rng(n + 1)
        nseg = max(n // NSEG_FRACTION, 1)
        vals = jnp.asarray(rng.integers(-1000, 1000, size=(n, 1)).astype(np.int32))
        seg = jnp.asarray(np.sort(rng.integers(0, nseg, n)).astype(np.int32))

        f_jnp = jax.jit(lambda v, s: ref.segment_reduce_ref(v, s, nseg))
        f_pal = jax.jit(lambda v, s: ops.segment_reduce(v, s, nseg,
                                                        force=pallas_mode))
        t_jnp = time_fn(f_jnp, vals, seg)
        t_pal = time_fn(f_pal, vals, seg)

        exact = bool(jnp.array_equal(f_jnp(vals, seg), f_pal(vals, seg)))
        assert exact, f"segment_reduce parity failed at n={n}"

        speedup = t_jnp / t_pal
        emit(f"kernels/segment_reduce_n{n}_jnp", t_jnp, f"per_row={t_jnp / n:.3e}")
        emit(f"kernels/segment_reduce_n{n}_{pallas_mode}", t_pal,
             f"speedup_vs_jnp={speedup:.3f}x exact={exact}")
        results["segment_reduce"].append(
            {"rows": n, "jnp_s": t_jnp, "pallas_s": t_pal,
             "pallas_mode": pallas_mode, "speedup": speedup, "exact": exact})


def audit_dispatch(results: dict) -> None:
    """Check registry decisions against the calibrated model for the full
    (kernel, rows, dtype, override) grid."""
    params = registry.current_params()
    mismatches = 0
    for kernel in registry.KERNEL_OPS:
        thr = params.min_rows[kernel]
        for rows in (1, thr - 1, thr, 16 * thr):
            for dtype in (None, "int32", "float32", "float64"):
                if kernel == "segment_reduce" and dtype is None:
                    continue
                for override in ("auto", "pallas", "jnp"):
                    with registry.use_backend(override):
                        got = registry.resolve(kernel, rows, dtype)
                    supported = params.dtype_supported(kernel, dtype) \
                        if dtype is not None else True
                    if override == "jnp" or not supported:
                        want = "jnp"
                    elif override == "pallas":
                        want = "pallas" if params.native else "interpret"
                    else:
                        want = "pallas" if (params.native and rows >= thr) \
                            else "jnp"
                    ok = got == want
                    mismatches += 0 if ok else 1
                    results["dispatch"].append(
                        {"kernel": kernel, "rows": rows, "dtype": dtype,
                         "override": override, "decision": got,
                         "expected": want, "ok": ok})
    assert mismatches == 0, f"{mismatches} dispatch decisions off-model"
    emit("kernels/dispatch_audit", 0.0,
         f"decisions={len(results['dispatch'])} mismatches={mismatches}")


def main() -> None:
    results: dict = {"jax_backend": jax.default_backend(),
                     "kernel_params": {
                         k: {"min_rows": registry.current_params().min_rows[k],
                             "block": registry.current_params().block[k]}
                         for k in registry.KERNEL_OPS},
                     "hash_partition": [], "segment_reduce": [],
                     "dispatch": []}
    bench_hash_partition(results)
    bench_segment_reduce(results)
    audit_dispatch(results)
    out = os.path.join(os.path.dirname(__file__), "BENCH_KERNELS.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
    emit("kernels/json", 0.0, f"wrote {out}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
