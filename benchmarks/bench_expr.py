"""Expression-compiled operators vs the legacy callable path (ISSUE 4).

Runs a select -> derive -> groupby pipeline over 8 host devices four ways —
{callable, expression} x {eager, lazy-optimized} — asserting all four are
bit-identical before timing anything. Expressions compile to the same XLA
as the callables (the win is analyzability: exact pushdown sets, structural
cache keys, host-compilable scan predicates, no probe), so the acceptance
bar is parity: the expression path must be within 20% of the callable path
in steady state. Also times cold plan-build (callable probe + fingerprint
vs expression validation) and writes ``BENCH_EXPR.json`` next to this file.
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
from repro.expr import col

N = 240_000
KEYS = 64


def make_table(ctx):
    rng = np.random.default_rng(0)
    cap = 2 * (-(-N // ctx.nworkers))
    data = {"k": rng.integers(0, KEYS, N).astype(np.int32),
            "v": rng.integers(0, 1000, N).astype(np.int32),
            "junk_a": rng.integers(0, 5, N).astype(np.int32),
            "junk_b": rng.integers(0, 5, N).astype(np.int32)}
    return DDF.from_numpy(data, ctx, capacity=cap)


def _pred_callable(c):
    return (c["v"] % 3 != 0) & (c["k"] < 48)


_PRED_EXPR = (col("v") % 3).ne(0) & (col("k") < 48)
_DERIVE_EXPR = col("v") * 2 + col("k")


def eager_callable(d):
    s = d.select(_pred_callable, name="bench")
    m = s.map_columns(lambda c: {**c, "d": c["v"] * 2 + c["k"]}, name="derive")
    g, _ = m.groupby(("k",), {"d": ("sum", "count")})
    return g


def eager_expr(d):
    s = d.select(_PRED_EXPR, name="bench")
    m = s.with_column("d", _DERIVE_EXPR)
    g, _ = m.groupby(("k",), [col("d").sum(), col("d").count()])
    return g


def lazy_callable(d):
    return (d.lazy().select(_pred_callable, name="bench")
            .map_columns(lambda c: {**c, "d": c["v"] * 2 + c["k"]},
                         name="derive")
            .groupby(("k",), {"d": ("sum", "count")})).collect()


def lazy_expr(d):
    return (d.lazy().select(_PRED_EXPR, name="bench")
            .with_column("d", _DERIVE_EXPR)
            .groupby(("k",), [col("d").sum(), col("d").count()])).collect()


def main():
    import warnings
    warnings.simplefilter("ignore", DeprecationWarning)
    nd = len(jax.devices())
    mesh = jax.make_mesh((nd,), ("data",))
    ctx = DDFContext(mesh=mesh, axes=("data",))
    d = make_table(ctx)

    # correctness first: all four variants bit-identical
    ref = eager_callable(d).to_numpy()
    variants = {"eager_expr": eager_expr(d).to_numpy(),
                "lazy_callable": lazy_callable(d).to_numpy(),
                "lazy_expr": lazy_expr(d).to_numpy()}
    for vname, got in variants.items():
        assert sorted(ref) == sorted(got), vname
        for k in ref:
            assert np.array_equal(ref[k], got[k]), (vname, k)

    t_eager_call = time_fn(lambda: eager_callable(d).counts, repeat=5)
    t_eager_expr = time_fn(lambda: eager_expr(d).counts, repeat=5)
    t_lazy_call = time_fn(lambda: lazy_callable(d).counts, repeat=5)
    t_lazy_expr = time_fn(lambda: lazy_expr(d).counts, repeat=5)

    # cold build cost: plan construction + validation, no execution
    def build_lazy_expr():
        return (d.lazy().select(_PRED_EXPR)
                .with_column("d", _DERIVE_EXPR)
                .groupby(("k",), [col("d").sum()]).plan)

    def build_lazy_callable():
        return (d.lazy().select(_pred_callable)
                .map_columns(lambda c: {**c, "d": c["v"] * 2 + c["k"]})
                .groupby(("k",), {"d": ("sum",)}).plan)

    t_build_expr = time_fn(build_lazy_expr, repeat=20)
    t_build_call = time_fn(build_lazy_callable, repeat=20)

    emit("expr/eager_callable", t_eager_call, f"P={nd}")
    emit("expr/eager_expr", t_eager_expr,
         f"P={nd},ratio={t_eager_call / t_eager_expr:.3f}")
    emit("expr/lazy_callable", t_lazy_call, f"P={nd}")
    emit("expr/lazy_expr", t_lazy_expr,
         f"P={nd},ratio={t_lazy_call / t_lazy_expr:.3f}")
    emit("expr/build_callable", t_build_call, "probe+fingerprint")
    emit("expr/build_expr", t_build_expr,
         f"ratio={t_build_call / t_build_expr:.3f}")

    record = {
        "P": nd,
        "rows": N,
        "pipeline": "select -> derive column -> groupby",
        "t_eager_callable_s": t_eager_call,
        "t_eager_expr_s": t_eager_expr,
        "t_lazy_callable_s": t_lazy_call,
        "t_lazy_expr_s": t_lazy_expr,
        "t_build_plan_callable_s": t_build_call,
        "t_build_plan_expr_s": t_build_expr,
        "expr_over_callable_eager": t_eager_call / t_eager_expr,
        "expr_over_callable_lazy": t_lazy_call / t_lazy_expr,
        "bit_identical": True,
    }
    out_path = os.path.join(os.path.dirname(__file__), "BENCH_EXPR.json")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
    assert t_lazy_expr <= 1.2 * t_lazy_call, (
        f"expression path {t_lazy_expr:.3f}s regressed >20% vs callable "
        f"{t_lazy_call:.3f}s")
    print(f"expr vs callable: eager {t_eager_call / t_eager_expr:.2f}x, "
          f"lazy {t_lazy_call / t_lazy_expr:.2f}x, "
          f"plan-build {t_build_call / t_build_expr:.2f}x", flush=True)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
