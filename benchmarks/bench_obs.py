"""Observability overhead + cost-model accounting benchmark (ISSUE 8).

Runs the standard 4-op pipeline (select -> project -> shuffle join ->
groupby) on 8 host devices two ways — tracing disabled vs tracing
enabled — and asserts:

- results are **bit-identical** (observability never changes answers);
- the traced median is within **3%** of the untraced median (the
  acceptance bound; warm caches, so the comparison isolates span/record
  overhead rather than compile time);
- the per-pattern ``model_report`` for the pipeline is populated, and a
  traced streaming scan -> groupby adds ``partitioned_io`` coverage.

Also measures the disabled-mode null-span cost (the price every engine
call site pays when tracing is off — nanoseconds, by design). Writes
``BENCH_OBS.json`` next to this file.
"""

import json
import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from benchmarks._util import emit
from repro import obs, stream
from repro.core import DDF, DDFContext
from repro.data.dataset import write_dataset
from repro.expr import col
from repro.obs import trace

N_LEFT = 200_000
N_RIGHT = 50_000
KEYS = 20_000
REPEAT = 15
N_DISK = 64_000
N_BATCHES = 8


def four_op(dl, dr):
    return (dl.lazy()
            .select((col("v") % 2).eq(0))
            .project(["k", "v"])
            .join(dr.lazy(), on=("k",), strategy="shuffle",
                  capacity=4 * (-(-N_LEFT // 8)))
            .groupby(("k",), {"v": ("sum", "count")}))


def one_collect(lz):
    t0 = time.perf_counter()
    out = lz.collect()
    jax.block_until_ready(out.counts)
    return time.perf_counter() - t0, out


def main():
    nd = len(jax.devices())
    mesh = jax.make_mesh((nd,), ("data",))
    ctx = DDFContext(mesh=mesh, axes=("data",))
    rng = np.random.default_rng(0)

    dl = DDF.from_numpy(
        {"k": rng.integers(0, KEYS, N_LEFT).astype(np.int32),
         "v": rng.integers(0, 1000, N_LEFT).astype(np.int32),
         "pad": rng.random(N_LEFT).astype(np.float32)},
        ctx, capacity=2 * (-(-N_LEFT // nd)))
    dr = DDF.from_numpy(
        {"k": rng.integers(0, KEYS, N_RIGHT).astype(np.int32),
         "w": rng.integers(0, 50, N_RIGHT).astype(np.int32)},
        ctx, capacity=2 * (-(-N_RIGHT // nd)))
    lz = four_op(dl, dr)

    # warm both modes once: compiles + first-dispatch costs amortize out of
    # the overhead comparison (first traced dispatch would otherwise charge
    # compile time to "observed" wall)
    one_collect(lz)
    with trace.tracing():
        one_collect(lz)

    # interleave the two modes so clock drift (thermal, page cache) cancels
    # instead of biasing whichever mode runs second
    us, ts = [], []
    for _ in range(REPEAT):
        u, ref = one_collect(lz)
        us.append(u)
        with trace.tracing():
            t, got = one_collect(lz)
        ts.append(t)
    untraced_s, traced_s = float(np.median(us)), float(np.median(ts))
    overhead = traced_s / untraced_s - 1.0

    # bit-identity: tracing must never change the answer
    rn, gn = ref.to_numpy(), got.to_numpy()
    bit_identical = all(np.array_equal(rn[k], gn[k]) for k in rn)
    assert bit_identical, "traced collect diverged from untraced collect"

    # per-pattern model accounting for one profiled run of the pipeline
    with obs.profiled() as prof:
        out = lz.collect()
        jax.block_until_ready(out.counts)
    pipeline_report = prof.report()["model"]

    # streaming scan -> groupby for partitioned_io (decode-side) coverage
    tmp = tempfile.mkdtemp(prefix="repro-bench-obs-")
    man = write_dataset(
        {"k": rng.integers(0, KEYS, N_DISK).astype(np.int32),
         "v": rng.integers(0, 1000, N_DISK).astype(np.int32)},
        tmp, chunk_rows=(N_DISK // N_BATCHES) // 2)
    q = stream.scan_dataset(man, ctx, batch_rows=N_DISK // N_BATCHES) \
        .groupby(("k",), {"v": ("sum", "count")})
    with obs.profiled() as sprof:
        _, sinfo = stream.collect(q)
    stream_report = sprof.report()["model"]
    assert "partitioned_io" in stream_report, (
        f"streaming run recorded no scan samples: {sorted(stream_report)}")
    assert pipeline_report, "4-op pipeline recorded no model samples"

    # disabled-mode null-span cost per call site
    assert not trace.enabled()
    n_null = 200_000
    t0 = time.perf_counter()
    for _ in range(n_null):
        with trace.span("noop"):
            pass
    null_ns = (time.perf_counter() - t0) / n_null * 1e9

    emit("obs/untraced_collect", untraced_s, f"P={nd},rows={N_LEFT}")
    emit("obs/traced_collect", traced_s,
         f"P={nd},overhead={overhead * 100:.2f}%")
    emit("obs/null_span", null_ns * 1e-9, f"{null_ns:.0f}ns_per_disabled_span")
    emit("obs/model_patterns", 0.0,
         "pipeline=" + "|".join(sorted(pipeline_report))
         + ";stream=" + "|".join(sorted(stream_report)))

    record = {
        "P": nd,
        "rows_left": N_LEFT,
        "rows_right": N_RIGHT,
        "repeat": REPEAT,
        "untraced_median_s": untraced_s,
        "traced_median_s": traced_s,
        "overhead_frac": overhead,
        "bit_identical": bit_identical,
        "null_span_ns": null_ns,
        "pipeline_model_report": pipeline_report,
        "stream_model_report": stream_report,
        "stream_peak_working_set_bytes": sinfo.get("peak_working_set_bytes"),
    }
    out_path = os.path.join(os.path.dirname(__file__), "BENCH_OBS.json")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)

    assert overhead < 0.03, (
        f"tracing overhead {overhead * 100:.2f}% exceeds the 3% bound "
        f"(traced {traced_s * 1e3:.2f}ms vs untraced {untraced_s * 1e3:.2f}ms)")
    print(f"tracing overhead {overhead * 100:+.2f}% "
          f"(traced {traced_s * 1e3:.2f}ms / untraced {untraced_s * 1e3:.2f}ms, "
          f"median of {REPEAT}); disabled span {null_ns:.0f}ns; "
          f"patterns: pipeline={sorted(pipeline_report)} "
          f"stream={sorted(stream_report)}", flush=True)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
