"""Concurrent query service benchmark (ISSUE 7 tentpole).

Runs a batch of 8 mixed queries (streaming scans + scan-free lazy
pipelines) two ways on one 8-host-device mesh:

- **serial**: each query's ``collect``/``collect_stream`` back to back —
  the only option before ``repro.service``;
- **concurrent**: all 8 submitted to one ``QueryService`` and interleaved
  at morsel granularity under the ``fair`` policy.

Records batch throughput (queries/s, concurrent must be >= serial — one
driver thread, so the win comes from overlapping host decode/result
handling with device work, not from device parallelism), per-query
latency p50/p95, the fairness spread (max/min measured device seconds
across the equal-weight streaming queries), and the shared plan/compiled-
op cache hit rates across queries sharing a plan shape (must be > 0).
Asserts concurrent results are bit-identical to serial; writes
``BENCH_SERVICE.json`` next to this file.
"""

import json
import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from benchmarks._util import emit
from repro import stream
from repro.core import DDF, DDFContext
from repro.data.dataset import write_dataset
from repro.service import QueryService

N_DISK = 160_000     # per streaming query, on disk
N_MEM = 40_000       # per lazy query, in memory
KEYS = 10_000
N_BATCHES = 8
N_STREAM = 4         # 4 streaming + 4 lazy = 8 concurrent queries
N_LAZY = 4


def make_queries(ctx, man, dl, dr):
    aggs = {"v": ("sum", "count")}
    # aggregating the wide columns defeats projection pushdown on purpose:
    # every streaming morsel decodes the full row width on the host
    stream_aggs = {"v": ("sum", "count"), "j0": ("sum",), "j1": ("sum",),
                   "j2": ("sum",), "j3": ("sum",)}
    batch_rows = N_DISK // N_BATCHES
    qs = []
    for _ in range(N_STREAM):
        qs.append(("stream",
                   lambda: stream.scan_dataset(man, ctx, batch_rows=batch_rows)
                   .groupby(("k",), stream_aggs)))
    for _ in range(N_LAZY):
        qs.append(("lazy",
                   lambda: dl.lazy().join(dr.lazy(), on=("k",),
                                          strategy="shuffle")
                   .groupby(("k",), aggs)))
    return qs


def run_serial(kinds_queries):
    outs, lat = [], []
    import time
    for kind, mk in kinds_queries:
        t0 = time.perf_counter()
        q = mk()
        out = stream.collect(q)[0] if kind == "stream" else q.collect()
        jax.block_until_ready(out.counts)
        outs.append(out)
        lat.append(time.perf_counter() - t0)
    return outs, lat


def run_concurrent(kinds_queries):
    import time
    with QueryService(policy="fair", max_running=8) as svc:
        t0 = time.perf_counter()
        handles = [svc.submit(mk()) for _, mk in kinds_queries]
        outs = [h.result(timeout=600) for h in handles]
        for out in outs:
            jax.block_until_ready(out.counts)
        wall = time.perf_counter() - t0
        lat = [h.finished_at - h.submitted_at for h in handles]
        device_s = [h.device_s for h in handles
                    if getattr(h.query, "_scans", None)]
        caches = svc.stats()["caches"]
    return outs, lat, wall, device_s, caches


def main():
    nd = len(jax.devices())
    mesh = jax.make_mesh((nd,), ("data",))
    ctx = DDFContext(mesh=mesh, axes=("data",))
    rng = np.random.default_rng(0)

    # extra columns make host decode a real fraction of each morsel, so the
    # concurrent win (all queries' prefetch decodes overlap device work)
    # is visible and not noise
    disk = {"k": rng.integers(0, KEYS, N_DISK).astype(np.int32),
            "v": rng.integers(0, 1000, N_DISK).astype(np.int32),
            "j0": rng.integers(0, 5, N_DISK).astype(np.int32),
            "j1": rng.integers(0, 5, N_DISK).astype(np.int32),
            "j2": rng.random(N_DISK).astype(np.float32),
            "j3": rng.random(N_DISK).astype(np.float32)}
    mem = {"k": rng.integers(0, KEYS, N_MEM).astype(np.int32),
           "v": rng.integers(0, 1000, N_MEM).astype(np.int32)}
    right = {"k": rng.integers(0, KEYS, N_MEM // 4).astype(np.int32),
             "w": rng.integers(0, 50, N_MEM // 4).astype(np.int32)}

    tmp = tempfile.mkdtemp(prefix="repro-bench-service-")
    man = write_dataset(disk, tmp, chunk_rows=(N_DISK // N_BATCHES) // 2)
    dl = DDF.from_numpy(mem, ctx, capacity=2 * (-(-N_MEM // nd)))
    dr = DDF.from_numpy(right, ctx, capacity=2 * (-(-(N_MEM // 4) // nd)))

    queries = make_queries(ctx, man, dl, dr)

    # warm both code paths once (compiles amortize across the real runs)
    run_serial(queries[:1] + queries[N_STREAM:N_STREAM + 1])

    import time
    t0 = time.perf_counter()
    serial_outs, serial_lat = run_serial(queries)
    serial_wall = time.perf_counter() - t0

    conc_outs, conc_lat, conc_wall, device_s, caches = run_concurrent(queries)

    # correctness: concurrent == serial, bit for bit, per query
    for i, (ref, got) in enumerate(zip(serial_outs, conc_outs)):
        rn, gn = ref.to_numpy(), got.to_numpy()
        for k in rn:
            assert np.array_equal(rn[k], gn[k]), f"query {i} column {k}"

    thr_serial = len(queries) / serial_wall
    thr_conc = len(queries) / conc_wall
    p50 = float(np.percentile(conc_lat, 50))
    p95 = float(np.percentile(conc_lat, 95))
    p50_serial = float(np.percentile(serial_lat, 50))
    fairness = (max(device_s) / max(min(device_s), 1e-9)) if device_s else 1.0
    op_w = caches["op"]["window"]
    plan_w = caches["plan"]["window"]
    op_rate = op_w["hits"] / max(op_w["hits"] + op_w["misses"], 1)
    plan_rate = plan_w["hits"] / max(plan_w["hits"] + plan_w["misses"], 1)

    emit("service/serial_batch", serial_wall,
         f"P={nd},queries={len(queries)},thr={thr_serial:.2f}q/s")
    emit("service/concurrent_batch", conc_wall,
         f"P={nd},queries={len(queries)},thr={thr_conc:.2f}q/s,"
         f"speedup={serial_wall / conc_wall:.3f}")
    emit("service/latency_p50", p50, f"p95={p95 * 1e6:.1f}us")
    emit("service/fairness_spread", 0.0,
         f"max_over_min_device_s={fairness:.3f}")
    emit("service/cache_hit_rates", 0.0,
         f"op={op_rate:.3f},plan={plan_rate:.3f}")

    record = {
        "P": nd,
        "queries": len(queries),
        "mix": f"{N_STREAM} streaming + {N_LAZY} lazy",
        "rows_on_disk_per_stream_query": N_DISK,
        "rows_in_memory_per_lazy_query": N_MEM,
        "serial_wall_s": serial_wall,
        "concurrent_wall_s": conc_wall,
        "throughput_serial_qps": thr_serial,
        "throughput_concurrent_qps": thr_conc,
        "concurrent_speedup": serial_wall / conc_wall,
        "latency_serial_p50_s": p50_serial,
        "latency_concurrent_p50_s": p50,
        "latency_concurrent_p95_s": p95,
        "fairness_spread_device_s": fairness,
        "op_cache_hit_rate": op_rate,
        "plan_cache_hit_rate": plan_rate,
        "bit_identical_to_serial": True,
    }
    out_path = os.path.join(os.path.dirname(__file__), "BENCH_SERVICE.json")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)

    assert op_rate > 0.0, "no compiled-op cache reuse across queries"
    assert plan_rate > 0.0, "no plan cache reuse across queries"
    assert thr_conc >= 0.9 * thr_serial, (
        f"concurrent throughput {thr_conc:.2f} q/s fell more than 10% below "
        f"serial {thr_serial:.2f} q/s")
    print(f"concurrent {thr_conc:.2f} q/s vs serial {thr_serial:.2f} q/s "
          f"({serial_wall / conc_wall:.2f}x); p50 {p50 * 1e3:.0f}ms "
          f"p95 {p95 * 1e3:.0f}ms; fairness spread {fairness:.2f}; "
          f"cache hit rates op={op_rate:.2f} plan={plan_rate:.2f}",
          flush=True)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
