"""Statistics subsystem benchmark (ISSUE 9).

Two claims, both with bit-identity asserted:

- **Chunk skipping**: a selective scan over a dataset whose predicate
  column correlates with position (sorted ingest — the common
  time/id-ordered case) decodes measurably fewer chunks when the manifest
  carries per-chunk sketches, with output identical to the
  decode-everything run on the stats-stripped manifest.
- **Adaptive re-planning**: on a skewed-key streaming groupby (uniform
  keys early, one hot key late — the static quota is derived from the
  early shape), the cost model's ``shuffle_quota`` mean-abs-rel-err is
  strictly lower with ``adaptive=True`` than without, and the corrected
  stream's output is bit-identical to the static one.

Writes ``BENCH_STATS.json`` next to this file.
"""

import json
import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from benchmarks._util import emit
from repro import stream
from repro.core import DDFContext
from repro.data.dataset import write_dataset
from repro.expr import col
from repro.obs import model_check, trace

N_SCAN = 512_000
CHUNKS = 64
N_GB = 12_000
REPEAT = 5


def _canon(host):
    order = np.lexsort(tuple(host[k] for k in sorted(host)))
    return {k: v[order] for k, v in host.items()}


def _collect_timed(lz, **opts):
    t0 = time.perf_counter()
    out = lz.collect_stream(**opts).to_numpy()
    return out, time.perf_counter() - t0, lz.last_info


def bench_chunk_skip(ctx, root):
    rng = np.random.default_rng(0)
    data = {"ts": np.arange(N_SCAN, dtype=np.int32),  # sorted ingest column
            "v": rng.integers(0, 1000, N_SCAN).astype(np.int32)}
    man = write_dataset(data, os.path.join(root, "scan"),
                        chunk_rows=N_SCAN // CHUNKS)
    pred = col("ts") >= int(N_SCAN * 0.9)  # last ~10% of rows

    def run(manifest):
        lz = stream.scan_dataset(manifest, ctx, batch_rows=N_SCAN // 8,
                                 predicate=pred)
        return _collect_timed(lz)

    run(man)  # warm compile caches before timing
    ts_skip, ts_full = [], []
    for _ in range(REPEAT):
        out_s, t, info_s = run(man)
        ts_skip.append(t)
        out_f, t, info_f = run(dataclasses.replace(man, stats=None))
        ts_full.append(t)
    assert info_s["chunks_skipped"] > 0, "sketches must prune chunks"
    assert info_f["chunks_skipped"] == 0
    assert set(out_s) == set(out_f)
    for c in out_s:  # bit-identity: skipping never changes the answer
        assert np.array_equal(out_s[c], out_f[c]), c
    t_skip, t_full = float(np.median(ts_skip)), float(np.median(ts_full))
    emit("stats_scan_skip", t_skip,
         f"decoded {info_s['chunks_decoded']}/{CHUNKS} chunks")
    emit("stats_scan_full_decode", t_full,
         f"decoded {info_f['chunks_decoded']}/{CHUNKS} chunks")
    emit("stats_scan_skip_speedup", t_full - t_skip,
         f"x{t_full / max(t_skip, 1e-9):.2f}")
    return {
        "chunks_total": CHUNKS,
        "chunks_decoded_with_stats": int(info_s["chunks_decoded"]),
        "chunks_skipped": int(info_s["chunks_skipped"]),
        "seconds_with_stats": t_skip,
        "seconds_full_decode": t_full,
        "speedup": t_full / max(t_skip, 1e-9),
        "bit_identical": True,
    }


def bench_adaptive_quota(ctx, root):
    rng = np.random.default_rng(1)
    k = np.concatenate([rng.integers(0, 300, N_GB // 2),
                        np.full(N_GB - N_GB // 2, 7)]).astype(np.int64)
    v = rng.integers(0, 100, N_GB).astype(np.int64)
    man = write_dataset({"k": k, "v": v}, os.path.join(root, "skew"),
                        chunk_rows=500)

    def run(adaptive):
        lz = stream.scan_dataset(man, ctx, batch_rows=750) \
            .groupby(("k",), {"v": ("sum", "count")})
        since = model_check.mark()
        trace.enable()
        try:
            out = lz.collect_stream(adaptive=adaptive).to_numpy()
        finally:
            trace.disable()
        report = model_check.model_report(model_check.records(since))
        return _canon(out), report["shuffle_quota"], lz.last_info

    out_static, q_static, _ = run(adaptive=False)
    out_adapt, q_adapt, info = run(adaptive=True)
    for c in out_static:  # adaptation is result-invariant
        assert np.array_equal(out_static[c], out_adapt[c]), c
    assert info.get("replans", 0) >= 1, "skew must trigger a re-plan"
    err_s = q_static["mean_abs_rel_err"]
    err_a = q_adapt["mean_abs_rel_err"]
    assert err_a < err_s, (
        f"adaptive quota error {err_a:.3f} must beat static {err_s:.3f}")
    emit("stats_quota_err_static", err_s, f"{q_static['count']} samples")
    emit("stats_quota_err_adaptive", err_a,
         f"{info['replans']} replan(s); {q_adapt['count']} samples")
    return {
        "quota_mean_abs_rel_err_static": err_s,
        "quota_mean_abs_rel_err_adaptive": err_a,
        "replans": int(info["replans"]),
        "bit_identical": True,
    }


def main():
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    ctx = DDFContext(mesh=mesh, axes=("data",))
    results = {}
    with tempfile.TemporaryDirectory() as root:
        results["chunk_skip"] = bench_chunk_skip(ctx, root)
        results["adaptive_quota"] = bench_adaptive_quota(ctx, root)
    out_path = os.path.join(os.path.dirname(__file__), "BENCH_STATS.json")
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    emit("stats_total", 0.0, f"wrote {os.path.basename(out_path)}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
