"""Chip smoke test: the dataframe engine's main path on a TPU at the paper's
size, checked against numpy on the host.

With no arguments it runs on one chip, through the public API
(``repro.core.DDF`` / ``DDFContext``, ``.lazy()``, ``collect_stream``), on
the paper's workload (``configs/paper_cylon.py``: 25M rows per worker, two
int32 columns, 90% key cardinality), with data made from ``--seed``:

  (a) ``DDF.join`` of two such tables on ``c0`` (hash shuffle);
  (b) ``sort_values("c1")`` on the join result;
  (c) the 4-op lazy pipeline select -> project -> join -> groupby
      (sum, count, min, max, mean) via ``.collect()``;
  (d) the same pipeline out of core: ``collect_stream`` over a dataset
      written from the seed, in 5 batches;
  (e) a groupby of float32 and uint32 value columns, so the groupby kernel
      runs for every dtype its dispatch table lists.

``--chips 4`` runs only (a), on a 4-chip ``data`` mesh with 25M rows per
worker, and checks that each shard of the result lives on its own chip.

Every phase is checked against an answer numpy computes from the same
seeded data (row counts, per-key counts, payload sums, per-key aggregates;
monotonicity and a row-multiset fingerprint for the sort). Earlier lines
report set-up facts: the device, each phase's wall time split into
compile (XLA and Mosaic) and the rest (host tracing and lowering, and the
run), peak device memory, the compile cache and the kernel dispatch
counters. The last line is ``{"ok": true, "device": {...}}``. The script
exits non-zero, printing no such line, when JAX finds no TPU or any phase
fails its check.

Run:  python chip_smoke.py [--chips 4] [--seed N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import numpy as np  # noqa: E402

# c1 is drawn from [0, 2**31 - 1): the select keeps about half the rows
_SELECT_ABOVE = 1 << 30
_GROUP_AGGS = ("sum", "count", "min", "max", "mean")
_LOW_OPS = ("sum", "min", "max")
# phase (d) streams the left table in this many batches (at least 4)
_STREAM_BATCHES = 5


class SmokeFailure(RuntimeError):
    """A phase's result disagreed with its numpy check."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# -- numpy references (host, independent of the engine) ----------------------

def _wrap32(x: np.ndarray, dtype) -> np.ndarray:
    """Exact int64 values reduced modulo 2**32 into a 32-bit dtype — how
    the device's 32-bit sums wrap."""
    return (x.astype(np.int64) & 0xFFFFFFFF).astype(np.uint32).view(dtype)


def _keyed_min_max(keys: np.ndarray, vals: np.ndarray, n_keys: int):
    info = np.finfo if np.issubdtype(vals.dtype, np.floating) else np.iinfo
    lo = np.full(n_keys, info(vals.dtype).max, vals.dtype)
    hi = np.full(n_keys, info(vals.dtype).min, vals.dtype)
    np.minimum.at(lo, keys, vals)
    np.maximum.at(hi, keys, vals)
    return lo, hi


def row_fingerprint(cols: dict, names) -> int:
    """Order-independent multiset fingerprint: the uint64 sum of a mixed
    hash of each row."""
    h = np.zeros(len(cols[names[0]]), np.uint64)
    for name in names:
        x = cols[name].astype(np.int64).view(np.uint64) ^ h
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        h = x ^ (x >> np.uint64(31))
    return int(np.sum(h, dtype=np.uint64))


def join_reference(left: dict, right: dict, n_keys: int) -> dict:
    """Row count, per-key row counts and payload sums of the inner join on
    c0, from per-key counts and sums alone."""
    cl = np.bincount(left["c0"], minlength=n_keys).astype(np.int64)
    cr = np.bincount(right["c0"], minlength=n_keys).astype(np.int64)
    # float64 bincount sums are exact here: at most ~2**36 per key
    sl = np.bincount(left["c0"], weights=left["c1"], minlength=n_keys).astype(np.int64)
    sr = np.bincount(right["c0"], weights=right["c1"], minlength=n_keys).astype(np.int64)
    return {"rows": int(np.sum(cl * cr)), "per_key": cl * cr,
            "sum_c0": int(np.sum(np.arange(n_keys, dtype=np.int64) * cl * cr)),
            "sum_c1": int(np.sum(sl * cr)), "sum_c1_r": int(np.sum(cl * sr))}


def pipeline_reference(left: dict, right: dict, n_keys: int) -> dict:
    """select(c1 > T) -> join on c0 -> groupby c0 over c1, with int32 sums
    wrapped as on the device."""
    keep = left["c1"] > _SELECT_ABOVE
    k, v = left["c0"][keep], left["c1"][keep]
    cl = np.bincount(k, minlength=n_keys).astype(np.int64)
    cr = np.bincount(right["c0"], minlength=n_keys).astype(np.int64)
    sl = np.bincount(k, weights=v, minlength=n_keys).astype(np.int64)
    lo, hi = _keyed_min_max(k, v, n_keys)
    present = (cl > 0) & (cr > 0)
    s = _wrap32((sl * cr)[present], np.int32)
    c = (cl * cr)[present].astype(np.int32)
    return {"c0": np.flatnonzero(present).astype(np.int32),
            "c1_sum": s, "c1_count": c, "c1_min": lo[present],
            "c1_max": hi[present],
            "c1_mean": s.astype(np.float32) / c.astype(np.float32)}


def dtype_groupby_reference(left: dict, n_keys: int) -> dict:
    """groupby c0 over f = float32(c1) and u = uint32(c1): exact uint32
    sums (wrapped), exact min/max, float64 sums of the float32 values."""
    k, v = left["c0"], left["c1"]
    present = np.bincount(k, minlength=n_keys) > 0
    exact = np.bincount(k, weights=v, minlength=n_keys).astype(np.int64)
    f = v.astype(np.float32)
    fsum = np.bincount(k, weights=f.astype(np.float64), minlength=n_keys)
    flo, fhi = _keyed_min_max(k, f, n_keys)
    ulo, uhi = _keyed_min_max(k, v.astype(np.uint32), n_keys)
    return {"c0": np.flatnonzero(present).astype(np.int32),
            "f_sum": fsum[present], "f_min": flo[present], "f_max": fhi[present],
            "u_sum": _wrap32(exact[present], np.uint32),
            "u_min": ulo[present], "u_max": uhi[present],
            "max_rows_per_key": int(np.max(np.bincount(k)))}


def check_grouped(got: dict, exp: dict, names, phase: str, rtol=None):
    """Group keys and every column in ``names`` equal numpy's — exactly,
    or within the relative tolerance ``rtol`` gives for that column."""
    rtol = rtol or {}
    order = np.argsort(got["c0"], kind="stable")
    check(np.array_equal(got["c0"][order], exp["c0"]),
          f"{phase}: group keys differ ({len(got['c0'])} vs {len(exp['c0'])} groups)")
    for name in names:
        g = got[name][order]
        e = exp[name]
        if name in rtol:
            ok = np.allclose(g.astype(np.float64), e.astype(np.float64),
                             rtol=rtol[name], atol=0)
        else:
            ok = g.dtype == e.dtype and np.array_equal(g, e)
        check(ok, f"{phase}: column {name} ({g.dtype}) disagrees with numpy ({e.dtype})")


# -- the phases -------------------------------------------------------------------

class Phases:
    """Runs the phases on one context and prints a set-up line per phase."""

    def __init__(self, ctx, rows_per_worker: int, seed: int):
        from repro.configs.paper_cylon import CONFIG
        from repro.data.synthetic import uniform_table

        self.ctx = ctx
        n = rows_per_worker * ctx.nworkers
        self.n_keys = max(int(n * CONFIG.cardinality), 1)
        self.left = uniform_table(n, CONFIG.cardinality, CONFIG.n_columns, seed=seed)
        self.right = uniform_table(n, CONFIG.cardinality, CONFIG.n_columns, seed=seed + 1)
        self._compile_s = 0.0
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event: str, secs: float, **_):
        # XLA and Mosaic compilation; these spans never nest, unlike the
        # tracing and lowering ones, which stay in run_s
        if event == "/jax/core/compile/backend_compile_duration":
            self._compile_s += secs

    def timed(self, name: str, fn):
        import jax

        c0, t0 = self._compile_s, time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        wall = time.perf_counter() - t0
        compile_s = self._compile_s - c0
        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use", "not reported")
        print(f"phase {name}: wall_s={wall} compile_s={compile_s} "
              f"run_s={wall - compile_s} peak_bytes_in_use={peak}", flush=True)
        return out

    def join(self):
        from repro.core import DDF

        L = DDF.from_numpy(self.left, self.ctx)
        R = DDF.from_numpy(self.right, self.ctx)
        J, info = self.timed("a_join", lambda: L.join(R, on=("c0",), strategy="shuffle"))
        for k, v in info.items():
            check(int(np.sum(np.asarray(v))) == 0, f"a_join: {k} nonzero")
        got = J.to_numpy()
        exp = join_reference(self.left, self.right, self.n_keys)
        check(len(got["c0"]) == exp["rows"],
              f"a_join: {len(got['c0'])} rows, numpy says {exp['rows']}")
        check(np.array_equal(np.bincount(got["c0"], minlength=self.n_keys),
                             exp["per_key"]), "a_join: per-key row counts differ")
        for name in ("c0", "c1", "c1_r"):
            check(int(np.sum(got[name], dtype=np.int64)) == exp[f"sum_{name}"],
                  f"a_join: sum of {name} differs")
        print(f"check a_join: rows={exp['rows']} ok", flush=True)
        return J, got

    def sort(self, J, joined: dict):
        S, info = self.timed("b_sort", lambda: J.sort_values("c1"))
        for k, v in info.items():
            if k.startswith("overflow"):
                check(int(np.sum(np.asarray(v))) == 0, f"b_sort: {k} nonzero")
        got = S.to_numpy()
        names = ("c0", "c1", "c1_r")
        check(len(got["c1"]) == len(joined["c1"]), "b_sort: row count changed")
        check(bool(np.all(np.diff(got["c1"]) >= 0)), "b_sort: c1 not ascending")
        check(row_fingerprint(got, names) == row_fingerprint(joined, names),
              "b_sort: row multiset differs from the join's")
        print(f"check b_sort: rows={len(got['c1'])} ok", flush=True)

    def _pipeline(self, source):
        from repro.core import DDF
        from repro.expr import col

        R = DDF.from_numpy(self.right, self.ctx).lazy()
        return (source.select(col("c1") > _SELECT_ABOVE)
                .project(["c0", "c1"])
                .join(R, on=("c0",), strategy="shuffle")
                .groupby(("c0",), {"c1": _GROUP_AGGS}))

    def _check_pipeline(self, out, phase):
        exp = pipeline_reference(self.left, self.right, self.n_keys)
        # the mean is a float32 division on both sides; the chip's may
        # round differently in the last bits
        check_grouped(out.to_numpy(), exp, [f"c1_{op}" for op in _GROUP_AGGS],
                      phase, rtol={"c1_mean": 1e-6})
        print(f"check {phase}: groups={len(exp['c0'])} ok", flush=True)

    def lazy(self):
        from repro.core import DDF

        lz = self._pipeline(DDF.from_numpy(self.left, self.ctx).lazy())
        out = self.timed("c_lazy", lz.collect)
        self._check_pipeline(out, "c_lazy")

    def stream(self):
        from repro.data.dataset import write_dataset
        from repro.stream import scan_dataset

        # Batches sized for the chip: the cost model's default budget is a
        # CPU's (ROADMAP S2) and cuts 25M rows into 25 batches, each of
        # which re-shuffles the whole in-memory right side.
        batch_rows = -(-len(self.left["c0"]) // _STREAM_BATCHES)
        with tempfile.TemporaryDirectory() as tmp:
            manifest = write_dataset(self.left, os.path.join(tmp, "left"),
                                     chunk_rows=1 << 20, compress=False)
            lz = self._pipeline(scan_dataset(manifest, self.ctx, batch_rows=batch_rows))
            out = self.timed("d_stream", lz.collect_stream)
        batches = int(lz.last_info["batches"])
        print(f"phase d_stream: batches={batches}", flush=True)
        check(batches >= 4, f"d_stream: {batches} batches, want at least 4")
        self._check_pipeline(out, "d_stream")

    def dtypes(self):
        from repro.core import DDF
        from repro.expr import col

        d = (DDF.from_numpy(self.left, self.ctx)
             .with_column("f", col("c1").cast(np.float32))
             .with_column("u", col("c1").cast(np.uint32)))
        aggs = {"f": _LOW_OPS, "u": _LOW_OPS}
        g, _ = self.timed("e_dtypes", lambda: d.groupby(("c0",), aggs))
        exp = dtype_groupby_reference(self.left, self.n_keys)
        # float32 sums of n rows in any order are within (n - 1) * 2**-24
        # of the exact sum, relative
        rtol = max(exp["max_rows_per_key"] - 1, 1) * 2.0 ** -24
        check_grouped(g.to_numpy(), exp,
                      [f"{c}_{op}" for c in ("f", "u") for op in _LOW_OPS],
                      "e_dtypes", rtol={"f_sum": rtol})
        print(f"check e_dtypes: groups={len(exp['c0'])} ok", flush=True)


def dispatch_counters() -> dict:
    from repro.obs import metrics

    return {k: v for k, v in sorted(metrics.registry().counters().items())
            if k.startswith("kernels.dispatch.")}


def check_kernels_ran(counters: dict) -> None:
    """Every (kernel, dtype, op) the dispatch table lists ran as pallas."""
    from repro.core import cost_model

    want = ["kernels.dispatch.hash_partition.pallas"]
    for dt in cost_model.kernel_params("tpu").supported_dtypes["segment_reduce"]:
        want += [f"kernels.dispatch.segment_reduce.{dt}.{op}.pallas" for op in _LOW_OPS]
    missing = [k for k in want if counters.get(k, 0) == 0]
    check(not missing, f"kernels never dispatched as pallas: {missing}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: phases (a)-(e) on one chip; 4: phase (a) on a "
                         "4-chip data mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips}, JAX found {len(devices)}",
              file=sys.stderr)
        return 1

    from repro.configs.paper_cylon import CONFIG
    from repro.core import DDFContext
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh

    cache_dir = enable_compile_cache()
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event: str, **_):
        for k in cache_events:
            if event == f"/jax/compilation_cache/cache_{k}":
                cache_events[k] += 1

    jax.monitoring.register_event_listener(on_event)
    used = devices[:args.chips]
    print(f"device_kind={used[0].device_kind} platform={used[0].platform} "
          f"chips={len(used)} rows_per_worker={CONFIG.rows_per_worker} "
          f"seed={args.seed}", flush=True)
    ctx = DDFContext(mesh=make_mesh((len(used),), ("data",), devices=used),
                     axes=("data",))
    phases = Phases(ctx, CONFIG.rows_per_worker, args.seed)

    if args.chips == 4:
        J, _ = phases.join()
        for name, arr in sorted(J.columns.items()):
            shards = arr.addressable_shards
            devs = [s.device.id for s in shards]
            check(len(set(devs)) == len(used) == len(shards),
                  f"a_join: column {name} shards on devices {devs}")
            check(all(s.data.shape[0] == J.capacity for s in shards),
                  f"a_join: column {name} shards are not one partition each")
            print(f"shards {name}: devices={devs} rows_each={J.capacity}", flush=True)
    else:
        J, joined = phases.join()
        phases.sort(J, joined)
        del J, joined
        phases.lazy()
        phases.stream()
        phases.dtypes()

    counters = dispatch_counters()
    for k, v in counters.items():
        print(f"counter {k}={v}", flush=True)
    if args.chips == 1:
        check_kernels_ran(counters)
    else:
        check(counters.get("kernels.dispatch.hash_partition.pallas", 0) > 0,
              "hash_partition never dispatched as pallas")
    print(f"compile_cache dir={cache_dir} hits={cache_events['hits']} "
          f"misses={cache_events['misses']}", flush=True)
    print(json.dumps({"ok": True, "device": {"platform": used[0].platform,
                                             "kind": used[0].device_kind,
                                             "count": len(used)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
