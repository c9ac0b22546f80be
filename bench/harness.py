"""Runs one cell of ``BENCHMARK.json`` once and builds its result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in files of its own, found by the names in ``BENCHMARK.json``:

- ``bench/configs/<config>.json``: the configuration as it is run; its
  ``reference`` names ``bench/configs/<reference>.py``, the generator and
  plain numpy reference, which import nothing of the engine.
- ``bench/traffic/<mix>.json``: the mix's parameters; its ``query`` names
  ``bench/queries/<query>.py``, the entry the mix drives and its comparison,
  and its ``loop`` names ``bench/loops/<loop>.py``, which drives the query
  for the window inside a ``bench.window`` span and reports the mix's
  end-to-end metrics.
- ``bench/metrics/<metric>.py``: ``read(reading)`` reduces a traced run to
  one number, or to ``None`` where it finds nothing to read.

A run: build the tables from the seed on the host, put them on the cell's
mesh, warm up with one query (set-up ends there), let the loop drive the
window, then check every answer against the reference. A configuration
whose ``dtype`` is 64 bits wide runs with JAX's 64-bit types on, and its
``kernel_backend`` sets the engine's kernel dispatch. With
``trace`` the window runs under the profiler and the per-layer metrics
replace the end-to-end ones.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_module(path: pathlib.Path):
    """Import a file of the benchmark by its path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    entry: dict       # the cell's entry in BENCHMARK.json's workloads
    config: dict      # the configuration file's contents
    reference: object  # bench/configs/<config>.py
    traffic: dict
    query: object     # bench/queries/<query>.py
    loop: object      # bench/loops/<loop>.py
    end_to_end: list
    per_layer: list   # the per-layer metrics this cell reports

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])


def find_cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench or load_benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config_file = ROOT / conf["file"]
    config = json.loads(config_file.read_text())
    traffic = json.loads((BENCH / "traffic" / f"{entry['traffic']}.json").read_text())

    def applies(m):
        return name in m.get("workloads", [name])

    return Cell(entry=entry, config=config,
                reference=load_module(BENCH / "configs" / f"{config['reference']}.py"),
                traffic=traffic,
                query=load_module(BENCH / "queries" / f"{traffic['query']}.py"),
                loop=load_module(BENCH / "loops" / f"{traffic['loop']}.py"),
                end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                per_layer=[m for m in bench["per_layer"] if applies(m)])


def find_chips(chips: int) -> list:
    """The first ``chips`` TPU devices, or :class:`NoChip`. Never the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed path in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), every program cached."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileWatch:
    """Backend compile events (XLA and Mosaic), split at the window's start
    and end."""

    def __init__(self):
        self.phase = "setup"
        self.secs = {"setup": 0.0, "window": 0.0, "check": 0.0}
        self.count = {"setup": 0, "window": 0, "check": 0}

    def __call__(self, event: str, secs: float, **_):
        if event == BACKEND_COMPILE:
            self.secs[self.phase] += secs
            self.count[self.phase] += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self)


def annotate(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's reader gets."""
    trace: object          # bench.trace.Trace of the window
    compile: CompileWatch


def make_context(devices):
    from repro.core import DDFContext
    from repro.launch.mesh import make_mesh

    return DDFContext(mesh=make_mesh((len(devices),), ("data",), devices=devices),
                      axes=("data",))


def wants_x64(config: dict) -> bool:
    """Whether the configuration's columns are 64 bits wide."""
    import numpy as np

    return np.dtype(config.get("dtype", "int32")).itemsize == 8


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def run(cell_name: str, seed: int, seconds: float, trace: bool, devices,
        t_start: float, config: dict | None = None) -> dict:
    """One run of the cell on ``devices``; returns the result line's dict.

    ``config`` replaces the configuration file's sizes (the CPU tests run
    the cell at a size a test can hold)."""
    from bench import trace as trace_mod

    cell = find_cell(cell_name)
    if config is not None:
        cell.config = config
    if cell.config["workers"] != len(devices):
        raise ValueError(f"{cell_name}: {cell.config['workers']} workers, "
                         f"{len(devices)} devices")
    enable_compile_cache()
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    import jax
    from repro.kernels import use_backend

    with (jax.enable_x64(wants_x64(cell.config)),
          use_backend(cell.config.get("kernel_backend", "auto")),
          CompileWatch() as watch):
        ctx = make_context(devices)
        with annotate("bench.generate"):
            data = cell.reference.generate(cell.config, seed)
        with annotate("bench.upload"):
            q = cell.query.Query(ctx, data, cell.config, cell.traffic, cell.reference)
        with annotate("bench.warmup"):
            q.record(q.run())
        setup_s = time.perf_counter() - t_start
        watch.phase = "window"
        if trace:
            jax.profiler.start_trace(tdir)
        try:
            w = cell.loop.run(q, cell.traffic, seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
        watch.phase = "check"
        peak = peak_bytes(devices)
        queries = len(w["records"])
        checks, failed = q.check(w["records"], w["last"])
        values = {**w["values"], "setup_s": setup_s}
        del w, q

    kind = devices[0].device_kind
    result = {"correct": all(v <= lim for v, lim in checks.values()),
              "attempted": queries, "failed": failed,
              "device": {"platform": devices[0].platform, "kind": kind,
                         "count": len(devices), "memory_peak_bytes": peak}}
    if trace:
        try:
            tr = trace_mod.load(tdir)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        busy = trace_mod.device_busy(tr)
        lo, hi = tr.window
        result["device"]["busy_s"] = sum(busy.values()) / max(len(busy), 1) / 1e9
        result["device"]["window_s"] = (hi - lo) / 1e9
        reading = Reading(trace=tr, compile=watch)
        metrics = {}
        for m in cell.per_layer:
            value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["breakdown"] = trace_mod.breakdown(tr)
    else:
        result["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return result


def emit(result: dict) -> None:
    """The engine's kernel dispatch counters on an earlier line (which path
    each kernel took), each number compared beside its limit as the last
    lines on standard error, then the result as the last line of standard
    output."""
    from repro.obs import metrics

    counters = {k: v for k, v in sorted(metrics.registry().counters().items())
                if k.startswith("kernels.dispatch.")}
    print(f"dispatch {json.dumps(counters)}", flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
