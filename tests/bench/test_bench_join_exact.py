"""The reader of ``operators.join_exact_share``: the join cell's one int64
key takes exact key runs, so a tiny CPU run of the cell reads 1.0; an
engine that keeps neither of the join's counters reads ``None``."""

import bench_cases
import jax

from bench import harness
from repro.core import api
from repro.obs import metrics

share = harness.load_module(harness.BENCH / "metrics" / "operators.join_exact_share.py")


def test_join_cell_reads_exact_runs(monkeypatch):
    monkeypatch.setattr(api, "_OP_CACHE", api._LRUCache())
    monkeypatch.setattr(metrics, "_GLOBAL", metrics.MetricsRegistry())
    r = bench_cases.run_tiny("cylon_paper.join.p1", 4_000_000_017, jax.devices()[:1],
                             monkeypatch)
    assert r["correct"]
    assert metrics.registry().counters()["join.exact_runs"] >= 1
    assert share.read(None) == 1.0


def test_no_counters_read_none(monkeypatch):
    monkeypatch.setattr(metrics, "_GLOBAL", metrics.MetricsRegistry())
    assert share.read(None) is None
    metrics.registry().counter("join.verified_runs").add(1)
    assert share.read(None) == 0.0
