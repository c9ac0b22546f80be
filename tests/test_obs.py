"""Unified observability layer (tracing + metrics + cost-model checks):
span nesting and thread safety, the disabled fast path, the clock shared
with jax.profiler, the compile and dispatch spans, the pattern-phase
scopes in the compiled programs, per-pattern predicted-vs-observed
records across eager/streaming paths, bit-identity of profiled runs, and
the admission controller's learned working-set corrections."""

import glob
import os
import re
import threading
import time

import jax
import numpy as np
import pytest

from repro import obs, stream
from repro.core import DDF, DDFContext
from repro.expr import col
from repro.data.dataset import write_dataset
from repro.obs import metrics, model_check, trace
from repro.service import QueryService
from repro.service.admission import AdmissionController, query_learn_key


@pytest.fixture(scope="module")
def ctx():
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    return DDFContext(mesh=mesh, axes=("data",))


def _table(n, nkeys=100, seed=0):
    rng = np.random.default_rng(seed)
    return {"k": rng.integers(0, nkeys, n).astype(np.int32),
            "v": rng.integers(0, 1000, n).astype(np.int32)}


@pytest.fixture(scope="module")
def tables(ctx):
    L = DDF.from_numpy(_table(400, seed=1), ctx, capacity=800)
    R = {"k": np.arange(100, dtype=np.int32),
         "w": (np.arange(100, dtype=np.int32) % 7).astype(np.int32)}
    return L, DDF.from_numpy(R, ctx, capacity=200)


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    root = tmp_path_factory.mktemp("obs")
    return write_dataset(_table(4000, seed=2), str(root / "ds"),
                         chunk_rows=512)


@pytest.fixture()
def traced():
    """Enable tracing for one test, restoring prior state after."""
    with trace.tracing():
        trace_mark, model_mark = trace.mark(), model_check.mark()
        yield trace_mark, model_mark


# -- span mechanics -----------------------------------------------------------

def test_span_nesting_and_attrs(traced):
    mark, _ = traced
    with trace.span("outer", layer="test") as so:
        with trace.span("inner") as si:
            si.set(rows=7)
    t = trace.get_trace(since=mark)
    by_name = {sp.name: sp for sp in t.spans}
    assert set(by_name) >= {"outer", "inner"}
    assert by_name["inner"].parent == by_name["outer"].sid
    assert by_name["inner"].attrs["rows"] == 7
    assert by_name["outer"].attrs["layer"] == "test"
    assert by_name["outer"].t1 >= by_name["inner"].t1 >= by_name["inner"].t0


def test_retroactive_complete_and_instant(traced):
    mark, _ = traced
    t0 = trace.now()
    trace.complete("retro", t0, t0 + 0.5, kind="stage")
    trace.instant("marker", site="here")
    spans = trace.get_trace(since=mark).spans
    retro = next(sp for sp in spans if sp.name == "retro")
    assert retro.duration_s == pytest.approx(0.5)
    assert any(sp.name == "marker" and sp.t0 == sp.t1 for sp in spans)


def test_span_thread_safety(traced):
    """Concurrent spans from many threads: no misnesting across threads
    (parents resolve per-thread), no lost events."""
    mark, _ = traced
    n_threads, per_thread = 8, 25

    def work(i):
        for j in range(per_thread):
            with trace.span(f"t{i}", j=j):
                with trace.span(f"t{i}.child", j=j):
                    pass

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = trace.get_trace(since=mark).spans
    assert len(spans) == n_threads * per_thread * 2
    by_sid = {sp.sid: sp for sp in spans}
    for sp in spans:
        if sp.name.endswith(".child"):
            parent = by_sid[sp.parent]
            assert parent.name == sp.name[:-len(".child")]
            assert parent.tid == sp.tid


def test_disabled_mode_null_span():
    """Disabled tracing hands out one shared null span — no allocation,
    no recording — and records nothing."""
    assert not trace.enabled()
    mark = trace.mark()
    a = trace.span("x", big=list(range(100)))
    b = trace.span("y")
    assert a is b  # the singleton
    with a as sp:
        sp.set(rows=1)
    trace.instant("z")
    trace.complete("w", 0.0, 1.0)
    model_check.record("shuffle_compute", "op", 1.0, 2.0)
    assert len(trace.get_trace(since=mark).spans) == 0
    assert trace.summary()["enabled"] is False


def _host_events(trace_dir, names):
    """``{name: (start_ns, end_ns)}`` of the profiler's host-plane events,
    on the wall clock (the planes count from ``profile_start_time``)."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    data = ProfileData.from_file(path)
    origin = next(v for pl in data.planes for k, v in pl.stats
                  if k == "profile_start_time")
    return {e.name: (origin + e.start_ns, origin + e.end_ns)
            for pl in data.planes if pl.name.startswith("/host:")
            for line in pl.lines for e in line.events if e.name in names}


def test_spans_share_the_profilers_clock(tmp_path):
    """A live span lands in the profiler's host plane; it and a
    retroactive span agree with the recorder's intervals within 1 ms."""
    with trace.tracing(), jax.profiler.trace(str(tmp_path)):
        mark = trace.mark()
        t0 = trace.now()
        with trace.span("clock.live", rows=3):
            time.sleep(0.02)
        trace.complete("clock.retro", t0)
        with jax.profiler.TraceAnnotation("clock.twin"):
            t1 = trace.now()
            time.sleep(0.02)
            trace.complete("clock.retro_twin", t1)
    spans = {sp.name: sp for sp in trace.get_trace(since=mark).spans}
    plane = _host_events(str(tmp_path), {"clock.live", "clock.twin"})
    assert set(plane) == {"clock.live", "clock.twin"}

    def close(sp, ev):
        return (abs(sp.t0 * 1e9 - ev[0]) < 1e6
                and abs(sp.t1 * 1e9 - ev[1]) < 1e6)

    assert close(spans["clock.live"], plane["clock.live"])
    # the retroactive span opened just before the live one
    live = plane["clock.live"]
    retro = spans["clock.retro"]
    assert abs(retro.t0 * 1e9 - live[0]) < 1e6 and retro.t1 * 1e9 >= live[1]
    assert close(spans["clock.retro_twin"], plane["clock.twin"])


def test_span_durations_ignore_wall_clock_steps(traced, monkeypatch):
    """A step of the wall clock (an NTP correction) inside a span moves
    neither its duration nor the recorder's order of time."""
    mark, _ = traced
    wall = time.time_ns()
    monkeypatch.setattr(time, "time_ns", lambda: wall - 3_600 * 10**9)
    t0 = trace.now()
    with trace.span("clock.step"):
        time.sleep(0.01)
    (sp,) = trace.get_trace(since=mark).spans
    assert sp.t0 >= t0 and 0.01 <= sp.duration_s < 1.0
    assert abs(sp.t0 - wall / 1e9) < 60


# -- the engine's compile and dispatch spans ------------------------------------

def _fresh_join(ctx, monkeypatch, seed):
    """A join whose op is built on its first call: the op cache starts
    empty, so that call misses once."""
    from repro.core import api

    monkeypatch.setattr(api, "_OP_CACHE", api._LRUCache())
    L = DDF.from_numpy(_table(300, seed=seed), ctx, capacity=900, mode="eager")
    R = DDF.from_numpy(_table(300, seed=seed + 1), ctx, capacity=900,
                       mode="eager")
    return lambda: jax.block_until_ready(
        L.join(R, on=("k",), strategy="shuffle"))


def _builds() -> int:
    from repro.core import api

    return api._OP_CACHE.stats()["misses"]


def _spans_named(mark, *names):
    return [sp for sp in trace.get_trace(since=mark).spans if sp.name in names]


def test_compile_span_covers_the_first_call(ctx, traced, monkeypatch):
    mark, _ = traced
    run = _fresh_join(ctx, monkeypatch, seed=11)
    run()
    (first,) = _spans_named(mark, "core.compile")
    assert first.attrs["op"] == "join" and first.duration_s > 0
    assert _builds() == 1
    # the first call traced the program: trace-time decisions land in it
    assert first.attrs["kernel_dispatch"]
    mark = trace.mark()
    run()
    assert _spans_named(mark, "core.compile") == []
    (dispatch,) = _spans_named(mark, "core.dispatch")
    assert dispatch.attrs == {"op": "join"}
    assert _builds() == 1


def test_compile_span_off_records_nothing(ctx, monkeypatch):
    assert not trace.enabled()
    run = _fresh_join(ctx, monkeypatch, seed=13)
    mark = trace.mark()
    run()
    run()
    assert len(trace.get_trace(since=mark)) == 0
    assert _builds() == 1  # the op is still built once


# -- pattern-phase scopes in the compiled programs --------------------------------

def _op_names(monkeypatch, run) -> set:
    """Every ``op_name`` in the compiled HLO of the ops ``run`` builds."""
    from repro.core import api

    monkeypatch.setattr(api, "_OP_CACHE", api._LRUCache())
    texts = []
    call = api._Op.__call__

    def compiled_first(self, *flat):
        texts.append(self.jitted.lower(*flat).compile().as_text())
        return call(self, *flat)

    monkeypatch.setattr(api._Op, "__call__", compiled_first)
    run()
    return {n for t in texts for n in re.findall(r'op_name="([^"]*)"', t)}


@pytest.mark.parametrize("op,core", [("join", "local.join"),
                                     ("groupby", "local.groupby")])
def test_phase_scopes_reach_the_hlo(ctx, tables, monkeypatch, op, core):
    L, R = tables
    if op == "join":
        def run():
            return L.join(R, on=("k",), strategy="shuffle")
    else:
        def run():
            return L.groupby(("k",), {"v": ["sum"]})
    paths = _op_names(monkeypatch, run)
    segments = {seg for p in paths for seg in p.split("/")}
    assert {"partition", "shuffle", "shuffle.pack", "shuffle.unpack",
            "local", core} <= segments
    # each child sits under its kind
    assert any("/shuffle/shuffle.pack/" in p for p in paths)
    assert any(f"/local/{core}/" in p for p in paths)


# -- metrics registry ---------------------------------------------------------

def test_metrics_parent_chaining_and_restore():
    root = metrics.MetricsRegistry()
    child = metrics.MetricsRegistry(parent=root, prefix="run.")
    child.counter("batches").add(3)
    child.counter("batches").add(2)
    assert child.counter("batches").value == 5
    assert root.counter("run.batches").value == 5
    # restore is local-only: resumed checkpoint counts must not re-count
    # in the process totals
    child.counter("batches").restore(50)
    assert child.counter("batches").value == 50
    assert root.counter("run.batches").value == 5
    g = child.gauge("peak")
    g.max(10.0)
    g.max(4.0)
    assert g.value == 10.0
    assert root.gauge("run.peak").value == 10.0
    g.restore(100.0)
    assert root.gauge("run.peak").value == 10.0
    with pytest.raises(TypeError):
        child.gauge("batches")


def test_timing_summary():
    reg = metrics.MetricsRegistry()
    t = reg.timing("op")
    for s in (0.1, 0.3, 0.2):
        t.observe(s)
    summ = t.summary()
    assert summ["count"] == 3
    assert summ["total_s"] == pytest.approx(0.6)
    assert summ["min_s"] == pytest.approx(0.1)
    assert summ["max_s"] == pytest.approx(0.3)


# -- predicted-vs-observed accounting ------------------------------------------

def _four_op(ctx, tables):
    L, R = tables
    return (L.lazy().select((col("v") % 2).eq(0))
            .project(["k", "v"])
            .join(R.lazy(), on=("k",), strategy="shuffle", capacity=2000)
            .groupby(("k",), {"v": ("sum", "count")}))


def test_profiled_collect_bit_identical(ctx, tables):
    lz = _four_op(ctx, tables)
    base = lz.collect().to_numpy()
    got = lz.collect(profile=True).to_numpy()
    assert set(base) == set(got)
    for k in base:
        assert np.array_equal(base[k], got[k]), k
    prof = lz.last_profile
    assert prof is not None and prof.records
    report = prof.report()["model"]
    assert "shuffle_compute" in report
    for d in report.values():
        assert d["count"] >= 1 and d["observed_s"] >= 0.0
    text = prof.render()
    assert "predicted" in text and "per-pattern model error" in text


def test_explain_analyze(ctx, tables):
    lz = _four_op(ctx, tables)
    plain = lz.explain()
    analyzed = lz.explain(analyze=True)
    assert analyzed.startswith(plain)
    assert "per-pattern model error" in analyzed
    assert lz.last_info is not None  # it really executed


def test_stream_records_scan_and_shuffle_patterns(ctx, dataset, traced):
    """A streamed scan->groupby run while tracing records the paper's
    partitioned_io pattern per decoded batch plus the groupby's shuffle
    pattern per device dispatch."""
    _, mark = traced
    lz = (stream.scan_dataset(dataset, ctx, batch_rows=512)
          .groupby(("k",), {"v": ("sum",)}))
    out = lz.collect()
    assert int(np.asarray(out.counts).sum()) == 100
    recs = model_check.records(since=mark)
    patterns = {r.pattern for r in recs}
    assert "partitioned_io" in patterns  # one per decoded scan batch
    assert patterns & {"combine_shuffle_reduce", "shuffle_compute"}
    scans = [r for r in recs if r.pattern == "partitioned_io"]
    assert len(scans) == 8  # 4000 rows / 512-row batches
    for r in scans:
        assert r.observed_s >= 0.0 and r.observed_rows is not None
    report = model_check.model_report(recs)
    for d in report.values():
        assert {"count", "predicted_s", "observed_s", "mean_abs_rel_err",
                "bias"} <= set(d)


def test_stream_profiled_bit_identical_and_info_stable(ctx, dataset):
    lz = (stream.scan_dataset(dataset, ctx, batch_rows=512)
          .groupby(("k",), {"v": ("sum", "count")}))
    base = lz.collect().to_numpy()
    info_base = dict(lz.last_info)
    got = lz.collect(profile=True).to_numpy()
    info_prof = dict(lz.last_info)
    for k in base:
        assert np.array_equal(base[k], got[k]), k
    assert info_base["batches"] == info_prof["batches"] == 8
    assert info_base["peak_working_set_bytes"] > 0


def test_record_program_apportions_by_share(traced):
    preds = [
        {"node_index": 1, "op": "n1:Join", "pattern": "shuffle_compute",
         "predicted_s": 0.03, "predicted_rows": 10.0,
         "predicted_bytes": 80.0},
        {"node_index": 2, "op": "n2:GroupBy",
         "pattern": "combine_shuffle_reduce", "predicted_s": 0.01,
         "predicted_rows": 5.0, "predicted_bytes": 40.0},
    ]
    _, mark = traced
    model_check.record_program(preds, 0.4, observed_rows=5)
    recs = model_check.records(since=mark)
    assert len(recs) == 2
    total = sum(r.observed_s for r in recs)
    assert total == pytest.approx(0.4)
    join = next(r for r in recs if r.op == "n1:Join")
    gb = next(r for r in recs if r.op == "n2:GroupBy")
    assert join.observed_s == pytest.approx(0.3)
    assert join.meta["share"] == pytest.approx(0.75)
    assert join.observed_rows is None  # output attaches to the last op
    assert gb.observed_rows == 5


# -- kernel-dispatch + engine snapshot ----------------------------------------

def test_kernel_dispatch_counted(ctx, tables):
    before = metrics.registry().counters()
    lz = _four_op(ctx, tables)
    lz.collect()
    after = metrics.registry().counters()
    dispatched = {k: v - before.get(k, 0) for k, v in after.items()
                  if k.startswith("kernels.dispatch.")}
    assert sum(dispatched.values()) >= 0  # counters exist and are sane
    snap = obs.engine_snapshot()
    assert {"metrics", "caches", "kernel_backend"} <= set(snap)
    assert "plan" in snap["caches"] and "op" in snap["caches"]


# -- admission feedback (satellite: learned working-set corrections) ----------

def test_admission_learns_from_observed_peak(ctx, dataset):
    def q():
        return (stream.scan_dataset(dataset, ctx, batch_rows=512)
                .groupby(("k",), {"v": ("sum",)}))

    assert query_learn_key(q()) == query_learn_key(q())
    assert query_learn_key(lambda: None) is None
    with QueryService(max_running=2) as svc:
        s1 = svc.submit(q())
        s1.result()
        # the finished run taught the controller its shape's real peak
        stats1 = svc.admission.stats()
        assert stats1["observed_total"] >= 1
        assert stats1["learned_keys"] >= 1
        ratio = svc.admission.learned_ratio(q())
        assert ratio is not None and 0.125 <= ratio <= 8.0
        s2 = svc.submit(q())
        s2.result()
        # the second submission was costed with the learned correction
        assert s2.cost_bytes == pytest.approx(s2.cost_base * ratio, rel=0.6)
        assert np.array_equal(
            np.asarray(s1.result().to_numpy()["v_sum"]),
            np.asarray(s2.result().to_numpy()["v_sum"]))


def test_admission_ratio_clamped():
    ac = AdmissionController()

    class FakeSession:
        admission_key = "k1"
        cost_base = 100.0
        info = {"peak_working_set_bytes": 1e12}  # absurd observation

    ac.observe(FakeSession())
    with ac._lock:
        assert ac._learned["k1"] == 8.0  # clamped at the upper bound


def test_service_stats_include_trace(ctx, tables):
    with trace.tracing():
        with QueryService(max_running=2) as svc:
            h = svc.submit(_four_op(ctx, tables))
            h.result()
            st = svc.stats()
    assert st["trace"]["enabled"] is True
    assert "service.morsel" in st["trace"]["by_name"]
    assert "service.query" in st["trace"]["by_name"]
