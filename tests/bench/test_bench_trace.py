"""The reduction from a profiler trace to the per-layer metrics, on a small
synthetic trace whose answers are worked out by hand."""

import bench_cases  # noqa: F401  (puts the checkout on sys.path)
import pytest

from bench import harness, trace
from bench.trace import Event, Trace

MS = 1e6  # ns


# op names as a v5e's trace gives them (from traced runs on the chip)
SORT = ("%sort.17 = (u32[50000000]{0:T(1024)}, s32[50000000]{0:T(1024)}) "
        "sort(u32[50000000]{0:T(1024)} %pad_add_fusion, s32[50000000]{0:T(1024)} "
        "%iota.6), dimensions={0}, is_stable=true, to_apply=%region_17.29")
GATHER = ("%fusion.22 = s32[50000000]{0:T(1024)} fusion(s32[50000000]{0:T(1024)} "
          "%fusion.19, s32[50000896]{0:T(1024)} %pad_clamp_fusion.2), kind=kCustom, "
          "calls=%fused_computation.22")
SCATTER = ("%fusion.29 = s32[50000000]{0:T(1024)} fusion(s32[25000000]{0:T(1024)} "
           "%get-tuple-element.74, s32[25000000]{0:T(1024)} %get-tuple-element.75, "
           "s32[]{:T(128)} %constant.81), kind=kCustom, calls=%fused_computation.148")
# a scatter of an int64 column, as two u32 words over broadcast fills
SCATTER64 = ("%fusion.45 = (u32[25000000]{0:T(1024)S(1)}, u32[25000000]{0:T(1024)}) "
             "fusion(u32[25000000]{0:T(1024)S(1)} %broadcast.44.clone.2, u32[25000000]"
             "{0:T(1024)} %broadcast.44.clone.3, s32[25000000]{0:T(1024)} "
             "%broadcast_select_fusion.1, u32[25000000]{0:T(1024)} %fusion.3, "
             "u32[25000000]{0:T(1024)} %fusion.4), kind=kCustom, "
             "calls=%fused_computation.45")
GATHER64 = ("%fusion.30 = u32[50000000]{0:T(1024)} fusion(u32[50000000]{0:T(1024)} "
            "%fusion.28, s32[50000896]{0:T(1024)} %pad_clamp_fusion.2), kind=kCustom, "
            "calls=%fused_computation.30")
HASH = ("%wrapper.3 = s32[195584,128]{1,0:T(8,128)} custom-call(u32[195584,128]"
        "{1,0:T(8,128)} %pad_bitcast_fusion), custom_call_target=\"tpu_custom_call\", "
        "operand_layout_constraints={u32[195584,128]{1,0}}, "
        "frontend_attributes={kernel_metadata={}}")
A2A = ("%all-to-all.3 = s32[4,100]{1,0} all-to-all(s32[4,100]{1,0} %x), "
       "replica_groups={{0,1,2,3}}, dimensions={0}")


def _trace():
    # window [0, 100] ms; device 0 busy 10-30 (two overlapping ops), 40-50,
    # 60-90; device 1 busy 0-20; host spans: the window, two queries, a fetch
    d0 = [Event(SORT, 10 * MS, 25 * MS), Event(GATHER, 20 * MS, 30 * MS),
          Event(A2A, 40 * MS, 50 * MS), Event(HASH, 60 * MS, 90 * MS)]
    d1 = [Event(SORT, -5 * MS, 20 * MS)]
    spans = [Event("bench.window", 0, 100 * MS), Event("bench.query", 0, 55 * MS),
             Event("bench.query", 55 * MS, 100 * MS), Event("bench.fetch", 92 * MS, 99 * MS)]
    return Trace({0: d0, 1: d1}, spans)


def test_hlo_names_parse():
    h = trace.hlo(Event(SORT, 0, 1))
    assert h.opcode == "sort" and [(a.dtype, a.dims) for a in h.result] == [
        ("u32", (50000000,)), ("s32", (50000000,))]
    assert h.operands == h.result and "is_stable=true" in h.attrs
    h = trace.hlo(Event(SCATTER, 0, 1))
    assert h.opcode == "fusion" and h.operands[2].dims == () and h.operands[0].dtype == "s32"
    h = trace.hlo(Event(HASH, 0, 1))
    assert h.opcode == "custom-call" and h.operands == (trace.Array("u32", (195584, 128), False),)
    assert trace.hlo(Event("bench.query", 0, 1)) is None


def test_union_gaps_and_busy():
    tr = _trace()
    assert trace.union([(3, 4), (1, 2), (1.5, 3)]) == [(1, 4)]
    assert trace.device_busy(tr) == {0: 60 * MS, 1: 20 * MS}
    assert trace.gaps(tr.ops[0], 0, 100 * MS) == [
        (0, 10 * MS), (30 * MS, 40 * MS), (50 * MS, 60 * MS), (90 * MS, 100 * MS)]
    assert trace.gaps([], 0, 5) == [(0, 5)]


def test_labels_and_breakdown():
    tr = _trace()
    # the innermost span open at the gap's middle names it
    assert trace.label((90 * MS, 100 * MS), tr.spans) == "bench.fetch"
    assert trace.label((30 * MS, 40 * MS), tr.spans) == "bench.query"
    assert trace.label((200 * MS, 300 * MS), tr.spans) == "bench.none"
    b = trace.breakdown(tr, top=2)
    # per-device average of op time inside the window: sort.1 is 15 + 20 ms
    assert b["device_ops"] == [[SORT, pytest.approx(0.0175)],
                               [HASH, pytest.approx(0.015)]]
    assert [g[0] for g in b["idle_gaps"]] == ["bench.query", "bench.query"]
    assert b["idle_gaps"][0][1] == pytest.approx(0.01)


def _reading(tr):
    watch = harness.CompileWatch()
    watch.secs["setup"], watch.count["window"] = 12.5, 0
    return harness.Reading(trace=tr, compile=watch)


def _read(name, r):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read(r)


def test_metric_readers():
    r = _reading(_trace())
    assert _read("device.idle_frac", r) == pytest.approx(0.4)
    # sort time 15 ms on device 0 and 20 ms on device 1, over 80 ms busy
    assert _read("operators.sort_frac", r) == pytest.approx(35 / 80)
    assert _read("operators.gather_frac", r) == pytest.approx(10 / 80)
    assert _read("compile.setup_s", r) == 12.5
    assert _read("compile.in_window", r) == 0
    # no scatter ran: nothing to read
    assert _read("operators.scatter_frac", r) is None


def test_64_bit_scatters_and_gathers():
    # an int64 column moves as two u32 words: its scatter writes both at
    # one s32 index over broadcast fills; its gathers are one word each
    tr = Trace({0: [Event(SCATTER64, 0, 6 * MS), Event(GATHER64, 6 * MS, 8 * MS),
                    Event(SCATTER, 8 * MS, 9 * MS), Event(SORT, 9 * MS, 10 * MS)]},
               [Event("bench.window", 0, 10 * MS)])
    r = _reading(tr)
    assert _read("operators.scatter_frac", r) == pytest.approx(0.7)
    assert _read("operators.gather_frac", r) == pytest.approx(0.2)
    assert _read("operators.sort_frac", r) == pytest.approx(0.1)


def test_readers_find_nothing_in_an_empty_trace():
    r = _reading(Trace({}, [Event("bench.window", 0, 100 * MS)]))
    for name in ("device.idle_frac", "operators.sort_frac", "operators.gather_frac",
                 "operators.scatter_frac"):
        assert harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read(r) is None
