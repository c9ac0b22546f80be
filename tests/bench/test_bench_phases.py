"""``bench/phases.py``: pattern phases and engine spans from a profile, on a
small synthetic profile whose answers are worked out by hand."""

import bench_cases  # noqa: F401  (puts the checkout on sys.path)
import pytest

from bench import harness, phases, trace
from bench.trace import Event

MS = 1e6  # ns
ORIGIN = 1_700_000_000_000_000_000  # wall-clock ns at which the planes start

# op names as a v5e's trace gives them (tests/bench/test_bench_trace.py)
SCATTER64 = ("%fusion.45 = (u32[25000000]{0:T(1024)S(1)}, u32[25000000]{0:T(1024)}) "
             "fusion(u32[25000000]{0:T(1024)S(1)} %broadcast.44.clone.2, u32[25000000]"
             "{0:T(1024)} %broadcast.44.clone.3, s32[25000000]{0:T(1024)} "
             "%broadcast_select_fusion.1, u32[25000000]{0:T(1024)} %fusion.3, "
             "u32[25000000]{0:T(1024)} %fusion.4), kind=kCustom, "
             "calls=%fused_computation.45")
GATHER64 = ("%fusion.30 = u32[50000000]{0:T(1024)} fusion(u32[50000000]{0:T(1024)} "
            "%fusion.28, s32[50000896]{0:T(1024)} %pad_clamp_fusion.2), kind=kCustom, "
            "calls=%fused_computation.30")
SORT = ("%sort.17 = (u32[50000000]{0:T(1024)}, s32[50000000]{0:T(1024)}) "
        "sort(u32[50000000]{0:T(1024)} %pad_add_fusion, s32[50000000]{0:T(1024)} "
        "%iota.6), dimensions={0}, is_stable=true, to_apply=%region_17.29")


class _E:
    def __init__(self, name, start, end, **stats):
        self.name, self.start_ns, self.end_ns = name, start, end
        self.stats = list(stats.items())


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines, **stats):
        self.name, self.lines, self.stats = name, lines, list(stats.items())


class _Data:
    def __init__(self, planes):
        self.planes = planes


W = "jit(wrapper)"
# the program's op names, as its HLO module's metadata gives them
PROGRAMS = {"jit_wrapper(7)": {
    "fusion.1": f"{W}/partition/rem",
    "sort.17": f"{W}/shuffle/shuffle.pack/jit(argsort)/sort",
    "fusion.45": f"{W}/shuffle/shuffle.pack/scatter",
    "fusion.46": f"{W}/shuffle/shuffle.unpack/scatter",
    "fusion.30": f"{W}/local/local.join/local/local.sort/gather",
    "fusion.31": f"{W}/local/local.join/gather",
    "copy.1": "x",
    "all-to-all.2": f"{W}/shuffle/shuffle.exchange/all-to-all"}}


def _op(name, a, b):
    return _E(name, a * MS, b * MS)


def _profile():
    # window [0, 100] ms. Device 0: partition 0-2, pack sort 2-12, pack
    # scatter 12-42, unpack scatter 42-52, local.sort gather 52-80 (inside
    # local.join), local.join gather 80-90, an op under no phase 90-95;
    # idle 95-100 while core.dispatch is open. Device 1: the exchange 0-50,
    # under a program the device names without its id.
    d0 = [_op("%fusion.1 = u32[8] fusion()", 0, 2),
          _op(SORT, 2, 12),
          _op(SCATTER64, 12, 42),
          _op(SCATTER64.replace("%fusion.45 =", "%fusion.46 ="), 42, 52),
          _op(GATHER64, 52, 80),
          _op(GATHER64.replace("%fusion.30 =", "%fusion.31 ="), 80, 90),
          _op("%copy.1 = u32[8] copy()", 90, 95)]
    d1 = [_op("%all-to-all.2 = s32[1,50000000]{1,0} all-to-all(s32[1,50000000]{1,0} %x)",
              0, 50)]
    host = [_E("bench.window", 0, 100 * MS), _E("bench.query", 0, 100 * MS),
            _E("bench.dispatch", 93 * MS, 99 * MS),
            _E("core.dispatch", 94 * MS, 98 * MS, op="join"),
            _E("$python frame", 0, 1)]
    return _Data([
        _Plane("/device:TPU:0", [_Line("XLA Modules", [_op("jit_wrapper(7)", 0, 95)]),
                                 _Line("XLA Ops", d0), _Line("Steps", [_E("1", 0, 1)])]),
        _Plane("/device:TPU:1 (pid 3)", [_Line("XLA Modules", [_op("jit_wrapper", 0, 50)]),
                                         _Line("XLA Ops", d1)]),
        _Plane("/host:CPU", [_Line("python", host)]),
        _Plane("Task Environment", [], profile_start_time=ORIGIN)])


class _Span:  # a recorder span: wall-clock seconds
    def __init__(self, name, t0_ms, t1_ms):
        self.name = name
        self.t0, self.t1 = (ORIGIN + t0_ms * MS) / 1e9, (ORIGIN + t1_ms * MS) / 1e9


def _engine():
    # two builds in set-up (3 s and 1 s, before the window), one core.compile
    # in the window, and a retroactive span the plane lacks
    return phases.recorded([_Span("core.compile", -9000, -6000),
                            _Span("core.compile", -2000, -1000),
                            _Span("core.compile", 50, 60),
                            _Span("stream.stage", 90, 100)], ORIGIN)


def test_phase_of_takes_the_innermost_phase():
    assert phases.phase_of("jit(w)/shuffle/shuffle.pack/jit(argsort)/sort") == "shuffle.pack"
    assert phases.phase_of("jit(w)/local/local.join/local/local.sort/gather") == "local.sort"
    assert phases.phase_of("jit(w)/partition/rem") == "partition"
    assert phases.phase_of("jit(w)/jit(localize)/sort") is None
    assert phases.phase_of("") is None


def test_profile_reduction():
    ph = phases.reduce_profile(_profile(), PROGRAMS)
    assert ph.origin_ns == ORIGIN and ph.window == (0, 100 * MS)
    assert set(ph.ops) == {0, 1}
    assert [op.phase for op in ph.ops[0]] == [
        "partition", "shuffle.pack", "shuffle.pack", "shuffle.unpack",
        "local.sort", "local.join", None]
    # the harness's spans and the engine's, not the rest of the host plane
    assert [e.name for e in ph.spans] == ["bench.window", "bench.query",
                                          "bench.dispatch", "core.dispatch"]
    assert [op.phase for op in ph.ops[1]] == ["shuffle.exchange"]


def test_phase_metrics():
    ph = phases.reduce_profile(_profile(), PROGRAMS)
    m = phases.metrics(ph, _engine())
    busy = 95 + 50  # ms, both devices
    assert m["operators.partition_frac"] == pytest.approx(2 / busy)
    assert m["operators.shuffle_frac"] == pytest.approx((50 + 50) / busy)
    assert m["operators.local_frac"] == pytest.approx(38 / busy)
    # the unscoped copy counts in none of the three
    assert sum(m[f"operators.{k}_frac"] for k in phases.KINDS) == pytest.approx(140 / busy)
    assert m["compile.build_s"] == pytest.approx(4.0)
    assert m["compile.builds_in_window"] == 1


def test_idle_gap_takes_the_engine_span():
    ph = phases.reduce_profile(_profile(), PROGRAMS)
    gaps = phases.idle_gaps(ph, _engine())
    # 95-100 ms on device 0: core.dispatch (94-98) is open at its middle,
    # inside bench.dispatch; the harness's own label stops at bench.dispatch
    assert gaps == [["core.dispatch", pytest.approx(0.005)]]
    assert trace.label((95 * MS, 100 * MS), [e for e in ph.spans
                                            if e.name.startswith("bench.")]) == "bench.dispatch"
    # a span only the recorder holds (a retroactive one) labels a gap too
    assert phases.idle_gaps(ph, _engine() + phases.recorded(
        [_Span("stream.stage", 96, 99)], ORIGIN))[0][0] == "stream.stage"


def test_nothing_to_read_without_phases_or_recorder():
    # a program that names no phase and records no span: every metric None
    bare = {"jit_wrapper(7)": {i: f"{W}/mul" for i in PROGRAMS["jit_wrapper(7)"]}}
    for programs in (bare, {}):
        ph = phases.reduce_profile(_profile(), programs)
        assert all(v is None for v in phases.metrics(ph, []).values())


def test_op_name_resolves_the_program():
    programs = {"jit_wrapper(7)": {"fusion.1": "a/partition"},
                "jit_wrapper(9)": {"fusion.1": "a/local/local.sort", "fusion.2": "b"},
                "jit_other(3)": {"fusion.3": "c"}}
    assert phases.op_name(programs, "jit_wrapper(9)", "fusion.1") == "a/local/local.sort"
    assert phases.op_name(programs, "jit_wrapper(9)", "fusion.3") == ""
    # unnamed or id-less programs: the one module that holds the instruction
    assert phases.op_name(programs, "jit_wrapper", "fusion.2") == "b"
    assert phases.op_name(programs, None, "fusion.3") == "c"
    # two modules disagree: nothing
    assert phases.op_name(programs, "jit_wrapper", "fusion.1") == ""


# a v5e module's text, cut down: the two word-scatters of a 64-bit column
# merged into one root without metadata (fusion.45), a fusion whose root
# names its op (fusion.30), and one with an op name of its own (sort.17)
MODULE = """HloModule jit_wrapper, is_scheduled=true

%fused_computation.45 (param_0.1: u32[8], param_1.1: s32[8], param_2.1: u32[8]) -> (u32[8], u32[8]) {
  %param_0.1 = u32[8]{0:T(1024)S(1)} parameter(0)
  %param_2.1 = u32[8]{0} parameter(2)
  %reshape.657 = u32[8]{0} reshape(%param_2.1), metadata={op_name="jit(wrapper)/shuffle/shuffle.pack/gather" stack_frame_id=1}
  %transpose.362 = u32[8]{0} transpose(%reshape.657), dimensions={0}, metadata={op_name="jit(wrapper)/shuffle/shuffle.pack/gather" stack_frame_id=1}
  %copy.1 = u32[8]{0} copy(%param_0.1), metadata={op_name="jit(wrapper)/local/local.join/copy"}
  ROOT %scatter.81 = (u32[8]{0}, u32[8]{0}) scatter(%param_0.1, %param_0.1, %param_1.1, %transpose.362, %transpose.362), update_window_dims={}, to_apply=%region_4.13.clone
}

%fused_computation.30 (param_0.2: u32[8], param_1.2: s32[8]) -> u32[8] {
  %param_0.2 = u32[8]{0} parameter(0)
  ROOT %gather.3 = u32[8]{0} gather(%param_0.2, %param_1.2), slice_sizes={1}, metadata={op_name="jit(wrapper)/local/local.join/gather" stack_frame_id=2}
}

ENTRY %main.1 (x.1: u32[8]) -> u32[8] {
  %x.1 = u32[8]{0} parameter(0), metadata={op_name="flat[0]"}
  %sort.17 = u32[8]{0} sort(%x.1), dimensions={0}, to_apply=%region_0.1, metadata={op_name="jit(wrapper)/shuffle/shuffle.pack/jit(argsort)/sort" stack_frame_id=3}
  %fusion.45 = (u32[8]{0:T(1024)S(1)}, u32[8]{0}) fusion(%x.1, %sort.17, %x.1), kind=kCustom, calls=%fused_computation.45, backend_config={"a":"b"}
  %fusion.30 = u32[8]{0} fusion(%x.1, %sort.17), kind=kCustom, calls=%fused_computation.30
  ROOT %copy.2 = u32[8]{0} copy(%fusion.30)
}
"""


def test_module_op_names_take_the_fusions_root_or_its_majority():
    names = phases.module_op_names(MODULE)
    assert names["sort.17"] == "jit(wrapper)/shuffle/shuffle.pack/jit(argsort)/sort"
    assert phases.phase_of(names["fusion.45"]) == "shuffle.pack"
    assert phases.phase_of(names["fusion.30"]) == "local.join"
    assert "copy.2" not in names and names["x.1"] == "flat[0]"


def test_hlo_op_names_from_a_real_profile(tmp_path):
    # a CPU profile's /host:metadata plane holds each program's HLO module
    import jax
    import jax.numpy as jnp
    from repro.obs.trace import phase

    def f(x):
        with phase("shuffle.pack"):
            y = jnp.sort(x)
        with phase("local.join"):
            return y * 3

    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(jax.jit(f)(jnp.arange(64.0)))
    (path,) = tmp_path.glob("**/*.xplane.pb")
    programs = phases.hlo_op_names(path.read_bytes())
    (name,) = [n for n in programs if n.startswith("jit_f(")]
    scopes = {phases.phase_of(v) for v in programs[name].values()}
    assert {"shuffle.pack", "local.join"} <= scopes


def test_existing_readers_unchanged_on_the_phase_profile():
    # bench/trace.py's reduction of the same events, and its six readers
    ph = phases.reduce_profile(_profile(), PROGRAMS)
    tr = trace.Trace({d: [Event(o.name, o.start, o.end) for o in evs]
                      for d, evs in ph.ops.items()},
                     [e for e in ph.spans if e.name.startswith("bench.")])
    watch = harness.CompileWatch()
    r = harness.Reading(trace=tr, compile=watch)

    def read(name):
        return harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read(r)

    busy = 95 + 50
    assert read("operators.scatter_frac") == pytest.approx((30 + 10) / busy)
    assert read("operators.gather_frac") == pytest.approx(38 / busy)
    assert read("operators.sort_frac") == pytest.approx(10 / busy)
    assert read("device.idle_frac") == pytest.approx(0.05)
    assert read("compile.setup_s") == 0.0 and read("compile.in_window") == 0
