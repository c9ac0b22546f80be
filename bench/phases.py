"""Pattern phases and engine spans in a traced run of one cell.

  python3 bench/phases.py --workload <cell> --seed <n> --seconds <s>

Runs the cell once as ``bench/run.py --trace 1`` does, with the engine's
span recorder (``repro.obs.trace``) on from process start, and adds to the
result line what ``bench/trace.py``'s reduction does not keep:

- per device op, the pattern phase the engine's compiled programs name it
  with (``repro.obs.trace.phase``: ``partition``, ``shuffle.pack``,
  ``shuffle.exchange``, ``shuffle.unpack``, ``local.join``,
  ``local.groupby``, ``local.unique``, ``local.sort``): the ``op_name``
  metadata of its instruction in the HLO module that the profiler keeps
  for each program (the ``/host:metadata`` plane), the module being the
  one the device's ``XLA Modules`` line runs at the op's start. A fusion
  without an ``op_name`` of its own takes its root instruction's (see
  :func:`module_op_names`); an op under no phase counts in none;
- the engine's host spans (``core.*``, ``plan.*``, ``stream.*``,
  ``service.*``, ``kernels.*``): from the profiler's host plane inside the
  window, from the recorder before it (both on the wall clock).

It reports the five metrics below under ``metrics`` and labels the idle
gaps with the innermost span open in them, the harness's or the engine's.

=============================== ======= ===========================================
metric                          unit    what it reads
=============================== ======= ===========================================
``operators.partition_frac``    frac    device time under ``partition`` / busy time
``operators.shuffle_frac``      frac    the same for ``shuffle`` (pack, exchange, unpack)
``operators.local_frac``        frac    the same for ``local`` (join, groupby, unique, sort)
``compile.build_s``             s       seconds of ``core.compile`` spans in set-up
``compile.builds_in_window``    builds  ``core.compile`` spans inside the window
=============================== ======= ===========================================

Each reads ``None`` where it finds nothing: no op carries a phase, or the
recorder holds no engine span. JAX's persistent compile cache leaves
metadata out of its keys, so an executable compiled before the scopes
existed would come back without them: the run keys the cache on metadata
too (``jax_compilation_cache_include_metadata_in_key``), and compiles once
from cold after every change to the scopes.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import glob  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))

from bench import trace  # noqa: E402
from bench.trace import Event  # noqa: E402

KINDS = ("partition", "shuffle", "local")
ENGINE = ("core.", "plan.", "stream.", "service.", "kernels.")
COMPILE = "core.compile"
ORIGIN_STAT = "profile_start_time"
HLO_PROTO_STAT = "Hlo Proto"
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([^\s(]+) ")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?([^\s=]+) = ")
_META = re.compile(r'metadata=\{op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([^\s,]+)")


def phase_of(scope: str) -> str | None:
    """The innermost pattern phase of an ``op_name`` path
    (``jit(wrapper)/shuffle/shuffle.pack/jit(argsort)/sort`` ->
    ``shuffle.pack``), or ``None`` outside every phase."""
    for seg in reversed(scope.split("/")):
        if seg.split(".", 1)[0] in KINDS:
            return seg
    return None


@dataclasses.dataclass(frozen=True)
class Op(Event):
    phase: str | None = None


@dataclasses.dataclass
class Phases:
    ops: dict        # device id -> [Op], sorted by start
    spans: list      # [Event]: the harness's and the engine's host spans
    origin_ns: int | None  # wall-clock ns at which the planes count from 0

    @property
    def window(self) -> tuple[float, float]:
        return trace.Trace(self.ops, self.spans).window


# -- the HLO modules of an XSpace ------------------------------------------------
# ``ProfileData`` gives no access to the ``/host:metadata`` plane's event
# metadata, where the profiler keeps each program's HLO module; the few
# fields read here are decoded from the protobuf wire format. XSpace:
# planes = 1. XPlane: name = 2, event_metadata = 4 and stat_metadata = 5
# (maps: key = 1, value = 2). XEventMetadata: name = 2, stats = 5.
# XStatMetadata: name = 2. XStat: metadata_id = 1, bytes_value = 6.
# HloProto: hlo_module = 1. JAX 0.9 (the version the repo pins) exposes the
# HLO module printer only as ``jax._src.lib.xla_client._xla.HloModule``.

def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(number, value)`` of each field of one serialized message: an int
    for a varint, a slice for a length-delimited field; fixed-width fields
    are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield num, v


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def hlo_op_names(xspace: bytes) -> dict:
    """Program name (``jit_wrapper(12)``) -> {instruction -> ``op_name``},
    from the HLO modules in an XSpace's ``/host:metadata`` plane."""
    from jax._src.lib import xla_client

    opts = xla_client._xla.HloPrintOptions()
    opts.print_metadata = True
    out = {}
    for num, plane in _fields(memoryview(xspace)):
        if num != 1:
            continue
        fields = list(_fields(plane))
        if _str(dict(fields).get(2, b"")) != "/host:metadata":
            continue
        stat_names = {}
        for n, entry in fields:
            if n == 5:
                e = dict(_fields(entry))
                stat_names[e.get(1, 0)] = _str(dict(_fields(e.get(2, b""))).get(2, b""))
        for n, entry in fields:
            if n != 4:
                continue
            meta = list(_fields(dict(_fields(entry)).get(2, b"")))
            for k, stat in meta:
                st = dict(_fields(stat)) if k == 5 else {}
                if stat_names.get(st.get(1, 0)) == HLO_PROTO_STAT and 6 in st:
                    module = xla_client._xla.HloModule.from_serialized_hlo_module_proto(
                        bytes(dict(_fields(st[6]))[1]))
                    out[_str(dict(meta).get(2, b""))] = module_op_names(
                        module.to_string(opts))
    return out


def module_op_names(text: str) -> dict:
    """Instruction -> ``op_name`` of an HLO module's text. A fusion with no
    ``op_name`` of its own takes its fused computation's root's, or where
    the root has none either (XLA merges the two word-scatters of a 64-bit
    column into one root it gives no metadata), the op name most of the
    computation's instructions that name a phase carry."""
    names, calls, roots, inside = {}, {}, {}, {}
    comp = None
    for line in text.splitlines():
        if (m := _COMPUTATION.match(line)) and line.rstrip().endswith("{"):
            comp = m.group(1)
            inside[comp] = []
        elif comp is not None and (m := _INSTRUCTION.match(line)):
            instr = m.group(2)
            meta = _META.search(line)
            names[instr] = meta.group(1) if meta else ""
            inside[comp].append(names[instr])
            if m.group(1):
                roots[comp] = names[instr]
            if not meta and (c := _CALLS.search(line)):
                calls[instr] = c.group(1)
    for instr, comp in calls.items():
        phased = [n for n in inside.get(comp, []) if phase_of(n)]
        names[instr] = roots.get(comp) or (
            collections.Counter(phased).most_common(1)[0][0] if phased else "")
    return {i: n for i, n in names.items() if n}


def _base(program: str) -> str:
    return re.sub(r"\(\d+\)$", "", program)


def op_name(programs: dict, program: str | None, instruction: str) -> str:
    """The ``op_name`` of ``instruction`` in ``program``; where the device
    names its program otherwise, in the one module of that base name (or,
    failing that, of any name) that holds the instruction."""
    if program in programs:
        return programs[program].get(instruction, "")
    found = [names[instruction] for name, names in programs.items()
             if instruction in names and (program is None or _base(name) == _base(program))]
    return found[0] if len(set(found)) == 1 else ""


def reduce_profile(data, programs: dict) -> Phases:
    """The phases and host spans of a ``jax.profiler.ProfileData``, with
    the op names of :func:`hlo_op_names`."""
    ops, spans, origin = {}, [], None
    for plane in data.planes:
        origin = dict(plane.stats).get(ORIGIN_STAT, origin)
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            dev = int(plane.name.rsplit(":", 1)[1].split(" ")[0])
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((e.start_ns, e.end_ns, e.name)
                             for e in lines.get("XLA Modules", []))
            starts = [m[0] for m in modules]
            evs = []
            for e in lines.get("XLA Ops", []):
                i = bisect.bisect_right(starts, e.start_ns) - 1
                program = modules[i][2] if i >= 0 and e.start_ns < modules[i][1] else None
                instruction = e.name.split(" = ", 1)[0].lstrip("%")
                evs.append(Op(e.name, e.start_ns, e.end_ns,
                              phase_of(op_name(programs, program, instruction))))
            ops[dev] = sorted(evs, key=lambda e: e.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [Event(e.name, e.start_ns, e.end_ns) for e in line.events
                          if e.name.startswith((trace.PREFIX,) + ENGINE)]
    return Phases(ops, sorted(spans, key=lambda e: e.start),
                  None if origin is None else int(origin))


def load(trace_dir: str) -> Phases:
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    with open(path, "rb") as f:
        xspace = f.read()
    return reduce_profile(ProfileData.from_serialized_xspace(xspace), hlo_op_names(xspace))


def recorded(spans, origin_ns: int) -> list:
    """The recorder's spans as events on the planes' time axis."""
    return [Event(sp.name, sp.t0 * 1e9 - origin_ns, sp.t1 * 1e9 - origin_ns)
            for sp in spans]


# -- the metrics ---------------------------------------------------------------

def phase_frac(ph: Phases, kind: str) -> float | None:
    """Device time of the ops under phase ``kind`` over device busy time,
    both summed over the devices; ``None`` where no op carries a phase."""
    if not any(op.phase for evs in ph.ops.values() for op in evs):
        return None
    lo, hi = ph.window
    busy = sum(trace.busy_ns(evs, lo, hi) for evs in ph.ops.values())
    ns = sum(trace.busy_ns([op for op in evs if op.phase and op.phase.split(".")[0] == kind],
                           lo, hi) for evs in ph.ops.values())
    return ns / busy if busy > 0 else None


def builds(ph: Phases, engine: list) -> tuple[float, int] | None:
    """(seconds of ``core.compile`` spans before the window, their number
    inside it) from the recorder's ``engine`` spans on the planes' axis;
    ``None`` where the recorder holds no engine span."""
    if not any(e.name.startswith(ENGINE) for e in engine):
        return None
    lo, hi = ph.window
    compiles = [e for e in engine if e.name == COMPILE]
    return (sum(e.dur for e in compiles if e.start < lo) / 1e9,
            sum(lo <= e.start < hi for e in compiles))


def metrics(ph: Phases, engine: list) -> dict:
    """The five metrics, ``None`` where they find nothing."""
    b = builds(ph, engine)
    return {"operators.partition_frac": phase_frac(ph, "partition"),
            "operators.shuffle_frac": phase_frac(ph, "shuffle"),
            "operators.local_frac": phase_frac(ph, "local"),
            "compile.build_s": None if b is None else b[0],
            "compile.builds_in_window": None if b is None else b[1]}


def idle_gaps(ph: Phases, engine: list, top: int = 10) -> list:
    """The busiest device's longest idle gaps, each labelled with the
    innermost span open at its middle: the harness's, or the engine's from
    the host plane or (for spans the plane lacks) the recorder."""
    lo, hi = ph.window
    busy = {dev: trace.busy_ns(evs, lo, hi) for dev, evs in ph.ops.items()}
    if not busy:
        return []
    dev = max(busy, key=busy.get)
    plane = {e.name for e in ph.spans}
    spans = ph.spans + [e for e in engine if e.name not in plane]
    gaps = sorted(trace.gaps(ph.ops[dev], lo, hi), key=lambda g: g[0] - g[1])[:top]
    return [[trace.label(g, spans), (g[1] - g[0]) / 1e9] for g in gaps]


# -- one run ---------------------------------------------------------------------

def main(argv=None) -> int:
    """One traced run of the cell through ``harness.run``, whose profile it
    reads by standing in for ``bench.trace.load`` (the harness hands its
    readers only that reduction, and deletes the profile after it)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from repro.obs import trace as obs_trace

    obs_trace.enable()
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    from bench import harness

    cell = harness.find_cell(args.workload)
    try:
        devices = harness.find_chips(cell.chips)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    seen = {}
    harness_load = trace.load

    def load_both(tdir):
        # the harness deletes the profile once its own reduction is done
        seen["phases"] = load(tdir)
        return harness_load(tdir)

    trace.load = load_both
    result = harness.run(args.workload, args.seed, args.seconds, True, devices, T_START)
    ph = seen["phases"]
    engine = [] if ph.origin_ns is None else recorded(obs_trace.get_trace().spans,
                                                     ph.origin_ns)
    for name, value in metrics(ph, engine).items():
        if value is not None:
            unit = {"compile.build_s": "s", "compile.builds_in_window": "builds"}
            result["metrics"][name] = {"value": value, "unit": unit.get(name, "frac")}
    result["breakdown"]["idle_gaps"] = idle_gaps(ph, engine)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
