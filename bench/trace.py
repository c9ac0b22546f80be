"""From a ``jax.profiler`` trace to the numbers the per-layer metrics read.

A trace is reduced to two lists: the operations each device ran (the
``XLA Ops`` line of each ``/device:`` plane) and the harness's own host
spans (``TraceAnnotation`` events named ``bench.*``). Everything else is
arithmetic on intervals, kept here so that every PR computes the same
number in the same way.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

# the harness's host spans; the window's own span bounds every reduction
PREFIX = "bench."
WINDOW = "bench.window"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # ns
    end: float    # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: dict          # device id -> [Event], sorted by start
    spans: list        # [Event], the harness's host spans

    @property
    def window(self) -> tuple[float, float]:
        w = [s for s in self.spans if s.name == WINDOW]
        if not w:
            raise ValueError(f"trace has no {WINDOW} span")
        return w[0].start, w[0].end


def load(trace_dir: str) -> Trace:
    """Read the ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"want one .xplane.pb under {trace_dir}, found {paths}")
    data = ProfileData.from_file(paths[0])
    ops, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            dev = int(plane.name.rsplit(":", 1)[1].split(" ")[0])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[dev] = sorted((Event(e.name, e.start_ns, e.end_ns)
                                       for e in line.events), key=lambda e: e.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [Event(e.name, e.start_ns, e.end_ns) for e in line.events
                          if e.name.startswith(PREFIX)]
    return Trace(ops, sorted(spans, key=lambda e: e.start))


# -- interval arithmetic --------------------------------------------------------

def clip(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """The events' intervals cut to [lo, hi], empty ones dropped."""
    out = [(max(e.start, lo), min(e.end, hi)) for e in events]
    return [(a, b) for a, b in out if b > a]


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted, non-overlapping intervals."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(events, lo: float, hi: float) -> float:
    """Time in [lo, hi] during which at least one event runs."""
    return sum(b - a for a, b in union(clip(events, lo, hi)))


def gaps(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of [lo, hi]: where no event runs."""
    out, t = [], lo
    for a, b in union(clip(events, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap: tuple[float, float], spans) -> str:
    """The innermost harness span open at the middle of ``gap``, or
    ``bench.none`` where none is."""
    mid = (gap[0] + gap[1]) / 2
    open_ = [s for s in spans if s.start <= mid < s.end]
    return max(open_, key=lambda s: (s.start, -s.end)).name if open_ else "bench.none"


# -- device ops by their HLO text ---------------------------------------------------
# On a TPU each event of the ``XLA Ops`` line is named by its HLO instruction:
# ``%sort.17 = (u32[50000000]{...}, s32[...]) sort(u32[50000000]{...} %a, ...),
# dimensions={0}, ...``. Its opcode and the types of its operands tell the
# kinds of work apart.

_OPCODE = re.compile(r"\s([a-z][a-z0-9_\-]*)\(")
_TYPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\](\{[^}]*\})?")


@dataclasses.dataclass(frozen=True)
class Array:
    dtype: str
    dims: tuple
    on_chip: bool  # the layout's memory space S(1): on-chip memory, not HBM


@dataclasses.dataclass(frozen=True)
class Hlo:
    opcode: str
    result: tuple    # (Array, ...)
    operands: tuple  # (Array, ...)
    attrs: str


def _types(text: str) -> tuple:
    return tuple(Array(t, tuple(int(d) for d in dims.split(",") if d), "S(1)" in layout)
                 for t, dims, layout in _TYPE.findall(text))


def hlo(e: Event) -> Hlo | None:
    """The HLO instruction an event names, or ``None`` for other names."""
    if " = " not in e.name:
        return None
    rhs = e.name.split(" = ", 1)[1]
    m = _OPCODE.search(" " + rhs)  # offsets in rhs are one less
    if m is None:
        return None
    start, depth = m.end() - 2, 0
    for i in range(start, len(rhs)):
        depth += {"(": 1, ")": -1}.get(rhs[i], 0)
        if depth == 0:
            break
    return Hlo(m.group(1), _types(rhs[:m.start()]), _types(rhs[start:i + 1]),
               rhs[i + 1:])


def ops_where(trace: Trace, pred) -> dict:
    """Per device, the events whose HLO satisfies ``pred``."""
    out = {}
    for dev, evs in trace.ops.items():
        out[dev] = [e for e in evs if (h := hlo(e)) is not None and pred(h)]
    return out


def share_of_busy(trace: Trace, pred) -> float | None:
    """Device time of the ops ``pred`` selects over device busy time, both
    summed over the devices; ``None`` where no op was selected."""
    lo, hi = trace.window
    chosen = ops_where(trace, pred)
    ns = sum(busy_ns(evs, lo, hi) for evs in chosen.values())
    busy = sum(device_busy(trace).values())
    if ns <= 0 or busy <= 0:
        return None
    return ns / busy


# -- what the harness reports -----------------------------------------------------

def device_busy(trace: Trace) -> dict:
    """Busy ns per device inside the window."""
    lo, hi = trace.window
    return {dev: busy_ns(evs, lo, hi) for dev, evs in trace.ops.items()}


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The ``breakdown`` of the result line: the device ops that took most
    time (seconds per device, averaged over the devices) and the longest
    idle gaps of the busiest device, each labelled with the harness span
    open during it."""
    lo, hi = trace.window
    per_op: dict[str, float] = {}
    for evs in trace.ops.values():
        for e in evs:
            ns = min(e.end, hi) - max(e.start, lo)
            if ns > 0:
                per_op[e.name] = per_op.get(e.name, 0.0) + ns
    n = max(len(trace.ops), 1)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    busy = device_busy(trace)
    idle = []
    if busy:
        dev = max(busy, key=busy.get)
        idle = sorted(gaps(trace.ops[dev], lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[name, ns / n / 1e9] for name, ns in ops],
            "idle_gaps": [[label(g, trace.spans), (g[1] - g[0]) / 1e9] for g in idle]}
