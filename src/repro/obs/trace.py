"""Structured tracing: nestable spans with a process-wide recorder.

A span times one unit of engine work — a morsel decode, a compiled-program
dispatch, a service scheduling quantum — and nests per thread (the
streaming prefetch thread and the service driver thread each keep their
own span stack; the recorder they append to is shared and lock-guarded).

Near-zero cost when disabled (the default): :func:`span` returns one
shared no-op handle, so the hot paths pay a single boolean check and no
per-call object allocation. Enable with :func:`enable` / :func:`tracing`,
or process-wide via the ``REPRO_TRACE=1`` environment variable.

One timeline with the device: while recording, a live span also opens a
``jax.profiler.TraceAnnotation`` of the same name, and :func:`now` counts
wall-clock seconds, the clock of the profiler's host plane. Under
``jax.profiler.trace(dir, create_perfetto_trace=True)`` the engine's
spans and the device's ops land in one trace that Perfetto and
TensorBoard open; retroactive spans line up with it through the shared
clock. Inside compiled programs, :func:`phase` names the paper's pattern
phases (``partition``, ``shuffle.*``, ``local.*``) as JAX named scopes,
which reach every device op's ``op_name`` metadata.

Intervals that do not nest on a call stack (a streaming stage suspended
and resumed across service quanta, a query's whole lifetime closed from
the scheduler) are recorded retroactively with :func:`complete` from
explicit :func:`now` timestamps, so interleaved queries never corrupt a
thread's span stack.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import threading
import time

__all__ = [
    "Span",
    "Trace",
    "complete",
    "current_span",
    "disable",
    "enable",
    "enabled",
    "get_trace",
    "instant",
    "mark",
    "now",
    "phase",
    "reset",
    "span",
    "summary",
    "tracing",
]

# backstop against unbounded growth in long-lived traced processes; the
# drop count is surfaced on the Trace so truncation is never silent
_MAX_EVENTS = 1_000_000

_enabled = os.environ.get("REPRO_TRACE", "") not in ("", "0")
_lock = threading.Lock()
_events: list = []
_dropped = 0
_ids = itertools.count(1)
_tls = threading.local()
# jax.profiler.TraceAnnotation, imported on the first recorded span
_Annotation = None
# the monotonic counter's distance from the wall clock, taken once: spans
# sit on the profiler's wall-clock timeline, and a step of the wall clock
# later in the run moves none of their durations
_WALL_NS = time.time_ns() - time.perf_counter_ns()


def now() -> float:
    """Wall-clock seconds as of import, advanced by the monotonic counter
    (``time.perf_counter_ns``) — the spans' clock, and the clock of
    ``jax.profiler``'s host plane.

    Use with :func:`complete` to record intervals retroactively."""
    return (time.perf_counter_ns() + _WALL_NS) / 1e9


def _annotation(name: str, attrs: dict):
    """The profiler's twin of a live span; its scalar attrs become the
    event's stats."""
    global _Annotation
    if _Annotation is None:
        from jax.profiler import TraceAnnotation as _Annotation
    return _Annotation(name, **{k: v for k, v in attrs.items()
                                if isinstance(v, (str, int, float, bool))})


@contextlib.contextmanager
def phase(name: str):
    """Name a pattern phase inside a traced JAX function (always on).

    Opens ``jax.named_scope`` for the phase's kind and then for the phase
    itself — ``phase("shuffle.pack")`` scopes ops as ``shuffle/shuffle.pack``
    — so the ``op_name`` metadata of every device op says which auxiliary,
    communication or core local op it belongs to (paper §4). Scopes change
    HLO metadata only, never the computation; usable as a decorator."""
    import jax

    kind = name.split(".", 1)[0]
    with jax.named_scope(kind):
        if kind == name:
            yield
        else:
            with jax.named_scope(name):
                yield


def enabled() -> bool:
    """True when spans are currently being recorded."""
    return _enabled


def enable() -> None:
    """Start recording spans (process-global, all threads)."""
    global _enabled
    _enabled = True


def disable() -> None:
    """Stop recording spans; spans already recorded are kept."""
    global _enabled
    _enabled = False


def reset() -> None:
    """Drop every recorded span (the enabled flag is unchanged)."""
    global _dropped
    with _lock:
        _events.clear()
        _dropped = 0


def mark() -> int:
    """Current recorded-span count; pass as ``since`` to :func:`get_trace`
    to scope a later snapshot to spans recorded after this point."""
    with _lock:
        return len(_events)


def _record(sp: "Span") -> None:
    global _dropped
    with _lock:
        if len(_events) < _MAX_EVENTS:
            _events.append(sp)
        else:
            _dropped += 1


class Span:
    """One recorded (or in-flight) span: a name, a wall interval, attrs.

    Use via :func:`span` as a context manager; inside the ``with`` block,
    :meth:`set` (or mutating ``attrs`` directly) attaches data — e.g. the
    kernel registry appends its dispatch decisions to the enclosing span's
    ``attrs["kernel_dispatch"]`` list."""

    __slots__ = ("sid", "parent", "name", "cat", "t0", "t1", "tid",
                 "thread", "attrs", "_ann")

    def __init__(self, name: str, cat: str | None = None,
                 attrs: dict | None = None):
        self.sid = next(_ids)
        self.parent: int | None = None
        self.name = name
        self.cat = cat
        self.t0 = 0.0
        self.t1 = 0.0
        self.tid = 0
        self.thread = ""
        self.attrs = {} if attrs is None else attrs
        self._ann = None

    def set(self, **attrs):
        """Attach attributes to this span; returns the span."""
        self.attrs.update(attrs)
        return self

    @property
    def duration_s(self) -> float:
        """Recorded wall seconds (0.0 while still open)."""
        return max(self.t1 - self.t0, 0.0)

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self.parent = stack[-1].sid if stack else None
        t = threading.current_thread()
        self.tid = t.ident or 0
        self.thread = t.name
        stack.append(self)
        self._ann = _annotation(self.name, self.attrs)
        self._ann.__enter__()
        self.t0 = now()
        return self

    def __exit__(self, *exc):
        self.t1 = now()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        stack = getattr(_tls, "stack", [])
        if stack and stack[-1] is self:
            stack.pop()
        else:
            # out-of-order exit (a generator holding an open span was
            # closed while a later span was live): drop self wherever it
            # sits so the rest of the stack stays consistent
            try:
                stack.remove(self)
            except ValueError:
                pass
        _record(self)
        return False

    def __repr__(self):
        return (f"Span({self.name!r}, {self.duration_s * 1e3:.3f}ms, "
                f"attrs={self.attrs!r})")


class _NullSpan:
    """Shared do-nothing span handle returned while tracing is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self

    @property
    def attrs(self):
        # a throwaway dict: mutations are discarded, callers need no guard
        return {}

    @property
    def duration_s(self):
        return 0.0


_NULL = _NullSpan()


def span(name: str, cat: str | None = None, **attrs):
    """Open a nestable span: ``with span("shuffle", bytes=nb): ...``.

    Returns the shared no-op handle while tracing is disabled, so callers
    on hot paths need no enabled-check of their own (when attribute
    *computation* is expensive, gate it on :func:`enabled`)."""
    if not _enabled:
        return _NULL
    return Span(name, cat, attrs)


def instant(name: str, **attrs) -> None:
    """Record a zero-duration marker event (no stack participation)."""
    if not _enabled:
        return
    sp = Span(name, "instant", attrs)
    t = threading.current_thread()
    sp.tid = t.ident or 0
    sp.thread = t.name
    sp.t0 = sp.t1 = now()
    _record(sp)


def complete(name: str, t0: float, t1: float | None = None, **attrs) -> None:
    """Record a span retroactively from explicit :func:`now` timestamps.

    For intervals that do not nest on a thread's call stack — a streaming
    stage whose generator is suspended/resumed between other queries'
    quanta, or a query's submit-to-finish lifetime closed by the service
    scheduler."""
    if not _enabled:
        return
    sp = Span(name, None, attrs)
    t = threading.current_thread()
    sp.tid = t.ident or 0
    sp.thread = t.name
    sp.t0 = float(t0)
    sp.t1 = now() if t1 is None else float(t1)
    _record(sp)


def current_span() -> Span | None:
    """The innermost open span on this thread (None when disabled or no
    span is open) — the hook for attaching attributes from deep callees."""
    if not _enabled:
        return None
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


class _Tracing:
    """Context manager for :func:`tracing` (re-entrant, state-restoring)."""

    __slots__ = ("_prev",)

    def __enter__(self):
        self._prev = _enabled
        enable()
        return self

    def __exit__(self, *exc):
        if not self._prev:
            disable()
        return False


def tracing() -> _Tracing:
    """Enable tracing for a ``with`` block, restoring the prior state on
    exit (nesting inside an already-enabled region is a no-op)."""
    return _Tracing()


class Trace:
    """An immutable snapshot of recorded spans (see :func:`get_trace`).

    ``spans`` is the tuple of :class:`Span` records; ``dropped`` counts
    spans lost to the recorder's size backstop (0 in normal runs)."""

    def __init__(self, spans, dropped: int = 0):
        self.spans = tuple(spans)
        self.dropped = int(dropped)

    def __len__(self):
        return len(self.spans)

    def summary(self) -> dict:
        """Aggregate by span name: ``{name: {"count", "total_s"}}``."""
        out: dict[str, dict] = {}
        for sp in self.spans:
            d = out.setdefault(sp.name, {"count": 0, "total_s": 0.0})
            d["count"] += 1
            d["total_s"] += sp.duration_s
        return out


def get_trace(since: int = 0) -> Trace:
    """Snapshot the recorder (spans from index ``since``; see :func:`mark`)."""
    with _lock:
        return Trace(_events[since:], _dropped)


def summary() -> dict:
    """Compact process-trace summary for telemetry surfaces (e.g.
    ``QueryService.stats()["trace"]``): enabled flag, span/drop counts,
    and per-name aggregates."""
    tr = get_trace()
    return {"enabled": _enabled, "spans": len(tr), "dropped": tr.dropped,
            "by_name": tr.summary()}
