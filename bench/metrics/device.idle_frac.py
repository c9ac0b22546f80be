"""Share of the traced window in which the busiest device runs no
operation: 1 - (union of its op intervals) / window."""

from bench import trace


def read(r):
    busy = trace.device_busy(r.trace)
    if not busy or max(busy.values()) <= 0:
        return None
    lo, hi = r.trace.window
    return 1.0 - max(busy.values()) / (hi - lo)
