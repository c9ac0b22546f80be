"""Slots the shuffles built over the rows they were given: the engine's
trace-time counters ``shuffle.slot_rows`` (P x quota per shuffle) over
``shuffle.input_rows`` (the input table's capacity). 1.0 where the quota
is the capacity (P = 1); about 2 under ``default_quota`` at P > 1, the
padding every all-to-all then carries. ``None`` where the engine keeps no
such counters."""

from repro.obs import metrics


def read(r):
    counters = metrics.registry().counters()
    rows = counters.get("shuffle.input_rows", 0)
    if rows <= 0:
        return None
    return counters.get("shuffle.slot_rows", 0) / rows
