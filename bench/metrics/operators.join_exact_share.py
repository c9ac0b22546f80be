"""Share of the local joins that took exact key runs: the engine's
trace-time counters ``join.exact_runs`` over ``join.exact_runs`` plus
``join.verified_runs``. 1.0 where every join is on one integer column, so
no pair is verified or compacted; ``None`` where the engine keeps neither
counter."""

from repro.obs import metrics


def read(r):
    counters = metrics.registry().counters()
    exact = counters.get("join.exact_runs", 0)
    joins = exact + counters.get("join.verified_runs", 0)
    if joins <= 0:
        return None
    return exact / joins
