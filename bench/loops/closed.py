"""The closed loop: one client sends the mix's query, waits for its answer
and sends the next, until ``seconds`` have passed. The query in flight then
is finished and counted, so the window holds no partial query.

Reports ``rows_per_s``: the input rows of every completed query over the
time from the window's start to the end of the last one.
"""

from __future__ import annotations

import time

import jax


def run(q, traffic: dict, seconds: float) -> dict:
    """``{"records", "last", "values"}``: what the query kept of each
    answer, the last answer itself (kept for the full check), and this
    loop's end-to-end metrics."""
    if traffic.get("clients") != 1:
        raise ValueError("the closed loop runs one client")
    records, last = [], None
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            last = None  # each answer is freed before the next query
            with jax.profiler.TraceAnnotation("bench.query"):
                last = q.run()
            records.append(q.record(last))
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
    return {"records": records, "last": last,
            "values": {"rows_per_s": len(records) * q.rows / elapsed}}
