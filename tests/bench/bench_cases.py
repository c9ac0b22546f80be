"""Shared by the benchmark's CPU tests: the cells at a size a test can hold."""

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

# rows per worker of cylon_paper
TINY = {"cylon_paper": {"rows_per_worker": 3000}}


def tiny_config(cell_name: str, **changes) -> dict:
    cell = harness.find_cell(cell_name)
    return {**cell.config, **TINY[cell.entry["config"]], **changes}


def run_tiny(cell_name: str, seed: int, devices, monkeypatch=None, **changes) -> dict:
    """One run of the cell on CPU devices at the tiny size: one query in
    the window, no persistent compile cache."""
    if monkeypatch is not None:
        monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    return harness.run(cell_name, seed, 0.0, False, devices, time.perf_counter(),
                       config=tiny_config(cell_name, **changes))
