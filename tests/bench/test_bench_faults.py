"""A whole run of the join cell at a tiny size on the CPU, once sound and
once for each fault the cell can have, planted underneath the timed path:
the operator returns its input unchanged, half of the rows are left out,
an answer is altered where it is produced, the columns are narrowed to 32
bits. ``correct`` must come out true for the sound run and false for every
fault. (The exchange between chips left out is in
``test_bench_faults_four_workers.py``.)"""

import bench_cases
import jax
import jax.numpy as jnp
import pytest

from bench import harness
from repro.core import api, operators
from repro.core.dataframe import Table


@pytest.fixture
def fresh(monkeypatch):
    """No compiled op survives from another test: a planted fault must be
    traced into the program it breaks."""
    monkeypatch.setattr(api, "_OP_CACHE", api._LRUCache())
    return monkeypatch


CELL = "cylon_paper.join.p1"


def _run(monkeypatch):
    return bench_cases.run_tiny(CELL, 4_000_000_007, jax.devices()[:1], monkeypatch)


def test_sound_run_is_correct(fresh):
    r = _run(fresh)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] == 1
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())
    assert r["checks"]["columns_not_int64"]["value"] == 0


def _join_returns_input(comm, left, right, key_columns, quota, capacity, num_chunks=1):
    zero = jnp.zeros((), jnp.int32)
    out = Table({"c0": left["c0"], "c1": left["c1"], "c1_r": left["c1"]}, left.nvalid)
    return out, {"overflow_left": zero, "overflow_right": zero, "overflow_join": zero}


def _halve(fn):
    def wrapped(comm, table, *args, **kw):
        return fn(comm, Table(table.columns, table.nvalid // 2), *args, **kw)
    return wrapped


def _alter_join(fn):
    def wrapped(*args, **kw):
        out, ov = fn(*args, **kw)
        return out.replace(c1=out["c1"].at[0].add(1)), ov
    return wrapped


FAULTS = {
    "returns_input": [(operators, "dist_join_shuffle", lambda f: _join_returns_input)],
    "half_rows": [(operators, "dist_join_shuffle", _halve)],
    "answer_altered": [(operators, "local_join", _alter_join)],
    # the run without JAX's 64-bit types: the tables go up as int32
    "narrowed_to_32_bits": [(harness, "wants_x64", lambda f: lambda config: False)],
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(fault, fresh):
    for module, name, plant in FAULTS[fault]:
        fresh.setattr(module, name, plant(getattr(module, name)))
    r = _run(fresh)
    assert r["correct"] is False
    assert r["failed"] >= 1
