"""Shuffle placement against a plain numpy reference.

``build_shuffle_buffers`` (pack) lays each destination's rows into one row of
its ``(P, quota)`` buffers, and ``shuffle_table`` (unpack, native and Bruck)
lays each source's block into the output; both copy contiguous segments.
The reference below states the contract row by row: stable within
destination, source-major output, zero tails, the per-destination counts,
``nvalid`` and the overflow.

The engine runs every case in one subprocess on 8 host devices (this pytest
process keeps its one device), each case on a mesh of its first P devices,
and writes its arrays to a file; each test compares one case's arrays. The
same subprocess lowers the pack and the monolithic shuffle at P=4 for the
structural test, which holds them to copies: no ``scatter`` in their HLO.
"""

import json
import os
import subprocess
import sys
import zlib

import numpy as np
import pytest

# name -> (P, rows per worker, valid rows per worker, quota, output capacity,
# destinations: "uniform", "skip" (no row goes to destination P // 2) or
# "skew" (three rows in four go to destination 0))
CASES = {
    "p1": (1, 40, 33, 40, None, "uniform"),
    "p2": (2, 40, 40, 24, None, "uniform"),
    "p4": (4, 48, 40, 16, None, "uniform"),
    "p8": (8, 64, 50, 12, None, "uniform"),
    "p4_overflow_skew": (4, 48, 48, 6, None, "skew"),
    "p8_overflow": (8, 64, 50, 4, None, "uniform"),
    "p4_empty_dest": (4, 48, 44, 20, None, "skip"),
    "p8_invalid_rows": (8, 64, 20, 12, None, "uniform"),
    "p1_capacity_below": (1, 40, 33, 40, 25, "uniform"),
    "p4_capacity_below": (4, 48, 48, 16, 30, "uniform"),
    "p4_capacity_equal": (4, 48, 40, 16, 64, "uniform"),
    "p8_capacity_above": (8, 64, 50, 12, 8 * 12 + 37, "uniform"),
}
KINDS = ("pack", "native", "bruck")
COLUMNS = ("i64", "i32", "b", "f32", "i32x3")

ENGINE = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as PS
from repro.compat import shard_map
from repro.core.comm import collectives
from repro.core.dataframe import Table
from repro.core.partition import build_shuffle_buffers

work = sys.argv[1]
cases = json.load(open(os.path.join(work, "cases.json")))
inputs = np.load(os.path.join(work, "inputs.npz"))


def shard_fn(P, quota, capacity, kind):
    def f(cols, dest, n):
        t = Table(dict(cols), n.reshape(()))
        if kind == "pack":
            bufs = build_shuffle_buffers(t, dest, P, quota)
            return ({k: v[None] for k, v in bufs.columns.items()},
                    bufs.counts.reshape(1, P), bufs.overflow.reshape(1))
        out, ov = collectives.shuffle_table(
            t, dest, "data", quota, capacity=capacity, algorithm=kind)
        return dict(out.columns), out.nvalid.reshape(1), ov.reshape(1)
    mesh = Mesh(np.array(jax.devices()[:P]), ("data",))
    return shard_map(f, mesh=mesh, in_specs=(PS("data"), PS("data"), PS("data")),
                     out_specs=PS("data"), check_vma=False)


def args(name):
    cols = {c[len(name) + 1:]: jnp.asarray(inputs[c]) for c in inputs.files
            if c.startswith(name + "/") and c[len(name) + 1:] not in ("dest", "n")}
    return cols, jnp.asarray(inputs[name + "/dest"]), jnp.asarray(inputs[name + "/n"])


out = {}
for name, (P, cap, n, quota, capacity, _) in cases.items():
    for kind in ("pack", "native", "bruck"):
        cols, counts, overflow = jax.jit(shard_fn(P, quota, capacity, kind))(*args(name))
        for k, v in cols.items():
            out[f"{name}/{kind}/{k}"] = np.asarray(v)
        out[f"{name}/{kind}/#counts"] = np.asarray(counts)
        out[f"{name}/{kind}/#overflow"] = np.asarray(overflow)
np.savez(os.path.join(work, "outputs.npz"), **out)

# the structural test's programs: pack and the monolithic shuffle at P=4
P, cap, quota = 4, 48, 16
t = Table({"i64": jnp.zeros(cap, jnp.int64), "i32": jnp.zeros(cap, jnp.int32)},
          jnp.asarray(cap, jnp.int32))
dest = jnp.zeros(cap, jnp.int32)
pack = lambda t, d: dict(build_shuffle_buffers(t, d, P, quota).columns)
hlo = {"pack": jax.jit(pack).lower(t, dest).as_text()}
cols, d4, n4 = ({k: jnp.zeros(P * cap, v.dtype) for k, v in t.columns.items()},
                jnp.zeros(P * cap, jnp.int32), jnp.full(P, cap, jnp.int32))
hlo["native"] = jax.jit(shard_fn(P, quota, None, "native")).lower(cols, d4, n4).as_text()
json.dump(hlo, open(os.path.join(work, "hlo.json"), "w"))
print("PLACEMENT-ENGINE-OK")
"""


def _inputs(name, P, cap, n, dests):
    """Worker-major global columns, destinations (``P`` past ``n``: the drop
    bucket) and valid-row counts for one case."""
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    rows = P * cap
    dest = rng.integers(0, P, size=rows).astype(np.int32)
    if dests == "skip":
        dest[dest == P // 2] = (P // 2 + 1) % P
    elif dests == "skew":
        dest[rng.random(rows) < 0.75] = 0
    dest[(np.arange(rows) % cap) >= n] = P
    return {
        "i64": rng.integers(-2**62, 2**62, size=rows, dtype=np.int64),
        "i32": rng.integers(-2**31, 2**31 - 1, size=rows, dtype=np.int64).astype(np.int32),
        "b": rng.random(rows) < 0.5,
        "f32": rng.standard_normal(rows).astype(np.float32),
        "i32x3": rng.integers(-1000, 1000, size=(rows, 3)).astype(np.int32),
        "dest": dest,
        "n": np.full(P, n, np.int32),
    }


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    work = tmp_path_factory.mktemp("placement")
    inputs = {}
    for name, (P, cap, n, _, _, dests) in CASES.items():
        for k, v in _inputs(name, P, cap, n, dests).items():
            inputs[f"{name}/{k}"] = v
    np.savez(work / "inputs.npz", **inputs)
    (work / "cases.json").write_text(json.dumps(CASES))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"), env.get("PYTHONPATH", "")])
    res = subprocess.run([sys.executable, "-c", ENGINE, str(work)], capture_output=True,
                         text=True, timeout=600, env=env)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-3000:]
    assert "PLACEMENT-ENGINE-OK" in res.stdout
    return inputs, dict(np.load(work / "outputs.npz")), json.loads((work / "hlo.json").read_text())


def _ref_pack(cols, dest, P, quota):
    """One worker's (P, quota) buffers, counts and overflow, row by row."""
    bufs = {k: np.zeros((P, quota) + v.shape[1:], v.dtype) for k, v in cols.items()}
    counts = np.zeros(P, np.int32)
    overflow = 0
    for d in range(P):
        rows = np.flatnonzero(dest == d)  # ascending: stable
        kept = rows[:quota]
        counts[d] = len(kept)
        overflow += len(rows) - len(kept)
        for k, v in cols.items():
            bufs[k][d, :len(kept)] = v[kept]
    return bufs, counts, overflow


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("name", list(CASES))
def test_placement_matches_reference(engine, name, kind):
    inputs, outputs, _ = engine
    P, cap, _, quota, capacity, _ = CASES[name]
    packs = []
    for w in range(P):
        part = slice(w * cap, (w + 1) * cap)
        cols = {c: inputs[f"{name}/{c}"][part] for c in COLUMNS}
        packs.append(_ref_pack(cols, inputs[f"{name}/dest"][part], P, quota))
    got = {k[len(f"{name}/{kind}/"):]: v for k, v in outputs.items()
           if k.startswith(f"{name}/{kind}/")}
    overflow = np.array([ov for _, _, ov in packs])
    np.testing.assert_array_equal(got["#overflow"], overflow)
    if kind == "pack":
        assert got["#counts"].dtype == np.int32
        np.testing.assert_array_equal(got["#counts"], np.stack([c for _, c, _ in packs]))
        for c in COLUMNS:
            want = np.stack([b[c] for b, _, _ in packs])
            assert got[c].dtype == want.dtype, c
            np.testing.assert_array_equal(got[c], want, err_msg=c)
        return
    cap_out = P * quota if capacity is None else capacity
    # worker r receives, from each source s in turn, the live slots of the
    # buffer row s packed for r; the output is their concatenation, cut to
    # cap_out and zero past it
    nvalid = []
    for r in range(P):
        n = sum(int(cnt[r]) for _, cnt, _ in packs)
        nvalid.append(min(n, cap_out))
        for c in COLUMNS:
            live = np.concatenate([b[c][r, :cnt[r]] for b, cnt, _ in packs])[:cap_out]
            want = np.zeros((cap_out,) + live.shape[1:], live.dtype)
            want[:len(live)] = live
            got_r = got[c][r * cap_out:(r + 1) * cap_out]
            assert got[c].shape[0] == P * cap_out and got[c].dtype == want.dtype, c
            np.testing.assert_array_equal(got_r, want, err_msg=f"worker {r} column {c}")
    np.testing.assert_array_equal(got["#counts"], np.array(nvalid, np.int32))
    if name.endswith("_overflow") or name.endswith("_skew"):
        assert overflow.sum() > 0
    if name.endswith("_empty_dest"):
        assert all(cnt[P // 2] == 0 for _, cnt, _ in packs)
    if name.endswith("_capacity_below"):
        assert max(nvalid) == cap_out  # the cut drops received rows


@pytest.mark.parametrize("program", ["pack", "native"])
def test_placement_lowers_without_scatter(engine, program):
    """Pack and the monolithic unpack place rows by contiguous copies; an
    index-driven scatter coming back would show in their lowered HLO."""
    hlo = engine[2][program]
    assert "dynamic_slice" in hlo or "dynamic_update_slice" in hlo
    assert "scatter" not in hlo
