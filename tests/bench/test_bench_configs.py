"""The configuration's generator and plain numpy reference agree with the
repository's differential oracle (``tests/oracle.py``) at a tiny size, and
the comparison that decides ``correct`` sees a wrong answer."""

import os
import sys

import bench_cases
import numpy as np
import pytest

sys.path.insert(0, os.path.join(bench_cases.ROOT, "tests"))
import oracle  # noqa: E402

from bench import harness  # noqa: E402

JOIN = harness.find_cell("cylon_paper.join.p1")


def _join_tables(seed, rows=300):
    cfg = {**JOIN.config, "rows_per_worker": rows}
    return JOIN.reference.generate(cfg, seed)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_join_reference_matches_oracle(seed):
    d = _join_tables(seed)
    right = {"c0": d["right"]["c0"], "c1_r": d["right"]["c1"]}
    want = oracle.o_join(d["left"], right, ("c0",))
    got = JOIN.reference.join_rows(d["left"], d["right"])
    assert oracle.canonical(got) == oracle.canonical(want)
    ref = JOIN.reference.join_reference(d["left"], d["right"], d["n_keys"])
    digest = JOIN.reference.join_digest(want)
    assert all(v == 0 for v in JOIN.reference.compare(digest, ref).values())
    assert ref["rows"] == len(want["c0"]) > 0


def test_digest_sums_across_chunks(monkeypatch):
    # the threaded row sums give the same fingerprints in any chunking
    d = _join_tables(4, rows=5000)
    rows = JOIN.reference.join_rows(d["left"], d["right"])
    whole = JOIN.reference.join_digest(rows)
    ref = JOIN.reference.join_reference(d["left"], d["right"], d["n_keys"])
    monkeypatch.setattr(JOIN.reference, "CHUNK", 77)
    assert JOIN.reference.join_digest(rows) == whole
    assert JOIN.reference.join_reference(d["left"], d["right"], d["n_keys"]) == ref


def test_join_comparison_sees_wrong_pairs_and_values():
    d = _join_tables(3)
    ref = JOIN.reference.join_reference(d["left"], d["right"], d["n_keys"])
    rows = JOIN.reference.join_rows(d["left"], d["right"])
    # a key with two left and two right rows, paired (a, b), (a, b), (a', b'),
    # (a', b'): per-key counts and sums still agree, only the pairs differ
    k = np.flatnonzero((np.bincount(d["left"]["c0"], minlength=d["n_keys"]) == 2)
                       & (np.bincount(d["right"]["c0"], minlength=d["n_keys"]) == 2))[0]
    idx = np.flatnonzero(rows["c0"] == k)
    bad = {c: v.copy() for c, v in rows.items()}
    lvals, rvals = sorted(set(rows["c1"][idx])), sorted(set(rows["c1_r"][idx]))
    bad["c1"][idx] = [lvals[0], lvals[0], lvals[1], lvals[1]]
    bad["c1_r"][idx] = [rvals[0], rvals[0], rvals[1], rvals[1]]
    numbers = JOIN.reference.compare(JOIN.reference.join_digest(bad), ref)
    assert numbers["keys_diff"] == numbers["c1_diff"] == numbers["c1_r_diff"] == 0
    assert numbers["pairs_diff"] == 1
    bad = {c: v.copy() for c, v in rows.items()}
    bad["c1_r"][0] ^= 1 << 40  # one bit above int32's range
    numbers = JOIN.reference.compare(JOIN.reference.join_digest(bad), ref)
    assert numbers["c1_r_diff"] == 1 and numbers["c1_diff"] == numbers["keys_diff"] == 0
    bad = {c: v.copy() for c, v in rows.items()}
    bad["c0"][0] = (bad["c0"][0] + 1) % d["n_keys"]
    numbers = JOIN.reference.compare(JOIN.reference.join_digest(bad), ref)
    assert numbers["keys_diff"] == 1


def test_generator_is_seeded():
    a, b = _join_tables(11), _join_tables(11)
    assert all(np.array_equal(a[s][c], b[s][c]) for s in ("left", "right") for c in a[s])
    assert not np.array_equal(a["left"]["c0"], a["right"]["c0"])


def test_generator_fills_the_stated_width():
    # the configuration states int64: payloads span the int64 range, keys
    # stay under the 90% cardinality
    assert JOIN.config["dtype"] == "int64"
    d = _join_tables(2**32 + 3, rows=4000)
    for side in ("left", "right"):
        assert all(v.dtype == np.int64 for v in d[side].values())
        assert d[side]["c1"].max() > np.iinfo(np.int32).max * 2**16
        assert d[side]["c1"].min() >= 0
        assert 0 <= d[side]["c0"].min() and d[side]["c0"].max() < d["n_keys"] == 3600
