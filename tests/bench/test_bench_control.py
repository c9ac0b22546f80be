"""The control of the join's comparison: the reference put in the program's
place, computed one step below what the configuration states, must come out
not correct. The configuration states int64 columns, so the control carries
the payload as int32 (the narrowing that would halve the shuffle's payload
bytes). The same control runs on the chip at the cell's own size (PERF.md)."""

import bench_cases  # noqa: F401
import numpy as np
import pytest

from bench import harness

JOIN = harness.find_cell("cylon_paper.join.p1")


@pytest.mark.parametrize("seed", [21, 2**31 + 21, 10**12])
def test_join_int32_control_fails(seed):
    d = JOIN.reference.generate({**JOIN.config, "rows_per_worker": 2000}, seed)
    narrow = {s: {"c0": d[s]["c0"], "c1": d[s]["c1"].astype(np.int32).astype(np.int64)}
              for s in ("left", "right")}
    control = JOIN.reference.join_rows(narrow["left"], narrow["right"])
    ref = JOIN.reference.join_reference(d["left"], d["right"], d["n_keys"])
    numbers = JOIN.reference.compare(JOIN.reference.join_digest(control), ref)
    assert numbers["rows_diff"] == numbers["keys_diff"] == 0
    assert numbers["c1_diff"] == numbers["c1_r_diff"] == numbers["pairs_diff"] == 1
