"""The paper's weak-scaling tables (``cylon_paper.json``): the generator and
the plain numpy reference of the inner join on ``c0``.

Nothing here imports the engine. The generator follows the engine's
``uniform_table`` (keys uniform over ``cardinality * rows`` values), in the
configuration's ``dtype``, so a change to the engine cannot change the data
it is measured on.

The comparison is by fingerprints modulo 2**64, each a sum over the joined
rows that the reference gets without materialising the join:

- ``keys``: sum of f(c0), the multiset of joined keys;
- ``c1``, ``c1_r``: sums of f(c0) * c1 and f(c0) * c1_r, every payload
  bit at its key;
- ``pairs``: sum of f20(c0) * g20(c1) * h20(c1_r), which sees a join that
  pairs the right multisets of a key wrongly. It factors per key, and its
  20-bit hashes keep the per-key sums of g and h exact in float64.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

KEY = "c0"
CHUNK = 1 << 21  # rows per task of the threaded row sums
_SALTS = (0x9E3779B97F4A7C15, 0xC2B2AE3D27D4EB4F, 0x165667B19E3779F9)


def uniform_table(n_rows: int, cardinality: float, n_cols: int, seed: int,
                  dtype) -> dict:
    """Keys uniform over ``cardinality * n_rows`` values; payloads uniform
    over the non-negative range of ``dtype`` (arXiv:2307.01394 §6)."""
    rng = np.random.default_rng(seed)
    dtype = np.dtype(dtype)
    n_keys = max(int(n_rows * cardinality), 1)
    cols = {"c0": rng.integers(0, n_keys, size=n_rows, dtype=dtype)}
    for i in range(1, n_cols):
        cols[f"c{i}"] = rng.integers(0, np.iinfo(dtype).max, size=n_rows, dtype=dtype)
    return cols


def generate(config: dict, seed: int) -> dict:
    """Left table from ``seed``, right table from ``seed + 1``."""
    n = config["rows_per_worker"] * config["workers"]
    args = (n, config["cardinality"], config["n_columns"])
    return {"left": uniform_table(*args, seed=seed, dtype=config["dtype"]),
            "right": uniform_table(*args, seed=seed + 1, dtype=config["dtype"]),
            "n_keys": max(int(n * config["cardinality"]), 1)}


def _mix(x: np.ndarray, salt: int, bits: int = 64) -> np.ndarray:
    """splitmix64's finaliser of ``x`` (any integer dtype), cut to ``bits``,
    as uint64."""
    h = x.astype(np.int64).view(np.uint64) ^ np.uint64(salt)
    h = (h ^ (h >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    h = (h ^ (h >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    h = h ^ (h >> np.uint64(31))
    return h if bits == 64 else h & np.uint64((1 << bits) - 1)


def _u64(x: np.ndarray) -> np.ndarray:
    return x.astype(np.int64).view(np.uint64)


def _sum(x: np.ndarray) -> int:
    """Sum modulo 2**64."""
    return int(np.sum(x, dtype=np.uint64))


def _row_sums(fn, *cols) -> np.ndarray:
    """``fn(*chunk)`` over chunks of rows, on threads (numpy lets go of the
    GIL), summed modulo 2**64: fn returns a uint64 vector of sums."""
    n = len(cols[0])
    with ThreadPoolExecutor(8) as pool:
        parts = pool.map(lambda i: fn(*(c[i:i + CHUNK] for c in cols)),
                         range(0, n, CHUNK))
        return np.sum(list(parts), axis=0, dtype=np.uint64)


def join_reference(left: dict, right: dict, n_keys: int) -> dict:
    """The join's row count and fingerprints, from each side's rows and the
    other side's per-key counts: a left row appears once per right row of
    its key, and the other way round."""
    lk, rk = left[KEY], right[KEY]
    cl = np.bincount(lk, minlength=n_keys).astype(np.uint64)
    cr = np.bincount(rk, minlength=n_keys).astype(np.uint64)

    def side(k, v, other):  # f(c0) once per joined row, and f(c0) * c1
        f = _mix(k, _SALTS[0]) * other[k]
        return np.array([_sum(f), _sum(f * _u64(v))], dtype=np.uint64)

    keys, c1 = _row_sums(lambda k, v: side(k, v, cr), lk, left["c1"])
    c1_r = _row_sums(lambda k, v: side(k, v, cl), rk, right["c1"])[1]
    # float64 sums of 20-bit hashes are exact below 2**33 rows per key
    gl = np.bincount(lk, weights=_mix(left["c1"], _SALTS[1], 20),
                     minlength=n_keys).astype(np.uint64)
    hr = np.bincount(rk, weights=_mix(right["c1"], _SALTS[2], 20),
                     minlength=n_keys).astype(np.uint64)
    f20 = _mix(np.arange(n_keys, dtype=np.int64), _SALTS[0], 20)
    return {"rows": int(np.sum(cl * cr)), "keys": int(keys), "c1": int(c1),
            "c1_r": int(c1_r), "pairs": _sum(f20 * gl * hr)}


def join_digest(rows: dict) -> dict:
    """The same numbers as :func:`join_reference`, read off joined rows."""

    def sums(k, c1, c1_r):
        f = _mix(k, _SALTS[0])
        pairs = (_mix(k, _SALTS[0], 20) * _mix(c1, _SALTS[1], 20)
                 * _mix(c1_r, _SALTS[2], 20))
        return np.array([_sum(f), _sum(f * _u64(c1)), _sum(f * _u64(c1_r)), _sum(pairs)],
                        dtype=np.uint64)

    keys, c1, c1_r, pairs = _row_sums(sums, rows[KEY], rows["c1"], rows["c1_r"])
    return {"rows": len(rows[KEY]), "keys": int(keys), "c1": int(c1), "c1_r": int(c1_r),
            "pairs": int(pairs)}


def compare(got: dict, ref: dict) -> dict:
    """Each number compared: 0 when the join is exact."""
    return {"rows_diff": abs(got["rows"] - ref["rows"]),
            **{f"{name}_diff": int(got[name] != ref[name])
               for name in ("keys", "c1", "c1_r", "pairs")}}


def join_rows(left: dict, right: dict) -> dict:
    """The inner join on c0 materialised, in numpy: every (left, right)
    pair of equal keys once. Columns c0, c1 (left) and c1_r (right)."""
    lo = np.argsort(left[KEY], kind="stable")
    ro = np.argsort(right[KEY], kind="stable")
    lk, rk = left[KEY][lo], right[KEY][ro]
    start = np.searchsorted(rk, lk, side="left")
    stop = np.searchsorted(rk, lk, side="right")
    n = stop - start
    li = np.repeat(np.arange(len(lk)), n)
    first = np.repeat(start - np.concatenate(([0], np.cumsum(n)[:-1])), n)
    ri = first + np.arange(len(li))
    return {"c0": lk[li], "c1": left["c1"][lo][li], "c1_r": right["c1"][ro][ri]}
