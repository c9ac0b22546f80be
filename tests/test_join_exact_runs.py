"""``local_join`` on one integer or bool key column takes exact key runs.

The left side is sorted by the key's own value, so every expanded pair is a
true match and the pair list is already the result: ``nvalid`` is
``min(true pairs, capacity)`` and ``overflow`` the true excess. Every other
key (several columns, a float) keeps the hash runs, verified on emission
and compacted. The reference below is a plain numpy pair list in the
join's order: right-row-major, the left rows of a key in input order.
"""

import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import local_ops
from repro.core.dataframe import Table
from repro.core.partition import hash_columns
from repro.obs import metrics

I64 = np.iinfo(np.int64)
# int64 keys whose 32-bit hi ^ lo folds collide: 1 and 2**32 fold to 1;
# 2**32 + 3 and 3 * 2**32 + 2 fold to 2 (so do -1 and 0, and the two
# extremes, in the cases below)
FOLDS = [1, 2**32, 2**32 + 3, 3 * 2**32 + 2]

# name -> (key dtype, pool of live keys, left rows, left nvalid, right rows,
# right nvalid); the invalid tails hold live keys and the sorts-last value
CASES = {
    "int8": (np.int8, [-128, -3, 0, 5, 127], 60, 52, 50, 45),
    "int16": (np.int16, [-32768, -7, 0, 9, 32767], 60, 52, 50, 45),
    "int32": (np.int32, [-2**31, -1, 0, 11, 2**31 - 1], 60, 52, 50, 45),
    "int64": (np.int64, [-2**40, -1, 0, 13, 2**40], 60, 52, 50, 45),
    "uint32": (np.uint32, [0, 1, 2**31, 2**32 - 2, 2**32 - 1], 60, 52, 50, 45),
    "bool": (np.bool_, [False, True], 30, 24, 20, 17),
    "int64_folds_collide": (np.int64, FOLDS, 60, 52, 50, 45),
    "int64_extremes": (np.int64, [I64.min, I64.max, -1, 0], 60, 40, 50, 35),
    "left_empty": (np.int64, [0, 1, 2], 40, 0, 30, 30),
    "right_empty": (np.int64, [0, 1, 2], 40, 40, 30, 0),
}


def _sides(name, dtype=None):
    """(left columns, left nvalid, right columns, right nvalid) of a case."""
    dt, pool, lcap, ln, rcap, rn = CASES[name]
    dt = dt if dtype is None else dtype
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    pool = np.array(pool, dtype=dt)
    tail = np.concatenate([pool, [local_ops._sorts_last(pool.dtype).item()]]).astype(dt)

    def keys(cap, n):
        return np.concatenate([rng.choice(pool, n), rng.choice(tail, cap - n)]).astype(dt)

    left = {"k": keys(lcap, ln),
            "v": rng.integers(-2**62, 2**62, lcap, dtype=np.int64),
            "c": rng.integers(-1000, 1000, lcap).astype(np.int32)}
    right = {"k": keys(rcap, rn),
             "w": rng.standard_normal(rcap).astype(np.float32),
             "c": rng.integers(-1000, 1000, rcap).astype(np.int32)}
    return left, ln, right, rn


def _hashes_collide(name):
    """Whether two live keys of the case share the engine's key hash."""
    dt, pool = CASES[name][:2]
    pool = np.unique(np.array(pool, dtype=dt))
    with jax.enable_x64(True):
        h = hash_columns(_table({"k": pool}, len(pool)), ["k"])
    return len(np.unique(np.asarray(h))) < len(pool)


def _reference(left, ln, right, rn, on=("k",)):
    """The join's columns in its order, from a numpy pair list."""
    pairs = [(i, j) for j in range(rn) for i in range(ln)
             if all(left[k][i] == right[k][j] for k in on)]
    li = np.array([i for i, _ in pairs], np.int64)
    ri = np.array([j for _, j in pairs], np.int64)
    cols = {k: left[k][li] for k in on}
    cols |= {k: v[li] for k, v in left.items() if k not in on}
    cols |= {(k if k not in cols else f"{k}_r"): v[ri] for k, v in right.items() if k not in on}
    return cols, len(pairs)


def _table(cols, n):
    return Table({k: jnp.asarray(v) for k, v in cols.items()}, jnp.asarray(n, jnp.int32))


def _join(left, ln, right, rn, capacity, on=("k",)):
    """(columns of the live rows, nvalid, overflow, counter deltas)."""
    before = metrics.registry().counters()
    out, ov = local_ops.local_join(_table(left, ln), _table(right, rn), list(on), capacity)
    after = metrics.registry().counters()
    delta = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("join.exact_runs", "join.verified_runs")}
    n = int(out.nvalid)
    return {k: np.asarray(v)[:n] for k, v in out.columns.items()}, n, int(ov), delta


def _verified_join(monkeypatch, *args, **kw):
    """The same join forced onto the hash runs, for comparison."""
    with monkeypatch.context() as m:
        m.setattr(local_ops, "_exact_key", lambda *a: False)
        return _join(*args, **kw)


def _capacity(kind, true, left_rows, right_rows):
    return {"below": max(true // 2, 1), "at": max(true, 1), "above": true + 13,
            "roomy": left_rows * right_rows + 1}[kind]


def _assert_columns_equal(got, want):
    assert list(got) == list(want)
    for k in want:
        assert got[k].dtype == want[k].dtype, k
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


@pytest.mark.parametrize("cap", ["below", "at", "above"])
@pytest.mark.parametrize("name", list(CASES))
def test_exact_join_matches_numpy(name, cap):
    with jax.enable_x64(True):
        left, ln, right, rn = _sides(name)
        want, true = _reference(left, ln, right, rn)
        capacity = _capacity(cap, true, ln, rn)
        got, n, ov, delta = _join(left, ln, right, rn, capacity)
    assert delta == {"join.exact_runs": 1, "join.verified_runs": 0}
    assert n == min(true, capacity)
    assert ov == max(true - capacity, 0)
    _assert_columns_equal(got, {k: v[:n] for k, v in want.items()})
    if name in ("left_empty", "right_empty"):
        assert true == 0
    else:
        assert true > 0


@pytest.mark.parametrize("cap", ["at", "above", "roomy"])
@pytest.mark.parametrize("name", list(CASES))
def test_exact_join_is_the_verified_join(monkeypatch, name, cap):
    """Bit-identical to the hash runs, row order included, wherever those
    do not overflow; they do only where hash collisions use capacity."""
    with jax.enable_x64(True):
        left, ln, right, rn = _sides(name)
        _, true = _reference(left, ln, right, rn)
        capacity = _capacity(cap, true, len(left["k"]), len(right["k"]))
        got, n, ov, _ = _join(left, ln, right, rn, capacity)
        old, n_old, ov_old, delta = _verified_join(monkeypatch, left, ln, right, rn, capacity)
    assert delta == {"join.exact_runs": 0, "join.verified_runs": 1}
    assert ov == 0
    if ov_old:
        assert _hashes_collide(name) and cap != "roomy"
        return
    assert n_old == n
    _assert_columns_equal(got, old)


def test_verified_runs_overcount_under_collisions(monkeypatch):
    """Why the exact path exists: under colliding hashes the verified path
    spends capacity on false pairs and counts them in ``overflow``, so at a
    capacity of exactly the true pair count it drops true pairs and reports
    an overflow, where the exact path returns every pair."""
    with jax.enable_x64(True):
        left, ln, right, rn = _sides("int64_folds_collide")
        want, true = _reference(left, ln, right, rn)
        got, n, ov, _ = _join(left, ln, right, rn, true)
        _, n_old, ov_old, _ = _verified_join(monkeypatch, left, ln, right, rn, true)
    assert _hashes_collide("int64_folds_collide")
    assert (n, ov) == (true, 0)
    _assert_columns_equal(got, want)
    assert n_old < true and ov_old > 0


# -- every other key keeps the verified hash runs ---------------------------------

def _other_keys(kind):
    """(left, ln, right, rn, on) for a key that is not one integer column."""
    rng = np.random.default_rng(zlib.crc32(kind.encode()))
    if kind == "two_int32":
        def side(cap, n, payload):
            return {"a": rng.integers(0, 4, cap).astype(np.int32),
                    "b": rng.integers(-2, 2, cap).astype(np.int32),
                    payload: rng.integers(-1000, 1000, cap).astype(np.int32)}, n
        return (*side(60, 52, "v"), *side(50, 45, "w"), ("a", "b"))
    pool = np.array([-0.0, 0.0, 1.5, -2.25, np.inf, np.nan], np.float32)

    def side(cap, n, payload):
        return {"a": rng.choice(pool, cap),
                payload: rng.integers(-1000, 1000, cap).astype(np.int32)}, n
    return (*side(60, 52, "v"), *side(50, 45, "w"), ("a",))


def _hash_pairs(left, ln, right, rn, on):
    """Live pairs whose keys share the engine's key hash: the verified
    path's pair count, false pairs included."""
    def h(cols, n):
        return np.asarray(hash_columns(_table({k: cols[k] for k in on}, n), list(on)))[:n]
    return int((h(left, ln)[:, None] == h(right, rn)[None, :]).sum())


@pytest.mark.parametrize("kind", ["two_int32", "float32"])
def test_other_keys_take_the_verified_runs(kind):
    """Several key columns and a float key keep the verified hash runs. At a
    capacity of the hash-equal pairs they return every true pair; the float
    key's NaNs hash equal but never match, so there those pairs outnumber
    the true ones (``-0.0`` matches ``0.0``, as in numpy)."""
    left, ln, right, rn, on = _other_keys(kind)
    want, true = _reference(left, ln, right, rn, on)
    capacity = _hash_pairs(left, ln, right, rn, on)
    got, n, ov, delta = _join(left, ln, right, rn, capacity, on)
    assert delta == {"join.exact_runs": 0, "join.verified_runs": 1}
    assert (n, ov) == (true, 0) and true > 0
    _assert_columns_equal(got, want)
    if kind == "float32":
        assert capacity > true


# -- the lowered program ----------------------------------------------------------

OUT = 77  # the output capacity, distinct from the inputs' 40 and 48 rows


def _lowered(on, names, dtype=jnp.int64):
    """(lowered text, counter deltas) of a join of zero tables."""
    def table(cap):
        return Table({n: jnp.zeros(cap, dtype) for n in names}, jnp.asarray(cap - 3, jnp.int32))

    before = metrics.registry().counters()
    text = jax.jit(lambda a, b: local_ops.local_join(a, b, list(on), OUT)).lower(
        table(40), table(48)).as_text()
    after = metrics.registry().counters()
    return text, {k: after.get(k, 0) - before.get(k, 0)
                  for k in ("join.exact_runs", "join.verified_runs")}


def _gathers(text, dtype):
    return len(re.findall(rf'stablehlo\.gather".*-> tensor<{OUT}x{dtype}>', text))


def test_exact_join_lowers_without_the_verify_gathers():
    """An int64 one-key join gathers the key and the two payloads once each
    at the output size, and no validity (``i1``) mask; it sorts once less
    than a two-column join, which still verifies and compacts."""
    with jax.enable_x64(True):
        exact, d_exact = _lowered(["c0"], ["c0", "c1"])
        two, d_two = _lowered(["c0", "c1"], ["c0", "c1", "c2"])
    assert _gathers(exact, "i1") == 0
    assert _gathers(exact, "i64") == 3
    assert _gathers(two, "i1") > 0
    assert exact.count("stablehlo.sort") == two.count("stablehlo.sort") - 1
    assert d_exact == {"join.exact_runs": 1, "join.verified_runs": 0}
    assert d_two == {"join.exact_runs": 0, "join.verified_runs": 1}


@pytest.mark.parametrize("on, names, dtype, counter", [
    (["c0"], ["c0", "c1"], jnp.int64, "join.exact_runs"),
    (["c0"], ["c0", "c1"], jnp.int32, "join.exact_runs"),
    (["c0"], ["c0", "c1"], jnp.bool_, "join.exact_runs"),
    (["c0"], ["c0", "c1"], jnp.float32, "join.verified_runs"),
    (["c0", "c1"], ["c0", "c1"], jnp.int32, "join.verified_runs"),
])
def test_the_key_alone_decides_the_path(on, names, dtype, counter):
    with jax.enable_x64(True):
        _, delta = _lowered(on, names, dtype)
    assert delta[counter] == 1 and sum(delta.values()) == 1
