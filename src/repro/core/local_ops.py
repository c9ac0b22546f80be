"""Core local operators (paper §4.1, Table 4) under static shapes.

Single-partition implementations of the primitive operators that the
distributed patterns promote: sort, hash-join (sort-based under XLA),
groupby segment-reduction, unique, set membership. Every output is
capacity-bounded with an explicit ``nvalid`` and, where the true output size
can exceed capacity, an ``overflow`` counter.

Design notes (DESIGN.md §7.1):
- Join expansion uses ``jnp.repeat(..., total_repeat_length)`` — the
  static-shape equivalent of Arrow's variable-length take.
- A join on one integer (or bool) key column matches rows on the key's own
  value, so every expanded pair is a true match. Any other key is matched on
  a 32-bit key hash and *verified on emission* against the actual key
  columns, so hash collisions cost capacity, never correctness.
- Multi-column keys sort lexicographically (hash, col1, col2, ...), which
  makes equal keys adjacent for dedup/groupby adjacency logic.
"""

from __future__ import annotations

from typing import Mapping, Sequence

import jax
import jax.numpy as jnp

from ..obs import metrics
from ..obs.trace import phase
from .dataframe import Table, compact, max_sentinel, min_sentinel, valid_mask
from .partition import hash_columns

__all__ = [
    "local_sort",
    "local_join",
    "local_groupby",
    "local_unique",
    "local_anti_join",
    "select",
    "project",
    "with_column",
    "row_aggregate",
    "column_aggregate_local",
]

_AGG_OPS = ("sum", "count", "min", "max", "mean")

# canonical definitions live in dataframe.py (shared with the kernel layer)
_max_sentinel = max_sentinel
_min_sentinel = min_sentinel


# -- embarrassingly-parallel primitives (paper §5.3.1) -------------------------

def select(table: Table, pred) -> Table:
    """Filter rows by a predicate over the column dict. O(n)."""
    return compact(table, pred(table.columns))


def project(table: Table, names: Sequence[str]) -> Table:
    """Column projection. O(c) — zero-copy column selection."""
    return table.select_columns(names)


def with_column(table: Table, name: str, fn) -> Table:
    """Add (or overwrite) one column computed by ``fn`` over the column
    dict; all other columns pass through. A scalar result (literal-only
    expression) broadcasts to the table capacity. Shared by the eager
    ``DDF.with_column`` body and the plan executor's ``WithColumn`` step so
    the two layers cannot diverge."""
    v = jnp.asarray(fn(table.columns))
    if v.ndim == 0:
        v = jnp.full((table.capacity,), v)
    return Table({**table.columns, name: v}, table.nvalid)


def row_aggregate(table: Table, names: Sequence[str], out: str, op: str = "sum") -> Table:
    """Per-row aggregate across columns -> new column ``out`` (paper §5.3.1)."""
    cols = [table.columns[n] for n in names]
    stack = jnp.stack(cols, axis=0)
    if op == "sum":
        v = jnp.sum(stack, axis=0)
    elif op == "min":
        v = jnp.min(stack, axis=0)
    elif op == "max":
        v = jnp.max(stack, axis=0)
    elif op == "mean":
        v = jnp.mean(stack.astype(jnp.float32), axis=0)
    else:
        raise ValueError(op)
    return table.replace(**{out: v})


def column_aggregate_local(table: Table, name: str, op: str):
    """Local leg of the Globally-Reduce pattern (paper §5.3.5)."""
    v = table.columns[name]
    m = valid_mask(table)
    if op in ("sum", "mean"):
        s = jnp.sum(jnp.where(m, v, jnp.zeros_like(v)).astype(jnp.float64 if v.dtype == jnp.float64 else jnp.float32))
        cnt = jnp.sum(m, dtype=jnp.int32)
        return s, cnt
    if op == "min":
        return jnp.min(jnp.where(m, v, _max_sentinel(v.dtype))), jnp.sum(m, dtype=jnp.int32)
    if op == "max":
        return jnp.max(jnp.where(m, v, _min_sentinel(v.dtype))), jnp.sum(m, dtype=jnp.int32)
    if op == "count":
        return jnp.sum(m, dtype=jnp.int32), jnp.sum(m, dtype=jnp.int32)
    raise ValueError(op)


# -- sorting -------------------------------------------------------------------

def _sorts_last(dtype) -> jax.Array:
    """The value a stable sort places after every other: NaN for floats
    (sorts put it last), the maximum otherwise."""
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.nan, dtype)
    if dtype == jnp.bool_:
        return jnp.asarray(True)
    return jnp.asarray(jnp.iinfo(dtype).max, dtype)


@phase("local.sort")
def local_sort(table: Table, key_columns: Sequence[str], descending: bool = False) -> Table:
    """O(n log n) local sort; invalid rows stay at the tail (stable).

    Invalid rows carry the sorts-last value in every key instead of an
    extra validity key (each key of a TPU sort multiplies what it costs to
    compile): they form the input's tail, so a stable sort keeps them
    behind any live row with the same keys."""
    m = valid_mask(table)
    keys = []
    for name in reversed(key_columns):
        k = table.columns[name]
        if descending:
            # order-reversing map: -x for floats, ~x for ints (exact, no
            # INT_MIN overflow).
            k = -k if jnp.issubdtype(k.dtype, jnp.floating) else jnp.bitwise_not(k)
        keys.append(jnp.where(m, k, _sorts_last(k.dtype)))
    order = jnp.lexsort(tuple(keys))
    cols = {k: v[order] for k, v in table.columns.items()}
    return Table(cols, table.nvalid)


def _hash_identifies_key(table: Table, key_columns: Sequence[str]) -> bool:
    """True when equal hashes mean equal keys: one key column of at most
    32 bits, whose normalized value the lowbias32 chain maps one-to-one."""
    return (len(key_columns) == 1
            and table.columns[key_columns[0]].dtype.itemsize <= 4)


def _sort_hash(table: Table, key_columns: Sequence[str]) -> jax.Array:
    """The sort key of :func:`_sorted_by_key_hash`: the key hash, with
    ``0xFFFFFFFF`` for invalid rows.

    Invalid rows are the table's tail, so a stable sort keeps them behind
    live rows of the same hash. When the hash does not identify the key,
    the key columns break ties, and live hashes are clamped below
    ``0xFFFFFFFF`` so that no garbage key of an invalid row can sort ahead
    of a live row."""
    h = hash_columns(table, key_columns)
    if not _hash_identifies_key(table, key_columns):
        h = jnp.minimum(h, jnp.uint32(0xFFFFFFFE))
    return jnp.where(valid_mask(table), h, jnp.uint32(0xFFFFFFFF))


@phase("local.sort")
def _sorted_by_key_hash(table: Table, key_columns: Sequence[str]):
    """Sort rows stably by (:func:`_sort_hash`, key columns...) — by the
    hash alone where it identifies the key: each key of a TPU sort
    multiplies what it costs to compile. Returns (sorted_table,
    sorted_hash, order); live rows first, equal keys adjacent."""
    h = _sort_hash(table, key_columns)
    keys = [h]
    if not _hash_identifies_key(table, key_columns):
        keys = [table.columns[n] for n in reversed(key_columns)] + keys
    order = jnp.lexsort(tuple(keys))
    cols = {k: v[order] for k, v in table.columns.items()}
    return Table(cols, table.nvalid), h[order], order


def _exact_key(left: Table, right: Table, key_columns: Sequence[str]) -> bool:
    """True when the key's own value is an exact sort key: one integer or
    bool column, of one dtype on both sides. Floats are not (``-0.0 ==
    0.0`` while a sort separates them, and NaN never equals itself)."""
    if len(key_columns) != 1:
        return False
    dt = left.columns[key_columns[0]].dtype
    return (dt == right.columns[key_columns[0]].dtype
            and (jnp.issubdtype(dt, jnp.integer) or dt == jnp.bool_))


@phase("local.sort")
def _sorted_by_key(table: Table, key: str):
    """Sort rows stably by the value of one integer or bool ``key``, with
    invalid rows carrying the sorts-last value (as in :func:`local_sort`):
    live rows first, equal keys adjacent in input order. The ``key`` column
    is the sorted key, which the sort returns beside the order (no
    gather)."""
    k = jnp.where(valid_mask(table), table.columns[key], _sorts_last(table.columns[key].dtype))
    sk, order = jax.lax.sort((k, jnp.arange(table.capacity, dtype=jnp.int32)),
                             num_keys=1, is_stable=True)
    cols = {n: sk if n == key else v[order] for n, v in table.columns.items()}
    return Table(cols, table.nvalid)


def _adjacent_new_group(sorted_table: Table, key_columns: Sequence[str]) -> jax.Array:
    """is_new[i]: row i starts a new key group (rows sorted by key)."""
    cap = sorted_table.capacity
    is_new = jnp.zeros((cap,), bool).at[0].set(True)
    for name in key_columns:
        v = sorted_table.columns[name]
        neq = v[1:] != v[:-1]
        is_new = is_new.at[1:].max(neq)
    return is_new


# -- unique (hash dedup, paper Table 4: O(n), output O(nC)) --------------------

@phase("local.unique")
def local_unique(table: Table, key_columns: Sequence[str],
                 capacity: int | None = None, with_overflow: bool = False):
    """Deduplicate rows by key columns (first occurrence wins; hash-exact).

    ``with_overflow=True`` additionally returns how many distinct rows did
    not fit in ``capacity`` (``compact`` truncates silently otherwise —
    the distributed wrappers surface this so ``strict_overflow`` can turn
    a capacity misestimate into a loud error instead of dropped rows)."""
    st, _, _ = _sorted_by_key_hash(table, key_columns)
    keep = _adjacent_new_group(st, key_columns) & valid_mask(st)
    out = compact(st, keep, capacity=capacity)
    if not with_overflow:
        return out
    cap_out = st.capacity if capacity is None else capacity
    ov = jnp.maximum(jnp.sum(keep, dtype=jnp.int32) - cap_out, 0)
    return out, ov


# -- groupby (combine / reduce legs, paper §5.3.4) ------------------------------

def _seg_reduce_dispatch(vals: jax.Array, seg: jax.Array, nseg: int, op: str) -> jax.Array:
    """One segment reduction, routed to the Pallas kernel or jnp.

    ``vals`` is (cap,) already masked/sentinel-filled by the caller; ``seg``
    is non-decreasing dense ids with ``nseg-1`` as the invalid bucket.
    ``kernels.segment_reduce`` resolves the backend per (row count, dtype);
    both paths return the same (nseg,) result — bit-identical for integer
    ops and min/max, and for float sums up to summation order
    (docs/KERNELS.md)."""
    from ..kernels import ops as kernel_ops

    return kernel_ops.segment_reduce(vals, seg, nseg, op=op)


def agg_schema(aggs: Mapping[str, Sequence[str]]) -> list[tuple[str, str, str]]:
    """[(value_col, op, out_col)] with mean decomposed into sum+count."""
    out = []
    for col, ops in aggs.items():
        for op in ops:
            if op not in _AGG_OPS:
                raise ValueError(f"unsupported agg {op}")
            out.append((col, op, f"{col}_{op}"))
    return out


@phase("local.groupby")
def local_groupby(
    table: Table,
    key_columns: Sequence[str],
    aggs: Mapping[str, Sequence[str]],
    capacity: int | None = None,
    merge: bool = False,
    with_overflow: bool = False,
):
    """Hash-groupby via sort + segment reduction. O(n log n) under XLA (the
    paper's O(n) hash table does not map to static shapes; the extra log n is
    a documented hardware-adaptation cost, DESIGN.md §2).

    merge=False: input is raw rows; emits key cols + <col>_<op> partials
    (mean contributes <col>_sum & <col>_count; finalization happens in the
    distributed wrapper).
    merge=True: input columns are partials named <col>_<op>; re-reduces with
    the merge semantics (sum of sums, min of mins, ...).
    with_overflow=True: additionally return how many groups did not fit in
    ``capacity`` (``compact`` truncates silently otherwise; the distributed
    wrappers surface this so ``strict_overflow`` turns a reduce-side
    capacity misestimate into a loud error instead of dropped groups).
    """
    cap = table.capacity
    cap_out = cap if capacity is None else capacity
    st, _, _ = _sorted_by_key_hash(table, key_columns)
    m = valid_mask(st)
    is_new = _adjacent_new_group(st, key_columns) & m
    gid = jnp.cumsum(is_new.astype(jnp.int32)) - 1  # valid rows: [0, ngroups)
    seg = jnp.where(m, gid, cap)  # invalid -> overflow bucket
    nseg = cap + 1

    spec = agg_schema(aggs)
    out_cols: dict[str, jax.Array] = {}
    # group representative row (first row of each group) for key columns
    first_idx = jax.ops.segment_min(jnp.arange(cap, dtype=jnp.int32), seg, num_segments=nseg)[:cap]
    first_idx = jnp.minimum(first_idx, cap - 1)
    for name in key_columns:
        out_cols[name] = st.columns[name][first_idx]

    def seg_reduce(vals, op):
        # combine leg of Combine-Shuffle-Reduce: dispatched to the Pallas
        # segment_reduce kernel when profitable (registry + cost model).
        # seg is non-decreasing (cumsum of is_new; invalid rows -> the cap
        # bucket at the tail), which is the kernel path's precondition.
        if op == "min":
            vals = jnp.where(m, vals, _max_sentinel(vals.dtype))
        elif op == "max":
            vals = jnp.where(m, vals, _min_sentinel(vals.dtype))
        elif op != "sum":
            raise ValueError(op)
        return _seg_reduce_dispatch(vals, seg, nseg, op)[:cap]

    needed: dict[str, tuple[str, str]] = {}  # out partial name -> (src col partial, merge op)
    for col, op, out_name in spec:
        if op == "mean":
            needed[f"{col}_sum"] = (f"{col}_sum" if merge else col, "sum")
            needed[f"{col}_count"] = (f"{col}_count" if merge else col, "count")
        elif op == "count":
            needed[f"{col}_count"] = (f"{col}_count" if merge else col, "count")
        else:
            needed[out_name] = (out_name if merge else col, op)

    ones = m.astype(jnp.int32)
    for out_name, (src, op) in needed.items():
        if op == "count":
            if merge:
                vals = st.columns[src]
                vals = jnp.where(m, vals, jnp.zeros_like(vals))
                out_cols[out_name] = seg_reduce(vals, "sum")
            else:
                out_cols[out_name] = _seg_reduce_dispatch(ones, seg, nseg, "sum")[:cap]
        else:
            base = st.columns[src]
            vals = jnp.where(m, base, jnp.zeros_like(base)) if op == "sum" else base
            out_cols[out_name] = seg_reduce(vals, op)

    ngroups = jnp.sum(is_new, dtype=jnp.int32)
    out = Table(out_cols, jnp.asarray(cap, jnp.int32))
    keep = jnp.arange(cap, dtype=jnp.int32) < ngroups
    out = compact(out, keep, capacity=cap_out)
    if not with_overflow:
        return out
    return out, jnp.maximum(ngroups - cap_out, 0)


def finalize_groupby(table: Table, aggs: Mapping[str, Sequence[str]]) -> Table:
    """Compute mean = sum/count and drop helper partials not requested."""
    spec = agg_schema(aggs)
    cols = dict(table.columns)
    requested = set()
    for col, op, out_name in spec:
        if op == "mean":
            s = cols[f"{col}_sum"]
            c = jnp.maximum(cols[f"{col}_count"], 1)
            cols[out_name] = s.astype(jnp.float32) / c.astype(jnp.float32)
        requested.add(out_name)
    # keep key columns + requested outputs
    keys = [n for n in table.columns if not any(n == f"{c}_{o}" for c, ops in aggs.items() for o in _AGG_OPS)]
    keep_names = set(keys) | requested
    cols = {k: v for k, v in cols.items() if k in keep_names}
    return Table(cols, table.nvalid)


# -- join (sort-based hash join, paper Table 4 Sort-Join) ----------------------

def _equal_runs(lh: jax.Array, rh: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``searchsorted(lh, rh)`` on both sides: the run of the sorted ``lh``
    equal to each ``rh``, as [lo, hi). ``lh`` is a sorted key: a key hash
    or an exact key's value.

    One search by sorting (the default binary search gathers over every
    query once per step, seconds at 25M rows on a TPU); the run's end then
    comes from a reverse running minimum over ``lh``."""
    n = lh.shape[0]
    if n == 0:
        z = jnp.zeros(rh.shape, jnp.int32)
        return z, z
    lo = jnp.searchsorted(lh, rh, side="left", method="sort").astype(jnp.int32)
    idx = jnp.arange(n, dtype=jnp.int32)
    last = jnp.concatenate([lh[1:] != lh[:-1], jnp.ones((1,), bool)])
    run_end = jax.lax.cummin(jnp.where(last, idx, n), axis=0, reverse=True) + 1
    at = jnp.minimum(lo, n - 1)
    hi = jnp.where((lo < n) & (lh[at] == rh), run_end[at], lo)
    return lo, hi


@phase("local.join")
def local_join(
    left: Table,
    right: Table,
    key_columns: Sequence[str],
    capacity: int,
    suffix: str = "_r",
) -> tuple[Table, jax.Array]:
    """Inner equi-join. Returns (result, overflow = pairs beyond capacity).

    Left is sorted by a sort key, and each right row finds the run of left
    rows with its sort key; pairs expand via total_repeat_length,
    right-row-major, with the left rows of a key in input order.

    - One integer or bool key column: the sort key is the key's value, so
      every pair of a run is a true match and the first ``min(total,
      capacity)`` pairs are the result; ``overflow`` is the true excess.
    - Any other key: the sort key is the key hash. A run can hold keys whose
      hash collides, so pairs are verified against the real key columns and
      the survivors compacted (collision-exact; false pairs use capacity and
      count in ``overflow``).

    The path taken adds 1 to the trace-time counter ``join.exact_runs`` or
    ``join.verified_runs``.
    """
    exact = _exact_key(left, right, key_columns)
    metrics.registry().counter("join.exact_runs" if exact else "join.verified_runs").add(1)
    if exact:
        ls = _sorted_by_key(left, key_columns[0])
        lk, rk = ls.columns[key_columns[0]], right.columns[key_columns[0]]
    else:
        ls, lk, _ = _sorted_by_key_hash(left, key_columns)
        rk = _sort_hash(right, key_columns)
    rm = valid_mask(right)
    lo, hi = _equal_runs(lk, rk)
    # live left rows are the sorted prefix: never pair with the tail
    lo = jnp.minimum(lo, ls.nvalid)
    hi = jnp.minimum(hi, ls.nvalid)
    counts = jnp.where(rm, hi - lo, 0).astype(jnp.int32)
    offs = jnp.cumsum(counts) - counts  # exclusive prefix
    total = offs[-1] + counts[-1]

    cap_r = right.capacity
    out_pos = jnp.arange(capacity, dtype=jnp.int32)
    out_r = jnp.repeat(jnp.arange(cap_r, dtype=jnp.int32), counts, total_repeat_length=capacity)
    within = out_pos - offs[out_r]
    out_l = jnp.clip(lo[out_r].astype(jnp.int32) + within, 0, left.capacity - 1)

    cols: dict[str, jax.Array] = {}
    for name in key_columns:
        cols[name] = ls.columns[name][out_l]
    for name, v in ls.columns.items():
        if name not in key_columns:
            cols[name] = v[out_l]
    for name, v in right.columns.items():
        if name not in key_columns:
            out_name = name if name not in cols else f"{name}{suffix}"
            cols[out_name] = v[out_r]

    overflow = jnp.maximum(total - capacity, 0)
    if exact:
        # every pair in [0, total) is a true match: the pairs are the result
        return Table(cols, jnp.minimum(total, capacity)), overflow

    emit = out_pos < total
    # verify true key equality (hash-collision guard) + validity
    lvalid = jnp.arange(left.capacity, dtype=jnp.int32) < ls.nvalid
    for name in key_columns:
        emit = emit & (ls.columns[name][out_l] == right.columns[name][out_r])
    emit = emit & lvalid[out_l] & rm[out_r]

    res = Table(cols, jnp.asarray(capacity, jnp.int32))
    res = compact(res, emit, capacity=capacity)
    return res, overflow


@phase("local.join")
def local_anti_join(
    left: Table,
    right: Table,
    key_columns: Sequence[str],
    capacity: int | None = None,
    dedup_left: bool = True,
) -> Table:
    """Rows of left whose key does not appear in right (set difference leg).

    Exact under hash collisions: membership is established by joining the
    deduplicated keys and scattering hit marks back to left rows.
    """
    lu = local_unique(left, key_columns) if dedup_left else left
    ru = local_unique(right, key_columns)
    ls, _, _ = _sorted_by_key_hash(lu, key_columns)
    # Join the deduplicated keys (collision-exact: local_join's runs are exact
    # or verified) and scatter hit marks back onto left rows by row index.
    # Both sides are deduplicated, so the pair count is bounded by
    # ls.capacity — no overflow possible.
    member = jnp.zeros((ls.capacity,), bool)
    pairs, _ = local_join(
        Table({n: ls.columns[n] for n in key_columns} | {"__lidx": jnp.arange(ls.capacity, dtype=jnp.int32)}, ls.nvalid),
        Table({n: ru.columns[n] for n in key_columns}, ru.nvalid),
        key_columns,
        capacity=ls.capacity,
    )
    hit_idx = pairs.columns["__lidx"]
    hit_valid = jnp.arange(pairs.capacity, dtype=jnp.int32) < pairs.nvalid
    member = member.at[jnp.where(hit_valid, hit_idx, ls.capacity)].set(True, mode="drop")
    keep = valid_mask(ls) & ~member
    return compact(ls, keep, capacity=capacity)
