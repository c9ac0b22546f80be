"""Auxiliary partition operators (paper §4.2).

Hash partition, range partition, and the static-shape shuffle-buffer builder.
These are the paper's "auxiliary local sub-operators": they decide, per live
row, a destination partition, and lay rows out into fixed per-destination
quota buffers so that ``jax.lax.all_to_all`` (the TPU shuffle) can move them.

Dynamic Arrow buffers -> static quota buffers is the key hardware adaptation
(DESIGN.md §2): per-destination message sizes become a fixed ``quota`` with
explicit overflow accounting, and the quota is chosen from sampled histograms
per the paper's runtime-data-distribution discussion (§5.4.2).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from ..obs.trace import phase
from .dataframe import Table, valid_mask

__all__ = [
    "u32_normalize",
    "hash32",
    "hash_columns",
    "hash_partition_ids",
    "range_partition_ids",
    "build_shuffle_buffers",
    "ShuffleBuffers",
]

_M1 = jnp.uint32(0x7FEB352D)
_M2 = jnp.uint32(0x846CA68B)


def u32_normalize(x: jax.Array) -> jax.Array:
    """Canonical uint32 view of any column dtype, pre-hash.

    64-bit ints fold hi^lo (so the engine is independent of
    ``jax_enable_x64``), bools widen, floats bitcast with ``-0.0`` taken as
    ``0.0`` (equal floats hash equal). Shared by the jnp hash chain below
    and the Pallas ``kernels.hash_partition`` build side, so both hash
    bit-identically.
    """
    if x.dtype in (jnp.int64, jnp.uint64):
        u = x.astype(jnp.uint64)
        return (u ^ (u >> jnp.uint64(32))).astype(jnp.uint32)
    if x.dtype == jnp.bool_:
        return x.astype(jnp.uint32)
    if jnp.issubdtype(x.dtype, jnp.floating):
        f = x.astype(jnp.float32)
        f = jnp.where(f == 0, jnp.zeros_like(f), f)
        return jax.lax.bitcast_convert_type(f, jnp.uint32)
    return x.astype(jnp.uint32)


def hash32(x: jax.Array) -> jax.Array:
    """lowbias32 integer hash (Prospecting-for-hash-functions constants).

    Works on any dtype via :func:`u32_normalize`.
    """
    x = u32_normalize(x)
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 15)
    x = x * _M2
    x = x ^ (x >> 16)
    return x


def hash_columns(table: Table, key_columns: Sequence[str]) -> jax.Array:
    """(capacity,) uint32 combined hash over the key columns."""
    h = jnp.zeros((table.capacity,), jnp.uint32)
    for name in key_columns:
        hk = hash32(table.columns[name])
        # boost-style hash_combine
        h = h ^ (hk + jnp.uint32(0x9E3779B9) + (h << 6) + (h >> 2))
    return h


@phase("partition")
def hash_partition_ids(table: Table, key_columns: Sequence[str], num_partitions: int) -> jax.Array:
    """Destination partition per row; invalid rows get ``num_partitions``
    (a drop bucket).

    This is the shuffle build side for every shuffle-based operator
    (join/groupby/unique/set ops). It routes through the Pallas
    ``kernels.hash_partition`` kernel when the dispatch registry says so
    (TPU + profitable row count, or a forced ``set_backend("pallas")``);
    the jnp hash chain below is the fallback. Both paths share
    :func:`u32_normalize` and the same lowbias32 mixing, so destinations
    are bit-identical across backends.
    """
    mode = _registry().resolve("hash_partition", table.capacity)
    if mode != "jnp":
        from .. import kernels

        cols = [u32_normalize(table.columns[n]) for n in key_columns]
        dest, _ = kernels.hash_partition(cols, num_partitions, force=mode,
                                         with_hist=False)
    else:
        h = hash_columns(table, key_columns)
        dest = (h % jnp.uint32(num_partitions)).astype(jnp.int32)
    return jnp.where(valid_mask(table), dest, num_partitions)


def _registry():
    # deferred: repro.kernels.registry imports repro.core.cost_model, so a
    # module-level import here would cycle during package init
    from ..kernels import registry

    return registry


@phase("partition")
def range_partition_ids(
    table: Table, key_column: str, pivots: jax.Array, num_partitions: int, descending: bool = False
) -> jax.Array:
    """Ordered partition ids from (P-1) pivots (sample-sort, paper §5.3.3)."""
    keys = table.columns[key_column]
    if descending:
        dest = jnp.searchsorted(-pivots, -keys, side="left").astype(jnp.int32)
    else:
        dest = jnp.searchsorted(pivots, keys, side="right").astype(jnp.int32)
    dest = jnp.clip(dest, 0, num_partitions - 1)
    return jnp.where(valid_mask(table), dest, num_partitions)


class ShuffleBuffers(dict):
    """columns: name -> (P, quota, ...) buffers; counts: (P,) rows per dest;
    overflow: scalar int32 rows dropped because a destination exceeded quota."""

    def __init__(self, columns, counts, overflow):
        super().__init__(columns)
        self.columns = columns
        self.counts = counts
        self.overflow = overflow


def _runs_to_rows(srt: jax.Array, starts: jax.Array, counts: jax.Array, quota: int) -> jax.Array:
    """(P, quota, ...) rows: row d is ``srt[starts[d] : starts[d] + quota]``,
    zero from slot ``counts[d]`` on."""
    P, rest = counts.shape[0], srt.shape[1:]
    slot = jnp.arange(quota, dtype=jnp.int32).reshape((quota,) + (1,) * len(rest))

    def copy_run(d, buf):
        run = jax.lax.dynamic_slice_in_dim(srt, starts[d], quota)
        run = jnp.where(slot < counts[d], run, jnp.zeros((), srt.dtype))
        return jax.lax.dynamic_update_index_in_dim(buf, run, d, 0)

    # int32 bounds: under 64-bit types Python-int bounds give an int64
    # counter, which the TPU compiler splits into two words and then cannot
    # count, so even a one-trip loop (P == 1) would stay a loop
    return jax.lax.fori_loop(jnp.int32(0), jnp.int32(P), copy_run,
                             jnp.zeros((P, quota) + rest, srt.dtype))


@phase("shuffle.pack")
def build_shuffle_buffers(table: Table, dest: jax.Array, num_partitions: int, quota: int) -> ShuffleBuffers:
    """Lay live rows into fixed (P, quota) per-destination buffers.

    A stable argsort groups rows by destination, so destination ``d``'s rows
    are the contiguous run ``sorted[starts[d] : starts[d] + raw[d]]``. Its
    buffer row is one dynamic slice of ``quota`` rows from ``starts[d]``,
    zeroed from ``counts[d]`` on: a copy of the run, with no per-row index.
    Stable within destination (preserves row order); slots past ``counts``
    are zero. Drop-bucket rows (``dest == P``) sort last and are never kept.
    Rows beyond a destination's ``quota`` fall outside its slice; they are
    counted in ``overflow`` and dropped — callers size ``quota`` from sampled
    histograms (see ``repro.core.patterns``) so that overflow is zero in
    practice, and can assert on it.
    """
    P = num_partitions
    order = jnp.argsort(dest, stable=True)  # groups rows by destination
    sdest = dest[order]
    # where each destination's group starts (P + 2 binary searches; one
    # per row would cost a gather per step over every row)
    starts = jnp.searchsorted(sdest, jnp.arange(P + 2, dtype=sdest.dtype),
                              side="left").astype(jnp.int32)
    # raw per-destination counts (including overflowing rows)
    raw = jnp.diff(starts)[:P]
    counts = jnp.minimum(raw, quota).astype(jnp.int32)
    overflow = jnp.sum(raw - counts, dtype=jnp.int32)

    cols = {}
    for name, col in table.columns.items():
        # ``quota`` pad rows: a start is at most the capacity, so no slice
        # runs past the end, where dynamic_slice would clamp its start
        pad = jnp.zeros((quota,) + col.shape[1:], col.dtype)
        cols[name] = _runs_to_rows(jnp.concatenate([col[order], pad]), starts, counts, quota)
    return ShuffleBuffers(cols, counts, overflow)


def default_quota(capacity: int, num_partitions: int, safety: float = 2.0) -> int:
    """Quota heuristic for uniformly distributed keys: E[rows/dest] x safety.

    The paper's uniform-data experiments give n/P rows per destination; the
    safety factor absorbs hash variance. Skewed data should use
    ``patterns.sampled_quota`` instead (sample -> histogram -> quota).
    """
    base = -(-capacity // num_partitions)  # ceil
    q = int(base * safety) + 8
    return min(q, capacity)
