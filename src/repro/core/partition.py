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
    ``jax_enable_x64``), bools widen, floats bitcast (equal floats hash
    equal). Shared by the jnp hash chain below and the Pallas
    ``kernels.hash_partition`` build side, so both hash bit-identically.
    """
    if x.dtype in (jnp.int64, jnp.uint64):
        u = x.astype(jnp.uint64)
        return (u ^ (u >> jnp.uint64(32))).astype(jnp.uint32)
    if x.dtype == jnp.bool_:
        return x.astype(jnp.uint32)
    if jnp.issubdtype(x.dtype, jnp.floating):
        return jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
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


@phase("shuffle.pack")
def build_shuffle_buffers(table: Table, dest: jax.Array, num_partitions: int, quota: int) -> ShuffleBuffers:
    """Lay live rows into fixed (P, quota) per-destination buffers.

    Stable within destination (preserves row order). Rows whose destination
    bucket is full are counted in ``overflow`` and dropped — callers size
    ``quota`` from sampled histograms (see ``repro.core.patterns``) so that
    overflow is zero in practice, and can assert on it.
    """
    P, cap = num_partitions, table.capacity
    order = jnp.argsort(dest, stable=True)  # groups rows by destination
    sdest = dest[order]
    # where each destination's group starts (P + 2 binary searches; one
    # per row would cost a gather per step over every row)
    starts = jnp.searchsorted(sdest, jnp.arange(P + 2, dtype=sdest.dtype),
                              side="left").astype(jnp.int32)
    # rank of each row within its destination group
    rank = jnp.arange(cap, dtype=jnp.int32) - starts[sdest]
    is_row = sdest < P  # drop-bucket (==P) excluded
    keep = is_row & (rank < quota)
    # raw per-destination counts (including overflowing rows)
    raw = jnp.diff(starts)[:P]
    counts = jnp.minimum(raw, quota).astype(jnp.int32)
    overflow = jnp.sum(raw - counts, dtype=jnp.int32)

    scatter_d = jnp.where(keep, sdest, P)  # out-of-bounds rows -> dropped
    scatter_r = jnp.where(keep, rank, quota)
    cols = {}
    for name, col in table.columns.items():
        buf = jnp.zeros((P, quota) + col.shape[1:], col.dtype)
        cols[name] = buf.at[scatter_d, scatter_r].set(col[order], mode="drop")
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
