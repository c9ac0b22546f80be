"""Table/array/scalar collectives over ``jax.lax`` primitives (paper §3, Table 1).

The Cylon communication model exposes composite-data-structure collectives
(shuffle/gather/allgather/bcast/(all)reduce on tables, arrays, scalars) built
on buffer-level primitives. The TPU adaptation implements each table
collective as the corresponding ``jax.lax`` collective applied per column
buffer *inside a ``shard_map`` region* — the abstract-collectives layer of the
paper, with XLA's compiler-scheduled collectives replacing hand-progressed
MPI requests (DESIGN.md §2).

All functions here expect to run inside ``shard_map`` with ``axis`` naming
the (possibly tuple of) mesh axes that carry the row partitions.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp

from ...compat import axis_size as _compat_axis_size
from ...obs import metrics
from ...obs.trace import phase
from ..dataframe import Table, valid_mask
from ..partition import build_shuffle_buffers

__all__ = [
    "axis_size",
    "axis_index",
    "shuffle_table",
    "shuffle_table_pipelined",
    "allgather_table",
    "gather_table",
    "broadcast_table",
    "allreduce_array",
    "reduce_scatter_array",
    "allgather_array",
    "barrier",
]


def axis_size(axis) -> int:
    """Static number of workers on the row-partition axis (Python int)."""
    return _compat_axis_size(axis)


def axis_index(axis) -> jax.Array:
    """This worker's rank along the row-partition axis (traced scalar)."""
    return jax.lax.axis_index(axis)


# -- array / scalar collectives ----------------------------------------------

def allreduce_array(x: jax.Array, axis, op: str = "sum") -> jax.Array:
    """AllReduce an array across workers: sum | max | min | mean (Table 1)."""
    if op == "sum":
        return jax.lax.psum(x, axis)
    if op == "max":
        return jax.lax.pmax(x, axis)
    if op == "min":
        return jax.lax.pmin(x, axis)
    if op == "mean":
        return jax.lax.pmean(x, axis)
    raise ValueError(f"unknown reduce op {op}")


def reduce_scatter_array(x: jax.Array, axis) -> jax.Array:
    """Sum-reduce then scatter tiles: worker i gets slice i of the sum."""
    return jax.lax.psum_scatter(x, axis, tiled=True)


def allgather_array(x: jax.Array, axis, tiled: bool = False) -> jax.Array:
    """AllGather an array; tiled=True concatenates along axis 0."""
    return jax.lax.all_gather(x, axis, tiled=tiled)


def barrier(axis) -> None:
    """Explicit barrier (paper Table 1): a zero-byte psum. BSP supersteps are
    implicit at shard_map boundaries; this exists for tests."""
    jax.lax.psum(jnp.zeros((), jnp.int32), axis)


# -- table collectives ---------------------------------------------------------

@phase("shuffle.exchange")
def _all_to_all(x: jax.Array, axis) -> jax.Array:
    """(P, quota, ...) -> (P, quota, ...) where out[j] came from peer j."""
    return jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=False)


def _count_slots(table: Table, P: int, quota: int) -> None:
    """Trace-time counters of the plan's padding, from static shapes: the
    ``P * quota`` slots a shuffle builds against the capacity it was given
    (equal at P = 1, about twice it under ``default_quota`` at P > 1)."""
    reg = metrics.registry()
    reg.counter("shuffle.slot_rows").add(P * quota)
    reg.counter("shuffle.input_rows").add(table.capacity)


def _blocks_to_rows(recv: jax.Array, offsets: jax.Array, n: int) -> jax.Array:
    """``n`` zero rows with block ``recv[s]`` written from row ``offsets[s]``,
    in ascending ``s``: a later block overwrites an earlier one's tail."""
    def copy_block(s, out):
        block = jax.lax.dynamic_index_in_dim(recv, s, 0, keepdims=False)
        return jax.lax.dynamic_update_slice_in_dim(out, block, offsets[s], 0)

    # int32 bounds, as in ``partition._runs_to_rows``: a loop the compiler
    # can count, so a one-trip loop is inlined
    return jax.lax.fori_loop(jnp.int32(0), jnp.int32(recv.shape[0]), copy_block,
                             jnp.zeros((n,) + recv.shape[2:], recv.dtype))


def shuffle_table(table: Table, dest: jax.Array, axis, quota: int,
                  capacity: int | None = None, algorithm: str = "native") -> tuple[Table, jax.Array]:
    """AllToAll shuffle of live rows to ``dest`` partitions (paper §3.1/§5.1).

    Cylon implements shuffle on p2p channels because of "a mismatch in
    traditional MPI_Alltoall"; on TPU the mismatch disappears once rows sit in
    fixed quota buffers, so we use the native all-to-all (the paper's own
    future-work recommendation: offload shuffle to the library).

    ``algorithm``: "native" (XLA all-to-all) or "bruck" (paper §6.1.1 /
    Table 3: O(log P) startup, O(log P * n/2) transfer — the latency-bound
    choice for small payloads at large P, built from log2(P) ppermute
    rounds; see ``choose_shuffle_algorithm``).

    Returns (received table with capacity P*quota (or ``capacity``), overflow
    count). Received rows are compacted to the front, grouped by source rank
    (stable), preserving within-source order.
    """
    P = axis_size(axis)
    _count_slots(table, P, quota)
    bufs = build_shuffle_buffers(table, dest, P, quota)
    if algorithm == "bruck":
        recv_cols, recv_counts = _bruck_all_to_all(bufs.columns, bufs.counts, axis)
    else:
        recv_cols = {k: _all_to_all(v, axis) for k, v in bufs.columns.items()}
        recv_counts = _all_to_all(bufs.counts.reshape(P, 1), axis).reshape(P)
    # source s's live slots recv[s, :recv_counts[s]] land at src_offset[s]
    # (source-major, within-source stable), one block copy per source in
    # ascending s over zeros: block s's tail past recv_counts[s] is zero
    # (pack zero-fills it) and block s+1 overwrites it; the last block's
    # tail stays zero
    with phase("shuffle.unpack"):
        cap_out = P * quota if capacity is None else capacity
        src_offset = jnp.cumsum(recv_counts) - recv_counts
        # src_offset[s] <= s * quota, so in a buffer of at least P * quota
        # rows no block runs past the end: dynamic_update_slice would clamp
        # such a start silently and shift the block onto its predecessor
        n = max(P * quota, cap_out)
        cols = {k: _blocks_to_rows(v, src_offset, n)[:cap_out] for k, v in recv_cols.items()}
        nvalid = jnp.minimum(jnp.sum(recv_counts), cap_out).astype(jnp.int32)
    return Table(cols, nvalid), bufs.overflow


def shuffle_table_pipelined(
    table: Table,
    dest: jax.Array,
    axis,
    quota: int,
    num_chunks: int,
    capacity: int | None = None,
) -> tuple[Table, jax.Array]:
    """Pipelined chunked AllToAll shuffle (cost model §5 + comm/compute overlap).

    Splits every per-destination quota buffer into ``num_chunks`` chunks and
    issues chunk ``i+1``'s ``all_to_all`` before merging chunk ``i`` into the
    output partition, so XLA's async collectives can overlap transfer with the
    local merge (double buffering). This is the chunked-pipeline technique
    that drives Cylon/UCX scaling (arXiv:2301.07896) and combine-shuffle-
    reduce aggregation overlap (arXiv:2010.14596), adapted to static shapes.

    Contract (identical to :func:`shuffle_table` with ``algorithm="native"``):

    - Output rows are **bit-exact** equal to the monolithic path — compacted
      to the front, grouped by source rank (stable), preserving within-source
      order; the tail is zero padding.
    - The returned overflow counter counts rows dropped because a destination
      exceeded ``quota`` — unchanged by chunking (chunking splits the same
      quota buffers; it never adds or removes capacity).

    Each in-flight collective message shrinks from ``P * quota`` rows to
    ``P * ceil(quota/K)`` (the staging buffers themselves are still built at
    full size, so peak *live* memory in the jit region matches the
    monolithic path — the win is smaller transfers overlapping compute, not
    a lower high-water mark).

    Args:
      table: local row partition (inside ``shard_map``).
      dest: (capacity,) int32 destination partition per row; invalid rows
        carry ``P`` (drop bucket).
      axis: mesh axis name (or tuple) carrying the row partitions.
      quota: per-destination slot count (static).
      num_chunks: K >= 1 pipeline chunks; K=1 degenerates to one all_to_all.
        Clamped to ``quota`` (beyond that, extra chunks carry only padding).
      capacity: output capacity (defaults to ``P * quota``).

    Returns:
      (received table, overflow count) exactly as :func:`shuffle_table`.
    """
    P = axis_size(axis)
    K = max(min(int(num_chunks), quota), 1)
    cq = -(-quota // K)  # per-chunk quota (ceil)
    _count_slots(table, P, quota)
    bufs = build_shuffle_buffers(table, dest, P, quota)
    cap_out = (P * quota) if capacity is None else capacity

    # Counts travel first (one tiny all_to_all): the receiver then knows the
    # final position of every incoming row before any payload chunk lands.
    recv_counts = _all_to_all(bufs.counts.reshape(P, 1), axis).reshape(P)
    src_offset = jnp.cumsum(recv_counts) - recv_counts  # exclusive prefix

    # Pad the (P, quota) buffers to (P, K*cq) so chunks are equal-sized; the
    # pad slots sit above ``quota`` and are never valid (counts <= quota).
    pad = K * cq - quota
    cols = bufs.columns
    with phase("shuffle.pack"):
        if pad:
            cols = {
                k: jnp.concatenate(
                    [v, jnp.zeros((P, pad) + v.shape[2:], v.dtype)], axis=1)
                for k, v in cols.items()
            }
        chunks = {k: v.reshape((P, K, cq) + v.shape[2:]) for k, v in cols.items()}

    with phase("shuffle.unpack"):
        out_cols = {
            k: jnp.zeros((cap_out,) + v.shape[2:], v.dtype)
            for k, v in bufs.columns.items()
        }

    def _send(k: int):
        return {name: _all_to_all(c[:, k], axis) for name, c in chunks.items()}

    # Software pipeline: the all_to_all for chunk k+1 has no data dependence
    # on chunk k's merge, so the scheduler may run them concurrently.
    recv = _send(0)
    for k in range(K):
        nxt = _send(k + 1) if k + 1 < K else None
        # Rows of chunk k occupy quota slots [k*cq, (k+1)*cq) of each source;
        # slot q of source s is valid iff q < recv_counts[s] and lands at
        # final position src_offset[s] + q (source-major, within-source
        # stable — the monolithic compact order).
        with phase("shuffle.unpack"):
            q = k * cq + jnp.arange(cq, dtype=jnp.int32)  # (cq,)
            valid = q[None, :] < recv_counts[:, None]  # (P, cq)
            pos = src_offset[:, None].astype(jnp.int32) + q[None, :]
            pos = jnp.where(valid, pos, cap_out).reshape(P * cq)
            for name, v in recv.items():
                flat = v.reshape((P * cq,) + v.shape[2:])
                out_cols[name] = out_cols[name].at[pos].set(flat, mode="drop")
        recv = nxt

    with phase("shuffle.unpack"):
        nvalid = jnp.minimum(jnp.sum(recv_counts), cap_out).astype(jnp.int32)
    return Table(out_cols, nvalid), bufs.overflow


@phase("shuffle.exchange")
def _bruck_all_to_all(columns: dict, counts: jax.Array, axis):
    """Bruck all-to-all over ppermute rounds (Bruck et al. 1997; paper
    Table 3). Blocks are first rotated to relative order (slot j = block for
    rank+j), then round k ships every slot with bit k set to rank + 2^k —
    the slot sets are STATIC, so each round moves exactly P/2 quota-blocks.
    After ceil(log2 P) rounds slot j holds the block FROM rank-j; a final
    inverse rotation restores source order (matching the native layout)."""
    P = axis_size(axis)
    rank = axis_index(axis)

    rot = (jnp.arange(P) + rank) % P               # slot j <- block for rank+j
    cols = {k: v[rot] for k, v in columns.items()}
    cnts = counts[rot]

    nbits = max((P - 1).bit_length(), 1)
    for k in range(nbits):
        bit = 1 << k
        slots = [j for j in range(P) if j & bit]   # static slot set
        if not slots:
            continue
        idx = jnp.asarray(slots, jnp.int32)
        perm = [(i, (i + bit) % P) for i in range(P)]
        new_cols = {}
        for name, v in cols.items():
            send = v[idx]                          # (|slots|, quota, ...)
            recv = jax.lax.ppermute(send, axis, perm=perm)
            new_cols[name] = v.at[idx].set(recv)
        cnt_recv = jax.lax.ppermute(cnts[idx], axis, perm=perm)
        cnts = cnts.at[idx].set(cnt_recv)
        cols = new_cols

    inv = (rank - jnp.arange(P)) % P               # out[s] = slot (rank - s)
    return {k: v[inv] for k, v in cols.items()}, cnts[inv]


def allgather_table(table: Table, axis, capacity: int | None = None) -> Table:
    """AllGather a table: every worker ends with all live rows (paper Table 1)."""
    P = axis_size(axis)
    cap = table.capacity
    cols = {k: jax.lax.all_gather(v, axis) for k, v in table.columns.items()}  # (P, cap, ...)
    counts = jax.lax.all_gather(table.nvalid, axis)  # (P,)
    keep = (jnp.arange(cap, dtype=jnp.int32)[None, :] < counts[:, None]).reshape(P * cap)
    out = Table({k: v.reshape((P * cap,) + v.shape[2:]) for k, v in cols.items()},
                jnp.asarray(P * cap, jnp.int32))
    from ..dataframe import compact
    return compact(out, keep, capacity=capacity)


def gather_table(table: Table, axis, root: int = 0, capacity: int | None = None) -> Table:
    """Gather to ``root``; non-root workers receive an empty table."""
    out = allgather_table(table, axis, capacity=capacity)
    me = axis_index(axis)
    n = jnp.where(me == root, out.nvalid, 0)
    return Table(out.columns, n.astype(jnp.int32))


def broadcast_table(table: Table, axis, root: int = 0) -> Table:
    """Broadcast root's partition to all workers (paper Table 1; used by the
    broadcast-join pattern §5.3.7).

    Implemented as masked psum (zero everywhere but root, then sum): a single
    reduction-tree collective, which XLA lowers to an all-reduce. Costs match
    the paper's binomial-tree broadcast asymptotics in the log-P term.
    """
    me = axis_index(axis)
    sel = (me == root)
    cols = {}
    for k, v in table.columns.items():
        contrib = jnp.where(sel, v.astype(jnp.float32) if v.dtype == jnp.bool_ else v, jnp.zeros_like(v))
        out = jax.lax.psum(contrib, axis)
        cols[k] = out.astype(v.dtype)
    n = jax.lax.psum(jnp.where(sel, table.nvalid, 0), axis)
    return Table(cols, n.astype(jnp.int32))


def scatter_table(table: Table, axis, root: int = 0, quota: int | None = None) -> tuple[Table, jax.Array]:
    """Scatter root's live rows round-robin across workers (partitioned I/O)."""
    P = axis_size(axis)
    quota = quota if quota is not None else -(-table.capacity // P)
    me = axis_index(axis)
    # Non-root contributes no rows: zero out nvalid off-root.
    n = jnp.where(me == root, table.nvalid, 0).astype(jnp.int32)
    t = Table(table.columns, n)
    idx = jnp.arange(table.capacity, dtype=jnp.int32)
    dest = jnp.where(idx < n, idx % P, P)
    return shuffle_table(t, dest, axis, quota)
