"""jit'd wrappers + dispatch for the Pallas kernels.

Every wrapper resolves an execution mode — native ``pallas`` (TPU),
``interpret`` (the kernel body run by the Pallas interpreter: bit-identical
on any backend, the CPU correctness fallback), or the plain ``jnp``/``xla``
reference — through :mod:`repro.kernels.registry`, which consults
``cost_model.kernel_params`` (row thresholds, dtype support, native-lowering
flag) and the process-wide ``set_backend`` override. ``force`` pins a mode
for tests and benchmarks.

The dataframe wrappers (:func:`hash_partition`, :func:`segment_reduce`)
additionally handle the static-shape plumbing the hot paths need: padding
arbitrary row counts up to whole lane-dense blocks (with exact histogram
correction) and gathering each segment's scanned reduction into
per-segment outputs that match the jnp path bit-for-bit on every
associative case.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import hash_partition as _hp
from . import registry
from . import segment_reduce as _sr
from . import ssd_scan as _ssd
from . import ref

__all__ = ["on_tpu", "flash_attention", "ssd_scan", "hash_partition",
           "partition_histogram", "segment_reduce",
           "segment_reduce_partials", "ref"]


def on_tpu() -> bool:
    """True when the default jax backend is TPU (native Pallas lowering)."""
    return jax.default_backend() == "tpu"


def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    scale=None, q_block=128, kv_block=128, force: str | None = None):
    """(B,S,H,hd) x (B,S,KV,hd)^2 -> (B,S,H,hd) attention (model layer).

    Mode: native Pallas on TPU, XLA reference elsewhere; ``force`` pins
    "pallas" | "interpret" | "xla" for tests."""
    mode = force or ("pallas" if on_tpu() else "xla")
    if mode == "pallas":
        return _fa.flash_attention(q, k, v, causal=causal, window=window,
                                   softcap=softcap, scale=scale,
                                   q_block=q_block, kv_block=kv_block)
    if mode == "interpret":
        return _fa.flash_attention(q, k, v, causal=causal, window=window,
                                   softcap=softcap, scale=scale,
                                   q_block=q_block, kv_block=kv_block,
                                   interpret=True)
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   softcap=softcap, scale=scale)


def ssd_scan(x, dt, A, B, C, D, *, chunk=128, force: str | None = None):
    """Mamba-2 SSD chunked scan (model layer); mode selection as
    :func:`flash_attention`."""
    mode = force or ("pallas" if on_tpu() else "xla")
    if mode == "pallas":
        return _ssd.ssd_scan(x, dt, A, B, C, D, chunk=chunk)
    if mode == "interpret":
        return _ssd.ssd_scan(x, dt, A, B, C, D, chunk=chunk, interpret=True)
    return ref.ssd_scan_ref(x, dt, A, B, C, D, chunk=chunk)


def _tile_block(block: int, n_rows: int) -> int:
    """Grid block rows for a lane-dense kernel call: ``block`` rounded up
    to whole (8, 128) int32 tiles, and no larger than ``n_rows`` rounded up
    the same way (small inputs run as one padded block)."""
    tile = 8 * _hp.LANES
    up = lambda n: -(-max(n, 1) // tile) * tile  # noqa: E731
    return min(up(block), up(n_rows))


def _key_columns(keys) -> list:
    """Key columns as a list of (N,) arrays: ``keys`` is a sequence of
    columns, or an (N,) / (N, n_cols) array."""
    if isinstance(keys, (list, tuple)):
        return list(keys)
    if keys.ndim == 1:
        return [keys]
    return [keys[:, c] for c in range(keys.shape[1])]


def hash_partition(keys, num_partitions, *, block: int | None = None,
                   force: str | None = None, with_hist: bool = True):
    """Destination partition ids + histogram for the shuffle build side.

    Args:
      keys: a sequence of (N,) key columns, or an (N,) / (N, n_cols)
        array; integer/uint columns (bitcast to uint32; the dataframe path
        passes ``partition.u32_normalize``d columns so the kernel hash
        equals ``partition.hash_columns`` bit-for-bit).
      num_partitions: P.
      block: pallas grid block rows (default from
        ``cost_model.kernel_params``), rounded up to whole (8, 128) tiles.
        N is padded up to a block multiple internally; the histogram is
        corrected for the pad rows, so any N is accepted.
      force: pin "pallas" | "interpret" | "jnp"/"xla" (default: registry
        dispatch).
      with_hist: False skips the kernel's histogram accumulation and
        returns ``hist=None`` — what ``partition.hash_partition_ids``
        uses, since destinations are all the shuffle build needs.

    Returns:
      (dest (N,) int32, hist (P,) int32 | None) — bit-identical across
      modes.
    """
    cols = _key_columns(keys)
    (N,) = cols[0].shape
    mode = force or registry.resolve("hash_partition", N)
    if mode in ("jnp", "xla") or N == 0:
        dest, hist = ref.hash_partition_ref(jnp.stack(cols, axis=1),
                                            num_partitions)
        return dest, (hist if with_hist else None)
    if block is None:
        block = registry.current_params().block["hash_partition"]
    blk = _tile_block(block, N)
    pad = (-N) % blk
    cols = [c.astype(jnp.uint32) for c in cols]
    if pad:
        cols = [jnp.concatenate([c, jnp.zeros((pad,), c.dtype)]) for c in cols]
    dest, hist = _hp.hash_partition(cols, num_partitions, block=blk,
                                    interpret=(mode == "interpret"),
                                    with_hist=with_hist)
    if pad:
        if with_hist:
            # pad rows are all-zero keys: one deterministic destination
            hist = hist.at[dest[N]].add(-pad)
        dest = dest[:N]
    return dest, hist


def partition_histogram(keys, num_partitions, *, block: int | None = None,
                        force: str | None = None):
    """Per-partition destination counts for the shuffle keys — the
    statistics layer's observation primitive (ISSUE 9).

    The same dispatched :func:`hash_partition` pass that computes
    destination ids also accumulates the (P,) histogram in its kernel's
    resident accumulator; this wrapper returns just that histogram, so the
    adaptive re-planner and ``patterns.quota_from_histogram`` consume the
    exact per-partition row counts the shuffle is about to see
    (bit-identical across pallas/interpret/jnp modes, and to the streaming
    runner's host ``bincount`` mirror).
    """
    _, hist = hash_partition(keys, num_partitions, block=block, force=force,
                             with_hist=True)
    return hist


def segment_reduce_partials(values, seg_ids, *, block=32768, op="sum",
                            interpret=False):
    """Re-export of :func:`segment_reduce.segment_reduce_partials` (the raw
    combine kernel: a segmented inclusive scan) so hot paths and tests
    import one module."""
    return _sr.segment_reduce_partials(values, seg_ids, block=block, op=op,
                                       interpret=interpret)


def segment_reduce(values, seg_ids, num_segments, *, op="sum",
                   block: int | None = None, force: str | None = None):
    """Segment reduction over sorted seg_ids: scan kernel + one write per
    segment.

    The groupby hot path (``local_ops.local_groupby``) calls this with one
    (N,) value column and dense contiguous segment ids; any non-decreasing
    ids work. The column runs through the segmented-scan kernel
    (:func:`segment_reduce_partials`); the last row of every segment then
    holds its complete reduction and is the one row written to that
    segment's output slot. Integer results are bit-identical to the direct
    scatter path (float sums reassociate — docs/KERNELS.md).

    Args:
      values: (N,) values sorted by ``seg_ids``, or (N, width): each
        column reduced on its own.
      seg_ids: (N,) int32 non-decreasing segment ids.
      num_segments: segments in the output; ids outside
        ``[0, num_segments)`` are dropped, matching the callers'
        overflow-bucket use.
      op: "sum" | "max" | "min".
      block: kernel grid block rows (default: ``cost_model.kernel_params``
        block), rounded up to whole (8, 128) tiles.
      force: pin a mode; default dispatches via the registry.

    Returns:
      (num_segments,) or (num_segments, width) array in the value dtype;
      segments with no rows hold the op identity (0 / +max / -max), as on
      the jnp path.
    """
    if values.ndim == 2:
        return jnp.stack(
            [segment_reduce(values[:, c], seg_ids, num_segments, op=op,
                            block=block, force=force)
             for c in range(values.shape[1])], axis=1)
    (N,) = values.shape
    mode = force or registry.resolve("segment_reduce", N, values.dtype, op)
    if mode in ("jnp", "xla") or N == 0:
        return ref.segment_reduce_ref(values, seg_ids, num_segments, op=op)
    if block is None:
        block = registry.current_params().block["segment_reduce"]
    blk = _tile_block(block, N)
    pad = (-N) % blk
    fill = _sr.identity(op, values.dtype)
    seg_ids = seg_ids.astype(jnp.int32)
    if pad:
        values = jnp.concatenate([values, jnp.full((pad,), fill, values.dtype)])
        # the largest id keeps the order sorted and is never an output row
        seg_ids = jnp.concatenate(
            [seg_ids, jnp.full((pad,), jnp.iinfo(jnp.int32).max, jnp.int32)])
    scan = _sr.segment_reduce_partials(values, seg_ids, block=blk, op=op,
                                       interpret=(mode == "interpret"))
    last = jnp.concatenate([seg_ids[1:] != seg_ids[:-1],
                            jnp.ones((1,), bool)])
    live = last & (seg_ids >= 0) & (seg_ids < num_segments)
    dst = jnp.where(live, seg_ids, num_segments)  # everything else: dropped
    out = jnp.full((num_segments,), fill, values.dtype)
    return out.at[dst].set(scan, mode="drop")
