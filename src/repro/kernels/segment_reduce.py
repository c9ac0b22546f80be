"""Segment-reduce kernel: the combine leg of Combine-Shuffle-Reduce (§5.3.4).

Input: one value column sorted by segment id (the groupby sort order) and
the segment ids. The kernel computes the **segmented inclusive scan**: row
``i`` of the output holds the reduction of every row ``j <= i`` with
``seg[j] == seg[i]``, so the last row of each segment holds that segment's
complete reduction and the merge (``ops.segment_reduce``) writes just
those rows, one per segment — no scatter-add, which the TPU lowers to
serialized updates (it has no atomics; DESIGN.md §2).

TPU-native shape: values, segment ids and the scan are lane-dense
``(N/128, 128)`` arrays walked in ``(block/128, 128)`` tiles. Per tile:

1. a Hillis-Steele scan along the 128 lanes — seven ``pltpu.roll`` steps,
   each combining a row with the row ``k`` lanes to its left when both
   carry the same segment id (ids are sorted, so equal ids bound a
   contiguous run) — all on the VPU, in the value dtype;
2. a sequential pass down the tile's rows that folds the running value at
   the previous row's last lane into the leading run of the next row;
   the same carry crosses grid steps in a VMEM scratch, so the grid runs
   in order (``"arbitrary"``).

Every step is an elementwise ``+``/``min``/``max`` in the value dtype, so
integer sums wrap exactly like ``segment_sum`` and min/max are exact in
every dtype: the kernel needs neither an integer matmul nor a 3-D
one-hot broadcast, which is what the TPU compiler accepts for int32,
uint32 and float32. Float sums associate in scan order, not scatter
order (docs/KERNELS.md).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .hash_partition import LANES

__all__ = ["segment_reduce_partials", "identity"]

# the same identity sentinels the jnp operator paths mask with — one
# definition (core.dataframe) so kernel/jnp bit-parity cannot drift
from ..core.dataframe import max_sentinel as _hi_sentinel  # noqa: E402
from ..core.dataframe import min_sentinel as _lo_sentinel  # noqa: E402


def _combine(op: str, dtype):
    if op == "sum":
        return jnp.add
    if jnp.issubdtype(dtype, jnp.unsignedinteger):
        # the TPU compiler has no unsigned min/max; an unsigned compare
        # and select is the same function
        if op == "min":
            return lambda a, b: jnp.where(b < a, b, a)
        return lambda a, b: jnp.where(b > a, b, a)
    return {"min": jnp.minimum, "max": jnp.maximum}[op]


def identity(op: str, dtype):
    """The identity element of ``op`` in ``dtype`` (0, +max, -max)."""
    if op == "sum":
        return jnp.zeros((), dtype)
    if op == "min":
        return _hi_sentinel(dtype)
    if op == "max":
        return _lo_sentinel(dtype)
    raise ValueError(op)


def _kernel(vals_ref, segs_ref, out_ref, cval_ref, cseg_ref, *, op, rows):
    comb = _combine(op, vals_ref.dtype)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        # an identity carry is a no-op whatever segment it claims
        cval_ref[...] = jnp.full(cval_ref.shape, identity(op, cval_ref.dtype))
        cseg_ref[...] = jnp.zeros_like(cseg_ref)

    x = vals_ref[...]
    s = segs_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    k = 1
    while k < LANES:
        take = (lane >= k) & (pltpu.roll(s, k, 1) == s)
        x = jnp.where(take, comb(x, pltpu.roll(x, k, 1)), x)
        k *= 2
    out_ref[...] = x

    def fold_row(r, carry):
        cval, cseg = carry
        xr = out_ref[pl.ds(r, 1), :]
        sr = segs_ref[pl.ds(r, 1), :]
        xr = jnp.where(sr == cseg, comb(xr, cval), xr)
        out_ref[pl.ds(r, 1), :] = xr
        return (jnp.broadcast_to(xr[:, LANES - 1:], xr.shape),
                jnp.broadcast_to(sr[:, LANES - 1:], sr.shape))

    cval, cseg = jax.lax.fori_loop(0, rows, fold_row,
                                   (cval_ref[...], cseg_ref[...]))
    cval_ref[...] = cval
    cseg_ref[...] = cseg


def segment_reduce_partials(
    values: jax.Array,     # (N,) sorted by segment
    seg_ids: jax.Array,    # (N,) int32 non-decreasing
    *,
    block: int = 32768,
    op: str = "sum",
    interpret: bool = False,
) -> jax.Array:
    """Segmented inclusive scan of ``values`` by ``seg_ids``: the Pallas
    combine kernel.

    Returns an (N,) array in the value dtype whose row ``i`` is the ``op``
    reduction of rows ``j <= i`` of the same segment — the running partial
    of each segment, complete at the segment's last row. N must be a
    multiple of ``block`` and ``block`` a multiple of 128."""
    (N,) = values.shape
    assert block % LANES == 0 and N % block == 0, (N, block)
    rows = block // LANES
    tile = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, op=op, rows=rows),
        grid=(N // block,),
        in_specs=[tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((N // LANES, LANES), values.dtype),
        scratch_shapes=[pltpu.VMEM((1, LANES), values.dtype),
                        pltpu.VMEM((1, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(values.reshape(N // LANES, LANES),
      seg_ids.astype(jnp.int32).reshape(N // LANES, LANES))
    return out.reshape(N)
