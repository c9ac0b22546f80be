"""Hash-partition kernel: the paper's hot auxiliary operator (§4.2).

Computes, per row block: the lowbias32 key hash and the destination
partition id (hash % P), and optionally the destination histogram — the
quota-planning input (paper §5.4.2: sampled data distribution drives the
shuffle quota).

TPU-native shape: every per-row array is **lane-dense**. A key column of N
rows is viewed as an ``(N/128, 128)`` uint32 array and the grid walks it in
``(block/128, 128)`` tiles, so keys and destinations occupy full (8, 128)
vregs and HBM holds exactly their live bytes (an ``(N, 1)`` layout pads
every row out to 128 lanes, 128x the live bytes). The histogram is a
resident ``(P_pad, 128)`` int32 accumulator — row ``p`` holds per-lane
counts of destination ``p`` — summed over lanes once after the grid; the
GPU-style atomic-increment histogram has no TPU analogue (DESIGN.md §2).

This is the raw kernel (one (N,) uint32 array per key column, N a block
multiple, block a multiple of 128); the engine calls it through
``ops.hash_partition`` (padding + histogram correction + registry
dispatch) from ``partition.hash_partition_ids``, with
``partition.u32_normalize`` pre-normalizing key dtypes so the hash equals
the jnp chain bit-for-bit (docs/KERNELS.md).
"""

from __future__ import annotations

import functools
from collections.abc import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["hash_partition", "LANES"]

#: lanes of a TPU vreg: the minor dimension of every per-row tile
LANES = 128

_M1 = 0x7FEB352D
_M2 = 0x846CA68B
_GOLDEN = 0x9E3779B9


def _dest(key_refs, num_partitions):
    h = None
    for ref in key_refs:
        x = ref[...]
        x = x ^ (x >> 16)
        x = x * jnp.uint32(_M1)
        x = x ^ (x >> 15)
        x = x * jnp.uint32(_M2)
        x = x ^ (x >> 16)
        if h is None:
            h = jnp.zeros_like(x)
        h = h ^ (x + jnp.uint32(_GOLDEN) + (h << 6) + (h >> 2))
    return (h % jnp.uint32(num_partitions)).astype(jnp.int32)


def _kernel_dest_only(*refs, num_partitions, n_cols):
    refs[n_cols][...] = _dest(refs[:n_cols], num_partitions)


def _kernel(*refs, num_partitions, n_cols):
    dest_ref, hist_ref = refs[n_cols], refs[n_cols + 1]
    dest = _dest(refs[:n_cols], num_partitions)
    dest_ref[...] = dest

    @pl.when(pl.program_id(0) == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    def count(p, carry):
        # per-lane count of rows bound for partition p, added to row p
        n = jnp.sum((dest == p).astype(jnp.int32), axis=0, keepdims=True)
        hist_ref[pl.ds(p, 1), :] += n
        return carry

    jax.lax.fori_loop(0, num_partitions, count, 0)


def hash_partition(
    cols: Sequence[jax.Array],   # n_cols key columns, each (N,) uint32
    num_partitions: int,
    *,
    block: int = 65536,
    interpret: bool = False,
    with_hist: bool = True,
) -> tuple[jax.Array, jax.Array | None]:
    """Returns (dest (N,) int32, hist (num_partitions,) int32).

    ``with_hist=False`` skips the histogram accumulation entirely (hist
    comes back ``None``) and lets the grid run in any order — the shape
    the shuffle build side wants, since ``hash_partition_ids`` only
    consumes the destinations."""
    (N,) = cols[0].shape
    n_cols = len(cols)
    assert block % LANES == 0 and N % block == 0, (N, block)
    rows = block // LANES
    cols = [c.astype(jnp.uint32).reshape(N // LANES, LANES) for c in cols]
    tile = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    dest_shape = jax.ShapeDtypeStruct((N // LANES, LANES), jnp.int32)
    opts = dict(num_partitions=num_partitions, n_cols=n_cols)

    if not with_hist:
        dest = pl.pallas_call(
            functools.partial(_kernel_dest_only, **opts),
            grid=(N // block,),
            in_specs=[tile] * n_cols,
            out_specs=tile,
            out_shape=dest_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=interpret,
        )(*cols)
        return dest.reshape(N), None

    p_pad = -(-num_partitions // 8) * 8
    dest, hist = pl.pallas_call(
        functools.partial(_kernel, **opts),
        grid=(N // block,),
        in_specs=[tile] * n_cols,
        out_specs=[tile, pl.BlockSpec((p_pad, LANES), lambda i: (0, 0))],
        out_shape=[dest_shape,
                   jax.ShapeDtypeStruct((p_pad, LANES), jnp.int32)],
        # the histogram block stays resident across the grid: sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*cols)
    return dest.reshape(N), jnp.sum(hist[:num_partitions], axis=1)
