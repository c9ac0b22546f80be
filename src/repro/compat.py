"""The JAX surface the codebase enters ``shard_map`` regions through.

Everything that enters a ``shard_map`` region goes through these wrappers,
so a change of JAX's API lands in one module.
"""

from __future__ import annotations

import jax

__all__ = ["shard_map", "axis_size", "optimization_barrier"]


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` (``check_vma`` toggles the per-device
    varying-axis check)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check_vma)


def axis_size(axis) -> int:
    """Static size of a mapped mesh axis (or tuple of axes) from inside
    ``shard_map``. Returns a Python int usable as a loop bound / shape."""
    return jax.lax.axis_size(axis)


#: ``jax.lax.optimization_barrier``, which differentiates in both modes
optimization_barrier = jax.lax.optimization_barrier
