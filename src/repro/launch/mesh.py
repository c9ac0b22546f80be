"""Mesh construction (task spec: MULTI-POD DRY-RUN step 1).

FUNCTIONS, not module-level constants: importing this module never touches
jax device state.

Every mesh is built with ``Auto`` axis types. ``jax.make_mesh`` defaults to
``Explicit`` axes, under which ``with_sharding_constraint`` and the
``shard_map`` row specs this codebase writes are rejected; the engine and
the LM stack both place shardings by name and let the compiler propagate
them, which is what ``Auto`` means.
"""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_host_mesh"]


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], *, devices=None):
    """``jax.make_mesh`` with ``Auto`` axis types (``devices`` defaults to
    ``jax.devices()``)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod (TPU v5e pod); 2x16x16 = 512 across 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(axes: tuple[str, ...] = ("data",)):
    """All locally visible devices on the given axes (1-D, or the most
    square 2-D factorization)."""
    n = len(jax.devices())
    if len(axes) == 1:
        return make_mesh((n,), axes)
    assert len(axes) == 2
    a = int(math.sqrt(n))
    while n % a:
        a -= 1
    return make_mesh((a, n // a), axes)
