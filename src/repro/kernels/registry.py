"""Kernel dispatch registry: route hot paths to Pallas or jnp (ISSUE 5).

The dataframe hot paths (hash partitioning on the shuffle build side,
segment aggregation in the groupby combine leg) call :func:`resolve` at
trace time to pick an execution mode per kernel:

- ``"pallas"``    — the native Pallas lowering (TPU);
- ``"interpret"`` — the same kernel body executed via
  ``pallas_call(interpret=True)``: bit-identical semantics on any backend,
  used as the CPU correctness fallback so parity tests and the CI smoke
  leg run without a TPU;
- ``"jnp"``       — the plain jax.numpy implementation (the pre-ISSUE-5
  behavior, and the fallback whenever Pallas is not profitable).

Dispatch is driven by two inputs:

1. the process-wide **backend override** — ``set_backend("pallas")`` forces
   the Pallas path everywhere (interpret mode off-TPU), ``"jnp"`` pins the
   plain path, ``"auto"`` (default) defers to the cost model. The initial
   value comes from the ``REPRO_KERNEL_BACKEND`` environment variable (the
   CI kernel smoke leg sets it);
2. the **cost model** — ``repro.core.cost_model.kernel_params`` supplies
   per-kernel row thresholds, supported dtypes and the native-lowering flag
   for the current jax backend. ``auto`` picks Pallas only when
   ``KernelParams.profitable`` says the launch overhead amortizes.

Because the decision is taken at trace time, every compiled-operator cache
key must include :func:`dispatch_signature` — ``repro.core.api.cached_op``
and the plan cache in ``repro.plan.executor`` do — so flipping the backend
never aliases a compiled program built for the other one.
"""

from __future__ import annotations

import contextlib
import functools
import os

import jax
import numpy as np

from ..core import cost_model

__all__ = [
    "KERNEL_OPS",
    "set_backend",
    "get_backend",
    "use_backend",
    "current_params",
    "resolve",
    "explain",
    "dispatch_signature",
]

# kernels the registry dispatches (names match cost_model.kernel_params)
KERNEL_OPS = ("hash_partition", "segment_reduce")

_VALID = ("auto", "pallas", "jnp")

_backend = os.environ.get("REPRO_KERNEL_BACKEND", "auto")
if _backend not in _VALID:
    raise ValueError(
        f"REPRO_KERNEL_BACKEND={_backend!r} invalid; expected one of {_VALID}")


def set_backend(mode: str) -> str:
    """Set the process-wide kernel backend override; returns the previous
    value.

    ``"pallas"`` forces the Pallas path for every dispatched kernel
    (native on TPU, ``interpret=True`` elsewhere — bit-identical, slow);
    ``"jnp"`` pins the plain jax.numpy path; ``"auto"`` (the default)
    lets ``cost_model.kernel_params`` decide per kernel and row count.
    Compiled-op caches key on the override, so flipping it retraces
    rather than reusing programs built for the other backend."""
    global _backend
    if mode not in _VALID:
        raise ValueError(f"backend must be one of {_VALID}, got {mode!r}")
    prev = _backend
    _backend = mode
    return prev


def get_backend() -> str:
    """Current backend override: "auto" | "pallas" | "jnp"."""
    return _backend


@contextlib.contextmanager
def use_backend(mode: str):
    """Context manager form of :func:`set_backend` (restores on exit)."""
    prev = set_backend(mode)
    try:
        yield
    finally:
        set_backend(prev)


@functools.lru_cache(maxsize=8)
def _params(jax_backend: str) -> cost_model.KernelParams:
    return cost_model.kernel_params(jax_backend)


def current_params() -> cost_model.KernelParams:
    """The :class:`~repro.core.cost_model.KernelParams` for the current jax
    backend (cached per backend name)."""
    return _params(jax.default_backend())


def resolve(kernel: str, n_rows: int, dtype=None, op: str | None = None) -> str:
    """Pick the execution mode for one kernel call at trace time.

    Args:
      kernel: a :data:`KERNEL_OPS` name.
      n_rows: static row count of the call (the partition capacity).
      dtype: value dtype, for the kernel's supported-dtype gate (``None``
        skips the gate — hash_partition normalizes all dtypes itself).
      op: the reduction (segment_reduce's "sum" | "min" | "max"); it only
        labels the dispatch counter, every op lowers for every supported
        dtype.

    Returns:
      "pallas" | "interpret" | "jnp". A forced ``"pallas"`` backend still
      returns "jnp" for dtypes the kernel cannot lower — the jnp path *is*
      the kernel's semantics, so forced-parity runs stay exact.
    """
    if kernel not in KERNEL_OPS:
        raise ValueError(f"unknown kernel {kernel!r}; expected {KERNEL_OPS}")
    decision = _decide(kernel, n_rows, dtype)
    _note_dispatch(kernel, n_rows, decision, dtype, op)
    return decision


def _decide(kernel: str, n_rows: int, dtype) -> str:
    p = current_params()
    if _backend == "jnp":
        return "jnp"
    if dtype is not None and not p.dtype_supported(kernel, dtype):
        return "jnp"
    if _backend == "pallas":
        return "pallas" if p.native else "interpret"
    return "pallas" if p.profitable(kernel, n_rows, dtype) else "jnp"


def _note_dispatch(kernel: str, n_rows: int, decision: str, dtype=None,
                   op: str | None = None) -> None:
    """Record one dispatch decision: always counted in the global metrics
    registry as ``kernels.dispatch.<kernel>[.<dtype>][.<op>].<decision>``;
    while tracing, also attached to the enclosing span's
    ``kernel_dispatch`` attr (or an instant event when no span is open).
    Dispatch happens at trace time, so the cost is per compile, not per
    batch. Imports are deferred — ``repro.obs`` pulls in no engine modules,
    but keeping the registry import-light avoids any cycle risk."""
    from ..obs import metrics as _metrics
    from ..obs import trace as _trace

    labels = {"kernel": kernel}
    if dtype is not None:
        labels["dtype"] = np.dtype(dtype).name
    if op is not None:
        labels["op"] = op
    name = ".".join(["kernels.dispatch", *labels.values(), decision])
    _metrics.registry().counter(name).add(1)
    if _trace.enabled():
        sp = _trace.current_span()
        entry = {**labels, "n_rows": int(n_rows), "decision": decision}
        if sp is not None:
            sp.attrs.setdefault("kernel_dispatch", []).append(entry)
        else:
            _trace.instant("kernels.dispatch", **entry)


def explain(kernel: str, n_rows: int, dtype=None) -> dict:
    """The :func:`resolve` decision plus the model inputs that produced it
    (for benchmarks and debugging dispatch behavior). Unlike
    :func:`resolve`, no dispatch decision is recorded — explaining is not
    dispatching."""
    p = current_params()
    return {
        "kernel": kernel,
        "n_rows": int(n_rows),
        "dtype": None if dtype is None else str(dtype),
        "backend_override": _backend,
        "jax_backend": p.backend,
        "native": p.native,
        "min_rows": int(p.min_rows.get(kernel, 0)),
        "dtype_supported": (dtype is None or p.dtype_supported(kernel, dtype)),
        "decision": _decide(kernel, n_rows, dtype),
    }


def dispatch_signature() -> tuple:
    """Stable key component capturing every global input to :func:`resolve`
    — include it in any cache keyed on traced kernel behavior."""
    return (_backend, jax.default_backend())
