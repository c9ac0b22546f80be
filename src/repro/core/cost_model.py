"""Cost model for distributed dataframe operator patterns (paper §5).

T_total = T_core + T_aux + T_comm, with the Hockney model T = alpha + n*beta
per message. We reproduce paper Table 3 (collective algorithms), Table 4
(core local operator complexities), and the §5.3 per-pattern totals, then
re-parameterize for the TPU fabrics (ICI/DCN) so the planner can select
pattern variants at plan time (paper §5.4).

Units: seconds, bytes, rows. ``n`` follows the paper's bold-n convention:
work per process in *bytes* for communication terms and in *rows* for local
terms (row width ``row_bytes`` converts between them).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Mapping

from .comm.communicator import DCN, HOST, ICI, FabricProfile

__all__ = [
    "CostParams",
    "KernelParams",
    "kernel_params",
    "params_for_fabric",
    "t_shuffle",
    "t_shuffle_pipelined",
    "t_allgather",
    "t_broadcast",
    "t_reduce",
    "t_allreduce",
    "LOCAL_COSTS",
    "t_local",
    "pattern_cost",
    "choose_join_strategy",
    "choose_groupby_strategy",
    "choose_shuffle_algorithm",
    "choose_chunk_count",
    "choose_batch_rows",
    "ADAPTIVE_REPLAN_EVERY",
    "ADAPTIVE_DRIFT",
    "ADAPTIVE_QUOTA_SAFETY",
    "ADAPTIVE_CAPACITY_SAFETY",
]


@dataclasses.dataclass(frozen=True)
class CostParams:
    """Hockney (alpha, beta) + local-compute calibration.

    Attributes:
      fabric: the interconnect profile supplying alpha [s/message] and
        beta [s/byte] (ICI within a pod, DCN across pods, HOST on CPU).
      gamma_s_per_row: per-row local processing constant [s/row]
        (calibrated by benchmarks/bench_local_ops.py; default from CPU
        microbenchmarks).
    """

    fabric: FabricProfile = ICI
    gamma_s_per_row: float = 2e-9

    @property
    def alpha(self) -> float:
        """Per-message startup latency in seconds (Hockney alpha)."""
        return self.fabric.alpha_s

    @property
    def beta(self) -> float:
        """Per-byte transfer time in seconds/byte (Hockney beta = 1/BW)."""
        return self.fabric.beta_s_per_byte


_FABRIC_PROFILES = {"ici": ICI, "dcn": DCN, "host": HOST}


# -- Pallas kernel dispatch parameters (ISSUE 5) ---------------------------------
#
# The paper's cost breakdown (T_core + T_aux + T_comm) puts the local kernels
# — hash partitioning (the shuffle build side) and segment aggregation (the
# groupby combine leg) — on the critical path once shuffles are pipelined.
# ``kernel_params`` models when the Pallas implementations of those kernels
# beat the plain jnp lowering: each ``pallas_call`` pays a fixed launch
# overhead that only amortizes past a per-kernel row threshold, and the
# kernels support a fixed dtype set (everything else stays on jnp).

# Fixed per-launch overhead of a pallas_call (dispatch + VMEM staging), and
# the fraction of the jnp per-row cost the Pallas path saves on TPU (the
# kernels replace scatter-adds the TPU lowers to serialized updates). Both
# are calibration constants in the same spirit as
# ``CostParams.gamma_s_per_row``; ``benchmarks/bench_kernels.py`` reports
# measured speedups next to the thresholds these produce.
_KERNEL_LAUNCH_S = 2e-6
_KERNEL_SAVING_FRACTION = 0.5

# dtypes each kernel lowers for. hash_partition normalizes every engine
# dtype (ints, floats, bools) to uint32 host-side before the kernel, so it
# is unrestricted; segment_reduce computes in the value dtype (exact
# integer sums, f32 floats) and only lowers the dtypes listed here.
_KERNEL_DTYPES = {
    "hash_partition": None,  # None = any dtype (normalized to uint32)
    "segment_reduce": ("int32", "uint32", "float32"),
}

# per-kernel pallas block sizes: rows per grid step, each a whole number of
# lane-dense (8, 128) int32 tiles. segment_reduce takes half the rows: its
# lane scan keeps several block-sized temporaries live in VMEM.
_KERNEL_BLOCKS = {"hash_partition": 65536, "segment_reduce": 32768}


# -- Adaptive mid-stream re-planning knobs (ISSUE 9) -----------------------------
#
# The streaming runner's AdaptiveController (repro.stats.adaptive) corrects
# quota/capacity for later morsels from observed batch histograms. These are
# policy constants, not calibration: re-plans recompile the pipeline for new
# static shapes, so the controller acts only at a coarse cadence and only on
# substantial drift, and always leaves safety headroom over observed maxima
# (an undersized buffer raises under strict_overflow; an oversized one just
# wastes a bounded slice of memory).

#: batches between adaptive re-plan decision points
ADAPTIVE_REPLAN_EVERY = 4

#: relative quota drift (|target - current| / current) that triggers a re-plan
ADAPTIVE_DRIFT = 0.25

#: headroom multiplier over the max observed per-partition histogram cell
ADAPTIVE_QUOTA_SAFETY = 1.5

#: headroom multiplier over the max observed per-worker partial-group count
ADAPTIVE_CAPACITY_SAFETY = 2.0


@dataclasses.dataclass(frozen=True)
class KernelParams:
    """Dispatch inputs for the Pallas kernel layer (one per jax backend).

    Attributes:
      backend: jax default backend the parameters describe ("tpu", "cpu",
        "gpu").
      native: True when Pallas lowers natively on this backend (TPU). On
        every other backend the Pallas path exists only as the
        ``interpret=True`` correctness mode, which is never profitable —
        ``auto`` dispatch then always picks jnp and ``interpret`` is
        reserved for forced parity testing (``set_backend("pallas")``).
      min_rows: kernel name -> row-count threshold above which the Pallas
        launch overhead is amortized (``_KERNEL_LAUNCH_S`` against the
        per-row saving over jnp).
      supported_dtypes: kernel name -> tuple of dtype names the kernel
        lowers for (``None`` = unrestricted).
      block: kernel name -> pallas grid block size in rows.
    """

    backend: str
    native: bool
    min_rows: Mapping[str, int]
    supported_dtypes: Mapping[str, tuple | None]
    block: Mapping[str, int]

    def dtype_supported(self, kernel: str, dtype) -> bool:
        """True when ``kernel`` lowers for ``dtype`` (name, numpy/jnp dtype
        or scalar type)."""
        allowed = self.supported_dtypes.get(kernel)
        if allowed is None:
            return True
        import numpy as np

        try:
            name = np.dtype(dtype).name
        except TypeError:
            name = str(dtype)
        return name in allowed

    def profitable(self, kernel: str, n_rows: int, dtype=None) -> bool:
        """True when the native Pallas ``kernel`` beats jnp for ``n_rows``
        rows of ``dtype`` on this backend (the ``auto`` dispatch test)."""
        if not self.native:
            return False
        if dtype is not None and not self.dtype_supported(kernel, dtype):
            return False
        return n_rows >= self.min_rows.get(kernel, 0)


def kernel_params(backend: str | None = None,
                  p: CostParams = CostParams()) -> KernelParams:
    """Kernel-dispatch parameters for a jax backend (default: the current
    one).

    The row thresholds come from amortizing the fixed pallas_call launch
    overhead against the modeled per-row saving over the jnp lowering:
    ``min_rows = launch_s / (gamma * saving_fraction)``. The registry
    (``repro.kernels.registry``) consults this for every ``auto`` dispatch;
    ``benchmarks/bench_kernels.py`` checks the decisions against measured
    timings."""
    if backend is None:
        import jax  # deferred: cost_model is otherwise jax-free

        backend = jax.default_backend()
    saving = p.gamma_s_per_row * _KERNEL_SAVING_FRACTION
    threshold = int(math.ceil(_KERNEL_LAUNCH_S / max(saving, 1e-30)))
    return KernelParams(
        backend=backend,
        native=(backend == "tpu"),
        min_rows={k: threshold for k in _KERNEL_BLOCKS},
        supported_dtypes=dict(_KERNEL_DTYPES),
        block=dict(_KERNEL_BLOCKS),
    )


def params_for_fabric(fabric: str) -> CostParams:
    """CostParams for a DDFContext fabric name ("ici" | "dcn" | "host").

    Both the eager per-method planners and the lazy plan optimizer route
    through this so the same context yields the same cost-model constants."""
    return CostParams(fabric=_FABRIC_PROFILES.get(fabric, ICI))


# -- Table 3: collective communication costs ------------------------------------
# Each returns (T_startup, T_transfer, T_reduce) in seconds for per-worker
# payload of n bytes across P workers.

def t_shuffle(P: int, n_bytes: float, p: CostParams, algorithm: str = "isend-irecv"):
    """All-to-all shuffle cost (paper Table 3).

    Args:
      P: number of workers.
      n_bytes: per-worker payload in bytes (the paper's bold-n).
      p: Hockney/compute calibration (alpha [s], beta [s/B]).
      algorithm: "isend-irecv" | "ring" | "pairwise" | "bruck".

    Returns:
      (T_startup, T_transfer, T_reduce) in seconds; sum for wall time.
    """
    a, b = p.alpha, p.beta
    if algorithm == "isend-irecv":
        return ((P - 1) * a, (P - 1) / P * n_bytes * b, 0.0)
    if algorithm == "ring":
        return (P * a, P * n_bytes * b, 0.0)
    if algorithm == "pairwise":
        return (P * a, n_bytes * b, 0.0)
    if algorithm == "bruck":
        lg = math.log2(max(P, 2))
        return (lg * a, lg * n_bytes / 2 * b, 0.0)
    raise ValueError(algorithm)


def t_shuffle_pipelined(
    P: int,
    n_bytes: float,
    num_chunks: int,
    p: CostParams,
    core_s: float = 0.0,
    algorithm: str = "isend-irecv",
) -> float:
    """Wall time of the K-chunk pipelined shuffle (comm/compute overlap).

    With the payload split into K chunks, chunk ``i+1``'s transfer overlaps
    chunk ``i``'s local merge/compute, so the steady state runs at
    ``max(T_comm_chunk, T_core_chunk)`` per chunk and only the pipeline
    fill/drain is exposed:

        T ≈ t_comm + t_core + (K-1) * max(t_comm, t_core)

    where ``t_comm = T_startup + T_transfer/K`` (every chunk pays the full
    per-message startup — the alpha term that bounds useful K) and
    ``t_core = core_s / K``.

    Args:
      P: number of workers.
      n_bytes: per-worker *total* payload in bytes.
      num_chunks: pipeline depth K >= 1 (K=1 is the monolithic shuffle).
      p: Hockney/compute calibration.
      core_s: total local compute to overlap against, in seconds (e.g. the
        merge/compact leg of the pattern using the shuffle).
      algorithm: monolithic collective flavor used per chunk.

    Returns:
      Estimated wall seconds for the shuffle + overlapped compute.
    """
    K = max(int(num_chunks), 1)
    s, x, r = t_shuffle(P, n_bytes / K, p, algorithm)
    t_comm = s + x + r  # startup is paid per chunk: t_shuffle already has it
    t_core = core_s / K
    return t_comm + t_core + (K - 1) * max(t_comm, t_core)


def choose_chunk_count(
    P: int,
    n_bytes: float,
    p: CostParams = CostParams(),
    core_s: float = 0.0,
    max_chunks: int = 32,
    min_chunk_bytes: float = 4096.0,
) -> int:
    """Pick the pipeline depth K minimizing :func:`t_shuffle_pipelined`.

    Scans K over powers of two up to ``max_chunks``, rejecting chunk sizes
    below ``min_chunk_bytes`` (tiny chunks are pure startup overhead and
    their timing is noise-dominated). Returns K=1 (monolithic) whenever
    pipelining does not beat the single all-to-all — the planner can treat
    ``K > 1`` as "use the pipelined engine".

    Args:
      P: number of workers.
      n_bytes: per-worker total shuffle payload in bytes.
      p: Hockney/compute calibration.
      core_s: overlappable local compute in seconds.
      max_chunks: largest K considered.
      min_chunk_bytes: smallest per-chunk payload worth a message.

    Returns:
      The chosen chunk count K >= 1.
    """
    best_k, best_t = 1, t_shuffle_pipelined(P, n_bytes, 1, p, core_s)
    k = 2
    while k <= max_chunks:
        if n_bytes / k >= min_chunk_bytes:
            t = t_shuffle_pipelined(P, n_bytes, k, p, core_s)
            if t < best_t:
                best_k, best_t = k, t
        k *= 2
    return best_k


def choose_batch_rows(
    P: int,
    row_bytes: float,
    p: CostParams = CostParams(),
    total_rows: int | None = None,
    memory_budget_bytes: float = 32e6,
    working_set_factor: float = 4.0,
    dispatch_overhead_s: float = 1e-3,
    overhead_fraction: float = 0.05,
    min_rows: int = 256,
) -> int:
    """Pick the global row count per streamed batch (morsel size).

    Two forces bound the choice (the streaming analogue of
    :func:`choose_chunk_count`'s alpha-vs-beta tradeoff):

    - **memory ceiling** (hard): a batch's per-device working set —
      ``row_bytes * rows / P`` inflated by ``working_set_factor`` for
      shuffle buffers and operator intermediates — must fit
      ``memory_budget_bytes``;
    - **overhead amortization** (soft): each batch pays a fixed host-side
      cost ``dispatch_overhead_s`` (decode setup, cache lookups, one
      program dispatch), so batches should be large enough that this stays
      under ``overhead_fraction`` of per-batch device work, modeled as
      ``rows/P * (gamma + row_bytes * beta)`` seconds.

    The intra-batch shuffle pipeline depth is planned separately per
    shuffle op by :func:`choose_chunk_count` once batch-scale row estimates
    are known (``repro.plan.optimizer.plan_shuffles``).

    Args:
      P: number of workers.
      row_bytes: bytes per row of the scanned schema (post-pushdown).
      p: Hockney/compute calibration.
      total_rows: dataset rows, to clamp the batch to the data.
      memory_budget_bytes: per-device budget for one batch's working set.
      working_set_factor: working-set inflation over raw batch bytes.
      dispatch_overhead_s: fixed per-batch host overhead.
      overhead_fraction: target ceiling for overhead / device work.
      min_rows: floor on the returned batch size.

    Returns:
      Global rows per batch (>= 1).
    """
    P = max(int(P), 1)
    row_bytes = max(float(row_bytes), 1.0)
    mem_rows = P * memory_budget_bytes / (row_bytes * max(working_set_factor, 1.0))
    t_row = p.gamma_s_per_row + row_bytes * p.beta  # device seconds/row/worker
    amort_rows = dispatch_overhead_s * P / (max(overhead_fraction, 1e-6) * t_row)
    rows = min(mem_rows, max(amort_rows, float(min_rows)))
    if total_rows is not None:
        rows = min(rows, float(max(int(total_rows), 1)))
    return max(int(rows), 1)


def t_allgather(P: int, n_bytes: float, p: CostParams, algorithm: str = "ring"):
    """AllGather cost (paper Table 3): every worker ends with all N bytes.

    Args/returns as :func:`t_shuffle`; total moved is ``P * n_bytes``.
    """
    a, b = p.alpha, p.beta
    total = P * n_bytes  # paper's N: allgather moves the whole table
    if algorithm == "ring":
        return (P * a, (P - 1) / P * total * b, 0.0)
    if algorithm in ("recursive-doubling", "bruck"):
        return (math.log2(max(P, 2)) * a, (P - 1) / P * total * b, 0.0)
    raise ValueError(algorithm)


def t_broadcast(P: int, n_bytes: float, p: CostParams, algorithm: str = "binomial"):
    """Broadcast cost (paper Table 3): root's n bytes reach all P workers.

    Returns (T_startup, T_transfer, T_reduce) in seconds.
    """
    a, b = p.alpha, p.beta
    lg = math.log2(max(P, 2))
    if algorithm == "binomial":
        return (lg * a, lg * n_bytes * b, 0.0)
    if algorithm == "scatter-allgather":
        return ((lg + P) * a, (P - 1) / P * n_bytes * b, 0.0)
    raise ValueError(algorithm)


def t_reduce(P: int, n_bytes: float, p: CostParams, algorithm: str = "binomial"):
    """Reduce-to-root cost (paper Table 3); third term is reduction compute.

    Returns (T_startup, T_transfer, T_reduce) in seconds.
    """
    a, b = p.alpha, p.beta
    lg = math.log2(max(P, 2))
    if algorithm == "binomial":
        return (lg * a, lg * n_bytes * b, lg * n_bytes * b)
    if algorithm == "reduce-scatter-gather":
        return (lg * a, (P - 1) / P * n_bytes * b, (P - 1) / P * n_bytes * b)
    raise ValueError(algorithm)


def t_allreduce(P: int, n_bytes: float, p: CostParams, algorithm: str = "reduce-scatter-allgather"):
    """AllReduce cost (paper Table 3): all workers end with the reduction.

    Returns (T_startup, T_transfer, T_reduce) in seconds.
    """
    a, b = p.alpha, p.beta
    lg = math.log2(max(P, 2))
    if algorithm == "binomial":
        return (lg * a, lg * n_bytes * b, lg * n_bytes * b)
    if algorithm == "recursive-doubling":
        return (lg * a, lg * n_bytes * b, lg * n_bytes * b)
    if algorithm == "reduce-scatter-allgather":
        return (lg * a, 2 * (P - 1) / P * n_bytes * b, (P - 1) / P * n_bytes * b)
    raise ValueError(algorithm)


def _sum3(t):
    return t[0] + t[1] + t[2]


# -- Table 4: core local operator costs ------------------------------------------
# cost(n_rows, cardinality C) -> seconds, using the calibrated gamma.

LOCAL_COSTS: dict[str, Callable[[float, float, CostParams], float]] = {
    "selection": lambda n, C, p: p.gamma_s_per_row * n,
    "map": lambda n, C, p: p.gamma_s_per_row * n,
    "row_aggregation": lambda n, C, p: p.gamma_s_per_row * n,
    "projection": lambda n, C, p: p.gamma_s_per_row * 1.0,  # O(c)
    "union": lambda n, C, p: p.gamma_s_per_row * n,
    "set_difference": lambda n, C, p: p.gamma_s_per_row * n,
    # paper Table 4: Hash-Join O(n) + O(n/C); Sort-Join O(n log n) + O(n/C)
    "hash_join": lambda n, C, p: p.gamma_s_per_row * (n + n / max(C, 1e-9)),
    "sort_join": lambda n, C, p: p.gamma_s_per_row * (n * math.log2(max(n, 2)) + n / max(C, 1e-9)),
    "transpose": lambda n, C, p: p.gamma_s_per_row * n,
    "unique": lambda n, C, p: p.gamma_s_per_row * n,
    "groupby": lambda n, C, p: p.gamma_s_per_row * n,
    "column_aggregation": lambda n, C, p: p.gamma_s_per_row * n,
    "sort": lambda n, C, p: p.gamma_s_per_row * n * math.log2(max(n, 2)),
}


def t_local(op: str, n_rows: float, cardinality: float = 1.0, p: CostParams = CostParams()) -> float:
    """Core local operator cost (paper Table 4).

    Args:
      op: a key of :data:`LOCAL_COSTS` (e.g. "hash_join", "sort", "groupby").
      n_rows: local rows processed (the paper's bold-n, in rows).
      cardinality: key cardinality fraction C in (0, 1].
      p: calibration; uses ``gamma_s_per_row`` [s/row].

    Returns:
      Estimated local seconds.
    """
    return LOCAL_COSTS[op](n_rows, cardinality, p)


# -- §5.3 per-pattern totals -------------------------------------------------------

def pattern_cost(
    pattern: str,
    *,
    P: int,
    n_rows: float,
    row_bytes: float,
    cardinality: float = 1.0,
    core_op: str = "map",
    params: CostParams = CostParams(),
    shuffle_algorithm: str = "isend-irecv",
    num_chunks: int = 1,
) -> dict[str, float]:
    """Estimated wall time breakdown {core, aux, comm, total} per worker.

    Args:
      pattern: a key of :data:`repro.core.patterns.PATTERNS`.
      P: number of workers.
      n_rows: rows per worker (bold-n in rows).
      row_bytes: bytes per row (converts rows -> bytes for comm terms).
      cardinality: key cardinality fraction C in (0, 1].
      core_op: the core local operator (a :data:`LOCAL_COSTS` key).
      params: Hockney + gamma calibration.
      shuffle_algorithm: collective flavor for shuffle-based patterns.
      num_chunks: pipeline depth K for shuffle-based patterns. With K > 1
        the shuffle and the core op overlap
        (:func:`t_shuffle_pipelined`), so ``total < core + aux + comm``;
        the component terms still report the unoverlapped costs.

    Returns:
      {"core", "aux", "comm", "total"} in seconds.
    """
    p = params
    n_bytes = n_rows * row_bytes
    C = cardinality
    if pattern == "embarrassingly_parallel":
        core = t_local(core_op, n_rows, C, p)
        return _pack(core, 0.0, 0.0)
    if pattern == "shuffle_compute":
        aux = t_local("map", n_rows, C, p)  # hash partition is a map
        comm = _sum3(t_shuffle(P, n_bytes, p, shuffle_algorithm))
        core = t_local(core_op, n_rows, C, p)
        if num_chunks > 1:
            piped = t_shuffle_pipelined(P, n_bytes, num_chunks, p,
                                        core_s=core, algorithm=shuffle_algorithm)
            return {"core": core, "aux": aux, "comm": comm, "total": aux + piped}
        return _pack(core, aux, comm)
    if pattern == "sample_shuffle_compute":
        aux = t_local("sort", n_rows, C, p) + t_local("map", n_rows, C, p)
        comm = _sum3(t_allreduce(P, 8.0 * P, p)) + _sum3(t_shuffle(P, n_bytes, p, shuffle_algorithm))
        core = t_local("sort", n_rows, C, p)  # local merge
        return _pack(core, aux, comm)
    if pattern == "combine_shuffle_reduce":
        core1 = t_local(core_op, n_rows, C, p)
        aux = t_local("map", n_rows * C, C, p)
        comm = _sum3(t_shuffle(P, n_bytes * C, p, shuffle_algorithm))
        core2 = t_local(core_op, n_rows * C, C, p)
        if num_chunks > 1:
            piped = t_shuffle_pipelined(P, n_bytes * C, num_chunks, p,
                                        core_s=core2, algorithm=shuffle_algorithm)
            return {"core": core1 + core2, "aux": aux, "comm": comm,
                    "total": core1 + aux + piped}
        return _pack(core1 + core2, aux, comm)
    if pattern == "broadcast_compute":
        # broadcast the small relation (n here = small side), join locally
        comm = _sum3(t_allgather(P, n_bytes, p))
        core = t_local(core_op, n_rows, C, p)
        return _pack(core, 0.0, comm)
    if pattern == "globally_reduce":
        core = t_local("column_aggregation", n_rows, C, p)
        comm = _sum3(t_allreduce(P, row_bytes, p))
        return _pack(core, 0.0, comm)
    if pattern == "halo_exchange":
        core = t_local("map", n_rows, C, p)
        comm = p.alpha + row_bytes * p.beta  # one neighbor message
        return _pack(core, 0.0, comm)
    if pattern == "partitioned_io":
        core = t_local("map", n_rows, C, p)
        comm = _sum3(t_shuffle(P, n_bytes, p, shuffle_algorithm))
        return _pack(core, 0.0, comm)
    raise ValueError(pattern)


def _pack(core, aux, comm):
    return {"core": core, "aux": aux, "comm": comm, "total": core + aux + comm}


# -- §5.4 runtime strategy selection ----------------------------------------------

def choose_join_strategy(
    n_left_rows: float,
    n_right_rows: float,
    P: int,
    row_bytes: float,
    params: CostParams = CostParams(),
    broadcast_budget_bytes: float = 256e6,
) -> str:
    """Broadcast-join beats shuffle-join when one relation is small enough
    that replicating it costs less than shuffling both (paper §5.3.7/§5.4.2).

    A memory guard rejects broadcast when the replicated relation exceeds
    ``broadcast_budget_bytes`` per worker — the paper's observation that
    Modin's broadcast-only joins OOM on same-order relations is a memory
    failure, not just a bandwidth one."""
    small = min(n_left_rows, n_right_rows)
    if small * row_bytes > broadcast_budget_bytes:
        return "shuffle"
    shuffle_cost = (
        _sum3(t_shuffle(P, n_left_rows / P * row_bytes, params))
        + _sum3(t_shuffle(P, n_right_rows / P * row_bytes, params))
    )
    bcast_cost = _sum3(t_allgather(P, small / P * row_bytes, params))
    return "broadcast" if bcast_cost < shuffle_cost else "shuffle"


def choose_groupby_strategy(cardinality: float, threshold: float = 0.5) -> bool:
    """pre_combine? Combine-Shuffle-Reduce wins at low cardinality; at C->1 it
    degrades below plain Shuffle-Compute because the core op runs twice
    (paper §5.4.1). Returns True for pre-combine."""
    return cardinality < threshold


def choose_shuffle_algorithm(P: int, n_bytes: float, params: CostParams = CostParams()) -> str:
    """Latency-bound (small n, large P) -> Bruck; else pairwise/isend
    (paper §6.1.1 recommendation)."""
    best, best_t = None, float("inf")
    for alg in ("isend-irecv", "ring", "pairwise", "bruck"):
        t = _sum3(t_shuffle(P, n_bytes, params, alg))
        if t < best_t:
            best, best_t = alg, t
    return best
