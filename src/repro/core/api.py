"""User-facing distributed dataframe API (paper §2.1, Fig. 2b).

``DDF`` is the *virtual* collection of row partitions: users write
single-partition-style programs; the runtime decides local vs distributed
execution from operator semantics (paper Fig. 1). Globally a DDF is a set of
device-sharded columns of shape (P*capacity, ...) plus per-partition valid
counts (P,), laid out over the mesh's row-partition axes.

Each method wraps the corresponding in-shard_map operator from
``operators.py`` under jit (compiled callables are cached per (context,
operator, schema, static-params) so steady-state calls don't re-trace).
Planning (quota/capacity/strategy) is host-side via ``patterns.py``.

Auxiliary outputs (overflow counters, pivots, ...) come back with a leading
per-worker axis of size P.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import threading
from typing import Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import cost_model, operators, patterns
from .vocab import DictVocab, encode_strings, is_string_array
from .. import expr as _expr
from ..compat import shard_map
from ..obs import trace as _trace
from .comm.communicator import Communicator, make_communicator
from .dataframe import Table
from .local_ops import select as local_select
from .local_ops import with_column as local_with_column
from .partition import default_quota

__all__ = ["DDFContext", "DDF"]


class _LRUCache:
    """Bounded least-recently-used cache for compiled operators/plans.

    The previous unbounded dict keyed on ``id(mesh)`` could (a) grow without
    limit across contexts and (b) alias entries when a garbage-collected
    mesh's id was reused; this keys on stable signatures (see
    :func:`mesh_signature`) and evicts the least recently used entry past
    ``maxsize``.

    Thread-safe: concurrent queries multiplexed by ``repro.service`` share
    the process-wide plan/op caches, so get/put (including the recency
    reordering and eviction, which mutate the OrderedDict) run under a
    lock. Hit/miss/eviction counts are tracked for the service's cache
    telemetry (:meth:`stats`).
    """

    def __init__(self, maxsize: int = 256):
        self.maxsize = maxsize
        self._d: collections.OrderedDict = collections.OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key):
        with self._lock:
            try:
                self._d.move_to_end(key)
                val = self._d[key]
            except KeyError:
                self.misses += 1
                return None
            self.hits += 1
            return val

    def put(self, key, value):
        with self._lock:
            self._d[key] = value
            self._d.move_to_end(key)
            while len(self._d) > self.maxsize:
                self._d.popitem(last=False)
                self.evictions += 1

    def stats(self) -> dict:
        """Telemetry snapshot: ``{hits, misses, evictions, size, maxsize}``."""
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions, "size": len(self._d),
                    "maxsize": self.maxsize}

    def __len__(self):
        with self._lock:
            return len(self._d)


@functools.lru_cache(maxsize=32)
def mesh_signature(mesh: Mesh) -> tuple:
    """Stable identity for a mesh: axis names + shape + device ids.

    Unlike ``id(mesh)``, this survives garbage collection (ids can be
    reused) and treats equal meshes as equal, so cache entries are neither
    aliased nor duplicated. Memoized so the O(n_devices) tuple is not
    rebuilt on every operator dispatch."""
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(int(d.id) for d in mesh.devices.flat))


_OP_CACHE = _LRUCache(maxsize=256)


def cached_op(ctx: "DDFContext", key: tuple, fn: Callable, arg_schemas: tuple) -> Callable:
    """Fetch-or-compile the jitted shard_map for (context, op key, schemas).

    Shared by the eager ``DDF._run`` path and the lazy plan executor, so a
    lazy pipeline whose final stage matches an eager op reuses the same
    compiled callable. The key includes the kernel-dispatch signature
    (``repro.kernels.registry``): hot-path kernel routing is decided at
    trace time, so a compiled program built under one backend override
    must never serve another."""
    from ..kernels import registry as _kernel_registry

    cache_key = (mesh_signature(ctx.mesh), ctx.axes, key, arg_schemas,
                 _kernel_registry.dispatch_signature())
    op = _OP_CACHE.get(cache_key)
    if op is None:
        op = _Op(_build_op(ctx, fn, arg_schemas), str(key[0]))
        _OP_CACHE.put(cache_key, op)
    return op


class _Op:
    """A compiled operator as the op cache holds it: the jitted shard_map
    and its spans. ``jax.jit`` is lazy, so the first call is where the
    program is traced, lowered and compiled (or loaded from the persistent
    cache) and then enqueued: it is spanned ``core.compile``. Every later
    call is one host enqueue, spanned ``core.dispatch``."""

    __slots__ = ("jitted", "name", "fresh")

    def __init__(self, jitted: Callable, name: str):
        self.jitted = jitted
        self.name = name
        self.fresh = True

    def __call__(self, *flat):
        if self.fresh:
            self.fresh = False
            with _trace.span("core.compile", op=self.name):
                return self.jitted(*flat)
        with _trace.span("core.dispatch", op=self.name):
            return self.jitted(*flat)


@dataclasses.dataclass(frozen=True)
class DDFContext:
    """Execution environment: mesh + row-partition axes (paper's `env`)."""

    mesh: Mesh
    axes: tuple[str, ...] = ("data",)
    fabric: str = "ici"

    @property
    def nworkers(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.axes]))

    @property
    def axis(self):
        return self.axes if len(self.axes) > 1 else self.axes[0]

    def comm(self) -> Communicator:
        return make_communicator(self.axis, self.fabric)

    def row_spec(self) -> P:
        return P(self.axes)

    def sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.row_spec())


def _schema_sig(ddf: "DDF") -> tuple:
    return tuple((k, str(v.dtype), v.shape) for k, v in sorted(ddf.columns.items()))


def callable_signature(fn: Callable) -> tuple:
    """Best-effort stable identity for a user callable (predicate/map fn):
    code location + bytecode hash + hashable default/closure values.

    Cache keys for select/map ops include this alongside the user-supplied
    name, so two different lambdas (even same-line ones differing only in a
    captured constant) do not silently alias a compiled operator."""
    code = getattr(fn, "__code__", None)
    if code is None:
        return (repr(fn),)

    def ident(v):
        # keep the raw value when hashable: cache-key comparison then uses
        # __eq__, so hash-equal-but-unequal values (hash(-1)==hash(-2))
        # never alias; unhashable values fall back to object identity,
        # which the cache entry itself keeps alive.
        try:
            hash(v)
            return v
        except TypeError:
            return id(v)

    cells = tuple(ident(c.cell_contents)
                  for c in (getattr(fn, "__closure__", None) or ()))
    defaults = tuple(ident(v) for v in (getattr(fn, "__defaults__", None) or ()))
    # co_consts/co_names distinguish same-line lambdas that differ only in a
    # literal or a referenced column name (identical co_code).
    consts = tuple(ident(v) for v in code.co_consts)
    return (code.co_filename, code.co_firstlineno, hash(code.co_code),
            code.co_names, consts, defaults, cells)


def _build_op(ctx: DDFContext, fn: Callable, arg_schemas: tuple) -> Callable:
    """Compile ``fn(comm, *local_tables) -> Table | (Table|aux, ...)`` into a
    jitted shard_map over the context's row-partition axes."""
    spec = P(ctx.axes)
    nw = ctx.nworkers

    def wrapper(*flat):
        locs = []
        for i in range(0, len(flat), 2):
            cols, cnt = flat[i], flat[i + 1]
            locs.append(Table(dict(cols), cnt.reshape(())))
        res = fn(ctx.comm(), *locs)
        if not isinstance(res, tuple):
            res = (res,)
        out = []
        for r in res:
            if isinstance(r, Table):
                out.append((dict(r.columns), r.nvalid.reshape((1,))))
            else:
                # aux pytree: add a leading per-worker axis
                out.append(jax.tree.map(lambda x: jnp.asarray(x)[None, ...], r))
        return tuple(out)

    in_specs = []
    for schema in arg_schemas:
        in_specs.append({k: spec for k, _, _ in schema})
        in_specs.append(spec)
    # Every output leaf carries a leading per-worker axis (table columns have
    # their capacity dim; nvalid is reshaped (1,); aux leaves get [None]), so
    # a single prefix spec shards the whole output pytree.
    sm = shard_map(wrapper, mesh=ctx.mesh, in_specs=tuple(in_specs),
                   out_specs=spec, check_vma=False)
    return jax.jit(sm)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DDF:
    """Distributed dataframe: global columns (P*cap, ...) + counts (P,)."""

    columns: dict[str, jax.Array]
    counts: jax.Array  # (P,) int32 — valid rows per partition
    ctx: DDFContext
    #: host-side vocabularies of dict-encoded string columns (name ->
    #: ``DictVocab``); the device column holds int32 codes. Rides in the
    #: pytree aux data (DictVocab is hashable) so jit caching keys on it.
    vocabs: dict = dataclasses.field(default_factory=dict)
    # host-side caches (not pytree children): global row count + lazy handle
    _nrows: int | None = dataclasses.field(default=None, repr=False, compare=False)
    _lazy_cache: object = dataclasses.field(default=None, repr=False, compare=False)

    def tree_flatten(self):
        names = tuple(sorted(self.columns))
        return (tuple(self.columns[n] for n in names) + (self.counts,),
                (names, self.ctx, tuple(sorted(self.vocabs.items()))))

    @classmethod
    def tree_unflatten(cls, aux, children):
        names, ctx, vocabs = aux
        *cols, counts = children
        return cls(dict(zip(names, cols)), counts, ctx, dict(vocabs))

    # -- metadata --------------------------------------------------------------
    @property
    def capacity(self) -> int:
        return next(iter(self.columns.values())).shape[0] // self.ctx.nworkers

    @property
    def column_names(self):
        return tuple(sorted(self.columns))

    def num_rows(self) -> int:
        """Global live-row count (device->host sync; cached per instance)."""
        if self._nrows is None:
            self._nrows = int(np.sum(np.asarray(self.counts)))
        return self._nrows

    # -- construction ------------------------------------------------------------
    @classmethod
    def from_numpy(cls, data: Mapping[str, np.ndarray], ctx: DDFContext,
                   capacity: int | None = None, mode: str | None = None):
        """Partitioned input: rows split contiguously across workers
        (paper §5.3.8 partitioned I/O).

        ``mode`` selects the API flavor: "eager" returns a ``DDF`` whose
        methods execute immediately (today's semantics); "lazy" returns a
        ``repro.plan.LazyDDF`` that builds a logical plan and executes on
        ``.collect()``. None consults ``repro.plan.get_default_mode()``."""
        nw = ctx.nworkers
        n = len(next(iter(data.values())))
        per = -(-n // nw)
        cap = per if capacity is None else capacity
        cols = {}
        vocabs = {}
        for k, v in data.items():
            v = np.asarray(v)
            if is_string_array(v):  # dict-encode: int32 codes + host vocab
                v, vocabs[k] = encode_strings(v)
            buf = np.zeros((nw, cap) + v.shape[1:], v.dtype)
            for w in range(nw):
                chunk = v[w * per: (w + 1) * per][:cap]
                buf[w, : len(chunk)] = chunk
            cols[k] = jax.device_put(buf.reshape((nw * cap,) + v.shape[1:]), ctx.sharding())
        counts = np.minimum(np.maximum(n - per * np.arange(nw), 0), min(per, cap)).astype(np.int32)
        ddf = cls(cols, jax.device_put(counts, ctx.sharding()), ctx, vocabs)
        if mode is None:
            from .. import plan  # local import: plan depends on this module
            mode = plan.get_default_mode()
        return ddf.lazy() if mode == "lazy" else ddf

    def to_numpy(self) -> dict[str, np.ndarray]:
        """Gather live rows to host, in partition order. Dict-encoded
        columns come back decoded (numpy string arrays, not codes)."""
        counts = np.asarray(self.counts)
        cap = self.capacity
        out = {}
        for k, v in self.columns.items():
            v = np.asarray(v).reshape((self.ctx.nworkers, cap) + v.shape[1:])
            g = np.concatenate([v[w, : counts[w]] for w in range(self.ctx.nworkers)])
            out[k] = self.vocabs[k].decode(g) if k in self.vocabs else g
        return out

    # -- execution plumbing ---------------------------------------------------------
    def _run(self, key: tuple, fn, *ddfs: "DDF"):
        schemas = tuple(_schema_sig(d) for d in (self,) + ddfs)
        op = cached_op(self.ctx, key, fn, schemas)
        flat = []
        for d in (self,) + ddfs:
            flat.append(d.columns)
            flat.append(d.counts)
        results = op(*flat)
        out = []
        for item in results:
            if isinstance(item, tuple) and len(item) == 2 and isinstance(item[0], dict) and not isinstance(item[1], dict):
                out.append(DDF(item[0], item[1], self.ctx))
            else:
                out.append(item)
        return out[0] if len(out) == 1 else tuple(out)

    # -- dict-encoded string columns (vocab plumbing) ---------------------------
    def _attach(self, res, vocabs: Mapping[str, DictVocab]):
        """Attach vocab metadata to the DDF element(s) of an op result,
        restricted to columns the result actually has."""
        items = res if isinstance(res, tuple) else (res,)
        for item in items:
            if isinstance(item, DDF):
                item.vocabs = {n: v for n, v in vocabs.items()
                               if n in item.columns}
        return res

    def _recode(self, mappings: Mapping[str, np.ndarray]) -> "DDF":
        """Apply per-column int32 gather maps — the device half of vocab
        unification (``new_codes = map[old_codes]``). The op cache keys on
        the map *contents*, so two recodes into different merged vocabs
        never alias one compiled program."""
        maps = {n: np.asarray(m, dtype=np.int32) for n, m in mappings.items()
                if n in self.columns}
        if not maps:
            # shallow copy: callers overwrite .vocabs on the result, and
            # mutating self would corrupt the input relation's metadata
            return DDF(dict(self.columns), self.counts, self.ctx,
                       dict(self.vocabs))
        key = ("recode", tuple(sorted((n, m.tobytes()) for n, m in maps.items())))

        def fn(comm, t):
            cols = dict(t.columns)
            for n, m in maps.items():
                cols[n] = jnp.asarray(m)[cols[n]]
            return Table(cols, t.nvalid)

        return self._run(key, fn)

    def _unify_vocabs_with(self, other: "DDF", op: str):
        """Vocab unification at a binary boundary (join/union/difference):
        merge each shared dict column's vocabs host-side and recode both
        sides into the merged code space. Returns ``(left, right, merged)``
        where merged covers every dict column of either side."""
        mixed = sorted(n for n in set(self.vocabs) ^ set(other.vocabs)
                       if n in self.columns and n in other.columns)
        if mixed:
            raise TypeError(
                f"{op}: column(s) {mixed} are dict-encoded strings on one "
                f"side but plain numerics on the other — codes and raw "
                f"values are not comparable; encode both sides or neither")
        merged = {**other.vocabs, **self.vocabs}
        lmaps, rmaps = {}, {}
        for n in sorted(set(self.vocabs) & set(other.vocabs)):
            lv, rv = self.vocabs[n], other.vocabs[n]
            if lv.words == rv.words:
                continue
            mv = lv.merge(rv)
            merged[n] = mv
            if not lv.is_identity_into(mv):
                lmaps[n] = lv.recode_map(mv)
            if not rv.is_identity_into(mv):
                rmaps[n] = rv.recode_map(mv)
        left, right = self._recode(lmaps), other._recode(rmaps)
        left.vocabs = {n: merged[n] for n in self.vocabs}
        right.vocabs = {n: merged[n] for n in other.vocabs}
        return left, right, merged

    # -- embarrassingly parallel (paper §5.3.1) ----------------------------------
    def select(self, pred, name: str = "pred") -> "DDF":
        """Filter rows by a boolean expression: ``select(col("a") > 3)``.

        Expressions (``repro.expr``) are validated against the schema at
        call time (unknown columns raise ``KeyError`` listing the schema),
        constant-folded, compiled to a pure jax function, and cache-keyed
        by their structural hash. Passing a Python callable over the column
        dict is deprecated (one-shot ``DeprecationWarning``) but keeps
        bit-identical behavior through the legacy fingerprint path."""
        if isinstance(pred, (_expr.Expr, bool)) or _expr.is_when_builder(pred):
            pred = _expr.prepare_row_expr(pred, self.columns, "select",
                                          vocabs=self.vocabs or None)
            fn = _expr.to_jax_fn(pred)
            return self._attach(self._run(("select", name, pred),
                                          lambda comm, t: local_select(t, fn)),
                                self.vocabs)
        _expr.warn_callable_deprecated("select")
        return self._attach(self._run(("select", name, callable_signature(pred)),
                                      lambda comm, t: local_select(t, pred)),
                            self.vocabs)

    def with_column(self, name: str, value) -> "DDF":
        """Add (or overwrite) column ``name`` from an expression:
        ``with_column("c", col("a") + col("b"))``. Scalars are coerced to
        literals; all other columns pass through unchanged. The expression
        is validated against the schema (``KeyError`` listing the schema on
        unknown references) and compiled to a pure jax function."""
        e = _expr.prepare_row_expr(value, self.columns, "with_column",
                                   vocabs=self.vocabs or None)
        fn = _expr.to_jax_fn(e)
        return self._attach(
            self._run(("with_column", name, e),
                      lambda comm, t: local_with_column(t, name, fn)),
            {n: v for n, v in self.vocabs.items() if n != name})

    def _check_columns(self, names: Sequence[str], op: str) -> None:
        missing = [n for n in names if n not in self.columns]
        if missing:
            raise KeyError(
                f"{op}: unknown column(s) {missing}; "
                f"available schema: {sorted(self.columns)}")

    def project(self, names: Sequence[str]) -> "DDF":
        """Column projection (zero-copy). Unknown names raise ``KeyError``
        listing the available schema instead of failing inside jit."""
        self._check_columns(names, "project")
        return DDF({n: self.columns[n] for n in names}, self.counts, self.ctx,
                   {n: v for n, v in self.vocabs.items() if n in names})

    def drop(self, names: Sequence[str]) -> "DDF":
        """Drop columns — the natural inverse of :meth:`project`."""
        names = tuple(names)
        self._check_columns(names, "drop")
        gone = set(names)
        return DDF({k: v for k, v in self.columns.items() if k not in gone},
                   self.counts, self.ctx,
                   {k: v for k, v in self.vocabs.items() if k not in gone})

    def rename(self, mapping: Mapping[str, str]) -> "DDF":
        """Column rename (paper Fig. 6 Modin-algebra surface; zero-copy).
        Unknown source names raise ``KeyError``; colliding target names
        raise ``ValueError`` (a silent dict overwrite would drop a column)."""
        self._check_columns(tuple(mapping), "rename")
        targets = [mapping.get(k, k) for k in self.columns]
        dup = {t for t in targets if targets.count(t) > 1}
        if dup:
            raise ValueError(f"rename: duplicate target column(s) {sorted(dup)}")
        return DDF({mapping.get(k, k): v for k, v in self.columns.items()},
                   self.counts, self.ctx,
                   {mapping.get(k, k): v for k, v in self.vocabs.items()})

    def map_columns(self, fn, name: str = "map") -> "DDF":
        """Legacy column-wise map over the raw column dict (deprecated —
        one-shot ``DeprecationWarning``; use expression-based
        :meth:`with_column` / :meth:`project` instead, which the optimizer
        can analyze). Behavior is unchanged: bit-identical results through
        the callable-fingerprint cache path."""
        _expr.warn_callable_deprecated("map_columns")
        return self._run(("map", name, callable_signature(fn)),
                         lambda comm, t: Table(dict(fn(t.columns)), t.nvalid))

    # -- loosely synchronous ----------------------------------------------------
    def join(self, other: "DDF", on: Sequence[str], strategy: str = "auto",
             quota: int | None = None, capacity: int | None = None,
             num_chunks: int | None = None):
        """Equi-join. ``strategy="auto"`` lets the planner pick hash-shuffle
        vs broadcast AND the shuffle pipeline depth from the cost model;
        ``num_chunks`` overrides the depth (1 = monolithic all-to-all)."""
        on = tuple(on)
        left, right, merged = self._unify_vocabs_with(other, "join")
        nw = self.ctx.nworkers
        if strategy == "auto":
            plan = patterns.plan_join(
                left.num_rows(), right.num_rows(), nw, left.capacity,
                params=cost_model.params_for_fabric(self.ctx.fabric))
            strategy = plan.strategy
            if num_chunks is None:
                num_chunks = plan.num_chunks
        num_chunks = num_chunks or 1
        quota = quota or default_quota(left.capacity, nw)
        capacity = capacity or 2 * left.capacity
        if strategy == "broadcast":
            # replicate the small side; left/right column roles are preserved
            # either way (matches the lazy planner's broadcast_left/right)
            gather = "left" if left.num_rows() <= right.num_rows() else "right"
            return self._attach(
                left._run(("bjoin", on, capacity, gather),
                          lambda comm, l, r: operators.dist_join_broadcast(
                              comm, l, r, on, capacity, gather=gather),
                          right),
                merged)
        return self._attach(
            left._run(("join", on, quota, capacity, num_chunks),
                      lambda comm, l, r: operators.dist_join_shuffle(
                          comm, l, r, on, quota, capacity, num_chunks=num_chunks),
                      right),
            merged)

    def groupby(self, by: Sequence[str], aggs,
                pre_combine: bool | None = None, cardinality_hint: float | None = None,
                quota: int | None = None, capacity: int | None = None,
                num_chunks: int | None = None):
        """GroupBy-aggregate. ``aggs`` is either the canonical mapping
        ``{value_col: (op, ...)}`` or a sequence of aggregation expressions
        (``[col("v").sum(), col("v").mean().alias("avg")]`` — aliases apply
        as a zero-copy rename on the result). With ``pre_combine=None`` the
        planner picks combine-shuffle-reduce vs plain shuffle (from
        ``cardinality_hint``) and the shuffle pipeline depth from table
        sizes. A pinned ``pre_combine`` skips planning entirely (no
        device->host row-count sync) and defaults to the monolithic shuffle
        — pass ``num_chunks`` explicitly to pipeline on that path."""
        by = tuple(by)
        renames: tuple = ()
        if not isinstance(aggs, Mapping):
            aggs, renames = _expr.parse_agg_specs(aggs)
        aggs = {k: tuple(v) for k, v in aggs.items()}
        self._check_columns(sorted(aggs), "groupby(aggs)")
        bad = sorted(f"{c}.{o}" for c, ops_ in aggs.items() for o in ops_
                     if c in self.vocabs and o in ("sum", "mean"))
        if bad:
            raise TypeError(
                f"groupby: aggregation(s) {bad} are arithmetic over a "
                f"dict-encoded string column — codes have order but no "
                f"arithmetic; only min/max/count apply to strings")
        out_vocabs = dict(self.vocabs)
        for c, ops_ in aggs.items():
            if c in self.vocabs:  # ordered aggs of a dict column stay dict
                for o in ops_:
                    if o in ("min", "max"):
                        out_vocabs[f"{c}_{o}"] = self.vocabs[c]
        nw = self.ctx.nworkers
        if pre_combine is None:
            # planning reads row counts (a blocking device->host sync), so it
            # only runs when the caller left the strategy to the planner.
            card = cardinality_hint if cardinality_hint is not None else 0.0
            plan = patterns.plan_groupby(
                card, nw, capacity or self.capacity, n_rows=self.num_rows(),
                params=cost_model.params_for_fabric(self.ctx.fabric))
            pre_combine = plan.strategy == "combine_shuffle_reduce"
            if num_chunks is None:
                num_chunks = plan.num_chunks
        num_chunks = num_chunks or 1
        quota = quota or default_quota(self.capacity, nw)
        capacity = capacity or self.capacity
        key = ("groupby", by, tuple(sorted(aggs.items())), pre_combine, quota,
               capacity, num_chunks)
        res = self._attach(
            self._run(key, lambda comm, t: operators.dist_groupby(
                comm, t, by, aggs, quota, capacity, pre_combine,
                num_chunks=num_chunks)),
            out_vocabs)
        if renames:
            res = (res[0].rename(dict(renames)),) + tuple(res[1:])
        return res

    def unique(self, subset: Sequence[str], quota: int | None = None, capacity: int | None = None,
               num_chunks: int = 1):
        """Distinct rows by ``subset`` key columns (combine-shuffle-reduce)."""
        subset = tuple(subset)
        nw = self.ctx.nworkers
        quota = quota or default_quota(self.capacity, nw)
        capacity = capacity or self.capacity
        return self._attach(
            self._run(("unique", subset, quota, capacity, num_chunks),
                      lambda comm, t: operators.dist_unique(
                          comm, t, subset, quota, capacity, num_chunks=num_chunks)),
            self.vocabs)

    def union(self, other: "DDF", on: Sequence[str], quota: int | None = None,
              capacity: int | None = None, num_chunks: int = 1):
        """Set union by key (concat + distributed unique, paper Table 2)."""
        on = tuple(on)
        left, right, merged = self._unify_vocabs_with(other, "union")
        nw = self.ctx.nworkers
        cap = left.capacity + right.capacity
        quota = quota or default_quota(cap, nw)
        capacity = capacity or cap
        return self._attach(
            left._run(("union", on, quota, capacity, num_chunks),
                      lambda comm, l, r: operators.dist_union(
                          comm, l, r, on, quota, capacity, num_chunks=num_chunks),
                      right),
            merged)

    def difference(self, other: "DDF", on: Sequence[str], quota: int | None = None,
                   capacity: int | None = None, num_chunks: int = 1):
        """Set difference by key (co-partition + local anti-join)."""
        on = tuple(on)
        left, right, merged = self._unify_vocabs_with(other, "difference")
        nw = self.ctx.nworkers
        quota = quota or default_quota(left.capacity, nw)
        capacity = capacity or left.capacity
        return self._attach(
            left._run(("difference", on, quota, capacity, num_chunks),
                      lambda comm, l, r: operators.dist_difference(
                          comm, l, r, on, quota, capacity, num_chunks=num_chunks),
                      right),
            merged)

    def sort_values(self, by: str, descending: bool = False, quota: int | None = None,
                    capacity: int | None = None, num_chunks: int = 1):
        """Global sample sort by ``by``; partition i gets the i-th key range.
        ``num_chunks`` > 1 pipelines the range shuffle against the merge."""
        nw = self.ctx.nworkers
        quota = quota or default_quota(self.capacity, nw, safety=3.0)
        capacity = capacity or 2 * self.capacity
        return self._attach(
            self._run(("sort", by, descending, quota, capacity, num_chunks),
                      lambda comm, t: operators.dist_sort(
                          comm, t, by, quota, capacity, descending=descending,
                          num_chunks=num_chunks)),
            self.vocabs)

    def agg(self, column: str, op: str):
        if column in self.vocabs and op not in ("min", "max", "count"):
            raise TypeError(
                f"agg: {op!r} over dict-encoded string column {column!r} — "
                f"codes have order but no arithmetic; only min/max/count "
                f"apply to strings")
        out = self._run(("agg", column, op),
                        lambda comm, t: (operators.dist_column_agg(comm, t, column, op),))
        val = np.asarray(out)[0]  # replicated; take worker 0's copy
        if column in self.vocabs and op in ("min", "max"):
            return self.vocabs[column].words[int(val)]  # decode the scalar
        return val

    def length(self) -> int:
        out = self._run(("length",), lambda comm, t: (operators.dist_length(comm, t),))
        return int(np.asarray(out)[0])

    def rolling_sum(self, column: str, window: int):
        return self._run(("rolling", column, window),
                         lambda comm, t: operators.dist_window_sum(comm, t, column, window))

    def rolling(self, column: str, window: int, op: str = "sum"):
        """Rolling window aggregate: sum | mean | min | max (halo exchange)."""
        return self._run(("rollagg", column, window, op),
                         lambda comm, t: operators.dist_window_agg(comm, t, column, window, op))

    def transpose(self) -> "DDF":
        """Distributed transpose (gather-based; for matrix-shaped tables)."""
        return self._run(("transpose", self.capacity),
                         lambda comm, t: operators.dist_transpose(comm, t))

    def rebalance(self, quota: int | None = None, num_chunks: int = 1):
        """Evenly redistribute rows across workers, preserving global order."""
        quota = quota or self.capacity
        return self._attach(
            self._run(("rebalance", quota, num_chunks),
                      lambda comm, t: operators.rebalance(
                          comm, t, quota, num_chunks=num_chunks)),
            self.vocabs)

    def head(self, k: int) -> "DDF":
        return self._attach(
            self._run(("head", k), lambda comm, t: operators.dist_head(comm, t, k)),
            self.vocabs)

    # -- lazy plan layer (repro.plan) -------------------------------------------
    def lazy(self):
        """Lazy handle over this DDF: a ``repro.plan.LazyDDF`` whose operator
        methods build a logical plan; ``.collect()`` optimizes and executes
        the whole pipeline in one compiled program. Cached per instance so
        rebuilding a pipeline from the same DDF reuses plan/op caches."""
        if self._lazy_cache is None:
            from ..plan.frame import LazyDDF
            self._lazy_cache = LazyDDF.from_ddf(self)
        return self._lazy_cache

    def eager(self) -> "DDF":
        """This DDF itself — the eager escape hatch mirrors
        ``LazyDDF.eager()`` so either handle can be normalized."""
        return self
