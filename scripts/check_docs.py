"""Documentation lint (ISSUE 1-4 satellite CI check).

Fails (exit 1) if:
  1. any symbol exported via ``__all__`` from a module under
     ``repro.core`` (including ``repro.core.comm``), the lazy-plan
     package ``repro.plan``, the streaming engine ``repro.stream``, the
     chunked dataset layer ``repro.data.dataset``, or the expression API
     ``repro.expr`` lacks a docstring, or
  2. ``docs/PATTERNS.md`` / ``docs/ARCHITECTURE.md`` is missing, or does not
     mention every pattern key in ``repro.core.patterns.PATTERNS``, or
  3. ``docs/LAZY_PLANS.md`` is missing, or does not mention every logical
     node type and rewrite pass exported by ``repro.plan``, or
  4. ``docs/STREAMING.md`` is missing, or does not mention every
     ``repro.stream`` export (plus the batch-sizing entry point
     ``choose_batch_rows``), or
  5. ``docs/EXPRESSIONS.md`` is missing, or does not mention every
     ``repro.expr`` export (plus the entry points ``with_column`` and
     ``alias``), or
  6. ``docs/KERNELS.md`` is missing, or does not mention every
     ``repro.kernels`` export (plus the cost-model entry point
     ``kernel_params`` and the env override ``REPRO_KERNEL_BACKEND``), or
  7. ``docs/FAULT_TOLERANCE.md`` is missing, or does not mention every
     ``repro.testing`` export, the stream checkpoint/recovery API
     (``StreamCheckpoint``, ``RetryPolicy``, ``classify_error``, ...),
     every registered fault site, and the runner's checkpoint knobs
     (``checkpoint_dir`` / ``checkpoint_every`` / ``resume``), or
  8. ``docs/SERVICE.md`` is missing, or does not mention every
     ``repro.service`` export, lifecycle state, scheduling policy, and
     service knob (``max_running`` / ``memory_budget_bytes`` / ...), or
  9. ``docs/OBSERVABILITY.md`` is missing, or does not mention every
     ``repro.obs`` export, the engine's metric and span names, and the
     tracing/profiling knobs (``REPRO_TRACE`` / ``profile=True`` / ...), or
  10. ``docs/STATISTICS.md`` is missing, or does not mention every
     ``repro.stats`` export, the writer/stream statistics knobs
     (``stats_k`` / ``adaptive`` / ``replan_every``), and the
     cost-model adaptation constants (``ADAPTIVE_*``), or
  11. ``docs/TYPES.md`` is missing, or does not mention every
     ``repro.core.vocab`` export, the ``Recode`` plan node, the typed
     ``DatasetSchemaError``, and the vocab unification surface.

Run:  PYTHONPATH=src python scripts/check_docs.py
Wired into the test suite via tests/test_docs_lint.py.
"""

import importlib
import inspect
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

CORE_MODULES = [
    "repro.core.api",
    "repro.core.cost_model",
    "repro.core.dataframe",
    "repro.core.local_ops",
    "repro.core.operators",
    "repro.core.partition",
    "repro.core.patterns",
    "repro.core.comm.channels",
    "repro.core.comm.collectives",
    "repro.core.comm.communicator",
    # lazy logical-plan package (ISSUE 2): every export needs a docstring
    "repro.plan",
    "repro.plan.logical",
    "repro.plan.optimizer",
    "repro.plan.executor",
    "repro.plan.frame",
    # out-of-core streaming engine + dataset format (ISSUE 3)
    "repro.stream",
    "repro.stream.scan",
    "repro.stream.runner",
    "repro.data.dataset",
    # fault tolerance: checkpoint/resume + retry + fault injection (ISSUE 6)
    "repro.stream.checkpoint",
    "repro.stream.recovery",
    "repro.testing",
    "repro.testing.faults",
    # columnar expression API (ISSUE 4)
    "repro.expr",
    "repro.expr.tree",
    "repro.expr.aggs",
    # Pallas kernel layer + dispatch registry (ISSUE 5)
    "repro.kernels",
    "repro.kernels.ops",
    "repro.kernels.ref",
    "repro.kernels.registry",
    # concurrent query service (ISSUE 7)
    "repro.service",
    "repro.service.session",
    "repro.service.scheduler",
    "repro.service.admission",
    "repro.service.cache",
    # unified tracing + metrics + cost-model accounting (ISSUE 8)
    "repro.obs",
    "repro.obs.trace",
    "repro.obs.metrics",
    "repro.obs.model_check",
    # statistics: sketches, estimation, adaptive re-planning (ISSUE 9)
    "repro.stats",
    "repro.stats.sketch",
    "repro.stats.estimate",
    "repro.stats.adaptive",
    # dict-encoded string columns: vocabularies + unification (ISSUE 10)
    "repro.core.vocab",
]

REPO_ROOT = os.path.join(os.path.dirname(__file__), "..")


def missing_docstrings() -> list:
    """Return ["module.symbol", ...] for __all__ exports without docstrings."""
    missing = []
    for mod_name in CORE_MODULES:
        mod = importlib.import_module(mod_name)
        for sym in getattr(mod, "__all__", ()):
            obj = getattr(mod, sym, None)
            if obj is None:
                missing.append(f"{mod_name}.{sym} (missing symbol)")
                continue
            if not (inspect.isfunction(obj) or inspect.isclass(obj)):
                continue  # constants (dicts, profiles) document themselves
            if not inspect.getdoc(obj):
                missing.append(f"{mod_name}.{sym}")
    return missing


def missing_pattern_docs() -> list:
    """Return problems with docs/ coverage of the pattern registry."""
    from repro.core.patterns import PATTERNS

    problems = []
    for doc in ("docs/PATTERNS.md", "docs/ARCHITECTURE.md"):
        path = os.path.join(REPO_ROOT, doc)
        if not os.path.exists(path):
            problems.append(f"{doc} is missing")
            continue
        text = open(path).read()
        for pattern in PATTERNS:
            if pattern not in text:
                problems.append(f"{doc} does not mention pattern '{pattern}'")
    return problems


def missing_doc_mentions(doc: str, symbols) -> list:
    """Generic coverage check: every symbol must appear in the doc file."""
    path = os.path.join(REPO_ROOT, doc)
    if not os.path.exists(path):
        return [f"{doc} is missing"]
    text = open(path).read()
    return [f"{doc} does not mention '{sym}'" for sym in symbols
            if sym not in text]


def missing_lazy_plan_docs() -> list:
    """Return problems with docs/LAZY_PLANS.md coverage of the plan layer."""
    from repro.plan import logical, optimizer

    node_types = [s for s in logical.__all__
                  if inspect.isclass(getattr(logical, s, None))
                  and issubclass(getattr(logical, s), logical.Node)]
    passes = [s for s in optimizer.__all__ if s.startswith(("pushdown", "plan_",
                                                            "elide", "fuse"))]
    return missing_doc_mentions("docs/LAZY_PLANS.md", node_types + passes)


def missing_streaming_docs() -> list:
    """Return problems with docs/STREAMING.md coverage of repro.stream."""
    import repro.stream as stream_pkg

    return missing_doc_mentions(
        "docs/STREAMING.md",
        list(stream_pkg.__all__) + ["choose_batch_rows", "to_batches",
                                    "collect_stream"])


def missing_fault_tolerance_docs() -> list:
    """Return problems with docs/FAULT_TOLERANCE.md coverage of the
    fault-tolerance surface: the testing harness exports, the stream
    checkpoint/recovery API, every registered fault site, and the runner's
    checkpoint knobs."""
    import repro.testing as testing_pkg
    from repro.testing.faults import FAULT_SITES

    symbols = (list(testing_pkg.__all__)
               + ["StreamCheckpoint", "RetryPolicy", "call_with_retry",
                  "classify_error", "RETRYABLE_EXCEPTIONS",
                  "checkpoint_dir", "checkpoint_every", "resume",
                  "max_retries", "REPRO_CHAOS_SEED"]
               + list(FAULT_SITES))
    return missing_doc_mentions("docs/FAULT_TOLERANCE.md", symbols)


def missing_expression_docs() -> list:
    """Return problems with docs/EXPRESSIONS.md coverage of repro.expr."""
    import repro.expr as expr_pkg

    return missing_doc_mentions(
        "docs/EXPRESSIONS.md",
        list(expr_pkg.__all__) + ["with_column", "alias"])


def missing_service_docs() -> list:
    """Return problems with docs/SERVICE.md coverage of repro.service:
    every package export, each lifecycle state, both scheduling policies,
    and the admission/stream knobs of ``QueryService.submit``."""
    import repro.service as service_pkg
    from repro.service import POLICIES, QueryState

    symbols = (list(service_pkg.__all__)
               + list(QueryState.ALL) + list(POLICIES)
               + ["submit", "cancel", "shutdown", "stats",
                  "memory_budget_bytes", "max_running", "max_backlog",
                  "weight", "quantum_s"])
    return missing_doc_mentions("docs/SERVICE.md", symbols)


def missing_kernel_docs() -> list:
    """Return problems with docs/KERNELS.md coverage of repro.kernels."""
    import repro.kernels as kernels_pkg

    return missing_doc_mentions(
        "docs/KERNELS.md",
        list(kernels_pkg.__all__) + ["kernel_params", "KernelParams",
                                     "REPRO_KERNEL_BACKEND",
                                     "segment_reduce_partials"])


def missing_obs_docs() -> list:
    """Return problems with docs/OBSERVABILITY.md coverage of repro.obs:
    every package export, the metric names the engine emits, the span
    names each layer records, and the tracing/profiling knobs."""
    import repro.obs as obs_pkg

    symbols = (list(obs_pkg.__all__)
               + ["REPRO_TRACE", "create_perfetto_trace", "model_report",
                  "core.compile", "core.dispatch",
                  "partition", "shuffle.pack", "shuffle.exchange",
                  "shuffle.unpack", "local.join", "local.groupby",
                  "local.unique", "local.sort", "trace.phase",
                  "peak_working_set_bytes", "retries:", "checkpoints",
                  "kernels.dispatch", "stream.decode", "stream.device_op",
                  "stream.stage", "service.morsel", "service.query",
                  "profile=True", "analyze=True", "query_learn_key"])
    return missing_doc_mentions("docs/OBSERVABILITY.md", symbols)


def missing_stats_docs() -> list:
    """Return problems with docs/STATISTICS.md coverage of repro.stats:
    every package export, the writer/stream knobs, and the cost-model
    adaptation constants."""
    import repro.stats as stats_pkg

    symbols = (list(stats_pkg.__all__)
               + ["stats_k", "adaptive", "replan_every", "chunks_skipped",
                  "chunks_decoded", "replans", "partition_histogram",
                  "ADAPTIVE_REPLAN_EVERY", "ADAPTIVE_DRIFT",
                  "ADAPTIVE_QUOTA_SAFETY", "ADAPTIVE_CAPACITY_SAFETY",
                  "backfill_stats", "shuffle_quota"])
    return missing_doc_mentions("docs/STATISTICS.md", symbols)


def missing_types_docs() -> list:
    """Return problems with docs/TYPES.md coverage of the dict-encoded
    string column subsystem: every ``repro.core.vocab`` export, the
    unification/recode surface, and the typed ingestion error."""
    from repro.core import vocab as vocab_mod

    symbols = (list(vocab_mod.__all__)
               + ["Recode", "DatasetSchemaError", "vocab_map", "bind_vocabs",
                  "is_in", "decode", "recode_map", "merge", "'dict'"])
    return missing_doc_mentions("docs/TYPES.md", symbols)


def main() -> int:
    failures = missing_docstrings()
    if failures:
        print("Missing docstrings on exported symbols:")
        for f in failures:
            print(f"  - {f}")
    doc_failures = missing_pattern_docs()
    if doc_failures:
        print("Pattern documentation problems:")
        for f in doc_failures:
            print(f"  - {f}")
    lazy_failures = missing_lazy_plan_docs()
    if lazy_failures:
        print("Lazy-plan documentation problems:")
        for f in lazy_failures:
            print(f"  - {f}")
    stream_failures = missing_streaming_docs()
    if stream_failures:
        print("Streaming documentation problems:")
        for f in stream_failures:
            print(f"  - {f}")
    fault_failures = missing_fault_tolerance_docs()
    if fault_failures:
        print("Fault-tolerance documentation problems:")
        for f in fault_failures:
            print(f"  - {f}")
    expr_failures = missing_expression_docs()
    if expr_failures:
        print("Expression documentation problems:")
        for f in expr_failures:
            print(f"  - {f}")
    kernel_failures = missing_kernel_docs()
    if kernel_failures:
        print("Kernel documentation problems:")
        for f in kernel_failures:
            print(f"  - {f}")
    service_failures = missing_service_docs()
    if service_failures:
        print("Query-service documentation problems:")
        for f in service_failures:
            print(f"  - {f}")
    obs_failures = missing_obs_docs()
    if obs_failures:
        print("Observability documentation problems:")
        for f in obs_failures:
            print(f"  - {f}")
    stats_failures = missing_stats_docs()
    if stats_failures:
        print("Statistics documentation problems:")
        for f in stats_failures:
            print(f"  - {f}")
    types_failures = missing_types_docs()
    if types_failures:
        print("Types documentation problems:")
        for f in types_failures:
            print(f"  - {f}")
    if failures or doc_failures or lazy_failures or stream_failures \
            or fault_failures or expr_failures or kernel_failures \
            or service_failures or obs_failures or stats_failures \
            or types_failures:
        return 1
    print("check_docs: all exported core+plan+stream+expr+kernel+testing+"
          "service+obs+stats+vocab symbols documented; docs cover every "
          "pattern, node type, rewrite pass, streaming, fault-tolerance, "
          "expression, kernel, service, observability, statistics and "
          "string-type export")
    return 0


if __name__ == "__main__":
    sys.exit(main())
