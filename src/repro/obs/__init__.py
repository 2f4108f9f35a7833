"""repro.obs — observability for the Lyapunov machinery.

* :mod:`repro.obs.metrics` — the static :class:`MetricsSpec` and its
  collector registry: traced per-round telemetry (queues, drift,
  drift-plus-penalty decomposition, energy headroom, selection patterns,
  solver diagnostics) recorded *inside* the compiled scan / fused-kernel
  trajectories.
* :mod:`repro.obs.spans` — ``jax.named_scope`` / profiler
  ``TraceAnnotation`` wrappers, the compiled program's scope table, and
  host wall-clock span timers.
* :mod:`repro.obs.manifest` — structured JSONL run manifests emitted by
  ``benchmarks/run.py``.
"""
from repro.obs.manifest import (
    ManifestWriter,
    SCHEMA_VERSION,
    config_hash,
    read_manifest,
    runs_in_manifest,
)
from repro.obs.metrics import (
    FULL_TRACE_ELEM_CAP,
    REDUCTIONS,
    Collector,
    MetricsSpec,
    MetricsState,
    RoundContext,
    available_collectors,
    collector_table,
    finalize_metrics,
    get_collector,
    init_metrics,
    metric_key,
    metrics_round,
    round_context,
    solver_effort,
)
from repro.obs.spans import (
    SPANS,
    SpanRecorder,
    host_span,
    record_span,
    scope_table,
    trace_span,
    wall_span,
)

__all__ = [
    "Collector",
    "FULL_TRACE_ELEM_CAP",
    "ManifestWriter",
    "MetricsSpec",
    "MetricsState",
    "REDUCTIONS",
    "RoundContext",
    "SCHEMA_VERSION",
    "SPANS",
    "SpanRecorder",
    "available_collectors",
    "collector_table",
    "config_hash",
    "finalize_metrics",
    "get_collector",
    "host_span",
    "init_metrics",
    "metric_key",
    "metrics_round",
    "read_manifest",
    "record_span",
    "round_context",
    "runs_in_manifest",
    "scope_table",
    "solver_effort",
    "trace_span",
    "wall_span",
]
