"""Named profiler spans + host-side wall-clock span timers.

Two complementary layers:

* :func:`trace_span` — a ``jax.named_scope`` wrapper used *inside* traced
  code (``sim/engine.py``, ``core/selection.py``, ``core/solvers.py``,
  both kernels).  It attaches names like ``ocean/rank`` or
  ``ocean/p4_solve/newton`` to the emitted ops, so ``--profile`` traces
  (and compiled-HLO dumps) show the algorithm's phases instead of
  anonymous fusions.  Pure metadata: numerics and compiled programs are
  unchanged.
* :func:`wall_span` — a host-side context manager combining
  ``jax.profiler.TraceAnnotation`` (a named slice in an active profiler
  trace) with a wall-clock timer recorded into the module-global
  :class:`SpanRecorder`.  ``benchmarks/run.py`` wraps every benchmark
  module in one, and ``benchmarks/common.Timer`` records its named
  compile / first-call / steady phases through the same recorder — the
  drained spans land in the JSONL run manifest
  (``repro.obs.manifest``).
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional, Tuple

import jax

__all__ = [
    "trace_span",
    "wall_span",
    "SpanRecorder",
    "SPANS",
    "record_span",
]


def trace_span(name: str):
    """Name the ops traced under this scope (``jax.named_scope`` wrapper).

    Usable as a context manager or decorator inside jitted/vmapped/scanned
    code; adds profiler/HLO metadata only — never changes numerics.
    """
    return jax.named_scope(name)


class SpanRecorder:
    """Accumulates named wall-clock spans: ``{name: [seconds, ...]}``."""

    def __init__(self) -> None:
        self._spans: Dict[str, List[float]] = {}

    def record(self, name: str, seconds: float) -> None:
        self._spans.setdefault(name, []).append(float(seconds))

    def drain(self) -> List[Dict[str, object]]:
        """Return and clear the recorded spans (manifest-ready rows)."""
        out = [
            {
                "name": name,
                "count": len(times),
                "total_s": sum(times),
                "mean_s": sum(times) / len(times),
            }
            for name, times in self._spans.items()
        ]
        self._spans.clear()
        return out

    def snapshot(self) -> Dict[str, Tuple[float, ...]]:
        return {k: tuple(v) for k, v in self._spans.items()}


SPANS = SpanRecorder()


def record_span(name: str, seconds: float) -> None:
    """Record one wall-clock span into the global recorder."""
    SPANS.record(name, seconds)


@contextlib.contextmanager
def wall_span(name: str, recorder: Optional[SpanRecorder] = None):
    """Host-side span: TraceAnnotation (if a trace is active) + wall timer.

    ``TraceAnnotation`` is a cheap no-op outside an active
    ``jax.profiler`` trace, so benchmarks wrap phases unconditionally.
    """
    recorder = SPANS if recorder is None else recorder
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        try:
            yield
        finally:
            recorder.record(name, time.perf_counter() - t0)
