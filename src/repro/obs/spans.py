"""Named profiler scopes, host spans, and wall-clock span timers.

* :func:`trace_span` — a ``jax.named_scope`` wrapper used *inside* traced
  code (``sim/engine.py``, ``core/selection.py``, ``core/solvers.py``,
  ``core/ocean.py``, ``core/baselines.py``, the kernels).  It attaches
  names like ``ocean/rank`` or ``ocean/p4_solve/bisect`` to the ``op_name``
  metadata of the emitted ops.  Pure metadata: numerics and the compiled
  program's ops are unchanged.  A TPU profile names each device event by
  its HLO instruction alone, so the scopes do not show in a trace by
  themselves: :func:`scope_table` reads them back from the compiled
  program's text (``jax.stages.Compiled.as_text()``) as a map from
  instruction name to scope, which a trace reader joins to the events.
* :func:`host_span` — a bare ``jax.profiler.TraceAnnotation``: a named
  slice on the host in an active profiler trace, on the same clock as the
  device events, and a cheap no-op otherwise.  It records nothing, so it
  can sit in a long-running loop (``GridEngine.run``'s ``grid/keys``,
  ``grid/dispatch`` and ``grid/result``).
* :func:`wall_span` — a :func:`host_span` that also records its wall time
  into the module-global :class:`SpanRecorder`.  ``benchmarks/run.py``
  wraps every benchmark module in one, and ``benchmarks/common.Timer``
  records its named compile / first-call / steady phases through the same
  recorder — the drained spans land in the JSONL run manifest
  (``repro.obs.manifest``).
"""
from __future__ import annotations

import collections
import contextlib
import re
import time
from typing import Dict, List, Optional, Tuple

import jax

__all__ = [
    "trace_span",
    "host_span",
    "scope_of",
    "scope_table",
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


def host_span(name: str):
    """A named host slice in an active profiler trace; records nothing."""
    return jax.profiler.TraceAnnotation(name)


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([^\s,}]+)")


def _path(text: str) -> List[str]:
    """The parts of a name stack: a transform's group (``vmap(a/b)``) is
    replaced by its parts, a ``jit(...)`` group (a function's name, not a
    scope) is dropped."""
    parts: List[str] = []
    i, n = 0, len(text)
    while i < n:
        j = i
        while j < n and text[j] not in "/(":
            j += 1
        head = text[i:j]
        if j < n and text[j] == "(":
            depth, k = 1, j + 1
            while k < n and depth:
                depth += {"(": 1, ")": -1}.get(text[k], 0)
                k += 1
            if head != "jit":
                parts.extend(_path(text[j + 1:k - 1]))
            j = text.find("/", k)
            j = n if j < 0 else j
        elif head:
            parts.append(head)
        i = j + 1
    return parts


def scope_of(op_name: str) -> str:
    """The scope of one ``op_name``, ``""`` for an op traced under none.

    ``jit(...)`` groups and the trailing primitive are dropped and
    transform groups flattened: ``jit(f)/ocean/rank/jit(argsort)/sort`` ->
    ``ocean/rank``, ``jit(f)/grid/policy/smo/vmap(vmap(myopic/greedy))/
    cumsum`` -> ``grid/policy/smo/myopic/greedy``.  Where XLA merged the
    metadata of several ops (``a;b``), the first is read.
    """
    text = op_name.split(";", 1)[0]
    depth, cut = 0, -1
    for i, ch in enumerate(text):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if ch == "/" and depth == 0:
            cut = i
    if "(" not in text[cut + 1:]:       # a trailing primitive, not a group
        text = text[:max(cut, 0)]
    return "/".join(_path(text))


def scope_table(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: scope}`` of every instruction of an HLO module.

    ``hlo_text`` is the optimized module's text.  An instruction's scope is
    read from its ``op_name`` metadata (:func:`scope_of`); a fusion that
    carries none takes the most common scope of its fused computation; any
    other instruction without metadata maps to ``""``.
    """
    scoped: Dict[str, Optional[str]] = {}
    calls: Dict[str, str] = {}
    by_computation: Dict[str, collections.Counter] = {}
    current = ""
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            current = m.group(1)
            by_computation.setdefault(current, collections.Counter())
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        if op:
            scoped[name] = scope_of(op.group(1))
            by_computation.setdefault(current, collections.Counter())[
                scoped[name]] += 1
        else:
            scoped[name] = None
            c = _CALLS.search(line)
            if c:
                calls[name] = c.group(1)
    table = {}
    for name, scope in scoped.items():
        if scope is None:
            counts = by_computation.get(calls.get(name, ""))
            scope = counts.most_common(1)[0][0] if counts else ""
        table[name] = scope
    return table


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
    """Host-side span: :func:`host_span` (if a trace is active) + wall timer.

    ``TraceAnnotation`` is a cheap no-op outside an active
    ``jax.profiler`` trace, so benchmarks wrap phases unconditionally.
    """
    recorder = SPANS if recorder is None else recorder
    t0 = time.perf_counter()
    with host_span(name):
        try:
            yield
        finally:
            recorder.record(name, time.perf_counter() - t0)
