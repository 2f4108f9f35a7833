"""Per-phase readings: the program's scopes and host spans on the device
trace's clock, from a short traced probe of a cell.

A profile names each device op by its HLO instruction alone (``fusion.38``).
The program puts its phases in the ``op_name`` metadata of the compiled HLO
(``repro.obs.spans.trace_span``: ``ocean/p4_solve/bisect``,
``grid/sample_env``, ``myopic/min_bandwidth``, ...), and
``repro.obs.spans.scope_table`` reads them back as ``{instruction: scope}``
from the compiled program's text.  This module joins the two:

* ``read_trace``: every chip's ops (``XLA Ops``), its program runs
  (``XLA Modules``: one event per run of a compiled program, on the same
  clock) and every host span, the program's own (``grid/keys``,
  ``grid/dispatch``, ``grid/result``) included.  On the CPU, whose trace
  has no device plane, ops and runs are read from the host's events, so
  the readers can be tried without a chip; on a chip, never;
* ``assign``: each op to the program run that contains it;
* ``scope_seconds``: own time under each scope, for one program, or None
  where the trace is not whole: the profiler drops device events from
  some traces, and a run whose ops cover less than ``WHOLE_COVERAGE`` of
  its length has lost some (a whole chip trace covers 99.8 % or more).

The harness's traced window keeps neither the program runs nor the
program's host spans (``harness.Reading`` holds its reduction and the
benchmark's own spans only), so the per-phase readers take a probe of
their own: once per process and cell, after the run's window and check,
the cell's driver is set up again from a fixed seed, its programs are
compiled (from the cache) for their text, and it runs for
``PROBE_SECONDS`` under a trace with the Python tracer off, again once if
that trace is not whole or its device clock is off the host's (``aligned``).  It prints one line with every scope's share of
the busy time and the mean of every host span it saw.  A probe that cannot
be made reads as None: a reader never fails the run.
"""
from __future__ import annotations

import bisect
import json
import pathlib
import re
import shutil
import sys
import tempfile
import time
import traceback
import warnings
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from tracing import (DEVICE_PLANE, OPS_LINE, Op, Span, latest_xplane, length,
                     op_name, self_times, union)

BENCH = pathlib.Path(__file__).resolve().parent
MODULES_LINE = "XLA Modules"
PROBE_SPAN = "probe"
# About 100 closed-loop rounds, or one sweep, on one chip: each of the
# bisection's 1764 serial trips a round is a few device events, so one sweep
# is millions of them, and reading them takes longer than running them.  On
# the CPU a trace takes about 10^6 host events a second at the tests' sizes.
PROBE_SECONDS = {"closed_loop": 0.25, "grid_sweep": 0.5}
PROBE_SEED = 1302
PROBE_TRIES = 2
WHOLE_COVERAGE = 0.95
_HLO_MODULE = re.compile(r"^HloModule\s+([^\s,]+)", re.M)


class Run(NamedTuple):
    module: str      # the program's HLO module name, e.g. "jit__build"
    start: int       # ns, host clock
    end: int


class Trace(NamedTuple):
    ops: List[List[Op]]       # per chip
    runs: List[List[Run]]     # per chip, in the same order
    host: List[Span]


def module_name(event_name: str) -> str:
    """``jit__lambda(1144354304654653061)`` -> ``jit__lambda``."""
    return event_name.split("(", 1)[0]


def hlo_module_name(hlo_text: str) -> Optional[str]:
    m = _HLO_MODULE.search(hlo_text)
    return m.group(1) if m else None


def read_trace(path: str, host_ops: bool = False) -> Trace:
    """Ops, program runs and host spans of one ``.xplane.pb`` file.

    A chip's ops and runs come from its device plane alone.  With
    ``host_ops`` (for a CPU trace, which has no device plane) they come
    from the host events that carry an ``hlo_module`` stat, each run
    spanning the events of one ``run_id``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: List[List[Op]] = []
    runs: List[List[Run]] = []
    host: List[Span] = []
    cpu_ops: List[Op] = []
    cpu_runs: Dict[str, List] = {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            chip_ops, chip_runs = [], []
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                for ev in line.events:
                    start = int(ev.start_ns)
                    end = start + int(ev.duration_ns)
                    if line.name == OPS_LINE:
                        chip_ops.append(Op(op_name(ev.name), start, end, False))
                    else:
                        chip_runs.append(Run(module_name(ev.name), start, end))
            if chip_ops:
                ops.append(chip_ops)
                runs.append(sorted(chip_runs, key=lambda r: r.start))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    start = int(ev.start_ns)
                    end = start + int(ev.duration_ns)
                    host.append(Span(ev.name, start, end))
                    if not host_ops or ev.name.startswith("end: "):
                        continue
                    with warnings.catch_warnings():
                        warnings.simplefilter("ignore", DeprecationWarning)
                        stats = dict(ev.stats)
                    if "hlo_module" in stats and "run_id" in stats:
                        cpu_ops.append(Op(ev.name, start, end, False))
                        run = cpu_runs.setdefault(
                            str(stats["run_id"]),
                            [str(stats["hlo_module"]), start, end])
                        run[1], run[2] = min(run[1], start), max(run[2], end)
    if host_ops and cpu_ops:
        ops.append(cpu_ops)
        runs.append(sorted((Run(*r) for r in cpu_runs.values()),
                           key=lambda r: r.start))
    return Trace(ops, runs, host)


def assign(ops: Sequence[Op], runs: Sequence[Run]) -> List[Optional[int]]:
    """The index in ``runs`` (sorted by start, not overlapping) of the run
    that contains each op, or None."""
    starts = [r.start for r in runs]
    out: List[Optional[int]] = []
    for op in ops:
        i = bisect.bisect_right(starts, op.start) - 1
        out.append(i if i >= 0 and op.end <= runs[i].end else None)
    return out


def _inside(runs: Sequence[Run], window: Span, module: Optional[str]):
    """Indices of the runs of ``module`` (every program with None) that
    overlap ``window``.  Device events are placed on the host's clock by
    an offset estimated once per trace, so a run's edges may lie a little
    outside the host span that holds it: a run is not cut at the edge."""
    return [i for i, r in enumerate(runs)
            if r.end > window.start and r.start < window.end
            and (module is None or r.module == module)]


def module_runs(trace: Trace, window: Span, module: Optional[str] = None) -> float:
    """Runs of ``module`` (of every program with None) in ``window``,
    averaged over chips."""
    return sum(len(_inside(r, window, module)) for r in trace.runs) / len(trace.runs)


def _run_ops(ops: Sequence[Op], runs: Sequence[Run], window: Span,
             module: str) -> Dict[int, List[Op]]:
    """The ops of each run of ``module`` in ``window``, by run index."""
    keep = set(_inside(runs, window, module))
    out: Dict[int, List[Op]] = {i: [] for i in keep}
    for op, i in zip(ops, assign(ops, runs)):
        if i in keep:
            out[i].append(op)
    return out


def coverage(trace: Trace, window: Span, module: str) -> Optional[float]:
    """The least share of a run's length that its ops cover, over the runs
    of ``module`` in ``window`` on every chip; None without such a run."""
    shares = []
    for ops, runs in zip(trace.ops, trace.runs):
        for i, run_ops in _run_ops(ops, runs, window, module).items():
            busy = length(union((o.start, o.end) for o in run_ops))
            shares.append(busy / max(runs[i].end - runs[i].start, 1))
    return min(shares) if shares else None


def whole(trace: Trace, window: Span, module: str) -> bool:
    """Whether every run of ``module`` in ``window`` kept its ops."""
    c = coverage(trace, window, module)
    return c is not None and c >= WHOLE_COVERAGE


def scope_seconds(trace: Trace, window: Span, module: str,
                  table: Dict[str, str]) -> Optional[Dict[str, float]]:
    """Own time under each scope of ``module``'s runs in ``window``, in
    seconds averaged over chips; ``table`` is that module's
    ``{instruction: scope}`` and an instruction it lacks counts as ``""``.
    None where the trace is not ``whole``."""
    if not whole(trace, window, module):
        return None
    return {scope: sum(s for _, s in named)
            for scope, named in _by_scope(trace, window, module, table).items()}


def _by_scope(trace: Trace, window: Span, module: str, table: Dict[str, str]
              ) -> Dict[str, List[Tuple[str, float]]]:
    """``{scope: [(instruction, own seconds averaged over chips)]}``."""
    own: Dict[str, float] = {}
    for ops, runs in zip(trace.ops, trace.runs):
        for run_ops in _run_ops(ops, runs, window, module).values():
            for name, ns in self_times(run_ops).items():
                own[name] = own.get(name, 0.0) + ns / 1e9 / len(trace.ops)
    out: Dict[str, List[Tuple[str, float]]] = {}
    for name, secs in own.items():
        out.setdefault(table.get(name, ""), []).append((name, secs))
    return out


def under(scope: str, prefix: str) -> bool:
    """Whether ``scope`` lies under ``prefix``: the prefix's parts appear in
    the scope's, in order and side by side (``ocean/p4_solve`` lies in
    ``grid/policy/ocean-a/while/body/ocean/p4_solve/bisect``)."""
    want = prefix.strip("/").split("/")
    have = scope.split("/")
    return any(have[i:i + len(want)] == want
               for i in range(len(have) - len(want) + 1))


def under_total(seconds: Dict[str, float], prefix: str) -> float:
    """Seconds of every scope under ``prefix``."""
    return sum(s for scope, s in seconds.items() if under(scope, prefix))


def busy_seconds(trace: Trace, window: Span) -> float:
    """Union of the intervals of the ops that overlap ``window``, uncut
    (as runs are, see ``_inside``), averaged over chips."""
    return sum(length(union((o.start, o.end) for o in ops
                            if o.end > window.start and o.start < window.end))
               for ops in trace.ops) / len(trace.ops) / 1e9


def spans(trace: Trace, window: Span, name: str) -> List[Span]:
    return [s for s in trace.host if s.name == name
            and s.start >= window.start and s.end <= window.end]


def launch_gaps(trace: Trace, window: Span, span: str, module: str
                ) -> Tuple[List[float], int]:
    """For each host span ``span`` inside ``window``: seconds from its start
    to the start of the first run of ``module`` on the first chip that
    starts inside it; and the number of spans in which no run starts.
    Device events are put on the host's clock by one offset per trace, so
    a gap holds that offset's error, and a skewed trace drops spans."""
    runs = [trace.runs[0][i] for i in _inside(trace.runs[0], window, module)]
    starts = [r.start for r in runs]
    gaps, dropped = [], 0
    for s in spans(trace, window, span):
        i = bisect.bisect_left(starts, s.start)
        if i < len(runs) and runs[i].start <= s.end:
            gaps.append((runs[i].start - s.start) / 1e9)
        else:
            dropped += 1
    return gaps, dropped


def aligned(p: "Probe") -> bool:
    """Whether every run of the probe's main program starts inside a span
    of one of the driver's span names (the closed loop's ``solve``, the
    sweep's ``sweep``): in a trace whose device events the profiler put
    off the host's clock, runs fall outside the spans that launched them."""
    n = len(_inside(p.trace.runs[0], p.window, p.main))
    for name in p.span_names:
        gaps, dropped = launch_gaps(p.trace, p.window, name, p.main)
        if n and len(gaps) == n and not dropped:
            return True
    return False


def runs_per_span(trace: Trace, window: Span, span: str) -> Optional[float]:
    """Program runs (every program) in ``window`` per host span ``span`` in
    it, averaged over chips: in a window that holds nothing but those
    spans' calls, the programs each call launches, including those that
    start on the device after the call returned."""
    marks = spans(trace, window, span)
    return module_runs(trace, window) / len(marks) if marks else None


def busy_share(p: "Probe", prefix: str) -> Optional[float]:
    """Own time under ``prefix`` in the probe's main program, in % of the
    device's busy time; None without the program's scope table or where
    the trace is not whole."""
    if p.main not in p.tables:
        return None
    busy = busy_seconds(p.trace, p.window)
    own = scope_seconds(p.trace, p.window, p.main, p.tables[p.main])
    if busy <= 0 or own is None:
        return None
    return 100.0 * under_total(own, prefix) / busy


# ---------------------------------------------------------------- the probe
class Probe(NamedTuple):
    trace: Trace
    window: Span
    main: Optional[str]                 # module name of the cell's program
    tables: Dict[str, Dict[str, str]]   # module name -> {instruction: scope}
    units: int                          # rounds or cell-rounds completed
    span_names: Tuple[str, ...] = ()    # the driver's own host spans


def programs(drv) -> Dict[str, str]:
    """``{module name: compiled HLO text}`` of the program a driver runs:
    the closed loop's round, or the grid sweep's ``GridEngine`` program
    (none where the engine has no ``lower``)."""
    import jax

    if hasattr(drv, "engine"):
        if not hasattr(drv.engine, "lower"):
            return {}
        compiled = drv.engine.lower(drv._seeds()).compile()
    else:
        compiled = drv.fn.lower(drv.state, jax.device_put(drv.bank[0]),
                                drv.eta[0]).compile()
    text = compiled.as_text()
    return {hlo_module_name(text): text}


def _tables(texts: Dict[str, str]) -> Dict[str, Dict[str, str]]:
    try:
        from repro.obs.spans import scope_table
    except ImportError:       # a program that puts out no scope table
        return {}
    return {name: scope_table(text) for name, text in texts.items()}


def _probe(traffic_name: str, conf: dict) -> Probe:
    import jax

    import drivers

    t0 = time.perf_counter()
    traffic = json.loads((BENCH / "traffic" / f"{traffic_name}.json").read_text())
    drv = drivers.DRIVERS[traffic["driver"]](conf, traffic, PROBE_SEED)
    drv.setup()
    texts = programs(drv)
    t1 = time.perf_counter()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    tmp = tempfile.mkdtemp(prefix="bench_probe_")
    try:
        with jax.profiler.trace(tmp, profiler_options=options):
            with jax.profiler.TraceAnnotation(PROBE_SPAN):
                drv.run(PROBE_SECONDS[traffic["driver"]], traced=True)
        t2 = time.perf_counter()
        trace = read_trace(latest_xplane(tmp),
                           host_ops=jax.default_backend() == "cpu")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"bench: probe of {traffic_name}: set-up {t1 - t0:.1f} s, traced "
          f"{t2 - t1:.1f} s, read {time.perf_counter() - t2:.1f} s", file=sys.stderr)
    window = next(s for s in trace.host if s.name == PROBE_SPAN)
    main = next(iter(texts), None)
    return Probe(trace, window, main, _tables(texts), drv.units,
                 tuple(drv.span_names))


def summary(p: Probe) -> Dict:
    """What the probe saw, for its output line: whether its trace is whole
    and aligned, the main program's scopes' own time in % of the device's busy time
    (``""``: ops with no scope, named under ``unscoped_pct``), and for the
    driver's spans and the program's ``grid/*`` spans their number, mean
    length and the runs of the main program that start inside them."""
    busy = busy_seconds(p.trace, p.window)
    out: Dict = {"probe_units": p.units, "busy_s": busy,
                 "coverage": coverage(p.trace, p.window, p.main) if p.main else None}
    out["whole"] = out["coverage"] is not None and out["coverage"] >= WHOLE_COVERAGE
    out["aligned"] = p.main is not None and aligned(p)
    if p.main in p.tables and busy > 0:
        named = _by_scope(p.trace, p.window, p.main, p.tables[p.main])
        out["scope_pct"] = dict(sorted(
            ((scope, 100.0 * sum(s for _, s in v) / busy) for scope, v in named.items()),
            key=lambda kv: -kv[1]))
        out["unscoped_pct"] = dict(sorted(
            ((name, 100.0 * s / busy) for name, s in named.get("", [])),
            key=lambda kv: -kv[1])[:10])
    names = sorted({s.name for s in p.trace.host
                    if s.name in p.span_names or s.name.startswith("grid/")})
    out["spans"] = {}
    for name in names:
        marks = spans(p.trace, p.window, name)
        launched = len(launch_gaps(p.trace, p.window, name, p.main)[0]) if p.main else 0
        out["spans"][name] = {"n": len(marks),
                              "mean_ms": 1e3 * sum(s.end - s.start for s in marks)
                              / len(marks) / 1e9 if marks else None,
                              "main_runs_started": launched}
    return out


# The harness calls each reader on its own, with nothing they share: the
# probe is made once per process and cell, here, and read by all of them.
_PROBES: Dict[str, Optional[Probe]] = {}


def probe(traffic_name: str, conf: dict) -> Optional[Probe]:
    """The probe of the cell with this mix and configuration, made once per
    process, again once if its trace is not whole or not aligned; None if
    it cannot be made or its trace holds no op.  Prints ``summary`` as a
    JSON line."""
    key = traffic_name + json.dumps(conf, sort_keys=True)
    if key not in _PROBES:
        p = None
        try:
            for _ in range(PROBE_TRIES):
                p = _probe(traffic_name, conf)
                if not p.trace.ops or p.main is None or (
                        whole(p.trace, p.window, p.main) and aligned(p)):
                    break
                print(f"bench: probe of {traffic_name}: coverage "
                      f"{coverage(p.trace, p.window, p.main)!r}, aligned "
                      f"{aligned(p)}", file=sys.stderr)
            if p is not None and not p.trace.ops:
                p = None
            if p is not None:
                print(json.dumps({"probe": traffic_name, **summary(p)}), flush=True)
        except Exception:  # noqa: BLE001  a reader must not fail the run
            print(f"bench: no probe of {traffic_name}:", file=sys.stderr)
            traceback.print_exc()
            p = None
        _PROBES[key] = p
    return _PROBES[key]
