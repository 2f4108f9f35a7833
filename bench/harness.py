"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its per-layer metrics are
found by name: the cell in ``BENCHMARK.json`` at the checkout's root, the
configuration in the file it names, the deployment's reference in the module
the configuration names (``bench/deploy.py``), the mix in
``bench/traffic/<mix>.json`` and each per-layer metric's reader in
``bench/layer_metrics/<metric>.py``.

A run: set-up (imports, inputs drawn from the seed, compilation through the
persistent cache in ``<checkout>/.jax_cache``, warm-up), then the window of
``--seconds`` driven by the mix's driver, then the check of the answers the
window produced against the plain reference.  ``--trace 1`` traces a window
of the mix's ``trace_seconds`` instead and reports the per-layer metrics read
from that trace; where the profiler dropped device ops, they read the part
of the window before the first unit of work that lost some, and a window
that lost ops from its first unit is traced once again (``reduce_trace``).
Without an accelerator, or with fewer chips than the cell asks for, the run
exits 3 and prints no result.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Dict, NamedTuple, Optional

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
NO_CHIP = 3
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
# Traced windows a run makes at most, while each lost device ops from its
# first unit of work on.  Tracing and reading a window takes about as long
# as the probe after it (a 0.5 s sweep window some 100 s on a v5e), and a
# second window takes a traced sweep run near its time limit.
TRACE_TRIES = 2


def process_age_s() -> Optional[float]:
    """Seconds since this process started, from the kernel's own record."""
    try:
        with open("/proc/self/stat") as fh:
            fields = fh.read().rsplit(")", 1)[1].split()
        start = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        return time.clock_gettime(time.CLOCK_BOOTTIME) - start
    except (OSError, ValueError, IndexError, AttributeError):
        return None


class Cell(NamedTuple):
    name: str
    chips: int
    conf: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def find_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{', '.join(sorted(cells))}")
    cell = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = json.loads((root / confs[cell["config"]]["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in reported else [])]
    return Cell(name, cell["chips"], conf, traffic, e2e, layer)


def load_module(path: pathlib.Path, name: str):
    """The Python file at ``path``, loaded as a module of its own."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    path = BENCH / "layer_metrics" / f"{metric}.py"
    return load_module(path, "layer_metric_" + metric.replace(".", "_")).read


class Reading(NamedTuple):
    """What a per-layer metric's reader may read."""

    reduced: object          # tracing.Reduced of the traced window's whole part
    units: int               # rounds (or cell-rounds) completed in that part
    host: Dict[str, list]    # the benchmark's host spans, seconds each
    conf: dict
    device_kind: str


class Window(NamedTuple):
    """A traced window, reduced over its whole part: the window up to the
    first ``unit`` span from which the profiler dropped device ops, or all
    of it where none was dropped."""

    reduced: object          # tracing.Reduced of that part
    kept: int                # unit spans that start in that part
    units: int               # unit spans in the window


def reduce_trace(dev_ops, host, labels, unit: str) -> Window:
    """The traced window, reduced over the part of it that holds every
    device op (``tracing.cut_at``), so that busy time, idle time and the
    breakdown stay true of what they cover."""
    import tracing

    window = tracing.find_span(host, "window")
    starts = [s.start for s in host if s.name == unit
              and window.start <= s.start and s.end <= window.end]
    cut = tracing.cut_at(dev_ops, host, window, unit)
    if cut is None:
        return Window(tracing.reduce_window(dev_ops, host, window, labels),
                      len(starts), len(starts))
    part = tracing.Span(window.name, window.start, cut)
    return Window(tracing.reduce_window(dev_ops, host, part, labels),
                  sum(1 for t in starts if t < cut), len(starts))


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def execute(cell: Cell, seed: int, seconds: float, trace: bool,
            require_chip: bool = True, t_start: Optional[float] = None,
            ) -> Optional[dict]:
    """One run of ``cell``; returns the result line, or None without a chip.

    ``t_start`` is the process's start on the ``time.perf_counter`` clock,
    from which set-up is counted.
    """
    if t_start is None:
        t_start = time.perf_counter()
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    import repro  # noqa: F401  the system under test, from the checkout

    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu" or len(devices) < cell.chips):
        _log(f"bench: the cell needs {cell.chips} TPU chip(s); JAX sees "
             f"{len(devices)} {devices[0].platform} device(s)")
        return None
    cache = None
    if require_chip:
        from repro.compile_cache import enable_compilation_cache

        cache = enable_compilation_cache()
        # Every program, however quick to compile, comes from the cache
        # after a cell's first run.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = []

    def count(name, _secs, **_kw):
        if name == COMPILE_EVENT:
            compiles.append(name)

    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        return _execute(cell, seed, seconds, trace, require_chip, t_start,
                        devices, cache, compiles)
    finally:
        jax.monitoring.unregister_event_duration_listener(count)


def _execute(cell, seed, seconds, trace, require_chip, t_start, devices, cache,
             compiles):
    import jax

    import checks
    import drivers

    drv = drivers.DRIVERS[cell.traffic["driver"]](cell.conf, cell.traffic, seed)
    drv.setup()
    setup_s = time.perf_counter() - t_start
    before = len(compiles)
    if trace:
        import tracing

        for _ in range(TRACE_TRIES):
            t0 = time.perf_counter()
            tmp = tempfile.mkdtemp(prefix="bench_trace_")
            try:
                with jax.profiler.trace(tmp):
                    with jax.profiler.TraceAnnotation("window"):
                        drv.run(cell.traffic["trace_seconds"], traced=True)
                t1 = time.perf_counter()
                dev_ops, host = tracing.read_xplane(tracing.latest_xplane(tmp))
            finally:
                shutil.rmtree(tmp, ignore_errors=True)
            win = reduce_trace(dev_ops, host, drv.span_names, drv.unit_span)
            _log(f"bench: traced window {t1 - t0:.1f} s, read "
                 f"{time.perf_counter() - t1:.1f} s, {win.kept} of {win.units} "
                 f"{drv.unit_span} spans before the first that lost device ops")
            if win.kept:
                break
        drv.info["trace_whole"] = win.kept == win.units
        drv.info["trace_units_kept"] = [win.kept, win.units]
        measured: Dict[str, float] = {}
    else:
        measured = drv.run(seconds, traced=False)
    in_window = len(compiles) - before
    used = devices[: cell.chips] if require_chip else devices[:1]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)
    print(json.dumps({"compiles_in_window": in_window, "cache": cache,
                      "window_units": drv.units, **drv.info}), flush=True)

    drv.release()
    nums = checks.Numbers(cell.conf["limits"])
    drv.check(nums)
    correct, rows = nums.judged()
    correct = correct and in_window == 0

    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    metrics = {}
    if trace:
        shown = win.reduced if win.kept else None
        kept_units = drv.units * win.kept // win.units if win.units else 0
        reading = Reading(shown, kept_units, drv.host, cell.conf,
                          devices[0].device_kind)
        for m in cell.per_layer:
            value = reader(m["name"])(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
    else:
        measured["setup_s"] = setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": measured[m["name"]], "unit": units[m["name"]]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": int(drv.units),
              "failed": int(nums.failed), "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = win.reduced.busy_s
        device["window_s"] = win.reduced.window_s
        if shown is not None:
            result["breakdown"] = {"device_ops": shown.device_ops,
                                   "idle_gaps": shown.idle_gaps}
    rows.append(["compiles_in_window", float(in_window), 0.0])
    # JSON has no infinity: a number that is off every scale reads as the
    # largest float, which breaks every limit as infinity would.
    rows = [[n, min(v, sys.float_info.max), lim] for n, v, lim in rows]
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    for n, v, lim in rows:
        _log(f"check {n} {v!r} limit {lim!r} {'ok' if v <= lim else 'FAILED'}")
    return result


def main(argv=None) -> int:
    age = process_age_s()
    t_start = time.perf_counter() - (age or 0.0)
    # The compilation cache lives inside the checkout, at a fixed path (the
    # path is part of the cache key); the program's cache helper and JAX
    # itself both take it from this variable.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    cell = find_cell(args.workload)
    result = execute(cell, args.seed % 2**63, args.seconds, bool(args.trace),
                     t_start=t_start)
    if result is None:
        return NO_CHIP
    print(json.dumps(result), flush=True)
    return 0
