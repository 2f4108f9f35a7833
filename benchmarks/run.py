"""Benchmark driver — one module per paper figure/table (deliverable d).

Each module's ``run()`` prints ``benchmark,metric,value,note`` CSV rows,
validates the paper's claims (CLAIM rows), and returns overall success.

    PYTHONPATH=src python -m benchmarks.run [--only fig16] [--json-dir results]

``--json-dir`` additionally writes one machine-readable
``BENCH_<module>.json`` per module (the same rows as the CSV stream).

``--resume`` makes an interrupted sweep preemption-safe at module
granularity: modules already recorded as ``ok`` in the run manifest are
skipped, so a killed invocation re-run with the same arguments picks up
where it left off.  Trajectory-level snapshot save/restore events
(``repro.checkpoint``) drained during each module land on its manifest
record under ``"checkpoints"``.

``--check-baseline`` compares every throughput metric (``*_rounds_per_s``)
against the committed ``benchmarks/baselines/BENCH_<module>.json`` and
fails the run on a regression beyond ``--baseline-tolerance`` (default
30%) — the recorded perf trajectory is a gate, not just an artifact.
Refresh a baseline by re-running with ``--json-dir benchmarks/baselines``
on the reference machine and committing the result.

``--profile DIR`` wraps the run in a ``jax.profiler`` trace (viewable
with TensorBoard / Perfetto) so hot-path regressions come with a trace,
not just a slower CSV row.  A "step" is one benchmark module:
``--profile-start N`` skips the first N selected modules before the
trace starts and ``--profile-steps M`` stops it after M traced modules
(default: trace through the end), keeping trace files small when only
one module's regression is under investigation, e.g.::

    python -m benchmarks.run --only traj_bench --profile /tmp/jtrace

Every module runs under a named ``TraceAnnotation`` (``bench/<module>``)
and the in-graph ops carry ``jax.named_scope`` labels (``ocean/rank``,
``ocean/p4_solve/<backend>``, ``traj/chunk_io``, ...), so the trace shows
named regions per module and per algorithm phase instead of one
anonymous blob.

Every invocation also appends a structured *run manifest* — JSONL records
with the config hash, jax/device info, per-module claim outcomes,
baseline comparisons, drained wall-clock spans, and emitted BENCH files
(schema: ``repro.obs.manifest``).  Default path is
``<json-dir>/manifest.jsonl`` (or ``./manifest.jsonl`` without
``--json-dir``); override with ``--manifest PATH``, disable with
``--no-manifest``.  Render one with ``python -m benchmarks.report``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from repro.compile_cache import enable_compilation_cache

from benchmarks import (
    ablations,
    adaptivity,
    common,
    energy_consumption,
    grid_scaling,
    learning_performance,
    radio_sweep,
    reliability_sweep,
    robustness_sweep,
    roofline,
    scenarios,
    selection_patterns,
    solver_bench,
    structure,
    temporal_pattern,
    tradeoff,
    traj_bench,
)

BASELINE_DIR = os.path.join(os.path.dirname(__file__), "baselines")
BASELINE_METRIC_SUFFIX = "_rounds_per_s"


def check_baseline(name: str, rows, baseline_dir: str, tolerance: float):
    """Gate this run's throughput rows against the committed baseline.

    Compares every ``*_rounds_per_s`` metric to the same metric in
    ``<baseline_dir>/BENCH_<name>.json``; a value below
    ``(1 - tolerance) * baseline`` is a regression and fails the module.
    Metrics missing on either side are reported but don't fail (the
    lattice may legitimately grow/shrink across PRs).  No baseline file
    => silently passes (modules opt in by committing one).

    Returns ``(ok, records)`` where ``records`` is a manifest-ready list
    of ``{"metric", "status", "note"}`` dicts mirroring the printed rows.
    """
    path = os.path.join(baseline_dir, f"BENCH_{name}.json")
    records: list = []
    if not os.path.exists(path):
        return True, records
    with open(path) as f:
        base_rows = json.load(f)["rows"]
    base = {
        r["metric"]: float(r["value"])
        for r in base_rows
        if r["metric"].endswith(BASELINE_METRIC_SUFFIX)
    }
    ok = True
    for r in rows:
        metric = r["metric"]
        if not metric.endswith(BASELINE_METRIC_SUFFIX):
            continue
        if metric not in base:
            print(f"{name},BASELINE_NEW,{metric},no recorded baseline yet")
            records.append(
                {"metric": metric, "status": "NEW", "note": "no baseline"}
            )
            continue
        cur, ref = float(r["value"]), base[metric]
        ratio = cur / max(ref, 1e-12)
        status = "OK" if ratio >= 1.0 - tolerance else "REGRESSION"
        note = f"{cur:.6g} vs {ref:.6g} ({ratio:.2f}x)"
        print(f"{name},BASELINE_{status},{metric},{note}")
        records.append({"metric": metric, "status": status, "note": note})
        if status == "REGRESSION":
            ok = False
    missing = sorted(
        m for m in base if m not in {r["metric"] for r in rows}
    )
    for m in missing:
        print(f"{name},BASELINE_GONE,{m},metric no longer emitted")
        records.append(
            {"metric": m, "status": "GONE", "note": "metric no longer emitted"}
        )
    return ok, records


BENCHMARKS = {
    "fig1_4_temporal_pattern": temporal_pattern.run,
    "fig5_6_selection_patterns": selection_patterns.run,
    "fig7_energy_consumption": energy_consumption.run,
    "fig8_9_learning_performance": learning_performance.run,
    "fig10_14_scenarios": scenarios.run,
    "fig15_structure": structure.run,
    "fig16_tradeoff": tradeoff.run,
    "ablations_beyond_paper": ablations.run,
    "adaptivity_env_zoo": adaptivity.run,
    "radio_sweep": radio_sweep.run,
    "reliability_sweep": reliability_sweep.run,
    "robustness_sweep": robustness_sweep.run,
    "grid_scaling": grid_scaling.run,
    "solver_bench": solver_bench.run,
    "traj_bench": traj_bench.run,
    "roofline": roofline.run,
}


def main() -> int:
    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="substring filter")
    ap.add_argument(
        "--json-dir",
        default=None,
        help="also write BENCH_<module>.json row dumps into this directory",
    )
    ap.add_argument(
        "--check-baseline",
        action="store_true",
        help="fail on *_rounds_per_s regressions vs benchmarks/baselines/",
    )
    ap.add_argument(
        "--baseline-dir",
        default=BASELINE_DIR,
        help="directory of committed BENCH_<module>.json baselines",
    )
    ap.add_argument(
        "--baseline-tolerance",
        type=float,
        default=0.30,
        help="allowed fractional rounds/sec drop before failing (default 0.30)",
    )
    ap.add_argument(
        "--profile",
        metavar="DIR",
        default=None,
        help="write a jax.profiler trace of the benchmark run into DIR",
    )
    ap.add_argument(
        "--profile-start",
        type=int,
        default=0,
        help="selected-module index at which the profiler trace starts",
    )
    ap.add_argument(
        "--profile-steps",
        type=int,
        default=None,
        help="number of modules to trace (default: through the end)",
    )
    ap.add_argument(
        "--manifest",
        metavar="PATH",
        default=None,
        help="JSONL run-manifest path (default: <json-dir>/manifest.jsonl)",
    )
    ap.add_argument(
        "--no-manifest",
        action="store_true",
        help="skip writing the JSONL run manifest",
    )
    ap.add_argument(
        "--resume",
        action="store_true",
        help="skip modules already recorded ok in the run manifest "
        "(preemption-safe re-run; requires the manifest)",
    )
    args = ap.parse_args()

    selected = [n for n in BENCHMARKS if not args.only or args.only in n]
    if not selected:
        print(
            f"no benchmark matches --only {args.only!r}; "
            f"available: {', '.join(BENCHMARKS)}",
            file=sys.stderr,
        )
        return 2

    profiling = False
    traced = 0

    def _profile_tick(idx: int) -> None:
        """Start/stop the jax.profiler trace on module boundaries."""
        nonlocal profiling, traced
        if args.profile is None:
            return
        import jax

        done = args.profile_steps is not None and traced >= args.profile_steps
        if profiling and done:
            jax.profiler.stop_trace()
            profiling = False
            print(f"# profiler trace written to {args.profile}", file=sys.stderr)
        if not profiling and idx >= args.profile_start and not done:
            os.makedirs(args.profile, exist_ok=True)
            jax.profiler.start_trace(args.profile)
            profiling = True

    manifest = None
    manifest_path = args.manifest
    if manifest_path is None:
        manifest_path = os.path.join(args.json_dir or ".", "manifest.jsonl")

    done_modules: set = set()
    if args.resume:
        if args.no_manifest:
            print("--resume requires the run manifest", file=sys.stderr)
            return 2
        if os.path.exists(manifest_path):
            from repro.obs.manifest import read_manifest

            done_modules = {
                rec["name"]
                for rec in read_manifest(manifest_path)
                if rec.get("record") == "module" and rec.get("ok")
            }
            for name in selected:
                if name in done_modules:
                    print(
                        f"# --resume: skipping {name} (already ok in "
                        f"{manifest_path})",
                        file=sys.stderr,
                    )

    if not args.no_manifest:
        from repro.obs.manifest import ManifestWriter

        manifest = ManifestWriter(
            manifest_path, argv=sys.argv[1:], config=vars(args)
        )
        manifest.start(profile_dir=args.profile)

    from repro.checkpoint.trajectory import drain_events
    from repro.obs.spans import SPANS, wall_span

    print("benchmark,metric,value,note")
    failures = []
    idx = -1
    for name, fn in BENCHMARKS.items():
        if name not in selected:
            continue
        if name in done_modules:
            continue
        idx += 1
        _profile_tick(idx)
        rows_before = len(common.ROWS)
        SPANS.drain()  # a clean slate: spans below belong to this module
        drain_events()  # likewise for checkpoint save/restore events
        t0 = time.time()
        try:
            with wall_span(f"bench/{name}"):
                ok = fn()
        except Exception as e:  # pragma: no cover
            import traceback

            traceback.print_exc()
            print(f"{name},ERROR,{type(e).__name__},{str(e)[:120]}")
            ok = False
        elapsed = time.time() - t0
        spans = SPANS.drain()
        ckpt_events = drain_events()
        if profiling:
            traced += 1
        print(f"{name},total_runtime_s,{elapsed:.1f},")
        baseline_records = []
        if args.check_baseline:
            base_ok, baseline_records = check_baseline(
                name,
                common.ROWS[rows_before:],
                args.baseline_dir,
                args.baseline_tolerance,
            )
            ok &= base_ok
        bench_path = None
        if args.json_dir:
            os.makedirs(args.json_dir, exist_ok=True)
            payload = {
                "benchmark": name,
                "ok": bool(ok),
                "runtime_s": elapsed,
                "rows": common.ROWS[rows_before:],
            }
            bench_path = os.path.join(args.json_dir, f"BENCH_{name}.json")
            with open(bench_path, "w") as f:
                json.dump(payload, f, indent=2)
        if manifest is not None:
            manifest.module(
                name,
                ok=bool(ok),
                runtime_s=elapsed,
                rows=common.ROWS[rows_before:],
                baseline=baseline_records,
                bench_json=bench_path,
                spans=spans,
                checkpoints=ckpt_events,
            )
        if not ok:
            failures.append(name)
    if profiling:
        import jax

        jax.profiler.stop_trace()
        print(f"# profiler trace written to {args.profile}", file=sys.stderr)
    if manifest is not None:
        manifest.summary(ok=not failures, failed=failures)
        print(f"# run manifest appended to {manifest.path}", file=sys.stderr)
    if failures:
        print(f"SUMMARY,failed,{len(failures)},{';'.join(failures)}")
        return 1
    print(f"SUMMARY,all_passed,{len(selected)},")
    return 0


if __name__ == "__main__":
    sys.exit(main())
