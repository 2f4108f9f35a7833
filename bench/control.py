"""Readings that the limits of ``correct`` are set from; not run by the benchmark.

    python bench/control.py --workload <cell> --seeds 11,12,13 --seconds 3

For each seed, in one process: the cell's set-up and a short window of the
timed path, then each compared number read twice on the answers the window
kept: for the program, and for the control, the reference put in the
program's place and computed in bfloat16 on the same inputs.  Prints one JSON
line per seed, then for each number the largest program reading (the lower
end of its limit) and the smallest control reading (the upper end).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import checks  # noqa: E402
import drivers  # noqa: E402
import harness  # noqa: E402


def readings(cell, seed: int, seconds: float):
    """(program numbers, control numbers) of one short run."""
    drv = drivers.DRIVERS[cell.traffic["driver"]](cell.conf, cell.traffic, seed)
    drv.setup()
    drv.run(seconds, traced=False)
    drv.release()
    got = checks.Numbers(cell.conf["limits"])
    drv.check(got)
    ctl = checks.Numbers(cell.conf["limits"])
    drv.check(ctl, control=True)
    return got, ctl


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    cell = harness.find_cell(args.workload)
    lower, upper = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        got, ctl = readings(cell, seed, args.seconds)
        print(json.dumps({"seed": seed, "program": got.worst, "control": ctl.worst,
                          "answers": got.answers, "program_failed": got.failed,
                          "control_failed": ctl.failed}), flush=True)
        for k, v in got.worst.items():
            lower[k] = max(lower.get(k, 0.0), v)
        for k, v in ctl.worst.items():
            upper[k] = min(upper.get(k, float("inf")), v)
    print(json.dumps({"lower": lower, "upper": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
