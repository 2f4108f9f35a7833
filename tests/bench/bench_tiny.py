"""Tiny versions of the benchmark's cells, for CPU tests of the harness.

Same configurations, traffic mixes and drivers as on the chip, with the
horizon and the number of seeds cut so that a run takes seconds on the CPU.
"""
import copy
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT / "bench", ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import harness  # noqa: E402

CELLS = ("paper_k10.online", "paper_k10.sweep")


def tiny(name: str):
    cell = harness.find_cell(name)
    conf = copy.deepcopy(cell.conf)
    conf.update(num_rounds=12, frame_len=12, seeds_per_sweep=2)
    traffic = dict(cell.traffic, check_rounds=30, trace_seconds=0.2)
    return cell._replace(conf=conf, traffic=traffic)


def deployment(conf_file: pathlib.Path, mix: str):
    """A tiny cell of the deployment that ``conf_file`` configures, under the
    paper's traffic mix ``mix``; the file gives the tiny sizes itself."""
    conf = json.loads(conf_file.read_text())
    return tiny(f"paper_k10.{mix}")._replace(name=f"{conf['name']}.{mix}", conf=conf)
