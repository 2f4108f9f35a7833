"""CPU rehearsal of the benchmark harness: every cell's driver at tiny size,
the result line's format, and the refusals (no chip, no program)."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_tiny import CELLS, ROOT, harness, tiny


@pytest.fixture(scope="module")
def results():
    return {name: harness.execute(tiny(name), 2**31 + 7, 0.3, False,
                                  require_chip=False) for name in CELLS}


@pytest.mark.parametrize("name", CELLS)
def test_result_line_format(results, name):
    r = results[name]
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(r)[-1] == "checks"
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    cell = harness.find_cell(name)
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in r["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(r["device"])
    assert r["checks"]["compiles_in_window"]["value"] == 0
    json.loads(json.dumps(r))


@pytest.mark.parametrize("name", CELLS)
def test_same_seed_same_answers(name):
    import drivers

    def kept(seed):
        cell = tiny(name)
        d = drivers.DRIVERS[cell.traffic["driver"]](cell.conf, cell.traffic, seed)
        d.setup()
        return d

    a, b = kept(12345), kept(12345)
    if name == "paper_k10.online":
        assert (a.bank == b.bank).all()
        assert not (a.bank == kept(12346).bank).all()
    else:
        assert a._seeds() == b._seeds()
        assert a._seeds() != kept(12346)._seeds()


def test_every_layer_metric_has_a_reader():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert callable(harness.reader(m["name"]))


def test_exits_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper_k10.online",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == harness.NO_CHIP
    assert p.stdout.strip() == ""


def test_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper_k10.online",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "No module named 'repro'" in p.stderr


@pytest.mark.parametrize("name", CELLS)
def test_traced_window_and_layer_readers(name):
    """The traced window's host side, and every reader of the cell on a
    reduced trace (the device side needs the chip; see test_bench_trace)."""
    import drivers
    import tracing

    cell = tiny(name)
    d = drivers.DRIVERS[cell.traffic["driver"]](cell.conf, cell.traffic, 5)
    d.setup()
    d.run(0.2, traced=True)
    assert d.units > 0
    info = d.info["selected_per_round"]
    assert info if name == "paper_k10.sweep" else info["rounds"] > 0
    if name == "paper_k10.online":
        assert len(d.host["upload"]) == len(d.host["fetch"]) == d.units
    reduced = tracing.Reduced(window_s=1.0, busy_s=0.6, kernel_s=0.0,
                              device_ops=[], idle_gaps=[], kernels=[])
    reading = harness.Reading(reduced, d.units, d.host, cell.conf, "TPU v5 lite")
    for m in cell.per_layer:
        value = harness.reader(m["name"])(reading)
        assert value is not None and value > 0, m["name"]
        if m["unit"] == "%":
            assert value <= 100.0
