"""The TR 38.901 UMa sector as a deployment of files alone: its
configuration and reference run through the grid sweep of
``bench/drivers.py`` with no edit to ``bench/``, read ``correct``, and a
reference with the law a little off reads incorrect on ``h2_gap`` alone."""
import json
import pathlib

import pytest

from bench_tiny import ROOT, deployment, harness

import deploy

DATA = pathlib.Path(__file__).parent / "data"
UMA_K6 = DATA / "uma_k6.json"
SEED = 2**32 + 99


def test_sector_of_files_alone_reads_correct():
    cell = deployment(UMA_K6, "sweep")
    assert deploy.reference(cell.conf).__file__ == str(ROOT / "bench/uma_reference.py")
    r = harness.execute(cell, SEED, 0.3, False, require_chip=False)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["checks"]["h2_gap"]["value"] < 1e-3


@pytest.mark.parametrize("fault", ["wrong_sf", "wrong_los"])
def test_planted_sector_law_reads_incorrect_on_h2_gap(fault):
    cell = deployment(UMA_K6, "sweep")
    wrong = dict(cell.conf, reference=f"tests/bench/data/uma_k6_{fault}_reference.py")
    r = harness.execute(cell._replace(conf=wrong), SEED, 0.3, False,
                        require_chip=False)
    assert r["correct"] is False
    assert {n for n, c in r["checks"].items() if c["value"] > c["limit"]} == {"h2_gap"}


def test_sector_configurations_agree_but_for_size():
    tiny = json.loads(UMA_K6.read_text())
    full = json.loads((ROOT / "bench/configs/uma_sector_k10.json").read_text())
    for key in ("reference", "radio", "scenarios", "policies", "exec", "limits",
                "guarantees", "energy_budget_j"):
        assert tiny[key] == full[key], key
    assert [s.env.channel for s in deploy.scenarios(full)] == ["uma_38901"] * 3
