"""A deployment is added as files alone: a configuration that names its own
reference module, and scenarios that carry the program's ``env``, run
through both drivers with no edit to ``bench/``.  And the paper's cells
read what they read before their reference was named by the configuration.
"""
import json
import pathlib

import pytest

from bench_tiny import CELLS, ROOT, deployment, harness, tiny

import deploy
import drivers

DATA = pathlib.Path(__file__).parent / "data"
ENV_K6 = DATA / "env_k6.json"
SEED = 2**32 + 99


class Clock:
    """``time.perf_counter`` for the drivers: each read moves on one
    millisecond, so a window runs the same rounds and sweeps on any host."""

    def __init__(self) -> None:
        self.t = 0.0

    def perf_counter(self) -> float:
        self.t += 1e-3
        return self.t


# The checks rows of the tiny paper cells, recorded on the tree before
# configurations named their reference; the clock fixes what a window runs.
ROWS = json.loads((DATA / "paper_checks_rows.json").read_text())
WINDOW = {"paper_k10.online": 0.05, "paper_k10.sweep": 0.005}


@pytest.mark.parametrize("seed", [20260, 2**32 + 15])
@pytest.mark.parametrize("name", CELLS)
def test_paper_cells_read_what_they_read_before(monkeypatch, name, seed):
    monkeypatch.setattr(drivers, "time", Clock())
    r = harness.execute(tiny(name), seed, WINDOW[name], False, require_chip=False)
    got = {"attempted": r["attempted"], "checks": r["checks"]}
    assert got == ROWS[name][str(seed)]


@pytest.mark.parametrize("mix", ["online", "sweep"])
def test_deployment_of_files_alone_reads_correct(mix):
    cell = deployment(ENV_K6, mix)
    assert deploy.reference(cell.conf).__file__ == str(ROOT / cell.conf["reference"])
    r = harness.execute(cell, SEED, 0.3, False, require_chip=False)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    if mix == "sweep":
        assert "h2_gap" in r["checks"]


def test_planted_channel_law_reads_incorrect_on_h2_gap():
    cell = deployment(ENV_K6, "sweep")
    wrong = dict(cell.conf, reference="tests/bench/data/env_k6_wrong_reference.py")
    r = harness.execute(cell._replace(conf=wrong), SEED, 0.3, False,
                        require_chip=False)
    assert r["correct"] is False
    assert {n for n, c in r["checks"].items() if c["value"] > c["limit"]} == {"h2_gap"}


def test_scenario_env_reaches_the_program():
    conf = json.loads(ENV_K6.read_text())
    assert [s.env.to_dict() for s in deploy.scenarios(conf)] == [
        s["env"] for s in conf["scenarios"]]
    paper = harness.find_cell("paper_k10.sweep").conf
    assert [(s.env, s.pathloss_db) for s in deploy.scenarios(paper)] == [
        (None, tuple(s["pathloss_db"])) for s in paper["scenarios"]]
