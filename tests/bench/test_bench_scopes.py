"""The per-phase readers (``bench/scopes.py`` and the ``layer_metrics`` that
read it): program runs, op-to-run assignment, own time per scope, launch
gaps and spans, on hand-made windows and on the trace recorded on a TPU v5e
chip (``data/online_k4096.xplane.pb``)."""
import json
import pathlib

import pytest

from bench_tiny import harness

import scopes
import tracing
from scopes import Probe, Run, Trace
from tracing import Op, Span

RECORDED = pathlib.Path(__file__).parent / "data" / "online_k4096.xplane.pb"

BISECT = "grid/policy/ocean-a/while/body/ocean/p4_solve/bisect/p4/bisect/candidate_sweep"
ROUND_TABLE = {"while.1": "ocean/p4_solve/bisect/p4/bisect/candidate_sweep",
               "fusion.2": "ocean/p4_solve/bisect/p4/bisect/candidate_sweep/while/body",
               "sort.0": "ocean/rank"}
SWEEP_TABLE = {"fusion.537": BISECT, "fusion.9": "grid/sample_env",
               "while.1": "grid/policy/smo/myopic/min_bandwidth", "copy.3": ""}


def _online() -> Probe:
    """Two rounds: a ``solve`` span, the round program's run inside it, a
    bisect ``while`` holding a fusion, then a sort."""
    ops, runs, host = [], [], [Span("probe", 0, 1000)]
    for base, lag in ((100, 50), (500, 40)):
        host.append(Span("solve", base, base + 200))
        s = base + lag
        runs.append(Run("jit__lambda", s, s + 150))
        ops += [Op("while.1", s, s + 100, False), Op("fusion.2", s + 10, s + 50, False),
                Op("sort.0", s + 100, s + 150, False)]
    return Probe(Trace([ops], [runs], host), host[0], "jit__lambda",
                 {"jit__lambda": ROUND_TABLE}, 2, ("upload", "solve", "fetch"))


def _sweep() -> Probe:
    """Two sweeps, each: ``grid/keys`` with two key programs, the sweep's
    program (bisect, sampling, a baseline's bisection, a copy), then a
    reduction that starts after the ``sweep`` span has ended.  A key
    program's op shares a name with the sweep program's bisect fusion and
    must not count as it."""
    ops, runs, host = [], [], [Span("probe", 0, 10000)]
    for base, keys in ((0, 100), (5000, 200)):
        host += [Span("sweep", base, base + 3050),
                 Span("grid/keys", base + 10, base + 10 + keys),
                 Span("grid/dispatch", base + 400, base + 450)]
        runs += [Run("jit__threefry_fold_in", base + 200, base + 250),
                 Run("jit__threefry_fold_in", base + 300, base + 350),
                 Run("jit__build", base + 1000, base + 3000),
                 Run("jit__reduce_sum", base + 3100, base + 3200)]
        ops += [Op("fusion.537", base + 200, base + 250, False),
                Op("xor.1", base + 300, base + 350, False),
                Op("fusion.537", base + 1000, base + 2500, False),
                Op("fusion.9", base + 2500, base + 2800, False),
                Op("while.1", base + 2800, base + 3000, False),
                Op("copy.3", base + 2850, base + 2900, False),
                Op("reduce.1", base + 3100, base + 3200, False)]
    return Probe(Trace([ops], [runs], host), host[0], "jit__build",
                 {"jit__build": SWEEP_TABLE}, 2 * 90 * 300, ("sweep",))


def _dropped(p: Probe, name: str) -> Probe:
    """``p`` with the ops called ``name`` lost from its trace."""
    ops = [[o for o in chip if o.name != name] for chip in p.trace.ops]
    return p._replace(trace=p.trace._replace(ops=ops))


@pytest.mark.parametrize("scope,prefix,inside", [
    (BISECT, "ocean/p4_solve", True),
    (BISECT, "ocean/p4_solve/bisect", True),
    (BISECT, "grid/policy/ocean-a", True),
    ("ocean/p4", "ocean/p4_solve", False),
    ("x/ocean/p4_solvex", "ocean/p4_solve", False),
    ("", "ocean/rank", False),
])
def test_under(scope, prefix, inside):
    assert scopes.under(scope, prefix) is inside


def test_ops_go_to_the_run_that_holds_them():
    p = _sweep()
    ops, runs = p.trace.ops[0], p.trace.runs[0]
    assert [runs[i].module for i in scopes.assign(ops[:7], runs)] == [
        "jit__threefry_fold_in", "jit__threefry_fold_in", "jit__build", "jit__build",
        "jit__build", "jit__build", "jit__reduce_sum"]
    assert scopes.assign([Op("late", 3300, 3400, False)], runs) == [None]


def test_scope_seconds_counts_own_time_of_one_program():
    p = _sweep()
    own = scopes.scope_seconds(p.trace, p.window, "jit__build", SWEEP_TABLE)
    assert own == pytest.approx({BISECT: 3000e-9, "grid/sample_env": 600e-9,
                                 "grid/policy/smo/myopic/min_bandwidth": 300e-9,
                                 "": 100e-9})
    assert scopes.under_total(own, "ocean/p4_solve/bisect") == pytest.approx(3000e-9)
    assert scopes.busy_seconds(p.trace, p.window) == pytest.approx(4400e-9)
    assert scopes.module_runs(p.trace, p.window, "jit__build") == 2
    assert scopes.module_runs(p.trace, p.window) == 8
    half = Span("probe", 0, 5000)
    assert scopes.module_runs(p.trace, half, "jit__build") == 1
    # a run that reaches past the window's edges counts whole
    edge = Span("probe", 1500, 6500)
    assert scopes.module_runs(p.trace, edge, "jit__build") == 2
    assert scopes.scope_seconds(p.trace, edge, "jit__build", SWEEP_TABLE) == own


def test_launch_gaps_and_runs_per_span():
    p = _online()
    gaps, dropped = scopes.launch_gaps(p.trace, p.window, "solve", "jit__lambda")
    assert gaps == pytest.approx([50e-9, 40e-9]) and dropped == 0
    assert scopes.launch_gaps(p.trace, p.window, "solve", "jit__other") == ([], 2)
    q = _sweep()
    assert scopes.runs_per_span(q.trace, q.window, "sweep") == 4
    assert scopes.runs_per_span(q.trace, q.window, "no_such_span") is None


@pytest.mark.parametrize("metric,probe,value", [
    ("p4_solve_ms.online", _online, 1e3 * 100e-9),
    ("dispatch_ms.online", _online, 1e3 * 45e-9),
    ("p4_bisect_share.sweep", _sweep, 100.0 * 3000 / 4400),
    ("sample_env_share.sweep", _sweep, 100.0 * 600 / 4400),
    ("programs_per_sweep.sweep", _sweep, 4.0),
    ("grid_keys_ms.sweep", _sweep, 1e3 * 150e-9),
])
def test_reader_on_a_hand_made_window(monkeypatch, metric, probe, value):
    p = probe()
    monkeypatch.setattr(scopes, "probe", lambda traffic, conf: p)
    reading = harness.Reading(None, p.units, {}, {}, "TPU v5 lite")
    assert harness.reader(metric)(reading) == pytest.approx(value)


@pytest.mark.parametrize("metric", [
    "p4_solve_ms.online", "dispatch_ms.online", "p4_bisect_share.sweep",
    "sample_env_share.sweep", "programs_per_sweep.sweep", "grid_keys_ms.sweep"])
def test_reader_is_silent_without_a_probe_or_a_scope_table(monkeypatch, metric):
    reading = harness.Reading(None, 1, {}, {}, "TPU v5 lite")
    monkeypatch.setattr(scopes, "probe", lambda traffic, conf: None)
    assert harness.reader(metric)(reading) is None
    if metric.startswith(("p4_", "sample_env")):
        p = (_online if metric.endswith("online") else _sweep)()._replace(tables={})
        monkeypatch.setattr(scopes, "probe", lambda traffic, conf: p)
        assert harness.reader(metric)(reading) is None


def test_a_run_that_lost_ops_is_not_whole():
    """Both sweeps' bisect fusion lost: 1500 of each run's 2000 ns are no
    longer covered, so no scope's time is read, nor any share of it."""
    p = _sweep()
    assert scopes.coverage(p.trace, p.window, "jit__build") == 1.0
    assert scopes.whole(p.trace, p.window, "jit__build")
    q = _dropped(p, "fusion.537")
    assert scopes.coverage(q.trace, q.window, "jit__build") == pytest.approx(0.25)
    assert not scopes.whole(q.trace, q.window, "jit__build")
    assert scopes.scope_seconds(q.trace, q.window, "jit__build", SWEEP_TABLE) is None
    assert scopes.busy_share(q, "grid/sample_env") is None
    assert scopes.coverage(p.trace, p.window, "jit__other") is None
    assert not scopes.whole(p.trace, p.window, "jit__other")


@pytest.mark.parametrize("metric,probe,lost", [
    ("p4_solve_ms.online", _online, "sort.0"),
    ("p4_bisect_share.sweep", _sweep, "fusion.9"),
    ("sample_env_share.sweep", _sweep, "fusion.537"),
])
def test_scope_reader_is_silent_on_a_trace_that_is_not_whole(monkeypatch, metric,
                                                              probe, lost):
    p = _dropped(probe(), lost)
    monkeypatch.setattr(scopes, "probe", lambda traffic, conf: p)
    reading = harness.Reading(None, p.units, {}, {}, "TPU v5 lite")
    assert harness.reader(metric)(reading) is None


def test_dispatch_is_silent_when_a_round_has_no_run_in_its_span(monkeypatch):
    """The second round's run placed before its ``solve`` span, as a trace
    whose device clock is skewed would place it: no mean is read."""
    q = _skewed(_online())
    assert scopes.launch_gaps(q.trace, q.window, "solve", "jit__lambda") == (
        pytest.approx([50e-9]), 1)
    monkeypatch.setattr(scopes, "probe", lambda traffic, conf: q)
    reading = harness.Reading(None, q.units, {}, {}, "TPU v5 lite")
    assert harness.reader("dispatch_ms.online")(reading) is None


def test_summary_gives_every_scope_and_span():
    out = scopes.summary(_sweep())
    assert out["whole"] and out["coverage"] == 1.0
    assert out["busy_s"] == pytest.approx(4400e-9)
    assert out["scope_pct"] == pytest.approx({
        BISECT: 100.0 * 3000 / 4400, "grid/sample_env": 100.0 * 600 / 4400,
        "grid/policy/smo/myopic/min_bandwidth": 100.0 * 300 / 4400,
        "": 100.0 * 100 / 4400})
    assert list(out["scope_pct"])[0] == BISECT
    assert out["unscoped_pct"] == pytest.approx({"copy.3": 100.0 * 100 / 4400})
    assert out["spans"] == {
        "grid/dispatch": {"n": 2, "mean_ms": pytest.approx(50e-6), "main_runs_started": 0},
        "grid/keys": {"n": 2, "mean_ms": pytest.approx(150e-6), "main_runs_started": 0},
        "sweep": {"n": 2, "mean_ms": pytest.approx(3050e-6), "main_runs_started": 2}}
    online = scopes.summary(_online())
    assert online["spans"]["solve"]["main_runs_started"] == 2
    assert not scopes.summary(_dropped(_sweep(), "fusion.537"))["whole"]


def _skewed(p: Probe) -> Probe:
    """``p`` with its second round's run placed before its ``solve`` span,
    as a trace whose device clock is skewed would place it."""
    runs = [p.trace.runs[0][0], p.trace.runs[0][1]._replace(start=450, end=600)]
    return p._replace(trace=p.trace._replace(runs=[runs]))


def test_aligned_runs_start_inside_their_spans():
    assert scopes.aligned(_online()) and scopes.aligned(_sweep())
    assert not scopes.aligned(_skewed(_online()))
    assert not scopes.aligned(_online()._replace(span_names=("upload", "fetch")))
    assert scopes.summary(_online())["aligned"]
    assert not scopes.summary(_skewed(_online()))["aligned"]


def test_probe_is_made_again_once_when_its_trace_is_skewed(monkeypatch):
    made = []

    def fake(traffic, conf):
        made.append(traffic)
        return _skewed(_online()) if len(made) == 1 else _online()

    monkeypatch.setattr(scopes, "_probe", fake)
    monkeypatch.setattr(scopes, "_PROBES", {})
    p = scopes.probe("online", {})
    assert len(made) == 2 and scopes.aligned(p)


def test_probe_is_made_again_once_when_its_trace_is_not_whole(monkeypatch, capsys):
    made = []

    def fake(traffic, conf):
        made.append(traffic)
        return _dropped(_sweep(), "fusion.537") if len(made) == 1 else _sweep()

    monkeypatch.setattr(scopes, "_probe", fake)
    monkeypatch.setattr(scopes, "_PROBES", {})
    p = scopes.probe("sweep", {"tries": 1})
    assert len(made) == 2 and scopes.whole(p.trace, p.window, p.main)
    assert scopes.probe("sweep", {"tries": 1}) is p and len(made) == 2
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["probe"] == "sweep" and line["whole"]
    monkeypatch.setattr(scopes, "_probe",
                        lambda traffic, conf: _dropped(_sweep(), "fusion.537"))
    p = scopes.probe("sweep", {"tries": 2})
    assert p is not None and not scopes.whole(p.trace, p.window, p.main)
    assert scopes.busy_share(p, "ocean/p4_solve/bisect") is None


@pytest.fixture(scope="module")
def recorded():
    return scopes.read_trace(str(RECORDED))


def test_recorded_runs_lie_in_solve_spans(recorded):
    devices, _ = tracing.read_xplane(str(RECORDED))
    assert [(o.name, o.start, o.end) for o in recorded.ops[0]] == [
        (o.name, o.start, o.end) for o in devices[0]]
    runs = recorded.runs[0]
    solves = [s for s in recorded.host if s.name == "solve"]
    assert {r.module for r in runs} == {"jit__lambda"} and len(runs) == len(solves)
    assert all(any(s.start <= r.start and r.end <= s.end for s in solves) for r in runs)
    assert None not in scopes.assign(recorded.ops[0], runs)


def test_recorded_dispatch_lies_inside_the_solve_span(recorded):
    window = next(s for s in recorded.host if s.name == "window")
    gaps, dropped = scopes.launch_gaps(recorded, window, "solve", "jit__lambda")
    solves = [(s.end - s.start) / 1e9 for s in recorded.host if s.name == "solve"]
    assert len(gaps) == len(solves) and dropped == 0
    assert scopes.whole(recorded, window, "jit__lambda")
    assert 0 < sum(gaps) / len(gaps) < sum(solves) / len(solves)


@pytest.mark.parametrize("metric,value", [
    ("host_io_ms.online", 1.123155),
    ("round_xla_ms.online", 0.0023408333333333094),
    ("device_idle_share.online", 69.70813403150522),
    ("device_idle_share.offline", 69.70813403150522),
])
def test_accepted_readers_on_the_recorded_trace(metric, value):
    """The accepted readers read the recorded trace as they did before the
    per-phase readers came."""
    devices, host = tracing.read_xplane(str(RECORDED))
    reduced = tracing.reduce_window(devices, host, tracing.find_span(host, "window"),
                                    ("upload", "solve", "fetch"))
    spans = {n: [(s.end - s.start) / 1e9 for s in host if s.name == n]
             for n in ("upload", "fetch", "solve")}
    reading = harness.Reading(reduced, len(spans["solve"]),
                              {"upload": spans["upload"], "fetch": spans["fetch"]},
                              {}, "TPU v5 lite")
    assert harness.reader(metric)(reading) == pytest.approx(value, rel=1e-12)


def test_probe_of_a_tiny_sweep_shares_the_trace_clock(monkeypatch, capsys):
    """A probe of the tiny sweep on the CPU, its ops read from the host's
    events: the sweep's program runs after its ``grid/dispatch`` span starts
    and ends inside its ``sweep`` span, and the probe's line shows the
    ``grid/*`` spans."""
    from bench_tiny import tiny

    monkeypatch.setattr(scopes, "PROBE_SECONDS", {"grid_sweep": 0.1})
    monkeypatch.setattr(scopes, "_PROBES", {})
    p = scopes.probe("sweep", tiny("paper_k10.sweep").conf)
    assert p is not None and p.main == "jit__build" and p.units > 0
    dispatch = scopes.spans(p.trace, p.window, "grid/dispatch")
    sweeps = scopes.spans(p.trace, p.window, "sweep")
    main = [r for r in p.trace.runs[0] if r.module == p.main
            and p.window.start <= r.start <= p.window.end]
    assert main and len(dispatch) == len(sweeps)
    for r in main:
        assert any(d.start <= r.start for d in dispatch)
        assert any(s.start <= r.start and r.end <= s.end for s in sweeps)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["probe"] == "sweep"
    assert {"grid/keys", "grid/dispatch", "grid/result", "sweep"} <= set(line["spans"])
    assert line["spans"]["sweep"]["main_runs_started"] == len(sweeps)


def test_host_events_are_ops_only_when_asked(tmp_path):
    """A trace with no device plane gives no ops unless ``host_ops`` asks
    for the host's events, as the probe does on the CPU and never on a
    chip."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sort(x * 2.0))
    x = jnp.arange(64.0)
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        f(x).block_until_ready()
    path = tracing.latest_xplane(str(tmp_path))
    bare = scopes.read_trace(path)
    assert bare.ops == [] and bare.runs == [] and bare.host
    cpu = scopes.read_trace(path, host_ops=True)
    assert len(cpu.ops) == 1 and cpu.ops[0]
    assert [r.module for r in cpu.runs[0]] == ["jit__lambda"]
    assert None not in scopes.assign(cpu.ops[0], cpu.runs[0])
