"""The program's phase scopes in the compiled HLO, and its host spans in a
profiler trace (``repro.obs.spans``: ``scope_of``, ``scope_table``,
``host_span``; ``GridEngine.lower`` and the ``grid/*`` spans of ``run``)."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PolicyParams, Scenario
from repro.core.energy import RadioParams
from repro.core.ocean import OceanConfig, init_state, ocean_round
from repro.obs.spans import SPANS, host_span, scope_of, scope_table, wall_span
from repro.sim import GridEngine


def _has(scopes, prefix):
    want = "/" + prefix + "/"
    return any(want in "/" + s + "/" for s in scopes)


@pytest.fixture(scope="module")
def round_table():
    cfg = OceanConfig(num_clients=6, num_rounds=8, frame_len=4, radio=RadioParams())
    fn = jax.jit(lambda st, h2, eta: ocean_round(st, h2, np.float32(1e-5), eta, cfg))
    h2 = jnp.linspace(1e-4, 1e-3, 6, dtype=jnp.float32)
    text = fn.lower(init_state(cfg), h2, np.float32(1.0)).compile().as_text()
    return scope_table(text)


@pytest.fixture(scope="module")
def engine():
    scn = [Scenario(name=n, num_clients=5, num_rounds=6, frame_len=6,
                    pathloss_db=pl)
           for n, pl in (("flat", (36.0, 36.0)), ("ramp", (32.0, 45.0)))]
    return GridEngine(scn, [("ocean-a", PolicyParams(v=1e-5)), "smo", "amo"])


@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/ocean/rank/jit(argsort)/sort", "ocean/rank"),
    ("jit(f)/grid/policy/smo/vmap(vmap(myopic/greedy))/cumsum",
     "grid/policy/smo/myopic/greedy"),
    ("jit(_build)/grid/sample_env/vmap(vmap(jit(_uniform)))", "grid/sample_env"),
    ("jit(f)/a/while/body/closed_call/b/mul;jit(f)/c/add",
     "a/while/body/closed_call/b"),
    ("sort", ""),
    ("st.q", ""),
])
def test_scope_of(op_name, scope):
    assert scope_of(op_name) == scope


def test_scope_table_reads_metadata_and_fused_computations():
    text = "\n".join([
        "HloModule jit_f, is_scheduled=true",
        "",
        "%fused_computation.1 (param_0: f32[4]) -> f32[4] {",
        "  %param_0 = f32[4]{0} parameter(0)",
        '  %exp.1 = f32[4]{0} exponential(%param_0), metadata={op_name="jit(f)/ocean/energy/exp"}',
        '  ROOT %mul.1 = f32[4]{0} multiply(%exp.1, %exp.1), metadata={op_name="jit(f)/ocean/energy/mul"}',
        "}",
        "",
        "ENTRY %main.4 (x: f32[4]) -> f32[4] {",
        '  %x = f32[4]{0} parameter(0), metadata={op_name="x"}',
        "  %fusion.3 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1",
        '  ROOT %sort.2 = f32[4]{0} sort(%fusion.3), metadata={op_name="jit(f)/ocean/rank/jit(argsort)/sort"}',
        "}",
    ])
    table = scope_table(text)
    assert table["fusion.3"] == "ocean/energy"
    assert table["sort.2"] == "ocean/rank"
    assert table["x"] == "" and table["param_0"] == ""


@pytest.mark.parametrize("prefix", ["ocean/rank", "ocean/p4_solve/bisect",
                                    "ocean/energy", "ocean/queue"])
def test_round_program_carries_its_phases(round_table, prefix):
    assert _has(round_table.values(), prefix)


@pytest.mark.parametrize("prefix", ["grid/sample_env", "ocean/p4_solve/bisect",
                                    "ocean/energy", "ocean/queue",
                                    "myopic/min_bandwidth", "myopic/greedy"])
def test_grid_program_carries_its_phases(engine, prefix):
    table = scope_table(engine.lower([1, 2]).compile().as_text())
    assert _has(table.values(), prefix)


def test_lower_is_the_program_run_dispatches(engine):
    lowered = engine.lower([3, 4, 5])
    assert isinstance(lowered, jax.stages.Lowered)
    assert "HloModule jit__build" in lowered.compile().as_text()
    args = lowered.args_info[0]
    assert args[0].shape == (3,)                        # the seeds
    assert args[7].shape == (2, 3, 2)                   # (S, N) learn keys


def test_lower_refuses_a_sharded_engine(engine):
    sharded = GridEngine(engine.scenarios, ["smo"], shard=True)
    with pytest.raises(ValueError, match="unsharded"):
        sharded.lower([1])


def test_grid_run_spans_in_a_trace(engine, tmp_path):
    engine.run([5, 6])                                   # compiled outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        jax.block_until_ready(engine.run([5, 6]).e)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    spans = sorted((int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns), ev.name)
                   for plane in data.planes if plane.name.startswith("/host:")
                   for line in plane.lines for ev in line.events
                   if ev.name.startswith("grid/"))
    assert [name for _, _, name in spans] == ["grid/keys", "grid/dispatch", "grid/result"]
    assert all(end <= nxt for (_, end, _), (nxt, _, _) in zip(spans, spans[1:]))


def test_host_span_records_nothing_and_wall_span_still_does():
    SPANS.drain()
    with host_span("obs_test/host"):
        pass
    assert SPANS.drain() == []
    with wall_span("obs_test/wall"):
        pass
    assert [r["name"] for r in SPANS.drain()] == ["obs_test/wall"]
