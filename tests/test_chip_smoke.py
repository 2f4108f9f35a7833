"""chip_smoke.py off the chip: the CPU rehearsal runs, no chip means no run.

Also pins the pieces that keep the chip path honest: kernels interpret only
on the CPU backend, the fused kernel refuses what Mosaic cannot lower before
anything compiles, and the compile cache lands where it is told.
"""
import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

from repro import compile_cache
from repro.core.energy import RadioParams
from repro.core.ocean import OceanConfig
from repro.guard import GuardSpec
from repro.kernels import ocean_p, ocean_traj
from repro.obs import MetricsSpec

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_rehearsal_runs_every_phase_on_cpu(chip_smoke, capsys):
    assert chip_smoke.main(["--rehearse"]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [l.get("phase") for l in lines[:-1]] == ["A", "B", "C"]
    for line in lines[:-1]:
        assert line["selection_agreement"] >= chip_smoke.AGREE_MIN
    assert lines[-1] == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}
    }


def test_without_a_chip_it_refuses_to_run(chip_smoke, capsys):
    assert chip_smoke.main([]) == 2
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no TPU found" in captured.err


@pytest.mark.parametrize("backend, interpret", [("cpu", True), ("tpu", False), ("gpu", False)])
def test_kernels_interpret_only_on_cpu(monkeypatch, backend, interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ocean_p._default_interpret() is interpret
    assert ocean_traj._default_interpret() is interpret


def _fused_cfg(**overrides):
    kw = dict(
        num_clients=8, num_rounds=16, radio=RadioParams(b_min=0.01),
        solver="newton", ranking="topm", top_m=4,
    )
    kw.update(overrides)
    return OceanConfig(**kw)


@pytest.mark.parametrize(
    "overrides, named",
    [
        (dict(ranking="sort"), "ranking='sort'"),
        (dict(solver="bisect"), "solver='bisect'"),
        (dict(solver="pallas"), "solver='pallas'"),
        (dict(solver="pallas_tiled"), "solver='pallas_tiled'"),
        (dict(metrics=MetricsSpec.of("lyapunov:last")), "metrics lyapunov:last"),
        (dict(metrics=MetricsSpec.of("queue:histogram")), "metrics queue:histogram"),
        (dict(guard=GuardSpec(energy_cap=1.0)), "guard"),
    ],
)
def test_fused_refuses_unlowerable_combinations_before_compiling(overrides, named):
    cfg = _fused_cfg(**overrides)
    h2 = jnp.ones((16, 8), jnp.float32)
    v = jnp.full((16,), 1e-5, jnp.float32)
    with pytest.raises(ValueError, match=f"cannot compile for a TPU with {named}"):
        ocean_traj.ocean_trajectory_fused(
            cfg, h2, v, v, h2 * 0.01, interpret=False
        )


def test_fused_refuses_bf16_streams_and_overprovision():
    with pytest.raises(ValueError, match="stream_bf16"):
        ocean_traj.check_fused_lowerable(_fused_cfg(), stream_bf16=True)
    cfg = _fused_cfg(failure_mode="overprovision")
    ocean_traj.check_fused_lowerable(cfg)          # inert without failures
    with pytest.raises(ValueError, match="overprovision"):
        ocean_traj.check_fused_lowerable(cfg, has_failure=True)
    ocean_traj.check_fused_lowerable(_fused_cfg(failure_mode="reallocate"), True)


def test_fused_refuses_chunks_off_the_row_tile():
    h2 = jnp.ones((20, 8), jnp.float32)
    v = jnp.full((20,), 1e-5, jnp.float32)
    cfg = _fused_cfg(num_rounds=20)
    with pytest.raises(ValueError, match="chunk=7"):
        ocean_traj.ocean_trajectory_fused(
            cfg, h2, v, v, h2 * 0.01, chunk=7, interpret=False
        )


@pytest.fixture
def restore_cache_config():
    keys = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_compile_cache_defaults_to_the_repo(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compilation_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_follows_the_environment(
    monkeypatch, tmp_path, restore_cache_config
):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert compile_cache.enable_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
