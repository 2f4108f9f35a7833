"""Compile the main path's kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler, installed with JAX, compiles for a chip
that is described and not attached, and refuses what the chip would
refuse (unaligned tiles, scalar stores to VMEM, unlowerable primitives,
too much VMEM).  Every compile uses ``interpret=False`` and checks that
the program holds a Mosaic kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and pytest-xdist workers import every
test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.energy import RadioParams
from repro.core.ocean import OceanConfig, OceanState, simulate
from repro.core.solvers import get_solver
from repro.env.failure import TracedFailure
from repro.env.radio import TracedRadio
from repro.kernels import ocean_traj
from repro.kernels.ocean_p import ocean_p_prefixes_fused, ocean_p_topm_fused
from repro.obs import MetricsSpec
from repro.obs.metrics import init_metrics


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("k", (10, 10_000))
def test_prefixes_kernel_compiles(one_chip, k):
    radio = RadioParams(b_min=min(0.02, 0.1 / k))

    def solve(rho, n0, delta, v_eta):
        sol = ocean_p_prefixes_fused(
            rho, n0, delta, v_eta, radio, outer_iters=7, inner_iters=9,
            interpret=False,
        )
        return sol.m_star, sol.w_star, sol.b_pos_sorted

    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    n0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    text = _compile_text(solve, f32((k,)), n0, f32(()), f32(()))
    assert "tpu_custom_call" in text


def test_tiled_kernel_compiles_at_cross_device_scale(one_chip):
    k = 100_000
    radio = RadioParams(b_min=0.1 / k)

    def solve(rho, n0, delta, v_eta):
        return ocean_p_topm_fused(
            rho, n0, delta, v_eta, radio, top_m=128, block_k=128,
            interpret=False,
        )

    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    n0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    text = _compile_text(solve, f32((k,)), n0, f32(()), f32(()))
    assert "tpu_custom_call" in text


def test_fused_trajectory_compiles_newton_topm(one_chip):
    k, t = 10_000, 64
    cfg = OceanConfig(
        num_clients=k, num_rounds=t, radio=RadioParams(b_min=0.1 / k),
        solver="newton", ranking="topm", top_m=128,
    )

    def run(h2, v, eta, inc):
        return ocean_traj.ocean_trajectory_fused(
            cfg, h2, v, eta, inc, interpret=False
        )

    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    text = _compile_text(run, f32((t, k)), f32((t,)), f32((t,)), f32((t, k)))
    assert "tpu_custom_call" in text


def test_fused_trajectory_compiles_with_accepted_extras(one_chip):
    """What ``check_fused_lowerable`` lets through does lower: per-client
    telemetry, a traced radio, reallocating failures and a resumed segment."""
    k, t = 256, 16
    cfg = OceanConfig(
        num_clients=k, num_rounds=t, radio=RadioParams(b_min=0.1 / k),
        solver="newton", ranking="topm", top_m=32, failure_mode="reallocate",
        metrics=MetricsSpec.of("queue:full_trace", "selection_gap:mean"),
    )
    ocean_traj.check_fused_lowerable(cfg, has_failure=True)

    def run(h2, v, eta, inc, radio_leaves, dlv, rate, q0, es0, t0):
        radio = TracedRadio(*radio_leaves)
        init = OceanState(q=q0, t=t0, energy_spent=es0)
        return ocean_traj.ocean_trajectory_fused(
            cfg, h2, v, eta, inc, radio, TracedFailure(dlv, rate),
            interpret=False, init_state=init,
            init_mstate=init_metrics(cfg.metrics, cfg),
        )

    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    radio = tuple(f32((t,)) for _ in TracedRadio._fields)
    t0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    text = _compile_text(
        run, f32((t, k)), f32((t,)), f32((t,)), f32((t, k)), radio,
        f32((t, k)), f32((k,)), f32((k,)), f32((k,)), t0,
    )
    assert "tpu_custom_call" in text


def test_default_scan_bisect_program_compiles(one_chip):
    """The paper cell's default program (scan + bisect) is plain XLA."""
    k, t = 10, 300
    cfg = OceanConfig(num_clients=k, num_rounds=t, radio=RadioParams())
    eta = jnp.ones((t,), jnp.float32)
    h2 = jax.ShapeDtypeStruct((t, k), jnp.float32, sharding=one_chip)
    text = _compile_text(lambda h: simulate(cfg, h, eta, 1e-5), h2)
    assert "tpu_custom_call" not in text


def _bisect_sweep_whiles(one_chip, s, n, k):
    """The ``while`` lines of the sweep's bisect P4 compiled at the engine's
    (S, N) vmap around the candidate lattice, K clients."""

    def cell(rho, delta, v_eta, leaves):
        sol = get_solver("bisect").prefixes(
            rho, jnp.int32(0), delta, v_eta, TracedRadio(*leaves), 42, 42
        )
        return sol.m_star, sol.w_star, sol.b_pos_sorted, sol.sel_pos_sorted

    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    leaves = tuple(f32((s, n)) for _ in TracedRadio._fields)
    text = _compile_text(
        jax.vmap(jax.vmap(cell)), f32((s, n, k)), f32((s, n)), f32((s, n)), leaves
    )
    return [line for line in text.splitlines() if " while(" in line]


def test_bisect_inner_loop_runs_on_a_lane_dense_slab(one_chip):
    """The sweep's bisect P4 at (S, N) = (3, 10), K = 10: each inner
    bisection ``while`` carries (rows, 128) f32 slabs in (8, 128) tiles,
    padded by less than one row, and no loop carries the padded
    (S, N, K+1, K) lattice."""
    s, n, k = 3, 10, 10
    whiles = _bisect_sweep_whiles(one_chip, s, n, k)
    inner = [w.split(" while(")[0] for w in whiles if "p4/bisect/inner_slab" in w]
    assert len(inner) == 2  # one per outer trip, one for the final b(lam)
    for carry in inner:
        slabs = re.findall(r"f32\[(\d+),128\]\{1,0:T\(8,128\)", carry)
        assert len(slabs) == len(re.findall(r"f32\[", carry)) == 4
        assert all(0 <= int(rows) * 128 - s * n * (k + 1) * k < 128 for rows in slabs)
    assert not any(f"f32[{s},{n},{k + 1},{k}]" in w.split(" while(")[0] for w in whiles)


@pytest.mark.parametrize("k", (128, 200))
def test_bisect_lattice_puts_clients_on_the_lanes_from_k128(one_chip, k):
    """Where the slab does not engage (K >= 128), the inner bisection
    ``while`` carries the (S, N, K+1, K) lattice with the clients on the
    lanes and the candidates on the sublanes, in (8, 128) tiles: the
    lattice is lane-dense in its own layout."""
    s, n = 3, 10
    whiles = _bisect_sweep_whiles(one_chip, s, n, k)
    assert not any("p4/bisect/inner_slab" in w for w in whiles)
    lattice = f"f32[{s},{n},{k + 1},{k}]"
    inner = [w.split(" while(")[0] for w in whiles if lattice in w.split(" while(")[0]]
    assert len(inner) == 2
    for carry in inner:
        layouts = re.findall(re.escape(lattice) + r"(\{[^}]*\})", carry)
        assert len(layouts) == 2, layouts  # lo and hi
        assert all(lay.startswith("{3,2,1,0:T(8,128)") for lay in layouts), layouts
