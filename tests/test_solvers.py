"""Solver backends: exactness vs brute force + cross-backend identity.

The hypothesis property-test variants live in
tests/test_solver_properties.py (importorskip'd); this module keeps the
exactness guarantees exercised even without the dev extra installed.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import OceanConfig, Scenario
from repro.core.bandwidth import solve_p4
from repro.core.energy import RadioParams
from repro.core.selection import ocean_p, p3_value, priorities
from repro.core.solvers import available_solvers, get_solver

RADIO = RadioParams()
BACKENDS = ("bisect", "newton", "pallas")


def brute_force_best(q, h2, v, eta, radio):
    """Enumerate all 2^K selections; evaluate each via the p3_value oracle."""
    K = len(q)
    rho = np.asarray(priorities(jnp.asarray(q), jnp.asarray(h2)))
    best_val, best_set = 0.0, ()
    for r in range(K + 1):
        for subset in itertools.combinations(range(K), r):
            mask = np.zeros(K, bool)
            mask[list(subset)] = True
            s0 = mask & (rho <= 1e-30)
            rest = mask & ~s0
            delta = 1.0 - s0.sum() * radio.b_min
            b = np.where(s0, radio.b_min, 0.0)
            if rest.sum() > 0:
                b_rest, _ = solve_p4(
                    jnp.asarray(rho), jnp.asarray(rest), jnp.asarray(delta), radio
                )
                b = b + np.asarray(b_rest)
            val = float(
                p3_value(jnp.asarray(mask), jnp.asarray(b), q, h2, v, eta, radio)
            )
            if val > best_val + 1e-12:
                best_val, best_set = val, subset
    return best_val, best_set


def _draw(rng, k):
    q = rng.uniform(0, 0.2, size=k).astype(np.float32)
    q[rng.random(k) < 0.3] = 0.0
    h2 = (2.5e-4 * rng.exponential(size=k)).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(h2)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_every_backend_matches_bruteforce(backend, seed):
    rng = np.random.default_rng(seed)
    k = int(rng.integers(2, 6))
    q, h2 = _draw(rng, k)
    v, eta = 1e-5, 1.0
    ref, _ = brute_force_best(q, h2, v, eta, RADIO)

    sol = ocean_p(q, h2, jnp.asarray(v), jnp.asarray(eta), RADIO, solver=backend)
    ours = float(sol.objective)
    tol = max(1e-6, 5e-3 * abs(ref))
    assert ours >= ref - tol
    # the returned (a, b) must actually achieve the claimed value
    achieved = float(p3_value(sol.a, sol.b, q, h2, v, eta, RADIO))
    assert achieved == pytest.approx(ours, rel=1e-3, abs=1e-6)


@pytest.mark.parametrize("backend", ("newton", "pallas"))
def test_fast_backends_reproduce_bisect_selection_exactly(backend):
    """Same argmax selection set as the bit-stable reference, randomized
    (q, h2, V, eta, radio) draws included — the acceptance criterion."""
    rng = np.random.default_rng(7)
    for _ in range(12):
        k = int(rng.integers(2, 16))
        q, h2 = _draw(rng, k)
        v = jnp.asarray(10.0 ** rng.uniform(-6.0, -4.0), jnp.float32)
        eta = jnp.asarray(rng.uniform(0.5, 1.5), jnp.float32)
        radio = RadioParams(
            bandwidth_hz=float(10.0 ** rng.uniform(6.5, 7.5)),
            deadline_s=float(rng.uniform(0.1, 0.5)),
            b_min=float(rng.uniform(0.005, 0.9 / k)),
        )
        ref = ocean_p(q, h2, v, eta, radio, solver="bisect")
        sol = ocean_p(q, h2, v, eta, radio, solver=backend)
        np.testing.assert_array_equal(
            np.asarray(sol.a), np.asarray(ref.a), err_msg=f"k={k}"
        )
        assert float(jnp.sum(sol.b)) == pytest.approx(
            float(jnp.sum(ref.b)), abs=1e-5
        )
        assert float(sol.objective) == pytest.approx(
            float(ref.objective), rel=2e-2, abs=1e-7
        )


@pytest.mark.parametrize("method", ("newton", "pallas"))
def test_solve_p4_method_matches_bisect(method):
    rng = np.random.default_rng(3)
    rho = jnp.asarray(rng.uniform(1.0, 500.0, size=9).astype(np.float32))
    mask = jnp.asarray(rng.random(9) < 0.7)
    delta = jnp.asarray(0.9, jnp.float32)
    b_ref, c_ref = solve_p4(rho, mask, delta, RADIO)
    b, c = solve_p4(rho, mask, delta, RADIO, method=method)
    np.testing.assert_allclose(np.asarray(b), np.asarray(b_ref), atol=2e-4)
    assert float(c) == pytest.approx(float(c_ref), rel=1e-3)
    assert float(jnp.sum(b)) == pytest.approx(float(jnp.sum(b_ref)), abs=1e-5)


def test_pallas_kernel_parity_vs_ref():
    """ref.py-style harness: fused kernel vs the pure-jnp prefix oracle."""
    from repro.kernels.ocean_p import ocean_p_prefixes_fused
    from repro.kernels.ref import ocean_p_prefixes_ref

    rng = np.random.default_rng(11)
    for _ in range(6):
        k = int(rng.integers(3, 12))
        q, h2 = _draw(rng, k)
        rho = jnp.sort(priorities(q, h2))
        n0 = jnp.sum(rho <= 1e-30)
        delta = 1.0 - n0.astype(jnp.float32) * RADIO.b_min
        v_eta = jnp.asarray(1e-5, jnp.float32)
        ref = ocean_p_prefixes_ref(rho, n0, delta, v_eta, RADIO)
        sol = ocean_p_prefixes_fused(rho, n0, delta, v_eta, RADIO)
        assert int(sol.m_star) == int(ref.m_star)
        np.testing.assert_array_equal(
            np.asarray(sol.sel_pos_sorted), np.asarray(ref.sel_pos_sorted)
        )
        np.testing.assert_allclose(
            np.asarray(sol.b_pos_sorted), np.asarray(ref.b_pos_sorted), atol=2e-4
        )


def test_backends_vmap_and_jit():
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.uniform(0, 0.2, (4, 8)).astype(np.float32))
    h2 = jnp.asarray((2.5e-4 * rng.exponential(size=(4, 8))).astype(np.float32))
    for backend in BACKENDS:
        fn = jax.jit(
            jax.vmap(
                lambda q, h2, s=backend: ocean_p(
                    q, h2, jnp.asarray(1e-5), jnp.asarray(1.0), RADIO, solver=s
                ).num_selected
            )
        )
        assert fn(q, h2).shape == (4,)


# -- dtype promotion (regression: the old guard only caught int32) ---------
@pytest.mark.parametrize("dtype", [np.int64, np.int32, np.int16, bool])
def test_integer_and_bool_inputs_promote(dtype):
    q_i = np.asarray([0, 1, 0, 2, 1], dtype)
    h2 = np.full(5, 2.5e-4, np.float32)
    sol = ocean_p(
        jnp.asarray(q_i), jnp.asarray(h2), jnp.asarray(1e-5), jnp.asarray(1.0), RADIO
    )
    assert jnp.issubdtype(sol.b.dtype, jnp.floating)
    ref = ocean_p(
        jnp.asarray(q_i.astype(np.float32)),
        jnp.asarray(h2),
        jnp.asarray(1e-5),
        jnp.asarray(1.0),
        RADIO,
    )
    np.testing.assert_array_equal(np.asarray(sol.a), np.asarray(ref.a))
    np.testing.assert_array_equal(np.asarray(sol.b), np.asarray(ref.b))


def test_integer_h2_promotes_too():
    sol = ocean_p(
        jnp.asarray(np.zeros(4, np.int64)),
        jnp.asarray(np.ones(4, np.int16)),
        jnp.asarray(1e-5),
        jnp.asarray(1.0),
        RADIO,
    )
    assert jnp.issubdtype(sol.b.dtype, jnp.floating)
    assert int(sol.num_selected) == 4


# -- registry / config plumbing -------------------------------------------
def test_unknown_solver_rejected_everywhere():
    assert set(BACKENDS) <= set(available_solvers())
    with pytest.raises(ValueError, match="unknown solver backend"):
        get_solver("simplex")
    with pytest.raises(ValueError, match="unknown solver backend"):
        OceanConfig(num_clients=4, num_rounds=10, radio=RADIO, solver="simplex")
    with pytest.raises(ValueError, match="unknown solver backend"):
        Scenario(num_clients=4, num_rounds=10, solver="simplex")
    with pytest.raises(ValueError, match="unknown solver backend"):
        ocean_p(
            jnp.zeros(3), jnp.ones(3), jnp.asarray(1e-5), jnp.asarray(1.0),
            RADIO, solver="simplex",
        )


def test_scenario_solver_serialization_roundtrip():
    sc = Scenario(num_clients=4, num_rounds=10, solver="newton")
    assert Scenario.from_json(sc.to_json()).solver == "newton"
    # default backend omitted => pre-solver payloads stay byte-stable
    assert "solver" not in Scenario(num_clients=4, num_rounds=10).to_dict()
    assert sc.ocean_config().solver == "newton"


# -- dtype-aware Newton budgets + float64 (PR-5 satellite) ------------------
def test_newton_iteration_budgets_dtype_aware():
    from repro.core import solvers

    f32 = solvers.newton_iteration_budgets(np.float32)
    f64 = solvers.newton_iteration_budgets(np.float64)
    # float32 budgets unchanged from PR 4 => the hot path stays bit-stable
    assert f32 == (
        solvers.NEWTON_OUTER_ITERS,
        solvers.NEWTON_INNER_ITERS,
        solvers.NEWTON_GRID_LEVELS,
    )
    # float64 needs strictly wider budgets on every axis
    assert all(w > n for w, n in zip(f64, f32))


def test_x64_newton_matches_bisect_near_tie_boundaries():
    """Under jax.enable_x64 the newton backend must reproduce bisect's
    argmax selection set even on draws engineered to sit near W*(S_m) ==
    W*(S_{m+1}) tie boundaries (clustered priorities that differ at the
    ~1e-9 relative level, invisible in float32)."""
    rng = np.random.default_rng(42)
    with jax.enable_x64(True):
        for trial in range(10):
            k = int(rng.integers(4, 12))
            # clustered rho: pairs of nearly identical priorities
            base = rng.uniform(0.01, 0.2, size=(k + 1) // 2)
            q = np.repeat(base, 2)[:k] * (
                1.0 + rng.uniform(-1e-9, 1e-9, size=k)
            )
            q[rng.random(k) < 0.2] = 0.0
            h2 = np.repeat(
                2.5e-4 * rng.exponential(size=(k + 1) // 2), 2
            )[:k] * (1.0 + rng.uniform(-1e-9, 1e-9, size=k))
            q64 = jnp.asarray(q, jnp.float64)
            h64 = jnp.asarray(h2, jnp.float64)
            assert q64.dtype == jnp.float64  # x64 actually on
            v = jnp.asarray(10.0 ** rng.uniform(-6.0, -4.0), jnp.float64)
            eta = jnp.asarray(rng.uniform(0.5, 1.5), jnp.float64)
            ref = ocean_p(q64, h64, v, eta, RADIO, solver="bisect")
            sol = ocean_p(q64, h64, v, eta, RADIO, solver="newton")
            assert sol.b.dtype == jnp.float64
            np.testing.assert_array_equal(
                np.asarray(sol.a),
                np.asarray(ref.a),
                err_msg=f"trial={trial} k={k}",
            )
            assert float(jnp.sum(sol.b)) == pytest.approx(
                float(jnp.sum(ref.b)), abs=1e-9
            )
            assert float(sol.objective) == pytest.approx(
                float(ref.objective), rel=1e-6, abs=1e-12
            )


# -- bisect's inner bisection on a lane-dense slab -------------------------
def _rho_sorted(rng, shape):
    q, h2 = _draw(rng, int(np.prod(shape)))
    return jnp.sort(priorities(q, h2).reshape(shape), axis=-1)


def _prefixes(rho, radio):
    sol = get_solver("bisect").prefixes(
        rho, jnp.int32(0), jnp.asarray(1.0, rho.dtype),
        jnp.asarray(1e-5, rho.dtype), radio, 42, 42,
    )
    return sol.m_star, sol.w_star, sol.b_pos_sorted, sol.sel_pos_sorted


def _slab_case_unbatched():
    rho = _rho_sorted(np.random.default_rng(20), (10,))
    mask = jnp.arange(10) < 7
    return lambda r: solve_p4(r, mask, jnp.asarray(0.9), RADIO), (rho,)


def _slab_case_one_vmap():
    return lambda r: _prefixes(r, RADIO), (_rho_sorted(np.random.default_rng(21), (10,)),)


def _slab_case_nested():
    """The engine's (S, N) vmap around the candidate vmap, with a traced
    radio whose bandwidth and b_min differ per cell."""
    from repro.env.radio import TracedRadio, traced_radio

    s, n, k = 3, 4, 10
    base = traced_radio(RADIO)
    bw = jnp.asarray(np.linspace(5e6, 2e7, s * n).reshape(s, n), jnp.float32)
    b_min = jnp.asarray(np.linspace(0.01, 0.05, s * n).reshape(s, n), jnp.float32)
    leaves = {f: jnp.broadcast_to(jnp.asarray(getattr(base, f)), (s, n))
              for f in TracedRadio._fields}
    leaves.update(bandwidth_hz=bw, b_min=b_min,
                  beta=base.model_bits / (base.deadline_s * bw),
                  energy_scale=base.deadline_s * base.noise_w * bw)
    cell = lambda r, leaves: _prefixes(r, TracedRadio(**leaves))
    rho = _rho_sorted(np.random.default_rng(22), (s, n, k))
    return jax.vmap(jax.vmap(cell)), (rho, leaves)


def _slab_case_float64():
    rho = _rho_sorted(np.random.default_rng(23), (10,)).astype(jnp.float64)
    return lambda r: _prefixes(r, RADIO), (rho,)


def _slab_case_demoted():
    from repro.core.selection import RHO_DEMOTED

    rho = _rho_sorted(np.random.default_rng(24), (10,)).at[-2:].set(RHO_DEMOTED)
    return lambda r: _prefixes(r, RADIO), (rho,)


def _slab_case_k128():
    radio = RadioParams(b_min=0.005)
    return lambda r: _prefixes(r, radio), (_rho_sorted(np.random.default_rng(25), (128,)),)


@pytest.mark.parametrize("case", ["unbatched", "one_vmap", "nested", "float64",
                                  "demoted", "k128"])
def test_bisect_slab_equals_lattice_loop_bitwise(case, monkeypatch):
    """Below 128 clients the inner bisection runs on a (rows, 128) slab and
    gives the same bits as the loop in the lattice's own shape; at K = 128
    the slab does not engage."""
    from repro.core import bandwidth

    with jax.enable_x64(case == "float64"):
        fn, args = globals()[f"_slab_case_{case}"]()
        jaxpr = str(jax.make_jaxpr(fn)(*args))
        slab = jax.jit(fn)(*args)
        monkeypatch.setattr(bandwidth, "LANES", 0)  # every K takes the loop
        loop = jax.jit(lambda *a: fn(*a))(*args)  # a new function: traced anew
    k = args[0].shape[-1]
    assert ("custom_vmap_call" in jaxpr) == (k < 128)
    if case == "float64":
        assert slab[1].dtype == jnp.float64
    for got, want in zip(jax.tree_util.tree_leaves(slab), jax.tree_util.tree_leaves(loop)):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bisect_slab_pad_lanes_stay_finite():
    """Under ``jax.debug_nans`` every step of the loop over lanes that hold
    only the slab's padding stays finite (op by op), and so does a slab
    solve."""
    from repro.core import bandwidth

    pad = [jnp.full((8, 128), fill, jnp.float32) for fill in bandwidth._SLAB_FILL]
    rho = _rho_sorted(np.random.default_rng(26), (11, 10))
    lam = jnp.linspace(0.0, 1e-3, 11)[:, None]
    with jax.debug_nans(True):
        with jax.disable_jit():
            b_pad = bandwidth._bisect_steps(*pad, 42)
        b = bandwidth._b_of_lam(lam, rho, RADIO.beta, RADIO.b_min, jnp.full((11, 1), 0.5), 42)
    assert np.all(np.isfinite(np.asarray(b_pad))) and np.all(np.isfinite(np.asarray(b)))
