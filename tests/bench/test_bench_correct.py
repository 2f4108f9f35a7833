"""The comparison that decides ``correct`` must fail what is wrong.

At tiny size on the CPU: the control (the reference computed in bfloat16 in
the program's place) fails every cell, and a run of the harness with the
timed path broken underneath reads ``correct`` false, once for each fault a
cell can have: a round that returns its state unchanged, half the clients
left out of the decision, an answer altered where it is produced.  (The
cells run on one chip, so no exchange between chips can be left out.)
"""
import numpy as np
import pytest

from bench_tiny import CELLS, harness, tiny

import control
import reference as ref


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_and_program_passes(name):
    got, ctl = control.readings(tiny(name), 20260, 0.3)
    assert got.judged()[0], got.worst
    assert not ctl.judged()[0], ctl.worst
    assert ctl.failed > 0


def _unchanged(orig):
    def round_(state, h2, v, eta, cfg, *args, **kw):
        _, dec = orig(state, h2, v, eta, cfg, *args, **kw)
        return state._replace(t=state.t + 1), dec
    return round_


def _half(orig):
    """OCEAN-P decides over the first half of the clients only: the others'
    channel reports are dropped before ranking (energy still uses them)."""
    def ocean_p(q, h2, *args, **kw):
        import jax.numpy as jnp

        k = h2.shape[-1]
        h2 = jnp.where(jnp.arange(k) < k // 2, h2, 1e-30).astype(h2.dtype)
        return orig(q, h2, *args, **kw)
    return ocean_p


def _altered(orig):
    def round_(state, h2, v, eta, cfg, *args, **kw):
        new, dec = orig(state, h2, v, eta, cfg, *args, **kw)
        return new, dec._replace(b=dec.b * 1.01)
    return round_


FAULTS = {"state_unchanged": ("ocean_round", _unchanged),
          "half_left_out": ("ocean_p", _half),
          "answer_altered": ("ocean_round", _altered)}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_broken_timed_path_reads_incorrect(monkeypatch, name, fault):
    import repro.core.ocean as ocean

    attr, wrap = FAULTS[fault]
    monkeypatch.setattr(ocean, attr, wrap(getattr(ocean, attr)))
    r = harness.execute(tiny(name), 99, 0.3, False, require_chip=False)
    assert r["correct"] is False, r["checks"]
    assert r["failed"] > 0


def test_reference_p3_matches_brute_force():
    """Theorem 1's prefix search against every subset, at K=6."""
    import itertools

    rng = np.random.default_rng(3)
    radio = ref.Radio(1e7, 1e-12, 0.3, 3.4e5, 0.02)
    q = rng.uniform(0, 0.05, 6)
    q[1] = 0.0
    h2 = rng.exponential(2.5e-4, 6)
    rho = q / h2
    dec = ref.solve_p3(rho, 1e-5, radio)
    best = -np.inf
    s0 = rho <= ref.RHO_ZERO
    pos = np.flatnonzero(~s0)
    for r in range(len(pos) + 1):
        for sub in itertools.combinations(pos, r):
            ms = np.array([len(sub)]) if sub else None
            if sub:
                order = np.array(sub)[np.argsort(rho[list(sub)])]
                cost, _ = ref._prefix_costs(rho[order], ms, 1 - s0.sum() * 0.02,
                                            radio.beta, radio.b_min)
                w = 1e-5 * (s0.sum() + len(sub)) - radio.scale * cost[0]
            else:
                w = 1e-5 * s0.sum()
            best = max(best, w)
    assert dec.w == pytest.approx(best, rel=1e-12)
    assert ref.p3_value(dec.a, dec.b, q, h2, 1e-5, radio) == pytest.approx(dec.w, rel=1e-9)
    assert dec.b.sum() == pytest.approx(1.0, abs=1e-12)


def test_reference_p4_meets_kkt():
    radio = ref.Radio(1e8, 1e-12, 1.0, 2e4, 1e-6)
    rho = np.sort(np.random.default_rng(4).uniform(1, 50, 40))
    cost, b = ref._prefix_costs(rho, np.array([40]), 1.0, radio.beta, radio.b_min)
    lam = -rho * ref.f_prime(b[0], radio.beta)
    assert b.sum() == pytest.approx(1.0, abs=1e-11)
    assert np.ptp(lam) / lam.mean() < 1e-9
