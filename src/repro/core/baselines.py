"""Benchmark policies from the paper (§VI-A) plus an offline oracle.

* ``select_all``  — all K clients every round; bandwidth minimizes total
  energy subject to the deadline (ignores budgets).
* ``smo``         — Static Myopic Optimal: hard per-round budget H_k/T;
  equivalent to the 1-round-lookahead algorithm (paper Eq. 19-20).
* ``amo``         — Adaptive Myopic Optimal: recycles unused budget,
  per-round budget (H_k - spent) / (T - t).
* ``lookahead_dual`` — offline R=T oracle approximated by Lagrangian dual
  decomposition over the *known* channel sequence: dualizing the long-term
  energy constraints turns each round into a P3 with static multipliers
  mu_k in place of the queues; projected subgradient ascent on mu.  This
  realizes the paper's T-round-lookahead benchmark (§IV-D) to dual
  precision, which upper-bounds within the duality gap of the per-round
  mixed-integer problems.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.energy import RadioParams, energy, min_bandwidth_for_energy
from repro.core.ocean import OceanConfig
from repro.core.selection import ocean_p
from repro.obs.spans import trace_span

Array = jax.Array


class PolicyTrace(NamedTuple):
    a: Array   # (T, K) selections
    b: Array   # (T, K) bandwidth ratios
    e: Array   # (T, K) per-round energy
    num_selected: Array  # (T,)
    # in-graph telemetry ("<collector>/<reduction>" -> array) recorded when
    # the config carries a repro.obs.MetricsSpec; None (the default) for
    # metrics-off runs and for policies without Lyapunov machinery.
    metrics: Optional[Dict[str, Array]] = None
    # (T, K) selected-and-delivered mask when a repro.env.failure process
    # is active; None (the default) keeps pre-failure pytrees identical.
    delivered: Optional[Array] = None


def _trace(a, b, e, delivered=None):
    return PolicyTrace(
        a=a, b=b, e=e, num_selected=jnp.sum(a, axis=-1), delivered=delivered
    )


def _delivered_mask(a: Array, failure_seq) -> Optional[Array]:
    """Selected-and-delivered (T, K) bool mask; None without failures.

    Baselines keep their selections and spend their full transmission
    energy (the pessimistic accounting) — unreliability only gates which
    updates arrive.
    """
    if failure_seq is None:
        return None
    return a & (failure_seq.delivered > 0.0)


# --------------------------------------------------------------------------
# Select-All
# --------------------------------------------------------------------------
def select_all(
    cfg: OceanConfig, h2_seq: Array, radio_seq=None, failure_seq=None
) -> PolicyTrace:
    """Select everyone; minimize total energy via the P4 waterfiller.

    ``radio_seq`` — optional per-round radio physics, a pytree of (T,)
    leaves (``repro.env.radio.TracedRadio``); None bakes in the static
    ``cfg.radio`` exactly as before.  ``cfg.solver`` picks the P4
    waterfilling backend (``repro.core.solvers``).  ``failure_seq`` — an
    optional realized ``repro.env.failure.TracedFailure``; it gates the
    trace's ``delivered`` mask only.
    """
    from repro.core.bandwidth import solve_p4

    K = cfg.num_clients

    def per_round(h2, radio):
        rho = 1.0 / jnp.maximum(h2, 1e-30)  # energy weights, all positive
        b, _ = solve_p4(
            rho, jnp.ones((K,), bool), jnp.asarray(1.0), radio, method=cfg.solver
        )
        a = jnp.ones((K,), bool)
        return a, b, energy(b, h2, radio, a)

    if radio_seq is None:
        a, b, e = jax.vmap(lambda h2: per_round(h2, cfg.radio))(h2_seq)
    else:
        a, b, e = jax.vmap(per_round)(h2_seq, radio_seq)
    return _trace(a, b, e, _delivered_mask(a, failure_seq))


# --------------------------------------------------------------------------
# SMO / AMO
# --------------------------------------------------------------------------
def _myopic_round(h2: Array, budget: Array, radio: RadioParams):
    """Greedy of §VI-A: cheapest-bandwidth clients first until B is exhausted."""
    with trace_span("myopic/min_bandwidth"):
        b_dag = min_bandwidth_for_energy(budget, h2, radio)  # (K,), inf if infeasible
    with trace_span("myopic/greedy"):
        order = jnp.argsort(b_dag)
        b_sorted = b_dag[order]
        csum = jnp.cumsum(jnp.where(jnp.isfinite(b_sorted), b_sorted, 1e9))
        take_sorted = (csum <= 1.0) & jnp.isfinite(b_sorted)
        inv = jnp.argsort(order)
        a = take_sorted[inv]
        b = jnp.where(a, b_dag, 0.0)
    return a, b


def smo(
    cfg: OceanConfig,
    h2_seq: Array,
    budgets: Optional[Array] = None,
    budget_seq: Optional[Array] = None,
    radio_seq=None,
    failure_seq=None,
) -> PolicyTrace:
    """Static Myopic Optimal; ``budget_seq`` (T, K) makes the hard
    per-round cap follow a time-varying budget process instead of the
    constant H_k / T, ``radio_seq`` per-round radio physics (None bakes
    in the static ``cfg.radio``), ``failure_seq`` an optional realized
    reliability gating the ``delivered`` mask."""
    if budget_seq is None:
        per = (cfg.budgets() if budgets is None else budgets) / cfg.num_rounds
        budget_seq = jnp.broadcast_to(per, h2_seq.shape)

    def per_round(h2, cap, radio):
        a, b = _myopic_round(h2, cap, radio)
        return a, b, energy(b, h2, radio, a)

    if radio_seq is None:
        a, b, e = jax.vmap(lambda h2, cap: per_round(h2, cap, cfg.radio))(
            h2_seq, budget_seq
        )
    else:
        a, b, e = jax.vmap(per_round)(h2_seq, budget_seq, radio_seq)
    return _trace(a, b, e, _delivered_mask(a, failure_seq))


def amo_segment(
    cfg: OceanConfig,
    spent: Array,
    h2_seq: Array,
    ts: Array,
    budgets: Optional[Array] = None,
    radio_seq=None,
    failure_seq=None,
) -> Tuple[Array, PolicyTrace]:
    """AMO over one contiguous block of rounds from a carried ``spent``.

    ``ts`` holds the *global* round indices of the block (the budget
    recycling rate depends on how many of the T total rounds remain).
    ``amo`` is exactly this from ``spent = 0`` over ``ts = 0..T-1``; the
    segmented grid engine feeds the carry across checkpoint boundaries.
    """
    budgets = cfg.budgets() if budgets is None else budgets
    T = cfg.num_rounds

    def round_fn(spent, h2, t, radio):
        remaining = jnp.maximum(budgets - spent, 0.0)
        per_round_budget = remaining / jnp.maximum(T - t, 1).astype(jnp.float32)
        a, b = _myopic_round(h2, per_round_budget, radio)
        e = energy(b, h2, radio, a)
        return spent + e, (a, b, e)

    if radio_seq is None:
        def step(spent, inputs):
            h2, t = inputs
            return round_fn(spent, h2, t, cfg.radio)

        spent, (a, b, e) = jax.lax.scan(step, spent, (h2_seq, ts))
    else:
        def step(spent, inputs):
            h2, t, radio_t = inputs
            return round_fn(spent, h2, t, radio_t)

        spent, (a, b, e) = jax.lax.scan(step, spent, (h2_seq, ts, radio_seq))
    return spent, _trace(a, b, e, _delivered_mask(a, failure_seq))


def amo(
    cfg: OceanConfig,
    h2_seq: Array,
    budgets: Optional[Array] = None,
    radio_seq=None,
    failure_seq=None,
) -> PolicyTrace:
    budgets = cfg.budgets() if budgets is None else budgets
    _, trace = amo_segment(
        cfg,
        jnp.zeros_like(budgets),
        h2_seq,
        jnp.arange(cfg.num_rounds),
        budgets=budgets,
        radio_seq=radio_seq,
        failure_seq=failure_seq,
    )
    return trace


# --------------------------------------------------------------------------
# Offline T-round lookahead oracle via Lagrangian dual decomposition
# --------------------------------------------------------------------------
def lookahead_dual(
    cfg: OceanConfig,
    h2_seq: Array,
    eta_seq: Array,
    num_iters: int = 400,
    lr: float = 50.0,
    budgets: Optional[Array] = None,
    radio_seq=None,
) -> Tuple[PolicyTrace, Array]:
    """Approximate the R=T lookahead oracle with full channel knowledge.

    Returns the primal trace of the final multipliers and the dual value
    (an upper bound on the oracle utility, used in Theorem-2 checks).
    ``radio_seq`` — optional per-round radio physics (the oracle also
    knows the realized bandwidth/deadline sequence).
    """
    T, K = h2_seq.shape
    eta_seq = jnp.asarray(eta_seq, jnp.float32)
    budgets = cfg.budgets() if budgets is None else budgets

    def rounds_for(mu):
        def per_round(h2, eta_t, radio):
            sol = ocean_p(
                mu,
                h2,
                jnp.asarray(1.0),
                eta_t,
                radio,
                solver=cfg.solver,
                ranking=cfg.ranking,
                top_m=cfg.top_m,
                block_k=cfg.block_k,
            )
            e = energy(sol.b, h2, radio, sol.a)
            return sol.a, sol.b, e

        if radio_seq is None:
            return jax.vmap(lambda h2, eta_t: per_round(h2, eta_t, cfg.radio))(
                h2_seq, eta_seq
            )
        return jax.vmap(per_round)(h2_seq, eta_seq, radio_seq)

    def dual_step(mu, _):
        a, b, e = rounds_for(mu)
        viol = jnp.sum(e, axis=0) - budgets          # (K,) subgradient
        mu_next = jnp.maximum(mu + lr * viol, 0.0)
        util = jnp.sum(eta_seq * jnp.sum(a, axis=-1))
        dual_val = util - jnp.sum(mu * viol)
        return mu_next, dual_val

    mu, dual_vals = jax.lax.scan(
        dual_step, jnp.zeros((K,), jnp.float32), None, length=num_iters
    )
    a, b, e = rounds_for(mu)
    return _trace(a, b, e), dual_vals[-1]


def utility(trace: PolicyTrace, eta_seq: Array) -> Array:
    """sum_t eta^t * |S^t| — the paper's long-term objective (Eq. 4)."""
    return jnp.sum(jnp.asarray(eta_seq) * trace.num_selected.astype(jnp.float32))


def delivered_utility(trace: PolicyTrace, eta_seq: Array) -> Array:
    """sum_t eta^t * |delivered S^t| — Eq. 4 counting only the updates
    that actually arrived; equals ``utility`` without a failure process."""
    if trace.delivered is None:
        return utility(trace, eta_seq)
    ns = jnp.sum(trace.delivered.astype(jnp.float32), axis=-1)
    return jnp.sum(jnp.asarray(eta_seq) * ns)
