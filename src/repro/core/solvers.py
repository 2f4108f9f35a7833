"""Pluggable P4 / OCEAN-P solver backends (perf: the repo-wide hot loop).

Every benchmark spends nearly all of its time inside ``ocean_p``
(`repro.core.selection`), which evaluates K+1 candidate prefixes of the
rho-sorted client order, each via the convex waterfilling problem P4
(`repro.core.bandwidth`).  The reference implementation runs a 42-step
outer bisection on the waterfilling level ``lam`` whose every step runs a
42-step inner bisection per client — exact, bit-stable, and ~1764
transcendental sweeps of the (K+1, K) candidate lattice per round.  This
module makes the solver a pluggable backend:

``bisect``
    The original double bisection, verbatim (moved here from
    ``selection.ocean_p`` / dispatched to ``bandwidth.solve_p4``).  It is
    the default so every existing figure benchmark stays byte-stable.
    Below 128 clients its inner bisection runs on a lane-dense
    ``(rows, 128)`` slab into which every vmap level (candidates, seeds,
    scenarios) folds, and at K >= 128 in the lattice's own layout; both
    give the same bits (``repro.core.bandwidth``).

``newton``
    Safeguarded Newton waterfilling.  Two nested root-finds replace the
    two bisections:

    * **Inner** — invert ``rho_k f'(b) = -lam`` per client.  ``f`` is the
      Shannon-inversion ``b (2^{beta/b} - 1)`` (Lemma 1): ``f'`` is
      smooth, negative and strictly increasing, ``f'' > 0``, so the root
      is unique.  A closed-form seed (asymptotics of ``f'`` in
      ``y = beta/b``: ``y ~ sqrt(2u)/ln2`` for small ``u = lam/rho``,
      ``y ~ log2(u)``-corrected for large ``u``) lands near the root and
      ~6-9 Newton steps polish it to machine precision.
    * **Outer** — Newton on the monotone budget residual
      ``r(lam) = sum_S b_k(lam) - delta`` using the exact derivative
      ``dr/dlam = -sum 1/(rho_k f''(b_k))`` over unclamped clients.

    **Safeguards** (why this cannot diverge): both loops carry bracketing
    bounds.  The inner iteration maintains ``[lo, hi]`` around the root
    (updated from the sign of ``f'(b) - t`` each step) and any Newton
    step that leaves the open bracket, or goes non-finite, is replaced by
    the bisection midpoint — worst case degrades to plain bisection,
    typical case converges quadratically.  Clamped clients are detected
    analytically (``f'(b_min) >= t`` pins ``b_min``; ``f'(b_max) <= t``
    pins ``b_max``) instead of being chased iteratively.  The outer
    iteration starts from the provably valid bracket ``[0, lam_hi]``
    (``lam_hi = max_S rho_k |f'(b_min)|`` forces every ``b_k`` to
    ``b_min``, whose sum is feasible by the ``K b_min <= 1`` validation)
    and applies the same reject-to-midpoint rule.

    The K+1 candidate prefixes share work two ways: the ``b(lam)`` map is
    evaluated on a small log-spaced grid of common levels **once for all
    K clients**, and one masked cumulative sum per level yields every
    prefix's budget residual simultaneously (O(G K) instead of O(G K^2));
    the per-prefix sign pattern seeds each candidate's outer Newton with
    a tight upper bracket and a geometric-mean initial level.  The polish
    iterations then run vectorized over the (K+1, K) lattice — ~6 outer
    x ~9 inner evaluations instead of 42 x 42.

``pallas``
    A fused kernel (``repro.kernels.ocean_p``) that keeps ``rho_sorted``
    resident in VMEM, loops the K+1 candidates *sequentially inside the
    kernel* carrying only the running argmax, and therefore never
    materializes the (K+1, K) candidate intermediates.  On non-TPU
    backends it runs in interpret mode (same math, XLA-compiled), and a
    ``ref.py``-style parity harness pins it to the other backends.

Backends are selected per call (``ocean_p(..., solver="newton")``), per
config (``OceanConfig.solver`` / ``Scenario.solver``), or per sweep
(``GridEngine(..., solver=...)``).  All backends solve the same problem
exactly; ``newton`` and ``pallas`` reproduce ``bisect``'s argmax
selection set on randomized draws (see tests/test_solvers.py) but are
not bit-identical to it — keep ``bisect`` wherever byte-stable figures
matter.
"""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.energy import (
    f_shannon,
    f_shannon_prime,
    f_shannon_second,
)
from repro.obs.spans import trace_span

Array = jax.Array

DEFAULT_SOLVER = "bisect"

# Newton iteration budgets (cut from the 42 x 42 fixed bisection steps).
# Quadratic convergence roughly doubles correct bits per step, so float64
# (53-bit mantissa vs float32's 24) needs a handful of extra polish steps
# and a denser seeding grid to hit machine precision — budgets are
# resolved per dtype via ``newton_iteration_budgets``.  The float32
# values are unchanged from PR 4, keeping that hot path bit-stable.
NEWTON_OUTER_ITERS = 7
NEWTON_INNER_ITERS = 9
NEWTON_GRID_LEVELS = 9
NEWTON_OUTER_ITERS_X64 = 12
NEWTON_INNER_ITERS_X64 = 14
NEWTON_GRID_LEVELS_X64 = 13

# Candidate prefixes the newton backend solves at once: one sublane tile
# of the (K+1, K) lattice per step of its candidate sweep.
CANDIDATE_BLOCK = 8

# Budgets autotuned per (dtype, K-bucket).  Larger prefixes span more
# orders of magnitude in the waterfilling level (lam_hi scales with
# max rho over a wider pool) and the shared seeding grid covers each
# candidate less tightly, so big-K solves need a few extra safeguarded
# steps and denser grids to stay converged.  Bucket 0 is *exactly* the
# legacy dtype-only pair, so every K <= 128 program — all historical
# figures and tests — resolves to bit-identical budgets.
_NEWTON_BUDGET_TABLE: Tuple[
    Tuple[Optional[int], Tuple[int, int, int], Tuple[int, int, int]], ...
] = (
    # (bucket max K, float32 (outer, inner, grid), float64 (outer, inner, grid))
    (
        128,
        (NEWTON_OUTER_ITERS, NEWTON_INNER_ITERS, NEWTON_GRID_LEVELS),
        (NEWTON_OUTER_ITERS_X64, NEWTON_INNER_ITERS_X64, NEWTON_GRID_LEVELS_X64),
    ),
    (4096, (8, 10, 11), (13, 15, 15)),
    (None, (9, 11, 13), (14, 16, 17)),  # open-ended: K > 4096
)


def newton_iteration_budgets(dtype, k: Optional[int] = None) -> Tuple[int, int, int]:
    """(outer, inner, grid) Newton budgets for the given float dtype and K.

    Wider floats need more safeguarded-Newton steps: each rejected step
    degrades to (log-space) bisection, and the x64 tie-boundary studies
    (argmax selections near W*(S_m) == W*(S_{m+1})) only match ``bisect``
    when the waterfilling level is converged to the carry dtype's eps.
    ``k`` is the client-axis length; ``None`` (callers that don't know
    their K) and every K <= 128 resolve to the legacy dtype-only pair —
    bucket boundaries live in ``_NEWTON_BUDGET_TABLE``.
    """
    wide = jnp.dtype(dtype).itemsize >= 8
    for k_max, budget_f32, budget_f64 in _NEWTON_BUDGET_TABLE:
        if k is None or k_max is None or k <= k_max:
            return budget_f64 if wide else budget_f32
    raise AssertionError("unreachable: the last budget bucket is open-ended")


class PrefixSolution(NamedTuple):
    """The winning candidate of the K+1 prefix evaluation (sorted order)."""

    m_star: Array          # scalar int — number of positive-rho clients
    w_star: Array          # scalar     — optimal P3 value W*(S*)
    b_pos_sorted: Array    # (K,) allocation of the winning prefix members
    sel_pos_sorted: Array  # (K,) bool  — winning prefix membership


# fn(rho_sorted, n0, delta, v_eta, radio, outer_iters, inner_iters,
#    *, m_cands=None, rho_hi=None)
# ``m_cands``/``rho_hi`` support the sort-free ``ranking="topm"`` path of
# ``repro.core.selection.ocean_p``: only candidates m in [0, m_cands] are
# evaluated (the rest are provably not the argmax when the winner fits the
# extracted prefix), on a K-length array whose slots beyond the extracted
# top-m hold +inf sentinels; ``rho_hi`` is the order-insensitive global
# ``max(rho)`` the newton backend needs for its shared seeding grid.
PrefixFn = Callable[..., PrefixSolution]
# fn(rho, mask, delta, radio, outer_iters, inner_iters) -> (b, cost)
WaterfillFn = Callable[..., Tuple[Array, Array]]
# fn(rho, n0, delta, v_eta, radio, *, top_m, block_k) on *client-order* rho
# -> (m_star, w_star, b_pos, sel_pos); implemented only by sort-free
# backends that fuse ranking + solve + scatter in one kernel.
TopmFn = Callable[..., Tuple[Array, Array, Array, Array]]


class SolverBackend(NamedTuple):
    name: str
    prefixes: PrefixFn
    waterfill: Optional[WaterfillFn]  # single-mask P4; None => bisect's
    topm: Optional[TopmFn] = None     # fused sort-free path; None => rank+prefixes


_REGISTRY: Dict[str, SolverBackend] = {}


def register_solver(
    name: str,
    prefixes: PrefixFn,
    waterfill: Optional[WaterfillFn] = None,
    topm: Optional[TopmFn] = None,
) -> SolverBackend:
    """Add a solver backend to the registry (overwrites an existing name)."""
    backend = SolverBackend(name, prefixes, waterfill, topm)
    _REGISTRY[name] = backend
    return backend


def available_solvers() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_solver(name: Union[str, SolverBackend, None]) -> SolverBackend:
    """Look up a backend by name; ``None`` resolves to the default."""
    if name is None:
        name = DEFAULT_SOLVER
    if isinstance(name, SolverBackend):
        return name
    if name in _REGISTRY:
        return _REGISTRY[name]
    raise ValueError(
        f"unknown solver backend {name!r}; available: "
        f"{', '.join(available_solvers())} (see repro.core.solvers)"
    )


# --------------------------------------------------------------------------
# bisect — the reference backend (bit-identical to the pre-registry code)
# --------------------------------------------------------------------------
def _prefix_bisect(
    rho_sorted: Array,
    n0: Array,
    delta: Array,
    v_eta: Array,
    radio,
    outer_iters: int,
    inner_iters: int,
    *,
    m_cands: Optional[int] = None,
    rho_hi: Optional[Array] = None,
) -> PrefixSolution:
    """All K+1 prefixes via the double-bisection ``solve_p4``, vmapped.

    This is the original ``ocean_p`` candidate loop moved verbatim behind
    the registry — same ops in the same order, so the default backend
    stays byte-stable.  ``m_cands`` (the sort-free top-m path) clips the
    candidate sweep to m in [0, m_cands]: every per-candidate op runs on
    the same K-length array with identical mask slots, so each surviving
    candidate — and hence the argmax whenever the true winner fits the
    extracted prefix — is bit-identical to the full sweep.
    """
    del rho_hi  # bisect brackets per candidate; no shared seeding grid
    from repro.core.bandwidth import solve_p4

    dtype = rho_sorted.dtype
    K = rho_sorted.shape[0]
    ranks = jnp.arange(K)

    def eval_candidate(m):
        mask = (ranks >= n0) & (ranks < n0 + m)
        feasible = m <= (K - n0)
        b_sorted, cost = solve_p4(
            rho_sorted, mask, delta, radio, outer_iters, inner_iters
        )
        # W*(S) = V*eta*(n0 + m) - energy_scale * cost      (paper Eq. 13/14)
        w = v_eta * (n0 + m).astype(dtype) - radio.energy_scale * cost
        w = jnp.where(feasible, w, -jnp.inf)
        return w, b_sorted, mask

    ms = jnp.arange((K if m_cands is None else m_cands) + 1)
    with trace_span("p4/bisect/candidate_sweep"):
        w_all, b_all, mask_all = jax.vmap(eval_candidate)(ms)

    best = jnp.argmax(w_all)
    return PrefixSolution(
        m_star=ms[best],
        w_star=w_all[best],
        b_pos_sorted=b_all[best],
        sel_pos_sorted=mask_all[best],
    )


# --------------------------------------------------------------------------
# newton — safeguarded Newton waterfilling (see module docstring)
# --------------------------------------------------------------------------
def b_of_lam_newton(
    lam: Array, rho: Array, beta, b_min, b_max, iters: Optional[int] = None
) -> Array:
    """Solve ``rho * f'(b) = -lam`` elementwise, clamped to [b_min, b_max].

    Broadcasting: any (lam, rho) shapes that broadcast together work —
    the prefix solver calls this on a (levels, 1) x (1, K) lattice.
    Safeguarded Newton: bracketed, closed-form-seeded, boundary roots
    detected analytically (never iterated toward).  ``iters=None``
    resolves the dtype-aware inner budget (``newton_iteration_budgets``).
    """
    if iters is None:
        k = jnp.shape(rho)[-1] if jnp.ndim(rho) else None
        iters = newton_iteration_budgets(jnp.result_type(lam, rho), k)[1]
    rho_safe = jnp.maximum(rho, 1e-30)
    t = -lam / rho_safe            # want f'(b) = t  (t <= 0)
    u = lam / rho_safe             # = -t >= 0
    shape = jnp.broadcast_shapes(jnp.shape(t), jnp.shape(b_max))
    dtype = jnp.result_type(t)
    c = jnp.log(jnp.asarray(2.0, dtype))

    # Closed-form seed in y = beta/b (f'(b) = 2^y (1 - y ln2) - 1):
    #   u << 1:  f' ~ -(ln2 y)^2 / 2        =>  y ~ sqrt(2u) / ln2
    #   u >> 1:  2^y (y ln2 - 1) = u - 1    =>  y ~ log2((u-1)/(y0 ln2 - 1))
    y_small = jnp.sqrt(2.0 * u) / c
    y_log = jnp.log2(1.0 + u)
    y_big = jnp.log2(
        jnp.maximum(u - 1.0, 1e-12) / jnp.maximum(c * y_log - 1.0, 1e-12)
    )
    y0 = jnp.maximum(jnp.where(u > 2.0, y_big, y_small), 1e-12)
    b0 = jnp.clip(beta / y0, b_min, b_max)
    b0 = jnp.broadcast_to(b0, shape).astype(dtype)

    lo = jnp.broadcast_to(jnp.asarray(b_min, dtype), shape)
    hi = jnp.broadcast_to(jnp.asarray(b_max, dtype), shape)

    # Boundary roots, detected analytically: f' increasing means
    # f'(b_min) >= t pins b_min and f'(b_max) <= t pins b_max.
    at_min = f_shannon_prime(lo, beta) >= t
    at_max = f_shannon_prime(hi, beta) <= t

    def body(_, carry):
        b, lo, hi = carry
        g = f_shannon_prime(b, beta) - t
        below = g < 0                       # f'(b) < t => root is above b
        lo = jnp.where(below, b, lo)
        hi = jnp.where(below, hi, b)
        bn = b - g / jnp.maximum(f_shannon_second(b, beta), 1e-30)
        ok = (bn >= lo) & (bn <= hi) & jnp.isfinite(bn)
        b = jnp.where(ok, bn, 0.5 * (lo + hi))
        return b, lo, hi

    b, _, _ = jax.lax.fori_loop(0, iters, body, (b0, lo, hi))
    b = jnp.clip(b, b_min, b_max)
    b = jnp.where(at_min, jnp.broadcast_to(jnp.asarray(b_min, dtype), shape), b)
    b = jnp.where(at_max, jnp.broadcast_to(jnp.asarray(b_max, dtype), shape), b)
    return b


def _geo_mid(lo, hi):
    """Log-space bisection fallback for rejected outer-Newton steps.

    The waterfilling level spans orders of magnitude (lam_hi is
    ``max rho |f'(b_min)|``), so arithmetic midpoints converge one bit
    per step from above; the geometric midpoint (floored at ``1e-6 hi``
    when the lower bracket is still 0) is a log-space bisection instead.
    """
    return jnp.sqrt(jnp.maximum(lo, 1e-6 * hi) * jnp.maximum(hi, 1e-30))


def _budget_repair(b, mask, delta, b_min, b_max):
    """Distribute the residual over the headroom so sum(b) == delta exactly.

    Vectorized transcription of the repair step in ``solve_p4`` (leading
    candidate axes broadcast; ``b_max`` may be per-candidate).
    """
    s = jnp.sum(b, axis=-1, keepdims=True)
    residual = delta - s
    headroom = jnp.where(mask, jnp.maximum(b_max - b, 0.0), 0.0)
    slack = jnp.where(mask, jnp.maximum(b - b_min, 0.0), 0.0)
    pos_w = headroom / jnp.maximum(jnp.sum(headroom, axis=-1, keepdims=True), 1e-30)
    neg_w = slack / jnp.maximum(jnp.sum(slack, axis=-1, keepdims=True), 1e-30)
    b = jnp.where(residual >= 0, b + residual * pos_w, b + residual * neg_w)
    return jnp.where(mask, jnp.clip(b, b_min, b_max), 0.0)


def _outer_newton_polish(
    lam0, lo0, hi0, rho, mask, delta, beta, b_min, b_max,
    outer_iters: int, inner_iters: int,
) -> Array:
    """Safeguarded Newton on the budget residual; returns the final b.

    Shared by the single-mask waterfiller and the (K+1)-candidate prefix
    solver: ``rho``/``mask`` are (..., K), the level state ``lam0``/
    ``lo0``/``hi0`` and ``b_max`` carry the leading axes (scalar for one
    mask, (K+1,) for the prefix lattice).  The Pallas kernel inlines the
    same loop (full-array reductions — Pallas carries must keep scalar
    shapes, which the axis=-1 reductions here would promote).
    """
    def body(_, carry):
        lam, lo, hi = carry
        b = b_of_lam_newton(
            lam[..., None], rho, beta, b_min, b_max[..., None], inner_iters
        )
        r = jnp.sum(jnp.where(mask, b, 0.0), axis=-1) - delta
        too_big = r > 0
        lo = jnp.where(too_big, lam, lo)
        hi = jnp.where(too_big, hi, lam)
        interior = mask & (b > b_min) & (b < b_max[..., None])
        dbdlam = -1.0 / (
            jnp.maximum(rho, 1e-30) * jnp.maximum(f_shannon_second(b, beta), 1e-30)
        )
        drdlam = jnp.sum(jnp.where(interior, dbdlam, 0.0), axis=-1)
        lam_n = lam - r / jnp.minimum(drdlam, -1e-30)
        ok = (lam_n >= lo) & (lam_n <= hi) & jnp.isfinite(lam_n)
        lam = jnp.where(ok, lam_n, _geo_mid(lo, hi))
        return lam, lo, hi

    lam, _, _ = jax.lax.fori_loop(0, outer_iters, body, (lam0, lo0, hi0))
    return b_of_lam_newton(
        lam[..., None], rho, beta, b_min, b_max[..., None], inner_iters
    )


def waterfill_newton(
    rho: Array,
    mask: Array,
    delta: Array,
    radio,
    outer_iters: Optional[int] = None,
    inner_iters: Optional[int] = None,
) -> Tuple[Array, Array]:
    """Newton drop-in for ``solve_p4`` on one arbitrary selection mask.

    Same contract as ``repro.core.bandwidth.solve_p4``: returns
    ``(b, cost)`` with ``b == 0`` outside the mask and
    ``sum(b[mask]) == delta``.  ``None`` iteration budgets resolve
    per dtype (wider under ``jax.enable_x64``).
    """
    rho = jnp.asarray(rho)
    d_outer, d_inner, d_grid = newton_iteration_budgets(rho.dtype, rho.shape[-1])
    outer_iters = d_outer if outer_iters is None else outer_iters
    inner_iters = d_inner if inner_iters is None else inner_iters
    mask = jnp.asarray(mask, bool)
    delta = jnp.asarray(delta, rho.dtype)
    beta = radio.beta
    b_min = radio.b_min

    n = jnp.sum(mask)
    has_any = n > 0
    n_safe = jnp.maximum(n, 1)
    b_max = jnp.maximum(delta - (n_safe - 1) * b_min, b_min)

    fp_min = -f_shannon_prime(jnp.asarray(b_min, rho.dtype), beta)
    lam_hi = jnp.max(jnp.where(mask, rho, 0.0)) * fp_min * (1.0 + 1e-6) + 1e-30

    # Log-grid seeding: exact residuals at G shared levels give a valid
    # bracket and a geometric-mean seed (same scheme as the prefix solver,
    # but with this mask's exact b_max, so both bracket ends are trusted).
    G = d_grid
    rho_pos = jnp.where(mask & (rho > 0), rho, jnp.inf)
    rho_min = jnp.min(rho_pos)
    lam_lo_g = jnp.where(
        jnp.isfinite(rho_min),
        rho_min * jnp.maximum(-f_shannon_prime(b_max, beta), 1e-30) * 0.5,
        1e-30,
    )
    lam_lo_g = jnp.clip(lam_lo_g, 1e-30, lam_hi)
    # == jnp.linspace(0, 1, G), built from an integer iota.
    frac = jax.lax.broadcasted_iota(jnp.int32, (G,), 0).astype(rho.dtype) / jnp.asarray(
        G - 1, rho.dtype
    )
    lam_grid = jnp.exp(
        jnp.log(lam_lo_g) * (1.0 - frac) + jnp.log(jnp.maximum(lam_hi, 1e-30)) * frac
    )
    bg = b_of_lam_newton(lam_grid[:, None], rho[None, :], beta, b_min, b_max)
    rg = jnp.sum(jnp.where(mask[None, :], bg, 0.0), axis=1) - delta
    hi_seed = jnp.min(jnp.where(rg <= 0, lam_grid, jnp.inf))
    hi0 = jnp.minimum(jnp.where(jnp.isfinite(hi_seed), hi_seed, lam_hi), lam_hi)
    lo0 = jnp.max(jnp.where(rg > 0, lam_grid, 0.0))
    lam0 = jnp.clip(
        jnp.sqrt(jnp.maximum(lo0, 1e-30) * jnp.maximum(hi0, 1e-30)), 0.0, hi0
    )

    b = _outer_newton_polish(
        lam0, lo0, hi0, rho, mask, delta, beta, b_min, b_max,
        outer_iters, inner_iters,
    )
    b = jnp.where(mask, b, 0.0)
    b = _budget_repair(b, mask, delta, b_min, b_max)
    cost = jnp.sum(jnp.where(mask, rho * f_shannon(jnp.maximum(b, b_min), beta), 0.0))
    b = jnp.where(has_any, b, jnp.zeros_like(b))
    cost = jnp.where(has_any, cost, 0.0)
    return b, cost


def _prefix_newton(
    rho_sorted: Array,
    n0: Array,
    delta: Array,
    v_eta: Array,
    radio,
    outer_iters: int = 0,
    inner_iters: int = 0,
    *,
    m_cands: Optional[int] = None,
    rho_hi: Optional[Array] = None,
) -> PrefixSolution:
    """All K+1 prefixes: shared-grid seeding + blocked vectorized Newton.

    ``outer_iters``/``inner_iters`` are the *bisect* budgets and are
    ignored — Newton's own budgets (`NEWTON_*`) are an order of magnitude
    smaller because each step is superlinear.

    ``m_cands`` clips the candidate lattice to (m_cands+1, K) for the
    sort-free top-m path: the masked prefix sums only read slots the
    extraction filled exactly, and ``rho_hi`` (the order-insensitive
    global ``max(rho)``) reproduces the full sweep's shared-grid anchor
    ``lam_hi_glob`` bit-for-bit — weakly monotone rounding makes
    ``max_m(rho_last_m * c + d) == max(rho) * c + d`` — so every
    surviving candidate matches the full lattice bitwise.

    The candidates are swept ``CANDIDATE_BLOCK`` rows at a time, carrying
    the running argmax (strict ``>``: ties keep the smaller m, as
    ``argmax`` does).  Each row's math only reads its own row, so the
    blocking changes no value; it bounds the live lattice to
    (CANDIDATE_BLOCK, K), which is what lets the fused trajectory kernel
    hold a large-K round on-chip.  Every data-dependent index is a
    one-hot select that picks exactly one value — no gather, scatter or
    ``cumsum``, which the TPU kernel compiler cannot lower.
    """
    del outer_iters, inner_iters
    dtype = rho_sorted.dtype
    n_outer, n_inner, n_grid = newton_iteration_budgets(
        dtype, rho_sorted.shape[0]
    )
    K = rho_sorted.shape[0]
    beta = radio.beta
    b_min = radio.b_min
    M = (K if m_cands is None else m_cands) + 1

    ranks = jax.lax.broadcasted_iota(jnp.int32, (K,), 0)
    pos = ranks >= n0                                        # positive-rho region
    fp_min = -f_shannon_prime(jnp.asarray(b_min, dtype), beta)
    c_hi = fp_min * (1.0 + 1e-6)

    # ---- shared-grid seeding: b(lam) once per level for all K clients;
    # each candidate block contracts it with its prefix masks below.
    G = n_grid
    # Ascending sort => max(rho_sorted) is the last rank, the largest
    # prefix max; the top-m path passes the global max(rho) instead.
    rho_hi = jnp.max(rho_sorted) if rho_hi is None else rho_hi
    lam_hi_glob = rho_hi * c_hi + 1e-30
    rho_pos = jnp.where(pos & (rho_sorted > 0), rho_sorted, jnp.inf)
    rho_min_pos = jnp.min(rho_pos)
    b_cap_glob = jnp.maximum(delta, b_min)
    lam_lo_glob = jnp.where(
        jnp.isfinite(rho_min_pos),
        rho_min_pos * jnp.maximum(-f_shannon_prime(b_cap_glob, beta), 1e-30) * 0.5,
        1e-30,
    )
    lam_lo_glob = jnp.clip(lam_lo_glob, 1e-30, lam_hi_glob)
    # == jnp.linspace(0, 1, G), built from an integer iota.
    frac = jax.lax.broadcasted_iota(jnp.int32, (G, 1), 0).astype(dtype) / jnp.asarray(
        G - 1, dtype
    )
    lam_grid = jnp.exp(
        jnp.log(lam_lo_glob) * (1.0 - frac) + jnp.log(jnp.maximum(lam_hi_glob, 1e-30)) * frac
    )                                                        # (G, 1) ascending
    with trace_span("p4/newton/grid_seed"):
        bg = b_of_lam_newton(
            lam_grid, rho_sorted[None, :], beta, b_min, b_cap_glob
        )                                                    # (G, K) shared
    bg = jnp.where(pos[None, :], bg, 0.0)

    CB = CANDIDATE_BLOCK
    rows = jax.lax.broadcasted_iota(jnp.int32, (CB, K), 0)
    ranks2 = jax.lax.broadcasted_iota(jnp.int32, (CB, K), 1)
    rho_b = rho_sorted[None, :]

    def candidate_block(jb, carry):
        best_w, best_m, best_b = carry
        ms2 = rows + jb * CB
        ms = ms2[:, 0]
        mask = (ranks2 >= n0) & (ranks2 < n0 + ms2)          # (CB, K)
        feasible = (ms <= (K - n0)) & (ms < M)
        b_max = jnp.maximum(
            delta - (jnp.maximum(ms, 1) - 1).astype(dtype) * b_min, b_min
        )
        # Ascending sort => the prefix max rho is its last member.
        last = jnp.clip(n0 + ms2 - 1, 0, K - 1)
        rho_last = jnp.where(
            ms >= 1,
            jnp.sum(jnp.where(ranks2 == last, rho_b, 0.0), axis=1),
            0.0,
        )
        lam_hi = rho_last * c_hi + 1e-30                     # valid upper bracket
        # r_grid[g, m] = level g's b summed over candidate m's prefix.
        r_grid = jax.lax.dot_general(
            bg,
            mask.astype(dtype),
            (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
        ) - delta                                            # (G, CB)
        # The grid uses the *global* cap (>= each candidate's), so r_grid
        # over-estimates: "r <= 0" certifies a valid upper bracket, "r > 0"
        # only seeds — the polish re-brackets from exact evaluations.
        nonpos = r_grid <= 0
        hi_seed = jnp.min(jnp.where(nonpos, lam_grid, jnp.inf), axis=0)
        hi0 = jnp.minimum(jnp.where(jnp.isfinite(hi_seed), hi_seed, lam_hi), lam_hi)
        lo_seed = jnp.max(jnp.where(~nonpos, lam_grid, 0.0), axis=0)
        lam0 = jnp.clip(
            jnp.sqrt(jnp.maximum(lo_seed, 1e-30) * jnp.maximum(hi0, 1e-30)),
            0.0,
            hi0,
        )
        with trace_span("p4/newton/polish"):
            b = _outer_newton_polish(
                lam0, jnp.zeros_like(lam0), hi0, rho_b, mask, delta, beta,
                b_min, b_max, n_outer, n_inner,
            )
        b = jnp.where(mask, b, 0.0)
        b = _budget_repair(b, mask, delta, b_min, b_max[:, None])
        cost = jnp.sum(
            jnp.where(mask, rho_b * f_shannon(jnp.maximum(b, b_min), beta), 0.0),
            axis=1,
        )
        has_any = ms > 0
        b = jnp.where(has_any[:, None], b, 0.0)
        cost = jnp.where(has_any, cost, 0.0)

        w = v_eta * (n0.astype(dtype) + ms.astype(dtype)) - radio.energy_scale * cost
        w = jnp.where(feasible, w, -jnp.inf)
        j = jnp.argmax(w).astype(jnp.int32)
        win = rows == j                                      # one-hot row
        w_j = jnp.max(jnp.where(ms == jb * CB + j, w, -jnp.inf))
        better = w_j > best_w
        return (
            jnp.where(better, w_j, best_w),
            jnp.where(better, jb * CB + j, best_m),
            jnp.where(better, jnp.sum(jnp.where(win, b, 0.0), axis=0), best_b),
        )

    w_star, m_star, b_best = jax.lax.fori_loop(
        0,
        -(-M // CB),
        candidate_block,
        (
            jnp.asarray(-jnp.inf, dtype),
            jnp.zeros((), jnp.int32),
            jnp.zeros((K,), dtype),
        ),
    )
    return PrefixSolution(
        m_star=m_star,
        w_star=w_star,
        b_pos_sorted=b_best,
        sel_pos_sorted=pos & (ranks < n0 + m_star),
    )


# --------------------------------------------------------------------------
# pallas — fused kernel backend (repro.kernels.ocean_p)
# --------------------------------------------------------------------------
def _prefix_pallas(
    rho_sorted: Array,
    n0: Array,
    delta: Array,
    v_eta: Array,
    radio,
    outer_iters: int = 0,
    inner_iters: int = 0,
    *,
    m_cands: Optional[int] = None,
    rho_hi: Optional[Array] = None,
) -> PrefixSolution:
    del outer_iters, inner_iters, rho_hi
    from repro.kernels.ocean_p import ocean_p_prefixes_fused

    return ocean_p_prefixes_fused(
        rho_sorted, n0, delta, v_eta, radio, n_cands=m_cands
    )


# --------------------------------------------------------------------------
# pallas_tiled — fully sort-free fused kernel (repro.kernels.ocean_p)
# --------------------------------------------------------------------------
def _prefix_pallas_tiled(*args, **kwargs) -> PrefixSolution:
    raise ValueError(
        "solver 'pallas_tiled' is sort-free: it fuses top-m extraction, "
        "the candidate solve and the client-order scatter in one kernel "
        "and never sees a rho-sorted array; run it with ranking='topm' "
        "(OceanConfig/Scenario ranking field or ocean_p(ranking=...))"
    )


def _topm_pallas_tiled(
    rho: Array,
    n0: Array,
    delta: Array,
    v_eta: Array,
    radio,
    *,
    top_m: int,
    block_k: int,
) -> Tuple[Array, Array, Array, Array]:
    from repro.kernels.ocean_p import ocean_p_topm_fused

    return ocean_p_topm_fused(
        rho, n0, delta, v_eta, radio, top_m=top_m, block_k=block_k
    )


register_solver("bisect", _prefix_bisect, waterfill=None)
register_solver("newton", _prefix_newton, waterfill=waterfill_newton)
# The fused kernel covers the prefix lattice; single-mask P4 calls reuse
# the Newton waterfiller (same math, no candidate axis to fuse over).
register_solver("pallas", _prefix_pallas, waterfill=waterfill_newton)
# Client-tiled sort-free kernel: on-chip top-m extraction (BLOCK_K
# two-stage reductions, no argsort, no K-length gather), a compact
# (top_m,)-shaped candidate solve, and a blockwise one-hot scatter back
# to client order.  Requires ranking="topm".
register_solver(
    "pallas_tiled",
    _prefix_pallas_tiled,
    waterfill=waterfill_newton,
    topm=_topm_pallas_tiled,
)
