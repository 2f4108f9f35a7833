"""P4 — the per-selection-set convex bandwidth-allocation problem (paper §V-B).

Given a selection set S (encoded as a boolean mask over clients with
priority rho_k = q_k / h_k^2 > 0) and a bandwidth budget ``delta``:

    minimize    sum_k rho_k * f(b_k)          (equivalently maximize P4)
    subject to  sum_k b_k = delta,   b_k >= b_min

with f(b) = b (2^{beta/b} - 1), which is decreasing and convex (Lemma 1),
so the problem is convex.  The KKT conditions give, for interior clients,

    rho_k * f'(b_k) = -lam   (lam >= 0)

with f' negative and strictly increasing, so b_k(lam) is found by an inner
bisection on f' and the waterfilling level lam by an outer bisection on the
budget residual.  Both loops are fixed-iteration ``lax.fori_loop``s so the
whole solver jits, vmaps (over candidate selection sets — OCEAN-P) and
differentiates-nowhere (it is piecewise constant in integers; we never need
gradients through it).

Layout of the inner loop.  The inner bisection is 42 dependent steps inside
each of the 42 outer ones, so its layout sets the solver's cost.  A TPU
vector register is 8 sublanes by 128 lanes, and the minor axis of an array
goes on the lanes.  With K < 128 clients the (K+1, K) candidate lattice,
and each vmapped copy of it (the grid's scenarios and seeds), would sit in
mostly padded tiles: at (S, N) = (3, 10), K = 10, a hundred (4, 128) tiles
hold 3300 values.  So below 128 clients the inner loop runs on a
lane-dense ``(rows, 128)`` slab whose ``vmap`` rule folds every batch
level into its elements (``_slab_bisection``): at that size, 26 rows in
four registers.  The slab is padded to whole rows only (fewer than 128 extra
elements); the TPU rounds the rows up to (8, 128) tiles itself, and a
backend without tiling, such as the CPU, carries no more than that.  At
K >= 128 the loop runs in the lattice's own shape: a v5e compile of the
(3, 10)-vmapped lattice puts the clients on the lanes there
(``{3,2,1,0:T(8,128)}``), so 129 of 136 sublanes carry values at K = 128
and 200 of 256 lanes at K = 200 (``tests/test_tpu_compile.py``), and a
slab would add relayouts for little.  The outer bisection, its masked sum
over the clients and the budget repair keep the lattice shape.

This replaces the CVX calls of the paper with an accelerator-native exact
solver (see DESIGN.md §3, hardware adaptation).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.energy import RadioParams, f_shannon, f_shannon_prime
from repro.obs.spans import trace_span

Array = jax.Array


LANES = 128  # lanes of a TPU vector register: the slab's minor width
# What the slab's padding lanes hold: target, lo, hi, beta.  f'(1) at
# beta = 1 is finite, so they stay finite (``jax_debug_nans``).
_SLAB_FILL = (-1.0, 1.0, 1.0, 1.0)


def _bisect_steps(target: Array, lo: Array, hi: Array, beta, iters: int) -> Array:
    """``iters`` halvings of ``[lo, hi]`` toward f'(b) = target, elementwise."""

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        below = f_shannon_prime(mid, beta) < target  # need larger b
        lo = jnp.where(below, mid, lo)
        hi = jnp.where(below, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return 0.5 * (lo + hi)


def _slab_bisection(iters: int):
    """``_bisect_steps`` over every element at once, on a ``(rows, 128)`` slab.

    The four operands share one shape; ``beta`` is per element.  Under
    ``vmap`` the batching rule broadcasts the unbatched operands and folds
    the new axis into the element axis, so each vmap level (OCEAN's
    candidates, the grid's scenarios and seeds) adds elements to the one
    slab instead of a padded tile dimension.  The body flattens, pads to
    whole 128-lane rows with ``_SLAB_FILL``, runs the loop and cuts the
    padding off again: the relayout happens once per call, never inside
    the loop.
    """

    @jax.custom_batching.custom_vmap
    def bisect(target, lo, hi, beta):
        shape, n = target.shape, target.size
        pad = -n % LANES

        def slab(x, fill):
            return jnp.pad(x.reshape(-1), (0, pad), constant_values=fill).reshape(
                -1, LANES
            )

        with trace_span("p4/bisect/inner_slab"):
            b = _bisect_steps(*map(slab, (target, lo, hi, beta), _SLAB_FILL), iters)
        return b.reshape(-1)[:n].reshape(shape)

    @bisect.def_vmap
    def _fold(axis_size, in_batched, *args):
        args = [
            a if batched else jnp.broadcast_to(a, (axis_size,) + a.shape)
            for a, batched in zip(args, in_batched)
        ]
        return bisect(*args), True

    return bisect


def _b_of_lam(
    lam: Array, rho: Array, beta: float, b_min: float, b_max: Array, iters: int
) -> Array:
    """Solve rho_k f'(b) = -lam for each k by bisection; clamp to [b_min, b_max].

    f' is strictly increasing, so we bisect on b.  Where rho_k == 0 the
    client has no energy cost and the KKT stationarity never binds; callers
    mask those out (they sit in S0 with b = b_min).

    Layout: with fewer clients than a vector register has lanes
    (K < 128), the TPU's tiling pads the lattice — and, under ``vmap``,
    every batched copy of it — to mostly empty tiles, so the 42-step loop
    would push mostly padding through each step.  There it runs on a
    lane-dense slab (``_slab_bisection``).  At K >= 128 the clients fill
    the lanes (the module docstring gives the compile reading) and the
    loop runs in the lattice's own shape.  Both run the same operations on
    each element in the same order, so ``b`` has the same bits either way.
    """
    target = -lam / jnp.maximum(rho, 1e-30)  # want f'(b) = target (<0)

    lo = jnp.full_like(rho, b_min)
    hi = jnp.broadcast_to(b_max, rho.shape).astype(rho.dtype)
    if rho.shape[-1] < LANES:
        beta = jnp.broadcast_to(
            jnp.asarray(beta, jnp.result_type(beta, lo)), rho.shape
        )
        return _slab_bisection(iters)(target, lo, hi, beta)
    return _bisect_steps(target, lo, hi, beta, iters)


def solve_p4(
    rho: Array,
    mask: Array,
    delta: Array,
    radio: RadioParams,
    outer_iters: int = 42,
    inner_iters: int = 42,
    method: str = "bisect",
) -> Tuple[Array, Array]:
    """Optimal bandwidth split of ``delta`` among ``mask``-ed clients.

    Args:
      rho:   (K,) priorities q_k / h_k^2 (>0 for genuine P4 members).
      mask:  (K,) bool — membership of S - S0.
      delta: scalar — total ratio to distribute (= 1 - |S0| * b_min).
      radio: physics.
      method: solver backend name (``repro.core.solvers``).  ``bisect``
            (default) is this module's bit-stable double bisection; any
            other registered backend with a single-mask waterfiller
            (``newton``, ``pallas``) dispatches to it.  ``outer_iters``/
            ``inner_iters`` are bisect step counts and apply only to
            ``bisect`` — other methods converge superlinearly and use
            their own budgets (``repro.core.solvers.NEWTON_*``).

    Returns:
      b:    (K,) allocation, 0 outside the mask, sum(b[mask]) == delta.
      cost: scalar — sum_k rho_k f(b_k) over the mask (the energy-weighted
            objective P4 minimizes, *without* the N0*tau*B prefactor).
    """
    if method != "bisect":
        from repro.core.solvers import get_solver, waterfill_newton

        backend = get_solver(method)  # fail fast on unknown names
        waterfill = backend.waterfill or waterfill_newton
        return waterfill(rho, mask, delta, radio)
    rho = jnp.asarray(rho)
    mask = jnp.asarray(mask, bool)
    delta = jnp.asarray(delta, rho.dtype)
    beta = radio.beta
    b_min = radio.b_min

    n = jnp.sum(mask)
    has_any = n > 0
    n_safe = jnp.maximum(n, 1)
    # No member may exceed delta - (n-1) * b_min.
    b_max = jnp.maximum(delta - (n_safe - 1) * b_min, b_min)

    # --- outer bisection on the waterfilling level lam -------------------
    # lam = 0          => every b at its unconstrained max (sum too big)
    # lam = lam_hi     => every b at b_min (sum = n*b_min <= delta)
    fp_min = -f_shannon_prime(jnp.asarray(b_min, rho.dtype), beta)  # > 0
    lam_hi = jnp.max(jnp.where(mask, rho, 0.0)) * fp_min * (1.0 + 1e-6) + 1e-30

    def sum_b(lam):
        b = _b_of_lam(lam, rho, beta, b_min, b_max, inner_iters)
        return jnp.sum(jnp.where(mask, b, 0.0)), b

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        s, _ = sum_b(mid)
        too_big = s > delta  # allocation too generous -> raise lam
        lo = jnp.where(too_big, mid, lo)
        hi = jnp.where(too_big, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(
        0, outer_iters, body, (jnp.zeros_like(lam_hi), lam_hi)
    )
    lam = 0.5 * (lo + hi)
    _, b = sum_b(lam)
    b = jnp.where(mask, b, 0.0)

    # Exact budget repair: bisection leaves a tiny residual; distribute it
    # proportionally over the headroom above b_min so sum(b) == delta and
    # b >= b_min stay exact.  (For uniform-rho sets this is a no-op.)
    s = jnp.sum(b)
    residual = delta - s
    headroom = jnp.where(mask, jnp.maximum(b_max - b, 0.0), 0.0)
    slack = jnp.where(mask, jnp.maximum(b - b_min, 0.0), 0.0)
    pos_w = headroom / jnp.maximum(jnp.sum(headroom), 1e-30)
    neg_w = slack / jnp.maximum(jnp.sum(slack), 1e-30)
    b = jnp.where(
        residual >= 0, b + residual * pos_w, b + residual * neg_w
    )
    b = jnp.where(mask, jnp.clip(b, b_min, b_max), 0.0)

    cost = jnp.sum(jnp.where(mask, rho * f_shannon(jnp.maximum(b, b_min), beta), 0.0))
    b = jnp.where(has_any, b, jnp.zeros_like(b))
    cost = jnp.where(has_any, cost, 0.0)
    return b, cost


def p4_objective(
    rho: Array, b: Array, mask: Array, v_eta: Array, radio: RadioParams
) -> Array:
    """W*(S) contribution of S - S0:  sum_k (V*eta - rho_k N0 tau B f(b_k))."""
    per_client = v_eta - rho * radio.energy_scale * f_shannon(
        jnp.maximum(b, radio.b_min), radio.beta
    )
    return jnp.sum(jnp.where(jnp.asarray(mask, bool), per_client, 0.0))
