"""Fused OCEAN-P prefix solver (Pallas) — the per-round P3 hot loop.

One kernel invocation solves the whole candidate lattice of the paper's
Theorem-1 structure: the K+1 prefixes of the rho-sorted client order,
each a convex P4 waterfilling problem.  The XLA backends (``bisect``,
``newton`` in ``repro.core.solvers``) vmap the candidates, materializing
(K+1, K) intermediates in HBM for every bisection/Newton step; this
kernel instead

  * keeps ``rho_sorted`` (and all per-candidate state) resident in VMEM,
  * iterates the K+1 candidates *sequentially* in an on-chip loop,
    carrying only the running argmax (best W, best m, best allocation) —
    the (K+1, K) lattice is never materialized anywhere,
  * reuses the exact safeguarded-Newton math of the ``newton`` backend
    (``repro.core.solvers.b_of_lam_newton``) inside the kernel, so the
    two backends agree to float32 precision by construction.

Scalars (n0, delta, V*eta, beta, b_min, energy_scale) arrive as one SMEM
row so a traced per-round radio pytree (``repro.env.radio``) lowers
straight into the kernel, and the winner's (W*, m*) leave through an SMEM
row.  On the CPU backend the kernel runs in interpret mode (same trace,
compiled by XLA), which is how tests and CI run it; on a TPU it is
compiled by Mosaic, with no interpret fallback.  Parity is pinned
against ``repro.kernels.ref``'s pure-jnp oracle in tests/test_solvers.py;
tests/test_tpu_compile.py compiles both kernels for a described v5e chip,
and ``chip_smoke.py`` runs them on one.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _default_interpret() -> bool:
    # Only the CPU interprets; on a TPU a kernel compiles or the call fails.
    return jax.default_backend() == "cpu"


def _iota_f32(shape, dim):
    # Mosaic's iota is integer-only; the f32 convert is exact for < 2^24.
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim).astype(jnp.float32)


def _fused_kernel(
    scal_ref,
    rho_ref,
    b_ref,
    wm_ref,
    *,
    K: int,
    outer: int,
    inner: int,
    n_cands: Optional[int] = None,
):
    from repro.core.solvers import _budget_repair, _geo_mid, b_of_lam_newton
    from repro.core.energy import f_shannon, f_shannon_prime, f_shannon_second

    n0 = scal_ref[0, 0]
    delta = scal_ref[0, 1]
    v_eta = scal_ref[0, 2]
    beta = scal_ref[0, 3]
    b_min = scal_ref[0, 4]
    scale = scal_ref[0, 5]

    rho = rho_ref[...]                                           # (1, K) resident
    ranks = _iota_f32((1, K), 1)
    pos = ranks >= n0
    kf = jnp.float32(K)
    fp_min = -f_shannon_prime(b_min, beta)                       # > 0 scalar

    def candidate(m, carry):
        best_w, best_m, best_b = carry
        mf = m.astype(jnp.float32)
        mask = pos & (ranks < n0 + mf)
        b_max = jnp.maximum(delta - jnp.maximum(mf - 1.0, 0.0) * b_min, b_min)
        rho_max = jnp.max(jnp.where(mask, rho, 0.0))
        lam_hi = rho_max * fp_min * (1.0 + 1e-6) + 1e-30
        # Seed at the KKT level of an equal split: the true lam lies between
        # min and max over the prefix of rho_k |f'(delta/m)|; start at their
        # geometric mean and let the bracketed Newton polish.
        rho_min = jnp.min(jnp.where(mask, rho, jnp.inf))
        rho_min = jnp.where(jnp.isfinite(rho_min), rho_min, 0.0)
        b_eq = jnp.clip(delta / jnp.maximum(mf, 1.0), b_min, b_max)
        lam0 = jnp.clip(
            jnp.sqrt(jnp.maximum(rho_min * rho_max, 1e-30))
            * jnp.maximum(-f_shannon_prime(b_eq, beta), 1e-30),
            0.0,
            lam_hi,
        )

        def outer_body(_, oc):
            lam, lo, hi = oc
            b = b_of_lam_newton(lam, rho, beta, b_min, b_max, inner)
            r = jnp.sum(jnp.where(mask, b, 0.0)) - delta
            too_big = r > 0
            lo = jnp.where(too_big, lam, lo)
            hi = jnp.where(too_big, hi, lam)
            interior = mask & (b > b_min) & (b < b_max)
            dbdlam = -1.0 / (
                jnp.maximum(rho, 1e-30)
                * jnp.maximum(f_shannon_second(b, beta), 1e-30)
            )
            drdlam = jnp.sum(jnp.where(interior, dbdlam, 0.0))
            lam_n = lam - r / jnp.minimum(drdlam, -1e-30)
            ok = (lam_n >= lo) & (lam_n <= hi) & jnp.isfinite(lam_n)
            lam = jnp.where(ok, lam_n, _geo_mid(lo, hi))
            return lam, lo, hi

        lam, _, _ = jax.lax.fori_loop(
            0, outer, outer_body, (lam0, jnp.zeros_like(lam_hi), lam_hi)
        )
        b = b_of_lam_newton(lam, rho, beta, b_min, b_max, inner)
        b = jnp.where(mask, b, 0.0)
        b = _budget_repair(b, mask, delta, b_min, b_max)
        cost = jnp.sum(jnp.where(mask, rho * f_shannon(jnp.maximum(b, b_min), beta), 0.0))
        has_any = mf > 0
        b = jnp.where(has_any, b, jnp.zeros_like(b))
        cost = jnp.where(has_any, cost, 0.0)

        w = v_eta * (n0 + mf) - scale * cost
        w = jnp.where(mf <= kf - n0, w, NEG_INF)

        better = w > best_w                  # strict: ties keep the smaller m
        best_b = jnp.where(better, b, best_b)
        return (
            jnp.where(better, w, best_w),
            jnp.where(better, mf, best_m),
            best_b,
        )

    # ranking="topm" clips the sequential sweep to the extracted prefix:
    # each candidate's ops are unchanged (same (1, K) shapes, same masked
    # slots), so the clipped sweep is bit-identical per candidate.
    best_w, best_m, best_b = jax.lax.fori_loop(
        0,
        (K if n_cands is None else n_cands) + 1,
        candidate,
        (jnp.float32(NEG_INF), jnp.float32(0.0), jnp.zeros((1, K), jnp.float32)),
    )
    b_ref[...] = best_b
    wm_ref[0, 0] = best_w
    wm_ref[0, 1] = best_m


def ocean_p_prefixes_fused(
    rho_sorted: jax.Array,
    n0: jax.Array,
    delta: jax.Array,
    v_eta: jax.Array,
    radio,
    *,
    outer_iters: int = 12,
    inner_iters: int = 9,
    n_cands: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Backend-contract wrapper: solve all K+1 prefixes, return the winner.

    Returns a ``repro.core.solvers.PrefixSolution``.  ``interpret=None``
    interprets on the CPU backend and compiles everywhere else.  ``n_cands``
    (the sort-free top-m path) clips the sequential candidate sweep to
    m in [0, n_cands].
    """
    from repro.core.solvers import PrefixSolution

    if interpret is None:
        interpret = _default_interpret()
    K = rho_sorted.shape[0]
    dtype = rho_sorted.dtype

    scal = jnp.stack(
        [
            jnp.asarray(n0, jnp.float32),
            jnp.asarray(delta, jnp.float32),
            jnp.asarray(v_eta, jnp.float32),
            jnp.asarray(radio.beta, jnp.float32),
            jnp.asarray(radio.b_min, jnp.float32),
            jnp.asarray(radio.energy_scale, jnp.float32),
            jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.float32),
        ]
    ).reshape(1, 8)
    rho2d = rho_sorted.astype(jnp.float32).reshape(1, K)

    kernel = functools.partial(
        _fused_kernel, K=K, outer=outer_iters, inner=inner_iters, n_cands=n_cands
    )
    if interpret:
        in_specs = out_specs = None
    else:  # TPU: scalars in SMEM, vectors in VMEM
        from jax.experimental.pallas import tpu as pltpu

        in_specs = [
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ]
        out_specs = (
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        )
    call_kwargs = {}
    if in_specs is not None:
        call_kwargs = dict(in_specs=in_specs, out_specs=out_specs)
    b2d, wm = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((1, K), jnp.float32),
            jax.ShapeDtypeStruct((1, 2), jnp.float32),
        ),
        interpret=interpret,
        **call_kwargs,
    )(scal, rho2d)

    m_star = jnp.round(wm[0, 1]).astype(jnp.int32)
    ranks = jnp.arange(K)
    sel = (ranks >= n0) & (ranks < n0 + m_star)
    return PrefixSolution(
        m_star=m_star,
        w_star=wm[0, 0].astype(dtype),
        b_pos_sorted=b2d[0].astype(dtype),
        sel_pos_sorted=sel,
    )


# --------------------------------------------------------------------------
# pallas_tiled — the sort-free, client-tiled kernel (ranking="topm")
# --------------------------------------------------------------------------
def _topm_kernel(
    scal_ref,
    rho_ref,
    b_ref,
    wm_ref,
    *,
    K: int,
    K_pad: int,
    block_k: int,
    top_m: int,
    outer: int,
    inner: int,
):
    """Extraction + compact candidate solve + scatter, all on-chip.

    Three phases, none of which sorts or gathers across the K axis:

    1. **Extraction** — ``top_m`` rounds of two-stage min-reduction over
       the (nb, BLOCK_K) tile view: per-block running minima, then a
       cross-block combine; the argmin is an index-min over a masked
       iota (first occurrence == stable-sort tie order).  min/argmin are
       order-insensitive, so the tiling is bit-neutral.
    2. **Compact solve** — the sequential candidate sweep of
       ``_fused_kernel``, but on the (1, top_m) extracted values instead
       of (1, K): per-round cost drops from O(K^2 iters) to
       O(top_m K + top_m^2 iters).
    3. **Scatter** — the winning (1, top_m) allocation goes back to
       client order one BLOCK_K tile at a time via one-hot compares
       against the extracted indices (f32-exact for K < 2^24).
    """
    from repro.core.solvers import _budget_repair, _geo_mid, b_of_lam_newton
    from repro.core.energy import f_shannon, f_shannon_prime, f_shannon_second

    n0 = scal_ref[0, 0]
    delta = scal_ref[0, 1]
    v_eta = scal_ref[0, 2]
    beta = scal_ref[0, 3]
    b_min = scal_ref[0, 4]
    scale = scal_ref[0, 5]

    kf = jnp.float32(K)
    nb = K_pad // block_k
    inf = jnp.float32(jnp.inf)
    fp_min = -f_shannon_prime(b_min, beta)

    # ---- phase 1: tiled top-m extraction --------------------------------
    work0 = rho_ref[...].reshape(nb, block_k)
    col = _iota_f32((nb, block_k), 1)
    row = _iota_f32((nb, block_k), 0)
    gidx2d = row * jnp.float32(block_k) + col     # global client index

    slot_iota = jax.lax.broadcasted_iota(jnp.int32, (1, top_m), 1)

    def extract(j, carry):
        work, vals, idxs = carry
        block_min = jnp.min(work, axis=1)         # (nb,) per-block running min
        gmin = jnp.min(block_min)                 # cross-block combine
        # first occurrence of the min — an index-min, not a gather
        gidx = jnp.min(jnp.where(work == gmin, gidx2d, jnp.float32(K_pad)))
        work = jnp.where(gidx2d == gidx, inf, work)
        # Masked lane select, not ``.at[0, j].set``: a dynamic lane
        # update lowers to a scatter, which Mosaic has no rule for.
        slot = slot_iota == j
        return (
            work,
            jnp.where(slot, gmin, vals),
            jnp.where(slot, gidx, idxs),
        )

    _, vals, idxs = jax.lax.fori_loop(
        0,
        top_m,
        extract,
        (
            work0,
            jnp.full((1, top_m), inf, jnp.float32),
            jnp.zeros((1, top_m), jnp.float32),
        ),
    )

    # ---- phase 2: compact candidate sweep over the extracted prefix -----
    jcol = _iota_f32((1, top_m), 1)

    def candidate(m, carry):
        best_w, best_m, best_b = carry
        mf = m.astype(jnp.float32)
        mask = jcol < mf
        b_max = jnp.maximum(delta - jnp.maximum(mf - 1.0, 0.0) * b_min, b_min)
        rho_max = jnp.max(jnp.where(mask, vals, 0.0))
        lam_hi = rho_max * fp_min * (1.0 + 1e-6) + 1e-30
        rho_min = jnp.min(jnp.where(mask, vals, inf))
        rho_min = jnp.where(jnp.isfinite(rho_min), rho_min, 0.0)
        b_eq = jnp.clip(delta / jnp.maximum(mf, 1.0), b_min, b_max)
        lam0 = jnp.clip(
            jnp.sqrt(jnp.maximum(rho_min * rho_max, 1e-30))
            * jnp.maximum(-f_shannon_prime(b_eq, beta), 1e-30),
            0.0,
            lam_hi,
        )

        def outer_body(_, oc):
            lam, lo, hi = oc
            b = b_of_lam_newton(lam, vals, beta, b_min, b_max, inner)
            r = jnp.sum(jnp.where(mask, b, 0.0)) - delta
            too_big = r > 0
            lo = jnp.where(too_big, lam, lo)
            hi = jnp.where(too_big, hi, lam)
            interior = mask & (b > b_min) & (b < b_max)
            dbdlam = -1.0 / (
                jnp.maximum(vals, 1e-30)
                * jnp.maximum(f_shannon_second(b, beta), 1e-30)
            )
            drdlam = jnp.sum(jnp.where(interior, dbdlam, 0.0))
            lam_n = lam - r / jnp.minimum(drdlam, -1e-30)
            ok = (lam_n >= lo) & (lam_n <= hi) & jnp.isfinite(lam_n)
            lam = jnp.where(ok, lam_n, _geo_mid(lo, hi))
            return lam, lo, hi

        lam, _, _ = jax.lax.fori_loop(
            0, outer, outer_body, (lam0, jnp.zeros_like(lam_hi), lam_hi)
        )
        b = b_of_lam_newton(lam, vals, beta, b_min, b_max, inner)
        b = jnp.where(mask, b, 0.0)
        b = _budget_repair(b, mask, delta, b_min, b_max)
        cost = jnp.sum(
            jnp.where(mask, vals * f_shannon(jnp.maximum(b, b_min), beta), 0.0)
        )
        has_any = mf > 0
        b = jnp.where(has_any, b, jnp.zeros_like(b))
        cost = jnp.where(has_any, cost, 0.0)

        w = v_eta * (n0 + mf) - scale * cost
        # Exhausted extraction slots carry +inf values: any candidate that
        # would admit one has infinite cost (or NaN through the inf/inf
        # seed) — both are non-answers, masked alongside infeasibility.
        w = jnp.where((mf <= kf - n0) & jnp.isfinite(w), w, NEG_INF)

        better = w > best_w                  # strict: ties keep the smaller m
        best_b = jnp.where(better, b, best_b)
        return (
            jnp.where(better, w, best_w),
            jnp.where(better, mf, best_m),
            best_b,
        )

    best_w, best_m, best_b = jax.lax.fori_loop(
        0,
        top_m + 1,
        candidate,
        (jnp.float32(NEG_INF), jnp.float32(0.0), jnp.zeros((1, top_m), jnp.float32)),
    )

    # ---- phase 3: blockwise one-hot scatter back to client order --------
    sel = (jcol < best_m) & jnp.isfinite(vals)    # (1, top_m)
    b_sel = jnp.where(sel, best_b, 0.0)
    idx_col = idxs.reshape(top_m, 1)
    b_col = b_sel.reshape(top_m, 1)

    def scatter(ib, _):
        base = (ib * block_k).astype(jnp.float32)
        tile_iota = _iota_f32((1, block_k), 1) + base
        onehot = idx_col == tile_iota              # (top_m, block_k)
        tile = jnp.sum(
            jnp.where(onehot, b_col, 0.0), axis=0, keepdims=True
        )                                          # (1, block_k)
        b_ref[pl.ds(0, 1), pl.ds(ib * block_k, block_k)] = tile
        return 0

    jax.lax.fori_loop(0, nb, scatter, 0)
    wm_ref[0, 0] = best_w
    wm_ref[0, 1] = best_m


def ocean_p_topm_fused(
    rho: jax.Array,
    n0: jax.Array,
    delta: jax.Array,
    v_eta: jax.Array,
    radio,
    *,
    top_m: int,
    block_k: int = 128,
    outer_iters: int = 12,
    inner_iters: int = 9,
    interpret: Optional[bool] = None,
):
    """Sort-free fused P3 solve on *client-order* rho (no argsort anywhere).

    The ``pallas_tiled`` backend: pads the client axis to a BLOCK_K
    multiple with +inf sentinels (never extracted, never selected) and
    runs ``_topm_kernel``.  Returns ``(m_star, w_star, b_pos, sel_pos)``
    in client order — the ``SolverBackend.topm`` contract.  Parity is
    oracle-pinned (selection-equal, allocation-allclose) against the
    bisect path rather than bitwise: the compact (top_m,)-shaped solve
    necessarily reduces through different trees than a (K,)-shaped one.
    """
    if interpret is None:
        interpret = _default_interpret()
    K = rho.shape[0]
    dtype = rho.dtype
    if top_m < 1:
        raise ValueError(f"top_m={top_m} must be >= 1")
    K_pad = -(-K // block_k) * block_k
    if K_pad >= 1 << 24:
        raise ValueError(
            f"K={K} (padded {K_pad}) exceeds the f32-exact index range "
            f"(2^24) of the tiled kernel's on-chip client indices"
        )

    from repro.core.selection import _RHO_ZERO_TOL

    work = jnp.where(rho > _RHO_ZERO_TOL, rho.astype(jnp.float32), jnp.inf)
    work = jnp.pad(work, (0, K_pad - K), constant_values=jnp.inf)
    rho2d = work.reshape(1, K_pad)

    scal = jnp.stack(
        [
            jnp.asarray(n0, jnp.float32),
            jnp.asarray(delta, jnp.float32),
            jnp.asarray(v_eta, jnp.float32),
            jnp.asarray(radio.beta, jnp.float32),
            jnp.asarray(radio.b_min, jnp.float32),
            jnp.asarray(radio.energy_scale, jnp.float32),
            jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.float32),
        ]
    ).reshape(1, 8)

    kernel = functools.partial(
        _topm_kernel,
        K=K,
        K_pad=K_pad,
        block_k=block_k,
        top_m=top_m,
        outer=outer_iters,
        inner=inner_iters,
    )
    if interpret:
        call_kwargs = {}
    else:  # TPU: scalars in SMEM, vectors in VMEM
        from jax.experimental.pallas import tpu as pltpu

        call_kwargs = dict(
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
            ],
            out_specs=(
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ),
        )
    b2d, wm = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((1, K_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, 2), jnp.float32),
        ),
        interpret=interpret,
        **call_kwargs,
    )(scal, rho2d)

    b_pos = b2d[0, :K].astype(dtype)
    sel_pos = b_pos > 0                      # winners carry b >= b_min > 0
    m_star = jnp.round(wm[0, 1]).astype(jnp.int32)
    return m_star, wm[0, 0].astype(dtype), b_pos, sel_pos
