"""OCEAN-P — optimal solver of the per-round problem P3 (paper §V-B, Alg. 2).

P3:  max_{a, b}  V * eta * sum_k a_k  -  sum_k q_k E(a_k, b_k | h_k)
     s.t.        sum_k b_k = 1,  b_k >= b_min for selected k,  a_k in {0,1}

Theorem 1 proves the optimal selection is a prefix of the clients sorted by
priority rho_k = q_k / h_k^2 (ascending), so only K candidate sets matter.
The paper iterates them serially with an early-termination test; we instead
evaluate *all* prefixes in parallel with ``vmap`` over the masked P4 solver
and take the argmax — same optimum, one XLA program (DESIGN.md §3).

Clients with rho_k == 0 (zero energy-deficit queue) form S0: they are
always selected and pinned at b_min; the remaining budget
delta = 1 - |S0| * b_min is waterfilled over the positive-rho prefix by P4.
Leftover bandwidth when *only* S0 is selected is spread evenly over S0
(costless — their weighted energy is zero).

``ocean_p`` is pure jnp end to end (argsort + the registry backend), so
it traces equally well inside a ``lax.scan`` step and inside the fused
whole-trajectory Pallas kernel (``repro.kernels.ocean_traj``), which
re-runs this exact function per resident round — that sharing is what
makes the ``fused`` trajectory backend bit-identical to ``scan``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from repro.core.energy import RadioParams, SAFE_DIV_FLOOR, f_shannon
from repro.core.solvers import SolverBackend, get_solver
from repro.obs.spans import trace_span

Array = jax.Array

_RHO_ZERO_TOL = 1e-30

# Ranking strategies for the Theorem-1 prefix structure:
#   sort — full ``argsort`` of rho, then the K+1 candidate sweep (the
#          bit-stable legacy path; O(K log K) + O(K^2 iters) per round);
#   topm — sort-free: only the selected prefix needs exact order, so an
#          iterative min-extraction ranks just the ``top_m`` smallest
#          positive-rho clients (stable ties) and the candidate sweep is
#          clipped to m in [0, top_m].  Bit-identical to ``sort`` for
#          every solver whenever the optimum prefix fits (m* <= top_m);
#          when it doesn't, the selection saturates at the best
#          top_m-prefix (a documented, deterministic approximation).
RANKINGS = ("sort", "topm")
DEFAULT_RANKING = "sort"
DEFAULT_TOP_M = 128
DEFAULT_BLOCK_K = 128

# Priority sentinel for clients demoted by the guard's ``admit`` mask
# (``repro.guard``).  Huge but FINITE: it must dominate every admitted
# client's rho (natural priorities top out around q / SAFE_DIV_FLOOR
# ~ 1e29 only for effectively-dead channels the guard demotes anyway),
# yet stay far enough below float32 max that ``rho * |f'(b_min)|`` in
# the solvers' bracket seeding cannot overflow to inf — selection safety
# itself never depends on the ordering, only on the prefix objective a
# demoted member poisons.
RHO_DEMOTED = 1e30


def check_ranking(name: str) -> str:
    """Fail fast on unknown ranking names."""
    if name not in RANKINGS:
        raise ValueError(
            f"unknown ranking {name!r}; available: {', '.join(RANKINGS)} "
            f"(``sort`` is the bit-stable argsort default, ``topm`` the "
            f"sort-free iterative extraction — see repro.core.selection)"
        )
    return name


class OceanPSolution(NamedTuple):
    a: Array          # (K,) bool  — selection decisions
    b: Array          # (K,) float — bandwidth ratios (sum == 1 over selected)
    objective: Array  # scalar     — optimal P3 value W*(S*)
    rho: Array        # (K,) float — priorities (diagnostics / Fig 15)
    num_selected: Array  # scalar int


def priorities(q: Array, h2: Array) -> Array:
    """rho_k = q_k / h_k^2 — lower is higher selection priority."""
    return jnp.asarray(q) / jnp.maximum(jnp.asarray(h2), SAFE_DIV_FLOOR)


def topm_extract(rho: Array, top_m: int) -> tuple[Array, Array]:
    """Rank the ``top_m`` smallest *positive* priorities without sorting.

    Iterative min-extraction: ``top_m`` rounds of (min, first-argmin,
    mask-to-+inf) over the working copy — O(top_m * K) reductions, no
    ``argsort``, no data-dependent gather.  ``jnp.argmin`` returns the
    first occurrence of the minimum, so ties break by client index —
    exactly the order a stable ascending ``argsort`` produces, which is
    what makes the reconstruction downstream bit-identical to the sorted
    path (oracle: ``repro.kernels.ref.topm_extract_ref``).

    Returns ``(vals, idx)`` of shape ``(top_m,)``: ascending extracted
    priorities and their client indices.  S0 members (rho <= 1e-30) are
    excluded (they are always selected and never ranked); slots past the
    number of positive-rho clients hold ``+inf`` / index 0.
    """
    rho = jnp.asarray(rho)
    K = rho.shape[0]
    dtype = rho.dtype
    inf = jnp.asarray(jnp.inf, dtype)
    work0 = jnp.where(rho > _RHO_ZERO_TOL, rho, inf)
    iota = jax.lax.broadcasted_iota(jnp.int32, (K,), 0)
    slot = jax.lax.broadcasted_iota(jnp.int32, (top_m,), 0)

    def extract(j, carry):
        work, vals, idx = carry
        v = jnp.min(work)
        # First occurrence on ties, as an index-min (no argmin gather).
        i = jnp.min(jnp.where(work == v, iota, K))
        i = jnp.where(i < K, i, 0)   # all-inf work: slot exhausted, index 0
        work = jnp.where(iota == i, inf, work)
        # Masked selects, not ``.at[j].set``: a dynamic update lowers to a
        # scatter, which the fused kernel's TPU compiler cannot lower.
        hit = slot == j
        return work, jnp.where(hit, v, vals), jnp.where(hit, i, idx)

    _, vals, idx = jax.lax.fori_loop(
        0,
        top_m,
        extract,
        (
            work0,
            jnp.full((top_m,), inf, dtype),
            jnp.zeros((top_m,), jnp.int32),
        ),
    )
    return vals, idx


def _promote_real(x: Array) -> Array:
    """Promote integer/bool inputs to the floating dtype they imply.

    ``jnp.promote_types`` handles every integer width (int16/int64/bool,
    not just the int32 the old guard caught); float inputs pass through
    untouched so the float32 hot path stays bit-identical.
    """
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.floating):
        x = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    return x


def ocean_p(
    q: Array,
    h2: Array,
    v: Array,
    eta: Array,
    radio: RadioParams,
    outer_iters: int = 42,
    inner_iters: int = 42,
    solver: Union[str, SolverBackend, None] = None,
    ranking: Union[str, None] = None,
    top_m: Union[int, None] = None,
    block_k: Union[int, None] = None,
    admit: Optional[Array] = None,
) -> OceanPSolution:
    """Solve P3 exactly.  All args jittable; shapes: q, h2 -> (K,).

    ``solver`` picks the P4 backend (``repro.core.solvers``): ``bisect``
    (default, bit-stable reference), ``newton`` (fast safeguarded
    Newton), ``pallas`` (fused kernel), or ``pallas_tiled`` (sort-free
    client-tiled kernel; requires ``ranking="topm"``).  All solve the
    same problem exactly; only ``bisect`` is byte-stable against
    historical figures.

    ``ranking`` picks how the Theorem-1 prefix order is produced:
    ``sort`` (default — full argsort, bit-stable) or ``topm`` (sort-free
    iterative extraction of the ``top_m`` best clients; bit-identical to
    ``sort`` per solver whenever m* <= top_m, and O((top_m + G) K) per
    round instead of O(K^2 iters)).  ``block_k`` is the client-tile width
    of the ``pallas_tiled`` kernel (ignored elsewhere).

    ``admit`` is an optional (K,) boolean availability mask (the guarded
    execution layer, ``repro.guard``): demoted clients get
    rho = ``RHO_DEMOTED`` — a huge *finite* sentinel (1e30, above any
    admitted priority in practice) so they sort last, fall outside S0
    (sentinel > tol), and any candidate prefix containing one carries an
    astronomically negative objective and always loses to the
    always-finite m = 0 candidate.  Finite by design: +inf here would
    reach the solvers' log-space bracket seeding as ``inf * 0`` NaNs,
    and the guarded paths must be NaN-free by construction
    (``JAX_DEBUG_NANS`` CI gate).  ``admit=None`` (the default) traces
    the legacy program byte-for-byte.
    """
    q = _promote_real(q)
    h2 = _promote_real(h2)
    dtype = jnp.result_type(q.dtype, h2.dtype, jnp.float32)
    q = q.astype(dtype)
    h2 = h2.astype(dtype)
    K = q.shape[0]
    v_eta = (jnp.asarray(v, dtype) * jnp.asarray(eta, dtype)).astype(dtype)

    ranking = check_ranking(DEFAULT_RANKING if ranking is None else ranking)
    backend = get_solver(solver)
    rho = priorities(q, h2)
    if admit is not None:
        rho = jnp.where(
            jnp.asarray(admit, bool), rho, jnp.asarray(RHO_DEMOTED, dtype)
        )

    if ranking == "topm":
        return _ocean_p_topm(
            rho,
            v_eta,
            radio,
            backend,
            outer_iters,
            inner_iters,
            DEFAULT_TOP_M if top_m is None else top_m,
            DEFAULT_BLOCK_K if block_k is None else block_k,
        )
    if backend.topm is not None:
        raise ValueError(
            f"solver {backend.name!r} is sort-free and has no argsort "
            f"path; call ocean_p(..., ranking='topm') (or set the "
            f"ranking config field)"
        )

    with trace_span("ocean/rank"):
        order = jnp.argsort(rho)      # ascending priority value
        rho_sorted = rho[order]

    in_s0 = rho_sorted <= _RHO_ZERO_TOL      # S0 members (always selected)
    n0 = jnp.sum(in_s0)
    delta = 1.0 - n0.astype(dtype) * radio.b_min

    # Candidate m = number of positive-rho clients admitted, m in [0, K].
    # Sorted rank r belongs to candidate m's P4 iff n0 <= r < n0 + m.
    with trace_span(f"ocean/p4_solve/{backend.name}"):
        sol = backend.prefixes(
            rho_sorted, n0, delta, v_eta, radio, outer_iters, inner_iters
        )
    m_star = sol.m_star
    w_star = sol.w_star
    b_pos_sorted = sol.b_pos_sorted     # positive-rho members' allocation
    sel_pos_sorted = sol.sel_pos_sorted

    # S0 allocation: b_min each, plus any leftover when nobody else is
    # selected (so sum b == 1 always holds when anyone is selected).
    leftover = jnp.where(m_star == 0, delta, 0.0)
    b0_each = radio.b_min + leftover / jnp.maximum(n0.astype(dtype), 1.0)
    b_sorted_full = jnp.where(in_s0, b0_each, b_pos_sorted)
    a_sorted = in_s0 | sel_pos_sorted

    # Un-sort back to client order.
    inv = jnp.argsort(order)
    a = a_sorted[inv]
    b = jnp.where(a_sorted, b_sorted_full, 0.0)[inv]

    return OceanPSolution(
        a=a,
        b=b,
        objective=w_star,
        rho=rho,
        num_selected=jnp.sum(a),
    )


def _ocean_p_topm(
    rho: Array,
    v_eta: Array,
    radio: RadioParams,
    backend: SolverBackend,
    outer_iters: int,
    inner_iters: int,
    top_m: int,
    block_k: int,
) -> OceanPSolution:
    """The sort-free P3 path: rank only the best ``top_m`` clients.

    Two sub-paths:

    * ``backend.topm`` set (``pallas_tiled``): the whole pipeline —
      extraction, candidate solve, scatter — is one fused client-tiled
      kernel on unsorted rho.
    * otherwise (``bisect``/``newton``/``pallas``): ``topm_extract``
      ranks the top_m positives, the extracted values are placed at
      their exact sorted slots ``n0..n0+top_m-1`` of a K-length +inf
      buffer, and the backend's normal prefix sweep runs clipped to
      ``m_cands`` candidates.  Because every per-candidate reduction is
      masked to slots the extraction filled with bitwise-equal floats —
      and masked sums/cumsums over identical array shapes with identical
      populated slots reduce through identical trees — the winning
      candidate is bit-identical to the argsort path whenever
      m* <= top_m.  Scatter back to client order is ``.at[idx]`` with
      exact +0.0 duplicates, never a K-length data-dependent gather.
    """
    dtype = rho.dtype
    K = rho.shape[0]
    if top_m < 1:
        raise ValueError(f"top_m={top_m} must be >= 1")
    if block_k < 1:
        raise ValueError(f"block_k={block_k} must be >= 1")
    m_cands = int(min(top_m, K))

    in_s0 = rho <= _RHO_ZERO_TOL
    n0 = jnp.sum(in_s0)
    delta = 1.0 - n0.astype(dtype) * radio.b_min

    if backend.topm is not None:
        with trace_span(f"ocean/p4_solve/{backend.name}"):
            m_star, w_star, b_pos, sel_pos = backend.topm(
                rho, n0, delta, v_eta, radio, top_m=m_cands, block_k=block_k
            )
    else:
        with trace_span("ocean/rank"):
            vals, idx = topm_extract(rho, m_cands)
            # Reconstruct the K-length sorted view: extracted values land
            # at their exact sorted offsets [n0, n0 + m_cands); everything
            # else is a +inf sentinel no masked candidate reduction ever
            # reads.  One-hot (m_cands, K) selects stand in for a dynamic
            # update / slice / scatter so the round also lowers inside
            # the fused TPU kernel; each picks one exact value (or none).
            ranks = jax.lax.broadcasted_iota(jnp.int32, (m_cands, K), 1)
            slots = jax.lax.broadcasted_iota(jnp.int32, (m_cands, K), 0)
            at_rank = ranks == n0 + slots                 # (m_cands, K)
            rho_rank = jnp.min(
                jnp.where(at_rank, vals[:, None], jnp.inf), axis=0
            )
        rho_hi = jnp.max(rho)  # order-insensitive == rho_sorted[K-1]
        with trace_span(f"ocean/p4_solve/{backend.name}"):
            sol = backend.prefixes(
                rho_rank,
                n0,
                delta,
                v_eta,
                radio,
                outer_iters,
                inner_iters,
                m_cands=m_cands,
                rho_hi=rho_hi,
            )
        m_star = sol.m_star
        w_star = sol.w_star
        # Winner's allocation lives at sorted slots [n0, n0 + m*): read
        # the candidate window, then scatter through the extraction
        # indices (exhausted slots carry idx 0 but sel_j False / +0.0).
        b_cand = jnp.sum(
            jnp.where(at_rank, sol.b_pos_sorted[None, :], 0.0), axis=1
        )
        sel_j = slots[:, :1] < m_star                     # (m_cands, 1)
        at_client = (idx[:, None] == ranks) & sel_j       # (m_cands, K)
        b_pos = jnp.sum(jnp.where(at_client, b_cand[:, None], 0.0), axis=0)
        sel_pos = jnp.any(at_client, axis=0)

    leftover = jnp.where(m_star == 0, delta, 0.0)
    b0_each = radio.b_min + leftover / jnp.maximum(n0.astype(dtype), 1.0)
    a = in_s0 | sel_pos
    b = jnp.where(in_s0, b0_each, jnp.where(sel_pos, b_pos, 0.0))

    return OceanPSolution(
        a=a,
        b=b,
        objective=w_star,
        rho=rho,
        num_selected=jnp.sum(a),
    )


def p3_value(
    a: Array, b: Array, q: Array, h2: Array, v: Array, eta: Array, radio: RadioParams
) -> Array:
    """Evaluate the P3 objective for arbitrary (a, b) — used by tests/oracles."""
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    rho = priorities(q, h2)
    util = jnp.asarray(v) * jnp.asarray(eta) * jnp.sum(a)
    en = radio.energy_scale * jnp.sum(
        jnp.where(a > 0, rho * f_shannon(jnp.maximum(b, radio.b_min), radio.beta), 0.0)
    )
    return util - en
