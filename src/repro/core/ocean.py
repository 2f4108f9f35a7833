"""OCEAN — Online Client sElection and bAndwidth allocatioN (paper Alg. 1).

Maintains a virtual energy-deficit queue per client,

    q_k(t+1) = [ E(a_k^t, b_k^t | h_k^t) - H_k / T + q_k(t) ]^+ ,

resets the queues at every frame boundary t = m*R (m = 1..M-1), and in
every round solves the drift-plus-penalty problem P3 via OCEAN-P with the
frame's control parameter V_m and temporal weight eta^t.

Everything here is jittable; ``simulate`` optionally runs the whole
T-round trajectory as one ``lax.scan`` given a precomputed channel matrix.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.bandwidth import solve_p4
from repro.core.energy import RadioParams, energy
from repro.core.selection import (
    DEFAULT_BLOCK_K,
    DEFAULT_TOP_M,
    OceanPSolution,
    check_ranking,
    ocean_p,
    p3_value,
)
from repro.core.solvers import get_solver
from repro.checkpoint.trajectory import CheckpointSpec
from repro.guard.spec import GuardSpec
from repro.obs.spans import trace_span
from repro.obs.metrics import (
    MetricsSpec,
    finalize_metrics,
    init_metrics,
    metrics_round,
    round_context,
)

Array = jax.Array

TRAJ_BACKENDS = ("scan", "fused")

FAILURE_MODES = ("plain", "overprovision", "reallocate")

# Mirrors repro.core.selection._RHO_ZERO_TOL (S0 membership); kept local
# so the failure-aware re-solves classify zero-rho clients exactly as the
# committed P3 solve did.
_RHO_ZERO_TOL = 1e-30


def check_failure_mode(name: str) -> str:
    """Fail fast on unknown failure-aware OCEAN variant names."""
    if name not in FAILURE_MODES:
        raise ValueError(
            f"unknown failure mode {name!r}; available: "
            f"{', '.join(FAILURE_MODES)} (``plain`` commits the legacy "
            f"decision, ``overprovision`` ranks extra clients so expected "
            f"deliveries match the plain selection, ``reallocate`` re-runs "
            f"the P4 bandwidth solve on the mid-round survivor set)"
        )
    return name


def check_traj_backend(name: str) -> str:
    """Fail fast on unknown trajectory-backend names."""
    if name not in TRAJ_BACKENDS:
        raise ValueError(
            f"unknown trajectory backend {name!r}; available: "
            f"{', '.join(TRAJ_BACKENDS)} (``scan`` is the bit-stable "
            f"lax.scan default, ``fused`` the whole-trajectory Pallas "
            f"kernel — see repro.kernels.ocean_traj)"
        )
    return name


@dataclasses.dataclass(frozen=True)
class OceanConfig:
    """Static configuration of one OCEAN run.

    Attributes:
      num_clients: K.
      num_rounds:  T.
      frame_len:   R (queues reset every R rounds; R = T => single frame,
                   the setting used in the paper's experiments §VI-A).
      radio:       physics (bandwidth, noise, deadline, model bits, b_min).
      energy_budget_j: per-client long-term budget H_k (scalar or (K,)).
      solver:      P4/OCEAN-P backend name (``repro.core.solvers``):
                   ``bisect`` (default, bit-stable reference), ``newton``
                   (fast safeguarded Newton), ``pallas`` (fused kernel),
                   or ``pallas_tiled`` (sort-free client-tiled kernel;
                   requires ``ranking="topm"``).
      ranking:     how the round body produces the rho prefix order
                   (``repro.core.selection``): ``sort`` (default — the
                   full ``argsort``, bit-stable legacy path) or ``topm``
                   (sort-free iterative top-m extraction; O(top_m * K),
                   Mosaic-lowerable, exact whenever the optimal prefix
                   fits in ``top_m``).
      top_m:       candidate-prefix length for ``ranking="topm"``
                   (clipped to K; ignored under ``sort``).
      block_k:     client-axis tile width for the ``pallas_tiled``
                   kernel (ignored by the XLA top-m path and ``sort``).
      traj:        trajectory execution backend for ``simulate``:
                   ``scan`` (default — the ``lax.scan`` over rounds,
                   bit-stable) or ``fused`` (``repro.kernels.ocean_traj``:
                   the whole T-round trajectory in one Pallas kernel with
                   VMEM-resident queues; bit-identical to ``scan`` under
                   interpret mode).
      metrics:     optional ``repro.obs.MetricsSpec`` selecting in-graph
                   telemetry collectors; ``simulate`` then returns a
                   third ``metrics`` dict.  ``None`` (default) keeps
                   every legacy code path byte-identical.  A
                   compiled-program static (grid must-agree).
      failure_mode: how OCEAN reacts to per-client delivery failures when
                   a failure process is active (``repro.env.failure``):
                   ``plain`` (default — commit the legacy decision; failed
                   clients burn their energy but deliver nothing),
                   ``overprovision`` (extend the rho-ascending selection
                   prefix until the declared delivery rates sum to the
                   plain cardinality, then re-solve P4 over the extended
                   set), or ``reallocate`` (detect failures at the round's
                   deadline midpoint and re-run P4 on the survivor set;
                   failed clients pay half a round of energy).  A
                   compiled-program static (grid must-agree); with no
                   failure process the knob is inert and every legacy
                   path stays byte-identical.
      guard:       optional ``repro.guard.GuardSpec`` enabling guarded
                   execution: bounded-energy admission (clients whose
                   minimum-allocation energy exceeds
                   ``energy_cap x H_k`` — or whose gain sits below
                   ``gain_floor`` — are demoted out of the rho ranking
                   for the round), an in-graph solver fallback cascade
                   (invalid backend output falls back to the bit-stable
                   bisect solve), and stream sanitization (non-finite
                   channel draws quarantine the client; the queue carry
                   never ingests a NaN).  Works identically on both
                   trajectory backends; ``None`` (default) keeps every
                   legacy path byte-identical.  A compiled-program
                   static (grid must-agree).
      checkpoint:  optional ``repro.checkpoint.CheckpointSpec`` enabling
                   preemption-safe segmented execution: ``simulate``
                   splits the T rounds into ``every_rounds``-sized
                   segments (one ``lax.scan`` / fused-kernel launch
                   each) and atomically snapshots the full carry at
                   every boundary, so a killed run resumes
                   mid-trajectory via ``simulate(resume_from=...)`` with
                   bitwise-identical traces.  ``None`` (default) keeps
                   the legacy single-program path byte-identical.  A
                   compiled-program static (grid must-agree).
    """

    num_clients: int
    num_rounds: int
    radio: RadioParams
    energy_budget_j: float = 0.15
    frame_len: Optional[int] = None  # default: R = T
    solver: str = "bisect"
    ranking: str = "sort"
    top_m: int = DEFAULT_TOP_M
    block_k: int = DEFAULT_BLOCK_K
    traj: str = "scan"
    failure_mode: str = "plain"
    metrics: Optional[MetricsSpec] = None
    guard: Optional[GuardSpec] = None
    checkpoint: Optional[CheckpointSpec] = None

    def __post_init__(self):
        backend = get_solver(self.solver)  # fail fast on unknown backend names
        check_ranking(self.ranking)
        check_traj_backend(self.traj)
        check_failure_mode(self.failure_mode)
        if backend.topm is not None and self.ranking != "topm":
            raise ValueError(
                f"solver {self.solver!r} is sort-free and only runs under "
                f"ranking='topm' (got ranking={self.ranking!r})"
            )
        if self.top_m < 1:
            raise ValueError(f"top_m={self.top_m} must be >= 1")
        if self.block_k < 1:
            raise ValueError(f"block_k={self.block_k} must be >= 1")
        self.radio.validate(self.num_clients)
        if self.metrics is not None:
            # eager lowering-time validation (unknown collectors raised at
            # MetricsSpec construction; the full_trace memory cap needs T/K)
            self.metrics.validate(self.num_rounds, self.num_clients)
        if self.guard is not None and not isinstance(self.guard, GuardSpec):
            raise TypeError(
                f"guard must be a repro.guard.GuardSpec or None; got "
                f"{self.guard!r}"
            )
        if self.frame_len is not None and self.frame_len <= 0:
            raise ValueError(
                f"frame_len={self.frame_len} must be a positive number of "
                f"rounds (or None for the single-frame R = T setting); "
                f"frame_len <= 0 would silently degrade to R = T"
            )

    @property
    def R(self) -> int:
        return self.frame_len or self.num_rounds

    @property
    def num_frames(self) -> int:
        return -(-self.num_rounds // self.R)

    def budgets(self) -> Array:
        h = jnp.asarray(self.energy_budget_j, jnp.float32)
        return jnp.broadcast_to(h, (self.num_clients,))


class OceanState(NamedTuple):
    q: Array            # (K,) energy-deficit queues
    t: Array            # scalar int32 round index
    energy_spent: Array  # (K,) cumulative true energy (diagnostics)


class RoundDecision(NamedTuple):
    a: Array            # (K,) bool selection
    b: Array            # (K,) bandwidth ratios
    e: Array            # (K,) energy consumed this round
    q: Array            # (K,) queues *before* update (as used by P3)
    rho: Array          # (K,) priorities
    objective: Array    # P3 optimum
    num_selected: Array
    # Failure extension (None without a failure process — the fields then
    # flatten to zero pytree leaves, keeping legacy traces byte-identical):
    delivered: Optional[Array] = None  # (K,) bool: selected AND delivered
    realloc: Optional[Array] = None    # () int32: 1 if P4 re-ran mid-round
    # Guard extension (None without a GuardSpec — same zero-leaf trick):
    fault_count: Optional[Array] = None  # () int32: quarantined draws
    demoted: Optional[Array] = None      # () int32: cap/floor demotions
    fallback: Optional[Array] = None     # () int32: 1 if bisect fallback fired


def init_state(cfg: OceanConfig) -> OceanState:
    k = cfg.num_clients
    return OceanState(
        q=jnp.zeros((k,), jnp.float32),
        t=jnp.zeros((), jnp.int32),
        energy_spent=jnp.zeros((k,), jnp.float32),
    )


def _masked_p4(cfg, rho, in_s0, mask, radio):
    """P4 bandwidth over an arbitrary selected set, with OCEAN-P's S0 split.

    Mirrors ``repro.core.selection`` exactly: zero-rho clients in the set
    get the ``b_min`` floor (absorbing the whole budget when no
    positive-rho client is selected), positive-rho clients share the
    remaining ``delta`` through the exact convex ``solve_p4``.
    """
    b_min = jnp.asarray(radio.b_min, jnp.float32)
    n0 = jnp.sum(mask & in_s0)
    delta = 1.0 - n0.astype(jnp.float32) * b_min
    pos = mask & ~in_s0
    b_pos, _ = solve_p4(rho, pos, delta, radio, method=cfg.solver)
    leftover = jnp.where(jnp.sum(pos) == 0, delta, 0.0)
    b0_each = b_min + leftover / jnp.maximum(n0.astype(jnp.float32), 1.0)
    return jnp.where(pos, b_pos, jnp.where(mask & in_s0, b0_each, 0.0))


def _guard_admission(cfg, h2, budgets, radio):
    """The guard's pre-P4 screens: sanitize h2, build the admission mask.

    Returns ``(h2, admit, fault_count, demoted)``: the (possibly
    sanitized) channel gains, the (K,) admission mask for ``ocean_p``
    (``None`` when the spec demotes nobody), the quarantined-draw count,
    and the cap/floor demotion count.  Eq. (2) energy is decreasing in b
    (Lemma 1), so ``E(b_min | h^2) <= energy_cap x H_k`` bounds every
    feasible allocation's spend — admission is a per-round per-client
    energy guarantee, not a heuristic.
    """
    g = cfg.guard
    k = cfg.num_clients
    ok = jnp.ones((k,), bool)
    fault_count = jnp.zeros((), jnp.int32)
    if g.quarantine:
        finite = jnp.isfinite(h2) & (h2 > 0.0)
        fault_count = jnp.sum(~finite).astype(jnp.int32)
        # Sanitize before ANY arithmetic touches the draw: downstream
        # math (rho, energy, the admission test itself) sees a benign
        # placeholder gain, never the corrupt value.
        h2 = jnp.where(finite, h2, jnp.ones_like(h2))
        ok = finite
    admit = ok
    if g.gain_floor is not None:
        admit = admit & (h2 >= jnp.asarray(g.gain_floor, h2.dtype))
    if g.energy_cap is not None:
        caps = jnp.asarray(g.energy_cap, jnp.float32) * (
            cfg.budgets() if budgets is None else jnp.asarray(budgets, jnp.float32)
        )
        b_min = jnp.broadcast_to(jnp.asarray(radio.b_min, h2.dtype), h2.shape)
        admit = admit & (energy(b_min, h2, radio) <= caps)
    demoted = jnp.sum(ok & ~admit).astype(jnp.int32)
    return h2, (admit if g.admits else None), fault_count, demoted


def _guard_fallback(cfg, q, h2, v, eta, radio, admit, sol):
    """Validate the backend's P3/P4 output; fall back to bisect on violation.

    In-graph checks: all-finite decision, budget residual
    ``|sum b - 1| <= residual_tol`` whenever anything is selected, and
    ``b >= b_min`` on every selected client.  The fallback solve runs the
    bit-stable ``bisect`` backend on the SAME guarded inputs (same
    ranking/admission), and a per-leaf select commits whichever solution
    survived — ``lax.cond`` would lower to the same select under the grid
    engine's vmaps anyway.
    """
    b_min = jnp.asarray(radio.b_min, jnp.float32)
    finite_ok = (
        jnp.all(jnp.isfinite(sol.b))
        & jnp.isfinite(sol.objective)
        & jnp.all(jnp.isfinite(sol.rho))
    )
    residual = jnp.abs(jnp.sum(jnp.where(jnp.isfinite(sol.b), sol.b, 0.0)) - 1.0)
    residual_ok = (sol.num_selected == 0) | (residual <= cfg.guard.residual_tol)
    bmin_ok = jnp.all(
        ~sol.a | (jnp.where(jnp.isfinite(sol.b), sol.b, 0.0) >= b_min * (1.0 - 1e-6))
    )
    bad = ~(finite_ok & residual_ok & bmin_ok)
    fb = ocean_p(
        q, h2, v, eta, radio,
        solver="bisect",
        ranking=cfg.ranking,
        top_m=cfg.top_m,
        block_k=cfg.block_k,
        admit=admit,
    )
    sol = OceanPSolution(*(
        jnp.where(bad, f, s) for s, f in zip(sol, fb)
    ))
    return sol, bad.astype(jnp.int32)


def _failure_adjust(
    cfg, q, h2, v, eta, sol, e, radio, delivered, fail_rate, admit=None
):
    """Apply the configured failure-aware variant to one committed round.

    Returns ``(a, b, e, objective, num_selected, delivered, realloc)``.
    Accounting convention (pessimistic, paper-faithful): selected clients
    spend transmission energy whether or not their update arrives — the
    virtual queue charges them — except under ``reallocate``, where a
    client detected failed at the deadline midpoint stops transmitting
    and pays half its committed-round energy while survivors pay half
    the committed allocation plus half the re-solved (cheaper, since
    bandwidth only grows) one.
    """
    ok = delivered > 0.0
    no_ral = jnp.zeros((), jnp.int32)
    if cfg.failure_mode == "plain":
        return sol.a, sol.b, e, sol.objective, sol.num_selected, sol.a & ok, no_ral

    in_s0 = sol.rho <= _RHO_ZERO_TOL

    if cfg.failure_mode == "overprovision":
        if fail_rate is None:
            raise ValueError(
                "failure_mode='overprovision' needs the failure process's "
                "declared delivery rates (TracedFailure.rate); pass the "
                "full TracedFailure, not a bare delivered mask"
            )
        b_min = jnp.asarray(radio.b_min, jnp.float32)
        m_plain = sol.num_selected
        order = jnp.argsort(sol.rho)  # ascending, stable: S0 first
        inv = jnp.argsort(order)
        csum = jnp.cumsum(fail_rate[order])
        # Smallest prefix whose declared delivery rates sum to the plain
        # cardinality (expected deliveries ~ |S_plain|), at least the
        # plain prefix itself, capped by b_min feasibility.
        n_exp = 1 + jnp.sum(csum < m_plain.astype(jnp.float32))
        n_max = jnp.minimum(
            jnp.asarray(cfg.num_clients, jnp.int32),
            jnp.floor((1.0 + 1e-9) / b_min).astype(jnp.int32),
        )
        if admit is not None:
            # Guarded runs: the rho-ascending extension must never reach
            # into demoted clients (they sit at the tail of the order
            # behind the RHO_DEMOTED sentinel) — cap the extended prefix
            # at the admitted-client count.  Gated on the guard being
            # active so unguarded programs trace byte-identically.
            n_max = jnp.minimum(n_max, jnp.sum(admit).astype(jnp.int32))
        n_ext = jnp.clip(jnp.maximum(n_exp, m_plain), 0, n_max)
        n_ext = jnp.where(m_plain > 0, n_ext, 0)
        a = inv < n_ext
        b = _masked_p4(cfg, sol.rho, in_s0, a, radio)
        e_ext = energy(b, h2, radio, a)
        obj = p3_value(a, b, q, h2, v, eta, radio)
        ns = jnp.sum(a).astype(m_plain.dtype)
        return a, b, e_ext, obj, ns, a & ok, no_ral

    # failure_mode == "reallocate": commit the plain decision, detect
    # failures at the deadline midpoint, re-run P4 on the survivor set.
    surv = sol.a & ok
    any_failed = jnp.any(sol.a & ~ok)
    b2 = _masked_p4(cfg, sol.rho, in_s0, surv, radio)
    e2 = energy(b2, h2, radio, surv)
    e_out = jnp.where(any_failed, 0.5 * e + 0.5 * e2, e)
    return (
        sol.a, sol.b, e_out, sol.objective, sol.num_selected, surv,
        any_failed.astype(jnp.int32),
    )


def ocean_round(
    state: OceanState,
    h2: Array,
    v: Array,
    eta: Array,
    cfg: OceanConfig,
    budgets: Optional[Array] = None,
    budget_inc: Optional[Array] = None,
    radio=None,
    delivered: Optional[Array] = None,
    fail_rate: Optional[Array] = None,
) -> Tuple[OceanState, RoundDecision]:
    """One OCEAN round: frame-reset -> P3 solve -> act -> queue update.

    ``budgets`` overrides ``cfg.budgets()`` (e.g. a traced (K,) array when
    the scenario axis of a grid sweep varies the budgets).  ``budget_inc``
    overrides the per-round queue drain (default ``H_k / T``) — this is
    how time-varying budget processes (energy harvesting, depleting
    batteries; see ``repro.env.energy``) enter the queue dynamics.
    ``radio`` overrides ``cfg.radio`` with this round's physics — any
    pytree of (traced) scalars exposing the ``RadioParams`` attributes,
    e.g. one round of a ``repro.env.radio`` sequence.

    ``delivered`` is this round's (K,) {0, 1} delivery mask from a
    ``repro.env.failure`` process; with it the round applies
    ``cfg.failure_mode`` (plain / overprovision / reallocate), charges
    energy under the pessimistic accounting, and reports the
    ``RoundDecision.delivered``/``realloc`` fields.  ``fail_rate`` is the
    (K,) declared stationary delivery rate (``TracedFailure.rate``),
    required by ``overprovision``.  Both ``None`` (the default) keeps the
    pre-failure program byte-identical.

    With ``cfg.guard`` set (``repro.guard.GuardSpec``) the round runs
    guarded: channel draws are quarantined/sanitized and the energy
    cap / gain floor demotes clients out of the ranking *before* P4
    (``ocean_p(admit=...)``), the backend's output is validated in-graph
    with a bisect fallback, and the queue update's increment is
    sanitized — reported through the ``fault_count``/``demoted``/
    ``fallback`` decision fields.  ``cfg.guard=None`` (default) traces
    the legacy round byte-for-byte.
    """
    R = cfg.R
    radio = cfg.radio if radio is None else radio
    # Frame boundary reset (Alg. 1 line 3-5): at t = m*R, m >= 1.
    with trace_span("ocean/queue"):
        at_boundary = (state.t > 0) & (jnp.mod(state.t, R) == 0)
        q = jnp.where(at_boundary, jnp.zeros_like(state.q), state.q)

    admit = fault_count = demoted = fb_flag = None
    if cfg.guard is not None:
        h2 = jnp.asarray(h2)
        h2, admit, fault_count, demoted = _guard_admission(
            cfg, h2, budgets, radio
        )

    sol: OceanPSolution = ocean_p(
        q,
        h2,
        v,
        eta,
        radio,
        solver=cfg.solver,
        ranking=cfg.ranking,
        top_m=cfg.top_m,
        block_k=cfg.block_k,
        admit=admit,
    )
    if cfg.guard is not None:
        if cfg.guard.fallback:
            sol, fb_flag = _guard_fallback(cfg, q, h2, v, eta, radio, admit, sol)
        else:
            fb_flag = jnp.zeros((), jnp.int32)
    with trace_span("ocean/energy"):
        e = energy(sol.b, h2, radio, sol.a)

    a, b, objective, num_selected = sol.a, sol.b, sol.objective, sol.num_selected
    dlv = ral = None
    if delivered is not None:
        a, b, e, objective, num_selected, dlv, ral = _failure_adjust(
            cfg, q, h2, v, eta, sol, e, radio, delivered, fail_rate,
            admit=admit,
        )

    with trace_span("ocean/queue"):
        if budget_inc is None:
            if budgets is None:
                budgets = cfg.budgets()
            budget_inc = budgets / cfg.num_rounds
        if cfg.guard is not None and cfg.guard.quarantine:
            # A corrupt budget draw must never reach the queue carry: a
            # non-finite increment is treated as "no allowance this round".
            budget_inc = jnp.where(
                jnp.isfinite(budget_inc), budget_inc,
                jnp.zeros_like(budget_inc),
            )
        q_next = jnp.maximum(q + e - budget_inc, 0.0)

        new_state = OceanState(
            q=q_next,
            t=state.t + 1,
            energy_spent=state.energy_spent + e,
        )
    dec = RoundDecision(
        a=a,
        b=b,
        e=e,
        q=q,
        rho=sol.rho,
        objective=objective,
        num_selected=num_selected,
        delivered=dlv,
        realloc=ral,
        fault_count=fault_count,
        demoted=demoted,
        fallback=fb_flag,
    )
    return new_state, dec


def v_schedule(cfg: OceanConfig, v: float | Array) -> Array:
    """Broadcast a scalar V (or per-frame (M,) sequence) to per-round (T,).

    A 1-D ``v`` must have exactly one entry per frame: silently clipping
    a wrong-length sequence (the old behavior) truncated or repeated
    control parameters without complaint.
    """
    v = jnp.asarray(v, jnp.float32)
    if v.ndim == 0:
        return jnp.full((cfg.num_rounds,), v)
    if v.ndim != 1 or v.shape[0] != cfg.num_frames:
        raise ValueError(
            f"per-frame V sequence has shape {v.shape}, but this config has "
            f"{cfg.num_frames} frames (T={cfg.num_rounds} rounds / "
            f"R={cfg.R} per frame => M=ceil(T/R)={cfg.num_frames}); pass a "
            f"scalar V or one entry per frame"
        )
    frame_idx = jnp.arange(cfg.num_rounds) // cfg.R
    return v[frame_idx]


def simulate(
    cfg: OceanConfig,
    h2_seq: Array,       # (T, K) channel power gains
    eta_seq: Array,      # (T,)   temporal weights
    v: float | Array,    # scalar or per-frame (M,)
    budgets: Optional[Array] = None,     # (K,) override of cfg.budgets()
    budget_seq: Optional[Array] = None,  # (T, K) per-round budget increments
    radio_seq=None,                      # (T,)-leaf radio pytree (TracedRadio)
    failure_seq=None,                    # TracedFailure ((T, K) mask + (K,) rate)
    traj: Optional[str] = None,          # trajectory backend; None => cfg.traj
    stream_bf16: bool = False,           # fused only: bf16 decision traces
    checkpoint: Union[CheckpointSpec, None, bool] = None,
    resume_from: Union[str, bool, None] = None,
):
    """Run T rounds as one program; returns final state + stacked decisions.

    With ``cfg.metrics`` set (a ``repro.obs.MetricsSpec``), returns the
    3-tuple ``(state, decisions, metrics)`` where ``metrics`` maps
    ``"<collector>/<reduction>"`` keys to recorded telemetry — collected
    *inside* the same compiled program, on both trajectory backends.
    ``cfg.metrics=None`` returns the legacy 2-tuple, byte-identical.

    ``budget_seq`` feeds a time-varying per-round allowance into the
    queue update (``repro.env`` budget processes); when omitted, the
    constant ``H_k / T`` drain of the paper applies.  ``radio_seq`` feeds
    per-round radio physics (``repro.env.radio`` processes: spectrum
    sharing, deadline jitter) — a pytree whose leaves carry a leading
    ``(T,)`` axis the scan slices; when omitted the static ``cfg.radio``
    is baked in, the paper's (and the legacy) program.  ``failure_seq``
    feeds a realized ``repro.env.failure`` reliability (a
    ``TracedFailure``: the (T, K) delivered mask plus the (K,) declared
    rates); each round then applies ``cfg.failure_mode`` and reports
    ``delivered``/``realloc`` decision fields — when omitted, the
    pre-failure program is byte-identical.

    ``traj`` picks the trajectory backend (a compiled-program static):
    ``scan`` runs the rounds as one ``lax.scan`` (the default, bit-stable
    path); ``fused`` hands the entire trajectory to the
    ``repro.kernels.ocean_traj`` Pallas kernel, which keeps the queue /
    energy carry resident in VMEM and is bit-identical to ``scan`` under
    interpret mode.  ``None`` resolves to ``cfg.traj``.

    ``stream_bf16=True`` (fused backend only) streams the per-round
    (T, K) float decision traces back to HBM in bfloat16; the on-chip
    carries — and hence the trajectory and final state — are unchanged.

    ``checkpoint`` (default ``None`` => ``cfg.checkpoint``; pass
    ``False`` to force off) switches to **segmented execution**: the T
    rounds run as ``every_rounds``-sized segments — one ``lax.scan`` /
    fused-kernel launch each — with the full carry (queues,
    energy_spent, round index, metrics accumulators, decision prefix)
    snapshotted atomically at every boundary.  ``resume_from`` (a
    snapshot directory, or ``True`` for the spec's own directory)
    restores the latest committed snapshot and continues mid-trajectory;
    the completed run's traces and telemetry are bitwise identical to
    the uninterrupted segmented run on both backends.  Segmented
    execution is a host-side driver: call it outside ``jit`` (each
    segment is jitted internally).  With checkpointing off everywhere
    the legacy single-program path below is byte-identical.
    """
    traj = check_traj_backend(cfg.traj if traj is None else traj)
    if stream_bf16 and traj != "fused":
        raise ValueError(
            "stream_bf16=True requires the 'fused' trajectory backend "
            "(the scan path materializes full-precision decisions by "
            f"construction); got traj={traj!r}"
        )
    ckpt_spec = cfg.checkpoint if checkpoint is None else (checkpoint or None)
    if ckpt_spec is not None or resume_from is not None:
        return _simulate_segmented(
            cfg, h2_seq, eta_seq, v, budgets, budget_seq, radio_seq,
            failure_seq, traj, stream_bf16, ckpt_spec, resume_from,
        )
    v_seq = v_schedule(cfg, v)
    eta_seq = jnp.asarray(eta_seq, jnp.float32)
    if budget_seq is None:
        per_round = (cfg.budgets() if budgets is None else budgets) / cfg.num_rounds
        budget_seq = jnp.broadcast_to(
            per_round, (cfg.num_rounds, cfg.num_clients)
        )
    budget_seq = jnp.asarray(budget_seq, jnp.float32)

    if traj == "fused":
        from repro.kernels.ocean_traj import ocean_trajectory_fused

        return ocean_trajectory_fused(
            cfg,
            h2_seq,
            v_seq,
            eta_seq,
            budget_seq,
            radio_seq,
            failure_seq,
            stream_bf16=stream_bf16,
        )

    dlv_seq = None if failure_seq is None else failure_seq.delivered
    fail_rate = None if failure_seq is None else failure_seq.rate
    # One step body for every optional-input combination: absent inputs
    # simply never join the scan xs and their kwargs stay None, so each
    # flag combination traces exactly the ops it always has.
    unpack = _make_unpack(radio_seq is not None, dlv_seq is not None)

    if cfg.metrics is None:
        def step(state, inputs):
            h2, v_t, eta_t, inc_t, radio_t, dlv_t = unpack(inputs)
            return ocean_round(
                state, h2, v_t, eta_t, cfg, budgets, budget_inc=inc_t,
                radio=radio_t, delivered=dlv_t, fail_rate=fail_rate,
            )

        return jax.lax.scan(
            step,
            init_state(cfg),
            _scan_xs(h2_seq, v_seq, eta_seq, budget_seq, radio_seq, dlv_seq),
        )

    # Metrics-enabled scan: the round math is the untouched ocean_round —
    # collectors only *read* its outputs (repro.obs.metrics.round_context),
    # so decisions stay bitwise identical to the metrics-off program; the
    # MetricsState dicts ride the carry, full traces stream as scan ys.
    spec = cfg.metrics

    def step_m(carry, inputs):
        state, mstate = carry
        h2, v_t, eta_t, inc_t, radio_t, dlv_t = unpack(inputs)
        new_state, dec = ocean_round(
            state, h2, v_t, eta_t, cfg, budgets, budget_inc=inc_t,
            radio=radio_t, delivered=dlv_t, fail_rate=fail_rate,
        )
        ctx = round_context(
            state.t, dec, new_state, v_t, eta_t, inc_t,
            cfg.radio if radio_t is None else radio_t,
        )
        mstate, traces = metrics_round(spec, cfg, ctx, mstate)
        return (new_state, mstate), (dec, traces)

    (state, mstate), (decs, traces) = jax.lax.scan(
        step_m,
        (init_state(cfg), init_metrics(spec, cfg)),
        _scan_xs(h2_seq, v_seq, eta_seq, budget_seq, radio_seq, dlv_seq),
    )
    return state, decs, finalize_metrics(spec, cfg, mstate, traces)


def _scan_xs(h2_seq, v_seq, eta_seq, budget_seq, radio_seq, dlv_seq):
    xs = (h2_seq, v_seq, eta_seq, budget_seq)
    if radio_seq is not None:
        xs = xs + (radio_seq,)
    if dlv_seq is not None:
        xs = xs + (dlv_seq,)
    return xs


def _make_unpack(has_radio: bool, has_failure: bool):
    def unpack(inputs):
        h2, v_t, eta_t, inc_t = inputs[:4]
        i = 4
        radio_t = dlv_t = None
        if has_radio:
            radio_t = inputs[i]
            i += 1
        if has_failure:
            dlv_t = inputs[i]
        return h2, v_t, eta_t, inc_t, radio_t, dlv_t

    return unpack


# ---------------------------------------------------------------------------
# Segmented execution with preemption-safe checkpoint/resume.
#
# The T-round trajectory is split at multiples of ``every_rounds`` into
# segments; each segment is ONE ``lax.scan`` (or one fused-kernel launch)
# continuing from the carried state, so the concatenated decisions are the
# same op sequence as the single-program run.  At every boundary the full
# carry plus the decision/trace prefix is snapshotted through the hardened
# ``repro.checkpoint`` (atomic replace, bit-exact dtypes); a resumed run
# re-enters the same segment grid, which makes resumed == uninterrupted a
# structural identity, not a numerical accident.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg", "traj", "stream_bf16"))
def _segment_step(
    cfg, traj, stream_bf16, state, mstate, h2, v_s, eta_s, inc_s, radio_s,
    failure_s, budgets,
):
    """One segment from a mid-trajectory carry -> (state', mstate', decs, traces)."""
    spec = cfg.metrics
    if traj == "fused":
        from repro.kernels.ocean_traj import ocean_trajectory_fused

        out = ocean_trajectory_fused(
            cfg, h2, v_s, eta_s, inc_s, radio_s, failure_s,
            stream_bf16=stream_bf16,
            init_state=state,
            init_mstate=mstate,
            raw_metrics=True,
        )
        if spec is None:
            new_state, decs = out
            return new_state, None, decs, None
        new_state, decs, mstate, traces = out
        return new_state, mstate, decs, traces

    dlv_s = None if failure_s is None else failure_s.delivered
    fail_rate = None if failure_s is None else failure_s.rate
    unpack = _make_unpack(radio_s is not None, dlv_s is not None)

    def step(carry, inputs):
        state, mstate = carry
        h2_t, v_t, eta_t, inc_t, radio_t, dlv_t = unpack(inputs)
        new_state, dec = ocean_round(
            state, h2_t, v_t, eta_t, cfg, budgets, budget_inc=inc_t,
            radio=radio_t, delivered=dlv_t, fail_rate=fail_rate,
        )
        if spec is None:
            return (new_state, mstate), (dec, None)
        ctx = round_context(
            state.t, dec, new_state, v_t, eta_t, inc_t,
            cfg.radio if radio_t is None else radio_t,
        )
        mstate, traces = metrics_round(spec, cfg, ctx, mstate)
        return (new_state, mstate), (dec, traces)

    xs = _scan_xs(h2, v_s, eta_s, inc_s, radio_s, dlv_s)
    (state, mstate), (decs, traces) = jax.lax.scan(step, (state, mstate), xs)
    return state, mstate, decs, traces


def _concat_parts(parts):
    """Concatenate per-segment stacked pytrees along the round axis."""
    if len(parts) == 1:
        return parts[0]
    return jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, axis=0), *parts
    )


def _simulate_segmented(
    cfg, h2_seq, eta_seq, v, budgets, budget_seq, radio_seq, failure_seq,
    traj, stream_bf16, ckpt_spec, resume_from,
):
    from repro.checkpoint import trajectory as ckpt_io

    if ckpt_spec is not None and not isinstance(ckpt_spec, CheckpointSpec):
        raise TypeError(
            f"checkpoint must be a CheckpointSpec, None, or False; got "
            f"{ckpt_spec!r}"
        )
    if isinstance(h2_seq, jax.core.Tracer):
        raise ValueError(
            "checkpointed simulate is a host-side segmented driver and "
            "cannot run under jit/vmap; call it un-jitted (each segment "
            "is jitted internally) or use GridEngine for batched sweeps"
        )
    T, K = cfg.num_rounds, cfg.num_clients
    spec = cfg.metrics
    v_seq = v_schedule(cfg, v)
    eta_seq = jnp.asarray(eta_seq, jnp.float32)
    if budget_seq is None:
        per_round = (cfg.budgets() if budgets is None else budgets) / cfg.num_rounds
        budget_seq = jnp.broadcast_to(per_round, (T, K))
    budget_seq = jnp.asarray(budget_seq, jnp.float32)
    every = ckpt_spec.every_rounds if ckpt_spec is not None else T

    def sl(tree, t0, t1):
        if tree is None:
            return None
        return jax.tree_util.tree_map(lambda x: x[t0:t1], tree)

    def fl(failure, t0, t1):
        # Slice the (T, K) mask only — the (K,) declared rates ride whole.
        if failure is None:
            return None
        return failure._replace(delivered=failure.delivered[t0:t1])

    def run_segment(state, mstate, t0, t1):
        return _segment_step(
            cfg, traj, stream_bf16, state, mstate,
            h2_seq[t0:t1], v_seq[t0:t1], eta_seq[t0:t1], budget_seq[t0:t1],
            sl(radio_seq, t0, t1), fl(failure_seq, t0, t1), budgets,
        )

    state = init_state(cfg)
    mstate = init_metrics(spec, cfg) if spec is not None else None
    dec_parts, trace_parts = [], []
    start = 0

    if resume_from is not None:
        if resume_from is True:
            if ckpt_spec is None:
                raise ValueError(
                    "resume_from=True needs a CheckpointSpec to name the "
                    "snapshot directory"
                )
            directory = ckpt_spec.directory
        else:
            directory = str(resume_from)
        r = ckpt_io.latest_round(directory)
        if r is None:
            raise FileNotFoundError(
                f"resume_from: no committed snapshots in {directory!r}"
            )

        def prefix_like(h2p, vp, ep, ip, radp, failp):
            st0 = init_state(cfg)
            ms0 = init_metrics(spec, cfg) if spec is not None else None
            st, ms, d, tr = _segment_step(
                cfg, traj, stream_bf16, st0, ms0, h2p, vp, ep, ip, radp,
                failp, budgets,
            )
            snap = {"state": st, "decs": d}
            if spec is not None:
                snap["mstate"] = ms
                snap["traces"] = tr
            return snap

        like = jax.eval_shape(
            prefix_like,
            h2_seq[:r], v_seq[:r], eta_seq[:r], budget_seq[:r],
            sl(radio_seq, 0, r), fl(failure_seq, 0, r),
        )
        snap, _ = ckpt_io.load_snapshot(directory, like, r)
        state = snap["state"]
        start = r
        dec_parts = [snap["decs"]]
        if spec is not None:
            mstate = snap["mstate"]
            trace_parts = [snap["traces"]]

    for t0, t1 in ckpt_io.segment_bounds(T, every, start):
        state, mstate, decs_s, traces_s = run_segment(state, mstate, t0, t1)
        dec_parts.append(decs_s)
        if spec is not None:
            trace_parts.append(traces_s)
        if ckpt_spec is not None:
            snapshot = {"state": state, "decs": _concat_parts(dec_parts)}
            if spec is not None:
                snapshot["mstate"] = mstate
                snapshot["traces"] = _concat_parts(trace_parts)
            ckpt_io.save_snapshot(ckpt_spec, snapshot, t1)

    decs = _concat_parts(dec_parts)
    if spec is None:
        return state, decs
    return state, decs, finalize_metrics(
        spec, cfg, mstate, _concat_parts(trace_parts)
    )
