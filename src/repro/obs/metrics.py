"""In-graph telemetry for the Lyapunov machinery — ``MetricsSpec`` collectors.

The paper's argument is *long-term*: OCEAN's guarantees live in the
virtual-queue backlogs q_k(t), the drift-plus-penalty decomposition
(O(1/V) optimality gap vs O(sqrt V) budget violation), and the temporal
selection patterns of §IV.  Yet the trajectories run inside one opaque
jitted ``lax.scan`` / fused Pallas kernel, and only the final figure
numbers come back out.  This module records telemetry *inside* those
compiled programs:

* a static :class:`MetricsSpec` — ``((collector, reduction), ...)`` pairs
  — selects traced per-round collectors from a registry and is carried on
  ``OceanConfig`` / ``Scenario`` as a compiled-program static (grid
  must-agree; ``spec=None`` leaves every legacy code path byte-identical),
* each collector reads a :class:`RoundContext` assembled *after* the
  untouched ``ocean_round`` math — the round body itself never changes,
* per-collector running state and per-``(collector, reduction)``
  accumulators form two small dict pytrees (:class:`MetricsState`) that
  ride the ``lax.scan`` carry, or live in VMEM scratch across the chunks
  of the fused ``repro.kernels.ocean_traj`` kernel,
* reductions are chosen statically so memory stays bounded at K = 10^5:
  ``last`` / ``mean`` / ``histogram`` cost one value shape each;
  ``full_trace`` streams (T, ...) and is capped by
  ``FULL_TRACE_ELEM_CAP`` with an eager, helpful error (mirroring the
  ``v_schedule`` validation style).

Solver *iteration budgets* are compile-time constants in this codebase
(fixed-budget safeguarded loops — see ``repro.core.solvers``), so they are
reported statically via :func:`solver_effort` (-> run manifests) while the
traced solver diagnostics are the *derived* per-round quantities:
allocation residual, b_min clamp count, and top-m saturation flags.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array

# Mirrors ``repro.core.selection._RHO_ZERO_TOL`` (the S0 membership
# threshold).  Kept as a local constant rather than an import so the core
# solver stack can depend on ``repro.obs`` (named spans) without a cycle;
# tests assert the two stay equal.
_RHO_ZERO_TOL = 1e-30

REDUCTIONS = ("last", "mean", "histogram", "full_trace", "full_trace_ds")

# Eager ceiling on any single full_trace stream: T * prod(value shape)
# elements (~134 MB as float32).  At the paper's T = 300 even K = 10^5
# fits; what this guards against is an accidental (T, K) trace on a
# long-horizon large-K sweep silently eating host memory.
FULL_TRACE_ELEM_CAP = 1 << 25

DEFAULT_HIST_BINS = 32

# Default slot budget of the ``full_trace_ds`` downsampled-trace
# reduction: the stream keeps at most this many strided samples no
# matter how long the horizon is, so long-horizon (T >> 1e4) sweeps can
# still record trace-shaped telemetry within a bounded accumulator.
DEFAULT_DS_SAMPLES = 256


def ds_stride(num_rounds: int, ds_samples: int) -> int:
    """Static sampling stride of ``full_trace_ds``: ceil(T / ds_samples).

    Rounds ``t`` with ``t % stride == 0`` are recorded, so the sampled
    indices are exactly ``ds_indices(T, ds_samples)`` and at most
    ``ds_samples`` slots exist.
    """
    return -(-int(num_rounds) // int(ds_samples))


def ds_indices(num_rounds: int, ds_samples: int):
    """The round indices ``full_trace_ds`` records (host-side helper).

    ``full_trace[ds_indices(T, n)] == full_trace_ds`` — the agreement
    contract pinned by ``tests/test_obs.py``.
    """
    import numpy as np

    stride = ds_stride(num_rounds, ds_samples)
    return np.arange(0, int(num_rounds), stride)


class RoundContext(NamedTuple):
    """Everything one OCEAN round exposes to the collectors (all traced).

    Assembled from the *outputs* of ``repro.core.ocean.ocean_round`` — the
    round math itself is never touched, which is what keeps ``spec=None``
    byte-identical.
    """

    t: Array             # scalar int32 round index
    q: Array             # (K,) queues as used by P3 (post frame-reset)
    q_next: Array        # (K,) queues after the update
    a: Array             # (K,) bool selections
    b: Array             # (K,) bandwidth ratios
    e: Array             # (K,) per-round energy
    rho: Array           # (K,) priorities q / h^2
    objective: Array     # scalar P3 optimum
    num_selected: Array  # scalar int
    energy_spent: Array  # (K,) cumulative energy *after* this round
    budget_inc: Array    # (K,) this round's queue drain
    v: Array             # scalar control parameter V
    eta: Array           # scalar temporal weight eta^t
    b_min: Array         # scalar bandwidth floor (traced radio compatible)
    # Failure extension (None without a failure process; the reliability
    # collectors fall back to their perfect-delivery values):
    delivered: Optional[Array] = None  # (K,) bool selected-and-delivered
    realloc: Optional[Array] = None    # () int32 mid-round P4 re-solve flag
    # Guard extension (None without a GuardSpec; the guard collectors
    # then report zeros — nothing was quarantined, demoted, or re-solved):
    fault_count: Optional[Array] = None  # () int32 quarantined draws
    demoted: Optional[Array] = None      # () int32 cap/floor demotions
    fallback: Optional[Array] = None     # () int32 bisect-fallback flag


def round_context(t, dec, new_state, v, eta, budget_inc, radio) -> RoundContext:
    """Build the collector view from one round's inputs and outputs."""
    return RoundContext(
        t=t,
        q=dec.q,
        q_next=new_state.q,
        a=dec.a,
        b=dec.b,
        e=dec.e,
        rho=dec.rho,
        objective=dec.objective,
        num_selected=dec.num_selected,
        energy_spent=new_state.energy_spent,
        budget_inc=budget_inc,
        v=jnp.asarray(v, jnp.float32),
        eta=jnp.asarray(eta, jnp.float32),
        b_min=jnp.asarray(radio.b_min, jnp.float32),
        delivered=getattr(dec, "delivered", None),
        realloc=getattr(dec, "realloc", None),
        fault_count=getattr(dec, "fault_count", None),
        demoted=getattr(dec, "demoted", None),
        fallback=getattr(dec, "fallback", None),
    )


class MetricsState(NamedTuple):
    """The metrics carry: per-collector state + per-entry accumulators.

    Both are dict pytrees (sorted-key flattening), so the whole struct
    rides a ``lax.scan`` carry, a ``vmap`` batch axis, or — leaf by leaf
    — the VMEM scratch of the fused trajectory kernel.
    """

    states: Dict[str, Any]
    accs: Dict[str, Array]


class Collector(NamedTuple):
    """One registered collector: a named per-round traced quantity."""

    name: str
    # per-round value shape as a function of K (scalar values use ())
    shape: Callable[[int], Tuple[int, ...]]
    # running-state init as a function of cfg (pytree; () if stateless)
    init: Callable[[Any], Any]
    # (cfg, ctx, state) -> (value, new_state)
    collect: Callable[[Any, RoundContext, Any], Tuple[Array, Any]]
    # static histogram support (cfg) -> (lo, hi); values clip into edge bins
    hist_range: Callable[[Any], Tuple[float, float]]
    doc: str


def _budget_hi(cfg) -> float:
    h = cfg.energy_budget_j
    return float(h if isinstance(h, (int, float)) else max(h))


def _f32(x: Array) -> Array:
    return jnp.asarray(x, jnp.float32)


# -- collector bodies -------------------------------------------------------
def _c_queue(cfg, ctx, state):
    return _f32(ctx.q), state


def _c_queue_next(cfg, ctx, state):
    return _f32(ctx.q_next), state


def _sum_sq(x):
    """sum(x * x) by pairwise halving, in one fixed association.

    A ``jnp.sum`` reduces through different trees in the scan step (run
    batched under the grid engine's vmaps) and in the fused kernel (run
    per cell), which left the two Lyapunov traces 1 ulp apart.
    """
    x = x * x
    n = x.shape[-1]
    x = jnp.pad(x, (0, (1 << max(n - 1, 0).bit_length()) - n))
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2
        x = x[:half] + x[half:]
    return x[0]


def _c_lyapunov(cfg, ctx, state):
    return 0.5 * _sum_sq(_f32(ctx.q)), state


def _c_lyapunov_drift(cfg, ctx, state):
    q, qn = _f32(ctx.q), _f32(ctx.q_next)
    return 0.5 * (_sum_sq(qn) - _sum_sq(q)), state


def _c_dpp_penalty(cfg, ctx, state):
    return ctx.v * ctx.eta * _f32(ctx.num_selected), state


def _c_dpp_drift(cfg, ctx, state):
    return jnp.sum(_f32(ctx.q) * _f32(ctx.e)), state


def _c_energy_headroom(cfg, ctx, state):
    cum_inc = state + _f32(ctx.budget_inc)
    return cum_inc - _f32(ctx.energy_spent), cum_inc


def _c_num_selected(cfg, ctx, state):
    return _f32(ctx.num_selected), state


def _c_selection_count(cfg, ctx, state):
    counts = state + _f32(ctx.a)
    return counts, counts


def _c_selection_gap(cfg, ctx, state):
    last_t, gap_sum, gap_n = state
    sel = ctx.a
    take = sel & (last_t >= 0)
    gap = _f32(ctx.t - last_t)
    gap_sum = gap_sum + jnp.where(take, gap, 0.0)
    gap_n = gap_n + jnp.where(take, 1.0, 0.0)
    last_t = jnp.where(sel, jnp.broadcast_to(ctx.t, last_t.shape), last_t)
    value = gap_sum / jnp.maximum(gap_n, 1.0)
    return value, (last_t, gap_sum, gap_n)


def _c_solver_residual(cfg, ctx, state):
    any_sel = _f32(ctx.num_selected > 0)
    return jnp.abs(jnp.sum(_f32(ctx.b)) - 1.0) * any_sel, state


def _c_bmin_active(cfg, ctx, state):
    clamped = ctx.a & (_f32(ctx.b) <= ctx.b_min * (1.0 + 1e-6))
    return jnp.sum(_f32(clamped)), state


def _c_topm_saturated(cfg, ctx, state):
    if cfg.ranking != "topm":
        return jnp.zeros((), jnp.float32), state
    m_cands = min(int(cfg.top_m), int(cfg.num_clients))
    n0 = jnp.sum(ctx.rho <= _RHO_ZERO_TOL)
    sat = (_f32(ctx.num_selected) - _f32(n0)) >= float(m_cands)
    return _f32(sat), state


def _c_delivery_rate(cfg, ctx, state):
    # Fraction of this round's selections whose update arrived; with no
    # failure process every selection delivers by definition.
    ns = _f32(ctx.num_selected)
    dlv = ns if ctx.delivered is None else jnp.sum(_f32(ctx.delivered))
    return dlv / jnp.maximum(ns, 1.0), state


def _c_wasted_energy(cfg, ctx, state):
    # Energy charged to selected-but-failed clients this round (the
    # pessimistic accounting: the virtual queue billed them anyway).
    if ctx.delivered is None:
        return jnp.zeros((), jnp.float32), state
    failed = ctx.a & ~ctx.delivered
    return jnp.sum(_f32(ctx.e) * _f32(failed)), state


def _c_reallocation_count(cfg, ctx, state):
    # Running count of mid-round P4 re-solves (failure_mode='reallocate').
    ral = 0.0 if ctx.realloc is None else _f32(ctx.realloc)
    count = state + ral
    return count, count


def _c_fault_count(cfg, ctx, state):
    # Running count of quarantined channel draws (repro.guard stream
    # sanitization); identically zero without a GuardSpec.
    faults = 0.0 if ctx.fault_count is None else _f32(ctx.fault_count)
    count = state + faults
    return count, count


def _c_demoted_clients(cfg, ctx, state):
    # Running count of bounded-energy admission demotions (energy cap /
    # gain floor); identically zero without a GuardSpec.
    dem = 0.0 if ctx.demoted is None else _f32(ctx.demoted)
    count = state + dem
    return count, count


def _c_fallback_rounds(cfg, ctx, state):
    # Running count of rounds the solver fallback cascade fired
    # (backend output rejected, bisect result committed).
    fb = 0.0 if ctx.fallback is None else _f32(ctx.fallback)
    count = state + fb
    return count, count


def _no_state(cfg):
    return ()


_COLLECTORS: Dict[str, Collector] = {}


def _register(name, shape, init, collect, hist_range, doc):
    _COLLECTORS[name] = Collector(name, shape, init, collect, hist_range, doc)


_register(
    "queue",
    lambda k: (k,),
    _no_state,
    _c_queue,
    lambda cfg: (0.0, _budget_hi(cfg)),
    "virtual energy-deficit queues q_k(t) as used by P3 (post frame-reset)",
)
_register(
    "queue_next",
    lambda k: (k,),
    _no_state,
    _c_queue_next,
    lambda cfg: (0.0, _budget_hi(cfg)),
    "queues after the round's update q_k(t+1) = [q + e - inc]^+",
)
_register(
    "lyapunov",
    lambda k: (),
    _no_state,
    _c_lyapunov,
    lambda cfg: (0.0, 0.5 * cfg.num_clients * _budget_hi(cfg) ** 2),
    "Lyapunov function L(t) = 0.5 * ||q(t)||^2",
)
_register(
    "lyapunov_drift",
    lambda k: (),
    _no_state,
    _c_lyapunov_drift,
    lambda cfg: (
        -0.5 * cfg.num_clients * _budget_hi(cfg) ** 2,
        0.5 * cfg.num_clients * _budget_hi(cfg) ** 2,
    ),
    "one-round Lyapunov drift 0.5 * (||q(t+1)||^2 - ||q(t)||^2)",
)
_register(
    "dpp_penalty",
    lambda k: (),
    _no_state,
    _c_dpp_penalty,
    lambda cfg: (0.0, 1e-3),
    "drift-plus-penalty utility term V * eta^t * |S^t|",
)
_register(
    "dpp_drift",
    lambda k: (),
    _no_state,
    _c_dpp_drift,
    lambda cfg: (0.0, 1e-3),
    "drift-plus-penalty queue-weighted energy term sum_k q_k * e_k",
)
_register(
    "energy_headroom",
    lambda k: (k,),
    lambda cfg: jnp.zeros((cfg.num_clients,), jnp.float32),
    _c_energy_headroom,
    lambda cfg: (-_budget_hi(cfg), _budget_hi(cfg)),
    "per-client budget headroom: cumulative allowance - cumulative spend",
)
_register(
    "num_selected",
    lambda k: (),
    _no_state,
    _c_num_selected,
    lambda cfg: (0.0, float(cfg.num_clients)),
    "realized selection cardinality |S^t|",
)
_register(
    "selection_count",
    lambda k: (k,),
    lambda cfg: jnp.zeros((cfg.num_clients,), jnp.float32),
    _c_selection_count,
    lambda cfg: (0.0, float(cfg.num_rounds)),
    "running per-client selection counts (the paper's §IV temporal patterns)",
)
_register(
    "selection_gap",
    lambda k: (k,),
    lambda cfg: (
        jnp.full((cfg.num_clients,), -1, jnp.int32),
        jnp.zeros((cfg.num_clients,), jnp.float32),
        jnp.zeros((cfg.num_clients,), jnp.float32),
    ),
    _c_selection_gap,
    lambda cfg: (0.0, float(cfg.num_rounds)),
    "running mean inter-selection gap per client (rounds between picks)",
)
_register(
    "solver_residual",
    lambda k: (),
    _no_state,
    _c_solver_residual,
    lambda cfg: (0.0, 1e-4),
    "P4 feasibility residual |sum_k b_k - 1| of the returned allocation",
)
_register(
    "bmin_active",
    lambda k: (),
    _no_state,
    _c_bmin_active,
    lambda cfg: (0.0, float(cfg.num_clients)),
    "selected clients pinned at the b_min bandwidth floor (clamp count)",
)
_register(
    "delivery_rate",
    lambda k: (),
    _no_state,
    _c_delivery_rate,
    lambda cfg: (0.0, 1.0),
    "fraction of selected clients whose update arrived (1.0 sans failures)",
)
_register(
    "wasted_energy",
    lambda k: (),
    _no_state,
    _c_wasted_energy,
    lambda cfg: (0.0, _budget_hi(cfg)),
    "energy charged to selected-but-failed clients this round",
)
_register(
    "reallocation_count",
    lambda k: (),
    lambda cfg: jnp.zeros((), jnp.float32),
    _c_reallocation_count,
    lambda cfg: (0.0, float(cfg.num_rounds)),
    "running count of mid-round P4 re-solves (failure_mode='reallocate')",
)
_register(
    "fault_count",
    lambda k: (),
    lambda cfg: jnp.zeros((), jnp.float32),
    _c_fault_count,
    lambda cfg: (0.0, float(cfg.num_rounds * cfg.num_clients)),
    "running count of quarantined (non-finite/non-positive) channel draws",
)
_register(
    "demoted_clients",
    lambda k: (),
    lambda cfg: jnp.zeros((), jnp.float32),
    _c_demoted_clients,
    lambda cfg: (0.0, float(cfg.num_rounds * cfg.num_clients)),
    "running count of bounded-energy admission demotions (cap/gain floor)",
)
_register(
    "fallback_rounds",
    lambda k: (),
    lambda cfg: jnp.zeros((), jnp.float32),
    _c_fallback_rounds,
    lambda cfg: (0.0, float(cfg.num_rounds)),
    "running count of rounds the solver fallback cascade committed bisect",
)
_register(
    "topm_saturated",
    lambda k: (),
    _no_state,
    _c_topm_saturated,
    lambda cfg: (0.0, 1.0),
    "1.0 when ranking='topm' admitted its full candidate prefix "
    "(the optimum may be truncated); always 0.0 under ranking='sort'",
)


def available_collectors() -> Tuple[str, ...]:
    return tuple(sorted(_COLLECTORS))


def get_collector(name: str) -> Collector:
    if name not in _COLLECTORS:
        raise ValueError(
            f"unknown metrics collector {name!r}; available: "
            f"{', '.join(available_collectors())} (see repro.obs.metrics)"
        )
    return _COLLECTORS[name]


def collector_table() -> Tuple[Tuple[str, str, str], ...]:
    """(name, shape, doc) rows for docs / ``benchmarks/report.py``."""
    rows = []
    for name in available_collectors():
        col = _COLLECTORS[name]
        shape = "(K,)" if col.shape(2) else "()"
        rows.append((name, shape, col.doc))
    return tuple(rows)


# -- the spec ---------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MetricsSpec:
    """Static selection of ``(collector, reduction)`` telemetry entries.

    A compiled-program static: it shapes the metrics carry and outputs, so
    every scenario of one grid must agree on it (the engine's must-agree
    check enforces this), and ``None`` means "no metrics" — the legacy
    programs, byte-identical.

    Attributes:
      collect:   ``((collector_name, reduction), ...)`` pairs; reductions
                 are ``last`` (final value), ``mean`` (running mean over T),
                 ``histogram`` (static-bin counts over all rounds/elements),
                 ``full_trace`` (the whole (T, ...) stream, capped by
                 ``FULL_TRACE_ELEM_CAP``), ``full_trace_ds`` (a strided
                 downsample of the stream — at most ``ds_samples`` slots,
                 recorded at rounds ``t % ds_stride(T, ds_samples) == 0``,
                 so trace-shaped telemetry stays bounded at T >> 1e4).
      hist_bins: number of histogram bins (collector-specific static
                 support; out-of-range values clip into the edge bins).
      ds_samples: slot budget of every ``full_trace_ds`` entry (the
                 sampling stride derives statically from T).
    """

    collect: Tuple[Tuple[str, str], ...]
    hist_bins: int = DEFAULT_HIST_BINS
    ds_samples: int = DEFAULT_DS_SAMPLES

    def __post_init__(self):
        entries = tuple((str(n), str(r)) for n, r in self.collect)
        object.__setattr__(self, "collect", entries)
        seen = set()
        for name, red in entries:
            get_collector(name)  # fail fast on unknown collector names
            if red not in REDUCTIONS:
                raise ValueError(
                    f"unknown metrics reduction {red!r} for collector "
                    f"{name!r}; available: {', '.join(REDUCTIONS)}"
                )
            if (name, red) in seen:
                raise ValueError(
                    f"duplicate metrics entry ({name!r}, {red!r}); each "
                    f"(collector, reduction) pair may appear once"
                )
            seen.add((name, red))
        if self.hist_bins < 2:
            raise ValueError(f"hist_bins={self.hist_bins} must be >= 2")
        if self.ds_samples < 1:
            raise ValueError(
                f"ds_samples={self.ds_samples} must be >= 1 (it is the "
                f"slot budget of every full_trace_ds entry)"
            )

    @classmethod
    def of(
        cls,
        *entries: str,
        hist_bins: int = DEFAULT_HIST_BINS,
        ds_samples: int = DEFAULT_DS_SAMPLES,
    ) -> "MetricsSpec":
        """Parse ``"collector:reduction"`` strings, e.g.
        ``MetricsSpec.of("queue:full_trace", "lyapunov_drift:mean")``."""
        pairs = []
        for s in entries:
            name, sep, red = s.partition(":")
            if not sep:
                raise ValueError(
                    f"metrics entry {s!r} must be 'collector:reduction' "
                    f"(e.g. 'queue:full_trace')"
                )
            pairs.append((name, red))
        return cls(collect=tuple(pairs), hist_bins=hist_bins, ds_samples=ds_samples)

    def validate(self, num_rounds: int, num_clients: int) -> "MetricsSpec":
        """Eager memory check at lowering: full traces must stay bounded.

        Mirrors the ``v_schedule`` style — a helpful error *before* the
        program traces, not an OOM after.
        """
        for name, red in self.collect:
            if red not in ("full_trace", "full_trace_ds"):
                continue
            shape = get_collector(name).shape(num_clients)
            if red == "full_trace_ds":
                # Bounded by construction (<= ds_samples slots) — but the
                # slot budget itself still honors the memory cap.
                elems = min(self.ds_samples, num_rounds)
            else:
                elems = num_rounds
            for d in shape:
                elems *= d
            if elems > FULL_TRACE_ELEM_CAP:
                raise ValueError(
                    f"metrics entry ('{name}', '{red}') would stream "
                    f"{elems} elements (T={num_rounds} x shape {shape}), "
                    f"above the FULL_TRACE_ELEM_CAP={FULL_TRACE_ELEM_CAP} "
                    f"memory cap; record a bounded reduction instead "
                    f"('last'/'mean'/'histogram'), shorten the horizon, or "
                    f"trace a scalar collector"
                )
        return self

    @property
    def names(self) -> Tuple[str, ...]:
        """Unique collector names, in first-appearance order."""
        out = []
        for name, _ in self.collect:
            if name not in out:
                out.append(name)
        return tuple(out)

    @property
    def full_trace_entries(self) -> Tuple[str, ...]:
        return tuple(n for n, r in self.collect if r == "full_trace")

    # -- serialization (rides on Scenario.to_dict) --------------------------
    def to_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {"collect": [list(p) for p in self.collect]}
        if self.hist_bins != DEFAULT_HIST_BINS:
            d["hist_bins"] = self.hist_bins
        if self.ds_samples != DEFAULT_DS_SAMPLES:
            d["ds_samples"] = self.ds_samples
        return d

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "MetricsSpec":
        return cls(
            collect=tuple(tuple(p) for p in d.get("collect", ())),
            hist_bins=int(d.get("hist_bins", DEFAULT_HIST_BINS)),
            ds_samples=int(d.get("ds_samples", DEFAULT_DS_SAMPLES)),
        )


def metric_key(name: str, reduction: str) -> str:
    """The output-dict key of one spec entry, ``"<collector>/<reduction>"``."""
    return f"{name}/{reduction}"


# -- the traced machinery ---------------------------------------------------
def init_metrics(spec: MetricsSpec, cfg) -> MetricsState:
    """Zero-initialized metrics carry for one trajectory."""
    states = {name: get_collector(name).init(cfg) for name in spec.names}
    accs: Dict[str, Array] = {}
    for name, red in spec.collect:
        if red == "full_trace":
            continue  # streamed, not accumulated
        key = metric_key(name, red)
        if red == "histogram":
            accs[key] = jnp.zeros((spec.hist_bins,), jnp.float32)
        elif red == "full_trace_ds":
            # A (n_slots,)+shape scatter accumulator riding the carry —
            # bounded at any horizon, and because it is an ordinary accs
            # leaf it flows through the fused kernel's generic metrics
            # scratch with zero kernel changes.
            stride = ds_stride(cfg.num_rounds, spec.ds_samples)
            n_slots = -(-cfg.num_rounds // stride)
            shape = get_collector(name).shape(cfg.num_clients)
            accs[key] = jnp.zeros((n_slots,) + shape, jnp.float32)
        else:
            shape = get_collector(name).shape(cfg.num_clients)
            accs[key] = jnp.zeros(shape, jnp.float32)
    return MetricsState(states=states, accs=accs)


def metrics_round(
    spec: MetricsSpec,
    cfg,
    ctx: RoundContext,
    mstate: MetricsState,
    valid: Array = True,
) -> Tuple[MetricsState, Dict[str, Array]]:
    """Collect one round: update states/accumulators, emit full-trace values.

    ``valid`` masks the carry updates on chunk-padded tail rounds of the
    fused kernel (their math runs on edge-replicated inputs but must not
    pollute the telemetry); the scan path always passes True.
    """
    valid = jnp.asarray(valid, bool)
    values: Dict[str, Array] = {}
    states: Dict[str, Any] = {}
    for name in spec.names:
        col = get_collector(name)
        value, new_state = col.collect(cfg, ctx, mstate.states[name])
        values[name] = value
        states[name] = jax.tree_util.tree_map(
            lambda n, o: jnp.where(valid, n, o), new_state, mstate.states[name]
        )

    accs = dict(mstate.accs)
    traces: Dict[str, Array] = {}
    for name, red in spec.collect:
        value = values[name]
        if red == "full_trace":
            traces[metric_key(name, red)] = value
            continue
        key = metric_key(name, red)
        acc = accs[key]
        if red == "last":
            accs[key] = jnp.where(valid, value, acc)
        elif red == "mean":
            accs[key] = acc + jnp.where(valid, value, jnp.zeros_like(value))
        elif red == "full_trace_ds":
            stride = ds_stride(cfg.num_rounds, spec.ds_samples)
            slot = ctx.t // stride
            take = valid & (jnp.mod(ctx.t, stride) == 0)
            accs[key] = acc.at[slot].set(
                jnp.where(take, _f32(value), acc[slot])
            )
        else:  # histogram
            lo, hi = get_collector(name).hist_range(cfg)
            width = (hi - lo) / spec.hist_bins
            idx = jnp.clip(
                jnp.floor((_f32(value) - lo) / width).astype(jnp.int32),
                0,
                spec.hist_bins - 1,
            )
            weight = jnp.where(valid, 1.0, 0.0)
            accs[key] = acc.at[idx].add(
                jnp.broadcast_to(weight, jnp.shape(idx))
            )
    return MetricsState(states=states, accs=accs), traces


def finalize_metrics(
    spec: MetricsSpec,
    cfg,
    mstate: MetricsState,
    traces: Optional[Dict[str, Array]] = None,
) -> Dict[str, Array]:
    """Resolve accumulators (+ stacked traces) into the output metrics dict."""
    out: Dict[str, Array] = {}
    for name, red in spec.collect:
        key = metric_key(name, red)
        if red == "full_trace":
            if traces is None or key not in traces:
                raise ValueError(
                    f"metrics entry {key!r} is a full trace but no streamed "
                    f"trace was provided to finalize_metrics"
                )
            out[key] = traces[key]
        elif red == "mean":
            out[key] = mstate.accs[key] / float(cfg.num_rounds)
        else:  # last / histogram / full_trace_ds: the accumulator itself
            out[key] = mstate.accs[key]
    return out


def solver_effort(cfg) -> Dict[str, Any]:
    """Static solver-effort report (iteration budgets are compile-time).

    The safeguarded P4 loops run *fixed* iteration budgets (bisect:
    42 x 42; newton: the dtype/K-bucketed ``newton_iteration_budgets``
    table), so per-round "iteration counts" are constants of the program,
    not traced quantities — they belong in the run manifest, while the
    traced diagnostics (``solver_residual`` / ``bmin_active`` /
    ``topm_saturated``) capture the data-dependent behavior.
    """
    out: Dict[str, Any] = {
        "solver": cfg.solver,
        "ranking": cfg.ranking,
        "outer_iters": 42,
        "inner_iters": 42,
    }
    if cfg.solver in ("newton", "pallas", "pallas_tiled"):
        from repro.core.solvers import newton_iteration_budgets

        outer, inner, grid = newton_iteration_budgets(
            jnp.float32, cfg.num_clients
        )
        out.update(outer_iters=outer, inner_iters=inner, seed_grid=grid)
    if cfg.ranking == "topm":
        out["top_m"] = int(cfg.top_m)
    return out
