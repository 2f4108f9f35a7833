"""Channel processes — the stochastic environment behind the (T, K) gains.

Every registered :class:`ChannelProcess` *lowers* a JSON-able parameter
dict to one shared :class:`ChannelParams` pytree, and a single
``lax.scan`` step (:func:`sample_channel_process`) interprets that pytree.
Because the program is the same for every process — only the *array*
parameters differ — a grid engine can vmap heterogeneous environments
(i.i.d. cells next to Markov-fading cells next to mobile clients) and
still compile exactly one executable.

Processes
---------
``iid_rayleigh``
    The paper's block-fading model: ``h^2 = g * X`` with ``X ~ Exp(1)``
    redrawn i.i.d. every round around the scheduled mean path loss.
    Bit-identical to the legacy ``ChannelModel.sample`` (same uniform
    stream, same ``-log(u)`` transform, same gain multiply).
``gauss_markov``
    AR(1)-correlated fading with per-client coherence ``rho`` via a
    Gaussian copula: the latent ``z_t = rho z_{t-1} + sqrt(1-rho^2) w_t``
    is pushed through ``ndtr`` so the *marginal* stays exactly Exp(1)
    while consecutive rounds correlate.  ``rho = 0`` short-circuits to
    the raw uniform stream and is therefore bit-identical to
    ``iid_rayleigh``.
``markov_shadowing``
    A 2-state LOS/NLOS blockage chain (enter/exit probabilities, extra
    NLOS loss in dB) layered on top of the fading; the chain starts from
    its stationary distribution so the declared mean gain is exact.
``mobility``
    Random-waypoint client trajectories around the server: distance-based
    log-path-loss generalizes the scenario-1/2 linear drifts (clients
    actually move away from / toward the base station instead of
    following a scripted dB ramp).
``uma_38901``
    One sector of a 3GPP TR 38.901 (V16.1.0) urban-macro site: clients
    roam a 120-degree wedge by random waypoint, each round's LOS state
    is a spatially consistent draw against Pr_LOS(d2D) (Table 7.4.2-1),
    the path loss is Table 7.4.1-1's LOS or NLOS law on the 3D distance,
    with log-normal shadow fading (Table 7.5-6) correlated over the
    path a client covers at its speed, under the paper's Exp(1) block
    fading.

Randomness is split into two independent streams: the *fading* stream
(keyed exactly like the legacy path, shared across scenarios) and the
*environment* stream (shadowing chain, waypoints, initial states), which
callers derive by folding a stable per-scenario salt into the seed key —
see ``repro.env.spec.env_key_salt``.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Mapping, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import ndtr, ndtri

from repro.obs.spans import trace_span

Array = jax.Array


# NOTE: these two primitives are defined here — the leaf of the import
# graph — and re-exported by ``repro.core.channel``, so ``repro.env`` is
# importable on its own (env never imports repro.core at module level,
# which would cycle through repro.core.__init__ back into repro.env).
def pathloss_to_gain(pl_db: Array) -> Array:
    """Mean channel power gain g = 10^{-PL_dB/10}."""
    return jnp.power(10.0, -jnp.asarray(pl_db, jnp.float32) / 10.0)


def pathloss_schedule(start_db: float, end_db: float, num_rounds: int) -> Array:
    """(T,) scheduled mean path loss; equal endpoints => constant.

    Bit-identical to evaluating ``constant_pathloss``/``linear_pathloss``
    (repro.core.channel) on ``arange(T)``, so environment processes that
    embed the schedule as an array reproduce the callable-based legacy
    path exactly.
    """
    t = jnp.arange(num_rounds)
    if start_db == end_db:
        return jnp.full(jnp.shape(t), start_db, jnp.float32)
    frac = jnp.asarray(t, jnp.float32) / max(num_rounds - 1, 1)
    return start_db + (end_db - start_db) * frac


class LowerCtx(NamedTuple):
    """Static scenario facts a process lowering may fall back on.

    Attributes:
      num_rounds:  T.
      num_clients: K.
      pathloss_db: the scenario's (start_db, end_db) scheduled drift.
      fading:      the scenario's legacy fading flag.
      budgets_j:   (K,) per-client total energy budgets H_k.
      radio:       the scenario's base radio physics — any object exposing
                   ``bandwidth_hz``/``noise_w``/``deadline_s``/``model_bits``/
                   ``b_min`` attributes (duck-typed so ``repro.env`` never
                   imports ``repro.core``; in practice a
                   ``repro.core.energy.RadioParams``).  ``None`` falls back
                   to the paper's §VI defaults.
    """

    num_rounds: int
    num_clients: int
    pathloss_db: Tuple[float, float] = (36.0, 36.0)
    fading: bool = True
    budgets_j: Tuple[float, ...] = (0.15,)
    radio: Any = None


class ChannelParams(NamedTuple):
    """Unified, vmappable parameterization of every channel process.

    All leaves are float32 arrays so parameters stack across the scenario
    axis of a grid; "off" features are encoded as zeros, never as
    structurally different pytrees.
    """

    sched_pl_db: Array     # (T,) scheduled mean path loss (mobility off)
    sched_gain: Array      # (T,) 10^{-pl/10}, precomputed *eagerly* at
                           #     lowering time: XLA re-derives pow() with
                           #     different rounding when it is fused into a
                           #     larger program, so the scheduled branch
                           #     must reuse these exact bits to stay
                           #     bit-identical to the legacy channel
    fading_on: Array       # ()  1.0 => Exp(1) power fading, 0.0 => mean only
    rho: Array             # (K,) AR(1) fading coherence; 0 => i.i.d.
    shadow_on: Array       # ()  1.0 => apply the LOS/NLOS chain
    shadow_p_enter: Array  # ()  P(LOS -> NLOS) per round
    shadow_p_exit: Array   # ()  P(NLOS -> LOS) per round
    shadow_db: Array       # ()  extra path loss while blocked (dB)
    mobility_on: Array     # ()  1.0 => distance-based path loss
    area_m: Array          # ()  clients roam [-area, area]^2 around server
    speed_min: Array       # ()  m/s, random-waypoint leg speed range
    speed_max: Array       # ()
    round_s: Array         # ()  wall-clock seconds per round (step length)
    pl_exp: Array          # ()  path-loss exponent n
    pl_ref_db: Array       # ()  path loss at the reference distance
    d_ref_m: Array         # ()  reference distance (also the min distance)
    uma_on: Array          # ()  1.0 => TR 38.901 UMa sector (below)
    fc_ghz: Array          # ()  carrier frequency (GHz)
    h_bs_m: Array          # ()  BS antenna height
    h_ut_m: Array          # ()  UT antenna height
    cell_r_m: Array        # ()  sector radius, ISD / sqrt(3)
    d_min_m: Array         # ()  minimum BS-UT 2D distance


def _f32(x) -> Array:
    return jnp.asarray(x, jnp.float32)


def _per_client(x, num_clients: int) -> Array:
    return jnp.broadcast_to(_f32(x), (num_clients,))


def _validate_rho(rho) -> None:
    """|rho| < 1, else sqrt(1 - rho^2) silently NaNs every gain."""
    vals = np.atleast_1d(np.asarray(rho, np.float64))
    if not np.all(np.isfinite(vals)) or np.any(np.abs(vals) >= 1.0):
        raise ValueError(
            f"fading coherence rho must satisfy |rho| < 1, got {rho!r}"
        )


def _validate_prob(name: str, p: float) -> None:
    if not 0.0 <= p <= 1.0:
        raise ValueError(f"{name} must be a probability in [0, 1], got {p}")


def check_spec_keys(process: str, spec: Mapping[str, Any], allowed) -> None:
    """Reject unknown parameter keys so typos fail fast instead of being
    silently replaced by defaults."""
    unknown = sorted(set(spec) - set(allowed))
    if unknown:
        raise ValueError(
            f"unknown parameter(s) {unknown} for process {process!r}; "
            f"allowed: {', '.join(sorted(allowed))}"
        )


_BASE_KEYS = ("pathloss_db", "fading")


_OFF = dict(
    fading_on=1.0,
    shadow_on=0.0,
    shadow_p_enter=0.0,
    shadow_p_exit=1.0,
    shadow_db=0.0,
    mobility_on=0.0,
    area_m=60.0,
    speed_min=1.0,
    speed_max=10.0,
    round_s=1.0,
    pl_exp=2.0,
    pl_ref_db=32.0,
    d_ref_m=10.0,
    uma_on=0.0,
    fc_ghz=3.5,
    h_bs_m=25.0,
    h_ut_m=1.5,
    cell_r_m=500.0 / np.sqrt(3.0),
    d_min_m=35.0,
)


def _base_params(ctx: LowerCtx, spec: Mapping[str, Any], **overrides) -> ChannelParams:
    """Everything-off defaults with the scenario's scheduled path loss."""
    start, end = tuple(spec.get("pathloss_db", ctx.pathloss_db))
    fields: Dict[str, Any] = dict(_OFF)
    fields["fading_on"] = 1.0 if spec.get("fading", ctx.fading) else 0.0
    fields.update(overrides)
    _validate_rho(fields.get("rho", 0.0))
    sched = pathloss_schedule(start, end, ctx.num_rounds)
    return ChannelParams(
        sched_pl_db=sched,
        sched_gain=pathloss_to_gain(sched),
        rho=_per_client(fields.pop("rho", 0.0), ctx.num_clients),
        **{k: _f32(v) for k, v in fields.items()},
    )


def _norm(v: Array) -> Array:
    return jnp.sqrt(jnp.sum(v**2, axis=-1))


# --------------------------------------------------------------------------
# 3GPP TR 38.901 V16.1.0, urban macro (UMa)
# --------------------------------------------------------------------------
UMA_SECTOR_HALF_RAD = np.pi / 3.0   # a three-sector site: 120-degree wedges
UMA_LOS_DCOR_M = 50.0               # Table 7.6.3.1-2: LOS-state correlation
UMA_SF_DB = (4.0, 6.0)              # Table 7.5-6: shadow-fading sigma, LOS/NLOS
UMA_SF_DCOR_M = (37.0, 50.0)        # Table 7.5-6: its correlation distance
UMA_PROCESS = "uma_38901"
# A fold_in (not a wider split of the env key) keeps every other process's
# draws bit-identical.
_UMA_STREAM = 0x554D_6138  # "UMa8"


def uma_los_probability(d2d: Array) -> Array:
    """Table 7.4.2-1, UMa, with C'(h_UT) = 0 (h_UT <= 13 m), in the
    precision of ``d2d`` (float32 unless 64-bit types are on)."""
    d2d = jnp.asarray(d2d, dtype=jnp.result_type(float))
    far = 18.0 / d2d + jnp.exp(-d2d / 63.0) * (1.0 - 18.0 / d2d)
    return jnp.where(d2d <= 18.0, 1.0, far)


def uma_pathloss_db(d3d: Array, fc_ghz: Array, h_ut_m: Array) -> Tuple[Array, Array]:
    """Table 7.4.1-1, UMa: (PL_LOS, PL_NLOS) in dB for 2D distances below
    the breakpoint d'_BP (PL_1), ``fc_ghz`` in GHz and distances in m."""
    lg = jnp.log10(_f32(d3d))
    f_db = 20.0 * jnp.log10(_f32(fc_ghz))
    pl_los = 28.0 + 22.0 * lg + f_db
    pl_nlos_p = 13.54 + 39.08 * lg + f_db - 0.6 * (_f32(h_ut_m) - 1.5)
    return pl_los, jnp.maximum(pl_los, pl_nlos_p)


def _uma_breakpoint_m(fc_ghz: float, h_bs_m: float, h_ut_m: float) -> float:
    """d'_BP = 4 h'_BS h'_UT fc / c with h_E = 1 m (Table 7.4.1-1, note 1)."""
    return 4.0 * (h_bs_m - 1.0) * (h_ut_m - 1.0) * fc_ghz * 1e9 / 299792458.0


class UmaPath(NamedTuple):
    """The UMa sector over T rounds, per client."""

    pl_db: Array      # (T, K) float32 path loss plus shadow fading
    los: Array        # (T, K) bool LOS state
    los_margin: Array  # (T, K) float64 ndtr(z_los) - Pr_LOS: LOS below 0
    z: Array          # (T, K, 3) float64 latent fields: LOS state, SF if LOS, SF if NLOS
    pos: Array        # (T, K, 2) float64 position (m), the BS at the origin
    arrived: Array    # (T, K) bool: reaches its waypoint on the move to the next round


def _uma_sector(params: "ChannelParams", env_key: Array, u_pos0: Array,
                u_wp0: Array, u_wp: Array) -> UmaPath:
    """The sector's T rounds for K clients: drops and waypoints from the
    interpreter's waypoint uniforms (``u_pos0``, ``u_wp0``: (K, 2);
    ``u_wp``: (T, K, 3), the third unused), latent fields from a stream
    folded off ``env_key``.

    Each round the loss is taken at the clients' places, then each moves
    ``speed * round_s`` toward its waypoint (one within that stops there
    and draws the next), kept at d2D >= d_min, and the latent fields are
    carried over ``speed * round_s``, the path a client covers in a round.
    A client is ``steps`` whole steps from ``start`` on its straight leg to
    its waypoint, so its place is not a sum of rounded moves.

    Places, fields and the LOS decision are computed in float64 from the
    float32 draws and parameters: the LOS state and a waypoint arrival are
    thresholds on continuous values, and float32's rounding would put some
    of them on the other side of a float64 law's (a LOS flip moves the gain
    by 13 dB or more).  The loss itself is float32."""
    T, K = u_wp.shape[:2]
    ku_n, ku_z = jax.random.split(jax.random.fold_in(env_key, _UMA_STREAM))
    n = jax.random.normal(ku_n, (T, K, 3), jnp.float32)
    z0 = jax.random.normal(ku_z, (K, 3), jnp.float32)
    with jax.enable_x64(True):
        f64 = jnp.float64
        d_min = params.d_min_m.astype(f64)
        r2_lo = d_min**2
        r2_hi = params.cell_r_m.astype(f64) ** 2

        def wedge(u):
            """Points uniform over the sector's area: d2D in [d_min,
            cell_r], azimuth within +-60 degrees of boresight (+x)."""
            u = u.astype(f64)
            r = jnp.sqrt(r2_lo + u[..., 0] * (r2_hi - r2_lo))
            phi = (2.0 * u[..., 1] - 1.0) * UMA_SECTOR_HALF_RAD
            return jnp.stack([r * jnp.cos(phi), r * jnp.sin(phi)], axis=-1)

        step_m = params.speed_min.astype(f64) * params.round_s.astype(f64)
        dcor = jnp.asarray((UMA_LOS_DCOR_M, *UMA_SF_DCOR_M), f64)
        rho = jnp.exp(-step_m / dcor)
        keep = jnp.sqrt(1.0 - rho**2)

        def move(carry, xs):
            start, wp, steps, z = carry
            wp_next, n_t = xs
            delta = wp - start
            leg = _norm(delta)
            per_m = 1.0 / jnp.maximum(leg, 1e-9)
            pos = start + delta * (steps * step_m * per_m)[:, None]
            arrive = leg - steps * step_m <= step_m
            ahead = start + delta * ((steps + 1.0) * step_m * per_m)[:, None]
            moved = jnp.where(arrive[:, None], wp, ahead)
            r = _norm(moved)
            inside = r < d_min
            start = jnp.where(arrive[:, None], wp, start)
            start = jnp.where(inside[:, None], moved * (d_min / r)[:, None], start)
            steps = jnp.where(arrive | inside, 0.0, steps + 1.0)
            wp = jnp.where(arrive[:, None], wp_next, wp)
            z_new = rho * z + keep * n_t.astype(f64)
            return (start, wp, steps, z_new), (pos, z, arrive)

        carry0 = (wedge(u_pos0), wedge(u_wp0), jnp.zeros((K,), f64), z0.astype(f64))
        _, (pos, z, arrived) = jax.lax.scan(move, carry0, (wedge(u_wp), n))
        d2d = _norm(pos)
        # ndtr(z) as erfc(-z / sqrt(2)) / 2: the same function, and a
        # fraction of the float64 code ``ndtr`` gives a TPU to compile.
        margin = 0.5 * jax.lax.erfc(z[..., 0] * -np.sqrt(0.5)) - uma_los_probability(d2d)
        los = margin < 0.0
        d3d = jnp.sqrt(d2d**2 + (params.h_bs_m.astype(f64) - params.h_ut_m.astype(f64)) ** 2)
        z32 = z.astype(jnp.float32)
    pl_los, pl_nlos = uma_pathloss_db(d3d.astype(jnp.float32), params.fc_ghz,
                                      params.h_ut_m)
    pl = jnp.where(los, pl_los + UMA_SF_DB[0] * z32[..., 1],
                   pl_nlos + UMA_SF_DB[1] * z32[..., 2])
    return UmaPath(pl, los, margin, z, pos, arrived)


# --------------------------------------------------------------------------
# the single interpreter: one lax.scan evaluates every registered process
# --------------------------------------------------------------------------
def sample_channel_process(
    params: ChannelParams,
    fade_key: Array,
    env_key: Array,
    num_rounds: int,
    num_clients: int,
    uma: bool = True,
) -> Array:
    """Draw the (T, K) matrix of channel power gains h^2.

    ``fade_key`` feeds the i.i.d. uniform stream exactly as the legacy
    ``ChannelModel.sample`` did (so ``iid_rayleigh`` is bit-identical);
    ``env_key`` feeds every scenario-specific stream (blockage chain,
    waypoints, initial states) and must be derived via a stable
    per-scenario salt so grid composition never perturbs other cells.
    ``uma=False`` (a Python static) leaves the UMa sector's float64 path
    out of the program, for a caller that knows no cell it samples is a
    sector; the gains are the same, since ``uma_on`` is 0 in every such
    cell.
    """
    T, K = num_rounds, num_clients
    u_fade = jax.random.uniform(fade_key, (T, K), minval=1e-6, maxval=1.0)
    # The i.i.d. transform is applied to the whole matrix *before* the
    # scan — the exact op sequence of ``ChannelModel.sample`` — so the
    # rho == 0 branch below reuses those bits verbatim.
    x_iid = -jnp.log(u_fade)
    w_fade = ndtri(u_fade)

    k_shadow, k_wp, k_init = jax.random.split(env_key, 3)
    u_shadow = jax.random.uniform(k_shadow, (T, K))
    u_wp = jax.random.uniform(k_wp, (T, K, 3))
    ki_pos, ki_wp, ki_speed, ki_z, ki_s = jax.random.split(k_init, 5)

    u_pos0 = jax.random.uniform(ki_pos, (K, 2))
    u_wp0 = jax.random.uniform(ki_wp, (K, 2))
    pos0 = (u_pos0 * 2.0 - 1.0) * params.area_m
    wp0 = (u_wp0 * 2.0 - 1.0) * params.area_m
    speed0 = params.speed_min + (
        params.speed_max - params.speed_min
    ) * jax.random.uniform(ki_speed, (K,))
    z0 = jax.random.normal(ki_z, (K,))  # stationary AR(1) start
    pi_nlos = params.shadow_p_enter / jnp.maximum(
        params.shadow_p_enter + params.shadow_p_exit, 1e-12
    )
    s0 = (jax.random.uniform(ki_s, (K,)) < pi_nlos).astype(jnp.float32)

    if uma:
        with trace_span("env/uma_38901"):
            # The sector reads the waypoint uniforms above through its own
            # geometry; its latent fields come from a stream of their own.
            pl_uma = _uma_sector(params, env_key, u_pos0, u_wp0, u_wp).pl_db
    else:
        pl_uma = jnp.zeros((T, K), jnp.float32)

    def step(carry, xs):
        z, s, pos, wp, speed = carry
        x_t, w_t, u_s, u_w, pl_uma_t, pl_sched_t, g_sched_t = xs

        # Fading: Gaussian-copula AR(1); rho == 0 takes the precomputed
        # i.i.d. stream so that case matches the legacy draw bit-for-bit.
        z_new = params.rho * z + jnp.sqrt(1.0 - params.rho**2) * w_t
        u_corr = jnp.clip(ndtr(z_new), 1e-6, 1.0 - 1e-7)
        x = jnp.where(params.rho == 0.0, x_t, -jnp.log(u_corr))
        x = jnp.where(params.fading_on > 0.0, x, 1.0)

        # LOS/NLOS blockage chain.
        p_flip = jnp.where(s > 0.0, params.shadow_p_exit, params.shadow_p_enter)
        s_new = jnp.where(u_s < p_flip, 1.0 - s, s)
        extra_db = jnp.where(params.shadow_on > 0.0, s_new * params.shadow_db, 0.0)

        # Random-waypoint mobility.
        delta = wp - pos
        dist = jnp.sqrt(jnp.sum(delta**2, axis=-1))
        step_m = speed * params.round_s
        arrive = dist <= step_m
        unit = delta / jnp.maximum(dist, 1e-9)[:, None]
        pos_new = jnp.where(arrive[:, None], wp, pos + unit * step_m[:, None])
        wp_new = jnp.where(
            arrive[:, None], (u_w[:, :2] * 2.0 - 1.0) * params.area_m, wp
        )
        speed_new = jnp.where(
            arrive,
            params.speed_min + (params.speed_max - params.speed_min) * u_w[:, 2],
            speed,
        )
        d = jnp.maximum(jnp.sqrt(jnp.sum(pos_new**2, axis=-1)), params.d_ref_m)
        pl_mob = params.pl_ref_db + 10.0 * params.pl_exp * jnp.log10(d / params.d_ref_m)

        # Scheduled-only scenarios must reuse the eagerly computed gain:
        # an in-program pow(10, .) rounds differently once XLA fuses it.
        pl = jnp.where(params.mobility_on > 0.0, pl_mob, pl_sched_t) + extra_db
        pl = jnp.where(params.uma_on > 0.0, pl_uma_t, pl)
        exact_sched = (
            (params.mobility_on == 0.0)
            & (params.shadow_on == 0.0)
            & (params.uma_on == 0.0)
        )
        g = jnp.where(exact_sched, g_sched_t, pathloss_to_gain(pl))
        h2 = g * x
        return (z_new, s_new, pos_new, wp_new, speed_new), h2

    carry0 = (z0, s0, pos0, wp0, speed0)
    _, h2 = jax.lax.scan(
        step,
        carry0,
        (x_iid, w_fade, u_shadow, u_wp, pl_uma, params.sched_pl_db,
         params.sched_gain),
    )
    return h2


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------
LowerFn = Callable[[Mapping[str, Any], LowerCtx], ChannelParams]
MeanGainFn = Callable[[Mapping[str, Any], LowerCtx], Optional[Array]]


class ChannelProcess(NamedTuple):
    """A registered environment process.

    Attributes:
      name:      registry key (the ``EnvSpec.channel`` string).
      lower:     (params dict, ctx) -> ChannelParams for the interpreter.
      mean_gain: (params dict, ctx) -> (T,) closed-form mean of h^2, or
                 None when no closed form exists (e.g. mobility).
      doc:       one-line description for tables/docs.
    """

    name: str
    lower: LowerFn
    mean_gain: Optional[MeanGainFn] = None
    doc: str = ""


_CHANNEL_REGISTRY: Dict[str, ChannelProcess] = {}


def register_channel_process(
    name: str,
    lower: LowerFn,
    *,
    mean_gain: Optional[MeanGainFn] = None,
    doc: str = "",
) -> ChannelProcess:
    proc = ChannelProcess(name, lower, mean_gain, doc)
    _CHANNEL_REGISTRY[name] = proc
    return proc


def available_channel_processes() -> Tuple[str, ...]:
    return tuple(sorted(_CHANNEL_REGISTRY))


def get_channel_process(name: str) -> ChannelProcess:
    if name not in _CHANNEL_REGISTRY:
        raise ValueError(
            f"unknown channel process {name!r}; available: "
            f"{', '.join(available_channel_processes())}"
        )
    return _CHANNEL_REGISTRY[name]


# -- registry entries -------------------------------------------------------
def _sched_mean_gain(spec: Mapping[str, Any], ctx: LowerCtx) -> Array:
    start, end = tuple(spec.get("pathloss_db", ctx.pathloss_db))
    return pathloss_to_gain(pathloss_schedule(start, end, ctx.num_rounds))


def _iid_lower(spec, ctx):
    check_spec_keys("iid_rayleigh", spec, _BASE_KEYS)
    return _base_params(ctx, spec)


def _gauss_markov_lower(spec, ctx):
    check_spec_keys("gauss_markov", spec, _BASE_KEYS + ("rho",))
    rho = spec.get("rho", 0.9)
    if isinstance(rho, Sequence) and len(rho) != ctx.num_clients:
        raise ValueError(
            f"gauss_markov per-client rho needs {ctx.num_clients} entries, "
            f"got {len(rho)}"
        )
    return _base_params(ctx, spec, rho=jnp.asarray(rho, jnp.float32))


def _shadowing_lower(spec, ctx):
    check_spec_keys(
        "markov_shadowing", spec, _BASE_KEYS + ("rho", "p_enter", "p_exit", "extra_db")
    )
    p_enter = float(spec.get("p_enter", 0.1))
    p_exit = float(spec.get("p_exit", 0.4))
    _validate_prob("markov_shadowing p_enter", p_enter)
    _validate_prob("markov_shadowing p_exit", p_exit)
    return _base_params(
        ctx,
        spec,
        rho=jnp.asarray(spec.get("rho", 0.0), jnp.float32),
        shadow_on=1.0,
        shadow_p_enter=p_enter,
        shadow_p_exit=p_exit,
        shadow_db=float(spec.get("extra_db", 8.0)),
    )


def _shadowing_mean_gain(spec, ctx):
    g = _sched_mean_gain(spec, ctx)
    p_enter = float(spec.get("p_enter", 0.1))
    p_exit = float(spec.get("p_exit", 0.4))
    pi_nlos = p_enter / max(p_enter + p_exit, 1e-12)
    block = float(
        jnp.power(10.0, -jnp.float32(spec.get("extra_db", 8.0)) / 10.0)
    )
    return g * ((1.0 - pi_nlos) + pi_nlos * block)


def _mobility_lower(spec, ctx):
    # no "pathloss_db": mobility derives path loss from distance, so a
    # scheduled mean would be a silent no-op — reject it instead.
    check_spec_keys(
        "mobility",
        spec,
        ("fading", "rho", "area_m", "speed_mps", "round_s", "pl_exp",
         "pl_ref_db", "d_ref_m"),
    )
    speed = spec.get("speed_mps", (1.0, 10.0))
    if isinstance(speed, (int, float)):
        speed = (float(speed), float(speed))
    if not 0.0 <= float(speed[0]) <= float(speed[1]):
        raise ValueError(
            f"mobility speed_mps must be 0 <= min <= max, got {speed!r}"
        )
    if float(spec.get("area_m", 60.0)) <= 0 or float(spec.get("d_ref_m", 10.0)) <= 0:
        raise ValueError("mobility area_m and d_ref_m must be positive")
    return _base_params(
        ctx,
        spec,
        rho=jnp.asarray(spec.get("rho", 0.0), jnp.float32),
        mobility_on=1.0,
        area_m=float(spec.get("area_m", 60.0)),
        speed_min=float(speed[0]),
        speed_max=float(speed[1]),
        round_s=float(spec.get("round_s", 1.0)),
        pl_exp=float(spec.get("pl_exp", 2.0)),
        pl_ref_db=float(spec.get("pl_ref_db", 32.0)),
        d_ref_m=float(spec.get("d_ref_m", 10.0)),
    )


_UMA_KEYS = ("fading", "fc_ghz", "isd_m", "h_bs_m", "h_ut_m", "d_min_m",
             "speed_mps", "round_s")


def _uma_lower(spec, ctx):
    """TR 38.901 UMa defaults: Table 7.2-1's ISD 500 m, h_BS 25 m, h_UT
    1.5 m (outdoor) and 35 m minimum distance, at 3.5 GHz."""
    check_spec_keys("uma_38901", spec, _UMA_KEYS)
    fc = float(spec.get("fc_ghz", 3.5))
    h_bs = float(spec.get("h_bs_m", 25.0))
    h_ut = float(spec.get("h_ut_m", 1.5))
    cell_r = float(spec.get("isd_m", 500.0)) / np.sqrt(3.0)
    d_min = float(spec.get("d_min_m", 35.0))
    speed = spec.get("speed_mps", 0.0)
    if not isinstance(speed, (int, float)) or speed < 0.0:
        raise ValueError(
            f"uma_38901 speed_mps must be one speed >= 0, got {speed!r}"
        )
    if not 0.5 <= fc <= 100.0:
        raise ValueError(f"uma_38901 covers 0.5-100 GHz, got fc_ghz={fc}")
    if not 1.5 <= h_ut <= 13.0:
        raise ValueError(
            f"uma_38901 takes 1.5 <= h_ut_m <= 13 (C'(h_UT) = 0), got {h_ut}"
        )
    if not 0.0 < d_min < cell_r:
        raise ValueError(
            f"uma_38901 needs 0 < d_min_m < isd_m / sqrt(3), got {d_min}, {cell_r}"
        )
    if cell_r > _uma_breakpoint_m(fc, h_bs, h_ut):
        raise ValueError(
            "uma_38901 models PL_1 only: the sector radius isd_m / sqrt(3) "
            f"({cell_r:.1f} m) exceeds the breakpoint distance "
            f"({_uma_breakpoint_m(fc, h_bs, h_ut):.1f} m)"
        )
    return _base_params(
        ctx,
        spec,
        uma_on=1.0,
        fc_ghz=fc,
        h_bs_m=h_bs,
        h_ut_m=h_ut,
        cell_r_m=cell_r,
        d_min_m=d_min,
        speed_min=float(speed),
        speed_max=float(speed),
        round_s=float(spec.get("round_s", 1.0)),
    )


register_channel_process(
    "iid_rayleigh",
    _iid_lower,
    mean_gain=_sched_mean_gain,
    doc="paper block fading: h^2 = g * Exp(1), i.i.d. per round",
)
register_channel_process(
    "gauss_markov",
    _gauss_markov_lower,
    mean_gain=_sched_mean_gain,
    doc="AR(1)-correlated fading, per-client coherence rho (0 => i.i.d.)",
)
register_channel_process(
    "markov_shadowing",
    _shadowing_lower,
    mean_gain=_shadowing_mean_gain,
    doc="2-state LOS/NLOS blockage chain layered on the fading",
)
register_channel_process(
    "mobility",
    _mobility_lower,
    mean_gain=None,
    doc="random-waypoint trajectories -> distance-based path loss",
)
register_channel_process(
    UMA_PROCESS,
    _uma_lower,
    mean_gain=None,
    doc="TR 38.901 UMa sector: LOS/NLOS 3D path loss, shadowing, waypoints",
)
