"""Reference of the deployment ``uma_sector_k10``: the paper's scheduler
(``reference.py``) on the channel of one 3GPP TR 38.901 V16.1.0 urban-macro
sector, written from the tables and independent of the code under test:
nothing here imports ``repro``.

The law, per client k and round t, with fc in GHz and distances in m:

* the client sits at p_t in a 120-degree wedge (boresight +x, azimuth within
  +-60 degrees) of radius ISD/sqrt(3), at d2D = |p_t| >= d_min (Table 7.2-1:
  35 m); d3D = sqrt(d2D^2 + (h_BS - h_UT)^2);
* Pr_LOS = 1 for d2D <= 18, else 18/d2D + exp(-d2D/63) (1 - 18/d2D)
  (Table 7.4.2-1, C'(h_UT) = 0 for h_UT <= 13);
* the LOS state: ndtr(z_los) < Pr_LOS, z_los a unit Gaussian field;
* PL_LOS = 28.0 + 22 log10(d3D) + 20 log10(fc) (PL_1, d2D < d'_BP),
  PL'_NLOS = 13.54 + 39.08 log10(d3D) + 20 log10(fc) - 0.6 (h_UT - 1.5),
  PL_NLOS = max(PL_LOS, PL'_NLOS) (Table 7.4.1-1);
* shadow fading SF = 4 z_sf_los dB in LOS, 6 z_sf_nlos dB in NLOS
  (Table 7.5-6), and h2 = 10^(-(PL + SF) / 10) X, X = -log(u) ~ Exp(1),
  the paper's block fading on the uniform draws of PRNGKey(seed);
* motion: at the scenario's one speed v, v round_s a round along a straight
  leg toward a waypoint; a client within one step stops at the waypoint and
  the next waypoint is drawn; a step that ends inside d_min is pushed out
  radially to d_min, from where the client heads on to its waypoint;
* each latent field follows z' = rho z + sqrt(1 - rho^2) n with
  rho = exp(-v round_s / d_cor), over the path a client covers in a round:
  50 m for the LOS state (Table 7.6.3.1-2), 37 m (LOS) and 50 m (NLOS) for
  shadow fading (Table 7.5-6).

Where it departs from the tables:

* one sector, a circular wedge instead of a hexagon's third, no other
  sites and so no interference;
* every UT outdoors at h_UT (no O2I penetration loss, no floors);
* an isotropic BS antenna (no Table 7.3-1 pattern or tilt);
* block Rayleigh fading per round instead of the CDL/TDL fast fading;
* spatial consistency along each client's own path: an AR(1) field over
  the path covered, not a 2D field (a client that comes back to a place
  does not find the state it left there; one that stops short at its
  waypoint still counts its whole step); the LOS state compares a normal
  field's ndtr with Pr_LOS, as 7.6.3.3 compares a spatially consistent
  uniform; shadow fading keeps one field per state, as the LSPs of LOS and
  NLOS are drawn apart;
* random waypoint in the wedge, which 38.901 leaves to the simulation.

Every draw is the program's: the fading key PRNGKey(seed), the environment
key folded from it by the CRC32 salt of the scenario's ``env`` (as
``repro.env.spec.env_key_salt`` and ``env_cell_keys`` derive it), the
waypoint uniforms of its split, and the sector's normals from its own fold,
each taken as ``jax.random`` gives it (the normals too: a float32 normal
re-derived in float64 from its uniform differs by up to some 1e-6).  Each
parameter is taken as the float32 number the program holds
(``FLOAT32_PARAMS``; ISD/sqrt(3) too), and everything after it in float64.
A client's place is computed from its leg's start and the whole steps taken
on it, as the program computes it.

``channel_state`` returns, besides h2, the LOS state and the waypoint
arrivals (a client that arrived from round t to t + 1), each with its margin
to the threshold: a LOS state or an arrival is a threshold on a continuous
value, and the program, which decides them in float64 too, has to take the
same side of each.
"""
from __future__ import annotations

import json
import zlib
from typing import NamedTuple

import numpy as np
from scipy.special import ndtr

import reference as paper
from reference import *  # noqa: F401,F403  every other part is the paper's

SECTOR_HALF_RAD = np.pi / 3.0
LOS_DCOR_M = 50.0
SF_DB = (4.0, 6.0)
SF_DCOR_M = (37.0, 50.0)
UMA_STREAM = 0x554D6138      # the sector's fold of the environment key
# The parameters the program holds as float32 numbers (and so the law's).
FLOAT32_PARAMS = ("fc_ghz", "h_bs_m", "h_ut_m", "d_min_m", "speed_mps",
                  "round_s", "cell_r_m")
DEFAULTS = dict(fading=True, fc_ghz=3.5, isd_m=500.0, h_bs_m=25.0,
                h_ut_m=1.5, d_min_m=35.0, speed_mps=0.0, round_s=1.0)


def los_probability(d2d, rnd=paper.exact):
    d2d = paper.exact(d2d)
    q = rnd(18.0 / d2d)
    far = rnd(q + rnd(rnd(np.exp(rnd(-d2d / 63.0))) * rnd(1.0 - q)))
    return np.where(d2d <= 18.0, 1.0, far)


def pathloss_db(d3d, fc_ghz, h_ut_m, rnd=paper.exact):
    """(PL_LOS, PL_NLOS) of Table 7.4.1-1, UMa, PL_1."""
    lg = rnd(np.log10(paper.exact(d3d)))
    f_db = rnd(20.0 * np.log10(fc_ghz))
    pl_los = rnd(rnd(28.0 + rnd(22.0 * lg)) + f_db)
    nlos_p = rnd(rnd(rnd(13.54 + rnd(39.08 * lg)) + f_db) - 0.6 * (h_ut_m - 1.5))
    return pl_los, np.maximum(pl_los, nlos_p)


def env_salt(env: dict, num_rounds: int, num_clients: int) -> int:
    """The CRC32 salt of a scenario's environment (``EnvSpec.to_dict()``)."""
    spec = {k: env[k] for k in ("channel", "channel_params", "budget",
                                "budget_params")}
    for axis, off in (("radio", "static"), ("failure", "none")):
        if env.get(axis, off) != off or env.get(axis + "_params"):
            spec[axis] = env[axis]
            spec[axis + "_params"] = env.get(axis + "_params", {})
    payload = json.dumps({"env": spec, "num_rounds": num_rounds,
                          "num_clients": num_clients},
                         sort_keys=True, default=list)
    return zlib.crc32(payload.encode()) & 0xFFFFFFFF


class Draws(NamedTuple):
    u_fade: np.ndarray     # (T, K)
    u_wp: np.ndarray       # (T, K, 3) next waypoint (radius, azimuth), and a
                           # leg speed the sector does not use
    u_pos0: np.ndarray     # (K, 2)
    u_wp0: np.ndarray      # (K, 2)
    n: np.ndarray          # (T, K, 3) LOS state, SF LOS, SF NLOS
    z0: np.ndarray         # (K, 3)


def draws(seed: int, num_rounds: int, num_clients: int, env: dict) -> Draws:
    """The program's random draws for one (scenario, seed) cell."""
    import jax

    t, k = num_rounds, num_clients
    fade_key = jax.random.PRNGKey(np.uint32(seed))
    env_key = jax.random.fold_in(fade_key, env_salt(env, t, k))
    k_chan, _ = jax.random.split(env_key)
    _, k_wp, k_init = jax.random.split(k_chan, 3)
    ki_pos, ki_wp, _, _, _ = jax.random.split(k_init, 5)
    ku_n, ku_z = jax.random.split(jax.random.fold_in(k_chan, UMA_STREAM))

    def f64(x):
        return np.asarray(x, np.float64)

    return Draws(
        u_fade=f64(jax.random.uniform(fade_key, (t, k), minval=1e-6, maxval=1.0)),
        u_wp=f64(jax.random.uniform(k_wp, (t, k, 3))),
        u_pos0=f64(jax.random.uniform(ki_pos, (k, 2))),
        u_wp0=f64(jax.random.uniform(ki_wp, (k, 2))),
        n=f64(jax.random.normal(ku_n, (t, k, 3))),
        z0=f64(jax.random.normal(ku_z, (k, 3))),
    )


def law(scenario: dict) -> dict:
    """The scenario's sector parameters, the process's defaults filled in."""
    p = dict(DEFAULTS, **scenario["env"]["channel_params"])
    p["cell_r_m"] = p["isd_m"] / np.sqrt(3.0)
    for k in FLOAT32_PARAMS:
        p[k] = float(np.float32(p[k]))
    return p


class ChannelState(NamedTuple):
    h2: np.ndarray         # (T, K)
    los: np.ndarray        # (T, K) bool, the LOS state of round t
    arrived: np.ndarray    # (T, K) bool, reached its waypoint from t to t + 1
    los_margin: np.ndarray     # (T, K) ndtr(z_los) - Pr_LOS, LOS below 0
    arrive_margin: np.ndarray  # (T, K) m to the waypoint less the step
    pos: np.ndarray        # (T, K, 2) where the client is in round t


def channel_state(seed: int, num_rounds: int, num_clients: int,
                  scenario: dict, rnd=paper.exact) -> ChannelState:
    """The sector's (T, K) gains for a sweep seed and a scenario, with the
    thresholds behind them."""
    p = law(scenario)
    d = draws(seed, num_rounds, num_clients, scenario["env"])
    r2_lo, r2_hi = p["d_min_m"] ** 2, p["cell_r_m"] ** 2

    def wedge(u_r, u_a):
        r = rnd(np.sqrt(rnd(r2_lo + rnd(u_r * (r2_hi - r2_lo)))))
        phi = rnd((2.0 * u_a - 1.0) * SECTOR_HALF_RAD)
        return np.stack([rnd(r * rnd(np.cos(phi))), rnd(r * rnd(np.sin(phi)))], -1)

    def norm(v):
        return rnd(np.sqrt(rnd(np.sum(rnd(v * v), axis=-1))))

    start = wedge(d.u_pos0[:, 0], d.u_pos0[:, 1])
    wp = wedge(d.u_wp0[:, 0], d.u_wp0[:, 1])
    steps = np.zeros(num_clients)
    step = rnd(p["speed_mps"] * p["round_s"])
    rho = rnd(np.exp(rnd(-step / np.array([LOS_DCOR_M, *SF_DCOR_M]))))
    keep = rnd(np.sqrt(rnd(1.0 - rnd(rho * rho))))
    z = rnd(d.z0)
    h_gap2 = (p["h_bs_m"] - p["h_ut_m"]) ** 2
    out = {k: [] for k in ChannelState._fields}

    def along(delta, leg, n):
        with np.errstate(invalid="ignore", divide="ignore"):
            frac = np.where(leg > 0.0, rnd(rnd(n * step) / leg), 0.0)
        return rnd(start + rnd(delta * frac[:, None]))

    for t in range(num_rounds):
        delta = rnd(wp - start)
        leg = norm(delta)
        pos = along(delta, leg, steps)
        d2d = norm(pos)
        d3d = rnd(np.sqrt(rnd(rnd(d2d * d2d) + h_gap2)))
        margin = rnd(ndtr(z[:, 0])) - los_probability(d2d, rnd)
        los = margin < 0.0
        pl_los, pl_nlos = pathloss_db(d3d, p["fc_ghz"], p["h_ut_m"], rnd)
        pl = np.where(los, rnd(pl_los + rnd(SF_DB[0] * z[:, 1])),
                      rnd(pl_nlos + rnd(SF_DB[1] * z[:, 2])))
        gain = rnd(10.0 ** rnd(-pl / 10.0))
        fade = rnd(-np.log(d.u_fade[t])) if p["fading"] else 1.0

        to_go = rnd(leg - rnd(steps * step))
        arrive = to_go <= step
        moved = np.where(arrive[:, None], wp, along(delta, leg, steps + 1.0))
        r = norm(moved)
        inside = r < p["d_min_m"]
        start = np.where(arrive[:, None], wp, start)
        with np.errstate(invalid="ignore", divide="ignore"):
            pushed = rnd(moved * rnd(p["d_min_m"] / r)[:, None])
        start = np.where(inside[:, None], pushed, start)
        steps = np.where(arrive | inside, 0.0, steps + 1.0)
        wp = np.where(arrive[:, None], wedge(d.u_wp[t, :, 0], d.u_wp[t, :, 1]), wp)
        for k, v in zip(ChannelState._fields, (rnd(gain * fade), los, arrive,
                                              margin, to_go - step, pos)):
            out[k].append(v)
        z = rnd(rnd(rho * z) + rnd(keep * d.n[t]))
    return ChannelState(*(np.array(out[k]) for k in ChannelState._fields))


def channel(seed, num_rounds, num_clients, scenario, rnd=paper.exact):
    """(T, K) gains of the sector for a sweep seed and a scenario."""
    return channel_state(seed, num_rounds, num_clients, scenario, rnd).h2


def bank(conf, seed):
    raise NotImplementedError(
        "uma_sector_k10 has no closed-loop cell: its channel is a sweep's")
