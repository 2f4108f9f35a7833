"""Plain reference of the scheduler's semantics, in float64 NumPy.

Written from the paper's equations (arXiv:2004.04314 §IV-V and §VI-A) and
independent of the code under test: nothing here imports ``repro``.

* Eq. (2) energy:  E(b | h) = tau N0 B f(b) / h^2,  f(b) = b (2^(beta/b) - 1),
  beta = L / (tau B).
* P3 (one OCEAN round): maximise  V eta |S| - sum_{k in S} q_k E(b_k | h_k)
  subject to sum b = 1 and b_k >= b_min on S.  Clients with q_k = 0 (S0) cost
  nothing and are always selected at b_min; among the others Theorem 1 makes
  the optimum a prefix of the clients sorted by rho_k = q_k / h_k^2.  Each
  prefix is a convex waterfilling problem (P4), solved here from its KKT
  condition rho_k f'(b_k) = -lam in closed form with the Lambert W function,
  the level lam by safeguarded Newton on log(lam).  Prefixes are evaluated
  in blocks until a whole block past the best one scores below it.
* The queue update q(t+1) = [q(t) + e(t) - H/T]^+ and the frame reset.
* SMO/AMO (§VI-A): each client's cheapest bandwidth meeting its per-round
  energy cap, then the clients in ascending order of that bandwidth while
  the running sum stays within the band.
* The channel law, on both sides of the check: ``bank`` draws the closed
  loop's inputs, ``channel`` the gains a sweep's seed must give.

This is the reference of the configurations that name it under
``reference``.  Another deployment's module may import this one and replace
what differs, as a rule its channel law (``channel`` and ``bank``).

A ``Rounding`` passed through the functions below rounds the result of each
operation; ``bf16`` gives the control that a result computed in a lower
precision must fail.
"""
from __future__ import annotations

import math
from typing import Callable, NamedTuple

import ml_dtypes
import numpy as np
from scipy.special import lambertw

LN2 = math.log(2.0)
RHO_ZERO = 1e-30      # rho at or below this is S0 (an empty queue)
BLOCK = 32            # prefixes solved at once
NEWTON_ITERS = 60
MAX_PREFIX = 512      # prefixes searched; the program's own top-m is 128


class Radio(NamedTuple):
    bandwidth_hz: float
    noise_w: float
    deadline_s: float
    model_bits: float
    b_min: float

    @property
    def beta(self) -> float:
        return self.model_bits / (self.deadline_s * self.bandwidth_hz)

    @property
    def scale(self) -> float:
        return self.deadline_s * self.noise_w * self.bandwidth_hz


def exact(x):
    return np.asarray(x, np.float64)


def bf16(x):
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(np.float64)


Rounding = Callable[[np.ndarray], np.ndarray]


def rsum(x, rnd: Rounding = exact, axis: int = -1):
    """Sum along ``axis``, each partial sum passed through ``rnd``."""
    x = np.moveaxis(exact(x), axis, -1)
    if rnd is exact:
        return x.sum(axis=-1)
    acc = np.zeros(x.shape[:-1])
    for j in range(x.shape[-1]):
        acc = rnd(acc + x[..., j])
    return acc


def f(b, beta, rnd: Rounding = exact):
    """f(b) = b (2^(beta/b) - 1), each operation's result passed through
    ``rnd`` (in float64 its relative error stays below 1e-13 for every
    beta/b this benchmark reaches)."""
    b = exact(b)
    with np.errstate(divide="ignore", over="ignore", invalid="ignore"):
        return rnd(b * rnd(rnd(np.exp2(rnd(beta / b))) - 1.0))


def f_prime(b, beta):
    y = LN2 * beta / exact(b)
    with np.errstate(over="ignore", invalid="ignore"):
        return np.exp(y) * (1.0 - y) - 1.0


def f_second(b, beta):
    b = exact(b)
    with np.errstate(over="ignore"):
        return LN2**2 * np.exp2(beta / b) * beta**2 / b**3


def energy(b, h2, radio: Radio, rnd: Rounding = exact):
    """Eq. (2); zero where no bandwidth is allocated."""
    b = exact(b)
    with np.errstate(divide="ignore", invalid="ignore"):
        e = rnd(rnd(radio.scale * f(b, radio.beta, rnd)) / rnd(h2))
    return np.where(b > 0, e, 0.0)


def _b_of_lam(lam, rho, beta, b_min, b_max):
    """The KKT bandwidth rho f'(b) = -lam, clipped to [b_min, b_max].

    With y = ln2 beta / b the condition reads e^y (1 - y) = 1 - lam/rho, so
    y = 1 + W0((lam/rho - 1) / e) on the principal branch.
    """
    c = lam / rho
    y = 1.0 + lambertw((c - 1.0) / math.e).real
    with np.errstate(divide="ignore"):
        b = LN2 * beta / y
    return np.clip(np.where(y > 0, b, np.inf), b_min, b_max)


def _prefix_costs(rho_sorted, ms, delta, beta, b_min, rnd: Rounding = exact):
    """min sum_{j<m} rho_j f(b_j) s.t. sum b = delta, b in [b_min, b_max(m)].

    One row per candidate size in ``ms`` (all >= 1 and feasible).  Returns
    (cost, b) with b of shape (len(ms), max(ms)).  The level lam is solved
    in float64 whatever ``rnd`` is; the allocation and the cost pass through
    it.
    """
    width = int(ms.max())
    r = rho_sorted[None, :width]
    mask = np.arange(width)[None, :] < ms[:, None]
    b_max = np.maximum(delta - (ms - 1) * b_min, b_min)[:, None]
    r_hi = rho_sorted[ms - 1][:, None]
    lo = np.log(rho_sorted[0] * -f_prime(b_max, beta))[:, 0]
    hi = np.log(r_hi * -f_prime(b_min, beta))[:, 0]
    L = 0.5 * (lo + hi)
    for _ in range(NEWTON_ITERS):
        lam = np.exp(L)[:, None]
        b = np.where(mask, _b_of_lam(lam, r, beta, b_min, b_max), 0.0)
        g = b.sum(axis=1) - delta
        lo = np.where(g > 0, L, lo)
        hi = np.where(g > 0, hi, L)
        interior = mask & (b > b_min) & (b < b_max)
        with np.errstate(divide="ignore", invalid="ignore"):
            db = np.where(interior, -1.0 / (r * f_second(b, beta)), 0.0)
            L_new = L - g / (db.sum(axis=1) * lam[:, 0])
        ok = np.isfinite(L_new) & (L_new > lo) & (L_new < hi)
        L = np.where(ok, L_new, 0.5 * (lo + hi))
        if np.all(np.abs(g) <= 1e-15 * delta) or np.all(hi - lo < 1e-15):
            break
    lam = np.exp(L)[:, None]
    b = rnd(np.where(mask, _b_of_lam(lam, r, beta, b_min, b_max), 0.0))
    terms = np.where(mask, rnd(r * f(np.maximum(b, b_min), beta, rnd)), 0.0)
    return rsum(terms, rnd), b


class Decision(NamedTuple):
    a: np.ndarray          # (K,) bool
    b: np.ndarray          # (K,) float64
    w: float               # P3 value of (a, b)


def solve_p3(rho, v_eta: float, radio: Radio,
             rnd: Rounding = exact) -> Decision:
    """The optimal OCEAN-P decision for priorities ``rho``."""
    rho = exact(rho)
    k = rho.shape[0]
    b_min, beta = radio.b_min, radio.beta
    in_s0 = rho <= RHO_ZERO
    n0 = int(in_s0.sum())
    delta = 1.0 - n0 * b_min
    pos = np.flatnonzero(~in_s0)
    order = pos[np.argsort(rho[pos], kind="stable")]
    rho_sorted = rho[order]
    m_cap = min(len(order), int(math.floor(delta / b_min + 1e-9)), MAX_PREFIX)

    best_w, best_m, best_b = float(rnd(v_eta * n0)), 0, None
    start = 1
    while start <= m_cap:
        ms = np.arange(start, min(start + BLOCK, m_cap + 1))
        cost, b = _prefix_costs(rho_sorted, ms, delta, beta, b_min, rnd)
        w = rnd(rnd(v_eta * (n0 + ms)) - rnd(radio.scale * cost))
        for i in range(len(ms)):
            if w[i] > best_w:
                best_w, best_m, best_b = float(w[i]), int(ms[i]), b[i, : ms[i]]
        if w.max() < best_w:
            break
        start += BLOCK

    a = in_s0.copy()
    b = np.zeros(k)
    if best_m == 0:
        b[in_s0] = b_min + (delta / n0 if n0 else 0.0)
    else:
        b[in_s0] = b_min
        a[order[:best_m]] = True
        b[order[:best_m]] = best_b
    b = rnd(b)
    return Decision(a=a, b=b, w=best_w)


def p3_value(a, b, q, h2, v_eta: float, radio: Radio) -> float:
    """V eta |S| - sum_S q_k E(b_k | h_k) of any decision, in float64."""
    a = np.asarray(a, bool)
    e = energy(np.where(a, exact(b), 0.0), h2, radio)
    return float(v_eta * a.sum() - np.sum(np.where(a, exact(q) * e, 0.0)))


def p3_scale(dec: Decision, q, h2, v_eta: float, radio: Radio) -> float:
    """Size of the terms of the optimum, the denominator of a P3 gap."""
    e = energy(np.where(dec.a, dec.b, 0.0), h2, radio)
    return float(v_eta * max(int(dec.a.sum()), 1) + np.sum(exact(q) * e))


def queue_update(q, e, inc):
    """q(t+1) = [q(t) + e(t) - inc]^+ in float32, the program's carry dtype."""
    q, e, inc = (np.asarray(x, np.float32) for x in (q, e, inc))
    return np.maximum((q + e) - inc, np.float32(0.0))


class ControlRound(NamedTuple):
    a: np.ndarray
    b: np.ndarray
    e: np.ndarray
    q_next: np.ndarray


def ocean_round(q, h2, v_eta, inc, radio: Radio,
                rnd: Rounding = exact) -> ControlRound:
    """One OCEAN round, every stored value passed through ``rnd``."""
    q, h2 = rnd(q), rnd(h2)
    with np.errstate(divide="ignore"):
        rho = rnd(q / np.maximum(h2, RHO_ZERO))
    dec = solve_p3(rho, v_eta, radio, rnd)
    e = energy(np.where(dec.a, dec.b, 0.0), h2, radio, rnd)
    q_next = rnd(np.maximum(rnd(rnd(q + e) - rnd(inc)), 0.0))
    return ControlRound(a=dec.a, b=dec.b, e=e, q_next=q_next)


# ------------------------------------------------------------ SMO and AMO
def cheapest_bandwidth(cap, h2, radio: Radio, rnd: Rounding = exact,
                       iters: int = 200):
    """Smallest b in [b_min, 1] with E(b | h) <= cap; inf where none is."""
    cap, h2 = exact(cap), exact(h2)

    def e_of(b):
        return energy(b, h2, radio, rnd)

    lo = np.full(h2.shape, radio.b_min)
    hi = np.ones(h2.shape)
    for _ in range(iters):
        mid = rnd(0.5 * (lo + hi))
        over = e_of(mid) > cap
        lo = np.where(over, mid, lo)
        hi = np.where(over, hi, mid)
    b = np.where(e_of(np.full(h2.shape, radio.b_min)) <= cap, radio.b_min, hi)
    return np.where(e_of(np.ones(h2.shape)) <= cap, b, np.inf)


class Myopic(NamedTuple):
    a: np.ndarray
    b_dag: np.ndarray      # each client's cheapest feasible bandwidth
    margin: np.ndarray     # |running sum - 1| at each client's place


def myopic_round(cap, h2, radio: Radio, rnd: Rounding = exact) -> Myopic:
    """SMO/AMO's greedy: cheapest bandwidth first while the sum fits."""
    b_dag = rnd(cheapest_bandwidth(rnd(cap), rnd(h2), radio, rnd))
    order = np.argsort(b_dag, kind="stable")
    finite = np.isfinite(b_dag[order])
    steps = np.where(finite, b_dag[order], 1e9)
    csum = np.array([rsum(steps[: i + 1], rnd) for i in range(len(steps))])
    take = (csum <= 1.0) & finite
    a = np.zeros(b_dag.shape, bool)
    a[order] = take
    margin = np.empty(b_dag.shape)
    margin[order] = np.abs(csum - 1.0)
    return Myopic(a=a, b_dag=b_dag, margin=margin)


def amo_caps(budget, e, t_index, num_rounds):
    """AMO's per-round cap from the energy the program spent before it.

    ``e`` holds rounds 0..t_index-1; the spent energy is their float32
    running sum, the order in which the program carries it.
    """
    spent = np.zeros(e.shape[-1], np.float32)
    for row in np.asarray(e, np.float32)[:t_index]:
        spent = spent + row
    remaining = np.maximum(exact(budget) - spent, 0.0)
    return remaining / max(num_rounds - t_index, 1)


def eta(schedule: str, num_rounds: int) -> np.ndarray:
    """The temporal weights eta^t (§VI-A), each schedule of mean one."""
    if schedule == "uniform":
        return np.ones(num_rounds)
    e = np.linspace(0.2, 1.8, num_rounds)
    e = e / e.mean()
    if schedule == "ascend":
        return e
    if schedule == "descend":
        return e[::-1].copy()
    raise ValueError(f"unknown eta schedule {schedule!r}")


def pathloss_gain(pathloss_db, num_rounds: int) -> np.ndarray:
    """(T,) mean power gain 10^(-PL_t/10), PL drifting linearly over T."""
    start, end = pathloss_db
    t = np.arange(num_rounds) / max(num_rounds - 1, 1)
    return 10.0 ** (-(start + (end - start) * t) / 10.0)


def frame_reset(t: int, frame_len: int) -> bool:
    """Alg. 1 lines 3-5: queues restart at every t = m R, m >= 1."""
    return t > 0 and t % frame_len == 0


def channel(seed: int, num_rounds: int, num_clients: int, scenario: dict,
            rnd: Rounding = exact):
    """(T, K) gains of the paper's i.i.d. Rayleigh block fading for a seed
    and a scenario of the configuration: the uniform draws of
    ``jax.random.PRNGKey(seed)`` in [1e-6, 1), made Exp(1) and scaled by the
    scenario's path-loss schedule (``pathloss_db``)."""
    import jax

    key = jax.random.PRNGKey(np.uint32(seed))
    u = rnd(jax.random.uniform(key, (num_rounds, num_clients), minval=1e-6,
                               maxval=1.0))
    g = rnd(pathloss_gain(scenario["pathloss_db"], num_rounds))
    return rnd(g[:, None] * rnd(-np.log(u)))


def key_of(seed: int, stream: int):
    """A PRNG key for one of the benchmark's streams, from any whole seed."""
    import jax
    import jax.numpy as jnp

    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def bank(conf: dict, seed: int):
    """The closed loop's inputs: one (T, K) draw of the first scenario's
    channel gains, h2[t, k] = 10^(-PL_t / 10) x Exp(1), on the device."""
    import jax
    import jax.numpy as jnp

    t, k = conf["num_rounds"], conf["num_clients"]
    gain = jnp.asarray(pathloss_gain(conf["scenarios"][0]["pathloss_db"], t),
                       jnp.float32)
    return gain[:, None] * jax.random.exponential(key_of(seed, 100), (t, k),
                                                  jnp.float32)
