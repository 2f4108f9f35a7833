"""The numbers that decide ``correct``: what the timed path produced, held to
the deployment's plain reference (``ref``, the module its configuration names
under ``reference``; ``reference.py`` for the paper's) and to the
configuration's guarantees.

Each number is the worst reading over the answers checked in a run:

* ``p3_gap``: |W* - W(a, b)| / (size of W*'s terms), W* the reference's
  optimal P3 value for the round's queues and channel, W(a, b) the P3 value
  of the decision the program returned, both in float64.
* ``energy_gap``: largest relative gap between the energy the program
  charged a selected client and Eq. (2) at its bandwidth; 1 where an
  unselected client was charged.
* ``queue_gap``: largest difference between the queue the program carried
  and the float32 replay of q(t+1) = [q(t) + e(t) - H/T]^+ and the frame
  reset; an exact comparison, where the program returns its queues (the
  closed loop).  A sweep returns none: its OCEAN rounds are checked against
  queues replayed from the energy the program charged, and ``drain_gap``
  holds the per-round drain H/T the program used; also exact.
* ``sum_b_excess``, ``b_min_shortfall``: how far the band is overspent, and
  how far a selected client falls below b_min, relative to it.
* ``h2_gap``: largest relative gap between the channel gains the program
  sampled and the deployment's channel law drawn from the same seed.
* ``myopic_b_gap``: largest relative gap between the bandwidth SMO/AMO gave a
  selected client and its cheapest bandwidth within its cap;
  ``myopic_sel_wrong``: selections that differ from the reference's greedy
  where the reference's running sum is not within 1e-3 of the band.
"""
from __future__ import annotations

import contextlib
from typing import Dict, Optional

import numpy as np

SEL_MARGIN = 1e-3


class Numbers:
    """Worst reading of each number over the answers checked, and how many
    answers broke a limit."""

    def __init__(self, limits: Dict[str, float]) -> None:
        self.limits = {k: float(v) for k, v in limits.items()}
        self.worst: Dict[str, float] = {}
        self.answers = 0
        self.failed = 0
        self._bad = False

    def add(self, name: str, value: float) -> None:
        value = float(value)
        if np.isnan(value):
            value = np.inf
        self.worst[name] = max(self.worst.get(name, 0.0), value)
        self._bad = self._bad or value > self.limits[name]

    @contextlib.contextmanager
    def answer(self):
        """Group the readings of one answer (a round, a channel draw)."""
        self._bad = False
        yield
        self.answers += 1
        self.failed += int(self._bad)

    def judged(self):
        """(correct, [[name, value, limit], ...])."""
        rows = [[n, v, self.limits[n]] for n, v in self.worst.items()]
        ok = self.answers > 0 and all(v <= lim for _, v, lim in rows)
        return ok, rows


def bandwidth(nums: Numbers, a, b, b_min: float) -> None:
    a, b = np.asarray(a, bool), np.asarray(b, np.float64)
    nums.add("sum_b_excess", max(float(b.sum()) - 1.0, 0.0))
    short = np.where(a, np.maximum(b_min - b, 0.0), 0.0).max(initial=0.0)
    nums.add("b_min_shortfall", short / b_min)
    if not np.all(np.isfinite(b)):
        nums.add("sum_b_excess", np.inf)


def energy(nums: Numbers, ref, a, b, e, h2, radio) -> None:
    a = np.asarray(a, bool)
    e = np.asarray(e, np.float64)
    e_ref = ref.energy(np.where(a, b, 0.0), h2, radio)
    with np.errstate(divide="ignore", invalid="ignore"):
        rel = np.abs(e - e_ref) / e_ref
    gap = np.where(a, rel, np.where(e != 0.0, 1.0, 0.0))
    nums.add("energy_gap", gap.max(initial=0.0))


def p3(nums: Numbers, ref, a, b, q, h2, v_eta: float, radio) -> None:
    q64, h64 = ref.exact(q), ref.exact(h2)
    with np.errstate(divide="ignore"):
        rho = q64 / np.maximum(h64, ref.RHO_ZERO)
    best = ref.solve_p3(rho, v_eta, radio)
    got = ref.p3_value(a, b, q64, h64, v_eta, radio)
    nums.add("p3_gap", abs(best.w - got) / ref.p3_scale(best, q64, h64, v_eta, radio))


def queue(nums: Numbers, ref, q_used, e, q_next, inc,
          q_carried: Optional[np.ndarray] = None, reset: bool = False) -> None:
    """The update rule, and (given the carried queue) the frame reset."""
    q_used = np.asarray(q_used, np.float32)
    gap = np.abs(np.asarray(q_next, np.float64)
                 - ref.queue_update(q_used, e, inc)).max(initial=0.0)
    if q_carried is not None:
        want = np.zeros_like(q_used) if reset else np.asarray(q_carried, np.float32)
        gap = max(gap, float(np.abs(q_used.astype(np.float64) - want).max(initial=0.0)))
    nums.add("queue_gap", gap)


def ocean_round(nums: Numbers, ref, *, a, b, e, q_used, h2, v_eta: float,
                radio, q_next=None, inc=None, q_carried=None,
                reset=False) -> None:
    """Every number of one OCEAN round's answer; the queue's where the
    program returned the next one (``q_next``)."""
    if q_next is not None:
        queue(nums, ref, q_used, e, q_next, inc, q_carried, reset)
    p3(nums, ref, a, b, q_used, h2, v_eta, radio)
    energy(nums, ref, a, b, e, h2, radio)
    bandwidth(nums, a, b, radio.b_min)


def control_round(nums: Numbers, ref, *, q_used, h2, v_eta: float,
                  radio, inc=None, q_carried=None, reset=False) -> None:
    """The same numbers for the reference put in the program's place,
    computed in bfloat16, on the same inputs; the queue's where ``inc`` is
    given."""
    c = ref.ocean_round(q_used, h2, v_eta, 0.0 if inc is None else inc, radio,
                        rnd=ref.bf16)
    ocean_round(nums, ref, a=c.a, b=c.b, e=c.e, q_used=ref.bf16(q_used),
                q_next=None if inc is None else c.q_next, h2=h2, v_eta=v_eta,
                inc=inc, radio=radio, q_carried=q_carried, reset=reset)


def myopic_round(nums: Numbers, ref, *, a, b, e, cap, h2, radio) -> None:
    """One SMO/AMO round's answer against the reference greedy."""
    want = ref.myopic_round(cap, h2, radio)
    a = np.asarray(a, bool)
    b = ref.exact(b)
    with np.errstate(divide="ignore", invalid="ignore"):
        rel = np.abs(b - want.b_dag) / want.b_dag
    nums.add("myopic_b_gap", np.where(a, np.nan_to_num(rel, nan=np.inf), 0.0)
             .max(initial=0.0))
    wrong = (a != want.a) & (want.margin > SEL_MARGIN)
    nums.add("myopic_sel_wrong", float(wrong.sum()))
    energy(nums, ref, a, b, e, h2, radio)
    bandwidth(nums, a, b, radio.b_min)


def control_myopic(nums: Numbers, ref, *, cap, h2, radio) -> None:
    c = ref.myopic_round(cap, h2, radio, rnd=ref.bf16)
    b = np.where(c.a, c.b_dag, 0.0)
    e = ref.energy(b, h2, radio, ref.bf16)
    myopic_round(nums, ref, a=c.a, b=b, e=e, cap=cap, h2=h2, radio=radio)


def channel(nums: Numbers, h2, h2_ref) -> None:
    rel = np.abs(np.asarray(h2, np.float64) - h2_ref) / h2_ref
    nums.add("h2_gap", rel.max(initial=0.0))
