"""The general traffic generator: one driver per kind of caller.

A traffic mix (``bench/traffic/<mix>.json``) names its ``driver`` and gives
its parameters; the configuration gives the deployment.  Each driver

* ``setup()``: builds the program's objects, makes the inputs from the seed
  and warms up every shape the window uses;
* ``run(seconds, traced)``: drives the system under test for ``seconds``
  and returns its end-to-end metrics, from the host clock;
* ``release()``: drops what the window made except the answers kept for
  the check;
* ``check(nums, control)``: holds the kept answers to the deployment's
  reference (the module its configuration names) and, with ``control``,
  puts the reference computed in bfloat16 in the program's place on the
  same inputs.

Answers are kept by a reservoir sample drawn from the seed, so which rounds
are checked does not depend on how many the window completes.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import checks
import deploy


def _span(traced: bool, name: str):
    return jax.profiler.TraceAnnotation(name) if traced else contextlib.nullcontext()


def _selected(counts) -> Dict:
    """Clients selected per round."""
    counts = np.asarray(counts)
    return {"rounds": int(counts.size), "min": int(counts.min()),
            "median": float(np.median(counts)), "max": int(counts.max())}


class Reservoir:
    """A uniform sample of ``size`` items from a stream, drawn from ``rng``."""

    def __init__(self, size: int, rng: np.random.Generator) -> None:
        self.size, self.rng, self.seen = size, rng, 0
        self.items: List = []

    def offer(self, item) -> None:
        if len(self.items) < self.size:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.size:
                self.items[j] = item
        self.seen += 1


class Driver:
    span_names: tuple = ()      # the host spans idle gaps are put down to
    unit_span: str = ""         # the host span that starts each unit of work

    def __init__(self, conf: dict, traffic: dict, seed: int) -> None:
        self.conf, self.traffic, self.seed = conf, traffic, seed
        self.ref = deploy.reference(conf)
        self.radio = self.ref.Radio(**conf["radio"])
        self.units = 0               # rounds or cell-rounds of the window
        self.info: Dict = {}          # printed on an earlier output line
        self.host: Dict[str, List[float]] = {}

    def _inc(self):
        t = np.float32(self.conf["num_rounds"])
        return np.float32(self.conf["energy_budget_j"]) / t


# ------------------------------------------------------------ closed loop
class ClosedLoop(Driver):
    """One coordinating server calling ``ocean_round`` round after round,
    with the configuration's first OCEAN policy."""

    span_names = ("upload", "solve", "fetch")
    unit_span = "upload"

    def setup(self) -> None:
        from repro.core.ocean import init_state, ocean_round

        conf = self.conf
        cfg = deploy.ocean_config(conf)
        self.t_rounds, self.frame = conf["num_rounds"], conf["frame_len"]
        pol = next(p for p in conf["policies"] if p["kind"] == "ocean")
        self.bank = np.asarray(self.ref.bank(conf, self.seed))
        self.eta = self.ref.eta(pol["eta"], self.t_rounds).astype(np.float32)
        self.v = pol["v"]
        v = np.float32(self.v)
        self.fn = jax.jit(lambda st, h2, eta_t: ocean_round(st, h2, v, eta_t, cfg))
        self.state = init_state(cfg)
        self.i = 0
        self.kept = Reservoir(self.traffic["check_rounds"],
                              np.random.default_rng([self.seed, 1]))
        for _ in range(self.traffic["warmup_rounds"]):
            self._round(False, None)
        jax.block_until_ready(self.state)

    def _round(self, traced: bool, counts):
        row = self.i % self.t_rounds
        if traced:
            t0 = time.perf_counter()
            with _span(True, "upload"):
                h = jax.device_put(self.bank[row]).block_until_ready()
            t1 = time.perf_counter()
            with _span(True, "solve"):
                new, dec = self.fn(self.state, h, self.eta[row])
                dec.b.block_until_ready()
            t2 = time.perf_counter()
            with _span(True, "fetch"):
                a, b = jax.device_get((dec.a, dec.b))
            t3 = time.perf_counter()
            self.host.setdefault("upload", []).append(t1 - t0)
            self.host.setdefault("fetch", []).append(t3 - t2)
            counts.append(int(a.sum()))
        else:
            h = jax.device_put(self.bank[row])
            new, dec = self.fn(self.state, h, self.eta[row])
            a, b = jax.device_get((dec.a, dec.b))
        return new, dec, a, b

    def run(self, seconds: float, traced: bool) -> Dict[str, float]:
        lat, counts = [], []
        end = time.perf_counter() + seconds
        while True:
            t0 = time.perf_counter()
            if t0 >= end:
                break
            new, dec, a, b = self._round(traced, counts)
            lat.append(time.perf_counter() - t0)
            self.kept.offer((self.i, self.state, new, dec, a, b))
            self.state = new
            self.i += 1
        self.units = len(lat)
        if traced:
            first = self.i - len(counts)
            self.info["selected_per_round"] = dict(
                _selected(counts),
                frame_start_rounds=sum(self.ref.frame_reset(t, self.frame)
                                       for t in range(first, self.i)))
        ms = np.asarray(lat) * 1e3
        return {"decision_ms_p50": float(np.median(ms)),
                "decision_ms_p99": float(np.percentile(ms, 99))}

    def release(self) -> None:
        self.kept.items = [
            (i, jax.device_get((st.q, dec.q, dec.e, new.q)), a, b)
            for i, st, new, dec, a, b in self.kept.items
        ]
        self.state = self.fn = None

    def check(self, nums: checks.Numbers, control: bool = False) -> None:
        ref, inc = self.ref, self._inc()
        for i, (q_in, q_used, e, q_next), a, b in self.kept.items:
            row = i % self.t_rounds
            args = dict(q_used=q_used, h2=self.bank[row],
                        v_eta=self.v * float(self.eta[row]), inc=inc,
                        radio=self.radio, q_carried=q_in,
                        reset=ref.frame_reset(i, self.frame))
            with nums.answer():
                if control:
                    checks.control_round(nums, ref, **args)
                else:
                    checks.ocean_round(nums, ref, a=a, b=b, e=e, q_next=q_next,
                                       **args)


# ------------------------------------------------------------ grid sweep
class GridSweep(Driver):
    """Back-to-back ``GridEngine.run`` sweeps, fresh channel seeds each."""

    span_names = ("sweep",)
    unit_span = "sweep"

    def setup(self) -> None:
        from repro.sim import GridEngine

        conf = self.conf
        self.engine = GridEngine(deploy.scenarios(conf), deploy.policies(conf))
        self.rng = np.random.default_rng([self.seed, 3])
        self.n_seeds = conf["seeds_per_sweep"]
        self.kept: List = []
        self._ready(self.engine.run(self._seeds()))

    def _seeds(self):
        return [int(s) for s in self.rng.integers(0, 2**31, size=self.n_seeds)]

    @staticmethod
    def _ready(res):
        jax.block_until_ready((res.a, res.b, res.e, res.num_selected))
        return res

    def run(self, seconds: float, traced: bool) -> Dict[str, float]:
        c = self.conf
        cells = len(c["policies"]) * len(c["scenarios"]) * self.n_seeds
        start = time.perf_counter()
        end, last, calls = start + seconds, start, 0
        first = None
        while time.perf_counter() < end:
            seeds = self._seeds()
            with _span(traced, "sweep"):
                res = self._ready(self.engine.run(seeds))
            last = time.perf_counter()
            if first is None:
                first = res
            calls += 1
        self.kept = [first] + ([res] if calls > 1 else [])
        self.units = calls * cells * c["num_rounds"]
        if traced:
            ns = np.asarray(res.num_selected)
            self.info["selected_per_round"] = {
                name: {"min": int(ns[p].min()), "median": float(np.median(ns[p])),
                       "max": int(ns[p].max())}
                for p, name in enumerate(res.policies)}
        return {"sweep_cell_rounds_per_s": self.units / (last - start)}

    def release(self) -> None:
        self.kept = [
            (res.seeds, *jax.device_get((res.a, res.b, res.e, res.h2,
                                         res.budget_inc)))
            for res in self.kept
        ]
        self.engine = None

    def check(self, nums: checks.Numbers, control: bool = False) -> None:
        c, ref = self.conf, self.ref
        t_rounds, k = c["num_rounds"], c["num_clients"]
        budget = np.float32(c["energy_budget_j"])
        inc = self._inc()
        rng = np.random.default_rng([self.seed, 4])
        for seeds, a, b, e, h2, budget_inc in self.kept:
            for s, scn in enumerate(c["scenarios"]):
                for n, seed in enumerate(seeds):
                    h2_ref = ref.channel(seed, t_rounds, k, scn)
                    with nums.answer():
                        checks.channel(nums, ref.channel(seed, t_rounds, k, scn, ref.bf16)
                                       if control else h2[s, n], h2_ref)
                        gap = np.abs(budget_inc[s, n] - inc).max()
                        nums.add("drain_gap", 0.0 if control else gap)
            m = self.traffic["check_rounds"]
            picks = zip(np.arange(m) % len(c["policies"]),
                        rng.integers(0, len(c["scenarios"]), m),
                        rng.integers(0, len(seeds), m),
                        rng.integers(0, t_rounds, m))
            for p, s, n, t in picks:
                pol = c["policies"][p]
                h2_t = h2[s, n, t]
                with nums.answer():
                    if pol["kind"] == "ocean":
                        # The sweep returns no queues: they are replayed from
                        # the energy the program charged, and p3_gap holds
                        # the round's decision to them.
                        q = np.zeros(k, np.float32)
                        for r in range(t):
                            q = ref.queue_update(q, e[p, s, n, r], inc)
                        v_eta = pol["v"] * float(ref.eta(pol["eta"], t_rounds)[t])
                        args = dict(q_used=q, h2=h2_t, v_eta=v_eta, radio=self.radio)
                        if control:
                            checks.control_round(nums, ref, inc=None, **args)
                        else:
                            checks.ocean_round(nums, ref, a=a[p, s, n, t],
                                               b=b[p, s, n, t], e=e[p, s, n, t], **args)
                    else:
                        if pol["kind"] == "smo":
                            cap = np.full(k, inc, np.float64)
                        else:
                            cap = ref.amo_caps(np.full(k, budget), e[p, s, n], t,
                                               t_rounds)
                        if control:
                            checks.control_myopic(nums, ref, cap=cap, h2=h2_t,
                                                  radio=self.radio)
                        else:
                            checks.myopic_round(nums, ref, a=a[p, s, n, t],
                                                b=b[p, s, n, t], e=e[p, s, n, t],
                                                cap=cap, h2=h2_t, radio=self.radio)


DRIVERS = {"closed_loop": ClosedLoop, "grid_sweep": GridSweep}
