"""Smoke test of the OCEAN scheduler on one TPU chip (or four with --chips 4).

    python chip_smoke.py               # phases A, B, C on one TPU chip
    python chip_smoke.py --chips 4     # sharded grid vs one chip, nothing else
    python chip_smoke.py --rehearse    # all phases, tiny sizes, CPU, interpret

Runs the system's normal entry points in one process:

  A  the paper's evaluation grid (3 channel scenarios x OCEAN-A/SMO/AMO x
     10 seeds, K=10, T=300, scan + bisect) through the ``GridEngine``
     that ``run_grid`` wraps, against the same grid on the host CPU
     backend;
  B  one cross-device round set (K=10^5, T=10, b_min=0.1/K, top-128
     ranking, the cross-device radio below) through ``simulate``, with the
     XLA ``newton`` solver and with the Mosaic ``pallas_tiled`` kernel;
  C  the fused whole-trajectory kernel against ``scan`` (K=10^4, T=64,
     newton + top-m, the cross-device radio).

Each phase checks that its decisions are finite and keep the invariants
(queue update and energy accounting identities, sum b <= 1, b >= b_min on
selected clients), prints one JSON line, and raises if a check fails.
The times printed are smoke timings, not benchmark numbers.  The last line
is ``{"ok": true, "device": {...}}``; without a TPU the script exits 2
before running anything, unless ``--rehearse`` asks for the CPU rehearsal.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

# Phase A's reference runs on the host CPU backend of the same process.
if os.environ.get("JAX_PLATFORMS") and "cpu" not in os.environ["JAX_PLATFORMS"]:
    os.environ["JAX_PLATFORMS"] += ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import V_DEFAULT, image_experiment  # noqa: E402
from repro.compile_cache import enable_compilation_cache  # noqa: E402
from repro.core import PolicyParams, Scenario, paper_scenarios  # noqa: E402
from repro.core.energy import RadioParams  # noqa: E402
from repro.core.ocean import simulate  # noqa: E402
from repro.sim import GridEngine  # noqa: E402

# Full sizes, and the tiny ones --rehearse runs on CPU.  Phases B and C
# run the full sizes under a cross-device radio: a 100 MHz carrier (the
# widest 5G NR FR1 channel), a 1 s upload window and a 20 kbit compressed
# update.  Alg. 1 starts with empty queues, so round 0 selects every
# client at b = 1/K; under the paper's radio (10 MHz, 0.3 s, 340 kbit)
# that costs ~1e21 J per client at K=10^4 and no client is selected
# again, which would leave nothing to compare.  The tiny sizes need a
# larger update for the same effect.
CROSS_DEVICE_RADIO = dict(bandwidth_hz=100e6, deadline_s=1.0, model_bits=2e4)
SIZES = {
    False: dict(a_rounds=300, a_seeds=10, a_learn=True, b_k=100_000,
                b_rounds=10, c_k=10_000, c_rounds=64, top_m=128,
                radio=CROSS_DEVICE_RADIO),
    True: dict(a_rounds=12, a_seeds=2, a_learn=False, b_k=300, b_rounds=4,
               c_k=64, c_rounds=9, top_m=16,
               radio=dict(CROSS_DEVICE_RADIO, model_bits=1e6)),
}
AGREE_MIN = 0.99        # share of equal selection entries a phase must reach
ENERGY_GAP_MAX = 0.01   # phase A: per-cell energy gap vs the CPU, before a fork


def _ready(tree):
    return jax.tree_util.tree_map(
        lambda x: x.block_until_ready() if hasattr(x, "block_until_ready") else x,
        tree,
    )


def _timed(fn):
    """Run ``fn`` twice; return (result, compile seconds, steady seconds).

    Compile seconds are the first call's wall time minus the second's: the
    first call also traces and compiles (or hits the cache).  Both calls
    end in ``block_until_ready``.
    """
    t0 = time.perf_counter()
    _ready(fn())
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = _ready(fn())
    steady = time.perf_counter() - t0
    return out, first - steady, steady


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _emit(record):
    record["peak_bytes_in_use"] = _peak_bytes()
    print(json.dumps(record), flush=True)


def _check(cond, what):
    if not bool(cond):
        raise AssertionError(f"chip smoke check failed: {what}")


def _check_allocation(a, b, b_min, what):
    """Finite decisions, sum b <= 1 per round, b >= b_min on selected."""
    a, b = np.asarray(a, bool), np.asarray(b, np.float64)
    _check(np.isfinite(b).all(), f"{what}: non-finite bandwidth")
    _check((b.sum(axis=-1) <= 1.0 + 1e-5).all(), f"{what}: sum b > 1")
    _check((np.where(a, b, np.inf) >= b_min * (1.0 - 1e-6)).all(),
           f"{what}: selected client below b_min")


def _check_trajectory(cfg, state, decs, inc, what):
    """The tests/test_metrics.py identities, replayed in float32 on the host.

    q(0) = 0 and q(t+1) = [q(t) + e(t) - inc(t)]^+ (one frame: no reset),
    the final carried queue is the last update, and energy_spent is the
    sequential float32 sum of the per-round energy.
    """
    _check(cfg.R == cfg.num_rounds, f"{what}: identity replay assumes R = T")
    q = np.asarray(decs.q, np.float32)
    e = np.asarray(decs.e, np.float32)
    inc = np.asarray(inc, np.float32)
    _check(np.isfinite(q).all() and np.isfinite(e).all(),
           f"{what}: non-finite queue or energy")
    nxt = np.maximum((q + e) - inc, np.float32(0.0))
    _check((q[0] == 0).all(), f"{what}: q(0) != 0")
    _check(np.array_equal(q[1:], nxt[:-1]), f"{what}: queue update identity")
    _check(np.array_equal(np.asarray(state.q), nxt[-1]),
           f"{what}: final queue != last update")
    spent = np.cumsum(e, axis=0, dtype=np.float32)[-1]
    _check(np.array_equal(np.asarray(state.energy_spent), spent),
           f"{what}: energy accounting identity")
    _check_allocation(decs.a, decs.b, float(cfg.radio.b_min), what)


def _selection_agreement(a_ref, a_got):
    a_ref, a_got = np.asarray(a_ref, bool), np.asarray(a_got, bool)
    return float(np.mean(a_ref == a_got))


# ---------------------------------------------------------------- phase A
def _paper_grid(sizes, shard):
    """``run_grid`` on the paper grid, as an engine that can run twice."""
    engine = GridEngine(
        list(paper_scenarios(num_rounds=sizes["a_rounds"]).values()),
        [("ocean-a", PolicyParams(v=V_DEFAULT)), "smo", "amo"],
        experiment=image_experiment() if sizes["a_learn"] else None,
        shard=shard,
    )
    return lambda: engine.run(range(sizes["a_seeds"]))


def phase_a(sizes, on_chip):
    run = _paper_grid(sizes, shard=False)
    res, compile_s, steady = _timed(run)
    if on_chip:
        # Built under the CPU default device, so its arrays live there too.
        with jax.default_device(jax.devices("cpu")[0]):
            ref = _paper_grid(sizes, shard=False)()
    else:  # the CPU rehearsal's device already is the host CPU
        ref = run()
    a, a_ref = np.asarray(res.a), np.asarray(ref.a)          # (P, S, N, T, K)
    e, e_ref = np.asarray(res.e, np.float32), np.asarray(ref.e, np.float64)
    # A cell forks at its first round whose selections differ: from there
    # on the two backends run different, equally valid trajectories.
    differs = (a != a_ref).any(axis=4)                       # (P, S, N, T)
    fork = np.where(differs.any(axis=3), differs.argmax(axis=3), a.shape[3])
    before = np.arange(a.shape[3]) < fork[..., None]         # (P, S, N, T)

    def gap(upto):
        total = np.where(upto[..., None], e, 0.0).sum(axis=(3, 4), dtype=np.float64)
        total_ref = np.where(upto[..., None], e_ref, 0.0).sum(axis=(3, 4))
        return np.abs(total - total_ref) / np.maximum(np.abs(total_ref), 1e-30)

    gap_run, gap_before_fork = gap(np.ones_like(before)), gap(before)
    agree = _selection_agreement(a_ref, a)
    _emit({
        "phase": "A", "what": "paper grid, scan+bisect, device vs host CPU",
        "cells": int(np.prod(a.shape[:3])), "compile_s": compile_s,
        "steady_s": steady, "selection_agreement": agree,
        "first_divergent_round": {
            f"{res.policies[p]}/{res.scenarios[s]}/{res.seeds[n]}": int(fork[p, s, n])
            for p, s, n in zip(*np.nonzero(fork < a.shape[3]))
        },
        "energy_gap_before_fork_max": float(gap_before_fork.max()),
        "energy_gap_max": float(gap_run.max()),
        "energy_gap_per_cell": gap_run.round(8).tolist(),
    })
    b_min = float(paper_scenarios()["stationary"].radio.b_min)
    _check_allocation(a, res.b, b_min, "A")
    _check(np.isfinite(e).all(), "A: non-finite energy")
    # The grid's energy_spent is a reduction over T, whose order the
    # backend picks: hold it to the float32 bound of any summation order,
    # (T - 1) * 2^-24 * sum|e|, around the exact sum.
    spent = np.asarray(e, np.float64).sum(axis=3)
    bound = (e.shape[3] - 1) * 2.0**-24 * np.abs(e).sum(axis=3, dtype=np.float64)
    _check((np.abs(np.asarray(res.energy_spent, np.float64) - spent) <= bound).all(),
           "A: energy accounting identity")
    _check(agree >= AGREE_MIN, f"A: selection agreement {agree} < {AGREE_MIN}")
    _check(gap_before_fork.max() <= ENERGY_GAP_MAX,
           f"A: energy gap before the fork {gap_before_fork.max()}")


# ---------------------------------------------------------- phases B and C
def _run_simulate(scn, on_chip, **cfg_overrides):
    """Jit ``simulate`` for one scenario; return timings and outputs."""
    cfg = scn.ocean_config()
    if cfg_overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    h2 = scn.sample_channel(0)
    eta = scn.eta_seq()
    t0 = time.perf_counter()
    fn = jax.jit(lambda h: simulate(cfg, h, eta, V_DEFAULT)).lower(h2).compile()
    compile_s = time.perf_counter() - t0
    custom = "tpu_custom_call" in fn.as_text() if on_chip else None
    (state, decs), _, steady = _timed(lambda: fn(h2))
    inc = np.broadcast_to(
        np.asarray(cfg.budgets(), np.float32) / np.float32(cfg.num_rounds),
        (cfg.num_rounds, cfg.num_clients),
    )
    return cfg, state, decs, inc, custom, compile_s, steady


def _compare_runs(phase, what, ref_name, ref, got_name, got, on_chip):
    (cfg_r, st_r, dec_r, inc, _, comp_r, steady_r) = ref
    (cfg_g, st_g, dec_g, _, custom, comp_g, steady_g) = got
    a_r, a_g = np.asarray(dec_r.a, bool), np.asarray(dec_g.a, bool)
    agree = _selection_agreement(a_r, a_g)
    round0 = bool(np.array_equal(a_r[0], a_g[0]))
    _emit({
        "phase": phase, "what": what,
        "K": cfg_r.num_clients, "T": cfg_r.num_rounds,
        f"{ref_name}_compile_s": comp_r, f"{ref_name}_steady_s": steady_r,
        f"{got_name}_compile_s": comp_g, f"{got_name}_steady_s": steady_g,
        "tpu_custom_call": custom,
        "round0_identical": round0, "selection_agreement": agree,
        "selected_per_round": a_g.sum(axis=1).tolist(),
    })
    _check_trajectory(cfg_r, st_r, dec_r, inc, f"{phase}/{ref_name}")
    _check_trajectory(cfg_g, st_g, dec_g, inc, f"{phase}/{got_name}")
    if on_chip:
        _check(custom, f"{phase}: no tpu_custom_call in the {got_name} program")
    _check(round0, f"{phase}: round-0 selections differ")
    _check(agree >= AGREE_MIN, f"{phase}: selection agreement {agree}")


def phase_b(sizes, on_chip):
    k = sizes["b_k"]
    scn = Scenario(
        name="cross_device", num_clients=k, num_rounds=sizes["b_rounds"],
        radio=RadioParams(b_min=0.1 / k, **sizes["radio"]),
        ranking="topm", top_m=sizes["top_m"],
    )
    ref = _run_simulate(scn, False, solver="newton")
    got = _run_simulate(scn, on_chip, solver="pallas_tiled")
    _compare_runs("B", "cross-device round, XLA newton vs Mosaic pallas_tiled",
                  "newton", ref, "pallas_tiled", got, on_chip)


def phase_c(sizes, on_chip):
    k = sizes["c_k"]
    scn = Scenario(
        name="fused", num_clients=k, num_rounds=sizes["c_rounds"],
        radio=RadioParams(b_min=0.1 / k, **sizes["radio"]),
        solver="newton", ranking="topm", top_m=sizes["top_m"],
    )
    ref = _run_simulate(scn, False, traj="scan")
    got = _run_simulate(scn, on_chip, traj="fused")
    _compare_runs("C", "fused trajectory kernel vs scan, newton + top-m",
                  "scan", ref, "fused", got, on_chip)


# ----------------------------------------------------------- four chips
def phase_four_chips(sizes):
    _check(len(jax.devices()) == 4, f"--chips 4 found {len(jax.devices())} devices")
    one, one_compile, one_steady = _timed(_paper_grid(sizes, shard=False))
    four, four_compile, four_steady = _timed(_paper_grid(sizes, shard=True))
    fields = ("a", "b", "e", "num_selected", "energy_spent")
    equal = {f: bool(np.array_equal(np.asarray(getattr(one, f)),
                                    np.asarray(getattr(four, f))))
             for f in fields}
    _check_allocation(four.a, four.b,
                      float(paper_scenarios()["stationary"].radio.b_min), "4x")
    _emit({
        "phase": "A4", "what": "paper grid sharded over 4 chips vs 1 chip",
        "one_chip_compile_s": one_compile, "one_chip_steady_s": one_steady,
        "four_chip_compile_s": four_compile, "four_chip_steady_s": four_steady,
        "bitwise_equal": equal,
    })
    _check(all(equal.values()), f"sharded != unsharded: {equal}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on CPU with interpreted kernels")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.rehearse:
        print(f"chip_smoke: no TPU found (JAX sees {dev.platform!r}); this "
              f"script needs the chip, or --rehearse for the CPU rehearsal",
              file=sys.stderr)
        return 2
    if on_chip and args.rehearse:
        print("chip_smoke: --rehearse is for hosts without a TPU", file=sys.stderr)
        return 2
    if on_chip:
        print(json.dumps({"compilation_cache": enable_compilation_cache()}),
              flush=True)
    sizes = SIZES[args.rehearse]
    if args.chips == 4:
        phase_four_chips(sizes)
    else:
        phase_a(sizes, on_chip)
        phase_b(sizes, on_chip)
        phase_c(sizes, on_chip)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
