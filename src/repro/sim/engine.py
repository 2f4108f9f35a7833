"""Scenario-grid simulation engine: one compiled program for the whole sweep.

The paper's evaluation is a grid over (policy, scenario, seed).  The legacy
path simulated one cell at a time — a Python loop that re-traced the
``lax.scan`` trajectory for every combination.  ``GridEngine`` instead
builds a single jitted program that

  1. samples every scenario's *environment* — channel process and budget
     process (``repro.env``) — with one vmapped ``lax.scan`` over the
     (scenario, seed) axes.  All registered processes lower to one shared
     parameter pytree, so a grid mixing i.i.d. Rayleigh cells with
     Markov-fading, blockage, or mobile-client cells still traces a
     single program; the ``iid_rayleigh`` shim is bit-identical to the
     legacy ``ChannelModel.sample`` per seed,
  2. runs every registered policy over every (scenario, seed) cell via
     nested ``vmap`` (policies are unrolled — they are structurally
     different programs — while scenarios and seeds are batched axes),
  3. optionally runs the FedAvg learning trajectory (``WflnExperiment``)
     for every cell, again under nested ``vmap``,

and returns stacked ``(P, S, N, T, K)`` outputs.  The program is traced
and compiled exactly once per ``GridEngine``; subsequent ``run`` calls with
the same grid shape reuse the executable.

Scenario-dependent *arrays* (environment params, eta schedules, budgets,
radio physics — bandwidth/deadline/noise/b_min lower to traced per-round
sequences via ``repro.env.radio``, so they form sweepable grid axes) are
batched; scenario-dependent *statics* (T, K, frame length) must agree
across the grid — they shape the compiled program.

Environment streams are keyed by ``fold_in(PRNGKey(seed), salt)`` where
``salt`` is a stable content hash of the scenario's EnvSpec — never its
grid index — so adding, removing, or reordering scenarios cannot change
any other cell's draws (see ``repro.env.spec``).

Three execution knobs (see the README "Performance" section):

* ``solver=`` picks the P3/P4 backend (``repro.core.solvers``) for the
  whole grid — a compiled-program static, so all scenarios must agree;
* ``traj=`` picks the trajectory backend for OCEAN policies (``scan``,
  the bit-stable ``lax.scan``, or ``fused`` — the whole-trajectory
  Pallas kernel of ``repro.kernels.ocean_traj``; the engine's nested
  vmaps batch its launch across all (scenario, seed) cells);
* ``shard=`` distributes the flattened (S*N) cell axis over an
  auto-built mesh of all local devices via ``shard_map`` (padded to the
  mesh size, donated input buffers off-CPU).  Cells are independent, so
  the sharded program is bit-identical to the unsharded nested-vmap one.

Preemption safety (``checkpoint=``, see the README "Checkpoint/resume"
section): a ``repro.checkpoint.CheckpointSpec`` switches ``run`` to a
*segmented* driver — the T-round trajectory is split at multiples of
``every_rounds``, each segment is one jitted program (one ``lax.scan``
or one fused-kernel launch per policy, continuing from carried state),
and at every boundary the full carry plus the decision/telemetry prefix
is snapshotted atomically.  ``run(..., resume_from=...)`` restores the
latest committed snapshot and re-enters the same segment grid, so a
killed-and-resumed sweep is bitwise identical to an uninterrupted one —
a structural identity (same op sequence), not a numerical accident.
``checkpoint=None`` (the default) keeps the legacy single-program path
byte-identical.  The segmented driver is host-side and runs unsharded
(``shard=`` is ignored); environment streams are re-sampled
deterministically from the seeds on resume, so snapshots hold only
policy carries and trace prefixes.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from repro.checkpoint import trajectory as ckpt_io
from repro.checkpoint.trajectory import CheckpointSpec
from repro.guard.spec import GuardSpec
from repro.core.baselines import PolicyTrace
from repro.core.ocean import OceanConfig
from repro.core.policy import (
    Policy,
    PolicyParams,
    get_policy,
    resolve_params,
)
from repro.core.scenario import Scenario
from repro.env.channel import UMA_PROCESS, sample_channel_process
from repro.env.energy import sample_budget_process
from repro.env.failure import TracedFailure, traced_failure
from repro.env.radio import TracedRadio, sample_radio_process
from repro.env.spec import env_cell_keys, failure_cell_key, radio_cell_key
from repro.obs.metrics import MetricsSpec, finalize_metrics
from repro.obs.spans import host_span, trace_span

Array = jax.Array

PolicySpec = Union[str, Policy, Tuple[Union[str, Policy], PolicyParams]]


class GridResult(NamedTuple):
    """Stacked outputs of one grid sweep.

    Leading axes are (P policies, S scenarios, N seeds); labels for each
    axis ride along so downstream code can index by name.
    """

    a: Array                 # (P, S, N, T, K) bool selections
    b: Array                 # (P, S, N, T, K) bandwidth ratios
    e: Array                 # (P, S, N, T, K) per-round energy
    num_selected: Array      # (P, S, N, T)
    energy_spent: Array      # (P, S, N, K) — per-client totals over T
    h2: Array                # (S, N, T, K) sampled channel power gains
    history: Optional[Dict[str, Array]]  # each (P, S, N, T); None w/o experiment
    policies: Tuple[str, ...]
    scenarios: Tuple[str, ...]
    seeds: Tuple[int, ...]
    budget_inc: Optional[Array] = None    # (S, N, T, K) per-round increments
    budget_total: Optional[Array] = None  # (S, N, K) realized totals H_k
    radio_seq: Optional[TracedRadio] = None  # pytree of (S, N, T) radio leaves
    # (P, S, N, T, K) selected-and-delivered masks plus the realized
    # reliability streams ((S, N, T, K) masks, (S, N, K) declared rates);
    # None for grids without an active repro.env.failure process — the
    # legacy payloads stay byte-identical.
    delivered: Optional[Array] = None
    failure_seq: Optional[TracedFailure] = None
    # per-policy in-graph telemetry: one entry per policy-axis index (None
    # for policies without the Lyapunov machinery), each a dict of
    # "<collector>/<reduction>" -> (S, N, ...) arrays.  A tuple — not a
    # name-keyed dict — because the policy axis may repeat a name (e.g.
    # fig16's V sweep registers "ocean" once per V).  None when the grid
    # ran without a MetricsSpec.
    metrics: Optional[Tuple[Optional[Dict[str, Array]], ...]] = None

    def cell(self, policy: str, scenario: str, seed: int) -> PolicyTrace:
        """Extract one (policy, scenario, seed) cell as a PolicyTrace."""
        for label, name, axis in (
            ("policy", policy, self.policies),
            ("scenario", scenario, self.scenarios),
        ):
            if axis.count(name) > 1:
                raise ValueError(
                    f"{label} name {name!r} appears {axis.count(name)} "
                    f"times on the {label} axis (e.g. a parameter sweep); "
                    f"index the result arrays positionally instead of via "
                    f"cell()"
                )
            if name not in axis:
                raise ValueError(
                    f"unknown {label} {name!r}; this grid's {label} axis: "
                    f"{', '.join(axis)}"
                )
        if seed not in self.seeds:
            raise ValueError(
                f"unknown seed {seed!r}; this grid ran seeds "
                f"{', '.join(str(s) for s in self.seeds)}"
            )
        p = self.policies.index(policy)
        s = self.scenarios.index(scenario)
        n = self.seeds.index(seed)
        mets = None
        if self.metrics is not None and self.metrics[p] is not None:
            mets = {k: v[s, n] for k, v in self.metrics[p].items()}
        return PolicyTrace(
            a=self.a[p, s, n],
            b=self.b[p, s, n],
            e=self.e[p, s, n],
            num_selected=self.num_selected[p, s, n],
            metrics=mets,
            delivered=(
                None if self.delivered is None else self.delivered[p, s, n]
            ),
        )


def _resolve_policy_specs(policies: Sequence[PolicySpec]):
    resolved = []
    for spec in policies:
        if isinstance(spec, tuple):
            name_or_pol, params = spec
        else:
            name_or_pol, params = spec, PolicyParams()
        pol = get_policy(name_or_pol)
        resolved.append((pol, params))
    return resolved


def _check_compatible(scenarios: Sequence[Scenario]) -> Scenario:
    # ``radio`` is deliberately absent: radio physics lower to traced
    # per-round sequences batched over the scenario axis, so bandwidth /
    # deadline / noise / b_min may all vary across the grid.
    base = scenarios[0]
    for sc in scenarios[1:]:
        mismatches = [
            f"{field}: {getattr(base, field)!r} != {getattr(sc, field)!r}"
            for field in (
                "num_rounds", "num_clients", "frame_len", "solver",
                "ranking", "top_m", "block_k", "traj", "metrics",
                "checkpoint", "failure_mode", "guard",
            )
            if getattr(base, field) != getattr(sc, field)
        ]
        if mismatches:
            raise ValueError(
                f"scenario {sc.name!r} is grid-incompatible with "
                f"{base.name!r}: these fields shape the compiled program and "
                f"must agree ({'; '.join(mismatches)}); run separate grids"
            )
    return base


class GridEngine:
    """Compile once, sweep many: vectorized (policy, scenario, seed) grids.

    Args:
      scenarios: Scenario specs sharing (T, K, frame_len); radio physics
                 and environments may differ per scenario.
      policies:  policy names, Policy objects, or (name, PolicyParams)
                 pairs — e.g. ``[("ocean", PolicyParams(v=v)) for v in VS]``
                 turns the policy axis into a V sweep.
      experiment: optional ``WflnExperiment``; when given, every cell's
                 FedAvg history is computed inside the same program.
      solver:    P4/OCEAN-P backend override (``repro.core.solvers``);
                 None keeps the scenarios' ``solver`` field (default
                 ``bisect``, the bit-stable reference).
      ranking:   rho-ranking override (``sort`` | ``topm``, see
                 ``repro.core.selection``); with ``top_m``/``block_k``
                 these join the grid's must-agree compiled-program
                 statics.  None keeps the scenarios' fields.
      top_m:     candidate-prefix length override for ``ranking="topm"``.
      block_k:   client-tile width override for ``solver="pallas_tiled"``.
      traj:      trajectory backend override for OCEAN policies
                 (``scan`` | ``fused``, see ``repro.kernels.ocean_traj``);
                 None keeps the scenarios' ``traj`` field (default
                 ``scan``).  Under ``fused`` the engine's nested
                 (scenario, seed) vmaps batch the trajectory kernel into
                 one multi-cell launch.  Also a compiled-program static.
      metrics:   in-graph telemetry override (a ``repro.obs.MetricsSpec``);
                 None keeps the scenarios' ``metrics`` field (default no
                 metrics).  When set, ``GridResult.metrics`` carries one
                 telemetry dict per policy-axis entry — recorded inside
                 the same single compiled program.  Also a
                 compiled-program static joining the must-agree set.
      checkpoint: preemption-safe segmented execution override (a
                 ``repro.checkpoint.CheckpointSpec``); None keeps the
                 scenarios' ``checkpoint`` field (default off — the
                 legacy single-program path, byte-identical).  When set,
                 ``run`` executes segment by segment and snapshots the
                 full sweep state at every ``every_rounds`` boundary;
                 ``run(..., resume_from=...)`` restores the latest
                 snapshot.  Joins the must-agree statics; the segmented
                 driver runs unsharded (``shard=`` is ignored).
      guard:     guarded-execution override (a ``repro.guard.GuardSpec``:
                 bounded-energy admission, solver fallback cascade,
                 stream sanitization); None keeps the scenarios' ``guard``
                 field (default off — every legacy path byte-identical).
                 Also a compiled-program static joining the must-agree
                 set.
      shard:     multi-device execution: the flattened (S*N) cell axis is
                 ``shard_map``-ped over an auto-built mesh of all local
                 devices, with donated input buffers (off-CPU).  None =
                 auto (shard iff more than one device is visible), True =
                 force (a 1-device mesh is a no-op), False = never.  The
                 sharded program is bit-identical to the unsharded one.
    """

    def __init__(
        self,
        scenarios: Sequence[Scenario],
        policies: Sequence[PolicySpec],
        *,
        experiment=None,
        solver: Optional[str] = None,
        shard: Optional[bool] = None,
        ranking: Optional[str] = None,
        top_m: Optional[int] = None,
        block_k: Optional[int] = None,
        traj: Optional[str] = None,
        metrics: Optional[MetricsSpec] = None,
        checkpoint: Optional[CheckpointSpec] = None,
        guard: Optional[GuardSpec] = None,
    ):
        if not scenarios or not policies:
            raise ValueError("need at least one scenario and one policy")
        self.scenarios = tuple(scenarios)
        base = _check_compatible(self.scenarios)
        self.cfg: OceanConfig = base.ocean_config()
        overrides = {
            k: v
            for k, v in (
                ("solver", solver),
                ("ranking", ranking),
                ("top_m", top_m),
                ("block_k", block_k),
                ("traj", traj),
                ("metrics", metrics),
                ("checkpoint", checkpoint),
                ("guard", guard),
            )
            if v is not None
        }
        if overrides:
            # replace() re-runs __post_init__, failing fast on bad names.
            self.cfg = dataclasses.replace(self.cfg, **overrides)
        self._resolved = _resolve_policy_specs(policies)
        self.policies = tuple(pol.name for pol, _ in self._resolved)
        self.experiment = experiment

        # Scenario-batched arrays (the vmapped axes): every scenario's
        # environment lowers to the same param pytrees, stacked on axis 0.
        lowered = [sc.lower_env() for sc in self.scenarios]
        self._chan_params = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[l.channel for l in lowered]
        )
        self._budget_params = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[l.budget for l in lowered]
        )
        self._radio_params = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[l.radio for l in lowered]
        )
        # Failure streams are gated by a Python static: grids where every
        # scenario runs failure="none" trace the exact pre-failure program
        # (and serialize the exact pre-failure payloads).
        self._has_failure = any(
            sc.env_spec().failure != "none" for sc in self.scenarios
        )
        self._failure_params = (
            jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *[l.failure for l in lowered]
            )
            if self._has_failure
            else None
        )
        self._env_salts = jnp.asarray(
            [l.key_salt for l in lowered], jnp.uint32
        )
        # The UMa sector's float64 path is traced only where a scenario
        # is a sector.
        self._has_uma = any(
            sc.env_spec().channel == UMA_PROCESS for sc in self.scenarios
        )
        self._etas = jnp.stack([sc.eta_seq() for sc in self.scenarios])

        devices = jax.devices()
        self._ndev = len(devices)
        self._shard = bool(shard) if shard is not None else self._ndev > 1
        if self._shard:
            mesh = Mesh(np.asarray(devices), ("cells",))
            pc, rep = PartitionSpec("cells"), PartitionSpec()
            fn = jax.shard_map(
                self._build_flat,
                mesh=mesh,
                in_specs=(pc, pc, pc, pc, pc, pc, pc, pc, rep, pc),
                out_specs=pc,
                check_vma=False,
            )
            # Flattened inputs are rebuilt per run() call, so their buffers
            # can be donated to the program (XLA aliases them into the
            # outputs).  CPU has no donation support — skip the warning.
            donate = (
                ()
                if jax.default_backend() == "cpu"
                else (0, 1, 2, 3, 4, 5, 6, 7, 9)
            )
            self._fn = jax.jit(fn, donate_argnums=donate)
        else:
            self._fn = jax.jit(self._build)

        # Segmented (checkpointed) execution: per-segment programs cached
        # by segment length — equal-length segments share one executable
        # (the global round offset t0 is a traced argument).
        self._seg_cache: Dict[int, object] = {}
        self._sample_fn = jax.jit(self._sample_grid_env)
        self._keys_fn = jax.jit(self._grid_keys)
        if self.cfg.checkpoint is not None:
            missing = [
                pol.name for pol, _ in self._resolved if pol.seg_fn is None
            ]
            if missing:
                raise ValueError(
                    f"checkpointed (segmented) execution needs seg_init/"
                    f"seg_fn hooks, missing for: {', '.join(missing)}; "
                    f"register them or run without checkpoint="
                )

    # -- environment sampling (shared by the legacy and segmented paths) -----
    def _sample_grid_env(
        self, seed_arr, chan_params, budget_params, radio_params, env_salts,
        failure_params=None,
    ):
        """Sample every (scenario, seed) cell's environment streams.

        The exact traced ops of the legacy ``_build`` sampling block — the
        segmented driver re-runs this same program, so a resumed sweep
        re-derives bit-identical streams from the seeds instead of
        snapshotting them.  ``failure_params=None`` (a leafless pytree)
        skips reliability sampling entirely, keeping pre-failure grids
        byte-identical; active failures draw from their own dedicated key
        stream, so they never perturb the channel/budget/radio draws.
        """
        cfg = self.cfg
        T, K = cfg.num_rounds, cfg.num_clients

        def sample_cell(cp, bp, rp, fp, salt, seed):
            # The fading key mirrors ChannelModel.sample exactly (shared
            # across scenarios); scenario-specific streams fold in the
            # spec's stable content salt (see module docstring).
            fade_key = jax.random.PRNGKey(seed)
            k_chan, k_budget = env_cell_keys(fade_key, salt)
            k_radio = radio_cell_key(fade_key, salt)
            h2 = sample_channel_process(cp, fade_key, k_chan, T, K,
                                        uma=self._has_uma)
            dh, total = sample_budget_process(bp, k_budget, T, K)
            radio_seq = sample_radio_process(rp, k_radio, T)
            failure_seq = None
            if fp is not None:
                k_fail = failure_cell_key(fade_key, salt)
                failure_seq = traced_failure(fp, k_fail, T, K)
            return h2, dh, total, radio_seq, failure_seq

        over_seeds = jax.vmap(
            sample_cell, in_axes=(None, None, None, None, None, 0)
        )
        return jax.vmap(
            over_seeds, in_axes=(0, 0, 0, 0, 0, None)
        )(chan_params, budget_params, radio_params, failure_params, env_salts,
          seed_arr)

    def _grid_keys(self, seed_arr, base_key):
        def cell_keys(s_idx):
            return jax.vmap(
                lambda seed: jax.random.fold_in(
                    jax.random.fold_in(base_key, s_idx), seed
                )
            )(seed_arr)

        return jax.vmap(cell_keys)(jnp.arange(len(self.scenarios)))

    # -- the single compiled program ----------------------------------------
    @staticmethod
    def _stack_delivered(traces):
        """(P, ...) delivered stack; policies that ignore failures (e.g.
        ``pattern``) report their selections as delivered."""
        if all(t.delivered is None for t in traces):
            return None
        return jnp.stack(
            [t.a if t.delivered is None else t.delivered for t in traces]
        )

    def _build(
        self, seed_arr, chan_params, budget_params, radio_params, env_salts,
        etas, base_key, learn_keys, failure_params=None,
    ):
        cfg = self.cfg

        with trace_span("grid/sample_env"):
            (
                h2, budget_inc, budget_total, radio_seq, failure_seq,
            ) = self._sample_grid_env(
                seed_arr, chan_params, budget_params, radio_params, env_salts,
                failure_params,
            )
        # h2/budget_inc: (S, N, T, K); budget_total: (S, N, K);
        # radio_seq: TracedRadio of (S, N, T) leaves;
        # failure_seq: TracedFailure of (S, N, T, K)/(S, N, K) leaves or None

        keys = self._grid_keys(seed_arr, base_key)  # (S, N, 2)

        traces = []
        histories = []
        for pol, pp in self._resolved:
            def cell(
                h2_cell, eta_s, total_cell, inc_cell, radio_cell, failure_cell,
                key_cell, pol=pol, pp=pp,
            ):
                params = resolve_params(
                    pol,
                    cfg,
                    pp._replace(key=pp.key if pp.key is not None else key_cell),
                    scenario_eta=eta_s,
                    scenario_budgets=total_cell,
                    scenario_budget_seq=inc_cell,
                    scenario_radio_seq=radio_cell,
                    scenario_failure_seq=failure_cell,
                )
                return pol.trace_fn(cfg, h2_cell, params)

            with trace_span(f"grid/policy/{pol.name}"):
                over_seeds = jax.vmap(cell, in_axes=(0, None, 0, 0, 0, 0, 0))
                tr = jax.vmap(over_seeds)(
                    h2, etas, budget_total, budget_inc, radio_seq, failure_seq,
                    keys,
                )                                                 # (S, N, ...)
            traces.append(tr)
            if self.experiment is not None:
                run = self.experiment.run
                histories.append(jax.vmap(jax.vmap(run))(learn_keys, tr))

        a = jnp.stack([t.a for t in traces])
        b = jnp.stack([t.b for t in traces])
        e = jnp.stack([t.e for t in traces])
        ns = jnp.stack([t.num_selected for t in traces])
        dlv = self._stack_delivered(traces)
        metrics = tuple(t.metrics for t in traces)
        history = (
            {k: jnp.stack([h[k] for h in histories]) for k in histories[0]}
            if histories
            else None
        )
        return (
            a, b, e, ns, h2, budget_inc, budget_total, radio_seq, history,
            metrics, dlv, failure_seq,
        )

    # -- the sharded program: one vmap over the flattened (S*N) cell axis ----
    def _build_flat(
        self, seed_flat, sidx_flat, chan_params, budget_params, radio_params,
        failure_params, env_salts, etas, base_key, learn_keys,
    ):
        """Per-cell program over the flattened (padded) cell axis.

        Runs inside ``shard_map``: every argument except ``base_key``
        carries a leading cell axis split over the mesh, so each device
        executes this vmap on its local chunk.  The per-cell math is the
        same as ``_build``'s nested vmaps (cell c = s * N + n), so the
        sharded sweep is bit-identical to the unsharded one.
        """
        cfg = self.cfg
        T, K = cfg.num_rounds, cfg.num_clients

        def cell(seed, s_idx, cp, bp, rp, fp, salt, eta_s, lkey):
            fade_key = jax.random.PRNGKey(seed)
            k_chan, k_budget = env_cell_keys(fade_key, salt)
            k_radio = radio_cell_key(fade_key, salt)
            h2 = sample_channel_process(cp, fade_key, k_chan, T, K,
                                        uma=self._has_uma)
            dh, total = sample_budget_process(bp, k_budget, T, K)
            radio_seq = sample_radio_process(rp, k_radio, T)
            failure_seq = None
            if fp is not None:
                k_fail = failure_cell_key(fade_key, salt)
                failure_seq = traced_failure(fp, k_fail, T, K)
            key_cell = jax.random.fold_in(
                jax.random.fold_in(base_key, s_idx), seed
            )

            traces, hists = [], []
            for pol, pp in self._resolved:
                params = resolve_params(
                    pol,
                    cfg,
                    pp._replace(key=pp.key if pp.key is not None else key_cell),
                    scenario_eta=eta_s,
                    scenario_budgets=total,
                    scenario_budget_seq=dh,
                    scenario_radio_seq=radio_seq,
                    scenario_failure_seq=failure_seq,
                )
                with trace_span(f"grid/policy/{pol.name}"):
                    tr = pol.trace_fn(cfg, h2, params)
                traces.append(tr)
                if self.experiment is not None:
                    hists.append(self.experiment.run(lkey, tr))
            a = jnp.stack([t.a for t in traces])
            b = jnp.stack([t.b for t in traces])
            e = jnp.stack([t.e for t in traces])
            ns = jnp.stack([t.num_selected for t in traces])
            dlv = self._stack_delivered(traces)
            metrics = tuple(t.metrics for t in traces)
            history = (
                {k: jnp.stack([h[k] for h in hists]) for k in hists[0]}
                if hists
                else {}
            )
            return (
                a, b, e, ns, h2, dh, total, radio_seq, history, metrics,
                dlv, failure_seq,
            )

        return jax.vmap(cell)(
            seed_flat, sidx_flat, chan_params, budget_params, radio_params,
            failure_params, env_salts, etas, learn_keys,
        )

    def _run_sharded(self, seed_arr, base_key, learn_keys):
        """Flatten (S, N) -> padded (C,), execute, restore the grid axes."""
        S, N = len(self.scenarios), seed_arr.shape[0]
        C = S * N
        pad = (-C) % self._ndev

        def pad_cells(x):
            if pad == 0:
                return x
            return jnp.concatenate([x, jnp.repeat(x[:1], pad, axis=0)], axis=0)

        def per_scenario(tree):  # (S, ...) leaves -> (C_pad, ...), s-major
            return jax.tree_util.tree_map(
                lambda x: pad_cells(jnp.repeat(x, N, axis=0)), tree
            )

        seed_flat = pad_cells(jnp.tile(seed_arr, S))
        sidx_flat = pad_cells(jnp.repeat(jnp.arange(S), N))
        lk_flat = pad_cells(learn_keys.reshape((C,) + learn_keys.shape[2:]))

        outs = self._fn(
            seed_flat,
            sidx_flat,
            per_scenario(self._chan_params),
            per_scenario(self._budget_params),
            per_scenario(self._radio_params),
            per_scenario(self._failure_params),
            pad_cells(jnp.repeat(self._env_salts, N, axis=0)),
            per_scenario(self._etas),
            base_key,
            lk_flat,
        )

        def to_grid(tree):  # (C_pad, ...) leaves -> (S, N, ...)
            return jax.tree_util.tree_map(
                lambda x: x[:C].reshape((S, N) + x.shape[1:]), tree
            )

        (
            a, b, e, ns, h2, budget_inc, budget_total, radio_seq, history,
            metrics, dlv, failure_seq,
        ) = outs
        # per-cell policy stacks sit on axis 2 after to_grid; lead with P.
        a, b, e, ns = (jnp.moveaxis(to_grid(x), 2, 0) for x in (a, b, e, ns))
        if dlv is not None:
            dlv = jnp.moveaxis(to_grid(dlv), 2, 0)
        history = (
            {k: jnp.moveaxis(v, 2, 0) for k, v in to_grid(history).items()}
            if history
            else None
        )
        # metrics' policy axis is the Python tuple itself — each entry's
        # leaves just go (C_pad, ...) -> (S, N, ...).
        return (
            a, b, e, ns,
            to_grid(h2), to_grid(budget_inc), to_grid(budget_total),
            to_grid(radio_seq), history, to_grid(metrics),
            dlv, to_grid(failure_seq),
        )

    # -- segmented (checkpointed) execution ----------------------------------
    def _init_carries(self, S: int, N: int):
        """Every policy's seg_init carry, broadcast over the (S, N) grid."""

        def bc(x):
            x = jnp.asarray(x)
            return jnp.broadcast_to(x, (S, N) + x.shape)

        return tuple(
            jax.tree_util.tree_map(bc, pol.seg_init(self.cfg))
            for pol, _ in self._resolved
        )

    def _segment_fn(self, n: int):
        """The jitted per-segment grid program for segments of length n.

        Receives the FULL per-round streams plus a traced global offset
        ``t0``; each policy's seg_fn slices its block internally, so all
        equal-length segments reuse one executable.
        """
        if n in self._seg_cache:
            return self._seg_cache[n]
        cfg = self.cfg

        def seg(carries, h2, etas, total, inc, radio_seq, failure_seq, keys, t0):
            new_carries, traces = [], []
            for i, (pol, pp) in enumerate(self._resolved):
                def cell(
                    carry, h2_cell, eta_s, total_cell, inc_cell, radio_cell,
                    failure_cell, key_cell, pol=pol, pp=pp,
                ):
                    params = resolve_params(
                        pol,
                        cfg,
                        pp._replace(
                            key=pp.key if pp.key is not None else key_cell
                        ),
                        scenario_eta=eta_s,
                        scenario_budgets=total_cell,
                        scenario_budget_seq=inc_cell,
                        scenario_radio_seq=radio_cell,
                        scenario_failure_seq=failure_cell,
                    )
                    return pol.seg_fn(cfg, carry, h2_cell, params, t0, n)

                with trace_span(f"grid/policy/{pol.name}"):
                    over_seeds = jax.vmap(
                        cell, in_axes=(0, 0, None, 0, 0, 0, 0, 0)
                    )
                    c2, tr = jax.vmap(over_seeds)(
                        carries[i], h2, etas, total, inc, radio_seq,
                        failure_seq, keys
                    )
                new_carries.append(c2)
                traces.append(tr)
            return tuple(new_carries), tuple(traces)

        fn = jax.jit(seg)
        self._seg_cache[n] = fn
        return fn

    @staticmethod
    def _concat_traces(parts):
        """Concatenate per-segment (S, N, n, ...) trace tuples on axis 2."""
        if len(parts) == 1:
            return parts[0]
        return jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=2), *parts
        )

    def _run_segmented(self, seed_arr, base_key, learn_keys, resume_from):
        cfg = self.cfg
        ckpt_spec = cfg.checkpoint
        T = cfg.num_rounds
        S, N = len(self.scenarios), int(seed_arr.shape[0])
        missing = [pol.name for pol, _ in self._resolved if pol.seg_fn is None]
        if missing:
            raise ValueError(
                f"checkpointed (segmented) execution needs seg_init/seg_fn "
                f"hooks, missing for: {', '.join(missing)}"
            )
        every = ckpt_spec.every_rounds if ckpt_spec is not None else T

        h2, budget_inc, budget_total, radio_seq, failure_seq = self._sample_fn(
            seed_arr, self._chan_params, self._budget_params,
            self._radio_params, self._env_salts, self._failure_params,
        )
        keys = self._keys_fn(seed_arr, base_key)
        etas = self._etas

        def sl(tree, r):
            return jax.tree_util.tree_map(
                lambda x: x[:, :, :r], tree
            )

        def fsl(fs, r):
            # Only the (S, N, T, K) delivered mask has a round axis; the
            # (S, N, K) declared rates must pass through unsliced.
            if fs is None:
                return None
            return fs._replace(delivered=fs.delivered[:, :, :r])

        carries = self._init_carries(S, N)
        trace_parts = []
        start = 0

        if resume_from is not None and resume_from is not False:
            if resume_from is True:
                if ckpt_spec is None:
                    raise ValueError(
                        "resume_from=True needs a CheckpointSpec (engine "
                        "checkpoint= or Scenario.checkpoint) to name the "
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

            def prefix_like(h2p, incp, radp, flp):
                c0 = self._init_carries(S, N)
                seg = self._segment_fn(r)
                c1, tr = seg(
                    c0, h2p, etas, budget_total, incp, radp, flp, keys,
                    jnp.asarray(0, jnp.int32),
                )
                return {"carries": c1, "traces": tr}

            like = jax.eval_shape(
                prefix_like, sl(h2, r), sl(budget_inc, r),
                jax.tree_util.tree_map(lambda x: x[:, :, :r], radio_seq),
                fsl(failure_seq, r),
            )
            snap, _ = ckpt_io.load_snapshot(directory, like, r)
            carries = snap["carries"]
            trace_parts = [snap["traces"]]
            start = r

        for t0, t1 in ckpt_io.segment_bounds(T, every, start):
            seg = self._segment_fn(t1 - t0)
            carries, traces_s = seg(
                carries, h2, etas, budget_total, budget_inc, radio_seq,
                failure_seq, keys, jnp.asarray(t0, jnp.int32),
            )
            trace_parts.append(traces_s)
            if ckpt_spec is not None:
                snapshot = {
                    "carries": carries,
                    "traces": self._concat_traces(trace_parts),
                }
                ckpt_io.save_snapshot(ckpt_spec, snapshot, t1)

        traces = self._concat_traces(trace_parts)

        # OCEAN traces carry RAW full-trace telemetry; finalize each from
        # its final carried MetricsState (once, at the end — exactly what
        # the single-program path does inside its scan epilogue).
        spec = cfg.metrics
        finalized = []
        for i, (pol, _) in enumerate(self._resolved):
            tr = traces[i]
            if spec is not None and tr.metrics is not None:
                _state, mstate = carries[i]
                mets = jax.jit(
                    jax.vmap(
                        jax.vmap(
                            lambda ms, t: finalize_metrics(spec, cfg, ms, t)
                        )
                    )
                )(mstate, tr.metrics)
                tr = tr._replace(metrics=mets)
            finalized.append(tr)
        traces = tuple(finalized)

        history = None
        if self.experiment is not None:
            run = self.experiment.run
            hfn = jax.jit(jax.vmap(jax.vmap(run)))
            hists = [hfn(learn_keys, tr) for tr in traces]
            history = {k: jnp.stack([h[k] for h in hists]) for k in hists[0]}

        a = jnp.stack([t.a for t in traces])
        b = jnp.stack([t.b for t in traces])
        e = jnp.stack([t.e for t in traces])
        ns = jnp.stack([t.num_selected for t in traces])
        dlv = self._stack_delivered(traces)
        metrics = tuple(t.metrics for t in traces)
        return (
            a, b, e, ns, h2, budget_inc, budget_total, radio_seq, history,
            metrics, dlv, failure_seq,
        )

    def _keys(self, seeds, base_key, learn_keys, learn_seed):
        """(seeds, seed array, base key, learn keys) of one sweep."""
        seeds = tuple(int(s) for s in seeds)
        seed_arr = jnp.asarray(seeds, jnp.uint32)
        S, N = len(self.scenarios), len(seeds)
        if base_key is None:
            base_key = jax.random.PRNGKey(0)
        if learn_keys is None:
            lk = jax.random.PRNGKey(learn_seed)
            learn_keys = jnp.stack(
                [
                    jnp.stack(
                        [
                            jax.random.fold_in(jax.random.fold_in(lk, s), n)
                            for n in seeds
                        ]
                    )
                    for s in range(S)
                ]
            )
        else:
            learn_keys = jnp.asarray(learn_keys)
            if learn_keys.shape[:2] != (S, N):
                raise ValueError(
                    f"learn_keys must have leading shape (S={S}, N={N}), "
                    f"got {learn_keys.shape}"
                )
        return seeds, seed_arr, base_key, learn_keys

    def _program_args(self, seed_arr, base_key, learn_keys):
        """The single-program path's arguments, in ``_build``'s order."""
        return (
            seed_arr,
            self._chan_params,
            self._budget_params,
            self._radio_params,
            self._env_salts,
            self._etas,
            base_key,
            learn_keys,
            self._failure_params,
        )

    # -- public API ----------------------------------------------------------
    def lower(self, seeds: Sequence[int]) -> jax.stages.Lowered:
        """The program ``run(seeds)`` dispatches, lowered, not run.

        Only the default single-program path has one program: a sharded
        or checkpointed engine raises ``ValueError``.
        """
        if self._shard or self.cfg.checkpoint is not None:
            raise ValueError(
                "lower() covers the unsharded, unsegmented program only"
            )
        _, seed_arr, base_key, learn_keys = self._keys(seeds, None, None, 0)
        return self._fn.lower(
            *self._program_args(seed_arr, base_key, learn_keys)
        )

    def run(
        self,
        seeds: Sequence[int],
        *,
        base_key: Optional[Array] = None,
        learn_keys: Optional[Array] = None,
        learn_seed: int = 0,
        resume_from: Union[str, bool, None] = None,
    ) -> GridResult:
        """Sweep the grid over ``seeds``; compiled once per grid shape.

        ``learn_keys`` — optional explicit (S, N, 2) PRNG keys for the
        learning trajectories (default: fold (scenario, seed) into
        ``PRNGKey(learn_seed)``).  ``base_key`` seeds stochastic policies.

        ``resume_from`` — restore the latest committed snapshot before
        running: ``True`` resumes from the configured ``CheckpointSpec``
        directory, a string names an explicit snapshot directory.  The
        resumed sweep must use the same grid, seeds, and keys as the
        interrupted one (snapshots hold only policy carries and trace
        prefixes; environment streams are re-derived from the seeds).

        Under an active profiler trace the call shows as three host spans:
        ``grid/keys`` (seeds and PRNG keys), ``grid/dispatch`` (the
        compiled program's call) and ``grid/result`` (the ``GridResult``).
        """
        with host_span("grid/keys"):
            seeds, seed_arr, base_key, learn_keys = self._keys(
                seeds, base_key, learn_keys, learn_seed
            )
        with host_span("grid/dispatch"):
            if self.cfg.checkpoint is not None or (
                resume_from is not None and resume_from is not False
            ):
                (
                    a, b, e, ns, h2, budget_inc, budget_total, radio_seq,
                    history, metrics, dlv, failure_seq,
                ) = self._run_segmented(
                    seed_arr, base_key, learn_keys, resume_from
                )
            elif self._shard:
                (
                    a, b, e, ns, h2, budget_inc, budget_total, radio_seq,
                    history, metrics, dlv, failure_seq,
                ) = self._run_sharded(seed_arr, base_key, learn_keys)
            else:
                (
                    a, b, e, ns, h2, budget_inc, budget_total, radio_seq,
                    history, metrics, dlv, failure_seq,
                ) = self._fn(
                    *self._program_args(seed_arr, base_key, learn_keys)
                )
        with host_span("grid/result"):
            if all(m is None for m in metrics):
                metrics = None  # metrics-off grid: keep the legacy None field
            return GridResult(
                a=a,
                b=b,
                e=e,
                num_selected=ns,
                energy_spent=e.sum(axis=-2),
                h2=h2,
                history=history,
                policies=self.policies,
                scenarios=tuple(sc.name for sc in self.scenarios),
                seeds=seeds,
                budget_inc=budget_inc,
                budget_total=budget_total,
                radio_seq=radio_seq,
                metrics=metrics,
                delivered=dlv,
                failure_seq=failure_seq,
            )


def run_grid(
    scenarios: Sequence[Scenario],
    policies: Sequence[PolicySpec],
    seeds: Sequence[int],
    *,
    experiment=None,
    solver: Optional[str] = None,
    shard: Optional[bool] = None,
    ranking: Optional[str] = None,
    top_m: Optional[int] = None,
    block_k: Optional[int] = None,
    traj: Optional[str] = None,
    metrics: Optional[MetricsSpec] = None,
    checkpoint: Optional[CheckpointSpec] = None,
    guard: Optional[GuardSpec] = None,
    base_key: Optional[Array] = None,
    learn_keys: Optional[Array] = None,
    learn_seed: int = 0,
    resume_from: Union[str, bool, None] = None,
) -> GridResult:
    """One-shot convenience wrapper around ``GridEngine``."""
    return GridEngine(
        scenarios, policies, experiment=experiment, solver=solver, shard=shard,
        ranking=ranking, top_m=top_m, block_k=block_k, traj=traj,
        metrics=metrics, checkpoint=checkpoint, guard=guard,
    ).run(
        seeds, base_key=base_key, learn_keys=learn_keys, learn_seed=learn_seed,
        resume_from=resume_from,
    )
