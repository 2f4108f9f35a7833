"""Serializable Scenario spec — the second axis of the evaluation grid.

A ``Scenario`` bundles everything that used to be scattered across
``benchmarks/common.py`` (radio constants), ``core/channel.py`` (path-loss
schedules) and the per-figure modules (budgets, eta schedules, horizons):
channel model + radio physics + energy budgets + eta schedule + (T, K).
It is a plain frozen dataclass of JSON-serializable leaves, so scenario
grids can be stored, diffed, and shipped to workers.

The default channel is the paper's block-fading model: a per-round mean
path loss (constant, or linearly drifting as in §VI scenarios 1/2) with
optional i.i.d. Exp(1) Rayleigh power fading.  Richer dynamics come from
the ``repro.env`` subsystem: setting ``env`` to an ``EnvSpec`` picks any
registered channel process (Gauss-Markov correlated fading, LOS/NLOS
blockage, random-waypoint mobility) and budget process (harvesting,
depleting).  The legacy ``pathloss_db``/``fading`` fields act as a
deprecated shim that lowers to the ``iid_rayleigh``/``static`` processes
bit-for-bit.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.checkpoint.trajectory import CheckpointSpec
from repro.core.channel import (
    ChannelModel,
    constant_pathloss,
    linear_pathloss,
    pathloss_to_gain,
)
from repro.core.energy import RadioParams
from repro.core.ocean import OceanConfig, check_failure_mode, check_traj_backend
from repro.core.patterns import eta_schedule
from repro.core.selection import DEFAULT_BLOCK_K, DEFAULT_TOP_M, check_ranking
from repro.core.solvers import get_solver
from repro.guard.spec import GuardSpec
from repro.obs.metrics import MetricsSpec
from repro.env.channel import (
    UMA_PROCESS,
    LowerCtx,
    get_channel_process,
    sample_channel_process,
)
from repro.env.energy import sample_budget_process
from repro.env.failure import TracedFailure, traced_failure
from repro.env.radio import TracedRadio, sample_radio_process
from repro.env.spec import (
    EnvSpec,
    LoweredEnv,
    env_cell_keys,
    failure_cell_key,
    lower_env,
    radio_cell_key,
)

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class Scenario:
    """One point on the scenario axis of a (policy, scenario, seed) grid.

    Attributes:
      name:            label used in results and error messages.
      num_clients:     K.
      num_rounds:      T.
      pathloss_db:     (start, end) mean path loss in dB; equal entries give
                       the stationary channel, unequal a linear drift
                       (paper scenarios 1: 32->45, 2: 45->32).
      fading:          i.i.d. Exp(1) power fading around the mean (Rayleigh).
      radio:           uplink physics (bandwidth, noise, deadline, bits, b_min).
      energy_budget_j: per-client long-term budget H_k — scalar, or a
                       length-K tuple for heterogeneous budgets.
      eta:             name of the temporal-weight schedule (see
                       ``repro.core.patterns.ETA_SCHEDULES``) used by
                       policies that don't pin their own.
      frame_len:       OCEAN frame length R (None => R = T).
      env:             optional ``EnvSpec`` picking registered channel and
                       budget processes; None lowers the legacy
                       ``pathloss_db``/``fading`` fields to the
                       ``iid_rayleigh``/``static`` shim.
      solver:          P4/OCEAN-P backend (``repro.core.solvers``):
                       ``bisect`` (default, bit-stable), ``newton``,
                       ``pallas``, or ``pallas_tiled`` (sort-free;
                       needs ``ranking="topm"``).  A compiled-program
                       static: all scenarios of one grid must agree.
      ranking:         rho-prefix ranking mode (``sort`` default /
                       ``topm`` sort-free extraction); with ``top_m``
                       and ``block_k`` these are compiled-program
                       statics joining the grid's must-agree set.
      top_m:           candidate-prefix length under ``ranking="topm"``.
      block_k:         client tile width of the ``pallas_tiled`` kernel.
      traj:            trajectory backend for OCEAN policies:
                       ``scan`` (default, the bit-stable ``lax.scan``) or
                       ``fused`` (whole-trajectory Pallas kernel,
                       ``repro.kernels.ocean_traj``).  Also a
                       compiled-program static.
      metrics:         optional ``repro.obs.MetricsSpec`` selecting
                       in-graph telemetry for OCEAN policies; the grid
                       then returns per-cell metrics dicts.  ``None``
                       (default) keeps the legacy programs and payloads
                       byte-identical.  Also a compiled-program static
                       joining the grid's must-agree set.
      checkpoint:      optional ``repro.checkpoint.CheckpointSpec``
                       enabling preemption-safe segmented execution with
                       periodic snapshots (see ``OceanConfig.checkpoint``
                       / ``GridEngine``).  ``None`` (default) keeps the
                       legacy programs and serialized payloads
                       byte-identical.  Joins the grid's must-agree set.
      failure_mode:    OCEAN's reaction to an active ``env.failure``
                       process (``repro.core.ocean.FAILURE_MODES``):
                       ``plain`` (default — legacy decisions, failures
                       only gate delivery), ``overprovision`` (rank past
                       top-m so expected deliveries match m), or
                       ``reallocate`` (re-run P4 on the survivor set at
                       the deadline midpoint).  A compiled-program
                       static; ``plain`` keeps payloads byte-stable.
      guard:           optional ``repro.guard.GuardSpec`` enabling the
                       guarded-execution layer (bounded-energy admission,
                       solver fallback cascade, stream sanitization — see
                       ``OceanConfig.guard``).  ``None`` (default) keeps
                       every legacy path byte-identical.  Also a
                       compiled-program static joining the grid's
                       must-agree set.
    """

    name: str = "stationary"
    num_clients: int = 10
    num_rounds: int = 300
    pathloss_db: Tuple[float, float] = (36.0, 36.0)
    fading: bool = True
    radio: RadioParams = RadioParams()
    energy_budget_j: Union[float, Tuple[float, ...]] = 0.15
    eta: str = "uniform"
    frame_len: Optional[int] = None
    env: Optional[EnvSpec] = None
    solver: str = "bisect"
    ranking: str = "sort"
    top_m: int = DEFAULT_TOP_M
    block_k: int = DEFAULT_BLOCK_K
    traj: str = "scan"
    metrics: Optional[MetricsSpec] = None
    checkpoint: Optional[CheckpointSpec] = None
    failure_mode: str = "plain"
    guard: Optional[GuardSpec] = None

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
        if len(self.pathloss_db) != 2:
            raise ValueError(
                f"pathloss_db must be a (start_db, end_db) pair, got "
                f"{self.pathloss_db!r}"
            )
        if not isinstance(self.energy_budget_j, (int, float)):
            if len(self.energy_budget_j) != self.num_clients:
                raise ValueError(
                    f"heterogeneous energy_budget_j needs {self.num_clients} "
                    f"entries, got {len(self.energy_budget_j)}"
                )
        eta_schedule(self.eta, 1)  # fail fast on unknown schedule names
        if self.env is not None:
            self.env.validate()  # fail fast on unknown process names
        if self.metrics is not None:
            # eager at spec time: unknown collectors raised by MetricsSpec
            # itself; the full_trace memory cap needs this scenario's (T, K)
            self.metrics.validate(self.num_rounds, self.num_clients)
        if self.guard is not None and not isinstance(self.guard, GuardSpec):
            raise TypeError(
                f"guard must be a repro.guard.GuardSpec or None, got "
                f"{type(self.guard).__name__}"
            )

    # -- derived objects ----------------------------------------------------
    def ocean_config(self) -> OceanConfig:
        return OceanConfig(
            num_clients=self.num_clients,
            num_rounds=self.num_rounds,
            radio=self.radio,
            energy_budget_j=self.energy_budget_j,  # type: ignore[arg-type]
            frame_len=self.frame_len,
            solver=self.solver,
            ranking=self.ranking,
            top_m=self.top_m,
            block_k=self.block_k,
            traj=self.traj,
            metrics=self.metrics,
            checkpoint=self.checkpoint,
            failure_mode=self.failure_mode,
            guard=self.guard,
        )

    def channel_model(self) -> ChannelModel:
        start, end = self.pathloss_db
        if start == end:
            sched = constant_pathloss(start)
        else:
            sched = linear_pathloss(start, end, self.num_rounds)
        return ChannelModel(self.num_clients, sched, fading=self.fading)

    # -- environment (repro.env) --------------------------------------------
    def env_spec(self) -> EnvSpec:
        """The embedded EnvSpec, or the legacy-field shim lowering."""
        return self.env if self.env is not None else EnvSpec()

    def lower_ctx(self) -> LowerCtx:
        return LowerCtx(
            num_rounds=self.num_rounds,
            num_clients=self.num_clients,
            pathloss_db=tuple(self.pathloss_db),
            fading=self.fading,
            budgets_j=tuple(
                (self.energy_budget_j,) * self.num_clients
                if isinstance(self.energy_budget_j, (int, float))
                else self.energy_budget_j
            ),
            radio=self.radio,
        )

    def lower_env(self) -> LoweredEnv:
        """Unified environment params + stable key salt for this scenario."""
        return lower_env(self.env_spec(), self.lower_ctx())

    def mean_gain_seq(self) -> Array:
        """(T,) closed-form mean power gain E[h^2]_t, when one exists."""
        spec = self.env_spec()
        proc = get_channel_process(spec.channel)
        if proc.mean_gain is None:
            raise ValueError(
                f"channel process {spec.channel!r} has no closed-form mean "
                f"gain (e.g. mobility trajectories); sample and average "
                f"instead"
            )
        return proc.mean_gain(spec.channel_params, self.lower_ctx())

    def sample_channel(self, seed_or_key: Union[int, Array]) -> Array:
        """(T, K) channel power gains h^2 for one realization.

        Scenarios without an ``env`` take the legacy ``ChannelModel``
        path unchanged; env scenarios sample their channel process with
        the same fading key plus a content-salted environment key — the
        exact keying the grid engine uses, so single runs and grid cells
        agree bit-for-bit.
        """
        key = (
            jax.random.PRNGKey(seed_or_key)
            if isinstance(seed_or_key, int)
            else seed_or_key
        )
        if self.env is None:
            return self.channel_model().sample(key, self.num_rounds)
        lowered = self.lower_env()
        k_chan, _ = env_cell_keys(key, jnp.uint32(lowered.key_salt))
        return sample_channel_process(
            lowered.channel, key, k_chan, self.num_rounds, self.num_clients,
            uma=self.env_spec().channel == UMA_PROCESS,
        )

    def sample_budget(self, seed_or_key: Union[int, Array]) -> Tuple[Array, Array]:
        """((T, K) per-round budget increments, (K,) totals) for one seed."""
        key = (
            jax.random.PRNGKey(seed_or_key)
            if isinstance(seed_or_key, int)
            else seed_or_key
        )
        lowered = self.lower_env()
        _, k_budget = env_cell_keys(key, jnp.uint32(lowered.key_salt))
        return sample_budget_process(
            lowered.budget, k_budget, self.num_rounds, self.num_clients
        )

    def sample_radio(self, seed_or_key: Union[int, Array]) -> TracedRadio:
        """Per-round (T,)-leaf radio sequences (``TracedRadio``) for one seed.

        The ``static`` process returns the scenario's ``RadioParams``
        broadcast bit-for-bit; ``spectrum_sharing``/``deadline_jitter``
        realize their modulators from the same content-salted key
        discipline the grid engine uses.
        """
        key = (
            jax.random.PRNGKey(seed_or_key)
            if isinstance(seed_or_key, int)
            else seed_or_key
        )
        lowered = self.lower_env()
        k_radio = radio_cell_key(key, jnp.uint32(lowered.key_salt))
        return sample_radio_process(lowered.radio, k_radio, self.num_rounds)

    def sample_failure(self, seed_or_key: Union[int, Array]) -> TracedFailure:
        """Realized reliability (``TracedFailure``) for one seed.

        The ``none`` process returns an exact all-ones mask; active
        processes draw from the dedicated failure key stream
        (``failure_cell_key``), so adding failures never perturbs the
        channel/budget/radio draws of existing runs.
        """
        key = (
            jax.random.PRNGKey(seed_or_key)
            if isinstance(seed_or_key, int)
            else seed_or_key
        )
        lowered = self.lower_env()
        k_fail = failure_cell_key(key, jnp.uint32(lowered.key_salt))
        return traced_failure(
            lowered.failure, k_fail, self.num_rounds, self.num_clients
        )

    def eta_seq(self) -> Array:
        return eta_schedule(self.eta, self.num_rounds)

    def budgets(self) -> Array:
        h = jnp.asarray(self.energy_budget_j, jnp.float32)
        return jnp.broadcast_to(h, (self.num_clients,))

    # -- serialization ------------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["pathloss_db"] = list(self.pathloss_db)
        if not isinstance(self.energy_budget_j, (int, float)):
            d["energy_budget_j"] = list(self.energy_budget_j)
        if self.env is None:
            d.pop("env")  # keep pre-EnvSpec payloads byte-stable
        else:
            d["env"] = self.env.to_dict()
        if self.solver == "bisect":
            d.pop("solver")  # keep pre-solver payloads byte-stable
        if self.ranking == "sort":
            d.pop("ranking")  # keep pre-ranking payloads byte-stable
        if self.top_m == DEFAULT_TOP_M:
            d.pop("top_m")
        if self.block_k == DEFAULT_BLOCK_K:
            d.pop("block_k")
        if self.traj == "scan":
            d.pop("traj")  # keep pre-traj payloads byte-stable
        if self.metrics is None:
            d.pop("metrics")  # keep pre-metrics payloads byte-stable
        else:
            d["metrics"] = self.metrics.to_dict()
        if self.checkpoint is None:
            d.pop("checkpoint")  # keep pre-checkpoint payloads byte-stable
        else:
            d["checkpoint"] = self.checkpoint.to_dict()
        if self.failure_mode == "plain":
            d.pop("failure_mode")  # keep pre-failure payloads byte-stable
        if self.guard is None:
            d.pop("guard")  # keep pre-guard payloads byte-stable
        else:
            d["guard"] = self.guard.to_dict()
        return d

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Scenario":
        """Build from a dict, ignoring unknown keys.

        Specs serialized by newer versions (more fields) must load on
        older ones and vice versa, so unknown keys are dropped instead of
        raising ``TypeError``.
        """
        known = {f.name for f in dataclasses.fields(cls)}
        d = {k: v for k, v in d.items() if k in known}
        d["pathloss_db"] = tuple(d.get("pathloss_db", (36.0, 36.0)))
        if "radio" in d and isinstance(d["radio"], dict):
            radio_known = {f.name for f in dataclasses.fields(RadioParams)}
            d["radio"] = RadioParams(
                **{k: v for k, v in d["radio"].items() if k in radio_known}
            )
        if isinstance(d.get("energy_budget_j"), list):
            d["energy_budget_j"] = tuple(d["energy_budget_j"])
        if isinstance(d.get("env"), dict):
            d["env"] = EnvSpec.from_dict(d["env"])
        if isinstance(d.get("metrics"), dict):
            d["metrics"] = MetricsSpec.from_dict(d["metrics"])
        if isinstance(d.get("checkpoint"), dict):
            d["checkpoint"] = CheckpointSpec.from_dict(d["checkpoint"])
        if isinstance(d.get("guard"), dict):
            d["guard"] = GuardSpec.from_dict(d["guard"])
        return cls(**d)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "Scenario":
        return cls.from_dict(json.loads(s))


def paper_scenarios(num_rounds: int = 300, num_clients: int = 10):
    """The paper's §VI channel settings as a named scenario dict."""
    base = dict(num_rounds=num_rounds, num_clients=num_clients)
    return {
        "stationary": Scenario(name="stationary", **base),
        "scenario1": Scenario(
            name="scenario1", pathloss_db=(32.0, 45.0), **base
        ),
        "scenario2": Scenario(
            name="scenario2", pathloss_db=(45.0, 32.0), **base
        ),
    }


def environment_zoo(
    num_rounds: int = 300, num_clients: int = 10, **overrides
) -> Dict[str, Scenario]:
    """One grid-compatible scenario per registered environment family.

    All entries share (T, K, radio, frame_len), so the whole zoo fits on
    one ``GridEngine`` scenario axis and compiles to a single program.
    ``overrides`` are forwarded to every ``Scenario`` (e.g. ``radio=...``,
    ``energy_budget_j=...``).
    """
    base = dict(num_rounds=num_rounds, num_clients=num_clients, **overrides)
    return {
        "stationary": Scenario(name="stationary", **base),
        "markov_fading": Scenario(
            name="markov_fading",
            env=EnvSpec(channel="gauss_markov", channel_params={"rho": 0.9}),
            **base,
        ),
        "blockage": Scenario(
            name="blockage",
            env=EnvSpec(
                channel="markov_shadowing",
                channel_params={"p_enter": 0.15, "p_exit": 0.5, "extra_db": 10.0},
            ),
            **base,
        ),
        "mobile": Scenario(
            name="mobile",
            env=EnvSpec(channel="mobility", channel_params={"area_m": 60.0}),
            **base,
        ),
        "harvesting": Scenario(
            name="harvesting",
            env=EnvSpec(budget="harvesting", budget_params={"p_active": 0.5}),
            **base,
        ),
        "depleting": Scenario(
            name="depleting",
            env=EnvSpec(budget="depleting"),
            **base,
        ),
        "spectrum_sharing": Scenario(
            name="spectrum_sharing",
            env=EnvSpec(
                radio="spectrum_sharing",
                radio_params={"share_min": 0.5, "share_max": 1.0},
            ),
            **base,
        ),
        "deadline_jitter": Scenario(
            name="deadline_jitter",
            env=EnvSpec(radio="deadline_jitter", radio_params={"amp": 0.3}),
            **base,
        ),
        "dropout": Scenario(
            name="dropout",
            env=EnvSpec(
                failure="iid_dropout", failure_params={"p_deliver": 0.85}
            ),
            **base,
        ),
        "bursty_outage": Scenario(
            name="bursty_outage",
            env=EnvSpec(
                failure="markov_availability",
                failure_params={"p_fail": 0.1, "p_recover": 0.4},
            ),
            **base,
        ),
        "stragglers": Scenario(
            name="stragglers",
            env=EnvSpec(
                failure="straggler_slowdown",
                failure_params={"sigma": 0.5, "compute_frac": 0.8},
            ),
            **base,
        ),
    }
