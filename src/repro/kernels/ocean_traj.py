"""Fused whole-trajectory OCEAN kernel (Pallas) — Alg. 1 end to end on-chip.

PR 4 made the per-round P3/P4 solve pluggable and fast, which moved the
bottleneck of ``repro.core.ocean.simulate`` to the ``lax.scan`` itself:
every round the (K,) queue / cumulative-energy carry takes an HBM round
trip and the scan step re-dispatches the solver.  The paper's queue
recursion

    q_{k,t+1} = [ E(a_k^t, b_k^t | h_k^t) + q_{k,t} - H_k / T ]^+
    (reset to 0 at every frame boundary t = m * R)

is an inherently sequential first-order scan — the same shape as the
selective-state-space recurrences ``kernels/mamba_scan.py`` already
fuses.  This kernel applies the identical treatment to OCEAN:

  * ``q`` and ``energy_spent`` stay **resident in VMEM scratch** for the
    whole T-round trajectory — the carry never leaves the chip,
  * the per-round inputs ``(h2, V, eta, budget_inc, radio)`` stream from
    HBM in chunked tiles (``grid = (T / chunk,)``), which the Pallas
    pipeline double-buffers against compute,
  * every round runs the **full** Alg. 1 step *inside* the kernel:
    frame-boundary reset, rho ranking, the K+1-prefix P4 solve, the
    energy model, and the queue update.  The round math is literally
    ``repro.core.ocean.ocean_round`` traced into the kernel body —
    including the configured solver backend (``bisect`` / ``newton`` /
    ``pallas``, see ``repro.core.solvers``) — so the fused trajectory is
    **bit-identical** to the ``lax.scan`` path under interpret mode by
    construction: same ops on the same shapes in the same order,
  * batched-cell execution comes from ``jax.vmap``: the grid engine's
    nested (scenario, seed) vmaps batch the ``pallas_call`` by
    prepending cell grid dimensions, so many small-K cells share one
    kernel launch and saturate the chip (see ``benchmarks/traj_bench.py``).

Exposed as the ``fused`` trajectory backend of
``repro.core.ocean.simulate(..., traj=)`` / ``OceanConfig.traj`` /
``Scenario.traj`` / ``GridEngine(traj=)``; ``scan`` remains the
bit-stable default.  The pure-jnp parity oracle is
``repro.kernels.ref.ocean_traj_ref``.

On the CPU backend the kernel runs in interpret mode (how tests and CI
run it); on a TPU it is compiled by Mosaic, with no interpret fallback.
Mosaic lowers the round body for ``solver="newton", ranking="topm"``
only; every other configuration is refused by
:func:`check_fused_lowerable` before anything compiles (use
``traj="scan"`` there).  ``chip_smoke.py`` runs this kernel on a v5e
chip against ``scan``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.ocean import (
    OceanConfig,
    OceanState,
    RoundDecision,
    ocean_round,
)
from repro.env.failure import TracedFailure
from repro.env.radio import TracedRadio
from repro.obs.metrics import (
    finalize_metrics,
    get_collector,
    init_metrics,
    metric_key,
    metrics_round,
    round_context,
)
from repro.obs.spans import trace_span

Array = jax.Array

# Rounds per grid step: one HBM tile of (chunk, K) inputs per step, small
# enough that the double-buffered pipeline overlaps the next tile's loads
# with the current tile's K+1-prefix solves.
DEFAULT_CHUNK = 32

# Auto-chunk VMEM ceiling: chunk * K elements per streamed tile.  At the
# historical K <= 2048 the default chunk of 32 is untouched; for the
# K = 10^4..10^5 cells of benchmarks/traj_bench.py the chunk shrinks so a
# tile (and the 9 output tiles mirroring it) still fits on-chip.
CHUNK_ELEM_BUDGET = 1 << 16

# A (chunk, K) tile's row count must be a multiple of the TPU's 8
# sublanes unless one tile covers all T rounds.
ROW_TILE = 8

_N_RADIO_LEAVES = len(TracedRadio._fields)


def _default_interpret() -> bool:
    # Only the CPU interprets; on a TPU a kernel compiles or the call fails.
    return jax.default_backend() == "cpu"


def check_fused_lowerable(
    cfg: OceanConfig, has_failure: bool = False, stream_bf16: bool = False
) -> None:
    """Refuse, before compiling, a fused round body Mosaic cannot lower.

    The compiled kernel supports ``solver="newton", ranking="topm"`` with
    any radio process, ``plain``/``reallocate`` failure handling,
    checkpoint segments and per-client telemetry.  Everything else raises
    here, on a TPU, rather than fail deep inside the compiler or fall back
    to interpretation.
    """
    refused = []
    if cfg.ranking != "topm":
        refused.append(f"ranking={cfg.ranking!r} (argsort in the round body)")
    if cfg.solver != "newton":
        why = {
            "pallas": "a pallas_call nested in the kernel",
            "pallas_tiled": "a pallas_call nested in the kernel",
        }.get(cfg.solver, "a round body Mosaic does not lower")
        refused.append(f"solver={cfg.solver!r} ({why})")
    if cfg.metrics is not None:
        # Per-client (K,) collectors lower with last/mean/full_trace; a
        # per-round scalar is a scalar store to VMEM, a histogram a
        # scatter-add and full_trace_ds a dynamic slice.
        bad = [
            f"{name}:{red}"
            for name, red in cfg.metrics.collect
            if red not in ("last", "mean", "full_trace")
            or get_collector(name).shape(1) == ()
        ]
        if bad:
            refused.append(f"metrics {', '.join(bad)} (only per-client "
                           "collectors with last/mean/full_trace lower)")
    if cfg.guard is not None:
        refused.append("guard (its bisect fallback solve)")
    if has_failure and cfg.failure_mode == "overprovision":
        refused.append("failure_mode='overprovision' (argsort in the round body)")
    if stream_bf16:
        refused.append("stream_bf16 (bf16 rows need 16-row aligned stores)")
    if refused:
        raise ValueError(
            "traj='fused' cannot compile for a TPU with "
            + "; ".join(refused)
            + ". The compiled kernel supports solver='newton', "
            "ranking='topm'; run other configurations with traj='scan'."
        )


def _put(ref, i, value):
    """Store a one-value round result into row ``i`` of a (chunk, 1) column."""
    ref[pl.ds(i, 1), :] = jnp.reshape(value, (1, 1)).astype(ref.dtype)


def _traj_kernel(
    *refs,
    cfg: OceanConfig,
    chunk: int,
    num_rounds: int,
    has_radio: bool,
    has_failure: bool = False,
    has_init: bool = False,
):
    # stream_bf16: the per-round (chunk, K) output refs may be bf16 — the
    # cast happens only at the per-round row stores below; the resident q/es
    # carries and all round math stay full precision, so the *trajectory*
    # (and the final state) is bit-identical to the unstreamed run.
    """One grid step = ``chunk`` sequential OCEAN rounds on the resident state.

    Ref layout (after the closure statics):
      inputs:  h2 (chunk, K), v (chunk, 1), eta (chunk, 1), inc (chunk, K)
               [+ the 7 TracedRadio leaves, (chunk, 1) each, iff has_radio]
               [+ dlv (chunk, K) streamed delivery mask and rate (1, K)
               declared stationary rates — the same slot every step, like
               the restored carry — iff has_failure]
               [+ q0 (1, K), es0 (1, K), t0 (1, 1) — the restored carry for
               a mid-trajectory segment launch — and one (1, ...) leaf
               per restored MetricsState leaf, iff has_init]
      outputs: a (chunk, K) int32, b, e, q_pre, rho (chunk, K);
               obj, nsel (chunk, 1);
               [+ dlv (chunk, K) int32 and ral (chunk, 1) iff has_failure;]
               [+ fault_count, demoted, fallback (chunk, 1) int32 guard
               telemetry iff cfg.guard is set;]
               q_final, es_final (1, K) — rewritten every step, so after
               the last step they hold the end-of-trajectory state;
               [+ one (chunk, ...) streamed tile per full_trace metrics
               entry, + one (1, ...) final leaf per MetricsState leaf —
               rewritten like q_final — iff cfg.metrics is set]
      scratch: q (1, K), es (1, K) — the VMEM-resident carry
               [+ one (1, ...) VMEM leaf per MetricsState leaf: the
               metrics accumulators/state stay chip-resident across
               chunks exactly like the queues]
    """
    spec = cfg.metrics
    # Guard telemetry rides exactly like the failure extension: a Python
    # static derived from cfg gates three extra (chunk,) int32 outputs,
    # so guard-free programs keep the legacy ref layout byte-identical.
    has_guard = cfg.guard is not None
    if spec is None:
        n_traces = n_mleaves = 0
        m_treedef = None
        m_init_leaves = []
    else:
        m_init_leaves, m_treedef = jax.tree_util.tree_flatten(
            init_metrics(spec, cfg)
        )
        n_traces = len(spec.full_trace_entries)
        n_mleaves = len(m_init_leaves)
    n_in = 4 + (_N_RADIO_LEAVES if has_radio else 0)
    h2_ref, v_ref, eta_ref, inc_ref = refs[:4]
    radio_refs = refs[4:n_in]
    if has_failure:
        dlv_ref, rate_ref = refs[n_in : n_in + 2]
        n_in += 2
    if has_init:
        q0_ref, es0_ref, t0_ref = refs[n_in : n_in + 3]
        minit_refs = refs[n_in + 3 : n_in + 3 + n_mleaves]
        n_in += 3 + n_mleaves
    n_out = 9 + (2 if has_failure else 0) + (3 if has_guard else 0)
    fixed = refs[n_in : n_in + n_out]
    a_ref, b_ref, e_ref, qp_ref, rho_ref, obj_ref, ns_ref = fixed[:7]
    off = 7
    if has_failure:
        dlvo_ref, ral_ref = fixed[off : off + 2]
        off += 2
    if has_guard:
        fco_ref, dmo_ref, fbo_ref = fixed[off : off + 3]
        off += 3
    qf_ref, esf_ref = fixed[off : off + 2]
    trace_refs = refs[n_in + n_out : n_in + n_out + n_traces]
    mfinal_refs = refs[
        n_in + n_out + n_traces : n_in + n_out + n_traces + n_mleaves
    ]
    scratch = refs[n_in + n_out + n_traces + n_mleaves :]
    q_scr, es_scr = scratch[:2]
    m_scrs = scratch[2:]

    K = cfg.num_clients
    ic = pl.program_id(0)

    @pl.when(ic == 0)
    def _init():
        if has_init:
            # Segment launch: seed the resident carry from the restored
            # mid-trajectory state instead of zeros.
            q_scr[...] = q0_ref[...]
            es_scr[...] = es0_ref[...]
            for ref, iref in zip(m_scrs, minit_refs):
                ref[...] = iref[...]
        else:
            q_scr[...] = jnp.zeros_like(q_scr)
            es_scr[...] = jnp.zeros_like(es_scr)
            for ref, leaf in zip(m_scrs, m_init_leaves):
                ref[0] = leaf

    def step(i, carry):
        q, es, m_leaves = carry
        # tl indexes rounds within THIS launch (drives validity masking of
        # chunk-padded tails); t is the global Alg. 1 round (drives frame
        # resets).  They coincide unless this is a resumed segment.
        t = tl = ic * chunk + i
        if has_init:
            t = t0_ref[0, 0] + tl
        v_t = v_ref[i, 0]
        eta_t = eta_ref[i, 0]
        radio_t = (
            TracedRadio(*(r[i, 0] for r in radio_refs)) if has_radio else None
        )
        row = pl.ds(i, 1)

        def round_row(q, es, h2, inc, dlv, rate):
            return ocean_round(
                OceanState(q=q, t=t, energy_spent=es),
                h2,
                v_t,
                eta_t,
                cfg,
                budget_inc=inc,
                radio=radio_t,
                delivered=dlv,
                fail_rate=rate,
            )

        # The round runs vmapped over a size-1 row axis: every (K,) client
        # vector becomes a (1, K) tile row.  Mosaic mislays rank-1 vectors,
        # and the batched round computes the same values as ``scan``'s.
        new_state, dec = jax.vmap(round_row)(
            q,
            es,
            h2_ref[row, :],
            inc_ref[row, :],
            dlv_ref[row, :] if has_failure else None,
            rate_ref[...] if has_failure else None,
        )
        # Every round's decision goes straight to its row of the output
        # tile: a dynamic sublane store, where a carried (chunk, K) buffer
        # would need a dynamic update Mosaic cannot lower.
        a_ref[row, :] = dec.a.astype(a_ref.dtype)
        b_ref[row, :] = dec.b.astype(b_ref.dtype)
        e_ref[row, :] = dec.e.astype(e_ref.dtype)
        qp_ref[row, :] = dec.q.astype(qp_ref.dtype)
        rho_ref[row, :] = dec.rho.astype(rho_ref.dtype)
        _put(obj_ref, i, dec.objective)
        _put(ns_ref, i, dec.num_selected)
        if has_failure:
            dlvo_ref[row, :] = dec.delivered.astype(dlvo_ref.dtype)
            _put(ral_ref, i, dec.realloc)
        if has_guard:
            _put(fco_ref, i, dec.fault_count)
            _put(dmo_ref, i, dec.demoted)
            _put(fbo_ref, i, dec.fallback)
        # Chunk-padded tail rounds (tl >= T) stream edge-replicated inputs:
        # their math runs but must not advance the resident carry.
        valid = tl < num_rounds
        if spec is not None:
            unrow = functools.partial(jax.tree_util.tree_map, lambda x: x[0])
            ctx = round_context(
                t, unrow(dec), unrow(new_state), v_t, eta_t, inc_ref[i],
                radio_t if has_radio else cfg.radio,
            )
            mstate, traces = metrics_round(
                spec, cfg, ctx, jax.tree_util.tree_unflatten(m_treedef, m_leaves),
                valid=valid,
            )
            m_leaves = tuple(jax.tree_util.tree_leaves(mstate))
            for ref, name in zip(trace_refs, spec.full_trace_entries):
                ref[i] = traces[metric_key(name, "full_trace")].astype(ref.dtype)
        q = jnp.where(valid, new_state.q, q)
        es = jnp.where(valid, new_state.energy_spent, es)
        return q, es, m_leaves

    carry0 = (q_scr[...], es_scr[...], tuple(ref[0] for ref in m_scrs))
    q, es, m_leaves = jax.lax.fori_loop(0, chunk, step, carry0)
    with trace_span("traj/chunk_io"):
        q_scr[...] = q
        es_scr[...] = es
        qf_ref[...] = q
        esf_ref[...] = es
        for scr, ref, leaf in zip(m_scrs, mfinal_refs, m_leaves):
            scr[0] = leaf
            ref[0] = leaf


def _pad_rounds(x: Array, pad: int) -> Array:
    """Edge-replicate the trailing rounds so padded tiles stay physical
    (no NaN traps in the solver); their results are masked/sliced away."""
    if pad == 0:
        return x
    widths = ((0, pad),) + ((0, 0),) * (x.ndim - 1)
    return jnp.pad(x, widths, mode="edge")


def ocean_trajectory_fused(
    cfg: OceanConfig,
    h2_seq: Array,        # (T, K) channel power gains
    v_seq: Array,         # (T,)   per-round control parameter V
    eta_seq: Array,       # (T,)   temporal weights
    budget_seq: Array,    # (T, K) per-round budget increments
    radio_seq: Optional[TracedRadio] = None,  # (T,)-leaf radio pytree
    failure_seq: Optional[TracedFailure] = None,  # (T, K) mask + (K,) rates
    *,
    chunk: Optional[int] = None,
    stream_bf16: bool = False,
    interpret: Optional[bool] = None,
    init_state: Optional[OceanState] = None,
    init_mstate=None,
    raw_metrics: bool = False,
):
    """Run the whole OCEAN trajectory as one fused kernel.

    With ``cfg.metrics`` set, returns ``(state, decisions, metrics)`` —
    the metrics carry lives in VMEM scratch across chunks, full traces
    stream out per chunk, and the telemetry is bit-identical to the
    metrics-enabled ``scan`` path under interpret mode.

    Same contract as the ``lax.scan`` body of ``repro.core.ocean.simulate``
    (which normalizes ``v``/``budgets`` before dispatching here): returns
    the final :class:`OceanState` and the stacked per-round
    :class:`RoundDecision`.  ``interpret=None`` interprets on the CPU
    backend and compiles everywhere else.  Batching: ``jax.vmap``
    over this function prepends cell grid dimensions to the kernel — the
    grid engine's (scenario, seed) axes become batched cells of one
    launch.

    ``chunk=None`` auto-sizes the per-step tile: ``DEFAULT_CHUNK`` (32)
    for the historical K <= 2048 regime, shrinking as
    ``CHUNK_ELEM_BUDGET // K`` for large-K cells so the streamed tiles
    stay within VMEM.  ``stream_bf16=True`` streams the per-round (T, K)
    float decisions (``b``, ``e``, ``q``, ``rho``) back to HBM in
    bfloat16 — a 2x cut in decision-trace bandwidth/footprint for
    K >= 10^5 sweeps.  The VMEM-resident carries stay full precision, so
    the trajectory itself (selection masks, queue evolution, final
    state) is unchanged; only the *stored* float traces are quantized.

    ``init_state`` turns the launch into a **mid-trajectory segment**:
    the resident carry is seeded from the given :class:`OceanState`
    (global round index included, so frame resets stay aligned) instead
    of zeros, and the input sequences cover only this segment's rounds.
    With ``cfg.metrics`` set, ``init_mstate`` must carry the restored
    ``MetricsState`` the same way.  ``raw_metrics=True`` returns the
    un-finalized ``(state, decs, mstate, traces)`` so a segmented driver
    can keep accumulating; ``init_state=None`` (the default) keeps the
    legacy whole-trajectory lowering byte-identical.
    """
    if interpret is None:
        interpret = _default_interpret()
    T, K = h2_seq.shape
    if init_state is None and T != cfg.num_rounds:
        raise ValueError(
            f"h2_seq has {T} rounds but cfg.num_rounds={cfg.num_rounds}"
        )
    has_init = init_state is not None
    if has_init and cfg.metrics is not None and init_mstate is None:
        raise ValueError(
            "segment launch with cfg.metrics set needs init_mstate (the "
            "restored MetricsState carry)"
        )
    fdtype = jnp.result_type(h2_seq.dtype, jnp.float32)
    if chunk is None:
        chunk = min(
            DEFAULT_CHUNK,
            max(ROW_TILE, CHUNK_ELEM_BUDGET // max(K, 1) // ROW_TILE * ROW_TILE),
        )
    chunk = max(1, min(chunk, T))
    if not interpret:
        check_fused_lowerable(cfg, failure_seq is not None, stream_bf16)
        if chunk % ROW_TILE and chunk != T:
            raise ValueError(
                f"chunk={chunk} rounds per tile cannot lower on a TPU: it "
                f"must be a multiple of {ROW_TILE} or cover all T={T} rounds"
            )
    pad = (-T) % chunk
    n_chunks = (T + pad) // chunk
    Tp = n_chunks * chunk

    has_radio = radio_seq is not None
    has_failure = failure_seq is not None
    has_guard = cfg.guard is not None

    def rows(x, dtype):
        # (T, K) streams tile as (chunk, K); per-round scalars as (Tp, 1)
        # columns — a rank-1 (chunk,) block is not a legal TPU tile.
        x = _pad_rounds(jnp.asarray(x, dtype), pad)
        return x if x.ndim == 2 else x.reshape(Tp, 1)

    inputs = [
        rows(h2_seq, fdtype),
        rows(v_seq, jnp.float32),
        rows(eta_seq, jnp.float32),
        rows(budget_seq, jnp.float32),
    ]
    if has_radio:
        inputs.extend(rows(leaf, jnp.float32) for leaf in radio_seq)
    if has_failure:
        # Streamed like the other per-round (T, K) inputs; the fixed (K,)
        # declared rates ride as a whole-array block appended below.
        inputs.append(rows(failure_seq.delivered, jnp.float32))

    def row_spec(width):
        return pl.BlockSpec((chunk, width), lambda ic: (ic, 0))

    def _chunked_spec(shape):
        block = (chunk,) + shape
        return pl.BlockSpec(block, lambda ic, _n=len(shape): (ic,) + (0,) * _n)

    def _final_spec(shape):
        block = (1,) + shape
        return pl.BlockSpec(block, lambda ic, _n=len(shape): (0,) * (1 + _n))

    sdtype = jnp.bfloat16 if stream_bf16 else fdtype
    kernel = functools.partial(
        _traj_kernel,
        cfg=cfg,
        chunk=chunk,
        num_rounds=T,
        has_radio=has_radio,
        has_failure=has_failure,
        has_init=has_init,
    )
    in_specs = [row_spec(x.shape[1]) for x in inputs]
    if has_failure:
        inputs.append(jnp.asarray(failure_seq.rate, jnp.float32).reshape(1, K))
        in_specs.append(pl.BlockSpec((1, K), lambda ic: (0, 0)))
    if has_init:
        # Restored-carry inputs: whole-array blocks, same slot every step
        # (only read at ic == 0).
        inputs.append(jnp.asarray(init_state.q, fdtype).reshape(1, K))
        inputs.append(
            jnp.asarray(init_state.energy_spent, fdtype).reshape(1, K)
        )
        inputs.append(jnp.asarray(init_state.t, jnp.int32).reshape(1, 1))
        in_specs.append(pl.BlockSpec((1, K), lambda ic: (0, 0)))
        in_specs.append(pl.BlockSpec((1, K), lambda ic: (0, 0)))
        in_specs.append(pl.BlockSpec((1, 1), lambda ic: (0, 0)))
        if cfg.metrics is not None:
            for leaf in jax.tree_util.tree_leaves(init_mstate):
                leaf = jnp.asarray(leaf)
                inputs.append(leaf.reshape((1,) + leaf.shape))
                block = (1,) + leaf.shape
                in_specs.append(
                    pl.BlockSpec(
                        block, lambda ic, _n=leaf.ndim: (0,) * (1 + _n)
                    )
                )
    # Masks leave the kernel as int32 (bool and int8 rows do not lower) and
    # per-round scalars as (Tp, 1) columns; both are undone below.
    out_specs = [row_spec(K)] * 5 + [row_spec(1)] * 2
    out_shape = [
        jax.ShapeDtypeStruct((Tp, K), jnp.int32),    # a
        jax.ShapeDtypeStruct((Tp, K), sdtype),       # b
        jax.ShapeDtypeStruct((Tp, K), sdtype),       # e
        jax.ShapeDtypeStruct((Tp, K), sdtype),       # q_pre
        jax.ShapeDtypeStruct((Tp, K), sdtype),       # rho
        jax.ShapeDtypeStruct((Tp, 1), fdtype),       # objective
        jax.ShapeDtypeStruct((Tp, 1), jnp.int32),    # num_selected
    ]
    if has_failure:
        out_specs += [row_spec(K), row_spec(1)]
        out_shape.append(jax.ShapeDtypeStruct((Tp, K), jnp.int32))  # dlv
        out_shape.append(jax.ShapeDtypeStruct((Tp, 1), jnp.int32))  # ral
    if has_guard:
        # fault_count / demoted / fallback guard telemetry, streamed like
        # the failure extension's realloc counter.
        for _ in range(3):
            out_specs.append(row_spec(1))
            out_shape.append(jax.ShapeDtypeStruct((Tp, 1), jnp.int32))
    out_specs.append(pl.BlockSpec((1, K), lambda ic: (0, 0)))           # q_final
    out_specs.append(pl.BlockSpec((1, K), lambda ic: (0, 0)))           # es_final
    out_shape.append(jax.ShapeDtypeStruct((1, K), fdtype))
    out_shape.append(jax.ShapeDtypeStruct((1, K), fdtype))
    scratch_shapes = [
        pltpu.VMEM((1, K), fdtype),   # q carry
        pltpu.VMEM((1, K), fdtype),   # energy_spent carry
    ]
    spec = cfg.metrics
    if spec is not None:
        # Streamed full-trace tiles mirror the decision outputs; the
        # MetricsState leaves get (1, ...) "final" outputs rewritten every
        # chunk (like q_final) plus matching VMEM-resident scratch.
        trace_shapes = [
            get_collector(name).shape(K) for name in spec.full_trace_entries
        ]
        for shape in trace_shapes:
            out_specs.append(_chunked_spec(shape))
            out_shape.append(jax.ShapeDtypeStruct((Tp,) + shape, jnp.float32))
        m_leaves, m_treedef = jax.tree_util.tree_flatten(
            init_metrics(spec, cfg)
        )
        for leaf in m_leaves:
            out_specs.append(_final_spec(leaf.shape))
            out_shape.append(
                jax.ShapeDtypeStruct((1,) + leaf.shape, leaf.dtype)
            )
            scratch_shapes.append(pltpu.VMEM((1,) + leaf.shape, leaf.dtype))
    out = pl.pallas_call(
        kernel,
        grid=(n_chunks,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        # Chunks carry the queues from one grid step to the next.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(*inputs)
    n_fixed = 9 + (2 if has_failure else 0) + (3 if has_guard else 0)
    a, b, e, q_pre, rho, obj, nsel = out[:7]
    a = a.astype(jnp.bool_)
    obj, nsel = obj[:, 0], nsel[:, 0]
    off = 7
    if has_failure:
        dlv, ral = out[off : off + 2]
        dlv, ral = dlv.astype(jnp.bool_), ral[:, 0]
        off += 2
    else:
        dlv = ral = None
    if has_guard:
        fc, dm, fb = (x[:, 0] for x in out[off : off + 3])
        off += 3
    else:
        fc = dm = fb = None
    q_final, es_final = out[n_fixed - 2 : n_fixed]

    t_final = (
        jnp.asarray(init_state.t, jnp.int32) + T
        if has_init
        else jnp.asarray(T, jnp.int32)
    )
    state = OceanState(
        q=q_final[0],
        t=t_final,
        energy_spent=es_final[0],
    )
    decs = RoundDecision(
        a=a[:T],
        b=b[:T],
        e=e[:T],
        q=q_pre[:T],
        rho=rho[:T],
        objective=obj[:T],
        num_selected=nsel[:T],
        delivered=None if dlv is None else dlv[:T],
        realloc=None if ral is None else ral[:T],
        fault_count=None if fc is None else fc[:T],
        demoted=None if dm is None else dm[:T],
        fallback=None if fb is None else fb[:T],
    )
    if spec is None:
        return state, decs
    n_traces = len(spec.full_trace_entries)
    traces = {
        metric_key(name, "full_trace"): tr[:T]
        for name, tr in zip(
            spec.full_trace_entries, out[n_fixed : n_fixed + n_traces]
        )
    }
    mstate = jax.tree_util.tree_unflatten(
        m_treedef, [x[0] for x in out[n_fixed + n_traces :]]
    )
    if raw_metrics:
        return state, decs, mstate, traces
    return state, decs, finalize_metrics(spec, cfg, mstate, traces)
