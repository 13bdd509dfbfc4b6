"""Device-resident open-system engine — ``ClusterSim(engine="scan")``.

PR 4 made the *closed* system one dispatch per race (``engine="scan"``),
but every open-system quantum still round-tripped to Python for queueing,
admission and departures.  This module ports the whole open-system cycle

    arrivals -> admission -> scheduling -> machine quantum -> departures

to JAX and runs it as a **single ``lax.scan`` dispatch** over the horizon:
the host exits only at stats extraction (transfer-guard-tested).  All
shapes are churn-stable — arrivals and departures change mask contents and
head/tail indices, never shapes — so one compiled program serves the whole
run regardless of traffic.

Design, stage by stage:

* **Arrivals are data, not compute.**  The arrival process is pre-sampled
  on host from ``numpy.default_rng(seed + 4242)`` — the host
  ``ClusterSim`` stream, drawn in the identical order
  (:func:`repro.online.arrivals.presample`) — into flat, arrival-sorted
  ``(arrive_q, pool, target)`` job arrays shipped once with the initial
  carry.  A device run therefore faces *bit-identical traffic* to the
  host run of the same seed.
* **The FIFO queue is a pair of indices.**  Jobs are admitted in arrival
  order, so the waiting queue is always the contiguous window
  ``[head, tail)`` of the sorted job array: ``tail`` (jobs arrived so
  far) is one masked count per quantum, ``head`` (jobs admitted so far)
  advances by the admitted count.  Queue depth is ``tail - head``; no
  ring buffer, no per-job state machine.
* **Admission is a masked scatter.**  ``"fifo"`` places the k-th dequeued
  job on the k-th lowest free context (rank = cumsum of the free mask) —
  the host rule, vectorised.  ``"synergy"`` runs the
  :class:`repro.online.admission.SynergyAdmission` rule in-graph: a
  bounded ``fori_loop`` places each dequeued job on the free context
  whose core-resident co-runner has the best Eq. 4 pool-cost score
  (empty cores score the expected pool cost), and seeds the newcomer's
  device-resident ST estimate with its profiled solo stack (the hint
  path), so the very first re-matching already sees an informative
  estimate.
* **Scheduling reuses the fused SYNPA step** (``synpa.make_fused_step``,
  the same jitted graph the host allocator dispatches) with
  membership-masked solve/solo/valid/fresh rows, and a new in-graph
  churn-repair matcher (:func:`repro.core.matching.device_repair_partner`)
  that keeps surviving pairs, pairs the dirty vertices (arrivals, widows,
  a toggled idle vertex) complementarily by interference degree, and
  ripples a bounded masked 2-opt outward — the streaming allocator's
  repair tier under partial occupancy, as pure array code.  Odd active
  populations wire the idle vertex (row ``capacity``) exactly like the
  host tier.
* **The machine quantum is the scan engine's**, generalised to the
  slot -> application indirection (``aid`` in
  ``scan_engine._corun_components_scan``): only active contexts advance,
  departures are detected in-graph (``progress >= target`` -> fractional
  ``finish_q`` scatter, context freed at quantum end, no §6.2 relaunch).
* **Job bookkeeping is a log, not objects.**  ``admit_q``/``finish_q``
  live as flat per-job arrays in the carry, scattered in-graph and
  fetched once; :meth:`repro.smt.metrics.OnlineStats.from_device_logs`
  rebuilds the host-shaped ``JobRecord`` list from them.

Parity contract vs the host ``ClusterSim`` (held by
``tests/test_device_sim.py``):

* **Deterministic parts are exact to f32.**  The arrival stream is
  bit-identical by construction; FIFO admission picks identical slots;
  progress/departure arithmetic equals the host's within float32
  round-off.  With a deterministic pairing policy
  (``ScanPolicy(kind="adjacent")`` vs the host
  :class:`repro.online.allocator.AdjacentOnline`) and single-phase
  applications (no poisson phase draws in play), the *entire trajectory*
  — admission quanta, queue depths, fractional finish quanta — matches
  the host run to f32.
* **RNG parts are distribution-equal, not bit-equal.**  Counter noise and
  phase durations come from the threefry streams of
  ``repro.smt.scan_engine`` (``SCAN_RNG_STREAM_VERSION`` v2: the same
  per-(context, quantum) keying as the closed engine, over the
  ``C = 2 * n_cores`` hardware contexts), so multi-phase trajectories and
  counter-driven (synpa) pairings agree statistically, not bitwise.  The
  device synpa tier's first pairing is its deterministic repair of the
  identity carry (under synergy admission, hint-informed), not the host's
  ``default_rng(seed + 7919)`` random pairing.

Timing note: policy, machine and bookkeeping are indivisible inside the
one dispatch; ``OnlineStats.policy_s`` reports the whole per-quantum wall
time (median over ``repeats`` back-to-back re-dispatches, compile
excluded) spread uniformly over the horizon.  Compare against the host
tier's policy + machine + loop *sum*.
"""

from __future__ import annotations

import functools
import time
from collections import OrderedDict
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import isc, matching
from repro.core.synpa import fused_pad, make_fused_step
from repro.obs import trace as obs_trace
from repro.obs.telemetry import (
    APP_FIELDS,
    APP_ST_WIDTH,
    AppTelemetryLog,
    OPEN_FIELDS,
    TelemetryLog,
)
from repro.online.arrivals import presample
from repro.online.faults import RETRY_NEVER
from repro.smt.metrics import OnlineStats
from repro.smt.scan_engine import (
    DeviceTables,
    ScanPolicy,
    _corun_components_scan,
    _machine_partner_of,
    _pmu_counters_scan,
)

#: Kinds of :class:`repro.smt.scan_engine.ScanPolicy` the open-system
#: engine supports: the fused SYNPA tier and the deterministic slot-ordered
#: baseline (the parity anchor; host twin ``AdjacentOnline``).
DEVICE_SIM_KINDS = ("synpa", "adjacent")


class _OpenCarry(NamedTuple):
    """Scan carry of the open system: context membership + queue indices +
    per-job logs.  Shapes depend only on (capacity, padded job count)."""

    app_id: jnp.ndarray       # (C,) i32  pool row per context (-1 = empty)
    job_at: jnp.ndarray       # (C,) i32  job id per context (-1)
    phase_idx: jnp.ndarray    # (C,) i32
    phase_left: jnp.ndarray   # (C,) f32
    progress: jnp.ndarray     # (C,) f32  retired instructions, current job
    target: jnp.ndarray       # (C,) f32  departure target (inf when empty)
    head: jnp.ndarray         # ()   i32  jobs admitted so far (queue head)
    counters: jnp.ndarray     # (C, 5) f32 previous quantum's PMU rows
    ran: jnp.ndarray          # (C,) bool context executed last quantum
    partner_prev: jnp.ndarray  # (C,) i32 machine partner last quantum
    mpart: jnp.ndarray        # (P,) i32  matcher partner carry
    st: jnp.ndarray           # (C, 4) f32 device-resident ST estimates
    admit_q: jnp.ndarray      # (J,) i32  admission quantum per job (-1)
    finish_q: jnp.ndarray     # (J,) f32  fractional finish quantum (inf)


class _FaultCarry(NamedTuple):
    """Per-job retry bookkeeping of a faulted run (``repro.online.faults``).
    Absent (None in the carry tuple) when the run has no FaultProfile, so
    the faults-off carry pytree — and therefore the compiled graph — is
    exactly the historical one."""

    retries: jnp.ndarray      # (J,) i32  evictions suffered so far
    retry_at: jnp.ndarray     # (J,) i32  quantum eligible for re-admission
    #                         #           (RETRY_NEVER = not waiting)
    saved: jnp.ndarray        # (J,) f32  progress to restore on re-admission


class _LaneCfg(NamedTuple):
    """Per-lane traced scenario knobs of the batched (``vmap``) path —
    the divergent per-scenario control flow (admission rule, retry
    policy) of ``repro.online.batch_sim``, carried as data.  ``None`` in
    the single-lane path, where those choices are static Python."""

    is_syn: jnp.ndarray                  # ()  bool  synergy admission
    max_retries: Optional[jnp.ndarray]   # ()  i32   retry cap (faulted)
    backoff: Optional[jnp.ndarray]       # ()  i32   requeue backoff
    preserve: Optional[jnp.ndarray]      # ()  bool  keep progress on evict


def _make_open_ops(spec: ScanPolicy, params, capacity: int, j_pad: int,
                   admission: str, telemetry: bool = False,
                   faults_cfg=None, segment: bool = False,
                   app_telemetry: bool = False, slot_log: bool = False):
    """Build the per-quantum scan ``body`` (plus ``carry0``/``unpack``)
    shared by the single-lane race (:func:`_build_race`) and the batched
    race (:func:`repro.online.batch_sim._build_batched_race`).

    ``admission`` extends the public rule set (``"fifo"``/``"synergy"``)
    with ``"lane"``: both rules are computed each quantum and a traced
    per-lane flag (``lane_cfg.is_syn``) selects between them — divergent
    per-scenario control flow as masked data, which is what makes the
    body ``vmap``-able over a scenario axis.  ``faults_cfg`` likewise
    accepts the sentinel ``"lane"``: the fault path compiles in with the
    retry knobs (``max_retries``/``backoff``/``preserve``) read off
    ``lane_cfg`` as traced scalars instead of Python constants.  The
    static modes trace the exact historical graphs — the pinned
    f32-trajectory tests hold them to it.

    ``app_telemetry`` (static, implies ``telemetry``) appends the
    per-application ring (``repro.obs.telemetry.APP_FIELDS``) as one
    more scan output: per-context occupant/partner identity, predicted
    vs ground-truth slowdown, signed residual, and the policy's ST
    stack estimates.  Identity/ground-truth columns come out of the
    ``open_slow_stats`` barrier shadow; the prediction column reuses
    the scalar ring's ``cost`` gather — no new doctrine surface.

    ``slot_log`` (static, one-dispatch variant only) appends one more
    output, ``(Q, 3, C)`` int32: per quantum and context the occupant's
    job id (-1 when empty), its phase index as the quantum runs it and
    its machine partner's context (itself when alone) — what a placement
    replay needs to know job by job.  All three are integer values the
    quantum already holds, so the log adds no consumer to its float
    graph."""
    assert telemetry or not app_telemetry, (
        "app_telemetry implies telemetry in the open-system ops"
    )
    assert not (segment and slot_log), "the slot log is one-dispatch only"
    lane = admission == "lane"
    lane_faults = faults_cfg == "lane"
    faults = faults_cfg is not None
    if faults and not lane_faults:
        s_max_retries, s_backoff, s_preserve = faults_cfg
    c = capacity
    p = fused_pad(c)
    idx = jnp.arange(c, dtype=jnp.int32)
    cycles = jnp.float32(params.quantum_cycles)
    use_hints = spec.kind == "synpa" and (admission == "synergy" or lane)
    if spec.kind == "synpa":
        assert spec.method is not None and spec.model is not None, (
            "synpa device sim needs a stack method and a fitted model"
        )
        fstep = make_fused_step(
            spec.method, spec.model, impl=spec.pair_impl, solver=spec.solver,
            with_diag=telemetry,
        )
        ncat = spec.method.n_categories
    else:
        fstep = None
        ncat = 4
    uniform = jnp.asarray(isc.uniform_stack(ncat))
    full_budget = 4 * (p // 2)

    # ------------------------------------------------------------ admission
    def admit_fifo(app_id, job_at, free, head, tail, job_pool):
        """k-th dequeued job -> k-th lowest free context (the host rule).
        ``free`` is passed in so the fault path can restrict it to up
        contexts not already taken by retry re-admissions."""
        n_admit = jnp.minimum(tail - head, jnp.sum(free))
        frank = jnp.cumsum(free.astype(jnp.int32)) - 1
        take = free & (frank < n_admit)
        jidx = jnp.where(take, head + frank, j_pad)
        pid = job_pool[jnp.clip(jidx, 0, j_pad - 1)]
        return (
            jnp.where(take, pid, app_id),
            jnp.where(take, jidx, job_at),
            take,
            head + n_admit,
        )

    def admit_synergy(app_id, job_at, head, tail, job_pool, syn_cost,
                      syn_mean, trip_gate=None):
        """FIFO dequeue order, predicted-best placement — the
        ``SynergyAdmission.place`` rule as a bounded in-graph loop (each
        dequeued job sees the residents the previous one placed).

        The loop runs ``n_admit`` trips (a ``while_loop``, not a full
        ``fori_loop(0, c)`` of masked no-op trips): in the steady state
        admissions per quantum are far below capacity, and the skipped
        trips were value-free by construction (``k >= n_admit`` left the
        state untouched), so trajectories are unchanged bit for bit.
        Under the lane-batched graph the trip count is the max over
        lanes, with each lane's state select-masked by its own
        ``k < n_admit`` — the vmap rule of ``while_loop``.

        ``trip_gate`` (lane mode) zeroes the *loop bound* for lanes
        whose synergy outputs are dead anyway (fifo lanes: the
        ``is_syn`` select discards them), so the batched trip count is
        the max over the synergy lanes only instead of the whole grid.
        The returned ``head`` advance keeps the ungated ``n_admit`` —
        it is the value the live lanes select — and gated lanes return
        their inputs untouched, exactly what the select replaces.  The
        last return value is the lane's trip count (the ``syn_trips``
        counter); the batch runs the maximum over its lanes."""
        n_admit = jnp.minimum(tail - head, jnp.sum(app_id < 0))
        n_trip = n_admit if trip_gate is None else jnp.where(
            trip_gate, n_admit, 0
        )

        def body(state):
            k, app_id, job_at = state
            j = head + k
            pid = job_pool[jnp.clip(j, 0, j_pad - 1)]
            mate = app_id[idx ^ 1]
            mcost = jnp.where(
                mate >= 0, syn_cost[pid, jnp.maximum(mate, 0)], syn_mean[pid]
            )
            cost_s = jnp.where(app_id < 0, mcost, jnp.inf)
            s = jnp.argmin(cost_s).astype(jnp.int32)  # ties -> lowest slot
            # Placement as a full-width masked select, not a 1-slot
            # scatter: same values, but the select stays a vector op
            # under the lane-batched (vmap) graph where a scatter with
            # per-lane indices lowers to a serial per-lane loop.
            put = idx == s
            return (
                k + 1,
                jnp.where(put, pid, app_id),
                jnp.where(put, j, job_at),
            )

        _k, app_id2, job_at2 = lax.while_loop(
            lambda s: s[0] < n_trip, body,
            (jnp.zeros((), jnp.int32), app_id, job_at),
        )
        return app_id2, job_at2, job_at2 != job_at, head + n_admit, n_trip

    # ------------------------------------------------------------ policies
    def adjacent_partner(active, n_active):
        """Slot-ordered pairing of the active set; odd leaves the highest
        active rank solo (the ``AdjacentOnline`` rule, in-graph)."""
        arank = jnp.cumsum(active.astype(jnp.int32)) - 1
        slot_of_rank = jnp.zeros(c, jnp.int32).at[
            jnp.where(active, arank, c)
        ].set(idx, mode="drop")
        mate = arank ^ 1
        return jnp.where(
            active & (mate < n_active),
            slot_of_rank[jnp.clip(mate, 0, c - 1)],
            idx,
        )

    # ------------------------------------------------ open machine quantum
    @jax.named_scope("machine")
    def open_quantum(dt, aid, active, phase_idx, phase_left, progress,
                     target, partner, mkey, q, speed=None):
        """Membership-masked quantum: the in-graph
        :meth:`repro.smt.machine.SMTMachine.open_quantum` (departures, no
        relaunch).  Draws are per (context, quantum) — stream layout v2.
        ``speed`` (straggler capability, host twin's keyword) scales
        retirement only; the static None default keeps the faults-off
        graph literally free of the multiply."""
        aid_safe = jnp.maximum(aid, 0)
        nph = dt.n_phases[aid_safe]
        ph = phase_idx % nph
        partner_m = jnp.where(active & active[partner], partner, idx)
        comps = _corun_components_scan(dt, ph, partner_m, params,
                                       aid=aid_safe)
        cpi = comps.sum(axis=-1)
        retired = jnp.where(active, cycles / cpi * dt.retire[aid_safe], 0.0)
        if speed is not None:
            retired = retired * speed
        after = progress + retired
        done = active & (after >= target)
        frac = jnp.clip(
            (target - progress) / jnp.maximum(retired, 1e-9), 0.0, 1.0
        )
        counters = _pmu_counters_scan(
            comps, dt.omega[aid_safe], dt.retire[aid_safe], cycles, params,
            jax.random.fold_in(jax.random.fold_in(mkey, q), 0),
        )
        counters = jnp.where(active[:, None], counters, 0.0)
        # Phase advance for survivors only (departed jobs leave at quantum
        # end); draws are keyed per (context, quantum), occupancy-blind.
        surv = active & ~done
        left = phase_left - 1.0
        trans = surv & (left <= 0.0)
        nidx = phase_idx + trans.astype(jnp.int32)
        lam = dt.duration[aid_safe, nidx % nph]
        draws = jax.random.poisson(
            jax.random.fold_in(jax.random.fold_in(mkey, q), 1), lam, (c,)
        ).astype(jnp.float32)
        new_left = jnp.where(
            trans, jnp.maximum(draws, 1.0), jnp.where(surv, left, phase_left)
        )
        new_idx = jnp.where(trans, nidx, phase_idx)
        return counters, after, done, frac, new_idx, new_left

    # ------------------------------------------------- telemetry shadow
    @jax.named_scope("telemetry")
    def open_slow_stats(dt, aid, active, phase_idx, partner,
                        per_ctx: bool = False):
        """``[mean, max]`` realized slowdown over the active contexts —
        the open-system twin of ``scan_engine._slow_stats``, recomputed
        behind an integer ``optimization_barrier`` so the quantum's own
        float subgraph keeps its exact consumer set (f32 reductions are
        not associative; an extra consumer changes XLA's fusion choices
        and would cost the telemetry-on run its bit-identity).

        ``per_ctx=True`` (static, the ``app_telemetry`` ring)
        additionally returns the un-reduced ``(C,)`` ratio vector plus
        the barriered occupant ids and the co-runner's app id (``-1``
        when solo or empty) — all already live inside the shadow, so
        emitting them adds nothing outside the barrier."""
        aid_b, act_b, ph_b, pt_b = lax.optimization_barrier(
            (aid, active, phase_idx, partner)
        )
        aid_safe = jnp.maximum(aid_b, 0)
        ph = ph_b % dt.n_phases[aid_safe]
        partner_m = jnp.where(act_b & act_b[pt_b], pt_b, idx)
        comps = _corun_components_scan(dt, ph, partner_m, params,
                                       aid=aid_safe)
        solo_cpi = dt.comps[aid_safe, ph].sum(axis=-1)
        ratio = jnp.where(act_b, comps.sum(axis=-1) / solo_cpi, 0.0)
        na = jnp.maximum(jnp.sum(act_b.astype(jnp.float32)), 1.0)
        stats = (jnp.sum(ratio) / na, jnp.max(ratio))
        if per_ctx:
            co = act_b & act_b[pt_b] & (pt_b != idx)
            partner_app = jnp.where(co, aid_b[pt_b], -1)
            return stats + (ratio, aid_b, partner_app)
        return stats

    # ----------------------------------------------------------- scan body
    def body(dt, job_pool, job_arrive, job_target, syn_cost, syn_mean,
             syn_stacks, mkey, fup, fspeed, lane_cfg, carry_t, q):
        carry, fc = carry_t
        with jax.named_scope("admission"):
            # 1. Arrivals: the queue tail is a masked count over the sorted
            # job array — no state to update.
            tail = jnp.sum(job_arrive <= q).astype(jnp.int32)

            app_id, job_at = carry.app_id, carry.job_at
            if faults:
                # 1b. Fault eviction: jobs on cores that are down this quantum
                # leave *before* admission (the host heartbeat order).  A core
                # stays masked while down, so only transition quanta evict.
                # Lane mode reads the retry knobs off the per-lane config —
                # they only enter comparisons and adds, so traced scalars
                # reproduce the static graph's values exactly.
                if lane_faults:
                    max_retries = lane_cfg.max_retries
                    backoff = lane_cfg.backoff
                else:
                    max_retries, backoff = s_max_retries, s_backoff
                upq = fup[q]
                speedq = fspeed[q]
                evict = (app_id >= 0) & ~upq
                ej = jnp.where(evict, job_at, j_pad)
                ej_safe = jnp.clip(ej, 0, j_pad - 1)
                retries = fc.retries.at[ej].add(1, mode="drop")
                over = retries[ej_safe] > max_retries
                requeue_c = evict & ~over     # dropped past max_retries
                retry_at = fc.retry_at.at[
                    jnp.where(requeue_c, ej, j_pad)
                ].set(q + backoff, mode="drop")
                if lane_faults:
                    saved_val = jnp.where(
                        lane_cfg.preserve, carry.progress, 0.0
                    )
                else:
                    saved_val = carry.progress if s_preserve else jnp.zeros(
                        c, jnp.float32
                    )
                saved = fc.saved.at[ej].set(saved_val, mode="drop")
                n_evict = jnp.sum(evict).astype(jnp.int32)
                app_id = jnp.where(evict, -1, app_id)
                job_at = jnp.where(evict, -1, job_at)

            # 2. Admission into free contexts (FIFO dequeue order either way).
            if faults:
                free = (app_id < 0) & upq
                # 2a. Retry pool ahead of the fresh queue: the r-th eligible
                # victim (ascending job id) re-enters on the r-th lowest free
                # up context — the host rule as a rank-matching scatter.
                elig = retry_at <= q
                n_take = jnp.minimum(jnp.sum(elig), jnp.sum(free)).astype(
                    jnp.int32
                )
                erank = jnp.cumsum(elig.astype(jnp.int32)) - 1
                take_j = elig & (erank < n_take)
                job_of_rank = jnp.full(c, j_pad, jnp.int32).at[
                    jnp.where(take_j, erank, c)
                ].set(jnp.arange(j_pad, dtype=jnp.int32), mode="drop")
                frank = jnp.cumsum(free.astype(jnp.int32)) - 1
                rtake = free & (frank < n_take)
                jr = jnp.where(rtake, job_of_rank[jnp.clip(frank, 0, c - 1)],
                               j_pad)
                app_id = jnp.where(
                    rtake, job_pool[jnp.clip(jr, 0, j_pad - 1)], app_id
                )
                job_at = jnp.where(rtake, jr, job_at)
                retry_at = retry_at.at[jnp.where(rtake, jr, j_pad)].set(
                    RETRY_NEVER, mode="drop"
                )
                n_requeue = jnp.sum(rtake).astype(jnp.int32)
                free = free & ~rtake
            else:
                free = app_id < 0
            pre_app = app_id
            syn_trips = jnp.int32(0)
            if admission == "synergy":
                with jax.named_scope("synergy"):
                    app_id, job_at, took_f, head, syn_trips = admit_synergy(
                        app_id, job_at, carry.head, tail, job_pool,
                        syn_cost, syn_mean,
                    )
            elif lane:
                # Both rules run every quantum; the per-lane flag selects.
                # The un-selected rule's outputs are dead values, so fifo
                # lanes are value-independent of the (shared) synergy tables.
                with jax.named_scope("synergy"):
                    s_app, s_job, s_took, s_head, syn_trips = admit_synergy(
                        app_id, job_at, carry.head, tail, job_pool,
                        syn_cost, syn_mean, trip_gate=lane_cfg.is_syn,
                    )
                f_app, f_job, f_took, f_head = admit_fifo(
                    app_id, job_at, free, carry.head, tail, job_pool,
                )
                is_syn = lane_cfg.is_syn
                app_id = jnp.where(is_syn, s_app, f_app)
                job_at = jnp.where(is_syn, s_job, f_job)
                took_f = jnp.where(is_syn, s_took, f_took)
                head = jnp.where(is_syn, s_head, f_head)
            else:
                app_id, job_at, took_f, head = admit_fifo(
                    app_id, job_at, free, carry.head, tail, job_pool,
                )
            # ``took`` covers every newly-placed context (fresh + retry) —
            # slot-state reset and the policy's fresh mask; ``took_f`` is the
            # fresh subset — queue head/admit_q/admission counts stay
            # first-admission-only so queue identities keep holding.
            took = (took_f | rtake) if faults else took_f
            jidx = jnp.where(took, job_at, j_pad)
            target = jnp.where(
                took, job_target[jnp.clip(jidx, 0, j_pad - 1)], carry.target
            )
            phase_idx = jnp.where(took, 0, carry.phase_idx)
            phase_left = jnp.where(
                took, dt.duration[jnp.maximum(app_id, 0), 0], carry.phase_left
            )
            if faults:
                # Re-admissions restart at phase 0 with saved (or zero)
                # progress; fresh admissions start from zero as always.
                progress = jnp.where(
                    rtake, saved[jnp.clip(jidx, 0, j_pad - 1)],
                    jnp.where(took_f, 0.0, carry.progress),
                )
            else:
                progress = jnp.where(took, 0.0, carry.progress)
            # Fresh admissions are exactly the contiguous queue window
            # [carry.head, head) of the sorted job array (both admission
            # rules dequeue in arrival order; retries don't move the head),
            # so the admit log is a vectorized range select — the equivalent
            # scatter over per-slot job indices lowers to a serial
            # per-source loop on XLA:CPU and serializes across lanes under
            # vmap.  Values are identical.
            jobs_idx = jnp.arange(j_pad, dtype=jnp.int32)
            admit_q = jnp.where(
                (jobs_idx >= carry.head) & (jobs_idx < head), q, carry.admit_q
            )
            st = carry.st
            if use_hints:
                # ST-hint seeding: a newcomer's estimate is its profiled
                # solo stack, not the uniform placeholder (fresh-mask
                # skipped below).  Lane mode masks the hint to synergy
                # lanes — fifo lanes keep the uniform start and the
                # fresh-solve path.
                hint_m = (took & lane_cfg.is_syn) if lane else took
                st = jnp.where(
                    hint_m[:, None], syn_stacks[jnp.maximum(app_id, 0)], st
                )

            active = app_id >= 0
            n_active = jnp.sum(active).astype(jnp.int32)
            odd = (n_active % 2) == 1
            queue_depth = tail - head

        # 3. Policy: pair the active population off the *previous*
        # quantum's counters (the host event-loop order).
        pol_diag = None
        pred_ctx = jnp.zeros(c, jnp.float32) if app_telemetry else None
        if spec.kind == "adjacent":
            partner = adjacent_partner(active, n_active)
            mpart = carry.mpart
            if telemetry:
                # No predictor/matcher in play: policy fields are zero.
                pol_diag = jnp.zeros(7, jnp.float32)
        else:
            with jax.named_scope("synpa_step"):
                solve = carry.ran & (carry.partner_prev != idx)
                solo_m = carry.ran & (carry.partner_prev == idx)
                if lane:
                    # Hinted (synergy) lanes skip the fresh solve; fifo
                    # lanes flag newcomers — the two static graphs,
                    # selected per lane.
                    fresh = jnp.where(lane_cfg.is_syn, False, took)
                else:
                    fresh = jnp.zeros(c, bool) if use_hints else took
                rows = [solve, solo_m, active, fresh]
                if use_hints:
                    # The hinted rows keep their seeded stack: a newcomer's
                    # slot may still hold its departed occupant's counters,
                    # whose pair solve would overwrite the hint.
                    rows.append(hint_m)
                masks = jnp.stack(rows)
                if telemetry:
                    cost, st, fdiag = fstep(carry.counters, carry.partner_prev,
                                            st, masks, odd)
                else:
                    cost, st = fstep(carry.counters, carry.partner_prev, st,
                                     masks, odd)
                valid_p = jnp.zeros(p, bool).at[:c].set(active).at[c].set(odd)
                if spec.matcher == "full":
                    matched = matching.device_pairs_partner(
                        cost, valid_p, eps=spec.refine_eps,
                        max_rounds=full_budget, with_rounds=telemetry,
                    )
                    if telemetry:
                        mpart, rounds = matched
                        # A full re-match rebuilds every pair: the whole
                        # valid population counts as dirty.
                        dirty = jnp.sum(valid_p.astype(jnp.float32))
                    else:
                        mpart = matched
                else:
                    matched = matching.device_repair_partner(
                        cost, carry.mpart, valid_p, eps=spec.refine_eps,
                        max_rounds=spec.refine_rounds, with_diag=telemetry,
                    )
                    if telemetry:
                        mpart, rounds, nd = matched
                        dirty = nd.astype(jnp.float32)
                    else:
                        mpart = matched
                with jax.named_scope("telemetry"):
                    if telemetry:
                        n_valid = jnp.maximum(
                            jnp.sum(valid_p.astype(jnp.float32)), 1.0
                        )
                        # Mean predicted cost per committed pair (each
                        # pair's entry appears twice over n_valid/2 pairs;
                        # factors of 2 cancel).
                        gathered = jnp.where(
                            valid_p, cost[jnp.arange(p), mpart], 0.0
                        )
                        pred = jnp.sum(gathered) / n_valid
                        pol_diag = jnp.concatenate([
                            jnp.stack([pred, dirty,
                                       rounds.astype(jnp.float32)]),
                            fdiag,
                        ])
                        if app_telemetry:
                            # Per-context predicted slowdown: cost[i, j] is
                            # slowdown(i|j) + slowdown(j|i), so a context's own
                            # share of its committed pair is half its
                            # gathered entry (masked to co-running contexts
                            # when the ring row is built below).
                            pred_ctx = gathered[:c] * 0.5
                partner = jnp.where(active, _machine_partner_of(mpart, c), idx)

        # 4. One membership-masked machine quantum + 5. departures.
        if app_telemetry:
            # Shadow slowdown stats use the pre-quantum phases/pairing —
            # exactly what the quantum below is about to run.  The
            # per-app variant also emits the per-context ratio and the
            # (barriered) occupant/partner identities.
            (slow_mean, slow_max, ratio_ctx, aid_ctx,
             partner_app) = open_slow_stats(
                dt, app_id, active, phase_idx, partner, per_ctx=True
            )
        elif telemetry:
            slow_mean, slow_max = open_slow_stats(
                dt, app_id, active, phase_idx, partner
            )
        run_phase = phase_idx
        counters, after, done, frac, phase_idx, phase_left = open_quantum(
            dt, app_id, active, phase_idx, phase_left, progress, target,
            partner, mkey, q, speed=speedq if faults else None,
        )
        with jax.named_scope("admission"):
            if segment:
                # Checkpoint variant: the finish log must live in the carry
                # (snapshots restore it), so it keeps the per-quantum
                # scatter.  Values match the streamed variant exactly.
                finish_q = carry.finish_q.at[
                    jnp.where(done, job_at, j_pad)
                ].set(q.astype(jnp.float32) + frac, mode="drop")
            else:
                # One-dispatch variant: a (J,)-indexed scatter per quantum
                # lowers to a serial per-source loop on XLA:CPU and
                # serializes across lanes under vmap — so the finish events
                # ride the scan ``ys`` as (slot-indexed job, value) pairs
                # and ``unpack`` rebuilds the log once post-scan with a
                # sort + binary-search gather.  Carry value is untouched.
                finish_q = carry.finish_q
            fin_j = jnp.where(done, job_at, j_pad)
            fin_v = q.astype(jnp.float32) + frac
            n_solo = jnp.sum(active & (partner == idx)).astype(jnp.int32)
            new = _OpenCarry(
                app_id=jnp.where(done, -1, app_id),
                job_at=jnp.where(done, -1, job_at),
                phase_idx=phase_idx,
                phase_left=phase_left,
                progress=after,
                target=jnp.where(done, jnp.inf, target),
                head=head,
                counters=counters,
                ran=active,
                partner_prev=partner,
                mpart=mpart,
                st=st,
                admit_q=admit_q,
                finish_q=finish_q,
            )
            fc_new = _FaultCarry(
                retries=retries, retry_at=retry_at, saved=saved
            ) if faults else None
        outs = (queue_depth, n_active, n_solo)
        if not segment:
            outs = outs + (fin_j, fin_v)
        if faults:
            outs = outs + (n_evict, n_requeue)
        with jax.named_scope("telemetry"):
            if telemetry:
                f32 = lambda v: v.astype(jnp.float32)  # noqa: E731
                # Fresh placements beside a resident: the core-mate held a
                # job before admission, or took one earlier in the queue
                # order (a lower job id) this quantum.  Integers only; the
                # core-mate view is a static swap of each core's two
                # contexts, not a gather (which runs near-serially on a
                # TPU).
                def mate(x):
                    return x.reshape(c // 2, 2)[:, ::-1].reshape(c)

                beside = took_f & ((mate(pre_app) >= 0) | (
                    mate(took_f) & (mate(job_at) < job_at)))
                # ``done`` is derived from a float comparison, and *any*
                # in-graph consumer of it (a sum, even a barrier) hands the
                # quantum's float subgraph a different fusion and costs the
                # run its bit-identity — so the departures column is left
                # zero here and filled host-side from the fetched finish
                # log (``run_device_sim``), where it is exactly
                # ``bincount(floor(finish_q))``.  The fault columns follow the
                # same doctrine (zeros in-graph, host-filled): failures/
                # recoveries/straggling are pure schedule data, and eviction/
                # requeue counts already ride the ``ys`` as integers.
                tvec = jnp.concatenate([
                    jnp.stack([
                        f32(head), f32(tail), f32(queue_depth),
                        f32(jnp.sum(took_f)), jnp.float32(0.0),
                        f32(n_active), f32(n_solo),
                        slow_mean, slow_max,
                    ]),
                    pol_diag,
                    jnp.zeros(5, jnp.float32),
                    jnp.stack([f32(syn_trips), f32(jnp.sum(beside))]),
                ])
                outs = outs + (tvec,)
            if app_telemetry:
                # Per-app ring row: identities and ground truth off the
                # barrier shadow, prediction off the policy's cost gather,
                # ST stacks off the policy carry.  Empty contexts record
                # app_id -1 and zeros.
                co_ctx = partner_app >= 0
                # Barriers: the residual must combine the *recorded*
                # (rounded) tensors, not FMA-fused upstream products.
                pred_col, real_col = lax.optimization_barrier(
                    (jnp.where(co_ctx, pred_ctx, 0.0), ratio_ctx))
                resid_col = jnp.where(pred_col > 0.0, pred_col - real_col,
                                      0.0)
                st4 = st[:, :APP_ST_WIDTH]
                if st4.shape[1] < APP_ST_WIDTH:
                    st4 = jnp.concatenate(
                        [st4, jnp.zeros((c, APP_ST_WIDTH - st4.shape[1]),
                                        jnp.float32)], axis=1)
                st4 = jnp.where((aid_ctx >= 0)[:, None], st4, 0.0)
                avec = jnp.concatenate([
                    jnp.stack([
                        aid_ctx.astype(jnp.float32),
                        partner_app.astype(jnp.float32),
                        pred_col, real_col, resid_col,
                    ], axis=1),
                    st4,
                ], axis=1)
                outs = outs + (avec,)
        if slot_log:
            outs = outs + (jnp.stack([job_at, run_phase, partner]),)
        return (new, fc_new), outs

    def carry0():
        ocarry = _OpenCarry(
            app_id=jnp.full(c, -1, jnp.int32),
            job_at=jnp.full(c, -1, jnp.int32),
            phase_idx=jnp.zeros(c, jnp.int32),
            phase_left=jnp.zeros(c, jnp.float32),
            progress=jnp.zeros(c, jnp.float32),
            target=jnp.full(c, jnp.inf, jnp.float32),
            head=jnp.int32(0),
            counters=jnp.zeros((c, 5), jnp.float32),
            ran=jnp.zeros(c, bool),
            partner_prev=idx,
            mpart=jnp.arange(p, dtype=jnp.int32),
            st=jnp.tile(uniform[None, :], (c, 1)),
            admit_q=jnp.full(j_pad, -1, jnp.int32),
            finish_q=jnp.full(j_pad, jnp.inf, jnp.float32),
        )
        fc = _FaultCarry(
            retries=jnp.zeros(j_pad, jnp.int32),
            retry_at=jnp.full(j_pad, RETRY_NEVER, jnp.int32),
            saved=jnp.zeros(j_pad, jnp.float32),
        ) if faults else None
        return (ocarry, fc)

    def unpack(final, ys):
        ocarry, fcarry = final
        if segment:
            finish_q, k = ocarry.finish_q, 3
        else:
            # Rebuild the finish log from the streamed (job, value)
            # events: each job departs at most once, so a stable sort
            # by job index followed by a binary-search gather is exact.
            # Sentinel rows (``j_pad``) sort past every real job and
            # can never match.  No scatter anywhere.
            flat_j = ys[3].reshape(-1)
            flat_v = ys[4].reshape(-1)
            order = jnp.argsort(flat_j)
            sj, sv = flat_j[order], flat_v[order]
            jobs = jnp.arange(j_pad, dtype=sj.dtype)
            pos = jnp.clip(jnp.searchsorted(sj, jobs), 0, sj.shape[0] - 1)
            finish_q = jnp.where(sj[pos] == jobs, sv[pos], jnp.inf)
            k = 5
        res = (ocarry.admit_q, finish_q) + ys[:3]
        if faults:
            res = res + (fcarry.retries, fcarry.retry_at) + ys[k:k + 2]
            k += 2
        if telemetry:
            res = res + (ys[k],)
            k += 1
        if app_telemetry:
            res = res + (ys[k],)
            k += 1
        if slot_log:
            res = res + (ys[k],)
        return res

    return body, carry0, unpack


def _build_race(spec: ScanPolicy, params, capacity: int, n_quanta: int,
                j_pad: int, admission: str, telemetry: bool = False,
                faults_cfg: Optional[Tuple[int, int, bool]] = None,
                segment: bool = False, app_telemetry: bool = False):
    """Compile-ready open-system run: one jitted function, one dispatch.

    Returns ``race(dt, job_pool, job_arrive, job_target, syn_cost,
    syn_mean, syn_stacks, mkey)`` -> ``(admit_q (J,), finish_q (J,),
    queue_depth (Q,), n_active (Q,), n_solo (Q,))``.  All shape-bearing
    configuration (capacity, horizon, padded job count, admission rule,
    policy spec) is static; tables, job data and keys are traced, so one
    compiled race serves every run of the same configuration.

    ``telemetry`` (static) appends a per-quantum ring output,
    ``(n_quanta, len(OPEN_FIELDS))``: queue indices, admission/departure
    counts, realized-slowdown stats (a barrier-isolated shadow of the
    quantum's interference transform — see
    ``scan_engine._slow_stats`` for why it is recomputed rather than
    read off the original intermediates), predicted pair cost,
    churn-repair dirty count, 2-opt rounds and GN solver diagnostics.
    Telemetry rides the scan ``ys`` only — never the carry — and the off
    path traces today's graph unchanged, so trajectories are
    bit-identical either way.

    ``faults_cfg`` (static) — ``(max_retries, backoff_quanta,
    preserve_progress)`` of a :class:`repro.online.faults.FaultProfile` —
    compiles the fault path in: the race takes two extra traced arrays
    (``fup (Q, C)`` bool membership, ``fspeed (Q, C)`` f32 capability,
    the pre-sampled schedule expanded to contexts), evicts jobs on down
    cores before admission, re-admits the retry pool ahead of the fresh
    FIFO queue, scales retirement by ``fspeed[q]``, and returns two extra
    job logs (``retries``, ``retry_at``) plus per-quantum
    eviction/requeue counts.  ``None`` (the default) traces the
    historical faults-off graph *unchanged* — no masks, no multiplies by
    one, no extra carry leaves — which is what the pinned-trajectory
    bit-identity tests hold the engine to.

    ``segment`` (static) builds the checkpoint/resume variant instead:
    the returned race takes an explicit ``(carry, q0)`` and scans quanta
    ``[q0, q0 + n_quanta)`` (``n_quanta`` is then the *segment* length),
    returning the full final carry so
    :func:`run_device_sim_checkpointed` can snapshot it at quantum
    boundaries and resume bit-identically.

    The scan body itself lives in :func:`_make_open_ops`, shared with
    the batched race of ``repro.online.batch_sim`` (``lane_cfg`` is None
    here: this is the single-lane path with static admission/faults).
    """
    body, carry0, unpack = _make_open_ops(
        spec, params, capacity, j_pad, admission, telemetry, faults_cfg,
        segment, app_telemetry=app_telemetry,
    )

    if segment:
        @jax.jit
        def race_seg(dt: DeviceTables, job_pool, job_arrive, job_target,
                     syn_cost, syn_mean, syn_stacks, mkey, fup, fspeed,
                     carry_t, q0):
            fn = functools.partial(body, dt, job_pool, job_arrive,
                                   job_target, syn_cost, syn_mean,
                                   syn_stacks, mkey, fup, fspeed, None)
            final, ys = lax.scan(
                fn, carry_t, q0 + jnp.arange(n_quanta, dtype=jnp.int32)
            )
            return final, ys

        return race_seg

    @jax.jit
    def race(dt: DeviceTables, job_pool, job_arrive, job_target, syn_cost,
             syn_mean, syn_stacks, mkey, fup=None, fspeed=None):
        fn = functools.partial(body, dt, job_pool, job_arrive, job_target,
                               syn_cost, syn_mean, syn_stacks, mkey,
                               fup, fspeed, None)
        final, ys = lax.scan(
            fn, carry0(), jnp.arange(n_quanta, dtype=jnp.int32)
        )
        return unpack(final, ys)

    return race


# Compiled races keyed by their static configuration.  The policy's
# method/model enter the key by identity (they are arrays, unhashable by
# value) and are held in the cache value so an id() can never be recycled
# onto a live entry; everything else is keyed by value, so fresh
# equal-config ScanPolicy instances sharing a model reuse the compiled
# race.  LRU-bounded: a long-lived process sweeping many configurations
# cannot pin compiled executables forever.
_RACE_CACHE: "OrderedDict[Tuple, Tuple]" = OrderedDict()
_RACE_CACHE_MAX = 16


def _race_key(spec: ScanPolicy, capacity: int, n_quanta: int, j_pad: int,
              admission: str, telemetry: bool = False,
              faults_cfg: Optional[Tuple[int, int, bool]] = None,
              segment: bool = False,
              app_telemetry: bool = False) -> Tuple:
    return (
        spec.kind, id(spec.method), id(spec.model), spec.pair_impl,
        spec.solver, spec.matcher, spec.refine_eps, spec.refine_rounds,
        spec.first_match, capacity, n_quanta, j_pad, admission, telemetry,
        faults_cfg, segment, app_telemetry,
    )


def _prepare_inputs(sim, n_quanta: int):
    """Host-side prologue shared by the one-dispatch and checkpointed
    runners: pre-sample arrivals (and the fault schedule when the sim
    carries a FaultProfile), build the flat job arrays and the synergy
    tables.  Everything returned is plain numpy — committed to device by
    the caller."""
    machine = sim.machine
    pool = sim.pool
    with obs_trace.span("device_sim.presample", quanta=n_quanta):
        rng_arr = np.random.default_rng(sim.seed + 4242)
        arrive_q, pids = presample(sim.arrivals, n_quanta, rng_arr)
    j = int(pids.size)
    # Jobs pad to the next power of two so re-runs of the same cell — and
    # nearby traffic levels — reuse the compiled race.
    j_pad = max(8, 1 << (j - 1).bit_length()) if j else 8
    pool_target = np.array(
        [machine.target_instructions(pr) for pr in pool]
    ) * sim.target_scale
    pool_rate = np.array([machine.solo_retire_rate(pr) for pr in pool])
    job_pool = np.zeros(j_pad, np.int32)
    job_arrive = np.full(j_pad, n_quanta, np.int32)  # padding never arrives
    job_target = np.full(j_pad, np.inf, np.float32)
    if j:
        job_pool[:j] = pids
        job_arrive[:j] = arrive_q
        job_target[:j] = pool_target[pids]
    n_apps = sim.tables.n_apps
    if sim.admission == "synergy":
        syn_cost = np.asarray(sim.synergy.pool_cost, np.float32)
        syn_mean = np.asarray(sim.synergy.mean_cost, np.float32)
        syn_stacks = np.asarray(sim.synergy.stacks, np.float32)
    else:
        syn_cost = np.zeros((n_apps, n_apps), np.float32)
        syn_mean = np.zeros(n_apps, np.float32)
        syn_stacks = np.zeros((n_apps, isc.N_CATS), np.float32)
    faults = getattr(sim, "faults", None)
    if faults is not None:
        sched = faults.schedule(n_quanta, sim.n_cores, sim.seed)
        fcfg = faults.static_config
        fup = sched.ctx_up()
        fspeed = sched.ctx_speed()
    else:
        sched, fcfg, fup, fspeed = None, None, None, None
    return dict(
        arrive_q=arrive_q, pids=pids, j=j, j_pad=j_pad,
        pool_rate=pool_rate, job_pool=job_pool, job_arrive=job_arrive,
        job_target=job_target, syn_cost=syn_cost, syn_mean=syn_mean,
        syn_stacks=syn_stacks, faults=faults, sched=sched, fcfg=fcfg,
        fup=fup, fspeed=fspeed,
    )


def _check_conservation(prep, n_quanta, admit, finish, retries, retry_at):
    """The job-conservation invariant of a faulted run: every *arrived*
    job is exactly one of completed / in flight / queued / waiting out a
    retry backoff / dropped — no duplicates, no losses.  Cheap (a few
    masks over the job log), so the engine asserts it on every fetch
    rather than leaving it to the property tests."""
    j = prep["j"]
    if not j:
        return
    max_retries = prep["fcfg"][0]
    admit = admit[:j]
    finish = finish[:j]
    retries = retries[:j]
    retry_at = retry_at[:j]
    completed = np.isfinite(finish)
    waiting = retry_at < int(RETRY_NEVER)
    dropped = retries > max_retries
    queued = admit < 0
    in_flight = (~completed) & (~waiting) & (~dropped) & (~queued)
    states = (completed.astype(int) + waiting.astype(int)
              + dropped.astype(int) + queued.astype(int)
              + in_flight.astype(int))
    assert (states == 1).all(), (
        "job-conservation violation: some job is in "
        f"{int((states != 1).sum())} states"
    )


def run_device_sim(sim, n_quanta: int, repeats: int = 1,
                   transfer_guard: bool = False,
                   warmup: bool = True,
                   telemetry: bool = False,
                   app_telemetry: bool = False) -> OnlineStats:
    """Run a :class:`repro.online.sim.ClusterSim` configuration on device.

    One ``lax.scan`` dispatch executes the whole run; ``repeats``
    re-dispatches the (pure) compiled race and reports the *median*
    per-quantum wall time in ``OnlineStats.policy_s`` (compile always
    excluded by a warm-up dispatch).  ``transfer_guard=True`` wraps the
    timed dispatches in ``jax.transfer_guard("disallow")``, proving the
    loop makes no per-quantum host transfers — inputs are
    device-committed up front, job logs are fetched after the guard
    exits.  ``warmup=False`` skips the extra warm-up dispatch so the run
    executes the race exactly once — the whole-run A/B timing mode
    (``benchmarks/online_churn.py``), where the caller medians wall times
    over back-to-back runs and sheds the compile round itself; the
    reported ``policy_s`` then includes compile on the first run of a
    configuration.

    ``telemetry=True`` records the per-quantum device ring
    (``repro.obs.telemetry.OPEN_FIELDS``) inside the same dispatch and
    attaches it to the returned stats as ``OnlineStats.telemetry`` — the
    trajectory stays bit-identical to a telemetry-off run and the
    one-dispatch transfer-guard contract is unchanged.

    ``app_telemetry=True`` (implies ``telemetry``) additionally records
    the per-application ring (``repro.obs.telemetry.APP_FIELDS``) and
    attaches it as ``OnlineStats.app_telemetry`` — same contract, same
    single dispatch.
    """
    telemetry = telemetry or app_telemetry
    machine = sim.machine
    spec: ScanPolicy = sim.policy
    assert spec.kind in DEVICE_SIM_KINDS, spec.kind
    params = machine.params
    c = sim.capacity
    pool = sim.pool
    tables = sim.tables

    # Pre-sample arrivals (and any fault schedule) — bit-identical to the
    # host run of the same seed.
    prep = _prepare_inputs(sim, n_quanta)
    j, j_pad = prep["j"], prep["j_pad"]
    arrive_q, pids = prep["arrive_q"], prep["pids"]
    job_target, pool_rate = prep["job_target"], prep["pool_rate"]
    fcfg = prep["fcfg"]
    faulted = fcfg is not None

    key = _race_key(spec, c, n_quanta, j_pad, sim.admission, telemetry,
                    fcfg, app_telemetry=app_telemetry)
    ent = _RACE_CACHE.get(key)
    if ent is None:
        with obs_trace.span("device_sim.compile_build", capacity=c,
                            quanta=n_quanta, telemetry=telemetry,
                            app_telemetry=app_telemetry):
            ent = (spec.method, spec.model, _build_race(
                spec, params, c, n_quanta, j_pad, sim.admission,
                telemetry=telemetry, faults_cfg=fcfg,
                app_telemetry=app_telemetry,
            ))
        _RACE_CACHE[key] = ent
        while len(_RACE_CACHE) > _RACE_CACHE_MAX:
            _RACE_CACHE.popitem(last=False)
    else:
        _RACE_CACHE.move_to_end(key)
    race = ent[2]

    with obs_trace.span("device_sim.commit"):
        dt = jax.device_put(DeviceTables.build(tables))
        args = (
            dt,
            jax.device_put(jnp.asarray(prep["job_pool"])),
            jax.device_put(jnp.asarray(prep["job_arrive"])),
            jax.device_put(jnp.asarray(prep["job_target"])),
            jax.device_put(jnp.asarray(prep["syn_cost"])),
            jax.device_put(jnp.asarray(prep["syn_mean"])),
            jax.device_put(jnp.asarray(prep["syn_stacks"])),
            jax.device_put(jax.random.PRNGKey(sim.seed)),
        )
        if faulted:
            # The schedule ships once with the inputs (faults are data);
            # the scan indexes it per quantum on device.
            args = args + (
                jax.device_put(jnp.asarray(prep["fup"])),
                jax.device_put(jnp.asarray(prep["fspeed"])),
            )
    out = None
    if warmup:
        with obs_trace.span("device_sim.compile"):
            out = jax.block_until_ready(race(*args))  # compile + first run
    walls = []
    for _ in range(max(int(repeats), 1)):
        t0 = time.perf_counter()
        with obs_trace.span("device_sim.dispatch"):
            if transfer_guard:
                with jax.transfer_guard("disallow"):
                    out = jax.block_until_ready(race(*args))
            else:
                out = jax.block_until_ready(race(*args))
        walls.append(time.perf_counter() - t0)
    per_quantum = float(np.median(walls)) / max(n_quanta, 1)

    with obs_trace.span("device_sim.fetch"):
        fetched = tuple(np.asarray(o) for o in out)
    admit, finish, queue_depth, n_active, n_solo = fetched[:5]
    fi = 5
    retries = retry_at = evictions = requeues = None
    if faulted:
        retries, retry_at, evictions, requeues = fetched[fi:fi + 4]
        fi += 4
        _check_conservation(prep, n_quanta, admit, finish, retries,
                            retry_at)
    if telemetry:
        tlm = fetched[fi]
        fi += 1
    if app_telemetry:
        app_ring = fetched[fi]
    solo_s = (
        job_target[:j] / pool_rate[pids] * params.quantum_s
        if j else np.zeros(0)
    )
    name = spec.name or f"scan-{spec.kind}"
    with obs_trace.span("device_sim.stats"):
        stats = OnlineStats.from_device_logs(
            policy_name=name,
            quantum_s=params.quantum_s,
            quanta=n_quanta,
            app_names=[pool[int(pid)].name for pid in pids],
            arrive_q=arrive_q,
            admit_q=admit[:j],
            finish_q=finish[:j],
            targets=job_target[:j],
            solo_s=solo_s,
            queue_depth=queue_depth,
            active=n_active,
            policy_s=np.full(n_quanta, per_quantum),
            solo_quanta=n_solo,
            retries=retries[:j] if faulted else None,
        )
    if faulted:
        _attach_fault_stats(stats, prep, retries, retry_at, evictions,
                            requeues)
    if telemetry:
        # The in-graph ring leaves the departures column zero (counting
        # ``done`` in-graph would perturb the quantum's float fusion and
        # break telemetry-off bit-identity); fill it here from the
        # reconstructed traffic timeline so the ring is complete.  The
        # fault columns are filled the same way: schedule data plus the
        # integer eviction/requeue counts off the ``ys``.
        tlm = np.array(tlm)
        tlm[:, OPEN_FIELDS.index("departures")] = stats.departures
        if faulted:
            for nm in ("failures", "recoveries", "evictions", "requeues",
                       "straggling"):
                tlm[:, OPEN_FIELDS.index(nm)] = getattr(stats, nm)
        stats.telemetry = TelemetryLog(OPEN_FIELDS, tlm, policy=name)
    if app_telemetry:
        stats.app_telemetry = AppTelemetryLog(APP_FIELDS, app_ring,
                                              policy=name)
    return stats


def _attach_fault_stats(stats: OnlineStats, prep, retries, retry_at,
                        evictions, requeues) -> None:
    """Fill the fault timelines/scalars of a device run's stats from the
    fetched job logs and the (host-side) fault schedule."""
    sched = prep["sched"]
    j = prep["j"]
    max_retries = prep["fcfg"][0]
    stats.failures = sched.failures()
    stats.recoveries = sched.recoveries()
    stats.straggling = sched.straggling()
    stats.evictions = np.asarray(evictions, np.float64)
    stats.requeues = np.asarray(requeues, np.float64)
    stats.n_dropped = int((retries[:j] > max_retries).sum()) if j else 0
    stats.n_retry_waiting = int(
        (retry_at[:j] < int(RETRY_NEVER)).sum()
    ) if j else 0
    # In flight = admitted but neither completed, dropped, nor waiting —
    # the residual of the conservation partition checked on fetch.
    stats.n_in_flight = (stats.n_admitted - stats.n_completed
                         - stats.n_dropped - stats.n_retry_waiting)


def _host_carry0(spec: ScanPolicy, capacity: int, j_pad: int, faults_cfg):
    """The initial scan carry, built host-side for the segmented runner
    (the one-dispatch race constructs the identical carry inside jit)."""
    c = capacity
    p = fused_pad(c)
    ncat = spec.method.n_categories if spec.kind == "synpa" else 4
    ocarry = _OpenCarry(
        app_id=jnp.full(c, -1, jnp.int32),
        job_at=jnp.full(c, -1, jnp.int32),
        phase_idx=jnp.zeros(c, jnp.int32),
        phase_left=jnp.zeros(c, jnp.float32),
        progress=jnp.zeros(c, jnp.float32),
        target=jnp.full(c, jnp.inf, jnp.float32),
        head=jnp.int32(0),
        counters=jnp.zeros((c, 5), jnp.float32),
        ran=jnp.zeros(c, bool),
        partner_prev=jnp.arange(c, dtype=jnp.int32),
        mpart=jnp.arange(p, dtype=jnp.int32),
        st=jnp.tile(jnp.asarray(isc.uniform_stack(ncat))[None, :], (c, 1)),
        admit_q=jnp.full(j_pad, -1, jnp.int32),
        finish_q=jnp.full(j_pad, jnp.inf, jnp.float32),
    )
    fc = _FaultCarry(
        retries=jnp.zeros(j_pad, jnp.int32),
        retry_at=jnp.full(j_pad, RETRY_NEVER, jnp.int32),
        saved=jnp.zeros(j_pad, jnp.float32),
    ) if faults_cfg is not None else None
    return (ocarry, fc)


def run_device_sim_checkpointed(sim, n_quanta: int, seg_len: int,
                                ckpt_dir: str, keep: int = 3,
                                resume: bool = True,
                                telemetry: bool = False,
                                app_telemetry: bool = False,
                                max_segments: Optional[int] = None
                                ) -> Optional[OnlineStats]:
    """Device run with checkpoint/resume: the horizon is scanned in
    ``n_quanta / seg_len`` segments, snapshotting the full scan carry (and
    the accumulated per-quantum outputs) through ``repro.checkpoint`` at
    every segment boundary.  A run killed between segments resumes from
    the newest valid snapshot (corrupt/partial ones are skipped and
    removed by the manager) and finishes **bit-identical** to the same
    segmented run left uninterrupted: the fault schedule and job arrays
    are pure functions of the seed, and the RNG streams are keyed per
    (context, quantum) — position in the horizon, not position in the
    process lifetime.  Against :func:`run_device_sim` the integer
    timelines match exactly and f32 finish times to rounding (~1 ulp):
    the segment race is a *different compiled program*, so XLA's
    fusion/FMA choices may differ.

    The trade against :func:`run_device_sim` is dispatch count: one
    dispatch and one host round-trip *per segment* (the checkpoint write
    is host I/O by definition), so this is the long-horizon/preemptible
    mode, not the benchmark mode.  ``n_quanta`` must divide evenly into
    segments — padding jobs carry ``arrive_q == n_quanta``, so a segment
    scanning past the horizon would spuriously admit them.

    ``max_segments`` stops after that many segments *this call* and
    returns None (the interrupted-run hook the resume tests use);
    ``resume=False`` ignores existing snapshots and restarts from
    quantum 0.
    """
    from repro.checkpoint import CheckpointManager

    telemetry = telemetry or app_telemetry
    machine = sim.machine
    spec: ScanPolicy = sim.policy
    assert spec.kind in DEVICE_SIM_KINDS, spec.kind
    assert seg_len > 0 and n_quanta % seg_len == 0, (
        f"horizon {n_quanta} must be a whole number of segments "
        f"(seg_len={seg_len})"
    )
    params = machine.params
    c = sim.capacity
    pool = sim.pool
    prep = _prepare_inputs(sim, n_quanta)
    j, j_pad = prep["j"], prep["j_pad"]
    fcfg = prep["fcfg"]
    faulted = fcfg is not None

    key = _race_key(spec, c, seg_len, j_pad, sim.admission, telemetry,
                    fcfg, segment=True, app_telemetry=app_telemetry)
    ent = _RACE_CACHE.get(key)
    if ent is None:
        with obs_trace.span("device_sim.compile_build", capacity=c,
                            quanta=seg_len, segment=True,
                            app_telemetry=app_telemetry):
            ent = (spec.method, spec.model, _build_race(
                spec, params, c, seg_len, j_pad, sim.admission,
                telemetry=telemetry, faults_cfg=fcfg, segment=True,
                app_telemetry=app_telemetry,
            ))
        _RACE_CACHE[key] = ent
        while len(_RACE_CACHE) > _RACE_CACHE_MAX:
            _RACE_CACHE.popitem(last=False)
    else:
        _RACE_CACHE.move_to_end(key)
    race = ent[2]

    with obs_trace.span("device_sim.commit"):
        dt = jax.device_put(DeviceTables.build(sim.tables))
        args = (
            dt,
            jax.device_put(jnp.asarray(prep["job_pool"])),
            jax.device_put(jnp.asarray(prep["job_arrive"])),
            jax.device_put(jnp.asarray(prep["job_target"])),
            jax.device_put(jnp.asarray(prep["syn_cost"])),
            jax.device_put(jnp.asarray(prep["syn_mean"])),
            jax.device_put(jnp.asarray(prep["syn_stacks"])),
            jax.device_put(jax.random.PRNGKey(sim.seed)),
            None if not faulted else jax.device_put(
                jnp.asarray(prep["fup"])
            ),
            None if not faulted else jax.device_put(
                jnp.asarray(prep["fspeed"])
            ),
        )

    ys_names = ["queue_depth", "n_active", "n_solo"]
    if faulted:
        ys_names += ["evictions", "requeues"]
    if telemetry:
        ys_names += ["telemetry"]
    if app_telemetry:
        ys_names += ["app_telemetry"]

    mgr = CheckpointManager(ckpt_dir, keep=keep)
    # The config fingerprint a snapshot must match to be resumable —
    # refuse-don't-migrate, like every recorded artefact in this repo.
    meta_want = {
        "n_quanta": int(n_quanta), "seg_len": int(seg_len),
        "seed": int(sim.seed), "capacity": int(c), "j_pad": int(j_pad),
        "admission": sim.admission, "kind": spec.kind,
        "telemetry": bool(telemetry), "faulted": bool(faulted),
        "app_telemetry": bool(app_telemetry),
    }
    carry = _host_carry0(spec, c, j_pad, fcfg)
    ys_acc = {nm: [] for nm in ys_names}
    q0 = 0
    if resume:
        step, nested, meta = mgr.restore_latest()
        if step is not None:
            got = {k: meta.get(k) for k in meta_want}
            assert got == meta_want, (
                f"checkpoint config mismatch under {ckpt_dir}: "
                f"{got} vs {meta_want}"
            )
            oc = _OpenCarry(**{
                k: jnp.asarray(v) for k, v in nested["ocarry"].items()
            })
            fc = _FaultCarry(**{
                k: jnp.asarray(v) for k, v in nested["fcarry"].items()
            }) if faulted else None
            carry = (oc, fc)
            ys_acc = {
                nm: [np.asarray(nested["ys"][nm])] for nm in ys_names
            }
            q0 = step

    t0 = time.perf_counter()
    segs_run = 0
    while q0 < n_quanta:
        if max_segments is not None and segs_run >= max_segments:
            return None          # interrupted on purpose; resume later
        with obs_trace.span("device_sim.dispatch", q0=q0, segment=True):
            final, ys = race(*args, carry, jnp.int32(q0))
            final = jax.block_until_ready(final)
        carry = final
        for nm, y in zip(ys_names, ys):
            ys_acc[nm].append(np.asarray(y))
        q0 += seg_len
        segs_run += 1
        tree = {
            "ocarry": {k: np.asarray(v)
                       for k, v in final[0]._asdict().items()},
            "ys": {nm: np.concatenate(ys_acc[nm], axis=0)
                   for nm in ys_names},
        }
        if faulted:
            tree["fcarry"] = {
                k: np.asarray(v) for k, v in final[1]._asdict().items()
            }
        with obs_trace.span("device_sim.checkpoint", step=q0):
            mgr.save(q0, tree, meta=meta_want)
    wall = time.perf_counter() - t0
    per_quantum = wall / max(segs_run * seg_len, 1)

    ocarry, fcarry = carry
    admit = np.asarray(ocarry.admit_q)
    finish = np.asarray(ocarry.finish_q)
    series = {nm: np.concatenate(ys_acc[nm], axis=0) for nm in ys_names}
    retries = retry_at = None
    if faulted:
        retries = np.asarray(fcarry.retries)
        retry_at = np.asarray(fcarry.retry_at)
        _check_conservation(prep, n_quanta, admit, finish, retries,
                            retry_at)
    arrive_q, pids = prep["arrive_q"], prep["pids"]
    job_target, pool_rate = prep["job_target"], prep["pool_rate"]
    solo_s = (
        job_target[:j] / pool_rate[pids] * params.quantum_s
        if j else np.zeros(0)
    )
    name = spec.name or f"scan-{spec.kind}"
    with obs_trace.span("device_sim.stats"):
        stats = OnlineStats.from_device_logs(
            policy_name=name,
            quantum_s=params.quantum_s,
            quanta=n_quanta,
            app_names=[pool[int(pid)].name for pid in pids],
            arrive_q=arrive_q,
            admit_q=admit[:j],
            finish_q=finish[:j],
            targets=job_target[:j],
            solo_s=solo_s,
            queue_depth=series["queue_depth"],
            active=series["n_active"],
            policy_s=np.full(n_quanta, per_quantum),
            solo_quanta=series["n_solo"],
            retries=retries[:j] if faulted else None,
        )
    if faulted:
        _attach_fault_stats(stats, prep, retries, retry_at,
                            series["evictions"], series["requeues"])
    if telemetry:
        tlm = np.array(series["telemetry"])
        tlm[:, OPEN_FIELDS.index("departures")] = stats.departures
        if faulted:
            for nm in ("failures", "recoveries", "evictions", "requeues",
                       "straggling"):
                tlm[:, OPEN_FIELDS.index(nm)] = getattr(stats, nm)
        stats.telemetry = TelemetryLog(OPEN_FIELDS, tlm, policy=name)
    if app_telemetry:
        stats.app_telemetry = AppTelemetryLog(
            APP_FIELDS, series["app_telemetry"], policy=name)
    return stats
