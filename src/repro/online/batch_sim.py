"""Batched device-resident open system — the whole scenario grid as ONE
``jit``-of-``vmap``-of-``scan`` dispatch.

``run_device_sim`` (PR 5) made one *scenario* one dispatch; the churn
grid (`benchmarks/online_churn.py`) still looped scenarios — seeds, load
points, admission rules — through independent dispatches, paying a host
round-trip and a dispatch per cell and leaving confidence intervals too
expensive to afford on a jittery container.  This module batches the
scenario axis itself: every per-scenario input of the open-system race
(job arrays, RNG key, admission rule, fault schedule, retry knobs) is
stacked on a leading **lane** axis and the shared scan body of
``device_sim._make_open_ops`` is ``vmap``-ed over it, so S×R scenarios
execute as a single compiled program.  Host exits only at stats
extraction — the transfer-guard contract of the single-lane engine,
unchanged.

What varies per lane and what is shared:

* **Shared (``in_axes=None``)** — the profiled :class:`DeviceTables`,
  the synergy admission tables, the machine params and every
  shape-bearing static (capacity, horizon, padded job count, policy
  spec).  One copy serves all lanes; lanes are scenarios over the same
  machine and pool, not different machines.
* **Per lane (``in_axes=0``)** — the pre-sampled job arrays
  (arrival quantum / pool id / target, re-padded to the max ``j_pad``
  across lanes; padding jobs carry ``arrive_q == n_quanta`` so a wider
  pad never changes a trajectory), the scenario seed, the admission
  flag, and — when any lane is faulted — the expanded fault schedule and
  the retry knobs.

**What one dispatch moves.**  The shared tables stay **resident**: the
profiled ``DeviceTables`` and the three synergy arrays are uploaded once
per ``PhaseTables`` instance (and ``SynergyAdmission``, or the FIFO
lanes' zero stand-ins), kept in ``_BATCH_CACHE`` beside the compiled
races and freed by ``_BATCH_CACHE.clear()``; the ``scan.tables`` span
appears only when they are uploaded.  The per-lane inputs go up as **one
packed buffer per dtype**: an ``int32`` row per lane of job pool ids,
arrival quanta, the seed (``seed mod 2**32`` as its ``uint32`` bits) and
the admission flag, and a ``float32`` row of job targets; the race
slices them apart outside the ``lax.scan`` and builds each lane's
threefry key in-graph (``_lane_key``), bit-equal to
``jax.random.PRNGKey(seed)``.  Faulted lanes' schedules and knobs go up
as arrays of their own.  The outputs come back in **one**
``jax.device_get``.  ``batch_sim.commit`` reports the host-to-device
transfers it made as its ``uploads`` argument: 2 with the tables
resident and no faulted lane.

**Divergent control flow is masked data.**  The single-lane race picks
its admission rule and fault constants at trace time (Python branches —
the static graphs the pinned bit-identity tests hold).  A batch cannot:
lanes disagree.  ``_make_open_ops(admission="lane")`` computes *both*
admission rules each quantum and selects by a traced per-lane flag, and
``faults_cfg="lane"`` reads ``max_retries``/``backoff``/``preserve`` off
traced scalars.  Unfaulted lanes in a mixed batch ride an all-up,
unit-speed schedule — eviction never fires, and scaling retirement by
exactly 1.0f keeps f32 values identical to the multiply-free graph.

**The parity contract, one axis up** (held by
``tests/test_batch_sim.py``): every lane of a batched run is
**f32-bit-identical** to the same scenario run through
:func:`repro.online.device_sim.run_device_sim` — admission quanta,
fractional finish times, queue/active/solo timelines, retry logs and
the telemetry ring all match bitwise, faulted lanes included.  This
holds because the lane body performs the *same arithmetic on the same
values* as each static graph (the un-selected admission rule's outputs
are dead values; XLA's batching rule for every op in the body —
including the threefry stream and the bounded matcher loops — is
elementwise over lanes), and because a lane's inputs are bit-identical
to the single run's by construction.  Lane count is a shape, not a
value: adding lanes never changes another lane's trajectory.  (The
closed-race sibling, ``repro.smt.scan_engine.run_quanta_multi_batched``,
promises f32 round-off rather than bitwise at multiple lanes — its
batched dots lower with different SIMD tails; see its docstring.)

Timing note: the lanes of one dispatch are indivisible, so per-lane
``policy_s`` reports the whole-grid wall time divided by ``L * quanta``
— the *per-scenario cost* the batched path is measured on
(``results/batched_grid_speedup.json``; expect sublinear wins on 2 CPUs,
near-linear lane throughput is the accelerator story).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from collections import OrderedDict
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.obs import trace as obs_trace
from repro.obs.telemetry import (
    APP_FIELDS,
    AppTelemetryLog,
    OPEN_FIELDS,
    TelemetryLog,
)
from repro.online.device_sim import (
    DEVICE_SIM_KINDS,
    _attach_fault_stats,
    _check_conservation,
    _LaneCfg,
    _make_open_ops,
    _prepare_inputs,
)
from repro.smt.metrics import OnlineStats
from repro.smt.scan_engine import DeviceTables, ScanPolicy


def _lane_key(word):
    """A lane's threefry key from its seed word (``int32`` bits of
    ``seed mod 2**32``): ``jax.random.PRNGKey`` of the ``uint32`` word,
    which is ``[0, seed mod 2**32]`` — bit-equal to the eager
    ``jax.random.PRNGKey(seed)`` of every seed :func:`_seed_words`
    accepts."""
    return jax.random.PRNGKey(lax.bitcast_convert_type(word, jnp.uint32))


def _seed_words(seeds: Sequence[int]) -> np.ndarray:
    """The lanes' seeds as ``int32`` words for :func:`_lane_key`.

    Without x64 the eager ``jax.random.PRNGKey(seed)`` narrows any seed
    in int64 range to its low 32 bits, so the key is
    ``[0, seed mod 2**32]``; with x64 it keeps the high word too, and the
    two agree only on ``0 <= seed < 2**32``.  A seed outside the agreeing
    range is refused rather than keyed differently."""
    lo, hi = (0, 2**32) if jax.config.jax_enable_x64 else (-2**63, 2**63)
    words = []
    for s in seeds:
        s = int(s)
        if not lo <= s < hi:
            raise ValueError(
                f"scenario seed {s} is outside [{lo}, {hi}): its in-graph "
                "key would differ from jax.random.PRNGKey(seed)")
        words.append(s % 2**32)
    return np.array(words, np.uint32).view(np.int32)


def _build_batched_race(spec: ScanPolicy, params, capacity: int,
                        n_quanta: int, j_pad: int, telemetry: bool,
                        faulted: bool, app_telemetry: bool = False,
                        slot_log: bool = False):
    """One jitted, lane-batched open-system race.

    ``race(dt, syn_cost, syn_mean, syn_stacks, ints (L, 2J + 2) i32,
    job_target (L, J) f32, fup, fspeed, max_retries, backoff, preserve)``
    -> per-lane outputs, every array of the single-lane race with a
    leading lane axis.  A lane's ``ints`` row packs its job pool ids,
    arrival quanta, seed word and admission flag (``_pack_ints``); the
    race slices it apart outside the ``lax.scan``.  Lane count is a
    trace-time shape: the same Python callable recompiles per distinct L,
    and per-lane trajectories are L-invariant (vmap batches every op
    elementwise over lanes).
    """
    body, carry0, unpack = _make_open_ops(
        spec, params, capacity, j_pad, "lane", telemetry,
        "lane" if faulted else None, app_telemetry=app_telemetry,
        slot_log=slot_log,
    )

    def lane_race(dt, syn_cost, syn_mean, syn_stacks, ints, job_target,
                  fup, fspeed, max_retries, backoff, preserve):
        job_pool = ints[:j_pad]
        job_arrive = ints[j_pad:2 * j_pad]
        mkey = _lane_key(ints[2 * j_pad])
        lane_cfg = _LaneCfg(ints[2 * j_pad + 1] != 0, max_retries, backoff,
                            preserve)
        fn = functools.partial(body, dt, job_pool, job_arrive, job_target,
                               syn_cost, syn_mean, syn_stacks, mkey,
                               fup, fspeed, lane_cfg)
        final, ys = lax.scan(
            fn, carry0(), jnp.arange(n_quanta, dtype=jnp.int32)
        )
        return unpack(final, ys)

    fax = 0 if faulted else None
    batched = jax.vmap(
        lane_race,
        in_axes=(None, None, None, None, 0, 0, fax, fax, fax, fax, fax),
    )
    return jax.jit(batched)


def _pack_ints(job_pool: np.ndarray, job_arrive: np.ndarray,
               seed_words: np.ndarray, is_syn: np.ndarray) -> np.ndarray:
    """The lanes' integer inputs as one ``(L, 2J + 2)`` ``int32`` buffer:
    ``[job_pool | job_arrive | seed word | is_syn]`` a row."""
    return np.concatenate([job_pool, job_arrive, seed_words[:, None],
                           is_syn.astype(np.int32)[:, None]], axis=1)


# Compiled batched races keyed by their static configuration (the lane
# count is a shape, handled by jit itself), and the resident tables keyed
# ``("tables", id(PhaseTables), id(SynergyAdmission or None))``.  Same
# identity-keyed discipline as device_sim._RACE_CACHE: every entry keeps
# the objects its key names by id, so an id is never reused while cached.
_BATCH_CACHE: "OrderedDict[Tuple, Tuple]" = OrderedDict()
_BATCH_CACHE_MAX = 8

#: Host-to-device transfers of one upload of the resident tables: the
#: ``DeviceTables`` arrays (``n_apps`` is static) and the synergy three.
_TABLE_UPLOADS = len(dataclasses.fields(DeviceTables)) - 1 + 3


def _cache_get(key: Tuple):
    ent = _BATCH_CACHE.get(key)
    if ent is not None:
        _BATCH_CACHE.move_to_end(key)
    return ent


def _cache_put(key: Tuple, ent: Tuple) -> None:
    _BATCH_CACHE[key] = ent
    while len(_BATCH_CACHE) > _BATCH_CACHE_MAX:
        _BATCH_CACHE.popitem(last=False)


def _batch_key(spec: ScanPolicy, capacity: int, n_quanta: int, j_pad: int,
               telemetry: bool, faulted: bool,
               app_telemetry: bool = False, slot_log: bool = False) -> Tuple:
    return (
        spec.kind, id(spec.method), id(spec.model), spec.pair_impl,
        spec.solver, spec.matcher, spec.refine_eps, spec.refine_rounds,
        spec.first_match, capacity, n_quanta, j_pad, telemetry, faulted,
        app_telemetry, slot_log,
    )


def _spec_statics(spec: ScanPolicy) -> Tuple:
    return (spec.kind, id(spec.method), id(spec.model), spec.pair_impl,
            spec.solver, spec.matcher, spec.refine_eps, spec.refine_rounds,
            spec.first_match)


def _repad(arr: np.ndarray, j_pad: int, fill) -> np.ndarray:
    out = np.full(j_pad, fill, arr.dtype)
    out[: arr.size] = arr
    return out


def run_device_sim_batched(sims: Sequence, n_quanta: int,
                           repeats: int = 1,
                           transfer_guard: bool = False,
                           warmup: bool = True,
                           telemetry: bool = False,
                           app_telemetry: bool = False,
                           slot_log: bool = False,
                           ) -> List[OnlineStats]:
    """Run a list of :class:`repro.online.sim.ClusterSim` scenarios as
    ONE batched dispatch; returns per-lane :class:`OnlineStats` in input
    order, each f32-bit-identical to ``run_device_sim`` of that scenario.

    The scenarios must share everything shape- or compile-bearing —
    machine params, capacity, profiled tables, policy statics
    (method/model by identity) — and may differ in seed, arrival
    process, admission rule and fault profile.  Synergy lanes must agree
    on their admission tables (they ship once, shared across lanes).

    ``repeats``/``warmup``/``transfer_guard``/``telemetry`` follow
    :func:`run_device_sim`; per-lane ``policy_s`` spreads the
    whole-grid median wall over ``L * n_quanta`` (per-scenario cost).
    ``app_telemetry`` (implies ``telemetry``) attaches each lane's
    per-application ring as ``OnlineStats.app_telemetry`` — per-lane
    rings are bit-identical to the single-dispatch twin's.  ``slot_log``
    attaches each lane's ``(Q, 3, C)`` occupant job ids, phase indices
    and partner contexts as ``OnlineStats.slot_log``
    (``device_sim._make_open_ops``).

    Spans: ``batch_sim.run`` around the call, and as its children, one
    after another, ``batch_sim.presample`` (lane checks and arrival
    pre-sampling), ``.pack`` (the lanes' inputs packed, the race and the
    resident tables looked up or the race built), ``.commit`` (the
    host-to-device transfers, counted in its ``uploads`` argument; the
    tables' upload, ``scan.tables``, only on a miss), ``.compile`` (the
    warm-up), ``.dispatch`` (each run, to ``block_until_ready``),
    ``.fetch`` (one ``device_get``) and ``.stats`` (the job records).
    """
    with obs_trace.span("batch_sim.run", lanes=len(sims), quanta=n_quanta):
        return _run_batched(sims, n_quanta, repeats, transfer_guard, warmup,
                            telemetry or app_telemetry, app_telemetry,
                            slot_log)


def _run_batched(sims, n_quanta, repeats, transfer_guard, warmup,
                 telemetry, app_telemetry, slot_log) -> List[OnlineStats]:
    with obs_trace.span("batch_sim.presample", lanes=len(sims),
                        quanta=n_quanta):
        assert len(sims) >= 1, "batched run needs at least one scenario lane"
        base = sims[0]
        spec: ScanPolicy = base.policy
        params = base.machine.params
        c = base.capacity
        statics = _spec_statics(spec)
        for s in sims:
            assert s.engine == "scan", "batched lanes must be scan-engine sims"
            assert s.policy.kind in DEVICE_SIM_KINDS, s.policy.kind
            assert s.capacity == c, (
                f"lane capacity mismatch: {s.capacity} != {c}"
            )
            assert s.machine.params == params, "lane machine params differ"
            assert _spec_statics(s.policy) == statics, (
                "batched lanes must share policy statics (method/model by "
                f"identity): {s.policy} vs {spec}"
            )
            assert s.tables is base.tables, (
                "batched lanes must share one profiled PhaseTables instance"
            )

        preps = [_prepare_inputs(s, n_quanta) for s in sims]
    with obs_trace.span("batch_sim.pack", lanes=len(sims)):
        L = len(sims)
        j_pad = max(p["j_pad"] for p in preps)
        faulted_lane = [p["fcfg"] is not None for p in preps]
        faulted = any(faulted_lane)

        # Synergy tables ship once; fifo lanes' selected path never reads
        # them, so sharing is value-neutral — but synergy lanes must agree.
        syn_lanes = [i for i, s in enumerate(sims) if s.admission == "synergy"]
        syn_src = sims[syn_lanes[0]].synergy if syn_lanes else None
        p0 = preps[syn_lanes[0] if syn_lanes else 0]
        syn_cost, syn_mean = p0["syn_cost"], p0["syn_mean"]
        syn_stacks = p0["syn_stacks"]
        for i in syn_lanes[1:]:
            assert (
                np.array_equal(preps[i]["syn_cost"], syn_cost)
                and np.array_equal(preps[i]["syn_mean"], syn_mean)
                and np.array_equal(preps[i]["syn_stacks"], syn_stacks)
            ), "synergy lanes must share admission tables"

        ints = _pack_ints(
            np.stack([_repad(p["job_pool"], j_pad, 0) for p in preps]),
            np.stack([_repad(p["job_arrive"], j_pad, n_quanta)
                      for p in preps]),
            _seed_words([s.seed for s in sims]),
            np.array([s.admission == "synergy" for s in sims]),
        )
        job_target = np.stack(
            [_repad(p["job_target"], j_pad, np.inf) for p in preps]
        )
        if faulted:
            # Unfaulted lanes ride an all-up unit-speed schedule: eviction
            # never fires and the speed multiply is exactly 1.0f — values
            # stay bit-identical to the multiply-free single-lane graph.
            fup = np.stack([
                p["fup"] if f else np.ones((n_quanta, c), bool)
                for p, f in zip(preps, faulted_lane)
            ])
            fspeed = np.stack([
                p["fspeed"] if f else np.ones((n_quanta, c), np.float32)
                for p, f in zip(preps, faulted_lane)
            ])
            max_retries = np.array([
                p["fcfg"][0] if f else 0
                for p, f in zip(preps, faulted_lane)
            ], np.int32)
            backoff = np.array([
                p["fcfg"][1] if f else 0
                for p, f in zip(preps, faulted_lane)
            ], np.int32)
            preserve = np.array([
                bool(p["fcfg"][2]) if f else True
                for p, f in zip(preps, faulted_lane)
            ], bool)
            fault_args = (fup, fspeed, max_retries, backoff, preserve)
        else:
            fault_args = ()

        key = _batch_key(spec, c, n_quanta, j_pad, telemetry, faulted,
                         app_telemetry=app_telemetry, slot_log=slot_log)
        ent = _cache_get(key)
        if ent is None:
            with obs_trace.span("batch_sim.compile_build", capacity=c,
                                quanta=n_quanta, lanes=L,
                                app_telemetry=app_telemetry):
                ent = (spec.method, spec.model, _build_batched_race(
                    spec, params, c, n_quanta, j_pad, telemetry, faulted,
                    app_telemetry=app_telemetry, slot_log=slot_log,
                ))
            _cache_put(key, ent)
        race = ent[2]
        tkey = ("tables", id(base.tables), id(syn_src))
        resident = _cache_get(tkey)

    uploads = 2 + len(fault_args) + (_TABLE_UPLOADS if resident is None
                                     else 0)
    with obs_trace.span("batch_sim.commit", lanes=L, uploads=uploads):
        if resident is None:
            resident = (base.tables, syn_src,
                        DeviceTables.build(base.tables),
                        *jax.device_put((syn_cost, syn_mean, syn_stacks)))
            _cache_put(tkey, resident)
        args = (resident[2:] + jax.device_put((ints, job_target))
                + (jax.device_put(fault_args) if faulted else (None,) * 5))
    out = None
    if warmup:
        with obs_trace.span("batch_sim.compile", lanes=L):
            out = jax.block_until_ready(race(*args))
    walls = []
    for _ in range(max(int(repeats), 1)):
        t0 = time.perf_counter()
        with obs_trace.span("batch_sim.dispatch", lanes=L):
            if transfer_guard:
                with jax.transfer_guard("disallow"):
                    out = jax.block_until_ready(race(*args))
            else:
                out = jax.block_until_ready(race(*args))
        walls.append(time.perf_counter() - t0)

    with obs_trace.span("batch_sim.fetch", lanes=L):
        fetched = jax.device_get(out)
        admit, finish, queue_depth, n_active, n_solo = fetched[:5]
        fi = 5
        retries = retry_at = evictions = requeues = None
        if faulted:
            retries, retry_at, evictions, requeues = fetched[fi:fi + 4]
            fi += 4
        tlm = app_tlm = slots = None
        if telemetry:
            tlm = fetched[fi]
            fi += 1
        if app_telemetry:
            app_tlm = fetched[fi]
            fi += 1
        if slot_log:
            slots = fetched[fi]

    stats_out: List[OnlineStats] = []
    with obs_trace.span("batch_sim.stats", lanes=L):
        # Per-scenario cost: the grid is indivisible, so each lane carries
        # an equal share of the whole-grid median wall.
        per_quantum = float(np.median(walls)) / max(L * n_quanta, 1)
        for i, (sim, prep) in enumerate(zip(sims, preps)):
            j = prep["j"]
            arrive_q, pids = prep["arrive_q"], prep["pids"]
            jt, pool_rate = prep["job_target"], prep["pool_rate"]
            lane_faulted = faulted_lane[i]
            if lane_faulted:
                _check_conservation(prep, n_quanta, admit[i], finish[i],
                                    retries[i], retry_at[i])
            solo_s = (
                jt[:j] / pool_rate[pids] * params.quantum_s
                if j else np.zeros(0)
            )
            lane_spec = sim.policy
            name = lane_spec.name or f"scan-{lane_spec.kind}"
            stats = OnlineStats.from_device_logs(
                policy_name=name,
                quantum_s=params.quantum_s,
                quanta=n_quanta,
                app_names=[sim.pool[int(pid)].name for pid in pids],
                arrive_q=arrive_q,
                admit_q=admit[i, :j],
                finish_q=finish[i, :j],
                targets=jt[:j],
                solo_s=solo_s,
                queue_depth=queue_depth[i],
                active=n_active[i],
                policy_s=np.full(n_quanta, per_quantum),
                solo_quanta=n_solo[i],
                retries=retries[i, :j] if lane_faulted else None,
            )
            if lane_faulted:
                _attach_fault_stats(stats, prep, retries[i], retry_at[i],
                                    evictions[i], requeues[i])
            if telemetry:
                ring = np.array(tlm[i])
                ring[:, OPEN_FIELDS.index("departures")] = stats.departures
                if lane_faulted:
                    for nm in ("failures", "recoveries", "evictions",
                               "requeues", "straggling"):
                        ring[:, OPEN_FIELDS.index(nm)] = getattr(stats, nm)
                stats.telemetry = TelemetryLog(OPEN_FIELDS, ring,
                                               policy=name)
            if app_telemetry:
                stats.app_telemetry = AppTelemetryLog(
                    APP_FIELDS, app_tlm[i], policy=name)
            if slot_log:
                stats.slot_log = slots[i]
            stats_out.append(stats)
    return stats_out
