"""Accelerator-resident machine engine — ``engine="scan"``.

The vectorised numpy engine (`repro.smt.machine`) runs a quantum as a few
host array ops and the fused SYNPA dispatch as one jitted device call, but
the *loop over quanta* — and the matching step — still live on the host:
every quantum costs a dispatch, a cost-matrix transfer and a host matcher
pass.  This module ports the whole per-quantum cycle to JAX and composes

    machine quantum  ->  fused SYNPA step  ->  device matcher

into a single ``lax.scan`` over quanta, so an entire K-policy race
(:func:`run_quanta_scan`, the scan twin of ``SMTMachine.run_quanta_multi``)
executes as **one dispatch** with host exits only at result extraction.

Parity contract (held by ``tests/test_scan_engine.py``):

* **Deterministic parts are exact to float tolerance.**  Given identical
  phase indices and pairings, the interference transform, instruction
  advance and noiseless PMU counters equal the numpy engine's within
  float32 round-off (the numpy engine computes in float64; the device
  engine in float32).
* **RNG parts are distribution-equal, not bit-equal.**  The numpy engine
  draws counter noise and phase durations from a ``numpy.Generator``
  stream; this engine draws them from threefry streams keyed per
  ``(quantum, purpose)``.  The draws match in distribution (lognormal
  noise moments, poisson phase durations) under the documented stream
  layout below, but a scan run and a vector run of the same seed follow
  different noise trajectories.  Aggregate metrics (IPC, mean true
  slowdown) agree statistically.

RNG stream layout (bump :data:`SCAN_RNG_STREAM_VERSION` when changing it):

* machine key  = ``PRNGKey(seed)``;
  counter noise of quantum ``q`` = ``fold_in(fold_in(key, q), 0)`` as one
  ``(N, 4)`` standard-normal block, ``exp(sigma * z)``;
  phase durations of quantum ``q`` = ``fold_in(fold_in(key, q), 1)`` as an
  ``(N,)`` poisson block (only transitioning slots consume theirs).
* policy key of the k-th raced policy = ``fold_in(PRNGKey(seed + 7919), k)``
  (the in-graph ``linux`` migrations); the *initial pairing* of every
  policy is drawn on host from ``numpy.default_rng(seed + 7919)`` — the
  same convention (and therefore the same first-quantum pairing) as the
  host schedulers' first ``_random_pairs`` call.
* **v2 (open system)**: the device-resident open-system engine
  (``repro.online.device_sim``) draws the identical per-quantum blocks
  over the ``C = 2 * n_cores`` hardware *contexts* instead of N apps —
  noise ``(C, 4)``, phase poisson ``(C,)`` — keyed per (context, quantum)
  regardless of occupancy, so a context's draws are membership- and
  pairing-independent.  Closed-race draws are bit-identical to v1; v2 is
  a pure extension of the layout.  Arrivals are *pre-sampled on host*
  from ``numpy.default_rng(seed + 4242)`` — the host ``ClusterSim``
  stream, bit for bit — and shipped as data with the initial carry.
* **Fault schedules** (``repro.online.faults``) follow the same
  faults-are-data convention on a *separate* host stream,
  ``numpy.default_rng(seed + 6007)``, versioned independently as
  ``FAULT_RNG_STREAM_VERSION`` — injecting faults never perturbs the
  threefry draws above (or the arrival stream), which is what keeps a
  faulted run's surviving contexts on their faults-off trajectories.

All K policies of a race face a bit-identical workload, as in
``run_quanta_multi``.  The scan engine's guarantee is in fact stronger:
noise and phase draws are keyed per (slot, quantum), never per visit
order, so a slot's draws are identical across policies even when their
pairings differ — whereas the vector engine assigns noise draws in pair
visit order (``draw_order``), making per-slot noise pairing-dependent
and only promising identical counters *for identical pairings*.

The engine targets the fixed-horizon throughput mode (``run_quanta``): no
§6.2 targets or relaunches, which is exactly what the cluster-scale races
use.  Odd populations follow the idle-context convention: a slot whose
partner is the idle vertex runs alone, interference-free, that quantum.

Timing note: the race is one dispatch, so machine and policy time cannot
be separated; :func:`run_quanta_scan` reports the whole per-quantum wall
time in ``ThroughputResult.machine_s_per_quantum`` (median over
``repeats`` back-to-back dispatches after the compile call) and leaves the
``sched_*`` fields zero.  Compare engines on the machine+policy *sum*.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, NamedTuple, Optional, Sequence

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
    CLOSED_FIELDS,
    TelemetryLog,
)
from repro.smt.machine import (
    MachineParams,
    PhaseTables,
    ThroughputResult,
)

#: Version of the threefry stream layout documented in the module
#: docstring.  Statistical-parity tests and recorded benchmark results are
#: tied to it; bump on any change to key derivation or draw shapes.
#: v2 extends v1 with the open-system (device sim) layout — closed-race
#: draws are bit-identical to v1, so v1-recorded closed-race A/Bs remain
#: valid under v2.
SCAN_RNG_STREAM_VERSION = 2


@dataclasses.dataclass(frozen=True)
class DeviceTables:
    """jnp (float32) mirror of :class:`repro.smt.machine.PhaseTables`."""

    n_apps: int
    n_phases: jnp.ndarray     # (A,) i32
    comps: jnp.ndarray        # (A, Pmax, 4)
    util: jnp.ndarray         # (A, Pmax)
    x_fe: jnp.ndarray         # (A, Pmax)
    x_be: jnp.ndarray         # (A, Pmax)
    duration: jnp.ndarray     # (A, Pmax)
    omega: jnp.ndarray        # (A,)
    retire: jnp.ndarray       # (A,)
    mem_sens: jnp.ndarray     # (A,)
    fetch_sens: jnp.ndarray   # (A,)

    @classmethod
    def build(cls, tables: PhaseTables) -> "DeviceTables":
        f = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
        with obs_trace.span("scan.tables", n_apps=tables.n_apps):
            return cls(
                n_apps=tables.n_apps,
                n_phases=jnp.asarray(tables.n_phases, jnp.int32),
                comps=f(tables.comps),
                util=f(tables.util),
                x_fe=f(tables.x_fe),
                x_be=f(tables.x_be),
                duration=f(tables.duration),
                omega=f(tables.omega),
                retire=f(tables.retire),
                mem_sens=f(tables.mem_sens),
                fetch_sens=f(tables.fetch_sens),
            )


jax.tree_util.register_pytree_node(
    DeviceTables,
    lambda t: (
        (t.n_phases, t.comps, t.util, t.x_fe, t.x_be, t.duration,
         t.omega, t.retire, t.mem_sens, t.fetch_sens),
        t.n_apps,
    ),
    lambda n_apps, leaves: DeviceTables(n_apps, *leaves),
)


@dataclasses.dataclass(frozen=True)
class ScanPolicy:
    """One raced policy of the scan engine.

    kind:
      ``"synpa"``   — fused SYNPA step + device matcher (needs ``method``
                      and ``model``);
      ``"static"``  — the initial random pairing, pinned (the scan twin of
                      ``RandomStaticScheduler``);
      ``"linux"``   — sticky pairing with occasional random migrations
                      (the scan *analogue* of ``LinuxScheduler``: same move
                      and probability, threefry instead of numpy draws).

    matcher:
      ``"refine"``  — full device re-match (sort seed + 2-opt) at the
                      first counter quantum, then a bounded masked 2-opt
                      from the carried pairing (the streaming allocator's
                      quality-equal tier, in-graph);
      ``"full"``    — fresh sort seed + 2-opt re-match every quantum (the
                      cold tier: measurably more work per quantum).

    ``refine_rounds`` bounds the parallel-swap rounds of the refine tier
    per quantum (each round applies every mutual-best improving swap);
    ``refine_eps`` is the per-swap improvement floor — the same noise-floor
    role as ``StreamingConfig.refine_eps``.

    ``first_match`` picks the refine tier's *once-per-race* full re-match
    seed at the first counter quantum: ``"seed"`` re-ranks from scratch
    (sort seed + full 2-opt, the PR 4 path), ``"carry"`` starts the full
    2-opt budget from the carried pairing instead.  Measured back to back
    (``docs/scaling.md`` §2c), ``"carry"`` is *slower* from a race start
    — the once-per-race cost is the 2-opt's convergence, not the seed
    construction, and the random initial carry converges slower than the
    complementary sort seed (0.95x at N = 256, 0.81x at N = 1024) — so
    ``"auto"`` resolves to ``"seed"`` at every size.  ``"carry"`` stays
    selectable for callers whose carry is *informative* (a re-entered
    race); the open-system engine (``repro.online.device_sim``) realises
    exactly that benefit structurally: its repair tier re-seeds from the
    previous quantum's partner vector every quantum and never pays a
    sort-seed re-match at all.

    ``name`` labels the policy in open-system stats
    (``repro.online.device_sim``); the closed race keys results by the
    ``policies`` dict instead.
    """

    kind: str = "synpa"
    method: Optional[isc.StackMethod] = None
    model: Optional[object] = None
    pair_impl: str = "auto"
    solver: str = "gn"
    matcher: str = "refine"
    refine_eps: float = 1e-2
    refine_rounds: int = 8
    p_migrate: float = 0.03
    first_match: str = "auto"
    name: Optional[str] = None


class _MachineState(NamedTuple):
    phase_idx: jnp.ndarray      # (N,) i32
    phase_left: jnp.ndarray     # (N,) f32
    total_retired: jnp.ndarray  # (N,) f32
    total_cycles: jnp.ndarray   # (N,) f32


def _corun_components_scan(dt: DeviceTables, ph, partner, params, aid=None):
    """In-graph :func:`repro.smt.machine.corun_components_batched`.

    ``partner[i] == i`` marks a solo slot: the interference terms are
    masked to zero, so its components are exactly the solo components.

    ``aid`` (optional) maps slots to pool rows of ``dt`` — the open
    system's slot -> application indirection (``repro.online.device_sim``).
    The closed engine's slots *are* pool rows (``aid = arange``, the
    default), so its path is unchanged.
    """
    n = ph.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    if aid is None:
        aid = idx
    co = (partner != idx).astype(jnp.float32)
    c = dt.comps[aid, ph]
    cpi = c.sum(axis=-1)
    php = ph[partner]
    aidp = aid[partner]
    u = dt.util[aidp, php] * co
    f = dt.x_fe[aidp, php] * co
    m = dt.x_be[aidp, php] * co
    mem = dt.mem_sens[aid]
    fetch = dt.fetch_sens[aid]
    out = jnp.stack(
        [
            c[:, 0] * (1.0 + params.a_disp * u),
            c[:, 1] * (1.0 + params.a_hw * u),
            c[:, 2] * (1.0 + params.a_fe * f)
            + params.e_fe * fetch * f * cpi,
            c[:, 3] * (1.0 + params.a_be * m + params.b_be * mem * m * m)
            + params.e_be * mem * m * cpi,
        ],
        axis=-1,
    )
    return out


def _pmu_counters_scan(comps, omega, retire, cycles, params, key,
                       noisy=True):
    """In-graph :func:`repro.smt.machine.pmu_counters_batched`.

    Noise is one ``(N, 4)`` lognormal block from ``key`` —
    distribution-equal to the numpy engine's draws (stream layout in the
    module docstring), applied to the same four noisy columns.
    """
    n = comps.shape[0]
    cpi = comps.sum(axis=-1)
    insts = cycles / cpi
    frac = comps / cpi[:, None]
    x_fe, x_be = frac[:, 2], frac[:, 3]
    overlap = omega * jnp.minimum(x_fe, x_be)
    noisy_cols = jnp.stack(
        [
            cycles * (x_fe + params.overlap_split * overlap),
            cycles * (x_be + (1.0 - params.overlap_split) * overlap),
            insts,
            insts * retire,
        ],
        axis=-1,
    )
    if noisy:
        z = jax.random.normal(key, (n, 4), jnp.float32)
        noisy_cols = noisy_cols * jnp.exp(params.noise_sigma * z)
    return jnp.concatenate(
        [jnp.full((n, 1), cycles, jnp.float32), noisy_cols], axis=-1
    )


def _make_machine_quantum(dt: DeviceTables, params: MachineParams):
    """Closure: one in-graph quantum of the fixed-horizon machine."""
    n = dt.n_apps
    idx = jnp.arange(n, dtype=jnp.int32)
    cycles = jnp.float32(params.quantum_cycles)

    @jax.named_scope("machine")
    def quantum(state: _MachineState, partner, mkey, q):
        ph = state.phase_idx % dt.n_phases
        comps = _corun_components_scan(dt, ph, partner, params)
        cpi = comps.sum(axis=-1)
        solo_cpi = dt.comps[idx, ph].sum(axis=-1)
        slowdown = jnp.mean(cpi / solo_cpi)

        retired = cycles / cpi * dt.retire
        counters = _pmu_counters_scan(
            comps, dt.omega, dt.retire, cycles, params,
            jax.random.fold_in(jax.random.fold_in(mkey, q), 0),
        )

        # Phase advance: transitioning slots draw their next duration from
        # the per-(slot, quantum) poisson block — pairing-independent, so
        # all raced policies see identical phase trajectories.
        left = state.phase_left - 1.0
        trans = left <= 0.0
        new_idx = state.phase_idx + trans.astype(jnp.int32)
        lam = dt.duration[idx, new_idx % dt.n_phases]
        draws = jax.random.poisson(
            jax.random.fold_in(jax.random.fold_in(mkey, q), 1), lam, (n,)
        ).astype(jnp.float32)
        new_left = jnp.where(trans, jnp.maximum(draws, 1.0), left)

        new_state = _MachineState(
            phase_idx=new_idx,
            phase_left=new_left,
            total_retired=state.total_retired + retired,
            total_cycles=state.total_cycles + cycles,
        )
        return counters, new_state, slowdown

    return quantum


def _slow_stats(dt: DeviceTables, params: MachineParams, phase_idx,
                partner, aid=None, per_slot: bool = False):
    """Telemetry shadow of the quantum's true-slowdown computation:
    ``[mean, max]`` of the per-slot slowdown ratio, ``(2,)`` f32.

    ``per_slot=True`` (static, the ``app_telemetry`` ring) additionally
    returns the un-reduced ``(n,)`` ratio vector and the barriered
    partner vector.  Both already exist inside the shadow — only the
    final reduction discards them — so emitting them adds no new
    consumer of the quantum's own float intermediates and the doctrine
    below is untouched.

    Recomputed from scratch behind an ``optimization_barrier`` on the
    *integer* inputs (phase indices + pairing) rather than read off the
    quantum's own intermediates: giving the quantum's ``ratio`` (or
    anything upstream of it) an extra consumer changes which fusions XLA
    picks for the original reductions, and f32 reductions are not
    associative — the telemetry-on run would drift from the telemetry-off
    run by an ulp per quantum.  The barrier blocks CSE from merging the
    shadow with the real subgraph (their inputs differ formally), and
    barriering integer arrays cannot perturb float codegen, so the
    trajectory stays bit-identical.  Cost: one extra interference
    transform per quantum — a few N x 4 flops, noise next to the fused
    policy step.
    """
    n = phase_idx.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    if aid is None:
        ph_b, pb = lax.optimization_barrier((phase_idx, partner))
        aid_b = idx
    else:
        ph_b, pb, aid_b = lax.optimization_barrier((phase_idx, partner, aid))
    ph = ph_b % dt.n_phases
    comps = _corun_components_scan(dt, ph, pb, params, aid=aid_b)
    cpi = comps.sum(axis=-1)
    solo_cpi = dt.comps[aid_b, ph].sum(axis=-1)
    ratio = cpi / solo_cpi
    stats = jnp.stack([jnp.mean(ratio), jnp.max(ratio)])
    if per_slot:
        return stats, ratio, pb
    return stats


def _machine_partner_of(mpart, n):
    """Matcher-space partner (P,) -> machine partner (N,): idle/pad -> self."""
    idx = jnp.arange(n, dtype=jnp.int32)
    mp = mpart[:n].astype(jnp.int32)
    return jnp.where(mp < n, mp, idx)


def _make_policy_step(spec: ScanPolicy, n: int, p_pad: int,
                      valid_p: jnp.ndarray, telemetry: bool = False,
                      app_telemetry: bool = False):
    """Closure: (q, counters, mpart, st, pkey, first=False) -> (mpart', st').

    ``first`` is a *static* Python flag marking the first quantum with
    counters: the synpa refine tier then runs the full sort-seed + 2-opt re-match
    instead of refining the carried pairing.  It is static — the race
    hoists the first policy call out of the ``lax.scan`` — so the seed
    compiles into exactly one execution per race instead of riding as a
    per-quantum ``lax.cond`` branch.

    ``telemetry`` (static) makes the step return a third output: the
    policy half of the per-quantum ring — ``CLOSED_FIELDS[2:]`` as a
    ``(6,)`` f32 vector (predicted pair cost, 2-opt rounds, GN solver
    diagnostics).  The kinds without a solver/matcher report zeros.  The
    off path builds today's graph exactly.

    ``app_telemetry`` (static, implies ``telemetry``) appends a fourth
    output: the per-machine-slot predicted slowdown, ``(n,)`` f32 — half
    the committed pair's Eq.4 cost, read off the *same* ``cost`` gather
    the scalar ring already performs (zero for the kinds that predict
    nothing).
    """
    assert telemetry or not app_telemetry, (
        "app_telemetry implies telemetry in the policy step"
    )
    idx = jnp.arange(n, dtype=jnp.int32)
    odd = n % 2 == 1
    pol_zeros = jnp.zeros(6, jnp.float32)
    pred_zeros = jnp.zeros(n, jnp.float32)

    if spec.kind == "static":
        def step(q, counters, mpart, st, pkey, first=False):
            if app_telemetry:
                return mpart, st, pol_zeros, pred_zeros
            if telemetry:
                return mpart, st, pol_zeros
            return mpart, st
        return step

    if spec.kind == "linux":
        p_mig = float(spec.p_migrate)

        def step(q, counters, mpart, st, pkey, first=False):
            key = jax.random.fold_in(pkey, q)
            k1, k2, k3 = jax.random.split(key, 3)
            x = jax.random.randint(k1, (), 0, n)
            y = jax.random.randint(k2, (), 0, n)
            px = mpart[x]
            py = mpart[y]
            distinct = (y != x) & (y != px) & (px < n) & (py < n)
            do = (jax.random.uniform(k3) < p_mig) & distinct
            # Swap x and y between their cores: (px, x)(py, y) ->
            # (px, y)(py, x) — the LinuxScheduler move in partner space.
            swapped = (
                mpart.at[px].set(y).at[y].set(px)
                .at[py].set(x).at[x].set(py)
            )
            out = jnp.where(do, swapped, mpart)
            if app_telemetry:
                return out, st, pol_zeros, pred_zeros
            if telemetry:
                return out, st, pol_zeros
            return out, st
        return step

    assert spec.kind == "synpa", spec.kind
    assert spec.method is not None and spec.model is not None, (
        "synpa scan policy needs a stack method and a fitted model"
    )
    fstep = make_fused_step(
        spec.method, spec.model, impl=spec.pair_impl, solver=spec.solver,
        with_diag=telemetry,
    )
    full_budget = 4 * (p_pad // 2)
    first_mode = spec.first_match
    if first_mode == "auto":
        # Measured: the carry (random at race start) converges slower
        # than the sort seed at every size — see the ScanPolicy docstring.
        first_mode = "seed"
    assert first_mode in ("seed", "carry"), spec.first_match
    p_idx = jnp.arange(p_pad, dtype=jnp.int32)
    n_valid = jnp.maximum(jnp.sum(valid_p.astype(jnp.float32)), 1.0)

    @jax.named_scope("synpa_step")
    def step(q, counters, mpart, st, pkey, first=False):
        partner = _machine_partner_of(mpart, n)
        solve = partner != idx
        solo = ~solve
        masks = jnp.stack(
            [solve, solo, jnp.ones(n, bool), jnp.zeros(n, bool)]
        )
        if telemetry:
            cost, st, fdiag = fstep(counters, partner, st, masks,
                                    jnp.asarray(odd))
        else:
            cost, st = fstep(counters, partner, st, masks, jnp.asarray(odd))
        if spec.matcher == "refine" and first and first_mode == "carry":
            # Once-per-race full re-match, seeded by the carried pairing:
            # the full 2-opt budget without the sort-seed construction.
            matched = matching.device_two_opt_partner(
                cost, mpart, valid_p, eps=spec.refine_eps,
                max_rounds=full_budget, with_rounds=telemetry,
            )
        elif spec.matcher == "full" or (spec.matcher == "refine" and first):
            matched = matching.device_pairs_partner(
                cost, valid_p, eps=spec.refine_eps, max_rounds=full_budget,
                with_rounds=telemetry,
            )
        else:
            assert spec.matcher == "refine", spec.matcher
            matched = matching.device_two_opt_partner(
                cost, mpart, valid_p, eps=spec.refine_eps,
                max_rounds=spec.refine_rounds, with_rounds=telemetry,
            )
        if telemetry:
            with jax.named_scope("telemetry"):
                mpart, rounds = matched
                # Mean predicted cost per committed pair: each pair's entry
                # appears twice (i->j and j->i) over n_valid/2 pairs, so the
                # two factors of 2 cancel.
                gathered = jnp.where(valid_p, cost[p_idx, mpart], 0.0)
                pred = jnp.sum(gathered) / n_valid
                pol = jnp.concatenate(
                    [jnp.stack([pred, rounds.astype(jnp.float32)]), fdiag]
                )
                if app_telemetry:
                    # Per-slot predicted slowdown: cost[i, j] is
                    # slowdown(i|j) + slowdown(j|i), so each slot's share of
                    # its committed pair is half the gathered entry.
                    return mpart, st, pol, gathered[:n] * 0.5
                return mpart, st, pol
        return matched, st

    return step


def _initial_mpart(n: int, p_pad: int, rng: np.random.Generator) -> np.ndarray:
    """Host-built initial matcher-space partner vector.

    The random permutation follows the host schedulers' first
    ``_random_pairs`` draw (``default_rng(seed + 7919)``); an odd
    population's leftover slot pairs the idle vertex (row ``n``), and
    padding vertices pair consecutively among themselves.
    """
    perm = rng.permutation(n)
    mpart = np.arange(p_pad, dtype=np.int32)
    for k in range(n // 2):
        a, b = int(perm[2 * k]), int(perm[2 * k + 1])
        mpart[a], mpart[b] = b, a
    pads = list(range(n, p_pad))
    if n % 2 == 1:
        solo = int(perm[-1])
        mpart[solo], mpart[n] = n, solo
        pads.remove(n)
    for k in range(0, len(pads), 2):
        a, b = pads[k], pads[k + 1]
        mpart[a], mpart[b] = b, a
    return mpart


def build_race(
    tables: PhaseTables,
    params: MachineParams,
    policies: Sequence[ScanPolicy],
    n_quanta: int,
    telemetry: bool = False,
    app_telemetry: bool = False,
):
    """Compile-ready K-policy race: one jitted function, one dispatch.

    Returns ``race(dt, init_mpart (K, P), init_st (K, N, 4), mkey, pkey)``
    -> ``(total_retired (K, N), total_cycles (K, N), slowdown_sum (K,))``.
    The K policy bodies are unrolled inside the jit (K is small and
    static); each runs quantum 0 with its initial pairing and then a
    ``lax.scan`` over quanta 1..Q-1 of policy step + machine quantum.

    ``telemetry`` (static) appends a fourth output: the per-quantum
    telemetry ring, ``(K, n_quanta, len(CLOSED_FIELDS))`` — machine and
    policy counters recorded in-graph every quantum, stacked as scan
    ``ys`` (the hoisted quanta 0/1 contribute inline-built rows) and
    fetched with the rest of the results in the same single dispatch.
    Telemetry never feeds the carry, and the off path traces today's
    graph unchanged, so trajectories are bit-identical either way.

    ``app_telemetry`` (static, implies ``telemetry``) appends a fifth
    output: the per-application ring, ``(K, n_quanta, N,
    len(APP_FIELDS))`` — occupant identity, predicted vs ground-truth
    slowdown, signed residual, and the policy's ST stack estimates for
    every hardware slot every quantum.  The identity/ground-truth
    columns come from the same integer-barrier shadow as the scalar
    ring; predictions reuse the scalar ring's ``cost`` gather — same
    doctrine, same bit-identity guarantee.
    """
    assert telemetry or not app_telemetry, (
        "app_telemetry implies telemetry in build_race"
    )
    n = tables.n_apps
    p_pad = fused_pad(n)
    valid_np = np.zeros(p_pad, bool)
    valid_np[:n] = True
    if n % 2 == 1:
        valid_np[n] = True
    valid_p = jnp.asarray(valid_np)
    steps = [_make_policy_step(s, n, p_pad, valid_p, telemetry=telemetry,
                               app_telemetry=app_telemetry)
             for s in policies]
    idx_n = jnp.arange(n, dtype=jnp.int32)

    def app_rows(ratio, pb, pred_slot, st):
        """One quantum's ``(N, len(APP_FIELDS))`` per-app ring block.

        ``ratio``/``pb`` come out of the ``_slow_stats`` barrier shadow;
        ``pred_slot`` is the policy step's per-slot cost gather (zeros
        when no policy ran).  Closed race: ``app_id`` *is* the slot
        index; a slot paired with the idle vertex (odd N) runs solo and
        records no partner/prediction.
        """
        co = pb != idx_n
        partner_app = jnp.where(co, pb, -1).astype(jnp.float32)
        # The barriers pin the *recorded* (rounded) tensors as the
        # residual's operands — without them XLA fuses the upstream
        # multiplies into FMAs and the residual column disagrees with
        # pred - real by an ulp.
        pred, real = lax.optimization_barrier(
            (jnp.where(co, pred_slot, 0.0), ratio))
        resid = jnp.where(pred > 0.0, pred - real, 0.0)
        st4 = st[:, :APP_ST_WIDTH]
        if st4.shape[1] < APP_ST_WIDTH:
            st4 = jnp.concatenate(
                [st4, jnp.zeros((n, APP_ST_WIDTH - st4.shape[1]),
                                jnp.float32)], axis=1)
        head = jnp.stack(
            [idx_n.astype(jnp.float32), partner_app, pred, real, resid],
            axis=1,
        )
        return jnp.concatenate([head, st4], axis=1)

    @jax.named_scope("telemetry")
    def ring_rows(dt, phase_idx, partner, pol, pred_slot, st):
        """(scalar ring row, per-app ring block or None) for one quantum."""
        if app_telemetry:
            stats, ratio, pb = _slow_stats(dt, params, phase_idx, partner,
                                           per_slot=True)
            return (jnp.concatenate([stats, pol]),
                    app_rows(ratio, pb, pred_slot, st))
        return (jnp.concatenate(
            [_slow_stats(dt, params, phase_idx, partner), pol]), None)

    def run_one(dt, quantum, policy_step, mpart0, st0, mkey, pkey):
        state = _MachineState(
            phase_idx=jnp.zeros(n, jnp.int32),
            phase_left=dt.duration[:, 0],
            total_retired=jnp.zeros(n, jnp.float32),
            total_cycles=jnp.zeros(n, jnp.float32),
        )
        pol_zeros = jnp.zeros(6, jnp.float32)
        pred_zeros = jnp.zeros(n, jnp.float32)
        # Quantum 0: the initial random pairing, no counters yet.
        partner0 = _machine_partner_of(mpart0, n)
        if telemetry:
            # No policy ran at quantum 0: policy fields are zero.
            tvec0, avec0 = ring_rows(dt, state.phase_idx, partner0,
                                     pol_zeros, pred_zeros, st0)
            tvecs, avecs = [tvec0], [avec0]
        counters, state, slow_sum = quantum(state, partner0, mkey, 0)
        mpart, st = mpart0, st0
        if n_quanta >= 2:
            # Quantum 1 is hoisted out of the scan: the synpa refine tier
            # runs its (once-per-race) full seed + 2-opt re-match here
            # as straight-line code rather than a per-quantum cond branch.
            if telemetry:
                stepped = policy_step(1, counters, mpart, st, pkey,
                                      first=True)
                mpart, st, pol1 = stepped[:3]
                pred1 = stepped[3] if app_telemetry else pred_zeros
                partner = _machine_partner_of(mpart, n)
                tvec1, avec1 = ring_rows(dt, state.phase_idx, partner,
                                         pol1, pred1, st)
                tvecs.append(tvec1)
                avecs.append(avec1)
                counters, state, slow1 = quantum(state, partner, mkey, 1)
            else:
                mpart, st = policy_step(1, counters, mpart, st, pkey,
                                        first=True)
                counters, state, slow1 = quantum(
                    state, _machine_partner_of(mpart, n), mkey, 1
                )
            slow_sum = slow_sum + slow1

        def body(carry, q):
            state, counters, mpart, st = carry
            if telemetry:
                stepped = policy_step(q, counters, mpart, st, pkey)
                mpart, st, pol = stepped[:3]
                pred = stepped[3] if app_telemetry else pred_zeros
                partner = _machine_partner_of(mpart, n)
                tvec, avec = ring_rows(dt, state.phase_idx, partner,
                                       pol, pred, st)
                counters, state, slow = quantum(state, partner, mkey, q)
                ys = ((slow, tvec, avec) if app_telemetry
                      else (slow, tvec))
                return (state, counters, mpart, st), ys
            mpart, st = policy_step(q, counters, mpart, st, pkey)
            partner = _machine_partner_of(mpart, n)
            counters, state, slow = quantum(state, partner, mkey, q)
            return (state, counters, mpart, st), slow

        (state, _c, _m, _st), ys = lax.scan(
            body, (state, counters, mpart, st),
            jnp.arange(2, n_quanta),
        )
        if telemetry:
            if app_telemetry:
                slows, tscan, ascan = ys
            else:
                slows, tscan = ys
            tlm = jnp.concatenate([jnp.stack(tvecs), tscan], axis=0)
            out = [
                state.total_retired,
                state.total_cycles,
                slow_sum + jnp.sum(slows),
                tlm,
            ]
            if app_telemetry:
                out.append(
                    jnp.concatenate([jnp.stack(avecs), ascan], axis=0)
                )
            return tuple(out)
        slows = ys
        return (
            state.total_retired,
            state.total_cycles,
            slow_sum + jnp.sum(slows),
        )

    n_out = 3 + int(telemetry) + int(app_telemetry)

    @jax.jit
    def race(dt: DeviceTables, init_mpart, init_st, mkey, pkey):
        quantum = _make_machine_quantum(dt, params)
        outs = [
            run_one(dt, quantum, step, init_mpart[k], init_st[k], mkey,
                    jax.random.fold_in(pkey, k))
            for k, step in enumerate(steps)
        ]
        return tuple(jnp.stack([o[i] for o in outs]) for i in range(n_out))

    return race


def run_quanta_scan(
    machine,
    profiles,
    policies: Dict[str, ScanPolicy],
    n_quanta: int = 20,
    seed: int = 0,
    tables: Optional[PhaseTables] = None,
    repeats: int = 1,
    transfer_guard: bool = False,
    telemetry: bool = False,
    app_telemetry: bool = False,
) -> Dict[str, ThroughputResult]:
    """The scan twin of ``SMTMachine.run_quanta_multi`` — one dispatch.

    ``repeats`` re-dispatches the (pure) compiled race and reports the
    *median* per-quantum wall time; the compile call is always excluded.
    ``transfer_guard=True`` wraps the timed dispatches in
    ``jax.transfer_guard("disallow")``, proving the loop makes no
    per-quantum host transfers (inputs are device-committed up front,
    results are fetched after the guard exits).

    ``telemetry=True`` records the per-quantum device ring
    (``repro.obs.telemetry.CLOSED_FIELDS``) inside the same dispatch and
    attaches it to each result as a ``TelemetryLog`` — trajectories stay
    bit-identical to a telemetry-off run and the one-dispatch
    transfer-guard contract is unchanged (the ring travels with the
    existing result fetch).

    ``app_telemetry=True`` (implies ``telemetry``) additionally records
    the per-application ring (``repro.obs.telemetry.APP_FIELDS``) and
    attaches it as ``ThroughputResult.app_telemetry`` — same contract,
    same single dispatch.
    """
    telemetry = telemetry or app_telemetry
    params = machine.params
    tables = tables if tables is not None else PhaseTables.build(profiles)
    n = tables.n_apps
    p_pad = fused_pad(n)
    specs = list(policies.values())
    with obs_trace.span("scan.compile_build", n=n, quanta=n_quanta,
                        telemetry=telemetry, app_telemetry=app_telemetry):
        race = build_race(tables, params, specs, n_quanta,
                          telemetry=telemetry, app_telemetry=app_telemetry)

    init_mpart = np.stack(
        [
            _initial_mpart(n, p_pad, np.random.default_rng(seed + 7919))
            for _ in specs
        ]
    )
    init_st = np.stack([_uniform_stacks(s, n) for s in specs])

    with obs_trace.span("scan.commit"):
        dt = jax.device_put(DeviceTables.build(tables))
        args = (
            dt,
            jax.device_put(jnp.asarray(init_mpart, jnp.int32)),
            jax.device_put(jnp.asarray(init_st, jnp.float32)),
            jax.device_put(jax.random.PRNGKey(seed)),
            jax.device_put(jax.random.PRNGKey(seed + 7919)),
        )

    with obs_trace.span("scan.compile"):
        out = jax.block_until_ready(race(*args))  # compile + first run
    walls = []
    for _ in range(max(int(repeats), 1)):
        t0 = time.perf_counter()
        with obs_trace.span("scan.dispatch"):
            if transfer_guard:
                with jax.transfer_guard("disallow"):
                    out = jax.block_until_ready(race(*args))
            else:
                out = jax.block_until_ready(race(*args))
        walls.append(time.perf_counter() - t0)
    per_quantum = float(np.median(walls)) / max(n_quanta, 1)

    with obs_trace.span("scan.fetch"):
        fetched = tuple(np.asarray(o) for o in out)
    retired, cycles, slow_sum = fetched[:3]
    tlm = fetched[3] if telemetry else None
    app = fetched[4] if app_telemetry else None
    results: Dict[str, ThroughputResult] = {}
    with obs_trace.span("scan.stats"):
        for k, name in enumerate(policies):
            ipc = retired[k] / np.maximum(cycles[k], 1.0)
            results[name] = ThroughputResult(
                n_apps=n,
                quanta=n_quanta,
                ipc=ipc,
                total_retired=float(retired[k].sum()),
                mean_true_slowdown=float(slow_sum[k]) / max(n_quanta, 1),
                sched_s_per_quantum=0.0,
                sched_s_per_quantum_median=0.0,
                machine_s_per_quantum=per_quantum,
                telemetry=(
                    TelemetryLog(CLOSED_FIELDS, tlm[k], policy=name)
                    if telemetry else None
                ),
                app_telemetry=(
                    AppTelemetryLog(APP_FIELDS, app[k], policy=name)
                    if app_telemetry else None
                ),
            )
    return results


def run_quanta_multi_batched(
    machine,
    profiles,
    policies: Dict[str, ScanPolicy],
    seeds: Sequence[int],
    n_quanta: int = 20,
    tables: Optional[PhaseTables] = None,
    repeats: int = 1,
    transfer_guard: bool = False,
    telemetry: bool = False,
    app_telemetry: bool = False,
) -> Dict[str, List[ThroughputResult]]:
    """The closed race over a batch of seeds as ONE dispatch —
    ``jit``-of-``vmap``-of-:func:`build_race` over a leading seed-lane
    axis.

    Every per-seed input of the race (initial pairing, initial ST
    estimates, machine and policy keys) stacks on the lane axis; the
    profiled :class:`DeviceTables` ship once, shared.  Returns
    ``{policy_name: [ThroughputResult, ...]}`` in ``seeds`` order.

    Parity: every lane consumes bit-identical inputs and RNG draws as
    ``run_quanta_scan`` of that seed (threefry under ``vmap`` is
    bitwise), and a single-lane batch reproduces the single dispatch
    **bit-for-bit**.  At multiple lanes XLA:CPU may lower some batched
    dots/transcendentals with a different SIMD reduction tail than the
    unbatched graph, so multi-lane results are guaranteed equal to
    within f32 round-off (last-ulp; ``tests/test_batch_sim.py`` pins
    both strengths).  The *open-system* batched path
    (``repro.online.batch_sim``) holds strict per-lane bit-identity —
    its per-context arithmetic lowers identically either way.

    Per-lane ``machine_s_per_quantum`` spreads the whole-batch median
    wall over ``len(seeds) * n_quanta`` — the per-scenario cost of the
    batch.
    """
    telemetry = telemetry or app_telemetry
    params = machine.params
    tables = tables if tables is not None else PhaseTables.build(profiles)
    n = tables.n_apps
    p_pad = fused_pad(n)
    specs = list(policies.values())
    seeds = [int(s) for s in seeds]
    S = len(seeds)
    assert S >= 1, "batched race needs at least one seed lane"
    with obs_trace.span("scan.compile_build", n=n, quanta=n_quanta,
                        telemetry=telemetry, app_telemetry=app_telemetry,
                        lanes=S):
        race = build_race(tables, params, specs, n_quanta,
                          telemetry=telemetry, app_telemetry=app_telemetry)
        batched = jax.jit(jax.vmap(race, in_axes=(None, 0, 0, 0, 0)))

    init_mpart = np.stack([
        np.stack([
            _initial_mpart(n, p_pad, np.random.default_rng(seed + 7919))
            for _ in specs
        ])
        for seed in seeds
    ])
    init_st = np.stack(
        [np.stack([_uniform_stacks(s, n) for s in specs])] * S
    )
    mkeys = np.stack([np.asarray(jax.random.PRNGKey(s)) for s in seeds])
    pkeys = np.stack(
        [np.asarray(jax.random.PRNGKey(s + 7919)) for s in seeds]
    )

    with obs_trace.span("scan.commit", lanes=S):
        dt = jax.device_put(DeviceTables.build(tables))
        args = (
            dt,
            jax.device_put(jnp.asarray(init_mpart, jnp.int32)),
            jax.device_put(jnp.asarray(init_st, jnp.float32)),
            jax.device_put(jnp.asarray(mkeys)),
            jax.device_put(jnp.asarray(pkeys)),
        )

    with obs_trace.span("scan.compile", lanes=S):
        out = jax.block_until_ready(batched(*args))
    walls = []
    for _ in range(max(int(repeats), 1)):
        t0 = time.perf_counter()
        with obs_trace.span("scan.dispatch", lanes=S):
            if transfer_guard:
                with jax.transfer_guard("disallow"):
                    out = jax.block_until_ready(batched(*args))
            else:
                out = jax.block_until_ready(batched(*args))
        walls.append(time.perf_counter() - t0)
    per_quantum = float(np.median(walls)) / max(S * n_quanta, 1)

    with obs_trace.span("scan.fetch", lanes=S):
        fetched = tuple(np.asarray(o) for o in out)
    retired, cycles, slow_sum = fetched[:3]
    tlm = fetched[3] if telemetry else None
    app = fetched[4] if app_telemetry else None
    results: Dict[str, List[ThroughputResult]] = {
        name: [] for name in policies
    }
    with obs_trace.span("scan.stats", lanes=S):
        for si in range(S):
            for k, name in enumerate(policies):
                ipc = retired[si, k] / np.maximum(cycles[si, k], 1.0)
                results[name].append(ThroughputResult(
                    n_apps=n,
                    quanta=n_quanta,
                    ipc=ipc,
                    total_retired=float(retired[si, k].sum()),
                    mean_true_slowdown=(
                        float(slow_sum[si, k]) / max(n_quanta, 1)
                    ),
                    sched_s_per_quantum=0.0,
                    sched_s_per_quantum_median=0.0,
                    machine_s_per_quantum=per_quantum,
                    telemetry=(
                        TelemetryLog(CLOSED_FIELDS, tlm[si, k],
                                     policy=name)
                        if telemetry else None
                    ),
                    app_telemetry=(
                        AppTelemetryLog(APP_FIELDS, app[si, k],
                                        policy=name)
                        if app_telemetry else None
                    ),
                ))
    return results


def _uniform_stacks(spec: ScanPolicy, n: int) -> np.ndarray:
    ncat = spec.method.n_categories if spec.method is not None else 4
    return np.tile(isc.uniform_stack(ncat), (n, 1))
