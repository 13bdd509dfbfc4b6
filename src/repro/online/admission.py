"""Queue-aware admission — the synergy placement tier of ``ClusterSim``.

FIFO admission places a dequeued job on the lowest free context and tells
the policy nothing about it: until its first counters land, a newcomer
scores with the uniform ST placeholder, so the re-matching pairs it blind.
A production cluster knows more — it has *historical profiles* of the job
types it runs.  ``SynergyAdmission`` packages exactly that information:

* per pool application, the measured noiseless **solo ISC stack** under the
  policy's stack method (``repro.smt.workloads.solo_stack`` — the §5
  profiling step a deployment performs once per job type);
* the **Eq. 4 predicted pair-cost matrix** over those stacks — which job
  types synergise, which interfere.

At admission time it (a) *places* the dequeued job (FIFO order is kept) on
the free context whose core-resident co-runner has the best predicted pair
score — falling back to the expected pool cost for contexts on empty
cores — and (b) hands the policy an **ST hint** for the newcomer's slot, so
the very first re-matching sees an informative estimate instead of the
uniform placeholder.

A note on (a) vs (b): the simulator's policies re-pair *arbitrary* slots
every quantum (cores are virtual for the pairing), so the slot index itself
carries no interference information — the placement rule is recorded for
realism and determinism, while the measurable quality lever is the hint:
it is what lets the churn repair pair a newcomer with a genuinely
compatible widow instead of an arbitrary one.  The A/B lives in
``benchmarks/online_churn.py`` (``synpa4-stream-syn`` arm).
"""

from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp
import numpy as np

from repro.core import isc, regression
from repro.obs import trace as obs_trace


class SynergyAdmission:
    """Profile-informed placement + ST seeding for dequeued jobs.

    machine/pool: the simulator's machine and application pool;
    method:       the stack method the *policy* uses — hints must live in
                  the same stack space as the allocator's estimates;
    model:        the fitted Eq. 4 model used for pair scoring;
    quanta:       solo-profiling horizon per pool application (noiseless).
    """

    def __init__(self, machine, pool, method: isc.StackMethod, model:
                 regression.CategoryModel, quanta: int = 40):
        from repro.smt.workloads import solo_stack

        self.method = method
        with obs_trace.span("syn.tables", apps=len(pool), quanta=quanta):
            self.stacks = np.stack([
                np.asarray(solo_stack(machine, p, method, quanta=quanta),
                           np.float32)
                for p in pool
            ])
            # Elementwise Eq. 4 over broadcast stacks and a 4-term sum: no
            # contraction, so no matmul precision applies on any backend.
            # The matrix's diagonal is the matcher's no-self-pairing
            # sentinel, but two jobs of one app do co-run: score the pool
            # against a copy of itself and keep the off-diagonal block,
            # whose diagonal is the real same-app pair cost.
            n = len(pool)
            twice = jnp.asarray(np.concatenate([self.stacks, self.stacks]))
            cost = regression.pair_cost_matrix(model, twice, impl="xla")
            self.pool_cost = np.asarray(cost[:n, n:], np.float64)
        # Expected pairing cost of each job type against a uniform random
        # co-runner of another type — the placement score of a context on
        # an empty core.
        off = ~np.eye(n, dtype=bool)
        self.mean_cost = np.array([
            self.pool_cost[k][off[k]].mean() for k in range(n)
        ])

    def place(self, pid: int, free_slots: Sequence[int],
              app_id: np.ndarray) -> int:
        """Free slot with the best predicted co-runner for pool app ``pid``.

        ``app_id`` maps slots to pool indices (-1 = empty); a free slot's
        co-runner is the resident of the other context of its core
        (``slot ^ 1``).  Ties break to the lowest slot, and a slot whose
        core-mate is empty scores the expected pool cost — so compatible
        residents attract newcomers, incompatible ones repel them onto
        empty cores.

        Vectorised (one gather + argmin over the free set): placing k jobs
        on an N-slot cluster is O(k * N) array work instead of the former
        O(k * N) *Python* loop — the piece of the host admission walk that
        showed at N >= 4096 under high churn.  The device engine runs the
        same rule in-graph (``repro.online.device_sim``).

        Tie semantics: argmin keeps the lowest slot among *exactly* equal
        costs — the common case, since clone pool apps predict identical
        pair costs — same as the pre-vectorised loop; costs that differ
        by less than the old loop's 1e-12 hysteresis (but are not equal)
        now resolve to the true minimum instead of the earlier slot.
        Runs stay seed-deterministic either way.
        """
        free = np.sort(np.asarray(list(free_slots), dtype=np.int64))
        assert free.size, "no free slot to place on"
        mate = app_id[free ^ 1]
        cost = np.where(
            mate >= 0,
            self.pool_cost[pid, np.maximum(mate, 0)],
            self.mean_cost[pid],
        )
        return int(free[int(np.argmin(cost))])

    def hint(self, pid: int) -> np.ndarray:
        """Profiled solo ST stack of pool app ``pid`` (the policy hint)."""
        return self.stacks[pid]
