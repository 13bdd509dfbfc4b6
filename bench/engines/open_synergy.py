"""Open rack above capacity under synergy admission, on the device engine.

As ``open_device.py``, one scenario per server per dispatch, the servers
as lanes of one ``run_device_sim_batched`` dispatch; but each server
admits by synergy: jobs leave its queue in arrival order, and each goes to
the free context whose core-mate is predicted (Eq. 4 on profiled solo
stacks) to co-run with it best, with its profiled stack as its first ST
estimate.  The set-up builds one ``SynergyAdmission`` (the pool's solo
profiling and its pair cost matrix), shared by every lane.  The dispatch
also hands back each lane's slot log, which the check replays placement
by placement.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from bench import traffic_gen
from bench.engines import open_device, program
from bench.reference import synergy as ref_syn


class Engine(open_device.Engine):
    def setup(self) -> None:
        from repro.online import PoissonArrivals, SynergyAdmission
        from repro.smt.machine import PhaseTables, SMTMachine

        t0 = time.perf_counter()
        self.profiles = program.profiles(self.pool)
        self.tables = PhaseTables.build(self.profiles)
        self.machine = SMTMachine(program.machine_params(self.cfg), seed=0)
        self.rate = traffic_gen.arrival_rate(self.cfg, self.traffic["rho"])
        self.arrivals = PoissonArrivals(rate=self.rate,
                                        n_pool=len(self.profiles))
        self.spec = program.scan_policy(self.traffic["policy"], self.cfg,
                                        name="bench")
        t1 = time.perf_counter()
        profiler = SMTMachine(program.machine_params(self.cfg),
                              seed=int(self.cfg["syn_profile_seed"]))
        self.synergy = SynergyAdmission(
            profiler, self.profiles, self.spec.method, self.spec.model,
            quanta=int(self.cfg["syn_profile_quanta"]))
        t2 = time.perf_counter()
        self._dispatch(-1)
        self.parts = {"tables_s": t1 - t0, "syn_tables_s": t2 - t1,
                      "warm_s": time.perf_counter() - t2}

    def _dispatch(self, index: int) -> list:
        from repro.online import ClusterSim
        from repro.online.batch_sim import run_device_sim_batched

        import jax

        sseeds = traffic_gen.lane_seeds(self.seed, index, self.lanes)
        sims = [ClusterSim(self.machine, self.profiles, self.cores, self.spec,
                           self.arrivals, seed=s,
                           target_scale=self.cfg["target_scale"],
                           tables=self.tables, engine="scan",
                           admission=self.traffic["admission"],
                           synergy=self.synergy)
                for s in sseeds]
        with jax.profiler.TraceAnnotation("bench.run_device_sim"):
            stats = run_device_sim_batched(sims, self.quanta, warmup=False,
                                           app_telemetry=True, slot_log=True)
        names = {p.name: k for k, p in enumerate(self.profiles)}
        return [{"sseed": sseed, "n_arrived": s.n_arrived,
                 "jobs": [{"job_id": r.job_id, "arrive_q": r.arrive_q,
                           "admit_q": r.admit_q, "finish_q": r.finish_q,
                           "app": names[r.app_name]} for r in s.completed],
                 "slowdowns": s.slowdowns,
                 "admissions": np.array(s.admissions),
                 "active": np.array(s.active),
                 "tlm": np.array(s.telemetry.data),
                 "ring": np.array(s.app_telemetry.data),
                 "slots": np.array(s.slot_log)}
                for sseed, s in zip(sseeds, stats)]

    def step(self) -> None:
        lanes = self._dispatch(len(self.records))
        if self.alter is not None:
            for lane in lanes:
                self.alter(lane)
        self.sampled.offer(lanes)
        # Only the sampled dispatches keep their jobs and per-context logs:
        # the window's other records hold arrays alone, so the heap the
        # garbage collector walks does not grow with the window.
        self.records.append([{"slowdowns": lane["slowdowns"],
                              "tlm": lane["tlm"], "done": len(lane["jobs"])}
                             for lane in lanes])

    def end_to_end(self, window_s: float) -> dict:
        out = super().end_to_end(window_s)
        done = sum(r["done"] for r in self.lane_records())
        print(f"open_synergy: {len(self.records)} dispatches, {done} "
              f"completed jobs in the window", file=sys.stderr)
        return out

    def check(self, dtype=None) -> dict:
        """The compared numbers, worst over a seed-drawn sample of the
        window's dispatches, and the pool cost table; with ``dtype`` the
        control's in the program's place."""
        stacks = ref_syn.solo_stacks(self.pool, self.cfg)
        worst = {"table_gap": ref_syn.table_gap(
            self.cfg, self.pool, self.synergy.pool_cost, dtype)}
        for rec in (lane for d in self.sampled.items for lane in d):
            sc = ref_syn.Scenario(self.cfg, self.pool, self.rate,
                                  rec["sseed"], rec, stacks=stacks)
            for k, v in sc.numbers(dtype).items():
                worst[k] = max(worst.get(k, 0), v)
        return worst


def _placement_fifo(engine, rec):
    """The admitted jobs are put into the lowest free contexts: each
    quantum's newcomers move, in arrival order, to the lowest contexts
    their residents leave free, and keep those contexts after."""
    slots, ring = rec["slots"], rec["ring"]
    quanta, _rows, c = slots.shape
    at = {}
    new_slots = np.zeros_like(slots)
    new_slots[:, 0] = -1
    new_slots[:, 2] = np.arange(c)
    new_ring = np.zeros_like(ring)
    new_ring[..., ref_syn.ref_open.APP] = -1
    new_ring[..., ref_syn.ref_open.PARTNER] = -1
    for q in range(quanta):
        jobs = slots[q, 0]
        held = {at[j] for j in jobs if j >= 0 and j in at}
        free = [x for x in range(c) if x not in held]
        for j in sorted(int(j) for j in jobs if j >= 0 and j not in at):
            at[j] = free.pop(0)
        pos = np.array([at[int(j)] if j >= 0 else -1 for j in jobs])
        for ctx in np.flatnonzero(jobs >= 0):
            mate = slots[q, 2, ctx]
            new_slots[q, :2, pos[ctx]] = slots[q, :2, ctx]
            new_slots[q, 2, pos[ctx]] = pos[mate] if mate != ctx else \
                pos[ctx]
            new_ring[q, pos[ctx]] = ring[q, ctx]
    slots[...] = new_slots
    ring[...] = new_ring


def _hint_dropped(engine, rec):
    """The newcomers' first ST rows are the uniform placeholder, not
    their profiled stacks."""
    slots, ring = rec["slots"], rec["ring"]
    for q in range(slots.shape[0]):
        before = slots[q - 1, 0] if q else np.full(slots.shape[2], -1)
        new = (slots[q, 0] >= 0) & (slots[q, 0] != before)
        ring[q, new, ref_syn.ref_open.ST] = 0.25


FAULTS = dict(open_device.FAULTS, placement_fifo=_placement_fifo,
              hint_dropped=_hint_dropped)
