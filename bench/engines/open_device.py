"""Open rack on the device engine: one scenario per server per dispatch.

Each server of the rack has its own arrival stream, FIFO queue and
allocator.  ``ClusterSim(engine="scan")`` describes one server's scenario
— Poisson arrivals, FIFO admission, the fused SYNPA step, the machine
quantum and run-to-target departures — and
``repro.online.batch_sim.run_device_sim_batched`` runs every server's as a
lane of one ``lax.scan`` dispatch, with its telemetry rings on so the
dispatch also hands back what the check compares.  The compiled race is
cached by the engine across dispatches of one shape; each server's
arrival count stays inside one power-of-two job bucket, so the window
never compiles (``compiles_in_window`` counts it if it did).
"""

from __future__ import annotations

import time

import numpy as np

from bench import traffic_gen
from bench.engines import program
from bench.reference import open as ref_open

#: Dispatches of a window whose outputs the check replays, every server's.
SAMPLES = 2


class Engine:
    def __init__(self, cfg: dict, traffic: dict, pool: dict, seed: int):
        self.cfg, self.traffic, self.pool = cfg, traffic, pool
        self.cores = int(cfg["n_cores"])
        self.lanes = int(cfg["servers"])
        self.quanta = int(cfg["quanta_per_scenario"])
        self.alter = None
        self.reset(seed)

    def reset(self, seed: int) -> None:
        self.seed, self.records = seed, []
        self.sampled = traffic_gen.Sample(seed, SAMPLES)

    def setup(self) -> None:
        from repro.online import PoissonArrivals
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
        self._dispatch(-1)
        self.parts = {"tables_s": t1 - t0, "warm_s": time.perf_counter() - t1}

    def _dispatch(self, index: int) -> list:
        from repro.online import ClusterSim
        from repro.online.batch_sim import run_device_sim_batched

        import jax

        sseeds = traffic_gen.lane_seeds(self.seed, index, self.lanes)
        sims = [ClusterSim(self.machine, self.profiles, self.cores, self.spec,
                           self.arrivals, seed=s,
                           target_scale=self.cfg["target_scale"],
                           tables=self.tables, engine="scan")
                for s in sseeds]
        # One span: the engine presamples arrivals, commits the inputs,
        # dispatches, fetches and rebuilds its job records inside.
        with jax.profiler.TraceAnnotation("bench.run_device_sim"):
            stats = run_device_sim_batched(sims, self.quanta, warmup=False,
                                           app_telemetry=True)
        names = {p.name: k for k, p in enumerate(self.profiles)}
        return [{"sseed": sseed, "n_arrived": s.n_arrived,
                 "jobs": [{"job_id": r.job_id, "arrive_q": r.arrive_q,
                           "admit_q": r.admit_q, "finish_q": r.finish_q,
                           "app": names[r.app_name]} for r in s.completed],
                 "slowdowns": s.slowdowns,
                 "admissions": np.array(s.admissions),
                 "active": np.array(s.active),
                 "tlm": np.array(s.telemetry.data),
                 "ring": np.array(s.app_telemetry.data)}
                for sseed, s in zip(sseeds, stats)]

    def step(self) -> None:
        lanes = self._dispatch(len(self.records))
        if self.alter is not None:
            for lane in lanes:
                self.alter(lane)
        self.sampled.offer(lanes)
        # Only the sampled dispatches keep their per-context ring.
        self.records.append([{k: v for k, v in lane.items() if k != "ring"}
                             for lane in lanes])

    def lane_records(self):
        return [lane for rec in self.records for lane in rec]

    def end_to_end(self, window_s: float) -> dict:
        lanes = self.lane_records()
        work = len(lanes) * 2 * self.cores * self.quanta
        slow = np.concatenate([r["slowdowns"] for r in lanes])
        return {"sim_rate": work / window_s,
                "slowdown_mean": float(np.mean(slow))}

    def attempted(self) -> int:
        return len(self.records)

    def failed(self) -> int:
        return 0

    def layer_data(self) -> dict:
        from repro.obs.telemetry import OPEN_FIELDS

        return {"telemetry": {
            "fields": OPEN_FIELDS,
            "rings": {self.traffic["policy"]: np.stack(
                [r["tlm"] for r in self.lane_records()])}},
            "contexts": 2 * self.cores * self.lanes}

    def release(self) -> None:
        from repro.online import batch_sim

        batch_sim._BATCH_CACHE.clear()

    def check(self, dtype=None) -> dict:
        """The compared numbers, worst over a seed-drawn sample of the
        window's dispatches; with ``dtype`` the control's in the
        program's place."""
        worst: dict = {}
        for rec in (lane for d in self.sampled.items for lane in d):
            sc = ref_open.Scenario(self.cfg, self.pool, self.rate,
                                   rec["sseed"], rec)
            for k, v in sc.numbers(dtype).items():
                worst[k] = max(worst.get(k, 0), v)
        return worst


def _state_unchanged(engine, rec):
    """Jobs never make progress past their first quantum: every finish is
    reported one quantum after admission."""
    for j in rec["jobs"]:
        j["finish_q"] = j["admit_q"] + 1.0


def _half_batch(engine, rec):
    """Half of the contexts are left out of the machine: their true
    slowdowns are reported as the other half's."""
    ring = rec["ring"]
    half = ring.shape[1] // 2
    ring[:, half:, ref_open.REAL] = ring[:, :half, ref_open.REAL]


def _answer_altered(engine, rec):
    """One completed job's answer is altered where it is produced: its
    finish quantum moves by a tenth of a quantum."""
    if rec["jobs"]:
        rec["jobs"][0]["finish_q"] += 0.1


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}
