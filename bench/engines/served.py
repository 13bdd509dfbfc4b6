"""Served allocator: one caller, one decision per simulated quantum.

The host ``ClusterSim`` (numpy machine, Poisson arrivals, FIFO admission,
run-to-target departures) of one server of the rack is the load
generator; the system under test is the allocator it calls every quantum,
``StreamingAllocator`` with the device matcher, as the server's T2C daemon
would be called: a closed loop.  The rack's other servers run daemons of
their own, alike and independent, so one stands for all.
Each timed call is one decision, from counters in to pairs out, on the
caller's clock.  A window runs whole scenarios back to back, a fresh seed
(apps, arrivals, machine noise) each.
"""

from __future__ import annotations

import time

import numpy as np

from bench import traffic_gen
from bench.engines import program
from bench.reference import served as ref_served

#: Decisions whose inputs and outputs the check keeps: each one with this
#: chance, drawn from the run's seed.
SAMPLE_SHARE = 0.1


class _Timed:
    """The allocator behind the caller's timer; keeps a seed-drawn sample
    of decisions (inputs, pairs and the allocator's ST estimates)."""

    def __init__(self, inner, engine):
        self.inner, self.engine = inner, engine
        self.name = inner.name

    def reset(self, machine, rng) -> None:
        self.inner.reset(machine, rng)

    def pair(self, q, active, counters, ran, arrived, departed, prev_pairs,
             prev_solo, **kw):
        import jax

        e = self.engine
        with jax.profiler.TraceAnnotation("bench.decision"):
            t0 = time.perf_counter()
            pairs, solo = self.inner.pair(q, active, counters, ran, arrived,
                                          departed, prev_pairs, prev_solo,
                                          **kw)
            e.decision_s.append(time.perf_counter() - t0)
        if e.keep.random() < SAMPLE_SHARE or e.alter is not None:
            d = {"first": not prev_pairs and prev_solo is None,
                 "counters": np.array(counters, np.float64),
                 "active": np.array(active), "ran": np.array(ran),
                 "arrived": list(arrived),
                 "prev_pairs": [tuple(p) for p in prev_pairs],
                 "prev_solo": prev_solo, "pairs": [tuple(p) for p in pairs],
                 "solo": solo, "st": np.array(self.inner._st)}
            if e.alter is not None:
                e.alter(d)
            e.kept.append(d)
        return pairs, solo


class Engine:
    def __init__(self, cfg: dict, traffic: dict, pool: dict, seed: int):
        self.cfg, self.traffic, self.pool, self.seed = cfg, traffic, pool, seed
        self.cores = int(cfg["n_cores"])
        self.quanta = int(cfg["quanta_per_scenario"])
        self.alter = None
        self.reset(seed)

    def reset(self, seed: int) -> None:
        self.seed = seed
        self.scenarios, self.decision_s, self.kept, self.slowdowns = 0, [], [], []
        self.keep = np.random.default_rng(traffic_gen.scenario_seed(seed, -3))

    def setup(self) -> None:
        from repro.online import PoissonArrivals
        from repro.online.allocator import StreamingAllocator, StreamingConfig
        from repro.smt.machine import PhaseTables, SMTMachine

        t0 = time.perf_counter()
        self.profiles = program.profiles(self.pool)
        self.tables = PhaseTables.build(self.profiles)
        self.machine = SMTMachine(program.machine_params(self.cfg), seed=0)
        self.arrivals = PoissonArrivals(
            rate=traffic_gen.arrival_rate(self.cfg,
                                          self.traffic["rho"]),
            n_pool=len(self.profiles))
        self.policy = _Timed(StreamingAllocator(
            program.method(self.cfg), program.model(self.cfg),
            StreamingConfig(matcher=self.traffic["matcher"])), self)
        t1 = time.perf_counter()
        self._scenario(-1)
        self.reset(self.seed)
        self.parts = {"tables_s": t1 - t0, "warm_s": time.perf_counter() - t1}

    def _scenario(self, index: int):
        import jax

        from repro.online import ClusterSim

        sim = ClusterSim(self.machine, self.profiles, self.cores, self.policy,
                         self.arrivals,
                         seed=traffic_gen.scenario_seed(self.seed, index),
                         target_scale=self.cfg["target_scale"],
                         tables=self.tables)
        with jax.profiler.TraceAnnotation("bench.scenario"):
            return sim.run(self.quanta)

    def step(self) -> None:
        stats = self._scenario(self.scenarios)
        self.scenarios += 1
        self.slowdowns.extend(stats.slowdowns.tolist())

    def end_to_end(self, window_s: float) -> dict:
        ms = np.asarray(self.decision_s) * 1e3
        return {"decision_ms_p95": float(np.percentile(ms, 95)),
                "decision_ms_p50": float(np.percentile(ms, 50)),
                "slowdown_mean": float(np.mean(self.slowdowns))}

    def attempted(self) -> int:
        return len(self.decision_s)

    def failed(self) -> int:
        return 0

    def layer_data(self) -> dict:
        return {"decision_ms": np.asarray(self.decision_s) * 1e3,
                "contexts": 2 * self.cores}

    def release(self) -> None:
        self.policy = None

    def check(self, dtype=None) -> dict:
        coef = np.asarray(self.cfg["policy_model"]["coeffs"])
        worst = {"bad_pairing": 0, "swap_gain": 0.0}
        rows = [np.zeros(0)]
        for d in self.kept:
            if d["first"]:
                worst["bad_pairing"] += ref_served.bad_pairing(d)
                continue
            nums = ref_served.compare(d, coef, dtype)
            worst["bad_pairing"] += nums["bad_pairing"]
            worst["swap_gain"] = max(worst["swap_gain"], nums["swap_gain"])
            rows.append(nums["st_rows"])
        rows = np.concatenate(rows)
        if rows.size:
            worst.update(st_gap=float(rows.max()),
                         st_gap_p50=float(np.median(rows)))
        return worst


def _state_unchanged(engine, d):
    """The allocator hands back its ST estimates unrefreshed."""
    if not d["first"]:
        d["st"][:] = 0.25


def _answer_altered(engine, d):
    """One pair of the decision is exchanged with another: a valid pairing
    that is not the one the allocator chose."""
    if len(d["pairs"]) >= 2:
        (a, b), (c, e) = d["pairs"][0], d["pairs"][1]
        d["pairs"][0], d["pairs"][1] = (a, e), (c, b)


def _half_batch(engine, d):
    """Half of the active contexts are left out of the pairing."""
    keep = len(d["pairs"]) // 2
    d["pairs"] = d["pairs"][:keep]


FAULTS = {"state_unchanged": _state_unchanged,
          "answer_altered": _answer_altered, "half_batch": _half_batch}
