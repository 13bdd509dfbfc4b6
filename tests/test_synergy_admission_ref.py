"""Synergy admission on the device engine against its plain reference.

The open rack of ``bench/configs/rack8-open-synergy.json`` cut to 2
servers of 16 cores and 24 quanta at rho 1.2: each server's batched lane
admits by synergy with profile hints, and ``bench/reference/synergy.py``
(numpy, float64, no import of the program) replays every placement,
newcomer hint, departure and machine quantum.  Each of the engine's
planted faults must turn the comparison false; the synergy counters ride
the telemetry ring without moving a trajectory; and a FIFO lane of the
``rack8-open`` rack still runs the single-lane FIFO graph's trajectory.
"""

import copy
import json
import os

import numpy as np
import pytest

from bench import run, traffic_gen
from bench.engines import open_synergy, program
from bench.reference import synergy as ref_syn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "open512.rho1.2-synergy"
SMALL = {"servers": 2, "n_cores": 16, "quanta_per_scenario": 24}
SEEDS = (2**33 + 17, 3_000_000_019)


def _resolved():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    r = run.resolve(spec, CELL)
    return dict(r["cfg"], **SMALL), r["traffic"], r["pool"], r["limits"]


def _passes(numbers, limits):
    return all(v <= limits[k] for k, v in numbers.items())


@pytest.fixture(scope="module")
def engine():
    cfg, traffic, pool, _limits = _resolved()
    eng = open_synergy.Engine(cfg, traffic, pool, SEEDS[0])
    eng.setup()
    return eng


@pytest.fixture(scope="module")
def runs(engine):
    """The sampled lane records of one dispatch per seed."""
    out = {}
    for seed in SEEDS:
        engine.reset(seed)
        engine.step()
        out[seed] = [lane for d in engine.sampled.items for lane in d]
    return out


def _numbers(engine, lanes):
    engine.sampled.items = [lanes]
    return engine.check()


@pytest.mark.parametrize("seed", SEEDS)
def test_device_synergy_matches_reference(engine, runs, seed):
    _cfg, _t, _p, limits = _resolved()
    numbers = _numbers(engine, runs[seed])
    assert numbers["placement_mismatch"] == 0
    assert numbers["hint_gap"] <= limits["hint_gap"]
    assert _passes(numbers, limits), numbers
    # A backlog forms: some quantum leaves jobs waiting.
    assert any(np.max(lane["tlm"][:, 2]) > 0 for lane in runs[seed])


@pytest.mark.parametrize("fault", sorted(open_synergy.FAULTS))
def test_fault_turns_correct_false(engine, runs, fault):
    _cfg, _t, _p, limits = _resolved()
    lanes = copy.deepcopy(runs[SEEDS[0]])
    for lane in lanes:
        open_synergy.FAULTS[fault](engine, lane)
    assert not _passes(_numbers(engine, lanes), limits)


def _sims(cfg, pool, admission, synergy=None):
    from repro.online import ClusterSim, PoissonArrivals
    from repro.smt.machine import PhaseTables, SMTMachine

    profiles = program.profiles(pool)
    tables = PhaseTables.build(profiles)
    machine = SMTMachine(program.machine_params(cfg), seed=0)
    spec = program.scan_policy("synpa", cfg, name="t")
    arrivals = PoissonArrivals(rate=traffic_gen.arrival_rate(cfg, 1.2),
                               n_pool=len(profiles))
    return [ClusterSim(machine, profiles, int(cfg["n_cores"]), spec,
                       arrivals, seed=s, target_scale=cfg["target_scale"],
                       tables=tables, engine="scan", admission=admission,
                       synergy=synergy)
            for s in traffic_gen.lane_seeds(SEEDS[1], 0, 2)]


def _trajectory(stats):
    return [(np.array([r.admit_q for r in s.completed]),
             np.array([r.finish_q for r in s.completed]),
             np.asarray(s.queue_depth), np.asarray(s.active)) for s in stats]


@pytest.mark.parametrize("admission", ["synergy", "fifo"])
def test_telemetry_leaves_trajectories_bit_identical(engine, admission):
    """Telemetry (with the synergy counters) on or off, the lanes run the
    same trajectory; the counters read what the reference replays."""
    from repro.obs.telemetry import OPEN_FIELDS
    from repro.online.batch_sim import run_device_sim_batched

    cfg, _t, pool, _l = _resolved()
    syn = engine.synergy if admission == "synergy" else None
    off = run_device_sim_batched(_sims(cfg, pool, admission, syn), 24,
                                 warmup=False)
    on = run_device_sim_batched(_sims(cfg, pool, admission, syn), 24,
                                warmup=False, app_telemetry=True,
                                slot_log=True)
    for a, b in zip(_trajectory(off), _trajectory(on)):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
    trips = OPEN_FIELDS.index("syn_trips")
    beside = OPEN_FIELDS.index("syn_beside")
    for sseed, s in zip(traffic_gen.lane_seeds(SEEDS[1], 0, 2), on):
        ring = s.telemetry.data
        if admission == "fifo":
            assert not ring[:, trips].any()
            continue
        np.testing.assert_array_equal(ring[:, trips], s.admissions)
        rec = {"ring": s.app_telemetry.data, "slots": s.slot_log,
               "admissions": s.admissions, "n_arrived": s.n_arrived,
               "jobs": [{"job_id": r.job_id, "admit_q": r.admit_q,
                         "finish_q": r.finish_q} for r in s.completed]}
        sc = ref_syn.Scenario(cfg, pool, engine.rate, sseed, rec,
                              stacks=ref_syn.solo_stacks(pool, cfg))
        assert sc.placements() == 0
        np.testing.assert_array_equal(ring[:, beside], sc.beside)


def test_fifo_lanes_run_the_single_lane_fifo_graph():
    """A ``rack8-open`` FIFO lane, batched with the bench engine's rings
    on, runs the trajectory of the single-lane FIFO graph, which four-row
    masks and no synergy loop keep as it was."""
    from repro.online.batch_sim import run_device_sim_batched
    from repro.online.device_sim import run_device_sim

    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    r = run.resolve(spec, "open512.synpa4.rho1")
    cfg = dict(r["cfg"], servers=2, n_cores=16)
    batched = run_device_sim_batched(_sims(cfg, r["pool"], "fifo"), 24,
                                     warmup=False, app_telemetry=True)
    single = [run_device_sim(s, 24, warmup=False)
              for s in _sims(cfg, r["pool"], "fifo")]
    for a, b in zip(_trajectory(batched), _trajectory(single)):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
