"""The one traffic generator: turns a traffic file's parameters and the
run's ``--seed`` into scenarios.  Every size is fixed by the files, and the
seed only picks the order of the apps and the noise and arrival streams a
scenario gets, so two seeds ask the same amount of work of the system.

A dispatch runs one scenario on each server of the rack (a lane), each
with a seed of its own.  A closed scenario is one server's ``n_apps``
apps: the same multiset on every server and in every scenario (the pool's
apps cycled to ``n_apps``), in an order drawn from the scenario seed, which
also keys the machine's counter noise and the policies' own draws.  An
open scenario is the pool, one server's arrival rate and a scenario seed;
the system pre-samples its Poisson arrivals from that seed
(``benchmarks/online_churn.py`` sets the rate the same way: ``rho *
contexts / mean service quanta``).
"""

from __future__ import annotations

import numpy as np

#: Scenario seeds stay below this so that ``seed + 7919`` (the policies'
#: stream) and ``seed + 6007`` still fit a signed 32-bit PRNG seed.
SEED_SPAN = 2**31 - 2**16


def scenario_seed(run_seed: int, index: int, lane: int = 0) -> int:
    """Seed of scenario ``index`` of a run on server ``lane`` (indices
    from -16 on; negative ones are the warm-up's and the samplers')."""
    seq = np.random.SeedSequence([run_seed % 2**32, run_seed // 2**32,
                                  index + 16, lane])
    return int(seq.generate_state(1, np.uint64)[0] % SEED_SPAN)


def lane_seeds(run_seed: int, index: int, lanes: int) -> list:
    """Scenario seeds of the ``lanes`` servers of dispatch ``index``."""
    return [scenario_seed(run_seed, index, lane) for lane in range(lanes)]


def closed_picks(pool: dict, n_apps: int, sseed: int) -> np.ndarray:
    """Pool rows of a closed scenario's ``n_apps`` contexts: the pool's
    apps cycled to ``n_apps``, in an order drawn from ``sseed``."""
    rng = np.random.default_rng(sseed)
    return rng.permutation(np.resize(np.arange(len(pool["apps"])), n_apps))


def arrival_rate(cfg: dict, rho: float) -> float:
    """Arrivals per quantum that offer ``rho`` times an open pool's
    capacity: ``rho * contexts / service``, where a job's expected service
    is its solo quanta under the scaled section 6.2 target times the
    typical SMT slowdown (the mapping of ``benchmarks/online_churn.py``)."""
    m = cfg["machine"]
    service = (round(m["solo_reference_s"] / m["quantum_s"])
               * cfg["target_scale"] * cfg["service_slowdown"])
    return rho * 2 * cfg["n_cores"] / service


class Sample:
    """A uniform sample of ``k`` items of a stream of unknown length,
    drawn from the run's seed (reservoir sampling): which of a window's
    dispatches the check replays."""

    def __init__(self, run_seed: int, k: int):
        self.rng = np.random.default_rng(scenario_seed(run_seed, -2))
        self.k, self.seen, self.items = k, 0, []

    def offer(self, item) -> None:
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.k:
                self.items[j] = item
        self.seen += 1
