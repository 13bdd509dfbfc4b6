"""Reference replay of a closed-pool race and the numbers compared.

The program hands back, for every quantum of a scenario, the pairing it
ran, its per-context ST-stack estimates, the predicted and true slowdown of
each context, and the retired instructions.  The replay recomputes the
machine under the program's pairings from the configuration alone, and the
SYNPA step from the replayed PMU counters:

* ``machine_gap``  true slowdown per context per quantum, relative;
* ``retired_gap``  instructions retired per context over the scenario;
* ``st_gap``       ST stacks of the Eq. 4 inverse, absolute, worst context
                   (the solver stops at a summed squared residual of 1e-4,
                   so this reads its stopping rule, not its precision);
* ``st_gap_p50``   the same, median context over the sample: reads the
                   precision;
* ``cost_gap``     predicted slowdown of each context's committed pair;
* ``swap_gain``    distance of the committed pairing from a 2-opt optimum
                   of the predicted costs;
* ``bad_pairing``  contexts not perfectly matched, or moved where the
                   policy may not move them (exact, limit 0).

Counter noise and phase lengths follow the system's documented stream
layout (``repro.smt.scan_engine``, stream version 2): machine key
``PRNGKey(seed)``; quantum q's noise is ``normal(fold_in(fold_in(key, q),
0), (N, 4))``, its phase draws ``poisson(fold_in(fold_in(key, q), 1))``.
The draws are data of the scenario, as the seed is.
"""

from __future__ import annotations

import numpy as np

from bench.reference import matching, smt

#: Pool rows of the ring's per-context fields (``APP_FIELDS`` order).
APP, PARTNER, PRED, REAL, RESID = 0, 1, 2, 3, 4
ST = slice(5, 9)


def draws(sseed: int, quanta: int, n: int, duration_of):
    """Noise (Q, N, 4) and phase index (Q, N) of a scenario's machine.

    ``duration_of(phase)`` gives each context's mean length of the given
    phase; lengths are drawn only when a context changes phase.
    """
    import jax

    key = jax.random.PRNGKey(sseed)
    noise = np.zeros((quanta, n, 4), np.float32)
    phase = np.zeros((quanta, n), np.int64)
    ph = np.zeros(n, np.int64)
    left = duration_of(ph).astype(np.float32)
    for q in range(quanta):
        kq = jax.random.fold_in(key, q)
        noise[q] = np.asarray(jax.random.normal(jax.random.fold_in(kq, 0),
                                                (n, 4), np.float32))
        phase[q] = ph
        left = left - np.float32(1.0)
        trans = left <= 0
        if trans.any():
            lam = duration_of(ph + trans).astype(np.float32)
            d = np.asarray(jax.random.poisson(jax.random.fold_in(kq, 1), lam,
                                              (n,))).astype(np.float32)
            left = np.where(trans, np.maximum(d, 1.0), left)
        ph = ph + trans
    return noise, phase


def partners_of(app_ring: np.ndarray) -> np.ndarray:
    """(Q, N) partner index per quantum from the ring (solo -> self)."""
    p = np.rint(app_ring[..., PARTNER]).astype(np.int64)
    idx = np.broadcast_to(np.arange(p.shape[-1]), p.shape)
    return np.where(p < 0, idx, p)


class Replay:
    """The machine of one scenario under given pairings, in ``dtype``."""

    def __init__(self, tables: smt.PoolTables, machine: dict, noise, phase,
                 dtype=np.float64):
        self.t, self.m = tables, machine
        self.noise, self.phase, self.dtype = noise, phase, dtype

    def run(self, partners):
        """True slowdown (Q, N), retired instructions (N,), the summed
        per-quantum mean slowdown, and the measured ISC stacks (Q, N, 4)."""
        dt = self.dtype
        cycles = dt(self.m["freq_hz"] * self.m["quantum_s"])
        real, stacks = [], []
        retired = np.zeros(len(partners[0]), dt)
        for q, partner in enumerate(partners):
            comps, solo = smt.corun(self.t, self.m, self.phase[q], partner, dt)
            cpi = comps.sum(-1)
            real.append(cpi / solo.sum(-1))
            retired = retired + cycles / cpi * self.t.retire.astype(dt)
            ctr = smt.counters(self.t, self.m, comps, self.noise[q], dt)
            stacks.append(smt.isc4_febe(ctr))
        real = np.stack(real)
        return real, retired, real.astype(np.float64).mean(-1).sum(), \
            np.stack(stacks)


#: A pair's measured stacks are consistent with Eq. 4 when some ST pair on
#: the simplex reproduces them to this summed squared fraction residual
#: (1e-3 per category).  Above it no ST stacks explain the counters, the
#: inverse has no root but a ridge of near-equal residuals, and any point
#: on it is as right as another: such pairs are left out of ``st_gap``.
CONSISTENT = 1e-6


def solve_pairs(coef, stacks, partner, dtype=np.float64):
    """ST estimates (N, 4) of every co-running pair under ``partner`` and
    the residual (N,) of its solve (NaN for contexts that ran alone)."""
    n = len(partner)
    i = np.flatnonzero(np.arange(n) < partner)
    j = partner[i]
    x, y, res = smt.inverse(np.asarray(coef, dtype),
                            stacks[i].astype(dtype), stacks[j].astype(dtype))
    st = np.full((n, 4), np.nan)
    r = np.full(n, np.nan)
    st[i], st[j] = x, y
    r[i] = r[j] = res
    return st, r


def pred_of(coef, st, partner, dtype=np.float64):
    """Predicted slowdown of each context's pair, halved (the ring's
    ``pred_cost`` column): (s(i|j) + s(j|i)) / 2."""
    st = np.asarray(st, dtype)
    return smt.pair_cost(np.asarray(coef, dtype), st, st[partner]) / 2


def relgap(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.abs(want)))


def compare(out: dict, ref: dict, coef) -> dict:
    """The numbers of one arm of one scenario (``st_rows``: the per-context
    ST gaps, for ``st_gap`` and ``st_gap_p50`` over the sample).  ``out`` holds what the
    program (or the control, in its place) produced; ``ref`` the float64
    replay.  Both give ``real``, ``retired``, ``slow`` and, for a SYNPA
    arm, ``st`` (Q, N, 4) and ``pred`` (Q, N) from quantum 1 on."""
    nums = {
        "machine_gap": relgap(out["real"], ref["real"]),
        "retired_gap": relgap(out["retired"], ref["retired"]),
        "slowdown_gap": relgap(out["slow"], ref["slow"]),
    }
    if "st" in ref:
        rows, cost_gap, gain = [], 0.0, 0.0
        for q in range(1, len(ref["st"])):
            ok = ref["res"][q] < CONSISTENT
            rows.append(np.abs(out["st"][q][ok] - ref["st"][q][ok]).max(-1))
            partner = out["partners"][q]
            want = pred_of(coef, out["st"][q], partner)
            cost_gap = max(cost_gap, relgap(out["pred"][q], want))
            cost = smt.pair_cost(np.asarray(coef, np.float64),
                                 out["st"][q][:, None, :].astype(np.float64),
                                 out["st"][q][None, :, :].astype(np.float64))
            gain = max(gain, matching.swap_gain(cost, partner))
        nums.update(st_rows=np.concatenate(rows), cost_gap=cost_gap,
                    swap_gain=gain)
    return nums
