"""Reference checks of an open-pool scenario run on the device engine.

The device engine hands back, per scenario: every completed job (arrival,
admission and fractional finish quantum, app), the per-quantum admission
and active-context counts, and per context per quantum its occupant's app,
its co-runner's app, the predicted and true slowdown and the ST stacks.
The reference recomputes from the configuration alone:

* ``arrival_mismatch``   the Poisson arrival stream (count per quantum and
                         each completed job's app), from the scenario seed
                         as the system documents it: ``default_rng(seed +
                         4242)``, per quantum ``poisson(rate)`` arrivals
                         drawn uniformly from the pool (exact, limit 0);
* ``admission_mismatch`` first come, first served into the lowest free
                         contexts: per quantum, ``min(queue, free)`` jobs
                         admitted, each into the context that then shows
                         its app (exact, limit 0);
* ``machine_gap``        each active context's true slowdown next to its
                         co-runner's app, relative;
* ``finish_gap``         each completed job's finish quantum, from the
                         instructions it retires in its context quantum by
                         quantum against its run-to-target goal (section
                         6.2), in quanta;
* ``cost_gap``           each co-running context's predicted slowdown
                         against Eq. 4 on its own and a co-runner's ST
                         stacks (the closest context that hosts the
                         co-runner's app and names this app back), relative.

Departures and pairings are the program's: each check takes the others'
outputs as given, so one disagreement does not cascade.  Within a
scenario no app leaves its first phase: every first phase lasts at least
``quanta_per_scenario + 1`` quanta (checked), so phase draws play no part.
"""

from __future__ import annotations

import numpy as np

from bench.reference import smt

APP, PARTNER, PRED, REAL = 0, 1, 2, 3
ST = slice(5, 9)


def arrivals(rate: float, n_pool: int, quanta: int, sseed: int):
    """(arrive_q, pool row) of every job of the scenario, in job order."""
    rng = np.random.default_rng(sseed + 4242)
    qs, pids = [], []
    for q in range(quanta):
        k = int(rng.poisson(rate))
        if k:
            pids.extend(int(x) for x in rng.choice(n_pool, size=k))
            qs.extend([q] * k)
    return np.asarray(qs, np.int64), np.asarray(pids, np.int64)


def solo_rates(pool: dict, machine: dict) -> np.ndarray:
    """Instructions each pool app retires per quantum alone, averaged over
    its phases by their mean lengths."""
    cycles = machine["freq_hz"] * machine["quantum_s"]
    t = smt.PoolTables.from_pool(pool, range(len(pool["apps"])))
    out = []
    for a, app in enumerate(pool["apps"]):
        n = len(app["phases"])
        rate = cycles / t.comps[a, :n].sum(-1) * t.retire[a]
        out.append((rate * t.duration[a, :n]).sum() / t.duration[a, :n].sum())
    return np.asarray(out)


def targets(pool: dict, machine: dict, target_scale: float) -> np.ndarray:
    """Run-to-target goal of each pool app: what it retires alone in the
    solo reference period, scaled."""
    quanta = round(machine["solo_reference_s"] / machine["quantum_s"])
    return solo_rates(pool, machine) * quanta * target_scale


class Scenario:
    """One scenario's reference, over the program's outputs ``out``:
    ``ring`` (Q, C, fields), ``jobs`` completed (job_id, arrive_q,
    admit_q, finish_q, app row), ``admissions`` and ``active`` (Q,),
    ``n_arrived``."""

    def __init__(self, cfg: dict, pool: dict, rate: float, sseed: int,
                 out: dict, dtype=np.float64):
        self.cfg, self.pool, self.out, self.dtype = cfg, pool, out, dtype
        self.quanta = int(cfg["quanta_per_scenario"])
        self.c = 2 * int(cfg["n_cores"])
        first = min(a["phases"][0]["duration"] for a in pool["apps"])
        if first <= self.quanta:
            raise ValueError("an app would leave its first phase")
        self.arrive_q, self.pids = arrivals(rate, len(pool["apps"]),
                                            self.quanta, sseed)
        self.t = smt.PoolTables.from_pool(pool, range(len(pool["apps"])))
        self.goal = targets(pool, cfg["machine"], cfg["target_scale"])
        ring = out["ring"]
        self.app = np.rint(ring[..., APP]).astype(np.int64)
        self.mate = np.rint(ring[..., PARTNER]).astype(np.int64)

    def corun(self, app, mate, dt):
        """True slowdown and retired instructions per context of apps
        ``app`` next to apps ``mate`` (-1: alone), all in phase 0."""
        n = len(app)
        tab = smt.PoolTables.from_pool(
            self.pool, np.concatenate([app, np.where(mate < 0, app, mate)]))
        partner = np.where(mate < 0, np.arange(n), np.arange(n) + n)
        partner = np.concatenate([partner, np.arange(n, 2 * n)])
        comps, solo = smt.corun(tab, self.cfg["machine"],
                                np.zeros(2 * n, np.int64), partner, dt)
        cpi = comps.sum(-1)[:n]
        cycles = dt(self.cfg["machine"]["freq_hz"]
                    * self.cfg["machine"]["quantum_s"])
        retired = cycles / cpi * tab.retire[:n].astype(dt)
        return cpi / solo.sum(-1)[:n], retired

    def arrival_mismatch(self) -> int:
        out = self.out
        bad = abs(out["n_arrived"] - len(self.pids))
        for j in out["jobs"]:
            jid = j["job_id"]
            if jid >= len(self.pids) or self.pids[jid] != j["app"] or \
                    self.arrive_q[jid] != j["arrive_q"]:
                bad += 1
        return int(bad)

    def placements(self):
        """Per job admitted in the scenario, its context; and the count of
        admissions that break the first-come, first-served rule."""
        out, c = self.out, self.c
        fin = {j["job_id"]: j["finish_q"] for j in out["jobs"]}
        occupied = np.zeros(c, bool)
        ctx_job = np.full(c, -1)
        head, bad, where = 0, 0, {}
        for q in range(self.quanta):
            arrived = int((self.arrive_q <= q).sum())
            free = np.flatnonzero(~occupied)
            k = min(arrived - head, len(free))
            bad += abs(k - int(out["admissions"][q]))
            for n, ctx in enumerate(free[:k]):
                jid = head + n
                if self.app[q, ctx] != self.pids[jid]:
                    bad += 1
                occupied[ctx], ctx_job[ctx], where[jid] = True, jid, (ctx, q)
            head += k
            if int(occupied.sum()) != int(out["active"][q]):
                bad += 1
            for ctx in np.flatnonzero(occupied):
                f = fin.get(int(ctx_job[ctx]))
                if f is not None and int(np.floor(f)) == q:
                    occupied[ctx], ctx_job[ctx] = False, -1
        for j in out["jobs"]:
            if where.get(j["job_id"], (None, None))[1] != j["admit_q"]:
                bad += 1
        return where, int(bad)

    def machine(self, dtype):
        """True slowdown and retired instructions (Q, C) of the program's
        occupants and co-runner apps, in ``dtype``."""
        real = np.zeros((self.quanta, self.c))
        retired = np.zeros((self.quanta, self.c))
        for q in range(self.quanta):
            act = self.app[q] >= 0
            r, ret = self.corun(self.app[q][act], self.mate[q][act], dtype)
            real[q][act], retired[q][act] = r, ret
        return real, retired

    def numbers(self, dtype=None) -> dict:
        """The compared numbers.  With ``dtype`` the control's slowdowns,
        finish quanta and predictions (the reference in that precision)
        take the program's place."""
        out = self.out
        where, adm_bad = self.placements()
        real_ref, ret_ref = self.machine(np.float64)
        real, finish = out["ring"][..., REAL], {
            j["job_id"]: j["finish_q"] for j in out["jobs"]}
        if dtype is not None:
            real, ret = self.machine(dtype)
        act = self.app >= 0
        machine_gap = float(np.max(np.abs(real[act] - real_ref[act])
                                   / real_ref[act]))
        finish_gap = 0.0
        for j in out["jobs"]:
            ctx, q0 = where.get(j["job_id"], (None, None))
            if ctx is None:
                continue
            goal = self.goal[j["app"]]
            f_ref = self.finish(ret_ref[:, ctx], q0, goal)
            got = finish[j["job_id"]] if dtype is None else self.finish(
                ret[:, ctx].astype(dtype), q0, dtype(goal))
            finish_gap = max(finish_gap, abs(float(got) - f_ref))
        return {"arrival_mismatch": self.arrival_mismatch(),
                "admission_mismatch": adm_bad,
                "machine_gap": machine_gap, "finish_gap": finish_gap,
                "cost_gap": self.cost_gap(dtype)}

    @staticmethod
    def finish(retired, q0: int, goal) -> float:
        """Fractional quantum at which a job admitted at ``q0`` reaches its
        goal retiring ``retired[q]`` per quantum (inf if it does not), in
        the arrays' precision."""
        done = retired[:0].sum()
        for q in range(q0, len(retired)):
            if done + retired[q] >= goal:
                return q + min(max((goal - done) / retired[q], 0.0), 1.0)
            done += retired[q]
        return float("inf")

    def cost_gap(self, dtype=None) -> float:
        """Each co-running context's predicted slowdown against Eq. 4 on its
        ST stacks and those of the context that hosts its co-runner's app,
        names its app back, and fits its prediction best; relative.  With
        ``dtype`` the control's prediction (Eq. 4 in that precision on the
        same two stacks) takes the program's place."""
        coef = np.asarray(self.cfg["policy_model"]["coeffs"], np.float64)
        st = self.out["ring"][..., ST].astype(np.float64)
        pred = self.out["ring"][..., PRED]
        worst = 0.0
        for q in range(1, self.quanta):
            co = np.flatnonzero(self.mate[q] >= 0)
            for a in np.unique(self.app[q][co]):
                me = co[self.app[q][co] == a]
                for b in np.unique(self.mate[q][me]):
                    rows = me[self.mate[q][me] == b]
                    cand = co[(self.app[q][co] == b) & (self.mate[q][co] == a)]
                    if len(cand) == 0:
                        return float("inf")
                    want = smt.pair_cost(coef, st[q][rows][:, None, :],
                                         st[q][cand][None, :, :]) / 2
                    best = np.abs(pred[q][rows][:, None] - want).argmin(1)
                    want = want[np.arange(len(rows)), best]
                    got = pred[q][rows]
                    if dtype is not None:
                        got = smt.pair_cost(
                            coef.astype(dtype), st[q][rows].astype(dtype),
                            st[q][cand[best]].astype(dtype)) / 2
                    gap = np.abs(np.asarray(got, np.float64) - want) / want
                    worst = max(worst, float(gap.max()))
        return worst
