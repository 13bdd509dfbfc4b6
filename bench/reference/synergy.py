"""Reference checks of an open-pool scenario under synergy admission.

The device engine hands back what ``bench/reference/open.py`` reads (the
completed jobs, the per-quantum admission and active counts, the
per-context ring) and a slot log: per quantum and context the occupant's
job id, its phase index and its machine partner's context.  From the
configuration alone the reference recomputes:

* the profiled solo stack of each pool app: ``syn_profile_quanta``
  noiseless solo quanta measured as SYNPA4_R-FEBE stacks and averaged; an
  app leaves a phase after its mean length the first time and after a
  ``poisson(mean)`` draw (at least 1) afterwards, drawn from one
  ``default_rng(syn_profile_seed)`` stream shared by the apps in pool order;
* the Eq. 4 pool cost matrix over those stacks, same-app pairs included,
  and each app's mean cost against the other apps (an empty core's score);
* ``table_gap``          the program's pool cost matrix, relative;
* ``placement_mismatch`` each quantum's admissions replayed in arrival
  order against the program's occupants after departures: each job to the
  free context whose core-mate (``slot ^ 1``) gives the lowest pair cost,
  an empty core scoring the mean cost, ties to the lowest slot.  The
  program's departures and earlier placements are taken as given, so one
  disagreement does not cascade.  Where the reference's best and
  second-best costs for the job differ by under ``TIE`` relative, either
  placement counts as consistent: the program scores in float32, where
  such costs can round to one value.  The slot log's own rules count
  too: occupants stay in their context from admission to departure, the
  newcomers are the next jobs of the queue, as many as it and the free
  contexts allow, each context shows its occupant's app, and a job's first
  phase lasts its mean length (exact, limit 0);
* ``hint_gap``           each newcomer's ST estimate in its admission
  quantum against its app's profiled stack, over the stack's total;
* ``arrival_mismatch``, ``machine_gap``, ``finish_gap`` and ``cost_gap``
  as in ``open.py``, the machine with each context's and its partner's
  phase from the slot log (the phase draws after the first are the
  program's device stream, taken as given).
"""

from __future__ import annotations

import numpy as np

from bench.reference import open as ref_open
from bench.reference import smt

#: Relative cost difference under which two placements count as a tie.
TIE = 1e-5
JOB, PHASE, PARTNER = 0, 1, 2


def solo_stacks(pool: dict, cfg: dict, dtype=np.float64) -> np.ndarray:
    """(A, 4) profiled solo SYNPA4_R-FEBE stack of each pool app."""
    m = cfg["machine"]
    quanta = int(cfg["syn_profile_quanta"])
    rng = np.random.default_rng(int(cfg["syn_profile_seed"]))
    t = smt.PoolTables.from_pool(pool, range(len(pool["apps"])))
    out = []
    for a, app in enumerate(pool["apps"]):
        durs = [ph["duration"] for ph in app["phases"]]
        phase, left, rows = 0, float(durs[0]), []
        for _ in range(quanta):
            rows.append(phase % len(durs))
            left -= 1.0
            if left <= 0.0:
                phase += 1
                left = float(max(1, rng.poisson(durs[phase % len(durs)])))
        one = smt.PoolTables.from_pool(pool, [a] * quanta)
        comps = t.comps[a, rows].astype(dtype)
        ctr = smt.counters(one, m, comps, np.zeros((quanta, 4)), dtype)
        out.append(smt.isc4_febe(ctr, m["width"]).mean(0))
    return np.asarray(out)


def pool_costs(coef, stacks):
    """(A, A) Eq. 4 pair cost of every two pool apps, and (A,) each app's
    mean over the other apps, in the stacks' precision."""
    dtype = stacks.dtype.type
    cost = smt.pair_cost(coef.astype(dtype), stacks[:, None, :],
                         stacks[None, :, :])
    off = ~np.eye(len(stacks), dtype=bool)
    mean = np.array([cost[k][off[k]].mean() for k in range(len(stacks))],
                    dtype)
    return cost, mean


def table_gap(cfg: dict, pool: dict, program_cost, dtype=None) -> float:
    """The program's pool cost matrix against the reference's, relative;
    with ``dtype`` the control's matrix takes the program's place."""
    coef = np.asarray(cfg["policy_model"]["coeffs"], np.float64)
    want, _m = pool_costs(coef, solo_stacks(pool, cfg))
    got = np.asarray(program_cost, np.float64)
    if dtype is not None:
        got = pool_costs(coef, solo_stacks(pool, cfg, dtype))[0]
    return float(np.max(np.abs(np.asarray(got, np.float64) - want) / want))


class Scenario(ref_open.Scenario):
    """One synergy scenario's reference over the program's outputs ``out``:
    those of ``open.Scenario`` plus ``slots`` (Q, 3, C)."""

    def __init__(self, cfg: dict, pool: dict, rate: float, sseed: int,
                 out: dict, stacks=None):
        self.cfg, self.pool, self.out = cfg, pool, out
        self.quanta = int(cfg["quanta_per_scenario"])
        self.c = 2 * int(cfg["n_cores"])
        self.arrive_q, self.pids = ref_open.arrivals(
            rate, len(pool["apps"]), self.quanta, sseed)
        self.t = smt.PoolTables.from_pool(pool, range(len(pool["apps"])))
        self.goal = ref_open.targets(pool, cfg["machine"],
                                     cfg["target_scale"])
        ring = out["ring"]
        self.app = np.rint(ring[..., ref_open.APP]).astype(np.int64)
        self.mate = np.rint(ring[..., ref_open.PARTNER]).astype(np.int64)
        self.job = np.asarray(out["slots"][:, JOB], np.int64)
        self.phase = np.asarray(out["slots"][:, PHASE], np.int64)
        self.partner = np.asarray(out["slots"][:, PARTNER], np.int64)
        # A job id the arrivals do not have reads as pool row 0 here and
        # counts in arrival_mismatch.
        self.app_of = np.append(self.pids, 0)[
            np.clip(self.job, 0, len(self.pids))]
        self.coef = np.asarray(cfg["policy_model"]["coeffs"], np.float64)
        self.stacks = solo_stacks(pool, cfg) if stacks is None else stacks
        self.cost, self.mean = pool_costs(self.coef, self.stacks)
        self.first = np.array([a["phases"][0]["duration"]
                               for a in pool["apps"]])
        self.beside = np.zeros(self.quanta, np.int64)

    def newcomers(self, q: int) -> np.ndarray:
        """Contexts whose occupant at quantum ``q`` was placed there at
        ``q``."""
        now = self.job[q]
        before = self.job[q - 1] if q else np.full(self.c, -1)
        return np.flatnonzero((now >= 0) & (now != before))

    def placements(self, dtype=None) -> int:
        """Placement and slot-log disagreements of the scenario; with
        ``dtype`` the control's placements (the rule on costs in that
        precision) take the program's place.  Counts the placements beside
        an occupied core-mate per quantum in ``beside``."""
        fin = {j["job_id"]: j["finish_q"] for j in self.out["jobs"]}
        admit = {j["job_id"]: j["admit_q"] for j in self.out["jobs"]}
        cost, mean = self.cost, self.mean
        if dtype is not None:
            cost, mean = pool_costs(self.coef, solo_stacks(
                self.pool, self.cfg, dtype))
        self.beside = np.zeros(self.quanta, np.int64)
        bad, head, seen = 0, 0, {}
        mates = np.arange(self.c) ^ 1
        for q in range(self.quanta):
            prev = self.job[q - 1] if q else np.full(self.c, -1)
            gone = np.array([np.floor(fin.get(j, np.inf)) == q - 1
                             for j in prev])
            stay = (prev >= 0) & ~gone
            bad += int(np.sum(stay & (self.job[q] != prev)))
            bad += int(np.sum(gone & (self.job[q] == prev)))
            occ = np.where(stay, self.app_of[q - 1], -1)
            new = self.newcomers(q)
            queue = int((self.arrive_q <= q).sum()) - head
            k = min(queue, int((occ < 0).sum()))
            jobs = sorted(int(j) for j in self.job[q][new])
            if jobs != list(range(head, head + k)) or \
                    k != int(self.out["admissions"][q]):
                bad += 1
            shown = np.where(self.job[q] >= 0, self.app_of[q], -1)
            bad += int(np.sum(shown != self.app[q]))
            for ctx in new[np.argsort(self.job[q][new])]:
                j, pid = int(self.job[q][ctx]), self.app_of[q][ctx]
                seen[j] = q
                bad += int(admit.get(j, q) != q)
                mate = occ[mates]
                score = np.where(occ < 0, np.where(
                    mate >= 0, self.cost[pid, np.maximum(mate, 0)],
                    self.mean[pid]), np.inf)
                best = int(np.argmin(score))
                if dtype is None:
                    got = int(ctx)
                else:
                    got = int(np.argmin(np.where(occ < 0, np.where(
                        mate >= 0, cost[pid, np.maximum(mate, 0)],
                        mean[pid]), np.inf)))
                # A float32 near-tie may resolve either way (module doc).
                if not (np.isfinite(score[got]) and
                        score[got] - score[best] <= TIE * score[best]):
                    bad += 1
                self.beside[q] += int(occ[got ^ 1] >= 0)
                occ[got] = pid
            head += k
            # The first phase lasts its mean length, then the index moves.
            for ctx in np.flatnonzero(self.job[q] >= 0):
                age = q - seen.get(int(self.job[q][ctx]), 0)
                first = age < self.first[self.app_of[q][ctx]]
                bad += int(first != (self.phase[q][ctx] == 0))
        return bad

    def machine(self, dtype):
        """True slowdown and retired instructions (Q, C) of the program's
        occupants next to their partners, in their logged phases."""
        real = np.zeros((self.quanta, self.c))
        retired = np.zeros((self.quanta, self.c))
        cycles = dtype(self.cfg["machine"]["freq_hz"]
                       * self.cfg["machine"]["quantum_s"])
        for q in range(self.quanta):
            act = np.flatnonzero(self.job[q] >= 0)
            if act.size == 0:
                continue
            row = np.full(self.c, -1)
            row[act] = np.arange(act.size)
            pt = self.partner[q][act]
            mate = np.where((pt != act) & (row[pt] >= 0), row[pt],
                            np.arange(act.size))
            tab = smt.PoolTables.from_pool(self.pool, self.app_of[q][act])
            comps, solo = smt.corun(tab, self.cfg["machine"],
                                    self.phase[q][act], mate, dtype)
            cpi = comps.sum(-1)
            real[q][act] = cpi / solo.sum(-1)
            retired[q][act] = cycles / cpi * tab.retire.astype(dtype)
        return real, retired

    def hint_gap(self, dtype=None) -> float:
        """Each newcomer's ST estimate in its admission quantum against its
        app's profiled stack, over the stack's total; with ``dtype`` the
        control's stacks in the program's place."""
        st = self.out["ring"][..., ref_open.ST].astype(np.float64)
        control = None if dtype is None else solo_stacks(
            self.pool, self.cfg, dtype).astype(np.float64)
        worst = 0.0
        for q in range(self.quanta):
            for ctx in self.newcomers(q):
                pid = self.app_of[q][ctx]
                want = self.stacks[pid]
                got = st[q, ctx] if dtype is None else control[pid]
                worst = max(worst, float(np.max(np.abs(got - want))
                                         / want.sum()))
        return worst

    def numbers(self, dtype=None) -> dict:
        out = self.out
        real_ref, ret_ref = self.machine(np.float64)
        real = out["ring"][..., ref_open.REAL]
        if dtype is not None:
            real, ret = self.machine(dtype)
        act = self.job >= 0
        machine_gap = float(np.max(np.abs(real[act] - real_ref[act])
                                   / real_ref[act])) if act.any() else 0.0
        finish_gap = 0.0
        for j in out["jobs"]:
            where = np.flatnonzero(self.job[j["admit_q"]] == j["job_id"])
            if where.size == 0:
                finish_gap = float("inf")
                continue
            ctx, q0 = int(where[0]), j["admit_q"]
            goal = self.goal[j["app"]]
            f_ref = self.finish(ret_ref[:, ctx], q0, goal)
            got = j["finish_q"] if dtype is None else self.finish(
                ret[:, ctx].astype(dtype), q0, dtype(goal))
            finish_gap = max(finish_gap, abs(float(got) - f_ref))
        return {"arrival_mismatch": self.arrival_mismatch(),
                "placement_mismatch": self.placements(dtype),
                "hint_gap": self.hint_gap(dtype),
                "machine_gap": machine_gap, "finish_gap": finish_gap,
                "cost_gap": self.cost_gap(dtype)}
