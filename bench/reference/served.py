"""Reference check of one served allocation decision.

A decision takes the previous quantum's PMU counters of every hardware
context, the active set, who ran and with whom, and who just arrived; it
returns the pairing of the active set (at most one context alone, when
the count is odd).  The allocator's rule for its ST-stack estimates:

* a context that ran next to a partner last quantum gets the Eq. 4 inverse
  of its pair's two measured ISC stacks;
* a context that ran alone measured its ST stack directly;
* a context that just arrived restarts from the uniform stack;
* any other keeps its estimate.

Numbers (worst over the sampled decisions):

* ``st_gap``      refreshed ST stacks against the reference's, absolute
                  (inverse rows only where the counters are consistent
                  with Eq. 4, as in ``closed.CONSISTENT``);
* ``st_gap_p50``  the same, median refreshed context over the sample;
* ``swap_gain``   distance of the returned pairing from a 2-opt optimum
                  of the predicted costs of the allocator's own stacks;
* ``bad_pairing`` active contexts not covered exactly once, inactive ones
                  covered, or a context left alone in an even population.
"""

from __future__ import annotations

import numpy as np

from bench.reference import closed, matching, smt

#: Pair cost of a context left alone next to the idle vertex: both
#: "directions" run interference-free (slowdown 1 each).
IDLE_COST = 2.0


def refresh_masks(capacity, active, ran, arrived, prev_pairs, prev_solo):
    """(solve partner, solved, solo, fresh) of a decision's contexts."""
    partner = np.arange(capacity)
    solved = np.zeros(capacity, bool)
    for a, b in prev_pairs:
        if ran[a] and ran[b]:
            partner[a], partner[b] = b, a
            solved[a] = solved[b] = True
    solo = np.zeros(capacity, bool)
    if prev_solo is not None and ran[prev_solo]:
        solo[prev_solo] = True
    fresh = np.zeros(capacity, bool)
    fresh[list(arrived)] = True
    return partner, solved, solo, fresh


def expected_st(d: dict, coef, dtype=np.float64):
    """The refreshed rows' ST stacks by the allocator's rule: (rows, st,
    inverse residual per row; NaN outside inverse rows)."""
    cap = len(d["counters"])
    partner, solved, solo, fresh = refresh_masks(
        cap, d["active"], d["ran"], d["arrived"], d["prev_pairs"],
        d["prev_solo"])
    stacks = smt.isc4_febe(np.asarray(d["counters"], dtype))
    st = np.full((cap, 4), np.nan)
    res = np.full(cap, np.nan)
    if solved.any():
        sub, r = closed.solve_pairs(coef, stacks,
                                    np.where(solved, partner, np.arange(cap)),
                                    dtype)
        st[solved], res[solved] = sub[solved], r[solved]
    st[solo] = stacks[solo]
    res[solo] = 0.0
    st[fresh] = 0.25
    res[fresh] = 0.0
    rows = solved | solo | fresh
    return rows, st, res


def decision_partner(d: dict) -> np.ndarray:
    """Partner per context (-1 uncovered, the capacity for the alone one);
    raises nothing: coverage faults show in ``bad_pairing``."""
    cap = len(d["counters"])
    partner = np.full(cap, -1)
    for a, b in d["pairs"]:
        partner[a], partner[b] = b, a
    if d["solo"] is not None:
        partner[d["solo"]] = cap
    return partner


def bad_pairing(d: dict) -> int:
    cap = len(d["counters"])
    seen = np.zeros(cap + 1, int)
    for a, b in d["pairs"]:
        seen[a] += 1
        seen[b] += 1
    if d["solo"] is not None:
        seen[d["solo"]] += 1
    want = np.zeros(cap + 1, int)
    want[np.asarray(d["active"], int)] = 1
    bad = int((seen != want).sum())
    if d["solo"] is not None and len(d["active"]) % 2 == 0:
        bad += 1
    return bad


def swap_gain(d: dict, coef) -> float:
    """2-opt distance of the pairing under the allocator's own stacks."""
    act = np.asarray(d["active"], int)
    cap = len(d["counters"])
    partner = decision_partner(d)
    st = np.asarray(d["st"], np.float64)[act]
    cost = smt.pair_cost(np.asarray(coef, np.float64), st[:, None, :],
                         st[None, :, :])
    verts = list(act)
    if len(act) % 2:
        verts.append(cap)
        cost = np.pad(cost, ((0, 1), (0, 1)), constant_values=IDLE_COST)
    pos = {v: k for k, v in enumerate(verts)}
    if d["solo"] is not None:
        partner = np.append(partner, d["solo"])
    mate = np.array([pos.get(int(partner[v]), k)
                     for k, v in enumerate(verts)])
    if (mate == np.arange(len(verts))).any():
        return float("inf")
    return matching.swap_gain(cost, mate)


def compare(d: dict, coef, dtype=None) -> dict:
    """One decision's per-row ST gaps, swap gain and pairing faults; with
    ``dtype`` the control's stacks (the reference in that precision) take
    the allocator's place."""
    rows, want, res = expected_st(d, coef)
    got = np.asarray(d["st"], np.float64)
    if dtype is not None:
        _r, got, _res = expected_st(d, coef, dtype)
    ok = rows & ~(res >= closed.CONSISTENT)
    return {"st_rows": np.abs(got[ok] - want[ok]).max(-1),
            "swap_gain": swap_gain(d, coef), "bad_pairing": bad_pairing(d)}
