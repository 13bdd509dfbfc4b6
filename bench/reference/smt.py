"""Plain reference of the simulated SMT pool and the SYNPA policy step.

Written from the paper's equations and the configuration files alone, in
numpy, with no import of the system under test.  Every function takes a
``dtype``: ``np.float64`` is the reference, and ``ml_dtypes.bfloat16`` is
the control (the same arithmetic with every intermediate rounded to the
precision below the configuration's float32).

Pieces, in the order a quantum runs them:

* ``PoolTables``  the solo per-instruction cycle components of each app
  phase (dispatch-stage ISC ground truth of a 4-wide ThunderX2 core);
* ``corun``       the machine's interference transform for a pairing;
* ``counters``    the five PMU counters of one quantum, with the seeded
  lognormal counter noise;
* ``isc4_febe``   the measured ISC stack, repaired as SYNPA4_R-FEBE;
* ``inverse``     Eq. 4 applied inversely to a co-running pair: the two ST
  stacks whose forward prediction reproduces both measured stacks;
* ``pair_cost``   Eq. 4 forward, slowdown(i|j) + slowdown(j|i).
"""

from __future__ import annotations

import dataclasses

import numpy as np

#: Slowdown clip of the Eq. 4 forward prediction (configuration file).
MIN_SLOWDOWN = 0.25
MAX_SLOWDOWN = 16.0


@dataclasses.dataclass(frozen=True)
class PoolTables:
    """Per-app tables of a scenario (rows = hardware contexts' apps)."""

    comps: np.ndarray       # (A, P, 4) solo cycles per inst: full, hw, fe, be
    util: np.ndarray        # (A, P) dispatch-slot utilisation
    x_fe: np.ndarray        # (A, P)
    x_be: np.ndarray        # (A, P)
    duration: np.ndarray    # (A, P) mean phase length in quanta
    n_phases: np.ndarray    # (A,)
    omega: np.ndarray
    retire: np.ndarray
    mem_sens: np.ndarray
    fetch_sens: np.ndarray

    @classmethod
    def from_pool(cls, pool: dict, picks) -> "PoolTables":
        """Tables of the contexts' apps: ``picks`` are pool rows."""
        apps = pool["apps"]
        pmax = max(len(a["phases"]) for a in apps)
        comps = np.zeros((len(apps), pmax, 4))
        util, x_fe, x_be, dur = (np.zeros((len(apps), pmax))
                                 for _ in range(4))
        for ai, app in enumerate(apps):
            for pi, ph in enumerate(app["phases"]):
                full = max(1.0 - ph["x_fe"] - ph["x_be"] - ph["x_hw"], 0.0)
                u = full + ph["fill"] * ph["x_hw"]
                cpi = 1.0 / max(4.0 * u, 1e-9)
                comps[ai, pi] = [full * cpi, ph["x_hw"] * cpi,
                                 ph["x_fe"] * cpi, ph["x_be"] * cpi]
                util[ai, pi] = u
                x_fe[ai, pi] = ph["x_fe"]
                x_be[ai, pi] = ph["x_be"]
                dur[ai, pi] = ph["duration"]
        col = lambda k: np.array([a[k] for a in apps], float)  # noqa: E731
        k = np.asarray(picks, np.int64)
        return cls(comps[k], util[k], x_fe[k], x_be[k], dur[k],
                   np.array([len(a["phases"]) for a in apps])[k],
                   col("omega")[k], col("retire")[k], col("mem_sens")[k],
                   col("fetch_sens")[k])


def corun(t: PoolTables, m: dict, phase, partner, dtype=np.float64):
    """Per-instruction cycle components of every context under ``partner``
    (``partner[i] == i``: context i runs alone).  Returns (comps, solo)."""
    n = len(partner)
    idx = np.arange(n)
    ph = phase % t.n_phases
    c = t.comps[idx, ph].astype(dtype)
    cpi = c.sum(-1)
    co = (partner != idx).astype(dtype)
    php = ph[partner]
    u = t.util[partner, php].astype(dtype) * co
    f = t.x_fe[partner, php].astype(dtype) * co
    b = t.x_be[partner, php].astype(dtype) * co
    mem = t.mem_sens.astype(dtype)
    fetch = t.fetch_sens.astype(dtype)
    p = {k: dtype(v) for k, v in m.items()}
    out = np.stack([
        c[:, 0] * (1 + p["a_disp"] * u),
        c[:, 1] * (1 + p["a_hw"] * u),
        c[:, 2] * (1 + p["a_fe"] * f) + p["e_fe"] * fetch * f * cpi,
        c[:, 3] * (1 + p["a_be"] * b + p["b_be"] * mem * b * b)
        + p["e_be"] * mem * b * cpi,
    ], axis=-1)
    return out, c


def counters(t: PoolTables, m: dict, comps, z, dtype=np.float64):
    """(n, 5) PMU counters of one quantum: cycles, stall_frontend,
    stall_backend, inst_spec, inst_retired.  ``z`` is the (n, 4) standard
    normal draw of the counter noise (lognormal, sigma from the config)."""
    cycles = dtype(m["freq_hz"] * m["quantum_s"])
    cpi = comps.sum(-1)
    insts = cycles / cpi
    frac = comps / cpi[:, None]
    fe, be = frac[:, 2], frac[:, 3]
    overlap = t.omega.astype(dtype) * np.minimum(fe, be)
    split = dtype(m["overlap_split"])
    cols = np.stack([
        cycles * (fe + split * overlap),
        cycles * (be + (1 - split) * overlap),
        insts,
        insts * t.retire.astype(dtype),
    ], axis=-1)
    cols = cols * np.exp(dtype(m["noise_sigma"]) * z.astype(dtype))
    return np.concatenate([np.full((len(cpi), 1), cycles, dtype), cols], -1)


def isc4_febe(ctr, width: int = 4):
    """Measured ISC stack (DI, FE, BE, HW), repaired as SYNPA4_R-FEBE:
    a stack under 100% exposes the gap as horizontal waste; one over 100%
    sheds the excess from FE and BE in proportion to their sizes."""
    dtype = ctr.dtype.type
    cyc = np.maximum(ctr[:, 0], dtype(1e-9))
    di = ctr[:, 3] / (dtype(width) * cyc)
    fe = ctr[:, 1] / cyc
    be = ctr[:, 2] / cyc
    h = di + fe + be
    gap = np.maximum(1 - h, 0)
    ex = np.maximum(h - 1, 0)
    den = np.maximum(fe + be, dtype(1e-9))
    lt = np.stack([di, fe, be, gap], -1)
    gt = np.stack([di, fe - ex * fe / den, be - ex * be / den,
                   np.zeros_like(di)], -1)
    return np.clip(np.where((h <= 1)[:, None], lt, gt), 0, None)


def forward(coef, x, y):
    """Eq. 4: predicted per-ST-cycle SMT category values of x next to y."""
    a, b, g, r = (coef[:, k] for k in range(4))
    return np.maximum(a + b * x + g * y + r * x * y, 0)


def slowdown(coef, x, y):
    return np.clip(forward(coef, x, y).sum(-1), MIN_SLOWDOWN, MAX_SLOWDOWN)


def pair_cost(coef, st_i, st_j):
    """Predicted mutual slowdown of each pair (i, j): s(i|j) + s(j|i)."""
    return slowdown(coef, st_i, st_j) + slowdown(coef, st_j, st_i)


def _softmax(z):
    e = np.exp(z - z.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


def inverse_residual(coef, fi, fj, x, y):
    """Fraction-normalised residual vector (B, 8) of candidate ST stacks:
    the forward prediction's shape must equal the measured stack."""
    pi, pj = forward(coef, x, y), forward(coef, y, x)
    return np.concatenate([pi - pi.sum(-1, keepdims=True) * fi,
                           pj - pj.sum(-1, keepdims=True) * fj], -1)


def inverse(coef, fi, fj, steps: int = 60):
    """Eq. 4 inverse of a co-running pair (paper section 5.3).

    Finds ST stacks x, y on the simplex (softmax coordinates) with
    ``forward(x, y) / sum`` equal to the measured stack ``fi`` and
    ``forward(y, x) / sum`` equal to ``fj``: six independent equations in
    six unknowns.  Levenberg-Marquardt from the measured stacks, in the
    arrays' own precision.  Returns (x, y, sum of squared residuals).
    """
    dtype = fi.dtype.type
    coef = coef.astype(dtype)
    a, b, g, r = (coef[:, k] for k in range(4))
    zi = np.log(np.clip(fi, dtype(1e-4), None))
    zj = np.log(np.clip(fj, dtype(1e-4), None))
    eye = np.eye(4, dtype=dtype)

    def res(zi, zj):
        rv = inverse_residual(coef, fi, fj, _softmax(zi), _softmax(zj))
        return rv, (rv * rv).sum(-1)

    def block(frac, slope, s):
        # d(p - sum(p) frac)/dz for p affine in s = softmax(z) with slope.
        dp = eye * slope[:, None, :] - frac[:, :, None] * slope[:, None, :]
        ds = eye * s[:, None, :] - s[:, :, None] * s[:, None, :]
        return np.matmul(dp.astype(np.float64), ds.astype(np.float64))

    rv, cur = res(zi, zj)
    lam = np.full(len(fi), 1e-2)
    for _ in range(steps):
        x, y = _softmax(zi), _softmax(zj)
        act_i = (a + b * x + g * y + r * x * y > 0).astype(dtype)
        act_j = (a + b * y + g * x + r * y * x > 0).astype(dtype)
        jac = np.concatenate([
            np.concatenate([block(fi, (b + r * y) * act_i, x),
                            block(fi, (g + r * x) * act_i, y)], -1),
            np.concatenate([block(fj, (g + r * y) * act_j, x),
                            block(fj, (b + r * x) * act_j, y)], -1),
        ], -2)
        rv64 = rv.astype(np.float64)
        h = np.einsum("bki,bkj->bij", jac, jac)
        grad = np.einsum("bki,bk->bi", jac, rv64)
        d = np.diagonal(h, axis1=1, axis2=2)
        step = np.linalg.solve(
            h + (lam[:, None] * d + 1e-30)[:, :, None] * np.eye(8),
            -grad[:, :, None])[:, :, 0].astype(dtype)
        ti, tj = zi + step[:, :4], zj + step[:, 4:]
        trv, trial = res(ti, tj)
        ok = np.isfinite(trial) & (trial < cur)
        zi = np.where(ok[:, None], ti, zi)
        zj = np.where(ok[:, None], tj, zj)
        rv = np.where(ok[:, None], trv, rv)
        cur = np.where(ok, trial, cur)
        lam = np.clip(np.where(ok, lam * 0.3, lam * 10.0), 1e-12, 1e12)
    return _softmax(zi), _softmax(zj), cur
