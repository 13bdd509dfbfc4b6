"""Per-category linear regression performance model — the paper's Eq. 4.

For every ISC category ``C`` a tiny linear model predicts the *cycles spent in
category C while executing a fixed window of instructions in SMT mode,
normalised by the ST cycles of that window*:

    C_smt(i|j) = alpha_C + beta_C * C_st(i) + gamma_C * C_st(j)
                 + rho_C * C_st(i) * C_st(j)                          (Eq. 4)

Units (this matches the paper's Table 3 coefficients and MSE magnitudes):

* ST stacks ``C_st`` are fractions of ST cycles — they sum to 1.
* SMT values ``C_smt`` are *per-ST-cycle* — the instruction-aligned mapping
  of §5.4 ("the number of committed instructions allows us to map the
  category values...").  Their sum is the application's slowdown (>= 1):
  e.g. a Dispatch component near beta = 0.9..1 (full-dispatch-equivalent
  cycles are invariant to interference), a Frontend component that grows
  ~1.4x regardless of the co-runner, and a Backend component dominated by
  the *co-runner's* backend pressure (gamma = 1.44 in the paper).

Consequently the predicted slowdown is the predicted SMT stack *height* —
every category contributes, which is exactly why the stack construction
(SYNPA3 vs SYNPA4, N vs R-FE vs R-FEBE) matters for scheduling quality.

Operations (paper §5.3 steps 1-2):

* :func:`fit`              — least-squares coefficients + per-category MSE.
* :func:`forward`          — ST stacks of a pair -> predicted per-ST-cycle SMT
                             category values of the first application.
* :func:`predict_slowdown` — sum of the forward components.
* :func:`inverse`          — measured SMT stack *fractions* of the currently
                             co-running pair -> estimated ST stacks
                             (normalised to 1).  Solved by a batched damped
                             Gauss-Newton (Levenberg-Marquardt) iteration over
                             softmax-parameterised simplex points, with the
                             retained heavy-ball gradient path as an in-graph
                             fallback for rows the GN iteration has not
                             converged (``solver="hb"`` selects it outright).
* :func:`pair_cost_matrix` — dense all-pairs cost (XLA reference for the
                             ``repro.kernels.pair_score`` Pallas kernel).

The inverse exploits Eq. 4's bilinear structure: with one side's stack held
fixed, every category residual is *affine* in the other side's stack, so the
Gauss-Newton Jacobian assembles in closed form from a handful of outer
products (no autodiff pass) and each LM step is a tiny batched 8x8
least-squares solve.  Because each residual vector sums to zero by
construction (both sides are fraction-normalised), the system has as many
independent equations as free simplex coordinates and is generically
*exactly* solvable: GN drives the residual to float noise (~1e-14) in a
median of 2-3 steps where the 80-step gradient scan plateaued around 1e-3
(the "flat valley" of docs/online.md was an optimiser artifact, not a
property of the landscape).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import isc

_EPS = 1e-8
MIN_SLOWDOWN = 0.25
MAX_SLOWDOWN = 16.0


@dataclasses.dataclass(frozen=True)
class CategoryModel:
    """Fitted Eq. 4 coefficients for one stack method.

    coeffs: (4, 4) array, rows in ISC category order (DI, FE, BE, HW), columns
            (alpha, beta, gamma, rho).  Rows beyond ``n_categories`` are zero.
    mse:    (4,) training mean-squared error per category (paper §5.2).
    n_categories: 3 or 4 (SYNPA3 vs SYNPA4 stacks).
    """

    coeffs: jnp.ndarray
    mse: jnp.ndarray
    n_categories: int


def design_matrix(c_i, c_j):
    """Rows of the Eq. 4 design: [1, C_i, C_j, C_i*C_j]."""
    c_i = jnp.asarray(c_i, jnp.float32)
    c_j = jnp.asarray(c_j, jnp.float32)
    one = jnp.ones_like(c_i)
    return jnp.stack([one, c_i, c_j, c_i * c_j], axis=-1)


def fit(
    st_i,
    st_j,
    smt_i,
    n_categories: int,
    ridge: float = 1e-6,
) -> CategoryModel:
    """Least-squares fit of Eq. 4, one independent model per category.

    st_i:  (S, 4) ST stack (fractions, height 1) of the measured app.
    st_j:  (S, 4) ST stack of its co-runner.
    smt_i: (S, 4) instruction-aligned SMT category values (per ST cycle).
    """
    st_i = jnp.asarray(st_i, jnp.float32)
    st_j = jnp.asarray(st_j, jnp.float32)
    smt_i = jnp.asarray(smt_i, jnp.float32)

    # Full f32 contractions: at a TPU's default precision the normal
    # equations move the coefficients by ~0.1.
    hi = jax.lax.Precision.HIGHEST
    coeffs, mses = [], []
    eye = jnp.eye(4, dtype=jnp.float32)
    for c in range(n_categories):
        X = design_matrix(st_i[:, c], st_j[:, c])
        y = smt_i[:, c]
        gram = jnp.matmul(X.T, X, precision=hi) + ridge * eye
        w = jnp.linalg.solve(gram, jnp.matmul(X.T, y, precision=hi))
        coeffs.append(w)
        mses.append(jnp.mean((jnp.matmul(X, w, precision=hi) - y) ** 2))
    while len(coeffs) < isc.N_CATS:
        coeffs.append(jnp.zeros(4, jnp.float32))
        mses.append(jnp.zeros((), jnp.float32))
    return CategoryModel(
        coeffs=jnp.stack(coeffs[: isc.N_CATS]),
        mse=jnp.stack(mses[: isc.N_CATS]),
        n_categories=n_categories,
    )


def forward(model: CategoryModel, st_i, st_j):
    """Eq. 4 forward: ST stacks -> per-ST-cycle SMT category values of i."""
    st_i = jnp.asarray(st_i, jnp.float32)
    st_j = jnp.asarray(st_j, jnp.float32)
    a, b, g, r = (model.coeffs[:, k] for k in range(4))
    pred = a + b * st_i + g * st_j + r * st_i * st_j
    mask = (jnp.arange(isc.N_CATS) < model.n_categories).astype(pred.dtype)
    return jnp.clip(pred * mask, 0.0, None)


def predict_slowdown(model: CategoryModel, st_i, st_j):
    """Predicted slowdown of i next to j = predicted SMT stack height."""
    s = jnp.sum(forward(model, st_i, st_j), axis=-1)
    return jnp.clip(s, MIN_SLOWDOWN, MAX_SLOWDOWN)


def _inverse_problem(model: CategoryModel, frac_i, frac_j, lr: float):
    """Shared internals of the §5.3 inverse solve.

    Returns ``(to_simplex, residual, solve_from)`` closures over the measured
    fractions; ``solve_from(z0_i, z0_j, n_steps)`` runs the heavy-ball
    gradient scan and returns the final ``(z_i, z_j)``.
    """
    mask = (jnp.arange(isc.N_CATS) < model.n_categories).astype(frac_i.dtype)

    def to_simplex(z):
        e = jnp.exp(z - jnp.max(z, axis=-1, keepdims=True)) * mask
        return e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), _EPS)

    def residual(zs):
        """Per-batch-element residual (independent across elements)."""
        z_i, z_j = zs
        x, y = to_simplex(z_i), to_simplex(z_j)
        p_i = forward(model, x, y)
        p_j = forward(model, y, x)
        r_i = p_i - jnp.sum(p_i, -1, keepdims=True) * frac_i
        r_j = p_j - jnp.sum(p_j, -1, keepdims=True) * frac_j
        return jnp.sum(r_i * r_i, -1) + jnp.sum(r_j * r_j, -1)

    def loss(zs):
        return jnp.sum(residual(zs))

    grad_fn = jax.grad(loss)

    def _make_step(trace: bool):
        def step(carry, _):
            zs, m = carry
            g = grad_fn(zs)
            # Heavy-ball momentum keeps the solve cheap yet fast-converging.
            m = tuple(0.7 * mm + gg for mm, gg in zip(m, g))
            zs = tuple(z - lr * mm for z, mm in zip(zs, m))
            return (zs, m), (residual(zs) if trace else None)
        return step

    def solve_from(z0_i, z0_j, n_steps: int, trace: bool = False):
        init = ((z0_i, z0_j), (jnp.zeros_like(z0_i), jnp.zeros_like(z0_j)))
        (zs, _m), res = jax.lax.scan(
            _make_step(trace), init, None, length=n_steps
        )
        return (zs, res) if trace else zs

    return to_simplex, residual, solve_from


def _log_init(stacks):
    """Masked-softmax pre-image of a (clipped) simplex point."""
    return jnp.log(jnp.clip(stacks, 1e-4, None))


# ---------------------------------------------------------------------------
# Damped Gauss-Newton inverse (§5.3 step 1) — the production solver.
# ---------------------------------------------------------------------------
#: LM step budget: the bilinear system is exactly determined, so GN reaches
#: float-noise residuals in a median of 2-3 accepted steps; 8 leaves margin
#: for rejected (damping-escalation) steps on awkward rows.
GN_STEPS = 8
_GN_LAM0 = 1e-2        # initial LM damping
_GN_LAM_DOWN = 0.33    # damping decay on an accepted step
_GN_LAM_UP = 10.0      # damping escalation on a rejected step
#: A row still improving by more than this relative amount over its last two
#: LM steps at budget end has not converged -> heavy-ball fallback.
_GN_PLATEAU_RTOL = 0.05
#: ...unless its residual is already below this: the 2x80-step heavy-ball
#: reference itself plateaus around 1e-4..1e-3 on measured fractions, so a
#: still-descending row below 1e-4 has nothing to gain from the fallback.
_GN_GOOD_ENOUGH = 1e-4
#: Damping level past which a rejected LM trial counts as a stall: from
#: lam0 = 1e-2 it takes ~5 consecutive rejections (x10 each) to get here,
#: at which point the trial steps are scaled-down gradient steps and two
#: rejections in a row mean a genuine local plateau.
_GN_LAM_STALL = 1e3


class InverseDiag(NamedTuple):
    """Per-row diagnostics of the §5.3 inverse solve (``return_diag=True``).

    iters:    (...,) int32 — LM steps taken while the row was still live
              (not yet converged/plateaued); ``gn_steps`` on a row that ran
              out of budget, the full ``n_steps`` under ``solver="hb"``.
    residual: (...,) float32 — final inverse residual of the returned
              solution (the fallback's when the fallback won the row).
    fallback: (...,) bool — the heavy-ball fallback's solution beat GN's
              on this row (always False when the fallback never ran).
    """

    iters: jnp.ndarray
    residual: jnp.ndarray
    fallback: jnp.ndarray


def _chol_solve_small(A, b, n: int):
    """Batched SPD solve by fully unrolled Cholesky (pure elementwise jnp).

    ``A``: (..., n, n) SPD (LM-damped normal equations), ``b``: (..., n).
    Unrolling keeps XLA on fused vector ops — at these sizes (n = 8) the
    LAPACK batched-solve custom call costs more than the whole GN step.
    Zeroed rows/columns (masked categories) pass through with a zero
    solution component because their gradient entries are exactly zero.
    """
    L = [[None] * n for _ in range(n)]
    for i in range(n):
        for j in range(i + 1):
            s = A[..., i, j]
            for k in range(j):
                s = s - L[i][k] * L[j][k]
            if i == j:
                L[i][j] = jnp.sqrt(jnp.maximum(s, 1e-20))
            else:
                L[i][j] = s / L[j][j]
    y = [None] * n
    for i in range(n):
        s = b[..., i]
        for k in range(i):
            s = s - L[i][k] * y[k]
        y[i] = s / L[i][i]
    x = [None] * n
    for i in reversed(range(n)):
        s = y[i]
        for k in range(i + 1, n):
            s = s - L[k][i] * x[k]
        x[i] = s / L[i][i]
    return jnp.stack(x, axis=-1)


def _gn_problem(model: CategoryModel, frac_i, frac_j):
    """Closures of the GN solve: simplex map, residual vector, Jacobian.

    The Jacobian exploits Eq. 4's bilinear structure.  With the co-runner's
    stack fixed, each predicted category is affine in the own stack —
    ``p_i = v(y) + u(y) * x`` elementwise — and the fraction-normalised
    residual ``r_i = p_i - (sum p_i) * frac_i`` is therefore affine too.
    Each C x C Jacobian block (including the chain through the masked
    softmax, whose Jacobian is ``diag(x) - x x^T``) reduces to
    ``diag(q) - frac q^T - (q - (sum q) frac) x^T`` with ``q = u * x``:
    one diagonal plus two outer products, assembled entirely from
    elementwise broadcasts — no autodiff pass, no batched matmul.
    """
    mask = (jnp.arange(isc.N_CATS) < model.n_categories).astype(jnp.float32)
    a, b, g, r = (model.coeffs[:, k] for k in range(4))
    eye = jnp.eye(isc.N_CATS, dtype=jnp.float32)

    def to_simplex(z):
        e = jnp.exp(z - jnp.max(z, axis=-1, keepdims=True)) * mask
        return e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), _EPS)

    def resvec(x, y):
        p_i = forward(model, x, y)
        p_j = forward(model, y, x)
        r_i = p_i - jnp.sum(p_i, -1, keepdims=True) * frac_i
        r_j = p_j - jnp.sum(p_j, -1, keepdims=True) * frac_j
        return jnp.concatenate([r_i, r_j], axis=-1)

    def residual(x, y):
        rv = resvec(x, y)
        return jnp.sum(rv * rv, -1)

    def _block(frac, u, x):
        """(d r / d z) block for residual ``r`` with slope ``u`` wrt the
        softmax pre-image of ``x``:  diag(q) - frac q^T - (q - s frac) x^T.
        """
        q = u * x
        s = jnp.sum(q, -1, keepdims=True)
        d = eye * q[..., None, :]
        d = d - frac[..., :, None] * q[..., None, :]
        return d - (q - s * frac)[..., :, None] * x[..., None, :]

    def jac(x, y):
        pred_i = (a + b * x + g * y + r * x * y) * mask
        pred_j = (a + b * y + g * x + r * y * x) * mask
        act_i = (pred_i > 0).astype(jnp.float32) * mask  # clip subgradient
        act_j = (pred_j > 0).astype(jnp.float32) * mask
        u_i = (b + r * y) * act_i      # d p_i / d x  (diagonal slope)
        w_i = (g + r * x) * act_i      # d p_i / d y
        u_j = (b + r * x) * act_j      # d p_j / d y
        w_j = (g + r * y) * act_j      # d p_j / d x
        top = jnp.concatenate(
            [_block(frac_i, u_i, x), _block(frac_i, w_i, y)], axis=-1)
        bot = jnp.concatenate(
            [_block(frac_j, w_j, x), _block(frac_j, u_j, y)], axis=-1)
        return jnp.concatenate([top, bot], axis=-2)

    return to_simplex, resvec, residual, jac


def _make_lm_step(model: CategoryModel, frac_i, frac_j):
    """One LM-damped Gauss-Newton step with per-row accept/reject.

    A trial step is kept only if it lowers that row's residual (the
    iteration is monotone by construction), and the damping interpolates
    towards a scaled gradient step as it escalates — Levenberg-Marquardt's
    built-in line search.  Returns the problem closures plus
    ``step(z_i, z_j, res, lam) -> (z_i, z_j, res, lam)``.
    """
    to_simplex, resvec, residual, jac = _gn_problem(model, frac_i, frac_j)
    two_c = 2 * isc.N_CATS
    eye2 = jnp.eye(two_c, dtype=jnp.float32)

    def init_carry(z_i, z_j):
        rv = resvec(to_simplex(z_i), to_simplex(z_j))
        res = jnp.sum(rv * rv, -1)
        lam = jnp.full(res.shape, _GN_LAM0, jnp.float32)
        return z_i, z_j, rv, res, lam

    def step(z_i, z_j, rv, res, lam):
        # ``rv`` is the residual vector at the current point — carried
        # across iterations so each LM step evaluates the Eq. 4 forward
        # model once (at the trial point), not twice.
        x, y = to_simplex(z_i), to_simplex(z_j)
        J = jac(x, y)
        # Full f32 normal equations (a TPU's default precision would
        # round J to bf16).
        hi = jax.lax.Precision.HIGHEST
        grad = jnp.einsum("...ki,...k->...i", J, rv, precision=hi)
        H = jnp.einsum("...ki,...kj->...ij", J, J, precision=hi)
        diag = jnp.diagonal(H, axis1=-2, axis2=-1)
        A = H + (lam[..., None, None] * diag[..., None, :] + 1e-8) * eye2
        delta = _chol_solve_small(A, -grad, two_c)
        z_i_t = z_i + delta[..., : isc.N_CATS]
        z_j_t = z_j + delta[..., isc.N_CATS:]
        rv_t = resvec(to_simplex(z_i_t), to_simplex(z_j_t))
        res_t = jnp.sum(rv_t * rv_t, -1)
        ok = (res_t < res) & jnp.isfinite(res_t)
        okx = ok[..., None]
        z_i = jnp.where(okx, z_i_t, z_i)
        z_j = jnp.where(okx, z_j_t, z_j)
        rv = jnp.where(okx, rv_t, rv)
        res = jnp.where(ok, res_t, res)
        lam = jnp.clip(
            jnp.where(ok, lam * _GN_LAM_DOWN, lam * _GN_LAM_UP), 1e-7, 1e8
        )
        return z_i, z_j, rv, res, lam

    return to_simplex, init_carry, step


def _gn_solve_scan(model: CategoryModel, frac_i, frac_j, z0_i, z0_j,
                   n_steps: int):
    """Fixed-step GN solve with a per-step residual trace (diagnostics).

    Returns ``(st_i, st_j, res, trace)``; ``trace`` has shape
    ``(n_steps, ...batch)``.  The production path (:func:`_gn_solve`)
    runs the *same* step function under an early-exit while-loop.
    """
    to_simplex, init_carry, step = _make_lm_step(model, frac_i, frac_j)

    def scan_step(carry, _):
        carry = step(*carry)
        return carry, carry[3]

    (z_i, z_j, _rv, res, _lam), trace = jax.lax.scan(
        scan_step, init_carry(z0_i, z0_j), None, length=n_steps
    )
    return to_simplex(z_i), to_simplex(z_j), res, trace


def _gn_solve(model: CategoryModel, frac_i, frac_j, z0_i, z0_j,
              n_steps: int, diag: bool = False):
    """Early-exit GN solve: iterate until every row is done or the budget
    runs out.

    A row is *done* when its residual is below :data:`_GN_GOOD_ENOUGH` or
    it has plateaued: two consecutive steps improving by less than
    :data:`_GN_PLATEAU_RTOL` relative.  On a row that has already
    descended (accepted at least one step) rejected trials count as
    plateau evidence like tiny accepted ones — it is sitting on a genuine
    residual floor.  On a row still stuck at its *starting* residual they
    do not (unless damping has escalated past :data:`_GN_LAM_STALL`, i.e.
    LM has degenerated into vanishing gradient steps): such a row keeps
    iterating and, if the budget runs out first, is flagged for the
    fallback rather than silently declared converged.  The loop stops as
    soon as *all* rows are done, which in the steady state (median 2-3
    accepted steps to float-noise residuals) cuts the per-quantum cost
    roughly in half versus always running the budget.

    Returns ``(st_i, st_j, res, not_converged)``; ``not_converged`` marks
    rows that exhausted the budget while still descending — the rows the
    caller hands to the heavy-ball fallback.

    ``diag=True`` (a static flag) additionally returns a per-row ``iters``
    int32 array — the number of LM steps each row took while still live.
    The counter rides the loop carry as a pure extra output: it never
    feeds the step math, so the default path's graph (and its float32
    trajectory) is exactly the ``diag=False`` code below.
    """
    to_simplex, init_carry, step = _make_lm_step(model, frac_i, frac_j)

    z0_i, z0_j, rv0, res0, lam0 = init_carry(z0_i, z0_j)
    stall0 = jnp.zeros(res0.shape, jnp.int32)
    ever0 = jnp.zeros(res0.shape, bool)
    k0 = jnp.zeros((), jnp.int32)

    def done_of(res, stall):
        return (res < _GN_GOOD_ENOUGH) | (stall >= 2)

    def advance(z_i, z_j, rv, res, lam, stall, ever):
        z_i, z_j, rv, res_n, lam = step(z_i, z_j, rv, res, lam)
        small = (res - res_n) <= _GN_PLATEAU_RTOL * (res_n + 1e-12)
        accepted = res_n < res
        # A rejected trial leaves res unchanged.  On a row that has
        # *descended* before (``ever`` accepted a step) that is plateau
        # evidence like any tiny accepted step; on a row still stuck at
        # its starting residual it is not — such a row only stalls once
        # damping has escalated past _GN_LAM_STALL (vanishing gradient
        # steps), and otherwise runs to the budget and is flagged for the
        # heavy-ball fallback instead of being declared converged.
        stalled = small & (accepted | ever | (lam >= _GN_LAM_STALL))
        stall = jnp.where(
            stalled, stall + 1, jnp.where(accepted, 0, stall)
        )
        return z_i, z_j, rv, res_n, lam, stall, ever | accepted

    if diag:
        def cond_d(carry):
            k, _its, _z_i, _z_j, _rv, res, _lam, stall, _ever = carry
            return (k < n_steps) & ~jnp.all(done_of(res, stall))

        def body_d(carry):
            k, its, z_i, z_j, rv, res, lam, stall, ever = carry
            live = ~done_of(res, stall)
            its = its + live.astype(jnp.int32)
            out = advance(z_i, z_j, rv, res, lam, stall, ever)
            return (k + 1, its) + out

        its0 = jnp.zeros(res0.shape, jnp.int32)
        (_k, iters, z_i, z_j, _rv, res, _lam, stall,
         _ever) = jax.lax.while_loop(
            cond_d, body_d,
            (k0, its0, z0_i, z0_j, rv0, res0, lam0, stall0, ever0),
        )
        not_converged = ~done_of(res, stall)
        return to_simplex(z_i), to_simplex(z_j), res, not_converged, iters

    def cond(carry):
        k, _z_i, _z_j, _rv, res, _lam, stall, _ever = carry
        return (k < n_steps) & ~jnp.all(done_of(res, stall))

    def body(carry):
        k = carry[0]
        out = advance(*carry[1:])
        return (k + 1,) + out

    _k, z_i, z_j, _rv, res, _lam, stall, _ever = jax.lax.while_loop(
        cond, body, (k0, z0_i, z0_j, rv0, res0, lam0, stall0, ever0)
    )
    not_converged = ~done_of(res, stall)
    return to_simplex(z_i), to_simplex(z_j), res, not_converged


def inverse_gn_trace(
    model: CategoryModel,
    frac_i,
    frac_j,
    n_steps: int = GN_STEPS,
    init_i=None,
    init_j=None,
):
    """Pure GN trajectory (no fallback): ``(st_i, st_j, trace)``.

    ``trace[k]`` is the residual after LM step ``k+1`` — the step-count
    budget assertions of the solver regression harness read it directly.
    """
    frac_i = jnp.asarray(frac_i, jnp.float32)
    frac_j = jnp.asarray(frac_j, jnp.float32)
    if init_i is None:
        z0_i, z0_j = _log_init(frac_i), _log_init(frac_j)
    else:
        z0_i = _log_init(jnp.asarray(init_i, jnp.float32))
        z0_j = _log_init(jnp.asarray(init_j, jnp.float32))
    st_i, st_j, _res, trace = _gn_solve_scan(
        model, frac_i, frac_j, z0_i, z0_j, n_steps
    )
    return st_i, st_j, trace


@jax.named_scope("inverse")
def inverse(
    model: CategoryModel,
    frac_i,
    frac_j,
    n_steps: int = 80,
    lr: float = 1.5,
    init_i=None,
    init_j=None,
    solver: str = "gn",
    gn_steps: int = GN_STEPS,
    return_diag: bool = False,
):
    """Invert Eq. 4 (paper §5.3 step 1).

    Inputs are the *measured SMT stack fractions* of the two applications
    currently sharing a core (each sums to 1).  We search for the two ST
    stacks (height 1) whose forward predictions are *parallel* to the
    measured fractions, i.e. minimise

        || forward(x, y) - (sum forward(x, y)) * frac_i ||^2  +  (i <-> j)

    over the product of simplices, parameterising each stack with a masked
    softmax.  The per-app scale that drops out is the slowdown itself, so no
    separate fixed-point over slowdowns is needed.

    ``solver="gn"`` (default): ``gn_steps`` damped Gauss-Newton steps from
    the measured fractions (or from ``init_i``/``init_j`` when given — they
    *replace* the start rather than adding a second trajectory, because the
    LM iteration is start-insensitive on this problem).  Rows that are still
    descending at budget end (or went non-finite) trigger an in-graph
    fallback: the retained heavy-ball gradient path runs with
    the full ``n_steps`` budget from both classic starts and the per-row
    lower-residual solution wins.  The whole solve — fallback included — is
    one jit-able graph; the fallback branch costs nothing unless taken
    (phrased as a 0/1-trip ``while_loop`` rather than ``lax.cond`` so it
    stays conditional under ``vmap`` — see :func:`_run_at_most_once`).

    ``solver="hb"``: the pre-GN behaviour, bit for bit — two heavy-ball
    trajectories of ``n_steps`` each from (a) the measured fractions and
    (b) the uniform stack (or the warm ``init``), per-row best.  Kept as the
    reference/fallback engine and for A/B benchmarks.

    ``return_diag=True`` (static) returns ``(st_i, st_j, diag)`` with a
    per-row :class:`InverseDiag` — LM iteration counts, final residuals
    and the fallback mask.  The stacks are bit-identical to the default
    call (diagnostics are pure extra outputs), and ``return_diag=False``
    compiles today's exact graph.  Under ``solver="hb"`` the fixed-length
    gradient scan has no early exit: ``iters`` is the full ``n_steps``
    and ``fallback`` is all-False.
    """
    frac_i = jnp.asarray(frac_i, jnp.float32)
    frac_j = jnp.asarray(frac_j, jnp.float32)
    if solver == "hb":
        st_i, st_j = _hb_best_of(model, frac_i, frac_j, n_steps, lr,
                                 init_i=init_i, init_j=init_j)
        if not return_diag:
            return st_i, st_j
        res = inverse_residual(model, frac_i, frac_j, st_i, st_j)
        return st_i, st_j, InverseDiag(
            iters=jnp.full(res.shape, n_steps, jnp.int32),
            residual=res,
            fallback=jnp.zeros(res.shape, bool),
        )
    assert solver == "gn", solver
    return _gn_with_fallback(model, frac_i, frac_j, gn_steps=gn_steps,
                             hb_steps=n_steps, lr=lr,
                             init_i=init_i, init_j=init_j,
                             return_diag=return_diag)


def _hb_best_of(model: CategoryModel, frac_i, frac_j, n_steps: int,
                lr: float, init_i=None, init_j=None):
    """The pre-GN heavy-ball solve: two trajectories, per-row best."""
    to_simplex, residual, solve_from = _inverse_problem(
        model, frac_i, frac_j, lr
    )
    za = solve_from(_log_init(frac_i), _log_init(frac_j), n_steps)
    if init_i is None:
        zb = solve_from(
            jnp.zeros_like(frac_i), jnp.zeros_like(frac_j), n_steps
        )
    else:
        zb = solve_from(
            _log_init(jnp.asarray(init_i, jnp.float32)),
            _log_init(jnp.asarray(init_j, jnp.float32)),
            n_steps,
        )
    better_b = (residual(zb) < residual(za))[..., None]
    z_i = jnp.where(better_b, zb[0], za[0])
    z_j = jnp.where(better_b, zb[1], za[1])
    return to_simplex(z_i), to_simplex(z_j)


def _run_at_most_once(pred, fn, init):
    """``lax.cond(pred, fn, identity, init)`` phrased as a 0/1-trip
    ``lax.while_loop`` so the conditional survives ``vmap``.

    ``cond``'s batching rule executes BOTH branches for every lane and
    selects — under a lane-batched caller (``repro.online.batch_sim``)
    that puts the heavy-ball fallback on the hot path of every quantum,
    roughly doubling the per-lane cost of the open-system race.
    ``while_loop``'s batching rule instead keeps the trip conditional
    (the loop body runs only while *some* lane's predicate holds, and
    each lane's carry is select-masked by its own predicate), so lanes
    that never need the fallback never pay for it.  Unbatched, XLA skips
    the body exactly as it skipped the cond branch.  Either way the
    selected values are unchanged — bit-identity contracts hold.

    Caveat: ``fn``'s expensive subgraph must *depend on the carried
    state*, not only on closure captures — XLA hoists loop-invariant
    nested loops out of a batched-pred while and runs them
    unconditionally, which silently re-creates the cost this helper
    exists to avoid.  Tie captures to ``state`` through one
    ``lax.optimization_barrier`` (an identity, so values are unchanged)
    as :func:`_gn_with_fallback` does."""
    def _cond(state):
        return state[0]

    def _body(state):
        _, x = state
        return jnp.zeros((), bool), fn(x)

    _, out = jax.lax.while_loop(_cond, _body, (jnp.asarray(pred), init))
    return out


def _gn_with_fallback(model: CategoryModel, frac_i, frac_j,
                      gn_steps: int = GN_STEPS, hb_steps: int = 80,
                      lr: float = 1.5, init_i=None, init_j=None,
                      return_diag: bool = False):
    """GN solve + in-graph heavy-ball fallback for non-converged rows.

    The building block behind :func:`inverse` and the fused per-quantum
    pipeline (``repro.core.synpa.make_fused_step``).  All inputs must
    already be float32 jnp arrays.

    ``return_diag=True`` (static) returns ``(st_i, st_j, diag)`` with a
    per-row :class:`InverseDiag`.  The diagnostics are pure extra outputs
    of the same solve — the returned stacks are bit-identical either way,
    and the default path compiles the exact ``return_diag=False`` graph.
    """
    assert gn_steps >= 3, "plateau detection needs at least 3 LM steps"
    if init_i is None:
        z0_i, z0_j = _log_init(frac_i), _log_init(frac_j)
    else:
        z0_i = _log_init(jnp.asarray(init_i, jnp.float32))
        z0_j = _log_init(jnp.asarray(init_j, jnp.float32))
    if return_diag:
        st_i, st_j, res, not_converged, iters = _gn_solve(
            model, frac_i, frac_j, z0_i, z0_j, gn_steps, diag=True
        )
    else:
        st_i, st_j, res, not_converged = _gn_solve(
            model, frac_i, frac_j, z0_i, z0_j, gn_steps
        )
    need_fb = jnp.any(not_converged | ~jnp.isfinite(res))

    if return_diag:
        def _with_fallback_d(state):
            si, sj, r, _fb = state
            fi_b, fj_b, si, sj = jax.lax.optimization_barrier(
                (frac_i, frac_j, si, sj)
            )
            hb_i, hb_j = _hb_best_of(model, fi_b, fj_b, hb_steps, lr,
                                     init_i=init_i, init_j=init_j)
            res_hb = inverse_residual(model, fi_b, fj_b, hb_i, hb_j)
            better = res_hb < r
            bx = better[..., None]
            return (
                jnp.where(bx, hb_i, si),
                jnp.where(bx, hb_j, sj),
                jnp.where(better, res_hb, r),
                better,
            )

        out_i, out_j, out_res, fb = _run_at_most_once(
            need_fb, _with_fallback_d,
            (st_i, st_j, res, jnp.zeros(res.shape, bool)),
        )
        return out_i, out_j, InverseDiag(
            iters=iters, residual=out_res, fallback=fb
        )

    def _with_fallback(state):
        si, sj = state
        fi_b, fj_b, si, sj = jax.lax.optimization_barrier(
            (frac_i, frac_j, si, sj)
        )
        hb_i, hb_j = _hb_best_of(model, fi_b, fj_b, hb_steps, lr,
                                 init_i=init_i, init_j=init_j)
        res_hb = inverse_residual(model, fi_b, fj_b, hb_i, hb_j)
        better = (res_hb < res)[..., None]
        return (
            jnp.where(better, hb_i, si),
            jnp.where(better, hb_j, sj),
        )

    return _run_at_most_once(need_fb, _with_fallback, (st_i, st_j))


def inverse_residual(model: CategoryModel, frac_i, frac_j, st_i, st_j):
    """Residual of a candidate ST-stack pair against measured fractions.

    The same objective :func:`inverse` minimises, evaluated at simplex points
    directly — used by tests and diagnostics to compare solve quality.
    """
    frac_i = jnp.asarray(frac_i, jnp.float32)
    frac_j = jnp.asarray(frac_j, jnp.float32)
    st_i = jnp.asarray(st_i, jnp.float32)
    st_j = jnp.asarray(st_j, jnp.float32)
    p_i = forward(model, st_i, st_j)
    p_j = forward(model, st_j, st_i)
    r_i = p_i - jnp.sum(p_i, -1, keepdims=True) * frac_i
    r_j = p_j - jnp.sum(p_j, -1, keepdims=True) * frac_j
    return jnp.sum(r_i * r_i, -1) + jnp.sum(r_j * r_j, -1)


def inverse_trace(
    model: CategoryModel,
    frac_i,
    frac_j,
    n_steps: int = 80,
    lr: float = 1.5,
    init_i=None,
    init_j=None,
):
    """Per-step residual trace of a single-start *heavy-ball* solve.

    The gradient-path (``solver="hb"``) diagnostic twin of
    :func:`inverse_gn_trace`.  Runs one gradient trajectory — from the
    measured fractions (cold) or from ``init_i``/``init_j`` (warm) — and
    returns ``(st_i, st_j, trace)``
    where ``trace`` has shape ``(n_steps, ...batch)``: the residual after
    each step.  This is how the property tests assert that a warm start
    reaches the convergence threshold in strictly fewer gradient steps than
    a cold start on a static population.
    """
    frac_i = jnp.asarray(frac_i, jnp.float32)
    frac_j = jnp.asarray(frac_j, jnp.float32)
    to_simplex, _residual, solve_from = _inverse_problem(
        model, frac_i, frac_j, lr
    )
    if init_i is None:
        z0_i, z0_j = _log_init(frac_i), _log_init(frac_j)
    else:
        z0_i = _log_init(jnp.asarray(init_i, jnp.float32))
        z0_j = _log_init(jnp.asarray(init_j, jnp.float32))
    (z_i, z_j), trace = solve_from(z0_i, z0_j, n_steps, trace=True)
    return to_simplex(z_i), to_simplex(z_j), trace


@jax.named_scope("pair_cost")
def pair_cost_matrix(model: CategoryModel, st_stacks, impl: str = "xla",
                     n_valid=None):
    """Dense all-pairs cost: cost[i, j] = slowdown(i|j) + slowdown(j|i).

    st_stacks: (N, 4) ST stacks.  Returns (N, N) symmetric; diagonal is set
    huge so an application never pairs with itself.

    ``impl`` selects the backend of ``repro.kernels.pair_score``: "xla"
    (dense reference), "pallas" (tiled TPU kernel for cluster-scale N),
    "pallas_interpret", or "auto" (pallas on TPU past the crossover N).
    ``n_valid`` marks rows at or past it as padding (sentinel cost, shape
    preserved) — see :func:`repro.kernels.pair_score.ops.pair_costs`.
    """
    from repro.kernels.pair_score import ops as pair_score_ops

    st = jnp.asarray(st_stacks, jnp.float32)
    return pair_score_ops.pair_costs(
        st, model.coeffs, n_categories=model.n_categories, impl=impl,
        n_valid=n_valid,
    )


def profile_to_training_set(
    st_stacks: np.ndarray,
    pair_smt_values: np.ndarray,
    pairs: Sequence[Tuple[int, int]],
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Assemble (st_i, st_j, smt_i) training triples from profiling runs.

    st_stacks:       (A, 4) per-app ST stacks.
    pair_smt_values: (P, 2, 4) per-pair instruction-aligned SMT values.
    pairs:           length-P list of (i, j) app indices.
    """
    xs_i, xs_j, ys = [], [], []
    for p, (i, j) in enumerate(pairs):
        xs_i.append(st_stacks[i]); xs_j.append(st_stacks[j])
        ys.append(pair_smt_values[p, 0])
        xs_i.append(st_stacks[j]); xs_j.append(st_stacks[i])
        ys.append(pair_smt_values[p, 1])
    return np.stack(xs_i), np.stack(xs_j), np.stack(ys)
