"""SYNPA — the family of SMT thread-to-core allocation policies (paper §5).

Every quantum (100 ms), a SYNPA policy:

  Step 0. reads the PMU counters of every application and builds its measured
          ISC stack with the variant's (LT100, GT100) repair pair (Table 2);
  Step 1. applies the Eq. 4 model *inversely* to the current pairs to recover
          the stack each application would have had running alone (ST mode),
          renormalised to height 1;
  Step 2. applies the forward model to every candidate pair (both directions)
          to predict each pair's mutual slowdown;
  Step 3. runs the Blossom algorithm on the predicted-degradation matrix and
          pins the selected pairs to cores for the next quantum.

Steps 0-2 plus the matching *cost preparation* (padding sentinels, the
idle-context vertex for odd populations) are one fused jitted dispatch —
:func:`make_fused_step` — shared verbatim by the batch scheduler here and
the streaming allocator (``repro.online``): per quantum there is exactly one
host->device transfer (the counter matrix) and one device->host transfer
(the prepared cost matrix + updated ST stacks).  Each co-running pair is
solved *once* (row i and row j pose the same bilinear system with the roles
swapped), by the damped Gauss-Newton engine of ``regression.inverse``.
Step 3 runs the exact Edmonds matching on host.  The all-pairs forward model
is also available as a Pallas TPU kernel (``repro.kernels.pair_score``) for
cluster-scale N; at N = 8 the XLA path is used.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import isc, matching, regression

Pair = Tuple[int, int]


class Scheduler:
    """Base interface shared by SYNPA, the baselines and Hy-Sched."""

    name = "base"

    def reset(self, n_apps: int, rng: np.random.Generator, machine=None) -> None:
        self.n_apps = n_apps
        self.rng = rng
        self.machine = machine

    def schedule(self, quantum: int, samples, prev_pairs: List[Pair]) -> List[Pair]:
        raise NotImplementedError

    # helpers ---------------------------------------------------------------
    def _random_pairs(self) -> List[Pair]:
        """Random perfect pairing; an odd population's leftover app (the
        last of the permutation) is left uncovered and runs solo."""
        perm = self.rng.permutation(self.n_apps)
        return [(int(perm[2 * k]), int(perm[2 * k + 1])) for k in range(self.n_apps // 2)]

    @staticmethod
    def _have_samples(samples) -> bool:
        """True once every application has a PMU readout."""
        if samples is None:
            return False
        if isinstance(samples, np.ndarray):
            return True
        return not any(s is None for s in samples)

    @staticmethod
    def _counters_array(samples) -> np.ndarray:
        """(N, 5) array: cycles, stall_fe, stall_be, inst_spec, inst_retired.

        The vectorised machine hands policies the counter matrix directly;
        the scalar engine hands a list of :class:`PMUSample`.
        """
        if isinstance(samples, np.ndarray):
            return samples.astype(np.float32)
        return np.array([s.as_tuple() for s in samples], dtype=np.float32)


def _partner_index(pairs: Sequence[Pair], n: int) -> np.ndarray:
    """Partner array of a pairing; an uncovered (solo) slot partners itself."""
    partner = np.arange(n, dtype=np.int32)
    for i, j in pairs:
        partner[i] = j
        partner[j] = i
    return partner


def fused_pad(n: int) -> int:
    """Padded vertex count of the fused pipeline: the smallest multiple of 8
    with room for the idle-context vertex (row ``n``).  Capacity is fixed
    per simulation, so the padded shape — and therefore the compiled
    program — is stable across quanta regardless of churn."""
    return max(8, ((n + 1 + 7) // 8) * 8)


def make_fused_step(
    method: isc.StackMethod,
    model: regression.CategoryModel,
    impl: str = "auto",
    solver: str = "gn",
    gn_steps: int = regression.GN_STEPS,
    hb_steps: int = 80,
    lr: float = 1.5,
    warm: bool = False,
    with_diag: bool = False,
):
    """The fused per-quantum SYNPA dispatch (Steps 0-2 + cost preparation).

    Returns ``step(counters, partner, prev_st, masks, idle)`` with, for
    capacity ``n`` and ``P = fused_pad(n)``:

    * ``counters``  (n, 5) f32 — previous-quantum PMU rows by slot;
    * ``partner``   (n,)  i32 — co-runner slot (self for solo/no-partner);
    * ``prev_st``   (n, 4) f32 — carried ST estimates (uniform placeholder
      for slots without one); rows that do not solve pass through — callers
      feed the returned ``st`` straight back next quantum, so the estimate
      state never leaves the device;
    * ``masks``     (4 or 5, n) bool — one packed host->device transfer,
      rows:

      0. *solve* — slot co-ran and its estimate should refresh;
      1. *solo*  — slot ran alone: its measured fractions *are* its ST
         stack (paper §5.3 degenerate case), no inverse needed;
      2. *valid* — slot hosts an active application;
      3. *fresh* — reset the slot to the uniform placeholder (an arrival
         whose first counters have not happened yet);
      4. *hinted* (optional row) — the slot keeps its carried ``prev_st``
         row whatever its previous occupant measured: an arrival seeded
         with an admission hint, in a slot whose departed occupant's
         counters would otherwise solve over it.  Four-row masks compile
         the graph without this select;

    * ``idle``      bool scalar — augment the idle-context vertex (row
      ``n``) with :data:`repro.core.matching.IDLE_COST` edges.

    and returns ``(cost (P, P) f32, st (n, 4) f32)``: the prepared matching
    matrix (sentinels on padding/invalid entries, idle edges when asked) and
    the refreshed ST stacks.  Everything is one jit graph: ISC stack repair,
    the §5.3 inverse — each co-running pair solved once, scattered to both
    slots — the all-pairs Eq. 4 scoring, and the cost preparation.

    ``solver`` picks the §5.3 engine: ``"gn"`` (damped Gauss-Newton with
    in-graph heavy-ball fallback; ``hb_steps`` is the fallback budget) is
    stateless — it starts from the measured fractions, so its result is a
    pure function of this quantum's counters and ``warm`` is ignored.
    ``"hb"`` is the retained gradient reference; with ``warm=True`` it
    starts from ``prev_st`` (plus the measured-fraction guard start).

    ``with_diag=True`` (static) returns ``(cost, st, diag)``: a (4,) f32
    solver-diagnostics vector reduced over this quantum's valid pair
    solves, in :data:`repro.obs.telemetry.FUSED_DIAG_FIELDS` order —
    [gn_iters_mean, gn_iters_max, gn_residual_max, gn_fallbacks].  The
    diagnostics are pure extra outputs of the same solve: ``cost`` and
    ``st`` stay bit-identical, and the default call compiles today's
    exact graph.
    """
    from repro.kernels.pair_score.ref import DIAG as _KERNEL_DIAG

    # The kernel's padding sentinel and the matcher's must be the same
    # value, or padded rows could out-compete real edges in the matching.
    assert _KERNEL_DIAG == matching.BIG, (_KERNEL_DIAG, matching.BIG)

    uniform = jnp.asarray(isc.uniform_stack(method.n_categories))

    @jax.jit
    @jax.named_scope("synpa_step")
    def step(counters, partner, prev_st, masks, idle):
        solve_mask, solo_mask, valid_mask, fresh_mask = (
            masks[0], masks[1], masks[2], masks[3]
        )
        n = counters.shape[0]
        p = fused_pad(n)
        idx = jnp.arange(n)

        # Step 0: measured SMT stack fractions of every slot.
        with jax.named_scope("isc"):
            raw = isc.raw_stack(
                counters[:, 0], counters[:, 1], counters[:, 2],
                counters[:, 3], dtype=jnp.float32,
            )
            frac = isc.build_stack(raw, method)

        # Step 1: one inverse solve per co-running *pair*.  Row i and row j
        # pose the same system with the roles swapped, so only the
        # lower-index side of each pair solves and both slots receive their
        # estimate from that single trajectory (which also makes the two
        # sides' estimates mutually consistent).
        first = solve_mask & (idx < partner)
        order = jnp.argsort(~first)          # pair-firsts to the front
        take = order[: n // 2]
        p_take = partner[take]
        valid = first[take]
        v1 = valid[:, None]
        fi = jnp.where(v1, frac[take], uniform)
        fj = jnp.where(v1, frac[p_take], uniform)
        idiag = None
        with jax.named_scope("inverse"):
            if solver == "gn":
                if with_diag:
                    si, sj, idiag = regression._gn_with_fallback(
                        model, fi, fj, gn_steps=gn_steps, hb_steps=hb_steps,
                        lr=lr, return_diag=True,
                    )
                else:
                    si, sj = regression._gn_with_fallback(
                        model, fi, fj, gn_steps=gn_steps, hb_steps=hb_steps,
                        lr=lr
                    )
            else:
                assert solver == "hb", solver
                if warm:
                    ii = jnp.where(v1, prev_st[take], uniform)
                    ij = jnp.where(v1, prev_st[p_take], uniform)
                else:
                    ii = ij = None
                si, sj = regression._hb_best_of(
                    model, fi, fj, hb_steps, lr, init_i=ii, init_j=ij
                )
                if with_diag:
                    idiag = regression.InverseDiag(
                        iters=jnp.full(valid.shape, hb_steps, jnp.int32),
                        residual=regression.inverse_residual(
                            model, fi, fj, si, sj
                        ),
                        fallback=jnp.zeros(valid.shape, bool),
                    )
        # Deliver the pair solves by gather, not scatter (a scatter with
        # computed indices lowers to a serial per-element loop on
        # XLA:CPU and serializes across lanes under vmap): slot s is the
        # solving side of pair rank[s] when ``first[s]`` (estimate si),
        # and the partner side of pair rank[partner[s]] when its partner
        # solves (estimate sj); every other slot keeps ``prev_st``.  The
        # take order is the firsts in index order (stable argsort), so
        # ``rank`` — the cumsum rank among firsts — is each first's row
        # in the solve batch, and the written values match the old
        # scatters bit for bit.
        rank = jnp.cumsum(first.astype(jnp.int32)) - 1
        k1 = jnp.clip(rank, 0, n // 2 - 1)
        k2 = jnp.clip(rank[partner], 0, n // 2 - 1)
        sec = first[partner]
        st = jnp.where(first[:, None], si[k1],
                       jnp.where(sec[:, None], sj[k2], prev_st))
        # A slot that ran alone measured its ST stack directly.
        st = jnp.where(solo_mask[:, None], frac, st)
        # Arrivals reset to the uniform placeholder (their slot may carry a
        # departed occupant's estimate until their first counters land).
        st = jnp.where(fresh_mask[:, None], uniform[None, :], st)
        if masks.shape[0] > 4:
            # Hinted arrivals keep their seeded estimate: the solve above
            # ran on the departed occupant's counters.
            st = jnp.where(masks[4][:, None], prev_st, st)

        # Step 2: all-pairs Eq. 4 scoring on the padded stack matrix.
        stp = jnp.concatenate(
            [st, jnp.tile(uniform[None, :], (p - n, 1))], axis=0
        )
        cost = regression.pair_cost_matrix(
            model, stp, impl=impl, n_valid=n
        )

        # Step 3 prep: sentinel out inactive slots, wire the idle vertex.
        validp = jnp.concatenate(
            [valid_mask, jnp.zeros((p - n,), bool)]
        )
        pairv = validp[:, None] & validp[None, :]
        cost = jnp.where(pairv, cost, matching.BIG)
        is_idle = (jnp.arange(p) == n) & idle
        cost = jnp.where(
            is_idle[:, None] & validp[None, :], matching.IDLE_COST, cost
        )
        cost = jnp.where(
            validp[:, None] & is_idle[None, :], matching.IDLE_COST, cost
        )
        if with_diag:
            # Reduce the per-row solver diagnostics over this quantum's
            # valid pair solves (masked rows solved placeholder systems).
            nv = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
            itf = jnp.where(valid, idiag.iters.astype(jnp.float32), 0.0)
            diag = jnp.stack([
                jnp.sum(itf) / nv,
                jnp.max(itf),
                jnp.max(jnp.where(valid, idiag.residual, 0.0)),
                jnp.sum(jnp.where(valid, idiag.fallback, False).astype(
                    jnp.float32)),
            ])
            return cost, st, diag
        return cost, st

    return step


def make_synpa_pipeline(
    method: isc.StackMethod,
    model: regression.CategoryModel,
    impl: str = "auto",
    n_steps: int = 80,
    solver: str = "gn",
    gn_steps: int = regression.GN_STEPS,
):
    """One jitted function: PMU counters + current partners -> pair costs.

    Returns ``fn(counters (N,5) f32, partner (N,) i32) -> (cost (N,N), st (N,4))``
    — the closed-population view of :func:`make_fused_step` (every slot
    active and co-running, no idle vertex), used by the batch
    :class:`SynpaScheduler`.

    ``impl`` picks the Step-2 all-pairs backend (see
    :func:`repro.core.regression.pair_cost_matrix`); "auto" routes
    cluster-scale N through the tiled Pallas kernel on TPU and the XLA
    lowering elsewhere.  The choice is resolved per input shape, so one
    pipeline instance serves any N.  ``n_steps`` is the heavy-ball §5.3
    budget — the fallback budget under ``solver="gn"``, the full budget
    under ``solver="hb"``.
    """
    step = make_fused_step(
        method, model, impl=impl, solver=solver, gn_steps=gn_steps,
        hb_steps=n_steps, warm=False,
    )

    @jax.jit
    def pipeline(counters: jnp.ndarray, partner: jnp.ndarray):
        n = counters.shape[0]
        ones = jnp.ones((n,), bool)
        zeros = jnp.zeros((n,), bool)
        prev = jnp.tile(
            jnp.asarray(isc.uniform_stack(method.n_categories))[None, :],
            (n, 1),
        )
        masks = jnp.stack([ones, zeros, ones, zeros])
        cost, st = step(
            counters.astype(jnp.float32), partner.astype(jnp.int32), prev,
            masks, jnp.asarray(False),
        )
        return cost[:n, :n], st

    return pipeline


class SynpaScheduler(Scheduler):
    """One member of the SYNPA family, e.g. SYNPA4_R-FEBE.

    Odd populations ride the idle-context convention: the fused step wires
    the idle vertex (row ``n``) into the prepared cost matrix and whoever
    the matcher pairs with it is left uncovered — it runs alone that
    quantum.  Even populations take the identical code path with the idle
    vertex disabled, so the closed-system behaviour is unchanged.
    """

    def __init__(
        self,
        method: isc.StackMethod,
        model: regression.CategoryModel,
        name: Optional[str] = None,
        matcher: str = "auto",
        pair_impl: str = "auto",
        solver: str = "gn",
        n_steps: int = 80,
    ):
        self.method = method
        self.model = model
        self.name = name or f"SYNPA{method.n_categories}_{method.name.split('_', 1)[1]}"
        self.matcher = matcher
        self._uniform = isc.uniform_stack(method.n_categories)
        self._step = make_fused_step(
            method, model, impl=pair_impl, solver=solver, hb_steps=n_steps,
            warm=False,
        )

    def schedule(self, quantum, samples, prev_pairs):
        if not self._have_samples(samples) or not prev_pairs:
            return self._random_pairs()
        n = self.n_apps
        odd = n % 2 == 1
        counters = self._counters_array(samples)
        partner = _partner_index(prev_pairs, n)
        idx = np.arange(n)
        solve = partner != idx        # co-ran last quantum
        masks = np.stack([
            solve,                    # refresh the estimate via the inverse
            ~solve,                   # a solo slot measured its ST directly
            np.ones(n, bool),         # every slot is active
            np.zeros(n, bool),        # no arrivals in a closed population
        ])
        cost, _st = self._step(
            jnp.asarray(counters), jnp.asarray(partner),
            jnp.asarray(np.tile(self._uniform, (n, 1))),
            jnp.asarray(masks), jnp.asarray(odd),
        )
        rows = list(range(n)) + ([n] if odd else [])
        compact = matching.compact_cost(np.asarray(cost), rows)
        pairs = matching.min_cost_pairs(compact, method=self.matcher)  # Step 3
        if not odd:
            return pairs
        # Drop the idle pair: its app runs solo this quantum.
        return [(a, b) for a, b in pairs if n not in (a, b)]
