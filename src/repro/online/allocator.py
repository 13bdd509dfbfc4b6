"""Online thread-to-core allocation under churn — the streaming SYNPA path.

The closed-system :class:`repro.core.synpa.SynpaScheduler` and this
streaming allocator now share one engine: the **fused per-quantum dispatch**
(:func:`repro.core.synpa.make_fused_step`).  Per quantum there is exactly
one jitted device call — ISC stack repair, the §5.3 inverse (damped
Gauss-Newton, one solve per co-running *pair*), the all-pairs Eq. 4 scoring
and the matching cost preparation (padding sentinels + the idle-context
vertex) — and one device->host transfer of the prepared cost matrix.  The
padded shape is a pure function of the context capacity, so the compiled
program is stable across churn: arrivals and departures change mask
contents, never shapes.

What remains stateful:

* **ST placeholders** — a slot whose application has not produced counters
  yet (admitted this quantum) scores with the uniform stack until its first
  quantum completes; a slot that ran *alone* takes its measured fractions as
  its ST stack directly (no co-runner, nothing to invert).
* **Incremental re-matching** — on churn quanta the surviving pairs are
  kept, the uncovered vertices (arrivals, widows, a previously idle
  context) are matched exactly among themselves, and the incremental
  2-opt (:func:`repro.core.matching.repair_pairs`) ripples the repair
  outward only through rows/columns it actually improves.  On static quanta
  the allocator re-matches like the batch scheduler — exactly (blossom) up
  to ``BLOSSOM_MAX_N``, and by re-converging the previous pairing
  (:func:`repro.core.matching.refine_pairs`) at cluster scale, where the
  batch tier itself is heuristic.

**Exactness.**  The Gauss-Newton inverse is *stateless*: it starts from the
measured fractions and converges to float-noise residuals in a handful of
LM steps, so its result is a pure function of this quantum's counters — no
warm-start trajectory, no history dependence.  The warm/cold distinction
that PR 2's gradient solver needed (and that capped its warm path at
quality-equal) therefore collapses for the inverse: every configuration
computes the *same* ST stacks, bitwise.  What still distinguishes
:func:`exact_config` from the default is only the matcher tier: exact mode
re-matches static quanta in full (bit-identical pairings to
``SynpaScheduler.schedule`` on static populations — integration-tested),
while the default re-converges the previous pairing past the blossom tier
(``rematch="auto"``), which is quality-equal but not bitwise above
``BLOSSOM_MAX_N``.  The retained heavy-ball engine (``solver="hb"``)
approximates the PR 2 solver for A/B comparisons — same two-start descent
and warm inits, but through the fused single-budget dispatch, so e.g. an
arrival's first-counter solve gets the warm budget rather than PR 2's
separate 80-step cold dispatch.

Odd populations follow the idle-context convention: a virtual idle vertex
with edge cost :data:`repro.core.matching.IDLE_COST` (= 1.0 + 1.0, two
interference-free slowdowns) joins the matching, and whoever pairs with it
runs alone on its core that quantum.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import isc, matching, regression
from repro.core.matching import IDLE_COST
from repro.core.synpa import Scheduler, make_fused_step
from repro.obs import trace as obs_trace

Pair = Tuple[int, int]

_BIG = matching.BIG


class OnlinePolicy:
    """Interface the open-system simulator drives every quantum.

    ``pair`` receives the *previous* quantum's PMU counters (rows of slots
    that executed it), membership deltas since the last call, and the
    previous pairing; it returns the co-run slot pairs for this quantum plus
    the slot left with an idle context when the population is odd.
    """

    name = "online-base"

    def reset(self, machine, rng: np.random.Generator) -> None:
        self.machine = machine
        self.rng = rng

    def pair(
        self,
        q: int,
        active: np.ndarray,
        counters: np.ndarray,
        ran: np.ndarray,
        arrived: Sequence[int],
        departed: Sequence[int],
        prev_pairs: List[Pair],
        prev_solo: Optional[int],
        hints: Optional[Dict[int, np.ndarray]] = None,
    ) -> Tuple[List[Pair], Optional[int]]:
        """``hints`` (optional) maps an *arrived* slot to a profiled ST
        stack estimate for its application — the queue-aware admission tier
        (``repro.online.admission``) supplies these so a newcomer scores
        with historical profile information instead of the uniform
        placeholder.  Policies are free to ignore them."""
        raise NotImplementedError

    # helpers --------------------------------------------------------------
    def _random_pairing(
        self, slots: Sequence[int]
    ) -> Tuple[List[Pair], Optional[int]]:
        slots = list(slots)
        perm = self.rng.permutation(len(slots))
        shuffled = [slots[k] for k in perm]
        solo = shuffled.pop() if len(shuffled) % 2 else None
        pairs = [
            (shuffled[2 * k], shuffled[2 * k + 1])
            for k in range(len(shuffled) // 2)
        ]
        return pairs, solo

    @staticmethod
    def _surviving(
        active: np.ndarray,
        arrived: Sequence[int],
        prev_pairs: List[Pair],
    ) -> Tuple[List[Pair], List[int]]:
        """Split the previous pairing into kept pairs + uncovered slots
        (a previously-solo slot falls out naturally as uncovered)."""
        alive = set(int(s) for s in active) - set(int(s) for s in arrived)
        kept = [
            (a, b) for a, b in prev_pairs if a in alive and b in alive
        ]
        covered = {v for p in kept for v in p}
        uncovered = [int(s) for s in active if int(s) not in covered]
        return kept, uncovered


class RandomOnline(OnlinePolicy):
    """Random-static under churn: pairs survive; churn is patched randomly."""

    name = "random"

    def pair(self, q, active, counters, ran, arrived, departed,
             prev_pairs, prev_solo, hints=None):
        if not prev_pairs and prev_solo is None:
            return self._random_pairing(active)
        kept, uncovered = self._surviving(active, arrived, prev_pairs)
        if not uncovered:
            return kept, None
        patch, solo = self._random_pairing(uncovered)
        return kept + patch, solo


class AdjacentOnline(OnlinePolicy):
    """Deterministic slot-ordered pairing: active slots pair in ascending
    adjacent order every quantum; an odd population leaves the highest
    active slot solo.  Interference-oblivious and *RNG-free* — the parity
    anchor of the device-resident engine (``repro.online.device_sim``
    implements the identical rule in-graph), where a shared arrival stream
    plus this policy pins the whole open-system trajectory."""

    name = "adjacent"

    def pair(self, q, active, counters, ran, arrived, departed,
             prev_pairs, prev_solo, hints=None):
        a = [int(s) for s in active]
        solo = a.pop() if len(a) % 2 else None
        pairs = [(a[2 * k], a[2 * k + 1]) for k in range(len(a) // 2)]
        return pairs, solo


class LinuxOnline(RandomOnline):
    """CFS-like under churn: sticky pairing, occasional migrations,
    random patching of arrivals/departures (interference-oblivious)."""

    name = "linux"

    def __init__(self, p_migrate: float = 0.03):
        self.p_migrate = p_migrate

    def pair(self, q, active, counters, ran, arrived, departed,
             prev_pairs, prev_solo, hints=None):
        pairs, solo = super().pair(
            q, active, counters, ran, arrived, departed, prev_pairs, prev_solo
        )
        if len(pairs) >= 2 and self.rng.random() < self.p_migrate:
            pl = [list(p) for p in pairs]
            a, b = self.rng.choice(len(pl), size=2, replace=False)
            sa = int(self.rng.integers(2))
            sb = int(self.rng.integers(2))
            pl[a][sa], pl[b][sb] = pl[b][sb], pl[a][sa]
            pairs = [tuple(p) for p in pl]
        return pairs, solo


@dataclasses.dataclass
class StreamingConfig:
    """Knobs of the streaming allocator (see module docstring)."""

    solver: str = "gn"           # §5.3 engine: "gn" (default) or "hb"
    gn_steps: int = regression.GN_STEPS   # LM budget per GN solve
    warm: bool = True            # hb only: warm-start from previous ST
    warm_steps: int = 24         # hb budget when warm
    cold_steps: int = 80         # hb budget when cold / gn fallback budget
    incremental: bool = True     # repair the matching on churn
    rematch: str = "auto"        # static-quantum re-match: full/refine/auto
    #: Engine for full re-matches (``matching.min_cost_pairs`` methods), or
    #: ``"device"`` to swap the host matcher for the device tier
    #: (:func:`repro.core.matching.device_pairs_partner`): greedy seed +
    #: parallel 2-opt run in-graph on the padded cost matrix every quantum,
    #: with only the (P,) partner vector transferred back.  Shapes are
    #: stable under churn (masks change contents, never shapes), so the
    #: compiled matcher survives arrivals/departures.  Quality: the device
    #: tier's 2-opt gap (property-tested) instead of blossom exactness.
    matcher: str = "auto"
    pair_impl: str = "auto"      # Step-2 backend (kernels.pair_score)
    #: Minimum cost improvement the refine/repair 2-opt tiers act on.
    #: Counter noise wiggles near-tie pair costs at the 1e-3..1e-2 level per
    #: quantum; swaps below this floor churn the pairing without moving
    #: ground-truth quality (hundreds of swaps/quantum at cluster N, each
    #: O(P)).  Full re-matches (the exact/cold paths) never use it.
    refine_eps: float = 1e-2
    #: Swap budget per refine/repair pass.  Bounds the matcher's latency on
    #: a single quantum; the 2-opt applies best-improvement-first, so the
    #: budget takes the swaps that matter and the residual (sub-noise)
    #: drift is repaired over the following quanta.
    refine_max_swaps: int = 24


def cold_config() -> StreamingConfig:
    """The batch SYNPA path verbatim: stateless inverse + full re-match
    every quantum.  The reference arm of the online benchmarks."""
    return StreamingConfig(warm=False, incremental=False, rematch="full")


def exact_config() -> StreamingConfig:
    """Bit-identical to ``SynpaScheduler.schedule`` on static populations
    (same fused dispatch + full re-match), incremental repair only on churn
    quanta — the safety configuration when bitwise reproducibility matters
    more than policy latency.  With the (stateless) Gauss-Newton inverse
    the only thing this switches off versus the default config is the
    ``refine`` matcher tier above ``BLOSSOM_MAX_N``."""
    return StreamingConfig(warm=False, incremental=True, rematch="full")


class StreamingAllocator(OnlinePolicy):
    """SYNPA through the fused dispatch + incremental re-matching."""

    def __init__(
        self,
        method: isc.StackMethod,
        model: regression.CategoryModel,
        config: Optional[StreamingConfig] = None,
        name: Optional[str] = None,
    ):
        self.method = method
        self.model = model
        self.cfg = cfg = config or StreamingConfig()
        # The auto-name reflects matcher statefulness (the inverse is
        # stateless under the default GN solver): cold = full re-match
        # every quantum, stream = anything that carries pairing state.
        mode = "stream" if (cfg.incremental or cfg.rematch != "full") \
            else "cold"
        self.name = name or (
            f"SYNPA{method.n_categories}_{method.name.split('_', 1)[1]}"
            f"-{mode}"
        )
        self._uniform = isc.uniform_stack(method.n_categories)
        hb_steps = (
            cfg.warm_steps if (cfg.solver == "hb" and cfg.warm)
            else cfg.cold_steps
        )
        self._step = make_fused_step(
            method, model, impl=cfg.pair_impl, solver=cfg.solver,
            gn_steps=cfg.gn_steps, hb_steps=hb_steps, warm=cfg.warm,
        )

    # ------------------------------------------------------------ lifecycle
    def reset(self, machine, rng: np.random.Generator) -> None:
        super().reset(machine, rng)
        self._st = None    # (capacity, 4) device-resident ST estimates

    def _ensure_state(self, capacity: int) -> None:
        if self._st is None or self._st.shape[0] != capacity:
            self._st = jnp.asarray(np.tile(self._uniform, (capacity, 1)))

    def _apply_hints(self, hints, arrived_set) -> List[int]:
        """Seed arrived slots' ST estimates from admission hints.

        Returns the hinted slot list (they skip the fresh-mask reset).  One
        tiny scatter onto the device-resident state, churn quanta only.
        """
        if not hints:
            return []
        slots = sorted(int(s) for s in hints if int(s) in arrived_set)
        if not slots:
            return []
        vals = np.stack([
            np.asarray(hints[s], np.float32).reshape(isc.N_CATS)
            for s in slots
        ])
        self._st = self._st.at[jnp.asarray(slots)].set(jnp.asarray(vals))
        return slots

    # ------------------------------------------------------------- pairing
    def pair(self, q, active, counters, ran, arrived, departed,
             prev_pairs, prev_solo, hints=None):
        with obs_trace.span("alloc.pair", q=q, n_active=len(active)):
            return self._pair(q, active, counters, ran, arrived, departed,
                              prev_pairs, prev_solo, hints)

    def _pair(self, q, active, counters, ran, arrived, departed,
              prev_pairs, prev_solo, hints):
        """One decision, in the spans a trace splits it into: ``alloc.prep``
        (masks and hint scatter), ``alloc.step`` (the fused step with its
        argument transfers), ``matcher.wait`` (the device matcher's fetch,
        in :func:`repro.core.matching.device_pairs`) and ``alloc.unpack``
        (vertices back to slots)."""
        active = np.asarray(active, np.int64)
        arrived_set = set(int(s) for s in arrived)
        capacity = int(counters.shape[0])
        if not prev_pairs and prev_solo is None:
            # First quantum with runnable applications: no counters yet.
            self._st = None
            self._ensure_state(capacity)
            self._apply_hints(hints, arrived_set)
            return self._random_pairing(active)
        with obs_trace.span("alloc.prep"):
            self._ensure_state(capacity)
            # --- Build the fused-dispatch masks from the previous quantum.
            partner = np.arange(capacity, dtype=np.int32)
            masks = np.zeros((4, capacity), bool)  # solve, solo, valid, fresh
            if prev_pairs:
                pp = np.asarray(prev_pairs, np.int64).reshape(-1, 2)
                both_ran = ran[pp[:, 0]] & ran[pp[:, 1]]
                pa, pb = pp[both_ran, 0], pp[both_ran, 1]
                partner[pa], partner[pb] = pb, pa
                masks[0, pa] = masks[0, pb] = True
            if prev_solo is not None and ran[prev_solo]:
                masks[1, prev_solo] = True
            masks[2, active] = True
            if arrived_set:
                masks[3, list(arrived_set)] = True
            hinted = self._apply_hints(hints, arrived_set)
            if hinted:
                # A hinted newcomer scores with its profiled stack, not the
                # uniform placeholder or its slot's departed occupant's
                # solve: the fifth mask row keeps the seeded estimate.
                masks[3, hinted] = False
                keep = np.zeros((1, capacity), bool)
                keep[0, hinted] = True
                masks = np.concatenate([masks, keep])
        a_count = int(active.size)
        odd = a_count % 2 == 1

        # --- Steps 0-2 + cost prep: one device dispatch, one transfer back.
        # The ST estimate state stays on the device: the returned ``st``
        # feeds the next quantum's call directly.
        with obs_trace.span("alloc.step"):
            cost_dev, self._st = self._step(
                np.asarray(counters, np.float32),
                partner,
                self._st,
                masks,
                odd,
            )

        if a_count == 1:
            return [], int(active[0])

        # --- Step 3 (device tier): greedy + parallel 2-opt in-graph on the
        # padded matrix; only the (P,) partner vector comes back.  Slots are
        # vertices directly (no compact remap); the idle vertex is row
        # ``capacity``.
        if self.cfg.matcher == "device":
            valid = np.zeros(int(cost_dev.shape[0]), bool)
            valid[active] = True
            if odd:
                valid[capacity] = True
            pairs_v = matching.device_pairs(
                cost_dev, valid, eps=self.cfg.refine_eps
            )
            with obs_trace.span("alloc.unpack"):
                out: List[Pair] = []
                solo: Optional[int] = None
                for x, y in pairs_v:
                    if capacity in (x, y):
                        solo = x if y == capacity else y
                    else:
                        out.append((x, y))
            return out, solo

        # --- Step 3: (incremental) matching on the compact active set.
        rows = [int(s) for s in active] + ([capacity] if odd else [])
        cost = matching.compact_cost(np.asarray(cost_dev), rows)
        nv = len(rows)
        compact = {int(s): k for k, s in enumerate(active)}
        idle = nv - 1 if odd else None

        churn = bool(arrived_set) or bool(departed) or (
            prev_solo is not None and not odd
        )
        kept_slots, _ = self._surviving(active, arrived, prev_pairs)
        kept = [(compact[a], compact[b]) for a, b in kept_slots]
        if prev_solo is not None and int(prev_solo) in compact and \
                int(prev_solo) not in arrived_set and odd and not churn:
            kept.append((compact[int(prev_solo)], idle))

        if churn and self.cfg.incremental and kept:
            covered = {v for p in kept for v in p}
            dirty = [v for v in range(nv) if v not in covered]
            pairs_c = matching.repair_pairs(
                cost, kept, dirty, eps=self.cfg.refine_eps,
                max_swaps=self.cfg.refine_max_swaps,
            )
        else:
            mode = self.cfg.rematch
            if mode == "auto":
                mode = "full" if nv <= matching.BLOSSOM_MAX_N else "refine"
            if mode == "refine" and not churn and len(kept) == nv // 2:
                pairs_c = matching.refine_pairs(
                    cost, kept, eps=self.cfg.refine_eps,
                    max_swaps=self.cfg.refine_max_swaps,
                )
            else:
                pairs_c = matching.min_cost_pairs(
                    cost, method=self.cfg.matcher
                )

        # Map back to slot space; the idle partner becomes the solo slot.
        inv = {k: int(s) for s, k in compact.items()}
        out: List[Pair] = []
        solo: Optional[int] = None
        for x, y in pairs_c:
            if idle is not None and idle in (x, y):
                solo = inv[x if y == idle else y]
            else:
                out.append((inv[x], inv[y]))
        return out, solo


class StreamingScheduler(Scheduler):
    """Closed-system adapter: the streaming allocator as a drop-in
    :class:`repro.core.synpa.Scheduler`.

    Lets ``SMTMachine.run_workload``/``run_quanta`` race the streaming
    path directly against the batch :class:`SynpaScheduler` on the *same*
    fixed population — the exactness and policy-cost comparisons of the
    acceptance tests.  Consumes the policy RNG exactly like SynpaScheduler
    (one permutation before samples exist), so a run only diverges if the
    chosen pairings do.
    """

    def __init__(
        self,
        method: isc.StackMethod,
        model: regression.CategoryModel,
        config: Optional[StreamingConfig] = None,
        name: Optional[str] = None,
    ):
        self._alloc = StreamingAllocator(method, model, config=config)
        self.name = name or self._alloc.name

    def reset(self, n_apps: int, rng: np.random.Generator, machine=None) -> None:
        super().reset(n_apps, rng, machine)
        self._alloc.reset(machine, rng)

    def schedule(self, quantum, samples, prev_pairs):
        if not self._have_samples(samples) or not prev_pairs:
            return self._random_pairs()
        counters = self._counters_array(samples)
        active = np.arange(self.n_apps, dtype=np.int64)
        ran = np.ones(self.n_apps, bool)
        pairs, solo = self._alloc.pair(
            quantum, active, counters, ran, arrived=(), departed=(),
            prev_pairs=[tuple(p) for p in prev_pairs], prev_solo=None,
        )
        assert solo is None, "closed populations are even"
        return pairs
