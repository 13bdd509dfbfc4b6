"""Closed rack race: the sweep user's scenario loop.

Each server of the rack runs its own allocator over its own apps, so one
dispatch races one scenario per server as a batched lane: the race is
built once (``repro.smt.scan_engine.build_race`` for one server's pool
and horizon, with its telemetry rings on, so each dispatch also hands back
what the correctness check compares), vmapped over the servers, and
dispatched once per scenario, every arm of the traffic file in the same
dispatch.  The arguments are built as ``run_quanta_scan`` builds them;
the next dispatch is built and queued while the chip runs the current
one.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import traffic_gen
from bench.engines import program
from bench.reference import closed as ref_closed
from bench.reference import matching, smt


def _state_unchanged(engine, rec):
    """Steps return their state unchanged: the machine's retired
    instructions never advance past the first quantum, and a SYNPA arm's
    ST estimates stay at their uniform start."""
    rec["retired"] = rec["retired"] / engine.quanta
    for k, arm in enumerate(engine.arms):
        if engine.traffic["arms"][arm] == "synpa":
            rec["app"][k, :, :, ref_closed.ST] = 0.25


def _half_batch(engine, rec):
    """The slowdown statistic is the mean over half of the contexts."""
    real = rec["app"][..., ref_closed.REAL]
    rec["slow"] = real[..., : engine.n // 2].mean(-1).sum(-1)


def _answer_altered(engine, rec):
    """The last quantum's answer is altered where it is produced: its
    pairing becomes another perfect matching (a SYNPA arm's: a random one;
    an oblivious arm's: two pairs exchange partners)."""
    app = rec["app"]
    rng = np.random.default_rng(0)
    for k, arm in enumerate(engine.arms):
        p = app[k, -1, :, ref_closed.PARTNER]
        if engine.traffic["arms"][arm] == "synpa":
            perm = rng.permutation(engine.n)
            p[perm[0::2]], p[perm[1::2]] = perm[1::2], perm[0::2]
        else:
            a, b = 0, int(p[0])
            c = next(i for i in range(engine.n) if i not in (a, b))
            d = int(p[c])
            p[a], p[b], p[c], p[d] = c, d, a, b


#: Faults the closed race can have, planted in its outputs (fault tests
#: and ``bench/control.py``); each must turn ``correct`` false.
FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}


#: Dispatches of a window whose outputs the check replays, every server's.
SAMPLES = 2


class Engine:
    def __init__(self, cfg: dict, traffic: dict, pool: dict, seed: int):
        self.cfg, self.traffic, self.pool, self.seed = cfg, traffic, pool, seed
        self.n = int(cfg["n_apps"])
        self.lanes = int(cfg["servers"])
        self.quanta = int(cfg["quanta_per_scenario"])
        self.arms = list(traffic["arms"])
        #: Fault tests plant a fault here: a function that alters one
        #: server's fetched outputs of a dispatch where they are produced.
        self.alter = None
        self.reset(seed)

    # -- the system under test -------------------------------------------
    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.core.synpa import fused_pad
        from repro.smt.machine import PhaseTables
        from repro.smt.scan_engine import build_race, _uniform_stacks

        self.jax = jax
        t0 = time.perf_counter()
        self.pool_tables = PhaseTables.build(program.profiles(self.pool))
        self.params = program.machine_params(self.cfg)
        self.specs = [program.scan_policy(self.traffic["arms"][arm], self.cfg)
                      for arm in self.arms]
        self.p_pad = fused_pad(self.n)
        t1 = time.perf_counter()
        race = build_race(
            self.tables(np.zeros(self.n, np.int64)), self.params,
            self.specs, self.quanta, telemetry=True, app_telemetry=True)
        self.race = jax.jit(jax.vmap(race))
        self.keys = jax.jit(jax.vmap(jax.random.PRNGKey))
        # Every scenario starts from the uniform ST stacks.
        self.st0 = jnp.asarray(np.stack([np.stack(
            [_uniform_stacks(spec, self.n) for spec in self.specs])]
            * self.lanes), jnp.float32)
        prepped = self._prep(-1)
        t2 = time.perf_counter()
        self._collect(self._launch(prepped))
        self.parts = {"tables_s": t1 - t0, "build_s": t2 - t1,
                      "warm_s": time.perf_counter() - t2}

    def tables(self, picks):
        """The servers' ``PhaseTables``, stacked: the pool's rows of each
        server's apps (``picks`` is servers x apps), so every scenario has
        the pool's phase count and the race's shapes."""
        t = self.pool_tables
        arrays = {f.name: getattr(t, f.name)[picks]
                  for f in dataclasses.fields(t) if f.name != "n_apps"}
        return dataclasses.replace(t, n_apps=picks.shape[-1], **arrays)

    def _prep(self, index: int) -> tuple:
        """Dispatch ``index``'s scenarios, one a server, on the device."""
        jax = self.jax
        import jax.numpy as jnp

        from repro.smt.scan_engine import DeviceTables, _initial_mpart

        with jax.profiler.TraceAnnotation("bench.prep"):
            sseeds = traffic_gen.lane_seeds(self.seed, index, self.lanes)
            picks = np.stack([traffic_gen.closed_picks(self.pool, self.n, s)
                              for s in sseeds])
            mpart = np.stack([np.stack([
                _initial_mpart(self.n, self.p_pad,
                               np.random.default_rng(s + 7919))
                for _ in self.specs]) for s in sseeds])
            seeds = np.asarray(sseeds, np.int32)
            args = (DeviceTables.build(self.tables(picks)),
                    jnp.asarray(mpart, jnp.int32), self.st0,
                    self.keys(seeds), self.keys(seeds + 7919))
        return index, sseeds, picks, args

    def _launch(self, prepped: tuple) -> tuple:
        """Start a dispatch; the chip runs it while the host goes on."""
        with self.jax.profiler.TraceAnnotation("bench.dispatch"):
            out = self.race(*prepped[3])
        return prepped[:3] + (out,)

    def _collect(self, launched: tuple) -> dict:
        index, sseeds, picks, out = launched
        with self.jax.profiler.TraceAnnotation("bench.fetch"):
            retired, _cycles, slow, tlm, app = (
                np.array(o) for o in self.jax.device_get(out))
        return {"index": index, "lanes": [
            {"sseed": s, "picks": picks[k], "retired": retired[k],
             "slow": slow[k], "tlm": tlm[k], "app": app[k]}
            for k, s in enumerate(sseeds)]}

    def reset(self, seed: int) -> None:
        """Start a fresh window on another seed (same compiled race)."""
        self.seed, self.records, self.running = seed, [], None
        self.sampled = traffic_gen.Sample(seed, SAMPLES)

    def step(self) -> None:
        """Collect one dispatch, with the next one already queued behind
        it on the chip: the host builds and launches dispatch k + 1, then
        waits for dispatch k."""
        index = len(self.records)
        if self.running is None:
            self.running = self._launch(self._prep(index))
        ahead = self._launch(self._prep(index + 1))
        rec = self._collect(self.running)
        self.running = ahead
        if self.alter is not None:
            for lane in rec["lanes"]:
                self.alter(lane)
        self.sampled.offer(rec)
        # Only the sampled dispatches keep their per-context ring.
        self.records.append([{k: v for k, v in lane.items() if k != "app"}
                             for lane in rec["lanes"]])

    def lane_records(self):
        return [lane for rec in self.records for lane in rec]

    def end_to_end(self, window_s: float) -> dict:
        lanes = self.lane_records()
        work = len(lanes) * len(self.arms) * self.n * self.quanta
        slow = np.mean([r["slow"] / self.quanta for r in lanes])
        return {"sim_rate": work / window_s, "slowdown_mean": float(slow)}

    def attempted(self) -> int:
        return len(self.records)

    def failed(self) -> int:
        return 0

    def layer_data(self) -> dict:
        """For the per-layer readers: each arm's telemetry ring over the
        window, ``(dispatches x servers, quanta, fields)``, with the field
        names."""
        from repro.obs.telemetry import CLOSED_FIELDS

        lanes = self.lane_records()
        return {"telemetry": {
            "fields": CLOSED_FIELDS,
            "rings": {self.traffic["arms"][arm]: np.stack(
                [r["tlm"][k] for r in lanes])
                for k, arm in enumerate(self.arms)}},
            "contexts": self.n * self.lanes}

    def release(self) -> None:
        self.race = self.running = None

    # -- the check --------------------------------------------------------
    def outputs(self, rec: dict, arm: int) -> dict:
        """What the program produced for one arm of one dispatch."""
        app = rec["app"][arm]
        return {"partners": ref_closed.partners_of(app),
                "real": app[..., ref_closed.REAL],
                "retired": rec["retired"][arm], "slow": rec["slow"][arm],
                "st": app[..., ref_closed.ST],
                "pred": app[..., ref_closed.PRED]}

    def replay(self, rec: dict, kind: str, partners, dtype=np.float64):
        """One arm of one scenario recomputed under the program's
        pairings: the float64 reference, or with a lower ``dtype`` the
        control, which takes the program's place in the comparison."""
        coef = np.asarray(self.cfg["policy_model"]["coeffs"], dtype)
        tables = smt.PoolTables.from_pool(self.pool, rec["picks"])
        noise, phase = ref_closed.draws(
            rec["sseed"], self.quanta, self.n,
            lambda ph: tables.duration[np.arange(self.n),
                                       ph % tables.n_phases])
        rp = ref_closed.Replay(tables, self.cfg["machine"], noise, phase,
                               dtype)
        real, retired, slow, stacks = rp.run(partners)
        out = {"partners": partners, "real": real, "retired": retired,
               "slow": slow}
        if kind != "synpa":
            return out
        st = np.full((self.quanta, self.n, 4), 0.25)
        res = np.full((self.quanta, self.n), np.nan)
        for q in range(1, self.quanta):
            st[q], res[q] = ref_closed.solve_pairs(
                coef, stacks[q - 1], partners[q - 1], dtype)
        out.update(st=st, res=res, pred=np.stack([
            ref_closed.pred_of(coef, st[q], partners[q], dtype)
            for q in range(self.quanta)]))
        return out

    def check(self, dtype=None) -> dict:
        """The compared numbers, worst over a seed-drawn sample of the
        window's dispatches.  With ``dtype`` the control takes the
        program's place: the reference in that precision."""
        coef = np.asarray(self.cfg["policy_model"]["coeffs"])
        worst: dict = {"bad_pairing": 0}
        rows = []
        for rec in (lane for d in self.sampled.items for lane in d["lanes"]):
            for k, arm in enumerate(self.arms):
                kind = self.traffic["arms"][arm]
                out = self.outputs(rec, k)
                worst["bad_pairing"] += self.bad_pairing(
                    kind, out["partners"], rec["sseed"])
                ref = self.replay(rec, kind, out["partners"])
                if dtype is not None:
                    out = self.replay(rec, kind, out["partners"], dtype)
                nums = ref_closed.compare(out, ref, coef)
                if "st_rows" in nums:
                    rows.append(nums.pop("st_rows"))
                for name, v in nums.items():
                    worst[name] = max(worst.get(name, 0.0), v)
        if rows:
            rows = np.concatenate(rows)
            worst.update(st_gap=float(rows.max()),
                         st_gap_p50=float(np.median(rows)))
        return worst

    def bad_pairing(self, kind: str, partners, sseed: int) -> int:
        """Contexts not perfectly matched in some quantum, plus contexts a
        policy moved against its rule: the static arm keeps its first
        pairing (a random perfect matching drawn as the host schedulers
        draw theirs), the Linux-like arm migrates at most one pair of
        apps per quantum."""
        bad = sum(matching.invalid_slots(p) for p in partners)
        if kind == "static":
            perm = np.random.default_rng(sseed + 7919).permutation(self.n)
            first = np.empty(self.n, np.int64)
            first[perm[0::2]], first[perm[1::2]] = perm[1::2], perm[0::2]
            bad += int(sum((p != first).sum() for p in partners))
        if kind == "linux":
            bad += int(sum(max((a != b).sum() - 4, 0)
                           for a, b in zip(partners, partners[1:])))
        return bad
