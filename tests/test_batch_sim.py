"""Batched-scenario simulator tests (``repro.online.batch_sim`` +
``repro.smt.scan_engine.run_quanta_multi_batched``).

The load-bearing contract (ISSUE 9): batching is a pure *packaging*
change.  Each lane of a ``vmap``-batched dispatch must be
**f32-bit-identical** to the single dispatch it replaces — divergent
per-lane control flow (admission mode, fault schedules, retry knobs)
rides along as masked data, never as structure — and the lane count is
a shape, not a semantic: any sub-batch reproduces its lanes bit-for-bit.

Also covered: the transfer guard over the batched dispatch, batched
telemetry rings, the stamp layer's refusal to compare batched and
single-lane recordings, and the ``bootstrap_ci``/``GridStats``
aggregation the multi-seed benchmark cells are built on.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import isc, regression
from repro.online import (
    ClusterSim,
    FaultProfile,
    PoissonArrivals,
    SynergyAdmission,
)
from repro.obs import trace as obs_trace
from repro.online import batch_sim
from repro.online.batch_sim import run_device_sim_batched
from repro.online.device_sim import run_device_sim
from repro.smt import machine as mc
from repro.smt import workloads
from repro.smt.apps import pool_profiles
from repro.smt.machine import PhaseTables
from repro.smt.metrics import GridStats, OnlineStats, bootstrap_ci
from repro.smt.scan_engine import (
    ScanPolicy,
    run_quanta_multi_batched,
    run_quanta_scan,
)

QUANTA = 12


def _toy_model(n_categories=4):
    coeffs = np.zeros((4, 4), np.float32)
    coeffs[isc.CAT_DI] = [0.007, 0.91, 0.004, 0.03]
    coeffs[isc.CAT_FE] = [0.02, 1.41, 0.0, 0.0]
    coeffs[isc.CAT_BE] = [0.0, 0.24, 1.07, 0.5]
    coeffs[isc.CAT_HW] = [0.03, 1.22, 0.33, 0.0]
    return regression.CategoryModel(
        coeffs=jnp.asarray(coeffs), mse=jnp.zeros(4),
        n_categories=n_categories,
    )


@pytest.fixture(scope="module")
def machine():
    return mc.SMTMachine(mc.MachineParams(), seed=0)


@pytest.fixture(scope="module")
def pool():
    return pool_profiles()


@pytest.fixture(scope="module")
def model():
    return _toy_model()


@pytest.fixture(scope="module")
def tables(pool):
    return PhaseTables.build(pool)


@pytest.fixture(scope="module")
def spec(model):
    return ScanPolicy(kind="synpa", method=isc.SYNPA4_R_FEBE, model=model)


@pytest.fixture(scope="module")
def synergy(machine, pool, model):
    return SynergyAdmission(machine, pool, isc.SYNPA4_R_FEBE, model,
                            quanta=12)


def _sim(machine, pool, spec, tables, seed, rate=1.4, n_cores=4,
         faults=None, **kw):
    return ClusterSim(
        machine, pool, n_cores, spec,
        PoissonArrivals(rate=rate, n_pool=len(pool)),
        seed=seed, target_scale=0.1, tables=tables, faults=faults,
        engine="scan", **kw,
    )


def _assert_lane_identical(a: OnlineStats, b: OnlineStats):
    """The bit-identity contract: trajectories compare ``==``, not
    approximately."""
    np.testing.assert_array_equal(a.queue_depth, b.queue_depth)
    np.testing.assert_array_equal(a.active, b.active)
    np.testing.assert_array_equal(a.solo_quanta, b.solo_quanta)
    ja = {j.job_id: (j.arrive_q, j.admit_q, j.finish_q, j.retries)
          for j in a.completed}
    jb = {j.job_id: (j.arrive_q, j.admit_q, j.finish_q, j.retries)
          for j in b.completed}
    assert ja == jb


# ------------------------------------------------ open-system bit-identity
class TestBatchedOpenSystem:
    def test_mixed_admission_lanes_bit_identical(
        self, machine, pool, spec, tables, synergy
    ):
        """FIFO and synergy-admission lanes at different seeds and rates
        in ONE dispatch, each bit-identical to its single-dispatch twin.
        The admission divergence is masked data (both rules computed per
        quantum, lane flag selects) — never a second compiled graph."""
        sims = [
            _sim(machine, pool, spec, tables, seed=5, rate=1.2),
            _sim(machine, pool, spec, tables, seed=9, rate=1.8),
            _sim(machine, pool, spec, tables, seed=5, rate=1.2,
                 admission="synergy", synergy=synergy),
            _sim(machine, pool, spec, tables, seed=13, rate=1.8,
                 admission="synergy", synergy=synergy),
        ]
        batched = run_device_sim_batched(sims, QUANTA)
        assert len(batched) == len(sims)
        singles = [run_device_sim(s, QUANTA) for s in sims]
        assert any(s.n_completed > 0 for s in singles)
        for b, s in zip(batched, singles):
            _assert_lane_identical(b, s)

    def test_faulted_lanes_bit_identical(self, machine, pool, spec,
                                         tables):
        """Divergent fault schedules and retry knobs per lane — a crash
        wave, MTTF churn with retries off, and a healthy control — as
        data in one dispatch; fault stats attach only to faulted
        lanes."""
        crash = FaultProfile(fail=((3, 0), (4, 1)), recover=((8, 0),),
                             max_retries=2)
        churn = FaultProfile(mttf_quanta=6.0, mttr_quanta=3.0,
                             max_retries=0, preserve_progress=False)
        sims = [
            _sim(machine, pool, spec, tables, seed=5, faults=crash),
            _sim(machine, pool, spec, tables, seed=7, faults=churn),
            _sim(machine, pool, spec, tables, seed=5),
        ]
        batched = run_device_sim_batched(sims, QUANTA)
        singles = [run_device_sim(s, QUANTA) for s in sims]
        for b, s in zip(batched, singles):
            _assert_lane_identical(b, s)
        assert batched[0].has_faults and batched[1].has_faults
        assert not batched[2].has_faults
        assert batched[0].summary()["n_evicted"] == \
            singles[0].summary()["n_evicted"]

    def test_lane_count_is_shape_not_semantics(self, machine, pool, spec,
                                               tables):
        """Property: any sub-batch reproduces its lanes bit-for-bit —
        the lane axis never leaks into a lane's trajectory."""
        sims = [_sim(machine, pool, spec, tables, seed=s, rate=r)
                for s, r in ((3, 1.2), (5, 1.5), (7, 1.8), (11, 1.2),
                             (13, 1.5))]
        full = run_device_sim_batched(sims, QUANTA)
        sub = run_device_sim_batched([sims[1], sims[3]], QUANTA)
        _assert_lane_identical(full[1], sub[0])
        _assert_lane_identical(full[3], sub[1])
        solo = run_device_sim_batched([sims[2]], QUANTA)
        _assert_lane_identical(full[2], solo[0])

    def test_transfer_guard_over_batched_dispatch(self, machine, pool,
                                                  spec, tables):
        """The batched race dispatches with zero host transfers — the
        whole grid commits up front and the host re-enters only at
        stats extraction."""
        sims = [_sim(machine, pool, spec, tables, seed=s)
                for s in (3, 5, 7)]
        batched = run_device_sim_batched(sims, QUANTA,
                                         transfer_guard=True)
        assert len(batched) == 3

    def test_batched_telemetry_rings(self, machine, pool, spec, tables):
        """Per-lane telemetry rings from one batched dispatch match the
        single-dispatch rings bit-for-bit (telemetry stays a pure
        observer one axis up)."""
        sims = [_sim(machine, pool, spec, tables, seed=s)
                for s in (3, 9)]
        batched = run_device_sim_batched(sims, QUANTA, telemetry=True)
        for b, s in zip(batched, sims):
            single = run_device_sim(s, QUANTA, telemetry=True)
            _assert_lane_identical(b, single)
            assert b.telemetry is not None
            assert b.telemetry.fields == single.telemetry.fields
            np.testing.assert_array_equal(b.telemetry.data,
                                          single.telemetry.data)

    def test_rejects_incompatible_lanes(self, machine, pool, spec,
                                        tables):
        """Lanes that cannot share one compiled graph — different
        capacity, or a different PhaseTables instance — are refused
        loudly, not silently re-padded."""
        a = _sim(machine, pool, spec, tables, seed=3)
        with pytest.raises(AssertionError):
            run_device_sim_batched(
                [a, _sim(machine, pool, spec, tables, seed=5, n_cores=6)],
                QUANTA,
            )
        other = PhaseTables.build(pool)
        with pytest.raises(AssertionError):
            run_device_sim_batched(
                [a, _sim(machine, pool, spec, other, seed=5)], QUANTA,
            )


# ------------------------------------- one dispatch's host-device traffic
EDGE_SEEDS = (0, 1, 2**31 - 1, 2**31, 2**32 - 1, 2**32 + 5, -1)


def _lanes(machine, pool, spec, tables, synergy, mix):
    """Two lanes at seeds 5 and 9: FIFO, synergy or one of each."""
    admissions = {"fifo": ("fifo", "fifo"), "synergy": ("synergy",
                  "synergy"), "mixed": ("fifo", "synergy")}[mix]
    return [_sim(machine, pool, spec, tables, seed=s, rate=1.2,
                 admission=a, synergy=synergy)
            for s, a in zip((5, 9), admissions)]


def _assert_stats_equal(a: OnlineStats, b: OnlineStats):
    """Trajectories, admission and finish quanta, and every log the
    dispatch hands back compare ``==``."""
    _assert_lane_identical(a, b)
    np.testing.assert_array_equal(a.admissions, b.admissions)
    for log in ("telemetry", "app_telemetry"):
        np.testing.assert_array_equal(getattr(a, log).data,
                                      getattr(b, log).data)


def _commit_uploads():
    return [e["args"]["uploads"] for e in obs_trace.events()
            if e["name"] == "batch_sim.commit"]


@pytest.fixture
def _spans_on():
    obs_trace.clear()
    obs_trace.enable()
    yield
    obs_trace.disable()
    obs_trace.clear()


class TestDispatchTraffic:
    @pytest.mark.parametrize("seed", EDGE_SEEDS)
    def test_in_graph_key_equals_eager_prngkey(self, seed):
        """The lane key built inside the jit from the packed seed word is
        bit-equal to the eager ``jax.random.PRNGKey(seed)``."""
        keys = jax.jit(jax.vmap(batch_sim._lane_key))(
            batch_sim._seed_words([seed, 7]))
        np.testing.assert_array_equal(
            np.asarray(keys[0]), np.asarray(jax.random.PRNGKey(seed)))

    def test_seed_outside_agreeing_range_refused(self):
        """A seed whose in-graph key could differ is refused, never
        keyed differently: past int64, and with x64 outside
        ``[0, 2**32)``."""
        with pytest.raises(ValueError, match="outside"):
            batch_sim._seed_words([2**64])
        with jax.enable_x64(True):
            for seed in (-1, 2**32 + 5):
                with pytest.raises(ValueError, match="outside"):
                    batch_sim._seed_words([seed])
            words = batch_sim._seed_words([2**32 - 1])
            keys = jax.jit(jax.vmap(batch_sim._lane_key))(words)
            np.testing.assert_array_equal(
                np.asarray(keys[0]),
                np.asarray(jax.random.PRNGKey(2**32 - 1)))

    @pytest.mark.parametrize("mix", ["fifo", "synergy", "mixed"])
    def test_resident_call_bit_identical(self, machine, pool, spec, tables,
                                         synergy, mix):
        """A second call on resident tables repeats the first bit for bit
        (admissions, finish quanta, both rings and the slot log), and
        each lane still equals its single-dispatch twin."""
        sims = _lanes(machine, pool, spec, tables, synergy, mix)
        kw = dict(warmup=False, app_telemetry=True, slot_log=True)
        first = run_device_sim_batched(sims, QUANTA, **kw)
        second = run_device_sim_batched(sims, QUANTA, **kw)
        for a, b, sim in zip(first, second, sims):
            _assert_stats_equal(a, b)
            np.testing.assert_array_equal(a.slot_log, b.slot_log)
            _assert_stats_equal(
                b, run_device_sim(sim, QUANTA, app_telemetry=True))

    def test_resident_call_moves_two_uploads_one_fetch(
        self, machine, pool, spec, tables, synergy, monkeypatch, _spans_on
    ):
        """With the tables resident a dispatch makes two host-to-device
        transfers (the packed ``int32`` and ``float32`` buffers) and one
        fetch, and uploads no tables."""
        sims = _lanes(machine, pool, spec, tables, synergy, "mixed")
        run_device_sim_batched(sims, QUANTA, warmup=False)
        puts, gets = [], []
        put, get = jax.device_put, jax.device_get

        def counting_put(x, *a, **kw):
            puts.append(len(jax.tree_util.tree_leaves(x)))
            return put(x, *a, **kw)

        def counting_get(x):
            gets.append(x)
            return get(x)

        monkeypatch.setattr(jax, "device_put", counting_put)
        monkeypatch.setattr(jax, "device_get", counting_get)
        obs_trace.clear()
        run_device_sim_batched(sims, QUANTA, warmup=False)
        assert sum(puts) == 2 and len(gets) == 1
        assert _commit_uploads() == [2]
        assert "scan.tables" not in obs_trace.record()

    def test_tables_uploaded_afresh_after_clear_or_new_tables(
        self, machine, pool, spec, tables, _spans_on
    ):
        """``_BATCH_CACHE.clear()`` frees the resident tables, and another
        ``PhaseTables`` instance gets its own upload."""
        sims = [_sim(machine, pool, spec, tables, seed=s) for s in (3, 4)]
        run_device_sim_batched(sims, QUANTA, warmup=False)
        run_device_sim_batched(sims, QUANTA, warmup=False)
        batch_sim._BATCH_CACHE.clear()
        run_device_sim_batched(sims, QUANTA, warmup=False)
        other = PhaseTables.build(pool)
        run_device_sim_batched(
            [_sim(machine, pool, spec, other, seed=s) for s in (3, 4)],
            QUANTA, warmup=False)
        miss = 2 + batch_sim._TABLE_UPLOADS
        assert _commit_uploads()[1:] == [2, miss, miss]
        assert obs_trace.record()["scan.tables"][0].size >= 2


# ------------------------------------------------- closed-race batching
class TestBatchedClosedRace:
    def test_seed_lanes_match_run_quanta_scan(self, machine, model, pool):
        """The closed race over seed lanes (odd N, so the idle-context
        path is in play): every lane equals the single-dispatch
        ``run_quanta_scan`` of that seed to f32 round-off — XLA:CPU may
        lower batched dots/transcendentals with a different SIMD
        reduction tail, so multi-lane equality is last-ulp, not bitwise
        (see the ``run_quanta_multi_batched`` docstring)."""
        profs = pool[:7]
        policies = {
            "static": ScanPolicy(kind="static"),
            "synpa": ScanPolicy(kind="synpa", method=isc.SYNPA4_R_FEBE,
                                model=model),
        }
        seeds = [3, 11, 42]
        batched = run_quanta_multi_batched(
            machine, profs, policies, seeds, n_quanta=8,
        )
        for si, seed in enumerate(seeds):
            single = run_quanta_scan(machine, profs, policies,
                                     n_quanta=8, seed=seed)
            for name in policies:
                b, s = batched[name][si], single[name]
                np.testing.assert_allclose(b.ipc, s.ipc, rtol=1e-6,
                                           atol=0.0)
                assert b.total_retired == pytest.approx(
                    s.total_retired, rel=1e-6)
                assert b.mean_true_slowdown == pytest.approx(
                    s.mean_true_slowdown, rel=1e-6)

    def test_single_lane_batch_is_bitwise(self, machine, model, pool):
        """A one-lane batch is the single dispatch, bit for bit — the
        lane packaging itself adds no arithmetic."""
        profs = pool[:7]
        policies = {
            "synpa": ScanPolicy(kind="synpa", method=isc.SYNPA4_R_FEBE,
                                model=model),
        }
        batched = run_quanta_multi_batched(
            machine, profs, policies, [11], n_quanta=8,
        )
        single = run_quanta_scan(machine, profs, policies, n_quanta=8,
                                 seed=11)
        b, s = batched["synpa"][0], single["synpa"]
        np.testing.assert_array_equal(b.ipc, s.ipc)
        assert b.total_retired == s.total_retired
        assert b.mean_true_slowdown == s.mean_true_slowdown


# ------------------------------------------------------- stamp refusal
class TestBatchedStamps:
    def test_check_stamp_refuses_protocol_mismatch(self):
        from repro.obs.metrics import check_stamp, version_stamp

        batched = version_stamp("device", batched=True, lanes=12)
        single = version_stamp("device")
        assert check_stamp(dict(batched), batched=True, lanes=12)
        assert not check_stamp(dict(batched), batched=False)
        assert not check_stamp(dict(single), batched=True)
        assert not check_stamp(dict(batched), batched=True, lanes=6)
        # No expectation stated: historical behaviour, both accepted.
        assert check_stamp(dict(batched))
        assert check_stamp(dict(single))

    def test_obs_report_refuses_cross_protocol_diff(self):
        import importlib.util
        import os

        path = os.path.join(os.path.dirname(__file__), os.pardir,
                            "tools", "obs_report.py")
        sp = importlib.util.spec_from_file_location("obs_report", path)
        mod = importlib.util.module_from_spec(sp)
        sp.loader.exec_module(mod)
        a = {"batched": True, "lanes": 12, "metrics": {}}
        b = {"metrics": {}}
        assert mod._protocol_mismatch(a, b) is not None
        assert mod._protocol_mismatch(a, dict(a)) is None
        assert mod._protocol_mismatch(
            a, {"batched": True, "lanes": 6, "metrics": {}}
        ) is not None


# ------------------------------------------- multi-seed aggregation layer
class TestSeedAggregation:
    def test_bootstrap_ci_properties(self):
        point, lo, hi = bootstrap_ci([2.0])
        assert point == lo == hi == 2.0
        rng = np.random.default_rng(0)
        vals = rng.normal(10.0, 1.0, size=30)
        point, lo, hi = bootstrap_ci(vals)
        assert lo <= point <= hi
        assert point == pytest.approx(float(np.mean(vals)))
        assert hi - lo < 2.0          # interval tightens with the sample
        # Seeded: the interval is reproducible.
        assert bootstrap_ci(vals) == (point, lo, hi)
        nan_triple = bootstrap_ci([])
        assert all(np.isnan(v) for v in nan_triple)

    def test_grid_stats_summary_shape(self, machine, pool, spec, tables):
        """Cell summaries keep metric means as top-level floats (the
        single-seed reader contract) with the CIs under ``"ci"``."""
        gs = GridStats()
        for seed in (3, 9):
            gs.add("cell", run_device_sim(
                _sim(machine, pool, spec, tables, seed=seed), QUANTA))
        summ = gs.summary()["cell"]
        assert summ["seeds"] == 2
        assert isinstance(summ["mean_slowdown"], float)
        lo, hi = summ["ci"]["mean_slowdown"]
        assert lo <= summ["mean_slowdown"] <= hi
        assert gs.pooled_slowdowns("cell").size == \
            sum(s.n_completed for s in gs.cells["cell"])
