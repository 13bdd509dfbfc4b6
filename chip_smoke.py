"""Drive the SYNPA main path once on one TPU chip and check what comes out.

Run from the repository root, in one process that owns the chip:

    python chip_smoke.py

Phases, in order (any failure exits non-zero; there is no CPU path):

1. device    — JAX must be on a TPU, ``pair_impl="auto"`` must resolve to
               the Pallas ``pair_score`` kernel at every padded size below,
               and the kernel must match the pure-jnp reference on chip.
2. models    — fit the SYNPA models from the seed (``benchmarks.common``)
               with the persistent compilation cache on, and check them
               against the recorded Table 3 coefficients.
3. closed    — the N = 1024 cluster race (``benchmarks/cluster_scale.py``
               line-up: synpa4 / linux / random) in one transfer-guarded
               ``lax.scan`` dispatch; the static arm must agree with the
               numpy machine within rel 0.03, SYNPA4 must beat both
               oblivious arms, every statistic must be finite.
4. parity    — host ``ClusterSim`` vs the device engine at 128 cores on the
               deterministic-trajectory contract (adjacent pairing,
               single-phase pool): equal counts and queue depths, finish
               quanta within rel 1e-4.
5. open      — a SYNPA4 device ``ClusterSim`` at 512 cores (1024 contexts)
               on the rho = 1.0 churn cell under the transfer guard; it must
               complete jobs and conserve them.
6. batched   — a 2 rho x 2 admission grid at 128 cores as one vmapped
               dispatch; every lane bit-identical to its single dispatch.

Each phase prints one ``#`` line with the device kind, the backend compile
seconds and the steady milliseconds per simulated quantum of this run.
They are readings of this run on this chip, not benchmark results.  The
last line of standard output is the JSON verdict.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

CLOSED_N = 1024           # benchmarks/cluster_scale.py SIZES[-1]
PARITY_CORES = 128        # tests/test_device_sim.py acceptance size
OPEN_CORES = 512          # 1024 contexts, the churn grid's largest cell
BATCH_CORES = 128
BATCH_RHOS = (0.85, 1.2)  # benchmarks/online_churn.py record_batched_ab
STATIC_REL = 0.03         # tests/test_scan_engine.py aggregate contract
FINISH_REL = 1e-4         # tests/test_device_sim.py trajectory contract
TABLE3_ATOL = 1e-3        # fitted coefficients vs the recorded Table 3


def require(ok: bool, what: str) -> None:
    """Fail the run (exit status 1, message on stderr) unless ``ok``."""
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def report(phase: str, kind: str, compile_s: float, ms_per_quantum=None,
           **extra) -> None:
    """One labelled reading line per phase (never the last line)."""
    row = {"phase": phase, "device_kind": kind,
           "backend_compile_s": compile_s}
    if ms_per_quantum is not None:
        row["steady_ms_per_quantum"] = ms_per_quantum
    row.update(extra)
    print(f"# chip reading, this run: {json.dumps(row)}", flush=True)


class CompileClock:
    """Backend compile seconds, summed from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.seconds += duration

    def take(self) -> float:
        """Seconds since the last ``take``."""
        seconds, self.seconds = self.seconds, 0.0
        return seconds


def phase_device(sizes):
    """The chip and the kernel: TPU only, Pallas at every padded size."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.synpa import fused_pad
    from repro.kernels.pair_score import ops
    from repro.kernels.pair_score.ref import DIAG, pair_cost_ref

    backend = jax.default_backend()
    require(backend == "tpu", f"JAX backend is {backend!r}, not a TPU")
    dev = jax.devices()[0]
    require(dev.platform == "tpu", f"device 0 is {dev.platform!r}")
    for n in sizes:
        p = fused_pad(n)
        impl = ops.resolve_impl("auto", p)
        require(impl == "pallas",
                f"pair_impl='auto' resolves to {impl!r} at padded N={p}")

    n = max(sizes)
    p = fused_pad(n)
    rng = np.random.default_rng(0)
    st = jnp.asarray(rng.dirichlet(np.ones(4), size=p), jnp.float32)
    coeffs = jnp.asarray(rng.normal(0.3, 0.5, (4, 4)), jnp.float32)
    got = np.asarray(ops.pair_costs(st, coeffs, impl="auto", n_valid=n))
    want = np.asarray(pair_cost_ref(st[:n], coeffs))
    require(got.shape == (p, p), f"kernel output shape {got.shape}")
    require(np.allclose(got[:n, :n], want, rtol=2e-5, atol=2e-5),
            "Pallas pair_score disagrees with pair_cost_ref on chip "
            f"(max abs diff {np.abs(got[:n, :n] - want).max()})")
    require(np.all(got[n:, :] == DIAG) and np.all(got[:, n:] == DIAG),
            "padding rows/cols do not carry the DIAG sentinel")
    return dev


def phase_models():
    """Fit every SYNPA variant on the chip; compare with Table 3."""
    import numpy as np

    from benchmarks.common import get_env, load_json

    machine, models, _wls = get_env(force=True)
    table3 = load_json("table3_model.json")
    require(table3 is not None, "benchmarks/results/table3_model.json missing")
    worst = 0.0
    for name, model in models.items():
        nc = model.n_categories
        got = np.asarray(model.coeffs)[:nc]
        ref = np.asarray(table3[name]["coeffs"])
        require(np.isfinite(got).all(), f"{name}: non-finite coefficients")
        worst = max(worst, float(np.abs(got - ref).max()))
    require(worst <= TABLE3_ATOL,
            f"fitted coefficients differ from Table 3 by {worst:.3g} "
            f"(limit {TABLE3_ATOL})")
    return machine, models, worst


def phase_closed(machine, models, n: int, quanta: int):
    """The cluster race in one dispatch, against the numpy machine."""
    import numpy as np

    from benchmarks.cluster_scale import _scan_policies
    from repro.core.baselines import RandomStaticScheduler
    from repro.smt import workloads

    profs = workloads.scaled_workload(n, seed=n)
    res = machine.run_quanta_multi(
        profs, _scan_policies(models), n_quanta=quanta, seed=3,
        engine="scan", transfer_guard=True, repeats=3,
    )
    for name, r in res.items():
        require(r.n_apps == n, f"closed {name}: {r.n_apps} apps")
        require(np.isfinite(r.ipc).all() and (r.ipc > 0).all(),
                f"closed {name}: non-finite or zero IPC")
        require(np.isfinite([r.mean_true_slowdown, r.ipc_geomean]).all()
                and r.mean_true_slowdown >= 1.0,
                f"closed {name}: bad aggregate statistics")
    ref = machine.run_quanta(profs, RandomStaticScheduler(),
                             n_quanta=quanta, seed=3)
    st = res["random"]
    for metric in ("mean_true_slowdown", "ipc_geomean"):
        a, b = getattr(st, metric), getattr(ref, metric)
        require(abs(a - b) <= STATIC_REL * abs(b),
                f"closed static {metric}: scan {a} vs numpy {b}")
    syn = res["synpa4"].mean_true_slowdown
    for other in ("random", "linux"):
        require(syn < res[other].mean_true_slowdown,
                f"closed: synpa4 slowdown {syn} does not beat {other} "
                f"{res[other].mean_true_slowdown}")
    return res, ref


def _churn_arrivals(machine, pool, n_ctx: int, rho: float):
    from benchmarks.online_churn import mean_service_quanta
    from repro.online import PoissonArrivals

    return PoissonArrivals(rate=rho * n_ctx / mean_service_quanta(machine),
                           n_pool=len(pool))


def phase_parity(machine, n_cores: int, quanta: int):
    """Host/device trajectory parity (adjacent pairing, single phase)."""
    import dataclasses

    import numpy as np

    from benchmarks.online_churn import TARGET_SCALE
    from repro.online import AdjacentOnline, ClusterSim
    from repro.smt.apps import pool_profiles
    from repro.smt.scan_engine import ScanPolicy

    pool1 = [dataclasses.replace(p, phases=(p.phases[0],))
             for p in pool_profiles()]
    sims = [
        ClusterSim(machine, pool1, n_cores, policy,
                   _churn_arrivals(machine, pool1, 2 * n_cores, 1.0),
                   seed=11, target_scale=TARGET_SCALE, **kw)
        for policy, kw in ((AdjacentOnline(), {}),
                           (ScanPolicy(kind="adjacent"), {"engine": "scan"}))
    ]
    hs = sims[0].run(quanta)
    ds = sims[1].run(quanta, repeats=3, transfer_guard=True)
    counts = [(s.n_arrived, s.n_admitted, s.n_completed) for s in (hs, ds)]
    require(counts[0] == counts[1],
            f"parity counts host {counts[0]} vs device {counts[1]}")
    require(ds.n_completed > 0, "parity: no job completed")
    require(np.array_equal(hs.queue_depth, ds.queue_depth),
            "parity: queue_depth trajectories differ")
    hf = {r.job_id: r.finish_q for r in hs.completed}
    df = {r.job_id: r.finish_q for r in ds.completed}
    require(hf.keys() == df.keys(), "parity: completed job sets differ")
    bad = [j for j in hf
           if abs(hf[j] - df[j]) > FINISH_REL * max(abs(hf[j]), 1.0)]
    require(not bad, f"parity: {len(bad)} finish quanta beyond rel "
            f"{FINISH_REL}")
    return ds


def phase_open(machine, models, n_cores: int, quanta: int):
    """A SYNPA4 pool on the device engine under the transfer guard."""
    from benchmarks.online_churn import TARGET_SCALE
    from repro.core import isc
    from repro.online import ClusterSim
    from repro.smt.apps import pool_profiles
    from repro.smt.scan_engine import ScanPolicy

    pool = pool_profiles()
    spec = ScanPolicy(kind="synpa", method=isc.SYNPA4_R_FEBE,
                      model=models["SYNPA4_R-FEBE"], name="synpa4-device")
    sim = ClusterSim(machine, pool, n_cores, spec,
                     _churn_arrivals(machine, pool, 2 * n_cores, 1.0),
                     seed=11, target_scale=TARGET_SCALE, engine="scan")
    s = sim.run(quanta, repeats=3, transfer_guard=True)
    require(s.n_completed > 0, "open: no job completed")
    # Every arrival is exactly one of queued, running or completed.  The
    # queue depth and occupancy are the last quantum's in-graph counters
    # (after admission, before departures); the completions come from the
    # job log.
    queued = int(s.queue_depth[-1])
    running = int(s.active[-1] - s.departures[-1])
    require(s.n_arrived == queued + running + s.n_completed,
            f"open: conservation {s.n_arrived} != {queued} queued + "
            f"{running} running + {s.n_completed} completed")
    require(s.n_admitted == running + s.n_completed,
            f"open: {s.n_admitted} admitted != {running} running + "
            f"{s.n_completed} completed")
    return s


def phase_batched(machine, models, n_cores: int, quanta: int):
    """The churn grid's lane batching: bit-identical to single dispatch."""
    from benchmarks.online_churn import TARGET_SCALE, _lanes_bit_identical
    from repro.core import isc
    from repro.online import ClusterSim, SynergyAdmission
    from repro.online.batch_sim import run_device_sim_batched
    from repro.online.device_sim import run_device_sim
    from repro.smt.apps import pool_profiles
    from repro.smt.machine import PhaseTables
    from repro.smt.scan_engine import ScanPolicy

    method, model = isc.SYNPA4_R_FEBE, models["SYNPA4_R-FEBE"]
    pool = pool_profiles()
    tables = PhaseTables.build(pool)
    synergy = SynergyAdmission(machine, pool, method, model)
    spec = ScanPolicy(kind="synpa", method=method, model=model,
                      name="synpa4-device")
    sims = [
        ClusterSim(machine, pool, n_cores, spec,
                   _churn_arrivals(machine, pool, 2 * n_cores, rho),
                   seed=11, target_scale=TARGET_SCALE, tables=tables,
                   engine="scan", **kw)
        for rho in BATCH_RHOS
        for kw in ({}, {"admission": "synergy", "synergy": synergy})
    ]
    batched = run_device_sim_batched(sims, quanta, repeats=3,
                                     transfer_guard=True)
    singles = [run_device_sim(s, quanta) for s in sims]
    require(any(s.n_completed > 0 for s in singles),
            "batched: no lane completed a job")
    for i, (b, s) in enumerate(zip(batched, singles)):
        require(_lanes_bit_identical(b, s),
                f"batched: lane {i} differs from its single dispatch")
    return batched


def main() -> int:
    import jax

    from benchmarks.cluster_scale import QUANTA as CLOSED_QUANTA
    from benchmarks.common import enable_compile_cache
    from benchmarks.online_churn import QUANTA as OPEN_QUANTA

    enable_compile_cache()
    clock = CompileClock()
    dev = phase_device((CLOSED_N, 2 * OPEN_CORES, 2 * BATCH_CORES))
    kind = dev.device_kind
    report("device", kind, clock.take(), count=len(jax.devices()))

    machine, models, worst = phase_models()
    report("models", kind, clock.take(), table3_max_abs_diff=worst)

    q = CLOSED_QUANTA[CLOSED_N]
    res, ref = phase_closed(machine, models, CLOSED_N, q)
    report("closed", kind, clock.take(),
           res["synpa4"].machine_s_per_quantum * 1e3, n=CLOSED_N, quanta=q,
           mean_true_slowdown={k: r.mean_true_slowdown
                               for k, r in res.items()},
           numpy_static_slowdown=ref.mean_true_slowdown)

    q = OPEN_QUANTA[2 * PARITY_CORES]
    ds = phase_parity(machine, PARITY_CORES, q)
    report("parity", kind, clock.take(), float(ds.policy_s[0]) * 1e3,
           cores=PARITY_CORES, quanta=q, completed=ds.n_completed)

    q = OPEN_QUANTA[2 * OPEN_CORES]
    s = phase_open(machine, models, OPEN_CORES, q)
    report("open", kind, clock.take(), float(s.policy_s[0]) * 1e3,
           cores=OPEN_CORES, quanta=q, arrived=s.n_arrived,
           completed=s.n_completed, mean_slowdown=s.mean_slowdown)

    q = OPEN_QUANTA[2 * BATCH_CORES]
    lanes = phase_batched(machine, models, BATCH_CORES, q)
    # The lanes share one dispatch: this is the whole grid's wall time per
    # simulated quantum.
    report("batched", kind, clock.take(),
           float(lanes[0].policy_s[0]) * len(lanes) * 1e3,
           cores=BATCH_CORES, quanta=q, lanes=len(lanes),
           completed=[b.n_completed for b in lanes])

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
