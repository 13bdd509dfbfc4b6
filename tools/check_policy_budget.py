"""Policy-time regression guard: warm-streaming + scan SYNPA4 at N=256.

Measures the steady-state (median) policy wall-time per quantum of the
default ``StreamingScheduler`` on a closed N=256 population — the fused
per-quantum dispatch plus the incremental matcher — the per-quantum
wall time of the single-dispatch scan engine
(``repro.smt.scan_engine.run_quanta_scan``, machine+policy indivisible),
the per-quantum wall time of the device-resident open system
(``ClusterSim(engine="scan")`` on a rho=1.0 churn cell, one dispatch per
run — **faults off**, so this number is the steady-state guard the
fault-injection PR holds itself to), the same cell with a light
``FaultProfile`` injected (the fault path compiles extra mask work into
the race; this arm keeps its cost honest), *and* the telemetry-ring
overhead of the scan engine
(``telemetry=True`` vs off on the same race) — and fails (exit 1) if any
timing regresses more than ``MAX_REGRESSION``x over the recorded
baseline in ``benchmarks/results/policy_time_n256.json``.

The baseline is a stamped :mod:`repro.obs.metrics` run export — the
``metrics`` block holds the comparable numbers and the RNG stream
stamps ride at the top level; a baseline recorded under different
stream layouts (or schema) is refused and must be re-recorded.  Each
timing is recorded with a seeded bootstrap interval over its
back-to-back passes (``<key>_ci_lo``/``<key>_ci_hi``); the guard
compares the live value against the *CI upper edge* times
``MAX_REGRESSION`` — noise widens the interval instead of faking a
tight baseline — falling back to the point estimate for pre-interval
baselines.  The
recorded ``telemetry_overhead_x`` must come in at or under
``TELEMETRY_BUDGET_X`` (the ISSUE's 1.10x contract) — ``--record``
retries the measurement and refuses to write a baseline that breaches
it, and ``tests/test_obs.py`` asserts the recorded value stays inside
the budget.

Beyond timing, the guard also measures prediction *accuracy*: a small
open churn cell per seed in ``ACC_SEEDS`` runs with the per-app rings
on (``app_telemetry=True``) and the cross-seed overall Eq.4 MAPE is
guarded against the recorded baseline with the tight
``ACC_REGRESSION`` budget — accuracy carries no wall-clock jitter, so a
breach means the policy's predictions actually got worse, not that the
box was busy.  The whole measurement runs under ``repro.obs.trace`` so
the baseline records its compile/steady split
(``compile_total_ms``/``compile_spans`` next to the steady medians).

Run via ``tools/run_bench_smoke.sh`` (and the slow-marked
``tests/test_bench_smoke.py``), so a change that quietly de-fuses the hot
path — or breaks the scan loop back into per-quantum dispatches, or
makes the telemetry ring expensive, or silently degrades the pair
predictor — cannot land without tier-1 noticing.  ``--record``
refreshes the baseline instead of checking against it (use after an
intentional change, on an otherwise quiet machine) and appends the
recorded export as one line to the append-only
``benchmarks/results/history/policy_time_n256.jsonl`` ledger, trended
by ``tools/perf_history.py``.

The measurement uses the fast-campaign models (the smoke tier's cache):
model coefficients only steer *which* local minimum the solver walks to,
not how much work a quantum costs, and the fast cache keeps the guard
inside the smoke-tier time budget.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)

BASELINE = os.path.join(_ROOT, "benchmarks", "results",
                        "policy_time_n256.json")
#: Append-only ledger of recorded baselines (one JSON line per
#: ``--record``), trended by ``tools/perf_history.py``.
HISTORY = os.path.join(_ROOT, "benchmarks", "results", "history",
                       "policy_time_n256.jsonl")
N_APPS = 256
N_QUANTA = 12          # median over the horizon absorbs the compile quantum
SCAN_REPEATS = 3       # scan: median over re-dispatches (compile excluded)
MAX_REGRESSION = 2.0
#: Recorded telemetry-on / telemetry-off dispatch-time ratio budget.
TELEMETRY_BUDGET_X = 1.10
#: Prediction-accuracy guard cell: a small open-system churn cell per
#: seed, rings on, overall Eq.4 MAPE aggregated across seeds.
ACC_SEEDS = (13, 17, 19)
ACC_QUANTA = 40
ACC_CORES = 8
ACC_RATE = 1.5
#: Allowed live-MAPE growth over the recorded baseline's CI upper edge.
#: Accuracy is deterministic given the stamps (no wall-clock jitter), so
#: the budget is much tighter than the 2x timing headroom — it exists to
#: absorb genuine model-cache refreshes, not measurement noise.
ACC_REGRESSION = 1.25


def measure(record: bool = False) -> dict:
    """Best-of-two measurement of the engines' steady per-quantum cost.

    The dev container's wall-clock jitter under load spikes exceeds the
    2x regression budget; taking the minimum over two back-to-back runs
    de-flakes the guard (a load spike inflates a run, a real regression
    inflates both) while the defects this guard exists for — a de-fused
    hot path, a scan loop broken back into per-quantum dispatches — are
    order-of-magnitude, not 2x.  ``record=True`` adds up to two extra
    passes over the telemetry pair when jitter pushes the overhead ratio
    past its budget, so a recorded baseline never starts life in breach.
    """
    from benchmarks.common import get_env
    from benchmarks.online_churn import TARGET_SCALE, mean_service_quanta
    from repro.core import isc
    from repro.obs import accuracy as obs_accuracy
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace
    from repro.online import (
        ClusterSim,
        FaultProfile,
        PoissonArrivals,
        StreamingScheduler,
    )
    from repro.smt import workloads
    from repro.smt.apps import pool_profiles
    from repro.smt.scan_engine import ScanPolicy

    machine, models, _ = get_env(fast=True)
    method = isc.SYNPA4_R_FEBE
    model = models["SYNPA4_R-FEBE"]
    profs = workloads.scaled_workload(N_APPS, seed=N_APPS)
    pool = pool_profiles()
    device_spec = ScanPolicy(kind="synpa", method=method, model=model)
    # The device-sim steady-state cell: rho=1.0 traffic at N=256 capacity
    # under the benchmark grid's own mean-service mapping, so the guard
    # always measures the published cell.  One sim (and one PhaseTables
    # build) serves both guard iterations; the compiled race is cached.
    rate = N_APPS / mean_service_quanta(machine)
    dev_sim = ClusterSim(
        machine, pool, N_APPS // 2, device_spec,
        PoissonArrivals(rate=rate, n_pool=len(pool)),
        seed=11, target_scale=TARGET_SCALE, engine="scan",
    )
    # Same cell with a light fault profile (MTTF/MTTR draws + one
    # straggler window): guards the compiled-in fault path's cost.  The
    # faults-off ``dev_sim`` above stays the steady-state guard.
    fault_sim = ClusterSim(
        machine, pool, N_APPS // 2, device_spec,
        PoissonArrivals(rate=rate, n_pool=len(pool)),
        seed=11, target_scale=TARGET_SCALE, engine="scan",
        faults=FaultProfile(
            mttf_quanta=4.0 * N_QUANTA, mttr_quanta=N_QUANTA / 2,
            straggle=((0, 2, N_QUANTA, 0.5),),
        ),
    )

    def scan_race(telemetry: bool) -> float:
        res = machine.run_quanta_multi(
            profs,
            {"synpa4-scan": ScanPolicy(kind="synpa", method=method,
                                       model=model)},
            n_quanta=N_QUANTA, seed=3, engine="scan", repeats=SCAN_REPEATS,
            telemetry=telemetry,
        )["synpa4-scan"]
        return res.machine_s_per_quantum * 1e6

    # The span record gives the measurement's compile/steady split
    # (compile cost is real user-visible latency but must never leak
    # into the steady medians the guard compares): what it gains from
    # here to the end.
    spans_before = obs_trace.breakdown()

    samples: dict = {
        "stream_median_us": [],
        "stream_mean_us": [],
        "device_sim_median_us": [],
        "scan_total_median_us": [],
        "scan_telemetry_median_us": [],
        "device_sim_faulted_median_us": [],
    }
    for _ in range(2):
        res = machine.run_quanta_multi(
            profs,
            {"synpa4-stream": lambda: StreamingScheduler(method, model)},
            n_quanta=N_QUANTA,
            seed=3,
        )["synpa4-stream"]
        dev = dev_sim.run(N_QUANTA, repeats=SCAN_REPEATS)
        samples["stream_median_us"].append(
            res.sched_s_per_quantum_median * 1e6)
        samples["stream_mean_us"].append(res.sched_s_per_quantum * 1e6)
        samples["device_sim_median_us"].append(
            float(np.median(dev.policy_s)) * 1e6)
    # The scan arms re-jit per call (no race cache in the closed engine),
    # so each runs once — the median over SCAN_REPEATS re-dispatches
    # inside the call is the de-flake; only ``--record`` pays for extra
    # passes, and only when jitter pushed the ratio past its budget.
    samples["scan_total_median_us"].append(scan_race(telemetry=False))
    samples["scan_telemetry_median_us"].append(scan_race(telemetry=True))
    faulted = fault_sim.run(N_QUANTA, repeats=SCAN_REPEATS)
    samples["device_sim_faulted_median_us"].append(
        float(np.median(faulted.policy_s)) * 1e6)
    if record:
        for _ in range(2):
            if (min(samples["scan_telemetry_median_us"])
                    / min(samples["scan_total_median_us"])
                    <= TELEMETRY_BUDGET_X):
                break
            samples["scan_total_median_us"].append(
                scan_race(telemetry=False))
            samples["scan_telemetry_median_us"].append(
                scan_race(telemetry=True))
    # Prediction-accuracy arm: a small open churn cell per seed with the
    # per-app rings on; the guard metric is the cross-seed mean of each
    # run's overall Eq.4 MAPE (deterministic given the stamps — the CI
    # covers seed-to-seed workload spread, not clock noise).
    acc_mapes, acc_worsts = [], []
    for s in ACC_SEEDS:
        cell = ClusterSim(
            machine, pool, ACC_CORES, device_spec,
            PoissonArrivals(rate=ACC_RATE, n_pool=len(pool)),
            seed=s, target_scale=TARGET_SCALE, engine="scan",
        )
        st = cell.run(ACC_QUANTA, app_telemetry=True)
        rep = obs_accuracy.accuracy_report(st.app_telemetry)
        acc_mapes.append(rep["overall"]["mape"])
        acc_worsts.append(max(
            (v["mape"] for v in rep["per_app"].values()), default=0.0))

    # Point estimate stays best-of-passes (a load spike inflates one
    # pass, a real regression inflates all); the bootstrap interval over
    # the passes is what the guard compares against — a noisy baseline
    # carries a wide CI instead of a falsely tight point.
    from repro.smt.metrics import bootstrap_ci

    metrics = {}
    for key, vals in samples.items():
        point = float(min(vals))
        _, lo, hi = bootstrap_ci(vals, stat=np.min)
        metrics[key] = point
        metrics[key + "_ci_lo"] = lo
        metrics[key + "_ci_hi"] = hi
    metrics["telemetry_overhead_x"] = (
        metrics["scan_telemetry_median_us"]
        / metrics["scan_total_median_us"]
    )
    point = float(np.mean(acc_mapes))
    _, lo, hi = bootstrap_ci(acc_mapes, stat=np.mean)
    metrics["acc_open_mape"] = point
    metrics["acc_open_mape_ci_lo"] = lo
    metrics["acc_open_mape_ci_hi"] = hi
    metrics["acc_open_mape_worst_app"] = float(np.mean(acc_worsts))
    # The compile/steady split: total wall spent in compile-tagged spans
    # across the measurement (a cold persistent cache pays it, a warm one
    # mostly skips it) next to the steady medians above.
    compile_rows = [
        (v["total_us"] - spans_before.get(k, {}).get("total_us", 0.0),
         v["count"] - spans_before.get(k, {}).get("count", 0))
        for k, v in obs_trace.breakdown().items() if "compile" in k]
    metrics["compile_total_ms"] = float(
        sum(us for us, _n in compile_rows) / 1e3)
    metrics["compile_spans"] = float(sum(n for _us, n in compile_rows))
    return obs_metrics.export_run(
        name="policy_time_n256",
        engine="scan",
        metrics=metrics,
        meta={"n": N_APPS, "quanta": N_QUANTA, "repeats": SCAN_REPEATS,
              "acc_seeds": list(ACC_SEEDS), "acc_quanta": ACC_QUANTA,
              "ci": "seeded percentile bootstrap over back-to-back "
                    "passes, stat=min (timings) / mean (accuracy)"},
        faults=True,
    )


def append_history(run: dict, path: str = HISTORY) -> str:
    """Append one JSON line for a recorded baseline to the perf ledger.

    The ledger is append-only — every ``--record`` adds a line (stamps,
    the full metric block with CI bounds, and the compile/steady split)
    and never rewrites old ones, so ``tools/perf_history.py`` can trend
    steady cost and prediction accuracy across the PR sequence even as
    the baseline file itself is overwritten in place.
    """
    import json

    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(run, sort_keys=True) + "\n")
    return path


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--record", action="store_true",
                    help="write the measurement as the new baseline")
    args = ap.parse_args()

    from repro.obs import metrics as obs_metrics

    run = measure(record=args.record)
    got = run["metrics"]
    if args.record:
        if got["telemetry_overhead_x"] > TELEMETRY_BUDGET_X:
            print(
                f"policy_guard: refusing to record a baseline with "
                f"telemetry overhead {got['telemetry_overhead_x']:.3f}x "
                f"> {TELEMETRY_BUDGET_X:.2f}x budget", file=sys.stderr,
            )
            return 1
        obs_metrics.save_run(BASELINE, run)
        append_history(run)
        print(f"policy_guard: recorded baseline "
              f"{got['stream_median_us']:.0f} us/quantum (median, N={N_APPS})"
              f", scan {got['scan_total_median_us']:.0f} us/quantum, "
              f"device sim {got['device_sim_median_us']:.0f} us/quantum, "
              f"telemetry overhead {got['telemetry_overhead_x']:.3f}x, "
              f"open MAPE {got['acc_open_mape']:.2%} "
              f"(compile {got['compile_total_ms']:.0f} ms across "
              f"{got['compile_spans']:.0f} spans); history -> "
              f"{os.path.relpath(HISTORY, _ROOT)}")
        return 0

    # The guard *diffs against* (and --record overwrites) the baseline:
    # write path, so a schema-v1 baseline is refused with a re-record
    # notice instead of being compared across schemas.
    base_run = obs_metrics.load_run(BASELINE, write=True)
    if base_run is None:
        print(f"policy_guard: no usable baseline at {BASELINE} (missing, "
              "stale-stamped or pre-obs format); run with --record first",
              file=sys.stderr)
        return 1
    base = base_run["metrics"]

    def _guard(key: str, label: str) -> bool:
        if key not in base:
            print(f"policy_guard: baseline has no {label} entry; run "
                  "--record to start guarding it")
            return True
        # Compare against the baseline CI's upper edge, not the point
        # estimate: a baseline recorded under jitter carries its noise
        # as interval width instead of tripping the guard later.  Old
        # baselines without interval fields fall back to the point.
        anchor = max(base[key], base.get(key + "_ci_hi", base[key]))
        b = anchor * MAX_REGRESSION
        good = got[key] <= b
        tag = "ci-hi" if key + "_ci_hi" in base else "point"
        print(
            f"policy_guard: {label} N={N_APPS} median "
            f"{got[key]:.0f} us/quantum vs baseline {base[key]:.0f} "
            f"({tag} budget {b:.0f}) -> {'OK' if good else 'REGRESSION'}"
        )
        return good

    ok = _guard("stream_median_us", "warm-streaming")
    scan_ok = _guard("scan_total_median_us", "scan-engine")
    tlm_ok = _guard("scan_telemetry_median_us", "scan-telemetry")
    device_ok = _guard("device_sim_median_us", "device-sim (faults off)")
    faults_ok = _guard("device_sim_faulted_median_us",
                       "device-sim (faults on)")
    # The live overhead ratio gets the same 2x jitter headroom as the
    # absolute timings; the strict 1.10x contract binds the *recorded*
    # value (enforced at --record time and by tests/test_obs.py).
    ratio_budget = TELEMETRY_BUDGET_X * MAX_REGRESSION
    ratio_ok = got["telemetry_overhead_x"] <= ratio_budget
    print(
        f"policy_guard: telemetry overhead "
        f"{got['telemetry_overhead_x']:.3f}x vs recorded "
        f"{base.get('telemetry_overhead_x', float('nan')):.3f}x "
        f"(live budget {ratio_budget:.2f}x) -> "
        f"{'OK' if ratio_ok else 'REGRESSION'}"
    )
    # Prediction-accuracy arm: same CI-anchored machinery as the timing
    # guards, but with the tight ACC_REGRESSION budget — MAPE carries no
    # wall-clock jitter, so growth past the recorded CI edge means the
    # model/policy surface actually got less accurate.
    if "acc_open_mape" not in base:
        print("policy_guard: baseline has no accuracy entry; run "
              "--record to start guarding prediction error")
        acc_ok = True
    else:
        anchor = max(base["acc_open_mape"],
                     base.get("acc_open_mape_ci_hi",
                              base["acc_open_mape"]))
        budget = anchor * ACC_REGRESSION
        acc_ok = got["acc_open_mape"] <= budget
        print(
            f"policy_guard: open-cell MAPE {got['acc_open_mape']:.2%} vs "
            f"baseline {base['acc_open_mape']:.2%} "
            f"(ci-hi budget {budget:.2%}) -> "
            f"{'OK' if acc_ok else 'ACCURACY REGRESSION'}"
        )
    return 0 if (ok and scan_ok and tlm_ok and device_ok and faults_ok
                 and ratio_ok and acc_ok) else 1


if __name__ == "__main__":
    sys.exit(main())
