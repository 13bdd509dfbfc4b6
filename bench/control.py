"""Readings that set the limits of ``correct``, at a cell's own size.

    python bench/control.py --workload <name> --seeds <k> [--first <seed>]

For each of ``k`` seeds, in one process that owns the chip: the program's
numbers (what a run compares), the control's (the plain reference in
bfloat16 put in the program's place, one precision below the
configuration's float32), and each planted fault's (the timed path's
outputs altered where they are produced).  Prints one JSON line per seed
and a summary: the largest program reading and the smallest control and
fault readings of each number.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None, require_tpu: bool = True, spec_path: str = None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--first", type=int, default=5_000_000_000)
    p.add_argument("--dispatches", type=int, default=2)
    p.add_argument("--fault-seeds", type=int, default=3)
    args = p.parse_args(argv)
    for path in (os.path.join(ROOT, "src"), ROOT):
        if path not in sys.path:
            sys.path.insert(0, path)
    import importlib

    import jax
    import ml_dtypes

    from bench import device, run

    spec = run.load(spec_path or os.path.join(ROOT, "BENCHMARK.json"))
    r = run.resolve(spec, args.workload)
    run.use_cache(jax)
    if require_tpu:
        device.check(jax.devices(), int(r["cell"]["chips"]))
    mod = importlib.import_module(f"bench.engines.{r['traffic']['engine']}")
    engine = mod.Engine(r["cfg"], r["traffic"], r["pool"], args.first)
    engine.setup()
    rows = []
    for s in range(args.seeds):
        seed = args.first + 7919 * s
        row = {"seed": seed}
        engine.reset(seed)
        for _ in range(args.dispatches):
            engine.step()
        row["program"] = engine.check()
        row["control"] = engine.check(dtype=ml_dtypes.bfloat16)
        for name, fault in (mod.FAULTS.items() if s < args.fault_seeds
                            else ()):
            engine.reset(seed)
            engine.alter = lambda rec, f=fault: f(engine, rec)
            for _ in range(args.dispatches):
                engine.step()
            engine.alter = None
            row[name] = engine.check()
        print(json.dumps(row), flush=True)
        rows.append(row)
    summary = {"limits": r["limits"], "program_max": {}, "lowest": {}}
    for k in r["limits"]:
        summary["program_max"][k] = max(x["program"].get(k, 0.0)
                                        for x in rows)
        for kind in ["control"] + list(mod.FAULTS):
            summary["lowest"].setdefault(kind, {})[k] = min(
                x[kind].get(k, 0.0) for x in rows if kind in x)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
