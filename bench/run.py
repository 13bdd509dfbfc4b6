"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name from
``BENCHMARK.json``: ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json`` (whose ``engine`` names the module in
``bench/engines/``) and, with ``--trace 1``, one reader per per-layer
metric in ``bench/layers/<metric>.py``.  A run sets up and warms every
shape it will use (``setup_s``), measures for ``--seconds``, checks what
the timed path produced against the plain reference in
``bench/reference/``, and prints one JSON object as its last line.  It
needs a TPU whose kind ``bench/data/peaks.json`` lists; anywhere else it
exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
#: The persistent compilation cache: a fixed path inside the checkout, so
#: that every run of a cell after the first finds its programs.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def use_cache(jax) -> None:
    """Keep every compiled program in ``CACHE_DIR``.  The cache is left
    unbounded whatever the environment says: a bounded cache reads an
    access-time file beside each entry, and one entry without it (written
    by an unbounded cache) makes every later write fail."""
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(spec: dict, workload: str) -> dict:
    """The cell's entries and files: workload, config, traffic, pool,
    limits, and its metrics by kind."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: unknown workload {workload!r}")
    cell = cells[workload]
    config = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = load(os.path.join(ROOT, config["file"]))
    traffic = load(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    pool = load(os.path.join(BENCH, "data", cfg["app_pool"] + ".json"))
    limits = load(os.path.join(BENCH, "limits", cfg["limits"] + ".json"))

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {"cell": cell, "cfg": cfg, "traffic": traffic, "pool": pool,
            "limits": limits,
            "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
            "per_layer": [m for m in spec["per_layer"] if mine(m)]}


class CompileCounter:
    """Programs compiled, or loaded from the persistent cache, from JAX's
    own monitoring events (JAX records its backend-compile event for
    both)."""

    def __init__(self, jax):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.count += 1


class Run:
    """What the per-layer readers read: the reduced trace, the engine's
    telemetry and decision times, the window's compile count, the peaks."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def read_layers(metrics, run: Run) -> dict:
    out = {}
    for m in metrics:
        path = os.path.join(BENCH, "layers", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_layer_" + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def measure(engine, seconds: float, jax, trace: bool):
    """Drive the engine for ``seconds``; return (window_s, xplane path or
    None, profiler directory or None)."""
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        # Host spans come from TraceAnnotation; a trace of every Python
        # call would swamp a host-heavy window.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while time.perf_counter() - t0 < seconds:
            engine.step()
    window_s = time.perf_counter() - t0
    xplane = None
    if trace:
        jax.profiler.stop_trace()
        for dirpath, _d, files in os.walk(tdir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    xplane = os.path.join(dirpath, f)
    return window_s, xplane, tdir


def main(argv=None, require_tpu: bool = True, spec_path: str = None,
         engine_hook=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    for path in (os.path.join(ROOT, "src"), ROOT):
        if path not in sys.path:
            sys.path.insert(0, path)
    spec = load(spec_path or os.path.join(ROOT, "BENCHMARK.json"))
    r = resolve(spec, args.workload)

    import jax

    from bench import device, trace_reduce

    parts = {"imports_s": time.perf_counter() - T_START}

    use_cache(jax)
    if require_tpu:
        try:
            dev = device.check(jax.devices(), int(r["cell"]["chips"]))
        except (device.DeviceError, RuntimeError) as e:
            print(f"bench: {e}", file=sys.stderr)
            return 2
    else:
        d0 = jax.devices()[0]
        dev = {"platform": d0.platform, "kind": d0.device_kind,
               "count": len(jax.devices()), "peaks": {}}

    parts["backend_s"] = time.perf_counter() - T_START - parts["imports_s"]
    mod = importlib.import_module(f"bench.engines.{r['traffic']['engine']}")
    engine = mod.Engine(r["cfg"], r["traffic"], r["pool"], args.seed)
    if engine_hook is not None:
        engine_hook(engine)
    compiles = CompileCounter(jax)
    with jax.profiler.TraceAnnotation("bench.setup"):
        engine.setup()
    setup_s = time.perf_counter() - T_START
    parts.update(engine.parts)
    parts["setup_programs"] = compiles.count

    before = compiles.count
    window_s, xplane, tdir = measure(engine, args.seconds, jax,
                                     bool(args.trace))
    in_window = compiles.count - before
    stats = jax.devices()[0].memory_stats() or {}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()) if stats else 0
    e2e = engine.end_to_end(window_s)
    layer_data = engine.layer_data()
    attempted, failed = engine.attempted(), engine.failed()
    engine.release()
    gc.collect()

    numbers = engine.check()
    # A number that could not be read at all (no co-runner found, no
    # finish) is as far off as a float goes; JSON has no infinity.
    checks = {k: {"value": min(float(v), sys.float_info.max),
                  "limit": float(r["limits"][k])}
              for k, v in numbers.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    device_out = {"platform": dev["platform"], "kind": dev["kind"],
                  "count": dev["count"], "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if args.trace:
        reduced = None
        if xplane is not None:
            reduced = trace_reduce.reduce(trace_reduce.load_xplane(xplane))
        shutil.rmtree(tdir, ignore_errors=True)
        run = Run(trace=reduced, compiles_in_window=in_window,
                  peaks=dev["peaks"], cfg=r["cfg"], cell=r["cell"],
                  **layer_data)
        result["metrics"] = read_layers(r["per_layer"], run)
        if reduced is not None:
            device_out.update(busy_s=reduced["busy_s"],
                              window_s=reduced["window_s"])
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
    else:
        e2e["setup_s"] = setup_s
        # A metric ``<quantity>.<cells>`` is the engine's ``<quantity>``,
        # bounded apart for the cells it lists.
        result["metrics"] = {m["name"]: {
            "value": e2e.get(m["name"], e2e.get(m["name"].split(".")[0])),
            "unit": m["unit"]} for m in r["end_to_end"]}
    result["setup_parts"] = parts
    result["device"] = device_out
    result["checks"] = checks
    print(f"setup parts {json.dumps(parts)}", file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
