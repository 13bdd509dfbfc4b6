"""Device self-time of one benchmark cell, split by the program's scopes.

    python tools/scope_time.py --workload closed1024.synpa4 --seconds 3 \
        --out scope_closed.json

Sets the cell up as ``bench/run.py`` does, captures a short steady window
with the profiler, and splits each device op's self time (its duration
less that of the ops nested in it on the same line) by the innermost of
the program's ``jax.named_scope`` names in the op's ``op_name``
metadata: ``machine``, ``synpa_step``, ``isc``, ``inverse``,
``pair_cost``, ``matcher`` (``seed``, ``two_opt``, ``repair``),
``admission`` (``synergy``), ``telemetry``; an op with none of them is
``unscoped``,
and an op of a program the run did not compile is ``undumped``.
A TPU op event carries no ``op_name``, so the metadata comes from the
optimized HLO that the run dumps (``--xla_dump_to``; the persistent
compilation cache is turned off so every program compiles and dumps),
by the program the ``XLA Modules`` line names and the op's instruction
name.  Without a TPU the ops of the CPU client's lines stand in, for a
rehearsal.  Prints one JSON object and writes it to ``--out``.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPES = ("machine", "synpa_step", "isc", "inverse", "pair_cost",
          "matcher", "seed", "two_opt", "repair", "admission", "synergy",
          "telemetry")
#: Sub-scopes, reported under their parent's name.
SUB = {"seed": "matcher", "two_opt": "matcher", "repair": "matcher",
       "synergy": "admission"}
OP_NAME = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.-]+) = .*op_name="([^"]*)"')


def scope_of(op_name: str) -> str:
    """The innermost known scope of an ``op_name`` path."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return f"{SUB[part]}/{part}" if part in SUB else part
    return "unscoped"


def hlo_op_names(dump_dir: str) -> dict:
    """``{module: {instruction: op_name}}`` from the dumped optimized HLO."""
    out = {}
    for f in os.listdir(dump_dir):
        if not f.endswith("after_optimizations.txt"):
            continue
        names = out.setdefault(f.split(".", 2)[1], {})
        with open(os.path.join(dump_dir, f)) as fh:
            for line in fh:
                m = OP_NAME.match(line)
                if m:
                    names[m.group(1)] = m.group(2)
    return out


def op_name_of(names: dict, module: str, op: str) -> str:
    """An op's ``op_name``: from its module's dump, else from the one
    module that has an instruction of that name."""
    if op in names.get(module, {}):
        return names[module][op]
    found = [m[op] for m in names.values() if op in m]
    return found[0] if len(found) == 1 else ""


def op_lines(pd):
    """``[[(op, start_ns, dur_ns, stats, module)]]``, one list a line of op
    events: each TPU plane's ``XLA Ops``, each op with the program of the
    plane's ``XLA Modules`` line that holds it; without a TPU, the events
    of the host lines that carry ``hlo_op``, with their ``hlo_module``."""
    out = []
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:TPU:") and "XLA Ops" in lines:
            mods = sorted((float(ev.start_ns), float(ev.start_ns)
                           + float(ev.duration_ns), ev.name.split("(")[0])
                          for ev in (lines["XLA Modules"].events
                                     if "XLA Modules" in lines else ()))
            starts = [m[0] for m in mods]
            evs = []
            for ev in lines["XLA Ops"].events:
                s = float(ev.start_ns)
                j = bisect.bisect_right(starts, s) - 1
                module = mods[j][2] if j >= 0 and s < mods[j][1] else ""
                evs.append((ev.name.split(" = ", 1)[0].lstrip("%"), s,
                            float(ev.duration_ns),
                            dict((k, v) for k, v in ev.stats), module))
            out.append(evs)
    if out:
        return out
    for plane in pd.planes:
        for line in plane.lines:
            evs = []
            for ev in line.events:
                st = dict((k, v) for k, v in ev.stats)
                if "hlo_op" in st:
                    evs.append((str(st["hlo_op"]), float(ev.start_ns),
                                float(ev.duration_ns), st,
                                str(st.get("hlo_module", ""))))
            if evs:
                out.append(evs)
    return out


def self_times(events):
    """``[(event, self_ns)]`` of one line: duration less direct children,
    the line's ops nesting by time."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    stack, kids = [], [0.0] * len(evs)
    for i, (_n, s, d, _st, _m) in enumerate(evs):
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            kids[stack[-1]] += d
        stack.append(i)
    return [(ev, ev[2] - k) for ev, k in zip(evs, kids)]


def busy_ns(events) -> float:
    total, end = 0.0, None
    for _n, s, d, _st, _m in sorted(events, key=lambda e: e[1]):
        if end is None or s >= end:
            total += d
            end = s + d
        elif s + d > end:
            total += s + d - end
            end = s + d
    return total


def reduce(path: str, names: dict) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    stat_keys = set()
    by_scope, unscoped, busy, self_sum = {}, {}, 0.0, 0.0
    for evs in op_lines(pd):
        for ev in evs:
            stat_keys.update(ev[3])
        busy += busy_ns(evs)
        for (name, _s, _d, _st, module), t in self_times(evs):
            op = op_name_of(names, module, name)
            scope = scope_of(op) if op or module in names else "undumped"
            by_scope[scope] = by_scope.get(scope, 0.0) + t
            self_sum += t
            if scope == "unscoped":
                key = f"{name} {op}".strip()
                unscoped[key] = unscoped.get(key, 0.0) + t
    top = sorted(unscoped.items(), key=lambda kv: -kv[1])[:10]
    return {"busy_s": busy * 1e-9, "self_sum_s": self_sum * 1e-9,
            "by_scope_s": {k: v * 1e-9 for k, v in sorted(
                by_scope.items(), key=lambda kv: -kv[1])},
            "unscoped_top": [[k, v * 1e-9] for k, v in top],
            "stat_keys": sorted(stat_keys)}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=8300000001)
    p.add_argument("--seconds", type=float, default=3.0)
    p.add_argument("--spec", default=os.path.join(ROOT, "BENCHMARK.json"))
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)

    dump = tempfile.mkdtemp(prefix="scope_hlo_")
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_dump_to={dump}"
                               " --xla_dump_hlo_as_text").strip()
    for path in (os.path.join(ROOT, "src"), ROOT):
        if path not in sys.path:
            sys.path.insert(0, path)
    import importlib

    import jax

    from bench import run

    # A program loaded from the persistent cache compiles nothing and
    # dumps nothing.
    jax.config.update("jax_enable_compilation_cache", False)

    r = run.resolve(run.load(args.spec), args.workload)
    mod = importlib.import_module(f"bench.engines.{r['traffic']['engine']}")
    engine = mod.Engine(r["cfg"], r["traffic"], r["pool"], args.seed)
    engine.setup()
    tdir = tempfile.mkdtemp(prefix="scope_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < args.seconds:
        engine.step()
    window_s = time.perf_counter() - t0
    jax.profiler.stop_trace()
    xplane = next(os.path.join(d, f) for d, _s, fs in os.walk(tdir)
                  for f in fs if f.endswith(".xplane.pb"))
    out = {"workload": args.workload, "seed": args.seed,
           "window_s": window_s,
           "device": jax.devices()[0].device_kind,
           **reduce(xplane, hlo_op_names(dump))}
    text = json.dumps(out, indent=1)
    print(text)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
