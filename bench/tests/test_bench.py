"""Tests of the benchmark harness, on the CPU at small sizes.

    python -m pytest bench/tests -q

They cover: every cell resolves to its files; the harness refuses a
device it cannot measure; the trace reduction, on a small trace recorded
on a v5e chip; a new cell, mix and metric need only new files and entries;
the control (the reference in bfloat16 in the program's place) comes out
not correct while the program comes out correct; and each fault the cells
can have, planted in the timed path's outputs, turns ``correct`` false.
"""

from __future__ import annotations

import copy
import gzip
import importlib
import json
import os
import re
import shutil
import subprocess
import sys
import types

import ml_dtypes
import pytest

from bench import device, run, trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
#: Small sizes a test run holds: servers of the rack, and apps and cores
#: of each server.
SMALL = {"servers": 2, "n_apps": 32, "n_cores": 16}
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.fixture(autouse=True, scope="session")
def _cache_outside_checkout(tmp_path_factory):
    """Runs made in this process keep their CPU programs out of the
    checkout's compilation cache, which the chip's runs use."""
    run.CACHE_DIR = str(tmp_path_factory.mktemp("jax_cache"))


def small_spec(tmp_path) -> str:
    """BENCHMARK.json with each configuration cut to ``SMALL`` (a copy of
    each configuration file under ``tmp_path``)."""
    spec = copy.deepcopy(SPEC)
    for c in spec["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        for k, v in SMALL.items():
            if k in cfg:
                cfg[k] = v
        path = tmp_path / f"{c['name']}.json"
        path.write_text(json.dumps(cfg))
        c["file"] = str(path)
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return str(path)


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_resolves(cell):
    r = run.resolve(SPEC, cell)
    mod = importlib.import_module(f"bench.engines.{r['traffic']['engine']}")
    assert hasattr(mod, "Engine") and mod.FAULTS
    for m in r["per_layer"]:
        assert os.path.exists(os.path.join(run.BENCH, "layers",
                                           m["name"] + ".py"))
    names = [m["name"] for m in r["end_to_end"]]
    assert "setup_s" in names and len(names) >= 2
    assert r["per_layer"]
    assert r["limits"] and r["cfg"]["name"] == r["cell"]["config"]


def test_names_units_and_references():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"]
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    for entry in SPEC["workloads"] + SPEC["configs"]:
        assert NAME.match(entry["name"])
    for w in SPEC["workloads"]:
        assert NAME.match(w["traffic"]) and len(w["why"]) <= 200
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert set(m.get("workloads", cells)) <= cells
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        e = next(x for x in SPEC["end_to_end"] if x["name"] == m["moves"])
        assert set(m.get("workloads", cells)) <= set(e.get("workloads",
                                                           cells))
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def _dev(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_device_refuses_cpu_and_unknown_kind():
    with pytest.raises(device.DeviceError):
        device.check([_dev("cpu", "cpu")], 1)
    with pytest.raises(device.DeviceError):
        device.check([_dev("tpu", "TPU v99")], 1)
    with pytest.raises(device.DeviceError):
        device.check([_dev("tpu", "TPU v5 lite")], 4)
    with pytest.raises(device.DeviceError):
        device.check([], 1)
    got = device.check([_dev("tpu", "TPU v5 lite")], 1)
    assert got["peaks"]["hbm_bytes_per_s"] == 819e9


def test_run_exits_nonzero_without_a_tpu(capsys):
    assert run.main(["--workload", SPEC["workloads"][0]["name"], "--seed",
                     "1", "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


def test_trace_reduce_synthetic():
    t = {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench.window", 0, 100], ["bench.prep", 10, 20],
            ["$x.py:1 f", 10, 20]]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["fusion.1", 30, 20], ["%my_kernel.3 = f32[8] custom-call(%fusion.1)", 40, 30],
            ["fusion.2", 90, 20]]}]},
    ]}
    r = trace_reduce.reduce(t)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(50e-9)   # [30, 70) and [90, 100)
    assert r["idle_gaps"][0] == ["bench.prep", pytest.approx(30e-9)]
    assert r["device_ops"][0] == ["my_kernel.3", pytest.approx(30e-9)]
    assert trace_reduce.reduce({"planes": []}) is None


def test_trace_reduce_ends_with_the_device_record():
    """A record of device ops that stops before the window closes (the
    profiler's bounded event buffer; the device's other lines run on) ends
    the window there, not in a long idle gap."""
    t = {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench.window", 0, 100], ["bench.fetch", 50, 50]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["fusion.1", 10, 20], ["fusion.2", 40, 20]]},
            {"name": "XLA Modules", "events": [["jit_race", 10, 90]]}]},
    ]}
    r = trace_reduce.reduce(t)
    assert r["window_s"] == pytest.approx(60e-9)
    assert r["busy_s"] == pytest.approx(40e-9)


def test_trace_reduce_recorded_chip_trace():
    path = os.path.join(os.path.dirname(__file__), "data",
                        "closed_synpa4_trace.json.gz")
    with gzip.open(path, "rt") as f:
        t = json.load(f)
    r = trace_reduce.reduce(t)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert len(r["device_ops"]) == 10 and r["idle_gaps"]
    assert all(name != "no host span" for name, _s in r["idle_gaps"][:3])


def test_new_cell_mix_and_metric_need_only_files(tmp_path):
    """A copy of the benchmark gains a mix, a cell and a per-layer metric
    by adding files and entries only, and a run reports the metric."""
    work = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), work / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "src"), work / "src")
    spec = json.loads(open(small_spec(tmp_path)).read())
    mix = json.load(open(work / "bench" / "traffic" / "oblivious.json"))
    mix["name"], mix["arms"] = "static-only", {"static": "static"}
    (work / "bench" / "traffic" / "static-only.json").write_text(
        json.dumps(mix))
    (work / "bench" / "layers" / "dispatches.sim.py").write_text(
        "def read(run):\n    return float(len(run.telemetry['rings']"
        "['static']))\n")
    cell = {"name": "closed1024.static", "config": "rack8-closed",
            "traffic": "static-only", "chips": 1, "why": "test"}
    spec["workloads"].append(cell)
    spec["per_layer"].append({
        "name": "dispatches.sim", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "harness",
        "moves": "sim_rate", "workloads": ["closed1024.static"]})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "closed1024.oblivious" in m.get("workloads", []):
            m["workloads"].append("closed1024.static")
    (work / "BENCHMARK.json").write_text(json.dumps(spec))
    code = ("import sys; from bench import run; sys.exit(run.main(["
            "'--workload', 'closed1024.static', '--seed', '4294967311', "
            "'--seconds', '1', '--trace', '1'], require_tpu=False))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false", PYTHONPATH=str(work))
    out = subprocess.run([sys.executable, "-c", code], cwd=work, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["metrics"]["dispatches.sim"]["value"] >= 1


def _engine(tmp_path, cell):
    spec = json.load(open(small_spec(tmp_path)))
    r = run.resolve(spec, cell)
    mod = importlib.import_module(f"bench.engines.{r['traffic']['engine']}")
    return mod, r


def _passes(numbers, limits):
    return all(v <= limits[k] for k, v in numbers.items())


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tmp_path, cell):
    mod, r = _engine(tmp_path, cell)
    engine = mod.Engine(r["cfg"], r["traffic"], r["pool"], 2**33 + 17)
    engine.setup()
    for _ in range(2):
        engine.step()
    assert _passes(engine.check(), r["limits"])
    assert not _passes(engine.check(dtype=ml_dtypes.bfloat16), r["limits"])


FAULT_CASES = [(w["name"], f) for w in SPEC["workloads"]
               for f in importlib.import_module(
                   "bench.engines." + json.load(open(os.path.join(
                       ROOT, "bench", "traffic",
                       w["traffic"] + ".json")))["engine"]).FAULTS]


@pytest.mark.parametrize("cell,fault", FAULT_CASES)
def test_fault_is_not_correct(tmp_path, capsys, cell, fault):
    """A run with the fault planted where the timed path produces its
    outputs (the chip check skipped) prints ``correct: false``."""
    spec = small_spec(tmp_path)
    mod, _r = _engine(tmp_path, cell)

    def plant(engine):
        engine.alter = lambda rec: mod.FAULTS[fault](engine, rec)

    rc = run.main(["--workload", cell, "--seed", "3000000019",
                   "--seconds", "1"], require_tpu=False, spec_path=spec,
                  engine_hook=plant)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
