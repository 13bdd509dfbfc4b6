"""The per-layer metrics read from the program's own span record, on the
CPU at small sizes.

    python -m pytest bench/tests/test_span_layers.py -q

Each cell that reports one runs traced through ``run.main``; the metric
must be present and positive, and in the served cell each decision's
``alloc.pair`` span (its wait plus its host time) lies within the
caller's own timing of that decision.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from bench import run
# The session fixture keeps this file's CPU programs out of the
# checkout's compilation cache too.
from bench.tests.test_bench import (  # noqa: F401
    SPEC, _cache_outside_checkout, small_spec)

METRICS = {
    "server64.served.rho1": ("decision_wait_ms_p50", "decision_host_ms_p50"),
    "open512.synpa4.rho1": ("batch_host_ms_p50",),
    "closed1024.oblivious": ("tables_ms_p50",),
}


def test_metrics_are_declared():
    declared = {m["name"]: m for m in SPEC["per_layer"]}
    for cell, names in METRICS.items():
        for name in names:
            assert declared[name]["workloads"] == [cell]
            assert declared[name]["source"] == "host_clock"


@pytest.mark.parametrize("cell", sorted(METRICS))
def test_span_metrics_in_traced_run(tmp_path, capsys, cell):
    from repro.obs import trace

    engines = []
    trace.clear()
    rc = run.main(["--workload", cell, "--seed", "3000000019",
                   "--seconds", "1", "--trace", "1"], require_tpu=False,
                  spec_path=small_spec(tmp_path),
                  engine_hook=engines.append)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    for name in METRICS[cell]:
        assert line["metrics"][name]["value"] > 0, name
    if cell == "server64.served.rho1":
        pair, wait, n = trace.contained("alloc.pair", "matcher.wait")
        caller_ns = np.asarray(engines[0].decision_s) * 1e9
        # Every call is one ``alloc.pair`` span; the set-up's scenario
        # comes first, the window's decisions last.
        assert pair.size >= caller_ns.size > 0
        assert np.all(pair[-caller_ns.size:] <= caller_ns)
        assert np.all(wait <= pair) and n.max() == 1
