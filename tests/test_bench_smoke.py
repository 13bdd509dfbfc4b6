"""Benchmark entry points cannot rot: run the --smoke tier under pytest.

Marked ``slow`` so the fast tier stays fast; the smoke script itself is
budgeted to finish in a couple of minutes on the dev container.  The
script also runs the N=256 policy-time guard
(``tools/check_policy_budget.py``): a >2x steady-state regression of the
fused warm-streaming path over the recorded baseline fails the suite.
"""

import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(_ROOT, "tools", "run_bench_smoke.sh")


@pytest.mark.slow
def test_bench_smoke_script_runs():
    res = subprocess.run(
        ["bash", _SCRIPT],
        cwd=_ROOT,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    out = res.stdout
    assert "online_churn," in out, out
    assert "cluster_scale," in out, out
    assert "policy_guard:" in out and "REGRESSION" not in out, out


_CACHE_PROBE = """
import jax
from benchmarks.common import enable_compile_cache
d = enable_compile_cache()
jax.jit(lambda x: x * 3 + 1)(2.0).block_until_ready()
print(d)
print(jax.config.jax_compilation_cache_dir)
"""


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_placement(tmp_path, placed):
    """``JAX_COMPILATION_CACHE_DIR`` places the cache and nothing in code
    overrides it; without it the cache is the checkout's ``.jax_cache``."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(_ROOT, ".jax_cache")
    if placed:
        want = str(tmp_path / "cache")
        env["JAX_COMPILATION_CACHE_DIR"] = want
    res = subprocess.run([sys.executable, "-c", _CACHE_PROBE], cwd=_ROOT,
                         env=env, capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    assert res.stdout.split() == [want, want]
    assert os.listdir(want), "nothing was written to the cache"
