"""Shared benchmark environment: one profiling campaign + fitted models,
cached on disk so every per-figure benchmark reuses the same §5.4 models.

Model caches are stamped with :data:`repro.smt.training.RNG_STREAM_VERSION`:
the fitted coefficients depend on the profiling campaign's RNG-stream
interleaving, so a cache written under a different interleaving (e.g. the
pre-vectorisation seed campaign) would silently skew every downstream
figure.  :func:`get_env` refuses to load such caches and refits instead.
"""

from __future__ import annotations

import json
import os
import pickle
import time
from typing import Dict, Optional, Tuple

import numpy as np

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")
os.makedirs(RESULTS_DIR, exist_ok=True)

_CACHE = os.path.join(RESULTS_DIR, "synpa_models.pkl")
_CACHE_FAST = os.path.join(RESULTS_DIR, "synpa_models_fast.pkl")

#: Home of the JAX persistent compilation cache when
#: ``JAX_COMPILATION_CACHE_DIR`` is not set.  A fixed path: the directory
#: is part of what a later process must find again.
COMPILE_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), os.pardir, ".jax_cache")
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Repeated bench/smoke invocations then stop paying the multi-second
    ``jit`` warm-up for races an earlier *process* already compiled.
    ``JAX_COMPILATION_CACHE_DIR`` places the cache (JAX reads it itself,
    and no other directory is set here); without it the cache is the
    checkout's ``.jax_cache/``.  JAX's own
    ``JAX_ENABLE_COMPILATION_CACHE=false`` turns it off, e.g. to measure
    cold compiles.  A cache that cannot be set up raises.  The cache key
    includes the XLA backend and version, so upgrades invalidate
    naturally rather than deserialising stale executables.
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = COMPILE_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # Every race here is worth caching: the open-system scan compiles for
    # tens of seconds at N=256, and the smoke tier's small races still
    # dominate its wall time.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def _load_cache(path: str):
    """Load a model cache; return None when missing, unstamped or stale.

    A valid payload is ``{"rng_stream_version": V, "models": {...}}`` with
    ``V`` equal to the current :data:`training.RNG_STREAM_VERSION`.  The
    seed repo's caches were bare model dicts (no stamp) fitted on the
    pre-vectorised RNG stream — those are refused, not migrated.
    """
    from repro.smt.training import RNG_STREAM_VERSION

    if not os.path.exists(path):
        return None
    try:
        with open(path, "rb") as f:
            payload = pickle.load(f)
    except Exception:
        print(f"# refusing unreadable model cache {os.path.basename(path)}; "
              "refitting")
        return None
    if not isinstance(payload, dict) or "rng_stream_version" not in payload:
        print(f"# refusing unstamped model cache {os.path.basename(path)} "
              "(pre-vectorisation RNG stream); refitting")
        return None
    if payload["rng_stream_version"] != RNG_STREAM_VERSION:
        print(f"# refusing model cache {os.path.basename(path)}: rng stream "
              f"v{payload['rng_stream_version']} != v{RNG_STREAM_VERSION}; "
              "refitting")
        return None

    from repro.core import regression
    import jax.numpy as jnp

    return {
        name: regression.CategoryModel(
            coeffs=jnp.asarray(c), mse=jnp.asarray(m), n_categories=n)
        for name, (c, m, n) in payload["models"].items()
    }


def _save_cache(path: str, models) -> None:
    from repro.smt.training import RNG_STREAM_VERSION

    payload = {
        "rng_stream_version": RNG_STREAM_VERSION,
        "models": {
            name: (np.asarray(m.coeffs), np.asarray(m.mse), m.n_categories)
            for name, m in models.items()
        },
    }
    # Write-then-rename so an interrupted dump never leaves a truncated
    # cache behind (the loader refuses unreadable files, but why make one).
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        pickle.dump(payload, f)
    os.replace(tmp, path)


def get_env(force: bool = False, fast: bool = False):
    """(machine, models, workloads_dict) — cached across benchmarks.

    ``fast=True`` fits on a shorter profiling campaign (own cache file) —
    the --smoke path of the benchmark entry points, where model fidelity
    matters less than wall time.
    """
    from repro.smt import machine as mc
    from repro.smt import training, workloads

    enable_compile_cache()
    machine = mc.SMTMachine(mc.MachineParams(), seed=0)
    wls = workloads.make_workloads(machine)
    cache = _CACHE_FAST if fast else _CACHE
    if not force:
        models = _load_cache(cache)
        if models is not None:
            return machine, models, wls
    t0 = time.time()
    kw = dict(solo_quanta=20, pair_quanta=4) if fast else dict(
        solo_quanta=60, pair_quanta=12)
    models, _data = training.build_all_models(machine, **kw)
    _save_cache(cache, models)
    print(f"# fitted SYNPA models in {time.time() - t0:.1f}s (cached)")
    return machine, models, wls


def save_json(name: str, obj) -> str:
    path = os.path.join(RESULTS_DIR, name)
    with open(path, "w") as f:
        json.dump(obj, f, indent=2)
    return path


def load_json(name: str):
    path = os.path.join(RESULTS_DIR, name)
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return None


# ---------------------------------------------------------------------------
# Version stamps for recorded A/Bs.  A recorded median is only comparable
# to a re-measurement when both ran under the same RNG stream layouts —
# the same reason the model caches are stamped and refused above.  The
# stamp logic itself lives in ``repro.obs.metrics`` (the run-export
# layer); these wrappers keep the historic benchmark API.
# ---------------------------------------------------------------------------
def version_stamp(engine: Optional[str] = None, faults: bool = False,
                  batched: bool = False,
                  lanes: Optional[int] = None) -> Dict:
    """Stamp dict for a result JSON (``repro.obs.metrics.version_stamp``)."""
    from repro.obs.metrics import version_stamp as _stamp

    return _stamp(engine, faults=faults, batched=batched, lanes=lanes)


def save_stamped(name: str, obj: Dict, engine: Optional[str] = None,
                 faults: bool = False, batched: bool = False,
                 lanes: Optional[int] = None) -> str:
    """``save_json`` with the version stamp merged in.
    ``faults=True`` adds the fault-schedule stream stamp — results of
    fault-injected runs are tied to ``FAULT_RNG_STREAM_VERSION`` too.
    ``batched``/``lanes`` mark lane-batched measurements, which are
    refused when loaded with a single-lane expectation (and vice
    versa).  Payload keys may not collide with stamp keys — a silent
    merge once cost a recorded A/B its whole ``batched`` arm (the
    stamp's ``batched: True`` flag ate the measurement dict), so the
    collision is now an error: nest payload under a sub-dict instead."""
    stamp = version_stamp(engine, faults=faults, batched=batched,
                          lanes=lanes)
    clash = sorted(set(obj) & set(stamp))
    if clash:
        raise ValueError(
            f"save_stamped({name!r}): payload keys {clash} collide with "
            "version-stamp keys; nest them under a sub-dict")
    return save_json(name, {**obj, **stamp})


def load_stamped(name: str, batched: Optional[bool] = None,
                 lanes: Optional[int] = None) -> Optional[Dict]:
    """Load a recorded result; refuse it when its stamps are stale.

    Returns None (and says why) when the file is missing, unstamped, or
    stamped with a different stream version than the current code — a
    recorded A/B under another RNG layout is not comparable and must be
    re-recorded, exactly like a stale model cache is refit.  The checks
    are ``repro.obs.metrics.check_stamp``; ``batched``/``lanes`` state
    the measurement-protocol expectation (see there).
    """
    from repro.obs.metrics import check_stamp

    obj = load_json(name)
    if obj is None:
        return None
    if not check_stamp(obj, label=name, batched=batched, lanes=lanes):
        return None
    return obj


def csv_row(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.1f},{derived}"
