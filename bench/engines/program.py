"""Inputs of the system under test, built from the benchmark's files.

The program gets the configuration's app pool, machine constants and Eq. 4
coefficients as its own types; it fits nothing and reads none of its own
recorded results.
"""

from __future__ import annotations


def profiles(pool: dict):
    """The pool's apps as ``repro.smt.apps.AppProfile`` objects."""
    from repro.smt.apps import AppProfile, Phase

    return [
        AppProfile(
            name=a["name"],
            phases=tuple(Phase(p["x_fe"], p["x_be"], p["x_hw"], p["fill"],
                               int(p["duration"])) for p in a["phases"]),
            omega=a["omega"], retire=a["retire"], mem_sens=a["mem_sens"],
            fetch_sens=a["fetch_sens"])
        for a in pool["apps"]
    ]


def machine_params(cfg: dict):
    from repro.smt.machine import MachineParams

    return MachineParams(**cfg["machine"])


def model(cfg: dict):
    """The configuration's Eq. 4 model (Table 3 coefficients)."""
    import jax.numpy as jnp

    from repro.core.regression import CategoryModel

    pm = cfg["policy_model"]
    return CategoryModel(coeffs=jnp.asarray(pm["coeffs"], jnp.float32),
                         mse=jnp.asarray(pm["mse"], jnp.float32),
                         n_categories=4)


def method(cfg: dict):
    from repro.core import isc

    return isc.STACK_METHODS[cfg["policy_model"]["method"]]


def scan_policy(kind: str, cfg: dict, name=None):
    """A ``ScanPolicy`` of the kinds the traffic files name."""
    from repro.smt.scan_engine import ScanPolicy

    if kind == "synpa":
        return ScanPolicy(kind="synpa", method=method(cfg), model=model(cfg),
                          name=name)
    return ScanPolicy(kind=kind, name=name)
