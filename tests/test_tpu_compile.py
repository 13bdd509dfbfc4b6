"""Ahead-of-time compiles of the main path's Pallas kernel for a TPU v5e.

Nothing here runs on a chip: the TPU compiler, which is installed with
JAX, compiles ``pair_score`` for a *described* v5e device, so what Mosaic
would refuse on the chip (tiling, VMEM limits, unsupported relayouts)
fails here.  The topology is described inside a module fixture, never at
import: only one process at a time may load the TPU library, and under
several pytest workers only the worker that runs this file may try.
"""

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.pair_score import ops

C = 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache off.
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n,n_valid", [
    (256, None),
    (1024, None),
    (1000, None),    # padded to 1024 in the wrapper, masked in the kernel
    (1032, 1024),    # the fused step's padded shape at N = 1024
])
def test_pair_costs_compiles_for_v5e(one_chip, n, n_valid):
    fn = jax.jit(lambda st, co: ops.pair_costs(
        st, co, impl="pallas", n_valid=n_valid))
    compiled = fn.lower(_spec((n, C), one_chip),
                        _spec((C, 4), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_vmapped_pair_costs_compiles_for_v5e(one_chip):
    """The lane-batched open system vmaps the fused step, so the
    ``pallas_call`` is batched: 4 lanes at the 128-core padded shape."""
    fn = jax.jit(jax.vmap(
        lambda st, co: ops.pair_costs(st, co, impl="pallas", n_valid=256),
        in_axes=(0, None)))
    compiled = fn.lower(_spec((4, 264, C), one_chip),
                        _spec((C, 4), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _big_gathers(hlo_text, min_elems):
    """Result shapes of the ``gather`` instructions (fused ones included)
    in an optimized HLO module that hold ``min_elems`` elements or more."""
    found = []
    for m in re.finditer(r"= \w+\[([\d,]*)\]\S* gather\(", hlo_text):
        dims = [int(d) for d in m.group(1).split(",") if d]
        if math.prod(dims) >= min_elems:
            found.append(m.group(0))
    return found


def test_device_matcher_has_no_element_gathers_for_v5e(one_chip):
    """The 2-opt round reads the cost matrix through one-hot products on
    the MXU; a gather of q*q elements with computed indices runs as a
    near-serial loop on the TPU and must not come back.  Gathers of (P,)
    vectors outside the loop may stay."""
    from repro.core import matching

    p, lanes = 136, 8
    q = p // 2
    two_opt = jax.jit(jax.vmap(functools.partial(
        matching.device_two_opt_partner, with_rounds=True)))
    lowered = [
        two_opt.lower(_spec((lanes, p, p), one_chip),
                      _spec((lanes, p), one_chip, jnp.int32),
                      _spec((lanes, p), one_chip, jnp.bool_)),
        matching._device_pairs_jit.lower(
            _spec((p, p), one_chip), _spec((p,), one_chip, jnp.bool_),
            eps=1e-9, max_rounds=None),
    ]
    for low in lowered:
        hlo = low.compile().as_text()
        assert "while" in hlo
        assert _big_gathers(hlo, q * q) == []
