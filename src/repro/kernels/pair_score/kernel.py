"""Pallas TPU kernel: all-pairs Eq. 4 slowdown scoring (paper Step 2).

At cluster scale the SYNPA policy re-scores every pair of N runnable jobs
each quantum: O(N^2 * C) fused multiply-adds plus clipping.  The kernel
tiles the (N, N) pair grid into (BM, BN) VMEM blocks.  The i-side stacks
arrive as a (BM, C) block and the j-side stacks as a (C, BN) block of the
transposed (C, N) stack matrix, so each category is a column of one and a
row of the other: both broadcast across the tile without a relayout.  The
tiny (C, 4) coefficient table lives in SMEM and is read as scalars, and
the C-category reduction is unrolled (C = 4).  VPU-only (no MXU) — the op
is elementwise-dominated, so the roofline here is HBM bandwidth on the
(N, N) output: one pass, fully fused, versus 5+ materialised
intermediates for the naive XLA lowering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pair_score.ref import DIAG, MAX_SLOWDOWN, MIN_SLOWDOWN

BLOCK = 128


def _pair_score_kernel(coeffs_ref, st_i_ref, st_jt_ref, out_ref, *,
                       n_categories: int, n_total: int, block: int):
    """One (BM, BN) tile of the pair-cost matrix."""
    bi = pl.program_id(0)
    bj = pl.program_id(1)
    st_i = st_i_ref[...]          # (BM, C) f32
    st_jt = st_jt_ref[...]        # (C, BN) f32

    bm = st_i.shape[0]
    bn = st_jt.shape[1]
    s_ij = jnp.zeros((bm, bn), jnp.float32)
    s_ji = jnp.zeros((bm, bn), jnp.float32)
    # Unrolled category loop: each term is rank-1 in the tile -> stays VPU.
    for cat in range(n_categories):
        a = coeffs_ref[cat, 0]
        b = coeffs_ref[cat, 1]
        g = coeffs_ref[cat, 2]
        r = coeffs_ref[cat, 3]
        xi = st_i[:, cat:cat + 1]             # (BM, 1)
        xj = st_jt[cat:cat + 1, :]            # (1, BN)
        cross = xi * xj
        s_ij += jnp.maximum(a + b * xi + g * xj + r * cross, 0.0)
        s_ji += jnp.maximum(a + b * xj + g * xi + r * cross, 0.0)
    s_ij = jnp.clip(s_ij, MIN_SLOWDOWN, MAX_SLOWDOWN)
    s_ji = jnp.clip(s_ji, MIN_SLOWDOWN, MAX_SLOWDOWN)
    cost = s_ij + s_ji

    # Diagonal (self-pairing) and padding rows/cols get the sentinel.
    rows = bi * block + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
    cols = bj * block + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
    invalid = (rows == cols) | (rows >= n_total) | (cols >= n_total)
    out_ref[...] = jnp.where(invalid, DIAG, cost)


def pair_score_pallas(st, coeffs, n_categories: int = 4,
                      block: int = BLOCK, interpret: bool = False,
                      n_valid: int = None):
    """st: (N, C) f32 (N padded to ``block`` by ops.py); coeffs: (C, 4).

    ``n_valid`` is the unpadded application count: rows/cols at or past it
    are padding and receive the ``DIAG`` sentinel (defaults to N, i.e. no
    padding).
    """
    n, c = st.shape
    assert n % block == 0, "ops.py pads N to the block size"
    n_valid = n if n_valid is None else n_valid
    st = st.astype(jnp.float32)
    kernel = functools.partial(
        _pair_score_kernel, n_categories=n_categories, n_total=n_valid,
        block=block)
    # Every (i, j) tile is independent: mark both grid dims parallel so
    # Mosaic is free to reorder/overlap tiles.
    return pl.pallas_call(
        kernel,
        grid=(n // block, n // block),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block, c), lambda i, j: (i, 0)),
            pl.BlockSpec((c, block), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block, block), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(coeffs.astype(jnp.float32), st, st.T)
