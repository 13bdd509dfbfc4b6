"""Plain checks of a pairing: a perfect matching, and how far from a 2-opt
local optimum it sits under a cost matrix."""

from __future__ import annotations

import numpy as np


def invalid_slots(partner: np.ndarray) -> int:
    """Contexts whose partner is out of range, itself, or not paired back
    (an even pool is matched perfectly; nobody runs alone)."""
    n = len(partner)
    p = np.asarray(partner, np.int64)
    bad = (p < 0) | (p >= n)
    q = np.where(bad, 0, p)
    bad |= (q == np.arange(n)) | (p[q] != np.arange(n))
    return int(bad.sum())


def swap_gain(cost: np.ndarray, partner: np.ndarray) -> float:
    """Largest drop of the matching's total cost that one exchange of
    partners between two of its pairs would give (0 at a 2-opt optimum)."""
    a = np.flatnonzero(np.arange(len(partner)) < partner)
    b = partner[a]
    cur = cost[a, b]
    here = cur[:, None] + cur[None, :]
    alt = np.minimum(cost[a[:, None], a[None, :]] + cost[b[:, None], b[None, :]],
                     cost[a[:, None], b[None, :]] + cost[b[:, None], a[None, :]])
    gain = here - alt
    np.fill_diagonal(gain, 0.0)
    return float(max(gain.max(), 0.0))
