"""Median decision time of the served allocator over the window, on the
caller's clock around each call (the same timer as ``decision_ms_p95``)."""

import numpy as np


def read(run):
    ms = getattr(run, "decision_ms", None)
    if ms is None or len(ms) == 0:
        return None
    return float(np.percentile(ms, 50))
