"""Median host time of the served allocator's decision: over the
``alloc.pair`` spans that hold a ``matcher.wait`` span, the median of the
decision less that wait (masks, hint scatter, the fused step's argument
transfers and dispatch, the matcher's dispatch, unpacking).  Read from the
program's own span record (``repro.obs.trace``) after the window; the
record also holds the set-up's one scenario, a few dozen decisions
beside about 6,000 in the window.  A program without the record reads
nothing."""

import numpy as np


def read(run):
    try:
        from repro.obs import trace
        pair, wait, n = trace.contained("alloc.pair", "matcher.wait")
    except (ImportError, AttributeError):
        return None
    if not np.any(n):
        return None
    return float(np.median((pair - wait)[n > 0])) * 1e-6
