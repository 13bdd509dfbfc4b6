"""Median time the served allocator's decision waits on the device: over
the ``alloc.pair`` spans that hold a ``matcher.wait`` span, the median of
that wait (the fused step, the matcher and the device-to-host copy of the
pairing, as far as the host still waits for them there).  Read from the
program's own span record (``repro.obs.trace``) after the window; the
record also holds the set-up's one scenario, a few dozen decisions
beside about 6,000 in the window.  A program without the record reads
nothing."""

import numpy as np


def read(run):
    try:
        from repro.obs import trace
        _pair, wait, n = trace.contained("alloc.pair", "matcher.wait")
    except (ImportError, AttributeError):
        return None
    if not np.any(n):
        return None
    return float(np.median(wait[n > 0])) * 1e-6
