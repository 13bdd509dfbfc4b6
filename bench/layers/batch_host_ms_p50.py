"""Median host time of one open-engine dispatch: ``batch_sim.run`` less
the ``batch_sim.dispatch`` inside it (pre-sampling, packing, commit,
fetch and the rebuilding of job records).  Read from the program's own
span record (``repro.obs.trace``) after the window; the record also holds
the set-up's one dispatch beside about 78 in the window.  A program
without the record reads nothing."""

import numpy as np


def read(run):
    try:
        from repro.obs import trace
        runs, wait, _n = trace.contained("batch_sim.run",
                                         "batch_sim.dispatch")
    except (ImportError, AttributeError):
        return None
    if runs.size == 0:
        return None
    return float(np.median(runs - wait)) * 1e-6
