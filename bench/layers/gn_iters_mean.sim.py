"""Mean Gauss-Newton steps per pair solve of the Eq. 4 inverse over the
window's SYNPA quanta (telemetry ring field ``gn_iters_mean``)."""

import numpy as np


def read(run):
    tlm = getattr(run, "telemetry", None)
    if not tlm or "synpa" not in tlm["rings"]:
        return None
    ring = tlm["rings"]["synpa"]
    col = ring[:, 1:, list(tlm["fields"]).index("gn_iters_mean")]
    return float(np.mean(col))
