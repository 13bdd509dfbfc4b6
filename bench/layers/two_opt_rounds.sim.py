"""Mean parallel 2-opt rounds of the device matcher per SYNPA quantum over
the window (telemetry ring field ``two_opt_rounds``)."""

import numpy as np


def read(run):
    tlm = getattr(run, "telemetry", None)
    if not tlm or "synpa" not in tlm["rings"]:
        return None
    ring = tlm["rings"]["synpa"]
    col = ring[:, 1:, list(tlm["fields"]).index("two_opt_rounds")]
    return float(np.mean(col))
