"""Share of the window's placements put beside a resident (a core-mate
already holding a job) rather than on an empty core, in % (telemetry
ring fields ``syn_beside`` over ``admissions``).  A program without the
field reads nothing."""

import numpy as np


def read(run):
    tlm = getattr(run, "telemetry", None)
    if not tlm or "syn_beside" not in tlm["fields"]:
        return None
    ring = tlm["rings"]["synpa"]
    fields = list(tlm["fields"])
    placed = float(np.sum(ring[..., fields.index("admissions")]))
    if placed == 0:
        return None
    return 100.0 * float(np.sum(ring[..., fields.index("syn_beside")])) \
        / placed
