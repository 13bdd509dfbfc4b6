"""Median time of the program's upload of a dispatch's phase tables
(``scan.tables``: ``DeviceTables.build``, ten host-to-device transfers).
Read from the program's own span record (``repro.obs.trace``) after the
window; the record also holds the set-up's one dispatch beside about
3,000 in the window.  A program without the record reads nothing."""

import numpy as np


def read(run):
    try:
        from repro.obs import trace
        durs = trace.record().get("scan.tables")
    except (ImportError, AttributeError):
        return None
    if durs is None or durs[1].size == 0:
        return None
    return float(np.median(durs[1])) * 1e-6
