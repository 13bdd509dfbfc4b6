"""Synergy placement loop trips per quantum that the batched dispatch ran:
the most of any lane (a batched ``while_loop`` runs until its last lane
is done), averaged over the window's quanta (telemetry ring field
``syn_trips``).  A program without the field reads nothing."""

import numpy as np


def read(run):
    tlm = getattr(run, "telemetry", None)
    if not tlm or "syn_trips" not in tlm["fields"]:
        return None
    ring = tlm["rings"]["synpa"]
    lanes = int(run.cfg["servers"])
    col = ring[..., list(tlm["fields"]).index("syn_trips")]
    return float(np.mean(col.reshape(-1, lanes, col.shape[-1]).max(1)))
