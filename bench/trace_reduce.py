"""Reduce a profiler trace of the measured window to device numbers.

A trace is read into plain data, ``{"planes": [{"name", "lines": [{"name",
"events": [[name, start_ns, duration_ns], ...]}]}]}``, so the reduction
runs the same on a capture of the chip (``load_xplane``) and on the small
recorded trace its test keeps.

* busy: the union of the intervals in which an operation ran on a device
  (the ``XLA Ops`` line of each ``/device:TPU:<k>`` plane), inside the
  window, averaged over the chips;
* window: the host span ``bench.window`` that the harness opens around the
  measured loop, up to the end of the last op the device planes recorded
  where that comes first: the profiler keeps a bounded number of op
  events, so a window of thousands of small dispatches loses its tail
  (while the device's other lines run on), and a tail with no record is
  not an idle device;
* device_ops: device time by operation name, largest first;
* idle_gaps: the longest stretches with no operation on the device, each
  named by the innermost host span open at its middle (the harness's own
  ``bench.*`` spans and the program's ``TraceAnnotation`` spans).
"""

from __future__ import annotations

import bisect
from typing import List, Optional, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"


def load_xplane(path: str) -> dict:
    """Plain-data form of an ``.xplane.pb`` capture: the device planes' op
    lines and the host planes, the rest left unread."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            lines.append({"name": line.name, "events": [
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def device_planes(trace: dict) -> list:
    return [p for p in trace["planes"] if p["name"].startswith(DEVICE_PREFIX)]


def host_spans(trace: dict) -> List[Tuple[str, float, float]]:
    """Named host spans (python-frame events, ``$file:line``, left out)."""
    out = []
    for p in trace["planes"]:
        if not p["name"].startswith("/host:"):
            continue
        for line in p["lines"]:
            for name, s, d in line["events"]:
                if d > 0 and not name.startswith("$"):
                    out.append((name, s, s + d))
    return out


def window(trace: dict) -> Optional[Tuple[float, float]]:
    for name, s, e in host_spans(trace):
        if name == WINDOW_SPAN:
            return s, e
    return None


def op_name(event_name: str) -> str:
    """An op's own name: a TPU trace names each op event by its whole HLO
    instruction (``%fusion.12 = f32[...] fusion(...)``); the part before
    `` = `` is the op's name."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def ops(trace: dict, lo: float, hi: float):
    """Per device plane: the op events (by op name) clipped to [lo, hi]."""
    out = []
    for p in device_planes(trace):
        evs = []
        for line in p["lines"]:
            if line["name"] != OPS_LINE:
                continue
            for name, s, d in line["events"]:
                if s + d > lo and s < hi:
                    evs.append((op_name(name), max(s, lo), min(s + d, hi)))
        out.append(evs)
    return out


def reduce(trace: dict, top: int = 10) -> Optional[dict]:
    """Device numbers of the window, or None when the trace holds no
    window or no device operation in it."""
    win = window(trace)
    if win is None:
        return None
    lo, hi = win
    ends = [s + d for p in device_planes(trace) for line in p["lines"]
            if line["name"] == OPS_LINE for _n, s, d in line["events"]]
    if ends and lo < max(ends) < hi:
        hi = max(ends)
    per_dev = ops(trace, lo, hi)
    if not any(per_dev):
        return None
    busy_each, by_op = [], {}
    for evs in per_dev:
        merged = _merge([(s, e) for _n, s, e in evs])
        busy_each.append(sum(e - s for s, e in merged))
        for name, s, e in evs:
            by_op[name] = by_op.get(name, 0.0) + (e - s)
    merged0 = _merge([(s, e) for _n, s, e in per_dev[0]])
    gaps, prev = [], lo
    for s, e in merged0 + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    spans = sorted(host_spans(trace), key=lambda x: x[1])
    starts = [s for _n, s, _e in spans]

    def name_of(mid):
        best = None
        for name, s, e in spans[:bisect.bisect_right(starts, mid)]:
            if s <= mid < e and name != WINDOW_SPAN and (
                    best is None or e - s < best[2] - best[1]):
                best = (name, s, e)
        return best[0] if best else "no host span"

    gaps.sort(key=lambda g: g[0] - g[1])
    out = {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy_each) / len(busy_each) * 1e-9,
        "device_ops": [[n, t * 1e-9] for n, t in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[name_of((s + e) / 2), (e - s) * 1e-9]
                      for s, e in gaps[:top]],
    }
    return out
