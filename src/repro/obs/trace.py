"""Host span tracing — program phases on the profiler's clock and in memory.

The device engines are one dispatch per run, so the host-side story of a
run is a handful of coarse phases: presample -> pack -> commit -> dispatch
-> fetch -> stats, and, on the served path, each allocator decision
(``alloc.pair``: prep -> step -> ``matcher.wait`` -> unpack).
:func:`span` wraps each phase as a context manager and always does two
things:

* it enters ``jax.profiler.TraceAnnotation(name, **args)``.  With no
  capture running that is one TraceMe check; inside a
  ``jax.profiler.trace`` capture the span lands in the same
  ``.xplane.pb`` as the device ops, on the profiler's clock, with
  ``args`` as its stats (they are passed only through the annotation, so
  nothing is formatted when no capture runs);
* it appends ``(start_ns, dur_ns)`` from ``time.perf_counter_ns`` to a
  bounded per-name record (the last :data:`RECORD_MAX` spans of each
  name).  :func:`record` returns it as arrays, :func:`contained` finds
  each span's children by time containment, :func:`breakdown` sums it by
  name, and :func:`clear` empties it.  A parent is found by containment
  on one thread: every program span is opened on the caller's thread.

:func:`enable` additionally keeps each span as a Chrome trace-event
``"X"`` (complete) event — microsecond timestamps, pid/tid — which
:func:`save` writes as a JSON file loadable in ``chrome://tracing`` or
https://ui.perfetto.dev.  That list is off by default.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Deque, Dict, List, Tuple

import numpy as np

#: Spans of one name the in-memory record keeps (the newest).
RECORD_MAX = 65536

_enabled = False
_events: List[Dict] = []
_t0_ns = 0
_lock = threading.Lock()
_record: Dict[str, Deque[Tuple[int, int]]] = {}
_profiler = None
_clock = time.perf_counter_ns


def _annotation(name: str, args: Dict):
    """``jax.profiler.TraceAnnotation(name, **args)``, looked up on the
    module each time so that a test can stand in for it."""
    global _profiler
    if _profiler is None:
        import jax.profiler

        _profiler = jax.profiler
    return _profiler.TraceAnnotation(name, **args)


def enable() -> None:
    """Also keep each span as a Chrome event, timed from now."""
    global _enabled, _t0_ns
    _t0_ns = _clock()
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def clear() -> None:
    """Empty the Chrome event list and the in-memory record."""
    with _lock:
        _events.clear()
        _record.clear()


class _Span:
    __slots__ = ("name", "args", "_ann", "_start")

    def __init__(self, name: str, args: Dict):
        self.name, self.args = name, args

    def __enter__(self):
        self._ann = _annotation(self.name, self.args)
        self._ann.__enter__()
        self._start = _clock()
        return self

    def __exit__(self, *exc):
        dur = _clock() - self._start
        self._ann.__exit__(*exc)
        rec = _record.get(self.name)
        if rec is None:
            rec = _record.setdefault(
                self.name, collections.deque(maxlen=RECORD_MAX))
        rec.append((self._start, dur))
        if _enabled:
            ev = {
                "name": self.name,
                "ph": "X",
                "ts": (self._start - _t0_ns) * 1e-3,
                "dur": dur * 1e-3,
                "pid": os.getpid(),
                "tid": threading.get_ident(),
            }
            if self.args:
                ev["args"] = {k: _jsonable(v) for k, v in self.args.items()}
            with _lock:
                _events.append(ev)
        return False


def span(name: str, **args) -> _Span:
    """One phase of the program: a ``TraceAnnotation`` and an entry of the
    in-memory record, always; a Chrome event while :func:`enable` is on.
    Spans nest by wall time on one thread."""
    return _Span(name, args)


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return repr(v)


def record() -> Dict[str, Tuple[np.ndarray, np.ndarray]]:
    """``{name: (starts_ns, durs_ns)}`` of the recorded spans, int64
    arrays in the order the spans closed."""
    with _lock:
        snap = {k: list(v) for k, v in _record.items()}
    out = {}
    for name, rows in snap.items():
        a = np.asarray(rows, np.int64).reshape(-1, 2)
        out[name] = (a[:, 0], a[:, 1])
    return out


def contained(parent: str, child: str, rec=None):
    """Each ``parent`` span's duration and the summed duration and count
    of the ``child`` spans inside it (by time containment; both from
    :func:`record`, or ``rec``).  Returns three arrays over the parent
    spans in start order: ``(parent_ns, child_ns, n_child)``; an empty
    triple when no parent was recorded."""
    rec = record() if rec is None else rec
    empty = np.zeros(0, np.int64)
    if parent not in rec:
        return empty, empty, empty
    ps, pd = rec[parent]
    order = np.argsort(ps, kind="stable")
    ps, pd = ps[order], pd[order]
    cs, cd = rec.get(child, (empty, empty))
    k = np.searchsorted(ps, cs, side="right") - 1
    inside = (k >= 0) & (cs + cd <= (ps + pd)[np.maximum(k, 0)])
    k, w = k[inside], cd[inside]
    child_ns = np.bincount(k, weights=w, minlength=ps.size)
    return pd, child_ns.astype(np.int64), np.bincount(k, minlength=ps.size)


def events() -> List[Dict]:
    """The Chrome events kept while enabled (a snapshot)."""
    with _lock:
        return list(_events)


def to_chrome_trace() -> Dict:
    """Chrome trace-event document: ``{"traceEvents": [...], ...}``."""
    return {
        "traceEvents": events(),
        "displayTimeUnit": "ms",
        "metadata": {"recorder": "repro.obs.trace"},
    }


def save(path: str) -> str:
    """Write the trace JSON (open in chrome://tracing or Perfetto)."""
    with open(path, "w") as f:
        json.dump(to_chrome_trace(), f)
    return path


def breakdown() -> Dict[str, Dict]:
    """The record summed by span name: count, total and mean duration
    (us).  The span table of ``tools/check_policy_budget.py``; also an
    assertion surface for tests."""
    out: Dict[str, Dict] = {}
    for name, (_starts, durs) in record().items():
        total = float(durs.sum()) * 1e-3
        out[name] = {"count": int(durs.size), "total_us": total,
                     "mean_us": total / max(durs.size, 1)}
    return out
