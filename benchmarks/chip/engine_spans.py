"""Device idle time put down to the engine's phase spans.

The engine opens a ``TraceAnnotation`` named ``engine.<phase>`` around each
host phase of a round (``serve/engine.py`` ``PHASES``).  ``trace_reduce``
keeps only the harness's host spans, so this module reads the same trace
file again with the engine's spans as well and lays the device's idle time
against the innermost host span over it:

* :func:`load_events` is ``trace_reduce.load_events`` with the engine's
  host spans added;
* :func:`reduce_events` gives the window and busy time exactly as
  ``trace_reduce.reduce_events`` does, the longest idle gaps named by the
  innermost span of the harness's and the engine's together, and
  ``idle_by_span``: the idle seconds (averaged over the devices, so they
  sum to ``window_s - busy_s``) under each innermost span, split where a
  gap crosses a span's edge, ``"none"`` where no span covers.  Every host
  span in the window has an entry, 0 where it covers no idle time;
* :func:`newest_trace` finds the trace a traced run just wrote.

A trace of a program without the engine's spans gives ``idle_by_span``
without ``engine.*`` keys.  Every time here is in seconds.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Any, Dict, Optional

import trace_reduce

ENGINE_PREFIX = "engine."


def load_events(path: str) -> Dict[str, Any]:
    """``trace_reduce.load_events`` of ``path`` with the engine's host
    spans added."""
    from jax.profiler import ProfileData
    ev = trace_reduce.load_events(path)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                ev["host"].extend(
                    (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events
                    if e.name.startswith(ENGINE_PREFIX))
    return ev


def reduce_events(ev: Dict[str, Any]) -> Dict[str, Any]:
    """``window_s``, ``busy_s``, ``idle_gaps`` (``trace_reduce``'s longest
    idle gaps, each named by the innermost host span over its middle, the
    engine's spans among them) and ``idle_by_span`` of a traced window."""
    red = trace_reduce.reduce_events(ev)
    _, w0, wd = max((e for e in ev["host"] if e[0] ==
                     trace_reduce.WINDOW_SPAN), key=lambda e: e[2])
    w1 = w0 + wd
    gaps = []
    for planes in ev["devices"].values():
        busy = trace_reduce._union(
            [(a, b) for _, a, b in trace_reduce._clip(planes["ops"], w0, w1)])
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    n_dev = len(ev["devices"])
    host = [(n, s, s + d) for n, s, d in ev["host"]
            if n != trace_reduce.WINDOW_SPAN]

    def doing(a: float, b: float) -> str:
        mid = 0.5 * (a + b)
        over = [(e - s, n) for n, s, e in host if s <= mid <= e]
        return min(over)[1] if over else "none"

    # between two consecutive span edges one span is innermost; a gap that
    # crosses edges is split between the spans it lies under
    cuts = sorted({w0, w1} | {t for _, s, e in host for t in (s, e)
                              if w0 < t < w1})
    pieces = [(a, b, doing(a, b)) for a, b in zip(cuts, cuts[1:])]
    starts = [a for a, _, _ in pieces]
    idle_by_span = {n: 0.0 for n, s, e in host if s < w1 and e > w0}
    for a, b in gaps:
        i = bisect.bisect_right(starts, a) - 1
        while i < len(pieces) and pieces[i][0] < b:
            pa, pb, name = pieces[i]
            idle_by_span[name] = idle_by_span.get(name, 0.0) + (
                min(b, pb) - max(a, pa)) / n_dev
            i += 1
    return {"window_s": red["window_s"], "busy_s": red["busy_s"],
            "idle_gaps": red["breakdown"]["idle_gaps"],
            "idle_by_span": idle_by_span}


def newest_trace(trace_root: str) -> Optional[str]:
    """The most recently written ``.xplane.pb`` under ``trace_root`` (a
    traced run empties its own directory first), or ``None``."""
    found = glob.glob(os.path.join(trace_root, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def engine_idle_s(red: Dict[str, Any]) -> Optional[float]:
    """Idle seconds under the engine's spans, or ``None`` when the trace
    holds none of them."""
    idle = [v for k, v in red["idle_by_span"].items()
            if k.startswith(ENGINE_PREFIX)]
    return sum(idle) if idle else None
