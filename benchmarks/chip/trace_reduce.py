"""Reduce a profiler trace to the numbers the per-layer metrics read.

Two stages, so that the second can be checked on a small recorded trace:

1. :func:`load_events` reads the ``.xplane.pb`` the JAX profiler wrote
   (``jax.profiler.ProfileData``, nothing but JAX) into plain events:
   per TPU device, its operations (line ``XLA Ops``, each named by its
   HLO instruction, e.g. ``decode_attention.4``) and its programs (line
   ``XLA Modules``, e.g. ``jit__decode(529...)``); on the host, the
   harness's spans.
2. :func:`reduce_events` cuts them to the traced window (the harness's
   ``bench_window`` span) and gives the device's busy time (the union of
   its operations' intervals, averaged over the devices), each
   operation's self time (a ``while`` holds the operations of its body,
   so its own time is what its children leave) summed per program and
   operation kind, each program's time, the longest idle gaps with the
   innermost host span over each, and the ``breakdown`` of the result
   line.

Every time here is in seconds.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench_window"
# host spans the harness writes; idle gaps are laid against these
HOST_SPANS = ("submit", "tick", WINDOW_SPAN)
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP_N = 10

Event = Tuple[str, float, float]          # (name, start_s, duration_s)


def trace_options():
    """Profiler options for the traced slice: the defaults, which name
    the device's operations by their HLO instructions, without the
    Python tracer, whose events the reduction never reads."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def instruction(text: str) -> str:
    """The HLO instruction's name from an ``XLA Ops`` event name, which
    may hold the whole instruction (``%while.13 = (...) while(...)``)."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def load_events(path: str) -> Dict[str, Any]:
    """Plain events of one trace file:
    ``{"devices": {plane: {"ops": [...], "modules": [...]}},
    "host": [...]}``, each event ``(name, start_s, duration_s)``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            ops: List[Event] = []
            modules: List[Event] = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((instruction(e.name), e.start_ns * 1e-9,
                                e.duration_ns * 1e-9) for e in line.events)
                elif line.name == MODULES_LINE:
                    modules.extend((e.name, e.start_ns * 1e-9,
                                    e.duration_ns * 1e-9)
                                   for e in line.events)
            devices[plane.name] = {"ops": ops, "modules": modules}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                            for e in line.events if e.name in HOST_SPANS)
    return {"devices": devices, "host": host}


def _union(intervals: Sequence[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(events: Sequence[Event], lo: float, hi: float
          ) -> List[Tuple[str, float, float]]:
    """Events cut to [lo, hi], as (name, start, end)."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b))
    return out


def _self_times(ops: Sequence[Tuple[str, float, float]]
                ) -> List[Tuple[str, float, float]]:
    """(name, start, self seconds) of nested events: an event's time less
    that of the events inside it."""
    eps = 1e-12       # a picosecond: times are float seconds of ns ticks
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    self_s = [b - a for _, a, b in ops]
    stack: List[int] = []
    for i in order:
        _, a, b = ops[i]
        while stack and ops[stack[-1]][2] <= a + eps:
            stack.pop()
        if stack and b <= ops[stack[-1]][2] + eps:
            self_s[stack[-1]] -= b - a
        stack.append(i)
    return [(ops[i][0], ops[i][1], self_s[i]) for i in range(len(ops))]


def program_name(module: str) -> str:
    """``jit__decode(5296...)`` -> ``jit__decode``."""
    return re.sub(r"\(\d+\)$", "", module)


def op_kind(name: str) -> str:
    """An instruction's name without its instance number:
    ``copy_bitcast_fusion.5`` -> ``copy_bitcast_fusion``."""
    return re.sub(r"(\.\d+)+$", "", name)


def reduce_events(ev: Dict[str, Any]) -> Dict[str, Any]:
    """The traced window's busy time, self time per ``program/operation``
    kind, time per program, and the longest idle gaps with the host span
    over each."""
    win = [e for e in ev["host"] if e[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    _, w0, wd = max(win, key=lambda e: e[2])
    w1 = w0 + wd
    if not ev["devices"]:
        raise ValueError("the trace holds no TPU device plane")
    busy_total = 0.0
    ops_s: Dict[str, float] = {}
    programs_s: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for planes in ev["devices"].values():
        ops = _clip(planes["ops"], w0, w1)
        busy = _union([(a, b) for _, a, b in ops])
        busy_total += sum(b - a for a, b in busy)
        mods = sorted(_clip(planes["modules"], w0, w1), key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for name, a, b in mods:
            k = program_name(name)
            programs_s[k] = programs_s.get(k, 0.0) + (b - a)
        for name, a, own in _self_times(ops):
            j = bisect.bisect_right(starts, a) - 1
            prog = program_name(mods[j][0]) \
                if j >= 0 and a < mods[j][2] else "?"
            k = f"{prog}/{op_kind(name)}"
            ops_s[k] = ops_s.get(k, 0.0) + own
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    n_dev = len(ev["devices"])
    host = [(n, s, s + d) for n, s, d in ev["host"] if n != WINDOW_SPAN]

    def doing(a: float, b: float) -> str:
        mid = 0.5 * (a + b)
        over = [(e - s, n) for n, s, e in host if s <= mid <= e]
        return min(over)[1] if over else "none"

    longest = sorted(gaps, key=lambda g: g[1] - g[0], reverse=True)[:TOP_N]
    top_ops = sorted(ops_s.items(), key=lambda kv: kv[1], reverse=True)
    return {
        "window_s": w1 - w0,
        "busy_s": busy_total / n_dev,
        "devices": n_dev,
        "ops_s": ops_s,
        "programs_s": programs_s,
        "breakdown": {
            "device_ops": [[k, v] for k, v in top_ops[:TOP_N]],
            "idle_gaps": [[doing(a, b), b - a] for a, b in longest],
        },
    }


def reduce_dir(trace_dir: str) -> Dict[str, Any]:
    return reduce_events(load_events(find_xplane(trace_dir)))


def time_matching(table: Dict[str, float], pattern: str) -> Optional[float]:
    """Seconds of the entries whose name matches ``pattern`` (a regular
    expression), or ``None`` when none does."""
    rx = re.compile(pattern)
    hits = [v for k, v in table.items() if rx.search(k)]
    return float(sum(hits)) if hits else None
