"""From a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

``jax.profiler.ProfileData`` reads the file.  Device planes are named
``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event per
operation executed and the ``XLA Modules`` line one per program executed.
Host threads sit on ``/host:CPU``; the harness's spans there are named
``chipbench.*``.  Every time is in nanoseconds on the trace's one clock.

* busy: the union of the op intervals of a device, inside the window;
* idle gaps: the window less that union;
* module time: device duration of the modules whose name starts with a
  prefix, and how many executions;
* each idle gap of at least ``LONG_GAP_NS`` is charged to the innermost
  ``chipbench.*`` host span that covers its midpoint (``host:none`` when
  none does); shorter gaps, between the ops of one program, are summed
  under ``device:short_gaps``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Tuple

import numpy as np

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = SPAN_PREFIX + "window"
LONG_GAP_NS = 50_000


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]           # device plane -> op events
    modules: Dict[str, List[Event]]       # device plane -> module events
    spans: List[Event]                    # chipbench.* host spans


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dst = ops.setdefault(plane.name, [])
                elif line.name == MODULES_LINE:
                    dst = modules.setdefault(plane.name, [])
                else:
                    continue
                for e in line.events:
                    dst.append((e.name, e.start_ns, e.start_ns
                                + e.duration_ns))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    return Trace(ops, modules, spans)


def window(trace: Trace) -> Tuple[float, float]:
    """(start, end) of the harness's window span."""
    for name, lo, hi in trace.spans:
        if name == WINDOW_SPAN:
            return lo, hi
    raise ValueError(f"no {WINDOW_SPAN} span in the trace")


def union(events: List[Event], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Merged intervals of ``events``, clipped to [lo, hi]."""
    iv = sorted((max(s, lo), min(e, hi)) for _n, s, e in events
                if e > lo and s < hi)
    out: List[List[float]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: List[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def busy_seconds(trace: Trace, lo: float, hi: float) -> float:
    """Busy seconds averaged over the device planes."""
    if not trace.ops:
        return 0.0
    tot = sum(sum(e - s for s, e in union(evs, lo, hi))
              for evs in trace.ops.values())
    return tot / len(trace.ops) * 1e-9


def module_time(trace: Trace, prefix: str, lo: float, hi: float
                ) -> Tuple[float, int]:
    """Device seconds and executions of the modules named ``prefix*``
    that start inside the window (over all device planes)."""
    secs, count = 0.0, 0
    for evs in trace.modules.values():
        for name, s, e in evs:
            if name.startswith(prefix) and lo <= s < hi:
                secs += (e - s) * 1e-9
                count += 1
    return secs, count


class HostActivity:
    """Which chipbench span (other than the window) was innermost at a
    given instant."""

    def __init__(self, spans: List[Event]):
        spans = [ev for ev in spans if ev[0] != WINDOW_SPAN]
        self.names = [n for n, _s, _e in spans]
        self.start = np.array([s for _n, s, _e in spans], np.float64)
        self.end = np.array([e for _n, _s, e in spans], np.float64)

    def at(self, t: float) -> str:
        cover = np.flatnonzero((self.start <= t) & (t < self.end))
        if not len(cover):
            return "host:none"
        inner = cover[np.argmin(self.end[cover] - self.start[cover])]
        return self.names[int(inner)]


def short_name(op: str) -> str:
    """An op event's name is its HLO instruction: keep what precedes " = "
    (``%fusion.12``)."""
    return op.split(" = ", 1)[0]


def breakdown(trace: Trace, lo: float, hi: float, k: int = 10) -> Dict:
    """The device ops that took most time, and idle time by what the host
    was doing, on the first device plane; at most ``k`` entries each."""
    plane = sorted(trace.ops)[0] if trace.ops else None
    ops: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    if plane is not None:
        for name, s, e in trace.ops[plane]:
            if e > lo and s < hi:
                name = short_name(name)
                ops[name] = ops.get(name, 0.0) + (min(e, hi) - max(s, lo))
        host = HostActivity(trace.spans)
        for s, e in gaps(union(trace.ops[plane], lo, hi), lo, hi):
            who = (host.at((s + e) / 2) if e - s >= LONG_GAP_NS
                   else "device:short_gaps")
            idle[who] = idle.get(who, 0.0) + (e - s)
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:k]
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:k]
    return {"device_ops": [[n, v * 1e-9] for n, v in top],
            "idle_gaps": [[n, v * 1e-9] for n, v in top_idle]}


def reduce(path: str) -> Dict:
    """Everything the readers and the result line take from one trace."""
    trace = load(path)
    lo, hi = window(trace)
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": busy_seconds(trace, lo, hi),
            "module_time": lambda prefix: module_time(trace, prefix, lo, hi),
            "breakdown": breakdown(trace, lo, hi)}
