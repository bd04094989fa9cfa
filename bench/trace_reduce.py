"""Reduction of a profiler trace of the measured window to device metrics.

Input is the JAX profiler's `.xplane.pb`: every operation the device ran
(the "XLA Ops" line of each `/device:TPU:<i>` plane, named after the program
it ran in from the "XLA Modules" line) and the harness's own
host spans (`jax.profiler.TraceAnnotation` names in `HOST_SPANS`). Output:

  busy_s      union of the device-operation intervals inside the window,
              averaged over the devices
  window_s    the window span's length
  device_ops  the operations that took most device time, by self time
              (nested operations subtracted): [name, seconds]
  idle_gaps   the longest gaps with no device operation: [host span, s],
              named by the innermost harness span open at the gap's middle
              ("none" where the harness had none open)

The same reduction applies to a recorded trace in the tests: `reduce` works
on plain (name, start_ns, end_ns) tuples.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence

WINDOW_SPAN = "window"
HOST_SPANS = (WINDOW_SPAN, "serve_call", "wait_due", "collect")
DEVICE_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
TOP = 10


class Event(NamedTuple):
    name: str
    start_ns: float
    end_ns: float


def read_xplane(trace_dir: str):
    """(device ops per device, harness host spans) of the newest trace
    under `trace_dir`; ([], spans) when the trace holds no device plane
    (a CPU run)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    devices: List[List[Event]] = []
    spans: List[Event] = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: [(e.name, e.start_ns, e.duration_ns)
                                 for e in line.events]
                     for line in plane.lines
                     if line.name in (DEVICE_LINE, MODULE_LINE)}
            devices.append(device_ops(lines.get(DEVICE_LINE, []),
                                      lines.get(MODULE_LINE, [])))
        elif plane.name.startswith("/host:"):
            spans += [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for line in plane.lines for e in line.events
                      if e.name in HOST_SPANS]
    return devices, spans


def device_ops(ops: Sequence[tuple], modules: Sequence[tuple]
               ) -> List[Event]:
    """Device operations from (name, start_ns, duration_ns) of the "XLA
    Ops" and "XLA Modules" lines, each named `<program>/<op>`: the jitted
    program it ran in (without its fingerprint) and its short HLO name
    (the text before " = ")."""
    spans = sorted((float(t), float(t) + float(d), n.split("(")[0])
                   for n, t, d in modules)
    out = []
    for name, t, d in ops:
        t, end, op = float(t), float(t) + float(d), name.split(" = ")[0]
        i = bisect.bisect_right(spans, (t, float("inf"))) - 1
        if i >= 0 and spans[i][1] >= end:
            op = spans[i][2] + "/" + op
        out.append(Event(op, t, end))
    return out


def union(intervals: Iterable[Sequence[float]]) -> List[List[float]]:
    """Merge [start, end] intervals into disjoint ones, ascending."""
    out: List[List[float]] = []
    for a, b in sorted((float(a), float(b)) for a, b in intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _innermost(spans: Sequence[Event], t: float) -> str:
    best: Optional[Event] = None
    for s in spans:
        if s.name != WINDOW_SPAN and s.start_ns <= t <= s.end_ns and (
                best is None or s.end_ns - s.start_ns
                < best.end_ns - best.start_ns):
            best = s
    return best.name if best is not None else "none"


def self_times(ops: Sequence[Event]) -> Dict[str, float]:
    """Time each operation name ran with no operation nested inside it
    (the device line nests a loop's body ops inside the loop's event)."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List] = []          # [event, time covered by children]

    def close(upto: float) -> None:
        while stack and stack[-1][0].end_ns <= upto:
            ev, child = stack.pop()
            dur = ev.end_ns - ev.start_ns
            out[ev.name] += dur - child
            if stack:
                stack[-1][1] += dur
    for ev in sorted(ops, key=lambda e: (e.start_ns, -e.end_ns)):
        close(ev.start_ns)
        stack.append([ev, 0.0])
    close(float("inf"))
    return out


def reduce(devices: Sequence[Sequence[Event]], spans: Sequence[Event]
           ) -> Optional[Dict]:
    """Device metrics of the window; None when there is no window span or
    no device operation inside it."""
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if not windows or not devices:
        return None
    w = max(windows, key=lambda s: s.end_ns - s.start_ns)
    lo, hi = w.start_ns, w.end_ns
    busy, op_time, gaps = [], defaultdict(float), []
    for ops in devices:
        clipped = [Event(e.name, max(e.start_ns, lo), min(e.end_ns, hi))
                   for e in ops if e.end_ns > lo and e.start_ns < hi]
        for name, t in self_times(clipped).items():
            op_time[name] += t / len(devices)
        merged = union((e.start_ns, e.end_ns) for e in clipped)
        busy.append(sum(b - a for a, b in merged))
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    busy_ns = sum(busy) / len(busy)
    if busy_ns <= 0:
        return None
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy_ns * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "device_ops": [[n, t * 1e-9] for n, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[_innermost(spans, (a + b) / 2), (b - a) * 1e-9]
                      for a, b in gaps[:TOP]],
    }


def reduce_dir(trace_dir: str) -> Optional[Dict]:
    return reduce(*read_xplane(trace_dir))
