#!/usr/bin/env python3
"""The serving program's own spans, scopes and counters in a traced window.

  python3 bench/program_trace.py [<trace dir>] [--fixture <out.json>]

The program (`repro.serve.DarthServer`) marks its serve loop with profiler
spans on the device trace's clock: `darth.serve` around each call and
`darth.serve.<phase>` around its phases (admit, dispatch, sync, harvest,
hook, refill, finish). Each `darth.serve` span carries the call's predictor
counters as stats (`predictor_calls`, `predictor_batches`, `num_slots`). Its
device ops carry the named scopes `darth.probe`, `darth.merge` (inside the
probe) and `darth.predict` in their metadata. This module reads them from the
newest `.xplane.pb` under a trace directory, beside the harness's own spans,
and reduces the window (the longest `window` span) to:

  spans_s          seconds under each program span name
  idle_by_span     seconds with no device operation, under the innermost
                   span open then (harness or program; "none" outside any)
  device_by_scope  device self time under each op's innermost scope ("none"
                   for ops in no scope): the parts add up to busy_s
  busy_s, window_s as `bench/trace_reduce.py` computes them
  idle_gaps        the longest gaps, named by the innermost span at their
                   middle
  counters         the counters summed over the `darth.serve` spans that
                   end inside the window

A program without these spans and scopes reads nothing here: the readers
built on this module then return None. Run as a script, it prints the
reduction as JSON, and with `--fixture` writes ~100 ms of the window for a
test.
"""
from __future__ import annotations

import argparse
import bisect
import functools
import glob
import json
import os
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, NamedTuple, Optional, Sequence

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace_reduce  # noqa: E402
from bench.trace_reduce import Event  # noqa: E402

SERVE_SPAN = "darth.serve"
SCOPES = ("darth.probe", "darth.merge", "darth.predict")
NO_SCOPE = "none"
COUNTERS = ("predictor_calls", "predictor_batches", "num_slots")
# the stat of an "XLA Ops" event's metadata that holds the op's op_name
# path; `jax.profiler.ProfileData` gives events without their metadata's
# stats, so `op_paths` reads them from the file's protobuf wire format
SCOPE_STAT = "tf_op"
PROGRAM_STAT = "program_id"


class Span(NamedTuple):
    name: str
    start_ns: float
    end_ns: float
    stats: Optional[dict] = None


def scope_of(op_name: str) -> str:
    """The innermost of SCOPES on an op's name path, e.g.
    `jit(run_chunk)/while/body/darth.probe/darth.merge/top_k` ->
    `darth.merge`."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return NO_SCOPE


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """(field number, value) of the protobuf message in buf[lo:hi]: an int
    for a varint, (start, end) for a length-delimited field, None for a
    fixed-width one."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def op_paths(path: str) -> Dict[str, Dict[tuple, str]]:
    """Per device plane, (program id, event metadata name) -> the op's
    op_name path (its SCOPE_STAT), read from the XSpace message: planes
    (field 1); a plane's name (2), event metadata map (4) and stat
    metadata map (5); an event metadata's name (2) and stats (5); a
    stat's metadata id (1) and value, a string (5) or a reference to a
    stat metadata whose name is the string (7), or an integer (3, 4)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: Dict[str, Dict[tuple, str]] = {}
    for num, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for pnum, v in _fields(buf, *plane):
            if pnum == 2:
                name = _text(buf, v)
                if not name.startswith("/device:TPU:"):
                    break
            elif pnum in (4, 5):
                entry = dict(_fields(buf, *v))
                if 2 not in entry:
                    continue
                if pnum == 4:
                    events.append(entry[2])
                else:
                    meta = dict(_fields(buf, *entry[2]))
                    stat_names[meta.get(1, 0)] = _text(buf, meta[2]) \
                        if 2 in meta else ""
        else:
            ids = {v: k for k, v in stat_names.items()}
            if SCOPE_STAT not in ids:
                continue
            table: Dict[tuple, str] = {}
            for ev in events:
                ev_name, op, program = "", None, None
                for enum, v in _fields(buf, *ev):
                    if enum == 2:
                        ev_name = _text(buf, v)
                    elif enum == 5:
                        stat = {}
                        for snum, sv in _fields(buf, *v):
                            stat[snum] = sv
                        sid = stat.get(1)
                        if sid == ids[SCOPE_STAT]:
                            op = (_text(buf, stat[5]) if 5 in stat
                                  else stat_names.get(stat.get(7), ""))
                        elif sid == ids.get(PROGRAM_STAT):
                            program = stat.get(3, stat.get(4))
                if op is not None:
                    table[(program, ev_name)] = op
            out[name] = table
    return out


def is_kept(name: str) -> bool:
    return name in trace_reduce.HOST_SPANS or name.startswith(SERVE_SPAN)


def _newest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


class Op(NamedTuple):
    name: str          # short HLO name, e.g. %fusion.97
    start_ns: float
    end_ns: float
    scope: str


def _program(module: str) -> Optional[int]:
    """The program id in an "XLA Modules" event name, `jit_f(<id>)`."""
    inner = module.partition("(")[2].rstrip(")")
    return int(inner) if inner.isdigit() else None


def scoped_ops(ops: Sequence[tuple], modules: Sequence[tuple],
               paths: Dict[tuple, str]) -> List[Op]:
    """Device ops from (name, start_ns, duration_ns) of the "XLA Ops" and
    "XLA Modules" lines, each with the scope of its op_name path, found
    under (the program it ran in, its name) in `paths`."""
    mods = sorted((float(t), float(t) + float(d), _program(n))
                  for n, t, d in modules)
    by_name = {name: p for (_, name), p in paths.items()}
    out = []
    for name, t, d in ops:
        t, end = float(t), float(t) + float(d)
        i = bisect.bisect_right(mods, (t, float("inf"))) - 1
        program = mods[i][2] if i >= 0 and mods[i][1] >= end else None
        path = paths.get((program, name), by_name.get(name, ""))
        out.append(Op(name.split(" = ")[0], t, end, scope_of(path)))
    return out


def read_xplane(path: str):
    """(scoped device ops per device, kept host spans) of one
    `.xplane.pb`."""
    from jax.profiler import ProfileData

    paths = op_paths(path)
    devices: List[List[Op]] = []
    spans: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: [(e.name, e.start_ns, e.duration_ns)
                                 for e in line.events]
                     for line in plane.lines
                     if line.name in (trace_reduce.DEVICE_LINE,
                                      trace_reduce.MODULE_LINE)}
            devices.append(scoped_ops(
                lines.get(trace_reduce.DEVICE_LINE, []),
                lines.get(trace_reduce.MODULE_LINE, []),
                paths.get(plane.name, {})))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if is_kept(e.name):
                        stats = (dict(e.stats) if e.name == SERVE_SPAN
                                 else None)
                        spans.append(Span(e.name, e.start_ns,
                                          e.start_ns + e.duration_ns, stats))
    return devices, spans


def _innermost(spans: Sequence[Span], t: float) -> str:
    best: Optional[Span] = None
    for s in spans:
        if s.name != trace_reduce.WINDOW_SPAN and s.start_ns <= t <= s.end_ns \
                and (best is None or s.end_ns - s.start_ns
                     < best.end_ns - best.start_ns):
            best = s
    return best.name if best is not None else NO_SCOPE


def split_by_span(spans: Sequence[Span], intervals: Sequence[Sequence[float]]
                  ) -> Dict[str, float]:
    """Length (ns) of the disjoint, ascending `intervals` under each
    innermost span: the one opened last of those open (spans of one
    thread nest). The window span names nothing."""
    edges = []
    for i, s in enumerate(spans):
        if s.name != trace_reduce.WINDOW_SPAN and s.end_ns > s.start_ns:
            edges += [(s.start_ns, 1, i), (s.end_ns, 0, i)]
    edges.sort()                    # at one instant, closes before opens
    out: Dict[str, float] = defaultdict(float)
    stack: List[int] = []
    g, t_prev = 0, float("-inf")

    def add(a: float, b: float) -> None:
        nonlocal g
        name = spans[stack[-1]].name if stack else NO_SCOPE
        while g < len(intervals) and intervals[g][1] <= a:
            g += 1
        k = g
        while k < len(intervals) and intervals[k][0] < b:
            out[name] += min(b, intervals[k][1]) - max(a, intervals[k][0])
            k += 1
    for t, opens, i in edges:
        if t > t_prev:
            add(t_prev, t)
        t_prev = t
        if opens:
            stack.append(i)
        else:
            stack.remove(i)
    add(t_prev, float("inf"))
    return dict(out)


def reduce(devices: Sequence[Sequence[Op]], spans: Sequence[Span]
           ) -> Optional[Dict]:
    """The window's program readings; None without a window span or a
    device operation inside it."""
    windows = [s for s in spans if s.name == trace_reduce.WINDOW_SPAN]
    if not windows or not devices:
        return None
    w = max(windows, key=lambda s: s.end_ns - s.start_ns)
    lo, hi = w.start_ns, w.end_ns
    inside = [Span(s.name, max(s.start_ns, lo), min(s.end_ns, hi), s.stats)
              for s in spans if s.end_ns > lo and s.start_ns < hi]
    busy, by_scope, gaps = [], defaultdict(float), []
    idle_by_span: Dict[str, float] = defaultdict(float)
    for ops in devices:
        clipped = [Event(e.scope, max(e.start_ns, lo), min(e.end_ns, hi))
                   for e in ops if e.end_ns > lo and e.start_ns < hi]
        for name, t in trace_reduce.self_times(clipped).items():
            by_scope[name] += t / len(devices)
        merged = trace_reduce.union((e.start_ns, e.end_ns) for e in clipped)
        busy.append(sum(b - a for a, b in merged))
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for name, t in split_by_span(inside, idle).items():
            idle_by_span[name] += t / len(devices)
        gaps += idle
    busy_ns = sum(busy) / len(busy)
    if busy_ns <= 0:
        return None
    spans_s: Dict[str, float] = defaultdict(float)
    counters: Dict[str, int] = defaultdict(int)
    for s in inside:
        if s.name.startswith(SERVE_SPAN):
            spans_s[s.name] += (s.end_ns - s.start_ns) * 1e-9
        if s.name == SERVE_SPAN and s.stats and s.end_ns < hi:
            got = {k: int(s.stats.get(k, 0)) for k in COUNTERS}
            for k, v in got.items():
                counters[k] += v
            counters["slot_evaluations"] += (got["predictor_batches"]
                                             * got["num_slots"])
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy_ns * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "spans_s": dict(spans_s),
        "idle_by_span": {k: v * 1e-9 for k, v in sorted(
            idle_by_span.items(), key=lambda kv: -kv[1])},
        "device_by_scope": {k: v * 1e-9 for k, v in sorted(
            by_scope.items(), key=lambda kv: -kv[1])},
        "idle_gaps": [[_innermost(inside, (a + b) / 2), (b - a) * 1e-9]
                      for a, b in gaps[:trace_reduce.TOP]],
        "counters": dict(counters),
    }


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, mtime: float) -> Optional[Dict]:
    return reduce(*read_xplane(path))


def of_run(run, trace_dir: Optional[str] = None) -> Optional[Dict]:
    """The program readings of a traced run (the harness writes its trace
    under `bench.harness.TRACE_DIR`), or None for an untraced run. The
    file is read once for all the readers of one run."""
    if run.trace is None:
        return None
    if trace_dir is None:
        from bench import harness
        trace_dir = str(harness.TRACE_DIR)
    try:
        path = _newest_xplane(trace_dir)
    except FileNotFoundError:
        return None
    return _reduce_file(path, os.path.getmtime(path))


def share(part: float, whole: float) -> Optional[float]:
    return 100.0 * part / whole if whole > 0 else None


def scope_share(run, scopes: Sequence[str]) -> Optional[float]:
    """Share (%) of device busy time in `scopes`; None where no op carries
    a scope (a program without them)."""
    r = of_run(run)
    if r is None or not set(SCOPES) & set(r["device_by_scope"]):
        return None
    return share(sum(r["device_by_scope"].get(s, 0.0) for s in scopes),
                 r["busy_s"])


def span_share(run, phase: str) -> Optional[float]:
    """Share (%) of the serve calls' span time under one phase span."""
    r = of_run(run)
    if r is None or SERVE_SPAN not in r["spans_s"]:
        return None
    return share(r["spans_s"].get(f"{SERVE_SPAN}.{phase}", 0.0),
                 r["spans_s"][SERVE_SPAN])


def due_share(run) -> Optional[float]:
    """Share (%) of the batched predictor's slot evaluations that a slot
    was due for: predictor calls over predictor batches x slots."""
    r = of_run(run)
    if r is None or not r["counters"].get("slot_evaluations"):
        return None
    return share(r["counters"]["predictor_calls"],
                 r["counters"]["slot_evaluations"])


def excerpt(path: str, ms: float = 100.0) -> Dict:
    """~`ms` from the middle of the window's first serve call on device 0:
    the ops with their scopes and the spans clipped to the excerpt, the
    window span among them, for a recorded test."""
    devices, spans = read_xplane(path)
    call = min((s for s in spans if s.name == SERVE_SPAN),
               key=lambda s: s.start_ns)
    a = (call.start_ns + call.end_ns) / 2
    b = a + ms * 1e6
    return {
        "source": f"TPU v5e trace: {ms} ms from the middle of the first "
                  f"serve call, device 0",
        "ops": [[e.name, e.start_ns, e.end_ns - e.start_ns, e.scope]
                for e in devices[0] if e.start_ns >= a and e.end_ns <= b],
        "spans": [[s.name, max(s.start_ns, a),
                   min(s.end_ns, b) - max(s.start_ns, a)]
                  for s in spans if s.end_ns > a and s.start_ns < b],
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir", nargs="?", default=str(
        Path(__file__).resolve().parents[1] / ".bench_trace"))
    ap.add_argument("--fixture")
    args = ap.parse_args()
    path = _newest_xplane(args.trace_dir)
    if args.fixture:
        with open(args.fixture, "w") as f:
            json.dump(excerpt(path), f)
    print(json.dumps(reduce(*read_xplane(path))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
