"""Reduction of a ``jax.profiler`` trace to device busy time, per-program
device time, the busiest device operations and the longest idle gaps.

The trace is read with ``jax.profiler.ProfileData`` (planes, lines and
events with ``start_ns``/``duration_ns``).  Device planes are those whose
name starts with ``/device:``; on them the ``XLA Ops`` line holds each
operation as it ran, and the ``XLA Modules`` line each execution of a
compiled program, named after its jitted function.  Host planes carry
the ``TraceAnnotation`` spans of the benchmark and the engine.

The traced window is the host span ``WINDOW_SPAN``, which the benchmark
opens right after the profiler starts and closes right before it stops;
device events are clipped to it.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.traced"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]          # seconds


def union(intervals: List[Interval]) -> List[Interval]:
    """Merge overlapping intervals; the result is sorted and disjoint."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of ``[lo, hi]`` between merged busy intervals."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def load(path: str):
    """Planes of the newest ``.xplane.pb`` under ``path`` as plain data:
    ``{plane: {line: [(name, start_s, end_s), ...]}}``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return None
    pd = ProfileData.from_file(files[-1])
    planes = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            lines[line.name] = [(ev.name, ev.start_ns * 1e-9,
                                 (ev.start_ns + ev.duration_ns) * 1e-9)
                                for ev in line.events]
        planes[plane.name] = lines
    return planes


def op_name(text: str) -> str:
    """The instruction's name where the trace gives the whole HLO text
    (``%copy.565 = bf16[...] copy(...)`` reads ``copy.565``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def self_times(events, lo: float, hi: float) -> List[Tuple[str, float]]:
    """Each op's time inside ``[lo, hi]`` less that of the ops nested in
    it (a loop holds its body's ops on the same line), by op name."""
    segs = sorted(((max(a, lo), min(b, hi), op_name(name))
                   for name, a, b in events if min(b, hi) > max(a, lo)),
                  key=lambda s: (s[0], -s[1]))
    out, stack = [], []                 # stack: [end, name, own time]
    for a, b, name in segs:
        while stack and stack[-1][0] <= a:
            _, n, t = stack.pop()
            out.append((n, t))
        if stack:
            stack[-1][2] -= min(b, stack[-1][0]) - a
        stack.append([b, name, b - a])
    out.extend((n, t) for _, n, t in stack)
    return out


def reduce(planes: dict, programs: Dict[str, str],
           span_names: Tuple[str, ...] = ()) -> Optional[dict]:
    """Per-device busy time and per-program device time inside the
    traced window, averaged over the devices that ran anything.

    ``programs`` maps a label to a substring of the jitted program's
    name (``{"decode": "_decode_paged_fn"}``).  Gaps are named after the
    innermost host span of ``span_names`` that holds their midpoint, or
    ``host_loop`` where none does: the serving loop's own Python between
    the calls those spans wrap (emission, finish checks, admission)."""
    host = [ev for name, lines in planes.items()
            if not name.startswith("/device:")
            for evs in lines.values() for ev in evs]
    marks = [ev for ev in host if ev[0] == WINDOW_SPAN]
    devices = {name: lines for name, lines in planes.items()
               if name.startswith("/device:") and lines.get(OPS_LINE)}
    if not marks or not devices:
        return None
    lo, hi = marks[0][1], marks[0][2]
    window = hi - lo
    spans = sorted((ev for ev in host if ev[0] in span_names),
                   key=lambda ev: ev[2] - ev[1])
    busy_s, per_prog, op_time, idle = [], {}, {}, []
    for lines in devices.values():
        ops = clip([(a, b) for _, a, b in lines[OPS_LINE]], lo, hi)
        merged = union(ops)
        busy_s.append(sum(b - a for a, b in merged))
        for name, t in self_times(lines[OPS_LINE], lo, hi):
            op_time[name] = op_time.get(name, 0.0) + t
        for label, sub in programs.items():
            runs = [(a, b) for name, a, b in lines.get(MODULES_LINE, ())
                    if sub in name and a >= lo and b <= hi]
            agg = per_prog.setdefault(label, {"count": 0, "seconds": 0.0})
            agg["count"] += len(runs)
            agg["seconds"] += sum(b - a for a, b in runs)
        for a, b in gaps(merged, lo, hi):
            mid = (a + b) / 2
            held = next((ev[0] for ev in spans if ev[1] <= mid <= ev[2]),
                        "host_loop")
            idle.append((held, b - a))
    n = len(devices)
    for agg in per_prog.values():
        agg["count"] /= n
        agg["seconds"] /= n
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(idle, key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window,
        "busy_s": sum(busy_s) / n,
        "programs": per_prog,
        "device_ops": [[k, v / n] for k, v in top_ops],
        "idle_gaps": [[k, v] for k, v in top_gaps],
        "n_devices": n,
        "window": (lo, hi),
        "device_extent": (min(a for ls in devices.values()
                              for _, a, _ in ls[OPS_LINE]),
                          max(b for ls in devices.values()
                              for _, _, b in ls[OPS_LINE])),
    }


def outline(planes: dict, per_line: int = 3) -> List[str]:
    """A few event names of every line: what a first look at a trace
    needs to find its programs."""
    out = []
    for pname, lines in planes.items():
        for lname, evs in lines.items():
            names = sorted({e[0] for e in evs})[:per_line]
            out.append(f"{pname} | {lname} | {len(evs)} events | {names}")
    return out
