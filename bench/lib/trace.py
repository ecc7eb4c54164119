"""Reduce a profiler trace to device busy time, idle gaps and op time.

A trace is read into plain lists (``Trace``), so the reduction is tested
on hand-built events and every run computes its numbers the same way:

  busy       the union of the intervals in which an operation ran on a
             device, clipped to the window; averaged over the devices;
  idle gaps  the complement of that union within the window, each
             labelled with the innermost ``bench.*`` host annotation that
             covers its midpoint (what the host was doing);
  op time    the summed device durations of the ops whose name (the
             HLO instruction, e.g. "%weighted_agg.7 = f32[...] ...")
             holds a pattern.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass
from typing import Dict, List, Tuple

HOST_PREFIX = "bench."
# lines of a device plane that hold the individual operations; other
# lines (modules, steps, sparse cores) overlap them
OP_LINES = ("XLA Ops",)


@dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    devices: Dict[str, List[Event]]          # device plane -> op events
    host: List[Event]                        # bench.* annotations


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops = []
            for line in plane.lines:
                if line.name in OP_LINES:
                    ops.extend(Event(e.name, float(e.start_ns),
                                     float(e.duration_ns))
                               for e in line.events)
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append(Event(e.name, float(e.start_ns),
                                          float(e.duration_ns)))
    return Trace(devices=devices, host=host)


def merge(intervals) -> List[Tuple[float, float]]:
    """Union of (start, end) intervals as a sorted disjoint list."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def window(tr: Trace, name: str = "bench.window") -> Tuple[float, float]:
    """The traced window: the span of the harness's window annotation."""
    spans = [e for e in tr.host if e.name == name]
    if not spans:
        raise ValueError(f"no {name!r} annotation in the trace")
    return (min(e.start_ns for e in spans), max(e.end_ns for e in spans))


def busy_ns(ops: List[Event], lo: float, hi: float) -> float:
    return sum(e - s for s, e in
               clip(merge((o.start_ns, o.end_ns) for o in ops), lo, hi))


def mean_busy_s(tr: Trace, lo: float, hi: float) -> float:
    """Busy seconds averaged over the traced devices."""
    if not tr.devices:
        return 0.0
    return sum(busy_ns(ops, lo, hi) for ops in tr.devices.values()) \
        / len(tr.devices) / 1e9


def idle_gaps(ops: List[Event], lo: float, hi: float):
    """(start, end) gaps in which no op ran, within [lo, hi]."""
    busy = clip(merge((o.start_ns, o.end_ns) for o in ops), lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def label(host: List[Event], t: float) -> str:
    """Innermost (shortest) bench.* annotation covering time t."""
    cover = [e for e in host if e.start_ns <= t <= e.end_ns
             and e.name != "bench.window"]
    if not cover:
        return "none"
    return min(cover, key=lambda e: e.dur_ns).name


def op_seconds(tr: Trace, patterns, lo: float, hi: float) -> float:
    """Summed device seconds of matching ops inside the window, over all
    devices (the caller divides by the device count where it wants a
    per-chip figure)."""
    total = 0.0
    for ops in tr.devices.values():
        for o in ops:
            if lo <= o.start_ns <= hi and any(p in o.name
                                              for p in patterns):
                total += o.dur_ns
    return total / 1e9


def short(name: str) -> str:
    """An op's name without its HLO text: "%fusion.3 = f32[..] ..." ->
    "%fusion.3"."""
    return name.split(" = ", 1)[0]


def self_ns(ops: List[Event]) -> List[Tuple[Event, float]]:
    """Each op with its self time: its duration less that of the ops
    nested inside it (a while loop holds its body's ops)."""
    out, stack = [], []
    for o in sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and o.start_ns >= stack[-1][0].end_ns:
            stack.pop()
        rec = [o, o.dur_ns]
        if stack:
            stack[-1][1] -= o.dur_ns
        stack.append(rec)
        out.append(rec)
    return [(o, t) for o, t in out]


def breakdown(tr: Trace, lo: float, hi: float, n: int = 10) -> dict:
    """The ``n`` device ops that took most self time (summed by name,
    seconds per chip) and the ``n`` longest idle gaps, labelled by host
    activity."""
    by_name: Dict[str, float] = {}
    for ops in tr.devices.values():
        for o, t in self_ns(ops):
            if lo <= o.start_ns <= hi:
                k = short(o.name)
                by_name[k] = by_name.get(k, 0.0) + t
    n_dev = max(1, len(tr.devices))
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    gaps = []
    for ops in tr.devices.values():
        gaps.extend(idle_gaps(ops, lo, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return {"device_ops": [[k, v / n_dev / 1e9] for k, v in top],
            "idle_gaps": [[label(tr.host, (s + e) / 2), (e - s) / 1e9]
                          for s, e in gaps[:n]]}
