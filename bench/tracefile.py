"""From a profiler trace to device busy time, time per XLA module, device
time inside each of the benchmark's host spans, and idle gaps by what the
benchmark's host was doing.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
events: per device, one interval per XLA module run (the "XLA Modules"
line, or "XLA Ops" where a device has no module line), and the host spans
the benchmark opened with ``TraceAnnotation`` (names starting ``bench.``).
``summarize`` reduces those events; it is plain Python, so a recorded trace
in ``bench/tests/data`` checks it without a chip.
"""
from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
DEVICE_LINES = ("XLA Modules", "XLA Ops")
_SUFFIX = re.compile(r"\(\d+\)$")

Interval = Tuple[str, float, float]          # (name, start_ns, end_ns)


@dataclass
class Events:
    device: Dict[str, List[Interval]] = field(default_factory=dict)
    host: List[Interval] = field(default_factory=list)

    def to_json(self) -> str:
        return json.dumps({"device": self.device, "host": self.host})

    @staticmethod
    def from_json(text: str) -> "Events":
        d = json.loads(text)
        return Events({k: [tuple(e) for e in v]
                       for k, v in d["device"].items()},
                      [tuple(e) for e in d["host"]])


def module_name(name: str) -> str:
    """``jit_foo(123)`` -> ``jit_foo``: one name per program, not per run."""
    return _SUFFIX.sub("", name)


def load(logdir: str) -> Events:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    ev = Events()
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            line = next((lines[n] for n in DEVICE_LINES if n in lines), None)
            if line is None:
                continue
            ev.device[plane.name] = [
                (module_name(e.name), float(e.start_ns),
                 float(e.start_ns + e.duration_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                ev.host.extend(
                    (e.name, float(e.start_ns),
                     float(e.start_ns + e.duration_ns))
                    for e in line.events if e.name.startswith("bench."))
    return ev


def union(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    spans = sorted((max(s, lo), min(e, hi)) for _n, s, e in intervals
                   if e > lo and s < hi)
    merged: List[List[float]] = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


@dataclass
class Summary:
    window_s: float
    busy_s: float                       # mean over the devices traced
    module_s: Dict[str, float]          # device seconds per XLA module
    gaps: Dict[str, float]              # idle seconds per host span name
    devices: int
    span_busy: Dict[str, float] = field(default_factory=dict)
    # device busy seconds inside the host spans of each name (mean over
    # the devices traced)

    def breakdown(self, top: int = 10) -> dict:
        def first(d):
            return [[k, v] for k, v in sorted(d.items(),
                                              key=lambda kv: -kv[1])[:top]]
        return {"device_ops": first(self.module_s),
                "idle_gaps": first(self.gaps)}


def summarize(ev: Events) -> Summary:
    windows = [(s, e) for n, s, e in ev.host if n == WINDOW]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW!r} span")
    lo, hi = windows[0]
    n_dev = max(1, len(ev.device))
    spans = [(n, s, e) for n, s, e in ev.host if n != WINDOW]
    by_name = {n: union([x for x in spans if x[0] == n], lo, hi)
               for n in {n for n, _s, _e in spans}}
    busy = 0.0
    module_s: Dict[str, float] = {}
    span_busy = dict.fromkeys(by_name, 0.0)
    every = []
    for intervals in ev.device.values():
        merged = union(intervals, lo, hi)
        busy += sum(e - s for s, e in merged)
        for name, host in by_name.items():
            span_busy[name] += _overlap(merged, host)
        for name, s, e in intervals:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                module_s[name] = module_s.get(name, 0.0) + (e - s) / n_dev
        every.extend(intervals)
    gaps: Dict[str, float] = {}
    t = lo
    for s, e in union(every, lo, hi) + [(hi, hi)]:
        if s > t:
            for name, a, b in _doing(spans, t, s):
                gaps[name] = gaps.get(name, 0.0) + (b - a) * 1e-9
        t = max(t, e)
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy / n_dev * 1e-9,
                   module_s={k: v * 1e-9 for k, v in module_s.items()},
                   gaps=gaps, devices=len(ev.device),
                   span_busy={k: v / n_dev * 1e-9
                              for k, v in span_busy.items()})


def _overlap(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _doing(spans, a: float, b: float) -> List[Interval]:
    """[a, b] cut where the host's spans begin and end, each piece named by
    the innermost span open in it (the window itself where none is)."""
    inside = [(n, s, e) for n, s, e in spans if e > a and s < b]
    cuts = sorted({a, b} | {t for _n, s, e in inside for t in (s, e)
                            if a < t < b})
    pieces = []
    for x, y in zip(cuts, cuts[1:]):
        open_ = [(e - s, n) for n, s, e in inside if s <= x and e >= y]
        pieces.append((min(open_)[1] if open_ else WINDOW, x, y))
    return pieces


def span_share(summary: Optional[Summary], span: str):
    """Per cent of the window the device was busy inside the benchmark's
    host spans named ``span``; None when there is no trace or no such span
    was open in the window."""
    if summary is None or summary.window_s <= 0 \
            or span not in summary.span_busy:
        return None
    return 100.0 * summary.span_busy[span] / summary.window_s


def idle_share(summary: Optional[Summary]):
    """Per cent of the window in which no operation ran on the device."""
    if summary is None or summary.window_s <= 0 or not summary.devices:
        return None
    return 100.0 * (1.0 - summary.busy_s / summary.window_s)
