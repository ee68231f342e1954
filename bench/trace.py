"""From a profiler trace to the numbers the metric readers use.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three kinds of events: the device's operations, the device's executables
(one event per run of a jitted program), and the host thread that carries
the benchmark's ``bench.*`` annotations. ``reduce`` then gives, over the
traced window (the ``bench.window`` span):

* busy time: the union of the device's operation intervals, clipped to the
  window, and the idle share 1 - busy / window;
* time per operation (``short_name``; ops that only contain others, such
  as a layer scan's while loop, are left out of this) and per executable;
* the idle gaps, each put down to what the host was doing in it: the
  ``bench.*`` span that overlaps it most, and inside that span the
  innermost host event at the gap's midpoint; a gap outside every span
  is the open loop's own work between its calls (``bench.loop``).

The reduction works on plain ``Event`` records, so a test can check it
against a small recorded trace without a chip.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
# ops that only contain other ops (a scanned layer stack runs as one
# while op around its body's ops): they count towards busy time, not
# towards any op's own time
CONTAINERS = ("while", "conditional", "call")
_HLO = re.compile(r"^(%[\w.\-]+) = .*? ([a-z][\w\-]*)\(")


def short_name(name: str) -> str:
    """``%fusion.12 (fusion)`` for an HLO op's full text; other names as
    they are."""
    m = _HLO.match(name)
    return f"{m.group(1)} ({m.group(2)})" if m else name


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Reduction:
    window_ns: Tuple[float, float]
    n_devices: int
    busy_ns: float                       # mean over the devices
    op_ns: Dict[str, float]              # summed over the devices
    op_count: Dict[str, int]
    module_ns: Dict[str, float]
    module_count: Dict[str, int]
    gaps: List[Tuple[float, float, str]]  # (start, end, what the host did)

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def ops_matching(self, pattern: str) -> Tuple[float, int]:
        """(seconds, count) of the operations whose name matches."""
        rx = re.compile(pattern)
        ns = sum(v for k, v in self.op_ns.items() if rx.search(k))
        n = sum(v for k, v in self.op_count.items() if rx.search(k))
        return ns * 1e-9 / self.n_devices, n // self.n_devices

    def modules_matching(self, pattern: str) -> Tuple[float, int]:
        rx = re.compile(pattern)
        ns = sum(v for k, v in self.module_ns.items() if rx.search(k))
        n = sum(v for k, v in self.module_count.items() if rx.search(k))
        return ns * 1e-9 / self.n_devices, n // self.n_devices

    def top_ops(self, n: int = 10) -> List[List]:
        items = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9 / self.n_devices] for k, v in items]

    def top_gaps(self, n: int = 10) -> List[List]:
        by: Dict[str, float] = defaultdict(float)
        for s, e, what in self.gaps:
            by[what] += (e - s) * 1e-9
        return [[k, v] for k, v in sorted(by.items(),
                                          key=lambda kv: -kv[1])[:n]]


def load(trace_dir: str) -> List[Event]:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out: List[Event] = []
    for plane in pd.planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            keep_dev = dev and line.name in (OPS_LINE, MODULES_LINE)
            if not keep_dev and plane.name != HOST_PLANE:
                continue
            evs = [Event(plane.name, line.name, e.name, float(e.start_ns),
                         float(e.duration_ns)) for e in line.events]
            if not keep_dev and not any(e.name.startswith("bench.")
                                        for e in evs):
                continue
            out.extend(evs)
    return out


def save_events(events: Iterable[Event], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([dataclasses.astuple(e) for e in events], f)


def load_events(path: str) -> List[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(*e) for e in json.load(f)]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


class _Host:
    """The host thread's events, indexed for attributing gaps."""

    def __init__(self, host: List[Event]):
        self.spans = sorted((h for h in host if h.name.startswith("bench.")
                             and h.name != WINDOW_SPAN),
                            key=lambda h: h.start_ns)
        self.span_starts = [h.start_ns for h in self.spans]
        self.inner = sorted((h for h in host
                             if not h.name.startswith("bench.")),
                            key=lambda h: h.start_ns)
        self.inner_starts = [h.start_ns for h in self.inner]

    def attribute(self, s: float, e: float) -> str:
        """The bench.* span with the most overlap of [s, e), then the
        innermost host event under the gap's midpoint inside it."""
        best, best_ov = None, 0.0
        i = bisect.bisect_left(self.span_starts, e) - 1
        while i >= 0:
            h = self.spans[i]
            ov = min(e, h.end_ns) - max(s, h.start_ns)
            if ov > best_ov:
                best, best_ov = h, ov
            if h.end_ns <= s:          # spans are sequential, not nested
                break
            i -= 1
        if best is None:
            return "bench.loop"     # between calls: the loop's own work
        mid = (s + e) / 2.0
        deepest = None
        j = bisect.bisect_right(self.inner_starts, mid) - 1
        while j >= 0 and self.inner[j].start_ns >= best.start_ns:
            h = self.inner[j]
            if h.end_ns >= mid and h.end_ns <= best.end_ns and (
                    deepest is None or h.dur_ns < deepest.dur_ns):
                deepest = h
            j -= 1
        return best.name if deepest is None \
            else f"{best.name} > {deepest.name}"


def reduce(events: List[Event], min_gap_ns: float = 1000.0,
           window: Optional[Tuple[float, float]] = None) -> Reduction:
    """Reduce ``events`` over the ``bench.window`` span (or ``window``)."""
    host = [e for e in events if e.plane == HOST_PLANE]
    if window is None:
        spans = [e for e in host if e.name == WINDOW_SPAN]
        if not spans:
            raise ValueError("the trace has no bench.window span")
        window = (spans[0].start_ns, spans[0].end_ns)
    lo, hi = window
    devices = sorted({e.plane for e in events
                      if DEVICE_PLANE.match(e.plane)})
    if not devices:
        raise ValueError("the trace has no device plane")
    op_ns: Dict[str, float] = defaultdict(float)
    op_count: Dict[str, int] = defaultdict(int)
    mod_ns: Dict[str, float] = defaultdict(float)
    mod_count: Dict[str, int] = defaultdict(int)
    busy_total = 0.0
    gaps: List[Tuple[float, float, str]] = []
    index = _Host(host)
    for d in devices:
        ops = [e for e in events if e.plane == d and e.line == OPS_LINE
               and e.end_ns > lo and e.start_ns < hi]
        for e in ops:
            name = short_name(e.name)
            if name.endswith(tuple(f"({c})" for c in CONTAINERS)):
                continue
            op_ns[name] += min(e.end_ns, hi) - max(e.start_ns, lo)
            op_count[name] += 1
        for e in events:
            if e.plane == d and e.line == MODULES_LINE \
                    and e.end_ns > lo and e.start_ns < hi:
                mod_ns[e.name] += min(e.end_ns, hi) - max(e.start_ns, lo)
                mod_count[e.name] += 1
        busy = _clip(_union([(e.start_ns, e.end_ns) for e in ops]), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        if d == devices[0]:
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            for s, e in zip(edges[::2], edges[1::2]):
                if e - s >= min_gap_ns:
                    gaps.append((s, e, index.attribute(s, e)))
    return Reduction(window_ns=(lo, hi), n_devices=len(devices),
                     busy_ns=busy_total / len(devices), op_ns=dict(op_ns),
                     op_count=dict(op_count), module_ns=dict(mod_ns),
                     module_count=dict(mod_count), gaps=gaps)
