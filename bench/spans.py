"""The program's own spans over the window, for the readers of the
scheduler's phases.

The scheduler records its spans in the process's span log,
``repro.obs.tracing.SPANS``: ``sched.step`` for each step, with the
children ``sched.prep``, ``sched.dispatch``, ``sched.wait``,
``sched.readback`` and ``sched.emit`` (each carrying the step's index as
its id), and ``sched.admit`` for each admission. Stamps are
``time.perf_counter()`` seconds, the clock of the window and of the step
records, and a ``sched.step`` span starts at its record's ``t_start``.

A reader takes the spans that start inside the window and leaves out
those that ran while the profiler was on: the steps whose record says
``traced``, and the admissions from the first traced step up to the
first untraced step after it. A program without the span log gives
``None``.
"""
from __future__ import annotations

import statistics
from collections import defaultdict
from typing import Dict, List, Optional

STEP = "sched.step"
ADMIT = "sched.admit"
STALL_FACTOR = 3.0


def _records():
    try:
        from repro.obs import tracing
        return tracing.SPANS.records()
    except (ImportError, AttributeError):
        return None


def _traced_stretch(run):
    """(start, end) of the block of traced steps, None if none."""
    steps = run.steps
    idx = [i for i, s in enumerate(steps) if s.traced]
    if not idx:
        return None
    end = steps[idx[-1] + 1].t_start if idx[-1] + 1 < len(steps) \
        else float("inf")
    return steps[idx[0]].t_start, end


class WindowSpans:
    """The window's untraced spans, by name."""

    def __init__(self, run, records):
        lo, hi = run.window
        traced = {s.t_start for s in run.steps if s.traced}
        stretch = _traced_stretch(run)
        self.seconds = hi - lo
        if stretch is not None:
            self.seconds -= max(0.0, min(stretch[1], hi)
                                - max(stretch[0], lo))
        steps = [r for r in records if r.name == STEP
                 and lo <= r.start < hi and r.start not in traced]
        by_id: Dict[int, List] = defaultdict(list)
        for r in steps:
            by_id[r.id].append(r)
        self.by_name: Dict[str, List] = defaultdict(list)
        self.by_name[STEP] = steps
        for r in records:
            if r.parent == STEP and any(p.start <= r.start <= p.end
                                        for p in by_id.get(r.id, ())):
                self.by_name[r.name].append(r)
            elif r.name == ADMIT and lo <= r.start < hi and not (
                    stretch and stretch[0] <= r.start < stretch[1]):
                self.by_name[ADMIT].append(r)

    def durations_ms(self, name: str) -> List[float]:
        return [(r.end - r.start) * 1e3 for r in self.by_name.get(name, ())]


def window_spans(run) -> Optional[WindowSpans]:
    """The window's spans, or None where the program records none."""
    records = _records()
    if not records:
        return None
    ws = WindowSpans(run, records)
    return ws if ws.by_name[STEP] else None


def median_ms(run, name: str) -> Optional[float]:
    """Median duration of the window's ``name`` spans, in ms."""
    ws = window_spans(run)
    if ws is None:
        return None
    d = ws.durations_ms(name)
    return statistics.median(d) if d else None


def stall_ms_per_s(run) -> Optional[float]:
    """Time by which ``sched.step`` and ``sched.admit`` spans exceed
    STALL_FACTOR times their name's median in the window, summed, per
    second of the window's untraced time."""
    ws = window_spans(run)
    if ws is None or ws.seconds <= 0:
        return None
    excess = 0.0
    for name in (STEP, ADMIT):
        d = ws.durations_ms(name)
        if d:
            cut = STALL_FACTOR * statistics.median(d)
            excess += sum(x - cut for x in d if x > cut)
    return excess / ws.seconds
