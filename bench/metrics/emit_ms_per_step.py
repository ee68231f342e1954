"""Median duration of the ``sched.emit`` span over the window's untraced
steps, in ms: the per-lane loop over the step's host outputs, the
requests' callbacks and the step's record."""
from bench.spans import median_ms


def read(run):
    return median_ms(run, "sched.emit")
