"""Median duration of the ``sched.prep`` span over the window's untraced
steps, in ms: the scheduler's host work before the compiled step: fault
hooks, the verify cadence, tier state and the step's arguments."""
from bench.spans import median_ms


def read(run):
    return median_ms(run, "sched.prep")
