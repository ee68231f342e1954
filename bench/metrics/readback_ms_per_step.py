"""Median duration of the ``sched.readback`` span over the window's
untraced steps, in ms: from the step's outputs being ready on the device
to their being host arrays."""
from bench.spans import median_ms


def read(run):
    return median_ms(run, "sched.readback")
