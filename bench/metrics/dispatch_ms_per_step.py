"""Median duration of the ``sched.dispatch`` span over the window's
untraced steps, in ms: the compiled step's call, from entering it to its
return (the enqueue), with the outputs' copies to the host queued behind
it."""
from bench.spans import median_ms


def read(run):
    return median_ms(run, "sched.dispatch")
