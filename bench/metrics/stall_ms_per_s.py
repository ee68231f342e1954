"""Stall time per second of the window: by how much each ``sched.step``
and ``sched.admit`` span exceeds three times the window's median for its
name, summed over the window's untraced spans, over the window's untraced
seconds, in ms/s (``bench.spans.stall_ms_per_s``)."""
from bench.spans import stall_ms_per_s


def read(run):
    return stall_ms_per_s(run)
