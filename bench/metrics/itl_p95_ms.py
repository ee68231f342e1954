"""95th percentile of every gap between consecutive tokens of a request,
over all gaps that start inside the window, of every request. A token's
stamp is taken once per step, after the step's synchronous readback, so
a gap is the exact interval between two steps that served the request.
A gap that has not closed when the run stopped (a request that never got
its next token) lasts until the stop."""
import numpy as np


def read(run):
    lo, hi = run.window
    gaps = []
    for r in run.sent:
        t = r.token_times
        for a, b in zip(t, t[1:]):
            if lo <= a < hi:
                gaps.append(b - a)
        if t and lo <= t[-1] < hi and not r.done:
            gaps.append(run.stopped - t[-1])
    if not gaps:
        return None
    return float(np.percentile(gaps, 95)) * 1e3
