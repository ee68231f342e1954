"""Output tokens emitted inside the window, by every request, over the
window's seconds."""


def read(run):
    lo, hi = run.window
    n = sum(1 for r in run.sent for t in r.token_times if lo <= t < hi)
    return n / (hi - lo)
