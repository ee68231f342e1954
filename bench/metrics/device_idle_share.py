"""1 - (union of the device's operation intervals) / (traced window), in
percent, over the traced stretch of the window."""


def read(run):
    r = run.reduction
    return None if r is None else 100.0 * r.idle_share
