"""95th percentile, over every request due in the window, of the time
from its scheduled arrival to its first token. A request that failed or
never got a first token ranks above every other; its value is how long
it had waited when the run stopped (at least that late)."""
import numpy as np


def read(run):
    vals, missing = [], []
    for r in run.due():
        if r.token_times and r.error is None:
            vals.append(r.token_times[0] - r.at)
        else:
            missing.append(run.stopped - r.at)
    if not vals and not missing:
        return None
    ranked = sorted(vals) + sorted(max(m, max(vals, default=0.0))
                                   for m in missing)
    return float(np.percentile(ranked, 95)) * 1e3
