"""Device time of the scheduler step's executable per run of it, from the
trace's executable events."""

STEP_MODULE = r"^jit_step\b"


def read(run):
    r = run.reduction
    if r is None:
        return None
    secs, n = r.modules_matching(STEP_MODULE)
    return secs / n * 1e3 if n else None
