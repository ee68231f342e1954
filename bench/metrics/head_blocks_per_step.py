"""Mean probe-union size U (IVF blocks the output layer read) over the
window's steps: the count that sets the estimator's HBM traffic."""


def read(run):
    steps = [s for s in run.window_steps() if s.n_active]
    return sum(s.head_live for s in steps) / len(steps) if steps else None
