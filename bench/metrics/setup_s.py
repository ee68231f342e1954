"""Process start to window start: imports, weights from the seed, the
IVF index build, compilation (or the compile cache) and the warm-up
stretch of traffic."""


def read(run):
    return run.setup_s
