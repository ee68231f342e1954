"""Mean |exp(log Z_hat - log Z) - 1| of the served log Z estimates against
the float32 reference's exact log Z, at the same positions, over the
seeded sample of the window's completions (computed after the window)."""


def read(run):
    return run.checks.get("logz_rel_err", {}).get("value")
