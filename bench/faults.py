"""Faults planted in the timed path, to show that ``correct`` catches them.

Each replaces one function of ``repro.serve.scheduler`` for as long as
the context is open:

* ``token``: every lane's token altered where it is produced (a bit
  flipped), its score left as the original token's;
* ``wrong_row``: every lane given the token and score the next lane chose;
* ``last_candidate``: greedy lanes take the last of their retrieved
  candidates, with that candidate's own score (a consistent wrong choice);
* ``log_z``: the estimate of log Z shifted by 0.7 where it is produced.

Used by the tests and by ``tools/control.py``; a benchmark run never
plants one.
"""
from __future__ import annotations

import contextlib

FAULTS = ("token", "wrong_row", "last_candidate", "log_z")


@contextlib.contextmanager
def planted(name: str):
    import jax.numpy as jnp
    from repro.serve import scheduler

    if name not in FAULTS:
        raise ValueError(f"unknown fault {name!r}; have {FAULTS}")
    sample, guard = scheduler.sample_slots, scheduler.apply_health_guard

    def sample_fault(out, keys, temperature, sample_k=None):
        tok, score = sample(out, keys, temperature, sample_k)
        if name == "token":
            return tok ^ 1, score
        if name == "wrong_row":
            return jnp.roll(tok, 1), jnp.roll(score, 1)
        last = out.top_id.shape[1] - 1
        greedy = jnp.asarray(temperature) == 0.0
        return (jnp.where(greedy, out.top_id[:, last], tok).astype(tok.dtype),
                jnp.where(greedy, out.top_score[:, last], score))

    def guard_fault(out, *a, **kw):
        out, flags = guard(out, *a, **kw)
        return out._replace(log_z=out.log_z + 0.7), flags

    attr, fn = (("apply_health_guard", guard_fault) if name == "log_z"
                else ("sample_slots", sample_fault))
    setattr(scheduler, attr, fn)
    try:
        yield
    finally:
        setattr(scheduler, attr, guard if name == "log_z" else sample)
