"""The comparison that decides ``correct``.

Once the window has closed, a sample of the completions it served,
drawn from the seed and always holding the longest request and the
longest greedy one, is teacher-forced through the float32 reference
(``reference.score``). For every token the program emitted it gave two
numbers: its log-probability, the token's logit minus the estimated
log Z, and the estimated log Z itself. So

* ``logit_err``: the widest gap between the logit the program served
  (log_prob + log_z) and the reference's logit of that same token. This
  holds the trunk's cached decode, prompt replay through the cache, and
  the head row that scored the token;
* ``logz_rel_err``: the mean of |exp(log Z_hat - log Z) - 1| against the
  reference's exact log Z over the whole vocabulary. This holds the
  output layer's estimate;
* ``greedy_rank``: over the tokens of greedy requests, the most rows of
  the vocabulary that the reference scores above the token the program
  chose (0: the reference's own best). This holds the choice of the
  token: a wrong candidate, a wrong lane's row, a sampled token where the
  request asked for the best;
* ``missing``: requests due in the window that never completed within the
  follow time, ``recompiles`` after warm-up, and ``health_flagged`` lanes
  (estimates the program itself found unhealthy) are held at 0.

Each number has its limit in the cell's file; the readings each limit was
set from are in PERF.md. The control (the reference in int8 put in the
program's place) is held to the same limits by the same ``hold``.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from . import reference, seeds

NUMBERS = ("logit_err", "logz_rel_err", "greedy_rank", "missing",
           "recompiles", "health_flagged")


def pick_sample(done: Sequence, n: int, seed: int) -> List:
    """``n`` completed requests drawn from the seed, the longest and the
    longest greedy one always among them (ties to the earliest)."""
    def longest(rs):
        return max(rs, key=lambda r: (r.n_prompt + len(r.tokens), -r.index))

    if not done:
        return []
    fixed = [longest(done)]
    greedy = [r for r in done if r.temperature == 0.0]
    if greedy and longest(greedy) is not fixed[0]:
        fixed.append(longest(greedy))
    rest = [r for r in done if all(r is not f for f in fixed)]
    k = max(0, min(n - len(fixed), len(rest)))
    pick = seeds.rng(seed, seeds.SAMPLE).choice(len(rest), size=k,
                                                replace=False)
    return fixed[:n] + [rest[i] for i in sorted(pick)]


def greedy_mask(sample: Sequence) -> np.ndarray:
    """Which served positions belong to greedy requests."""
    return np.concatenate([np.full(len(r.tokens), r.temperature == 0.0)
                           for r in sample])


def _greedy_rank(above: np.ndarray, greedy: np.ndarray) -> Optional[float]:
    return float(np.max(above[greedy])) if greedy.any() else None


def served_numbers(sample: Sequence, ref: reference.Scores
                   ) -> Dict[str, float]:
    """The compared numbers of the served tokens against the reference."""
    lp = np.concatenate([np.asarray(r.log_probs, np.float64)
                         for r in sample])
    lz = np.concatenate([np.asarray(r.log_zs, np.float64) for r in sample])
    tok = ref.tok.astype(np.float64)
    lse = ref.lse.astype(np.float64)
    greedy = greedy_mask(sample)
    gap = ref.best.astype(np.float64) - tok
    return {"logit_err": float(np.max(np.abs(lp + lz - tok))),
            "logz_rel_err": float(np.mean(np.abs(np.expm1(lz - lse)))),
            "greedy_rank": _greedy_rank(ref.above, greedy),
            "greedy_gap": float(np.max(gap[greedy])) if greedy.any()
            else None,
            "tokens": int(lp.size)}


def control_numbers(ctl: reference.Scores, ref: reference.Scores,
                    ranked: reference.Scores, greedy: np.ndarray
                    ) -> Dict[str, float]:
    """The same numbers for the control: the reference in lower precision
    put in the program's place, at the same positions and tokens. Its
    choice at a greedy position is the token it puts first; ``ranked`` is
    the float32 reference read with those tokens as targets."""
    return {"logit_err": float(np.max(np.abs(
                ctl.tok.astype(np.float64) - ref.tok))),
            "logz_rel_err": float(np.mean(np.abs(np.expm1(
                ctl.lse.astype(np.float64) - ref.lse)))),
            "greedy_rank": _greedy_rank(ranked.above, greedy)}


def hold(numbers: Dict[str, Optional[float]], limits: Dict[str, float]):
    """Hold every number to its limit: (correct, {name: {value, limit}}).
    A number that could not be read is not correct."""
    checks, correct = {}, True
    for name in NUMBERS:
        value = numbers.get(name)
        limit = float(limits.get(name, 0.0))
        checks[name] = {"value": value, "limit": limit}
        correct &= value is not None and bool(value <= limit)
    return correct, checks


def sequences(sample: Sequence):
    return [(np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)]),
             r.n_prompt) for r in sample]


def compare(params, arch, sample: Sequence, length: int,
            limits: Dict[str, float], counts: Dict[str, int],
            control: bool = False):
    """Run the reference over ``sample`` and hold every number to its
    limit. Returns (correct, checks, info). With ``control`` the int8
    control is read on the same sample, with the run's own counts, and
    held by the same ``hold``: ``info["control"]`` is its (correct,
    checks)."""
    numbers: Dict[str, Optional[float]] = dict(counts)
    info: Dict = {"sampled_requests": len(sample)}
    if not sample:
        return False, hold(numbers, limits)[1], info
    seqs = sequences(sample)
    ref = reference.score(params, arch, seqs, length)
    served = served_numbers(sample, ref)
    info.update(greedy_gap=served.pop("greedy_gap"),
                compared_tokens=served.pop("tokens"))
    numbers.update(served)
    correct, checks = hold(numbers, limits)
    if control:
        ctl = reference.score(params, arch, seqs, length, mode="int8")
        ranked = reference.score(params, arch, seqs, length,
                                 targets=ctl.first)
        c_numbers = dict(counts)
        c_numbers.update(control_numbers(ctl, ref, ranked,
                                         greedy_mask(sample)))
        info["control"] = hold(c_numbers, limits)
    return correct, checks, info
