"""One generator for every traffic mix: a mix file of parameters in, a
seeded arrival schedule out.

Arrivals are open loop: each request has a scheduled send time, fixed
before the run, and is sent at that time whatever the system is doing.

Every seed gets the same work in another order. A run is cut into
segments (warm-up, window, follow); in each, the request count is
``round(rate * seconds)``, the gaps between arrivals are the quantiles of
an exponential distribution (a Poisson process's gaps, stratified), and
prompt and answer lengths are the quantiles of the mix's length
distributions. Each list is dealt out over blocks of ``block_s`` seconds
of the segment, so that every block holds the same mix of short and long
(requests of up to 34 s are not short beside a 51 s window, and where the
longest land decides how much of their output falls inside it); the seed
orders each block, and draws the token ids and the per-request sampling
keys. So two seeds differ in which request comes when, never in how much
work a segment or one of its blocks holds, and a difference between seeds
is not a difference in load.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List, Sequence

import numpy as np

from . import seeds


@dataclasses.dataclass
class Planned:
    """One request of the schedule, before it is handed to the program."""
    index: int
    segment: str           # 'warmup' | 'window' | 'follow'
    at_s: float            # scheduled send time from the schedule's origin
    prompt: np.ndarray     # (P,) int32 token ids
    answer_tokens: int     # tokens to generate
    temperature: float
    key: np.ndarray        # (2,) uint32 sampling key of the request


def _quantiles(spec: Dict, n: int) -> List[int]:
    """n stratified draws of a clipped length distribution, as ints."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = NormalDist()
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        x = int(round(math.exp(mu + sigma * z)))
        out.append(min(max(x, spec["min"]), spec["max"]))
    return out


def _gaps(n: int, seconds: float) -> np.ndarray:
    """Exponential-quantile gaps scaled to fill ``seconds`` exactly."""
    q = (np.arange(n) + 0.5) / n
    g = -np.log1p(-q)
    return g * (seconds / g.sum())


def _dealt(values: Sequence, n_blocks: int, rng) -> List:
    """Deal the sorted values over ``n_blocks`` consecutive blocks, back
    and forth so that each block gets the same spread of sizes, and let
    the seed order each block."""
    blocks: List[List] = [[] for _ in range(n_blocks)]
    for i, x in enumerate(sorted(values)):
        lap, k = divmod(i, n_blocks)
        blocks[k if lap % 2 == 0 else n_blocks - 1 - k].append(x)
    return [x for b in blocks for x in rng.permutation(np.asarray(b))]


def schedule(mix: Dict, rate_per_s: float, window_s: float, seed: int,
             vocab: int) -> List[Planned]:
    """The whole run's arrivals: ``mix['warmup_s']`` of warm-up, the
    measured window, then ``mix['follow_s']`` more, all at one rate.
    Times are seconds from the start of the warm-up."""
    if mix.get("loop") != "open":
        raise ValueError("only open-loop mixes are defined")
    if rate_per_s <= 0:
        raise ValueError("rate_per_s must be positive")
    rng = seeds.rng(seed, seeds.TRAFFIC)
    temps: Sequence[float] = mix["temperatures"]
    segments = (("warmup", 0.0, float(mix["warmup_s"])),
                ("window", float(mix["warmup_s"]), float(window_s)),
                ("follow", float(mix["warmup_s"]) + window_s,
                 float(mix["follow_s"])))
    out: List[Planned] = []
    for name, start, seconds in segments:
        n = max(1, int(round(rate_per_s * seconds)))
        nb = max(1, min(n, int(round(seconds / float(mix["block_s"])))))
        gaps = np.asarray(_dealt(_gaps(n, seconds), nb, rng))
        # the first arrival comes half a gap in, so a segment's arrivals
        # centre in it and the next segment's first gap is a whole one
        at = start + np.cumsum(gaps) - gaps / 2.0
        p_lens = _dealt(_quantiles(mix["prompt_tokens"], n), nb, rng)
        a_lens = _dealt(_quantiles(mix["answer_tokens"], n), nb, rng)
        for i in range(n):
            j = len(out)
            out.append(Planned(
                index=j, segment=name, at_s=float(at[i]),
                prompt=rng.integers(0, vocab, size=int(p_lens[i]),
                                    dtype=np.int32),
                answer_tokens=int(a_lens[i]),
                temperature=float(temps[j % len(temps)]),
                key=rng.integers(0, 2**32, size=2, dtype=np.uint32)))
    return out


def max_tokens(mix: Dict) -> int:
    """Cache positions the longest request of the mix needs."""
    return mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"] - 1
