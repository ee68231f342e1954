"""Every random choice of a run comes from ``--seed`` through here, one
stream per use, so that no two uses share draws."""
from __future__ import annotations

import numpy as np

WEIGHTS, INDEX, SCHEDULER, TRAFFIC, SAMPLE = range(5)


def rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one use of the seed; any integer seed, however
    large or negative, maps to its own entropy."""
    return np.random.default_rng([abs(int(seed)), int(seed < 0), stream])


def key_words(seed: int, stream: int) -> np.ndarray:
    """A raw uint32[2] PRNG key (JAX's default key layout) for one use."""
    return rng(seed, stream).integers(0, 2**32, size=2, dtype=np.uint32)
