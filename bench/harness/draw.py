"""Seeded draws shared by the traffic generators.

Every size is a stratified quantile of its distribution, so each seed
serves the same multiset of sizes and only their order,
and the token ids, depend on the seed: two seeds then ask the system for
the same amount of work, and the spread between runs is the system's.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np

_NORMAL = NormalDist()


def stratified_lognormal(n: int, spec: Dict) -> List[int]:
    """``n`` integer sizes at the quantiles ``(i + 0.5) / n`` of a
    lognormal with the given ``median`` and ``sigma``, clipped to
    ``[min, max]``; ascending."""
    lo, hi = int(spec["min"]), int(spec["max"])
    out = []
    for i in range(n):
        z = _NORMAL.inv_cdf((i + 0.5) / n)
        v = float(spec["median"]) * math.exp(float(spec["sigma"]) * z)
        out.append(int(min(max(round(v), lo), hi)))
    return out


def tokens(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    """``n`` uniform token ids in ``[0, vocab)``."""
    return rng.integers(0, vocab, size=n, dtype=np.int64).astype(np.int32)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream...) — any whole number
    seed, negative or past 64 bits included."""
    return np.random.default_rng([int(seed) % (1 << 64), *stream])
