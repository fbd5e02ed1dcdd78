"""The one traffic generator: everything a runner draws from a seed.

A traffic mix is a data file, ``bench/traffic/<name>.json``, of
parameters (lengths, batch, grid shape); the runner it names drives the
program with it, drawing from the seed through the functions here.
"""
from __future__ import annotations

import numpy as np


def sample(seed: int, population: int, k: int, *, must=()) -> np.ndarray:
    """``k`` distinct indices of ``population`` drawn from the seed,
    always holding the indices in ``must``."""
    rng = np.random.default_rng([seed, 0x5eed])
    rest = [i for i in rng.permutation(population) if i not in set(must)]
    return np.sort(np.asarray(list(must) + rest[:max(0, k - len(must))]))
