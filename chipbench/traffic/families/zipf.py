"""Static Zipf(``alpha``) reads over ``n_items`` keys, ``length`` per trace.

A copy of the repository's ``repro.traces.synthetic.zipf_trace``, kept
here so that the yardstick cannot move with the program: ranks are drawn
from the exact Zipf distribution and mapped to key ids by a seeded
permutation.
"""
from __future__ import annotations

import numpy as np


def zipf_probs(n_items: int, alpha: float) -> np.ndarray:
    w = np.arange(1, n_items + 1, dtype=np.float64) ** (-alpha)
    return w / w.sum()


def zipf_trace(length: int, n_items: int, alpha: float, seed) -> np.ndarray:
    rng = np.random.default_rng(seed)
    cdf = np.cumsum(zipf_probs(n_items, alpha))
    cdf[-1] = 1.0
    ranks = np.searchsorted(cdf, rng.random(length), side="right")
    return rng.permutation(n_items).astype(np.int64)[ranks]


def trace(params: dict, seed) -> np.ndarray:
    return zipf_trace(params["length"], params["n_items"], params["alpha"],
                      seed)
