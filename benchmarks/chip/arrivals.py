"""Open-loop request traffic, made from ``--seed``.

Arrivals are a Poisson process at a fixed rate: exponential gaps, drawn
before the window opens, so a slow server receives the same schedule as a
fast one and its queue grows.  Each request asks for the predictions of
``batch`` (client id, feature row) pairs; ids follow a Zipf law of
exponent ``zipf_s`` over the ranks 1..m, the ranks mapped to clients by a
seeded permutation; features come from a pool of distinct batches drawn in
set-up, so no host sampling runs inside the window.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from benchmarks.chip.federation import seed_entropy

_STREAM = 0x617272   # "arr"


class Arrivals:
    def __init__(self, traffic: Dict, m: int, d: int, seed: int,
                 rate_per_s: float, seconds: float):
        rng = np.random.default_rng(
            np.random.SeedSequence([_STREAM, seed_entropy(seed)]))
        n = int(np.ceil(rate_per_s * seconds * 1.5)) + 16
        gaps = rng.exponential(1.0 / rate_per_s, n)
        self.offsets = np.cumsum(gaps) - gaps[0]   # the first arrives at 0
        self.offsets = self.offsets[self.offsets < seconds]
        ranks = np.arange(1, m + 1, dtype=np.float64)
        cdf = np.cumsum(ranks ** -traffic["zipf_s"])
        cdf /= cdf[-1]
        clients = rng.permutation(m)
        batch = traffic["batch"]
        u = rng.random((len(self.offsets), batch))
        self.ids = clients[np.minimum(np.searchsorted(cdf, u), m - 1)]
        pool = traffic["feature_pool"]
        self.features = (rng.normal(0.0, 1.0, (pool, batch, d))
                         / np.sqrt(d)).astype(np.float32)
        self.pool_index = rng.integers(0, pool, len(self.offsets))

    def __len__(self) -> int:
        return len(self.offsets)

    def request(self, i: int):
        """(ids (batch,), features (batch, d)) of request i."""
        return self.ids[i], self.features[self.pool_index[i]]
