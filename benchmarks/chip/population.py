"""The cross-device population law, for the reference to rebuild inputs.

A cohort cell hands the program a seeded ``repro.cohort.Population``, which
makes each sampled client's data inside the program's pack stage: that is
how the simulator works.  The reference must not take data the program
made, so this module is the benchmark's own copy of the laws that decide a
run's inputs: each client's data as a pure function of (population seed,
client id), the cohort schedule of a run seed, and the per-block seeds.
They follow ``repro.cohort.population``, ``repro.cohort.sampler`` and
``repro.cohort.driver._block_seed`` draw for draw, so that the same seeds
give the same bytes; a program that packs other data fails the comparison.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

_POP_STREAM = 0x706F70       # "pop": latent cluster centers
_CLIENT_STREAM = 0x636C69    # "cli": one client's data
_SCHEDULE_STREAM = 0x636F68  # "coh": cohort selection and dropout
_BLOCK_STREAM = 0x626C6B     # "blk": per-block solver seeds


def centers(cfg: Dict, seed: int) -> np.ndarray:
    rng = np.random.default_rng(np.random.SeedSequence([_POP_STREAM, seed]))
    d = cfg["d"]
    return rng.normal(0.0, 1.0, (cfg["clusters"], d)) / np.sqrt(d)


def client(cfg: Dict, seed: int, ctrs: np.ndarray, t: int
           ) -> Tuple[np.ndarray, np.ndarray]:
    """Client t's (X (n, d), y (n,)) float32 data."""
    d = cfg["d"]
    rng = np.random.default_rng(
        np.random.SeedSequence([_CLIENT_STREAM, seed, int(t)]))
    cluster = int(rng.integers(0, cfg["clusters"]))
    n = max(int(rng.integers(cfg["n_min"], cfg["n_max"] + 1)), 1)
    w_true = ctrs[cluster] + cfg["cluster_spread"] * rng.normal(
        0.0, 1.0, d) / np.sqrt(d)
    mu = cfg["feature_shift"] * rng.normal(0.0, 1.0, d) / np.sqrt(d)
    X = mu + rng.normal(0.0, 1.0, (n, d)) / np.sqrt(d)
    y = np.sign(X @ w_true + 1e-12)
    flip = rng.random(n) < cfg["label_noise"]
    y[flip] = -y[flip]
    return X.astype(np.float32), y.astype(np.float32)


def schedule(cfg: Dict, seed: int, blocks: int
             ) -> Tuple[np.ndarray, np.ndarray]:
    """((blocks, K) client ids, (blocks, K) dropped) of a run seed:
    uniform cohorts without replacement, then the dropout draws."""
    rng = np.random.default_rng(
        np.random.SeedSequence([_SCHEDULE_STREAM, seed]))
    K = cfg["cohort"]
    ids = np.empty((blocks, K), np.int64)
    for b in range(blocks):
        ids[b] = rng.choice(cfg["m"], K, replace=False)
    dropped = rng.random((blocks, K)) < cfg["dropout"]
    return ids, dropped


def block_seed(seed: int, block: int) -> int:
    ss = np.random.SeedSequence([_BLOCK_STREAM, seed, block])
    return int(ss.generate_state(1, np.uint32)[0])
