"""Cross-silo federations for the chip benchmark, made from ``--seed``.

The law is that of ``repro.data.synthetic.make_federation`` (the paper's
Table 2 shapes with synthetic data): latent clusters in weight space,
per-task shifted feature means, label noise, a 75/25 train/test split.  It
is copied here so that no change to the program can move the benchmark's
inputs.  One departure: the per-task sizes are a fixed, evenly spaced set
from ``n_min`` to ``n_max``, and the seed only permutes them over the tasks.
Every seed then has the same padded shapes and the same work per round, so
a run's set-up finds every program in the compile cache and seeds differ in
data, not in cost.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

#: domain separation of the federation stream from the benchmark's others
_STREAM = 0x666564   # "fed"

Split = Tuple[np.ndarray, np.ndarray, np.ndarray]   # X, y, mask (float32)


def seed_entropy(seed: int) -> int:
    """Any whole number as non-negative SeedSequence entropy."""
    return int(seed) % (1 << 64)


def task_sizes(cfg: Dict, rng: np.random.Generator) -> np.ndarray:
    sizes = np.round(np.linspace(cfg["n_min"], cfg["n_max"], cfg["m"]))
    return rng.permutation(sizes.astype(np.int64))


def make_federation(cfg: Dict, seed: int) -> Tuple[Split, Split]:
    """(train, test) padded arrays for configuration ``cfg``."""
    rng = np.random.default_rng(
        np.random.SeedSequence([_STREAM, seed_entropy(seed)]))
    m, d = cfg["m"], cfg["d"]
    sizes = task_sizes(cfg, rng)
    centers = rng.normal(0.0, 1.0, (cfg["clusters"], d)) / np.sqrt(d)
    assign = rng.integers(0, cfg["clusters"], m)
    w_true = centers[assign] + cfg["cluster_spread"] * rng.normal(
        0.0, 1.0, (m, d)) / np.sqrt(d)
    mu = cfg["feature_shift"] * rng.normal(0.0, 1.0, (m, d)) / np.sqrt(d)

    def build(split_sizes: np.ndarray) -> Split:
        npad = int(max(split_sizes.max(), 1))
        X = np.zeros((m, npad, d), np.float32)
        y = np.zeros((m, npad), np.float32)
        mask = np.zeros((m, npad), np.float32)
        for t in range(m):
            n = int(split_sizes[t])
            xt = mu[t] + rng.normal(0.0, 1.0, (n, d)) / np.sqrt(d)
            yt = np.sign(xt @ w_true[t] + 1e-12)
            flip = rng.random(n) < cfg["label_noise"]
            yt[flip] = -yt[flip]
            X[t, :n], y[t, :n], mask[t, :n] = xt, yt, 1.0
        return X, y, mask

    n_train = np.maximum((sizes * cfg["train_frac"]).astype(np.int64), 1)
    n_test = np.maximum(sizes - n_train, 1)
    return build(n_train), build(n_test)
