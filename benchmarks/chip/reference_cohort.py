"""Plain cross-device MOCHA over cohort blocks, the yardstick of the
population cells.

One block: K clients of the cohort schedule, their data (``population.py``),
one W-round of SDCA (``reference.run_block``) warm-started from the cached
dual blocks of returning clients, under the K x K relationship that the
clusters give (Omega_S[i, j] = omega_k[a_i, a_j] + eta 1[i = j]).  The fold
then re-assigns each client that took part to the nearest centroid that
has seen data (a client whose cluster is still cold keeps it), moves each
cluster's centroid to the running mean of its members' solved weights,
caches each client's dual block and its weights' offset from its centroid
(least recently used out first), and every ``omega_update_every`` blocks
takes the probabilistic Omega step on the k x d centroid matrix.  The fold
is written in float64; the block solve in float32 at the configuration's
precision.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import population, reference


class State:
    """The factored state a run of blocks carries."""

    def __init__(self, cfg: Dict):
        k, d = cfg["clusters"], cfg["d"]
        self.omega_k = np.eye(k) / k
        self.centroids = np.zeros((k, d))
        self.counts = np.zeros(k, np.int64)
        self.assign = np.arange(cfg["m"]) % k
        self.cache: "OrderedDict[int, tuple]" = OrderedDict()

    def snapshot(self) -> Dict:
        ids = np.array(sorted(self.cache), np.int64)
        deltas = (np.stack([self.cache[int(t)][1] for t in ids])
                  if ids.size else np.zeros((0, self.centroids.shape[1])))
        return {"centroids": self.centroids.copy(),
                "assign": self.assign.copy(), "cache_ids": ids,
                "cache_delta": deltas}


def _pack(cfg: Dict, pop_seed: int, ctrs, ids):
    K, n_pad, d = len(ids), cfg["n_pad"], cfg["d"]
    X = np.zeros((K, n_pad, d), np.float32)
    y = np.zeros((K, n_pad), np.float32)
    mask = np.zeros((K, n_pad), np.float32)
    sizes = np.zeros(K, np.int64)
    for slot, t in enumerate(ids):
        Xt, yt = population.client(cfg, pop_seed, ctrs, int(t))
        n = Xt.shape[0]
        X[slot, :n], y[slot, :n], mask[slot, :n] = Xt, yt, 1.0
        sizes[slot] = n
    return X, y, mask, sizes


def fold(cfg: Dict, st: State, b: int, ids, W, alpha, sizes, part) -> None:
    if not part.any():
        return
    pid, W_p = ids[part], np.asarray(W, np.float64)[part]
    warm = np.flatnonzero(st.counts > 0)
    if warm.size:
        d2 = ((W_p[:, None, :] - st.centroids[warm][None, :, :]) ** 2).sum(-1)
        nearest = warm[np.argmin(d2, axis=1)]
        cur = st.assign[pid]
        st.assign[pid] = np.where(st.counts[cur] > 0, nearest, cur)
    a_p = st.assign[pid]
    for c in np.unique(a_p):
        members = W_p[a_p == c]
        st.counts[c] += members.shape[0]
        st.centroids[c] += (members.shape[0] / st.counts[c]) * (
            members.mean(axis=0) - st.centroids[c])
    for slot in np.flatnonzero(part):
        t = int(ids[slot])
        delta = np.asarray(W[slot], np.float64) - st.centroids[st.assign[t]]
        st.cache[t] = (np.asarray(alpha[slot, :sizes[slot]]), delta)
        st.cache.move_to_end(t)
    while len(st.cache) > cfg["cache_clients"]:
        st.cache.popitem(last=False)
    every = cfg["omega_update_every"]
    if every and (b + 1) % every == 0:
        C = st.centroids
        w, q = np.linalg.eigh(C @ C.T)
        root = (q * np.sqrt(np.maximum(w, 1e-10))) @ q.T
        tr = np.trace(root)
        st.omega_k = (root / tr if tr > 1e-8
                      else np.eye(C.shape[0]) / C.shape[0])


def run_job(cfg: Dict, pop_seed: int, seed: int, blocks: int,
            precision: str = "highest",
            versions: Optional[List[Dict]] = None,
            stop_after: Optional[int] = None) -> Dict:
    """A run of ``blocks`` cohort blocks from the cold state (the first
    ``stop_after`` of them, when given).

    Returns the final state and each block's duality gap; ``versions``,
    when given, receives the state after every fold (what a serving tier
    publishing every fold would serve)."""
    ctrs = population.centers(cfg, pop_seed)
    ids_all, dropped = population.schedule(cfg, seed, blocks)
    lam, sigma2 = cfg["regularizer"]["lam"], cfg["regularizer"]["sigma2"]
    budget = reference.budget_tuple(cfg["budget"])
    n_steps = reference.max_steps(cfg["budget"], cfg["n_pad"])
    st, gaps = State(cfg), []
    eye = np.eye(cfg["cohort"])
    for b in range(blocks if stop_after is None else stop_after):
        ids = ids_all[b]
        X, y, mask, sizes = _pack(cfg, pop_seed, ctrs, ids)
        alpha0 = np.zeros_like(y)
        for slot, t in enumerate(ids):
            hit = st.cache.get(int(t))
            if hit is not None:
                alpha0[slot, :hit[0].shape[0]] = hit[0]
        a = st.assign[ids]
        omega = jnp.asarray(st.omega_k[np.ix_(a, a)] + cfg["eta"] * eye,
                            jnp.float32)
        kb, kr = (k[0] for k in reference.key_schedule(
            population.block_seed(seed, b), 1))
        steps = reference.round_budgets(budget, kb, jnp.asarray(mask.sum(1)))
        steps = jnp.minimum(jnp.where(jnp.asarray(dropped[b]), 0, steps),
                            n_steps)
        out = reference.run_block(X, y, mask, alpha0, omega, steps, kr, n_steps,
                                  lam, sigma2, precision)
        gaps.append(out["gap"])
        fold(cfg, st, b, ids, out["W"], out["alpha"], sizes,
             np.asarray(steps) > 0)
        if versions is not None:
            versions.append(st.snapshot())
    return dict(st.snapshot(), gap=np.asarray(gaps, np.float64))


def margins(snap: Dict, ids, X, precision: str = "highest") -> np.ndarray:
    """Decision margins <w_id, x> under a state snapshot: a client's weights
    are its cluster's centroid plus its cached offset, if it has one."""
    ids = np.asarray(ids, np.int64)
    W = snap["centroids"][snap["assign"][ids]].copy()
    pos = np.searchsorted(snap["cache_ids"], ids)
    for b, (p, t) in enumerate(zip(pos, ids)):
        if p < snap["cache_ids"].size and snap["cache_ids"][p] == t:
            W[b] += snap["cache_delta"][p]
    row = lambda w, x: reference._mm(w, x, precision)
    return np.asarray(jax.vmap(row)(jnp.asarray(W, jnp.float32),
                                    jnp.asarray(X, jnp.float32)), np.float64)
