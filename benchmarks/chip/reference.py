"""Plain MOCHA (Smith et al., NeurIPS 2017, Algorithm 1), the yardstick.

A straightforward implementation of the cross-silo job the benchmark times:
hinge-loss SDCA on every task's data-local subproblem (eq. 4), one
coordinate at a time, the W = (1/2) K V update, and the probabilistic
regularizer's Omega step (eq. 14, Omega = (W W^T)^(1/2) / tr).  It imports
nothing of the program; it shares with it only the inputs, which the
benchmark makes, and the random law of the job's seed (per-round keys,
budgets and coordinate draws, as ``jax.random`` computes them), without
which no two runs of a randomized solver could be compared.

Every product of two float32 operands goes through ``_mm``.  At
``precision="highest"`` that is an exact float32 product (the precision
the configuration states).  At ``precision="high"`` it is the control:
bfloat16x3, each operand split into a bfloat16 head and a bfloat16 tail
and the three leading partial products summed in float32, which is what
XLA's ``Precision.HIGH`` computes.  It is spelled out so that it runs as
written whatever the compiler would pick for a vector dot.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("highest", "high")


def _bf16(a):
    """``a`` rounded to bfloat16, kept in float32.  ``reduce_precision``
    and not a round trip through ``astype``: XLA may drop a convert pair as
    excess precision, and on a TPU it does, which turns the tail below into
    zero and the control into one bfloat16 pass."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _split(a):
    head = _bf16(a)
    return head, _bf16(a - head)


def _mm(a, b, precision: str):
    if precision == "highest":
        return jnp.matmul(a, b, precision=_HIGHEST)
    ah, al = _split(a)
    bh, bl = _split(b)
    mm = partial(jnp.matmul, precision=_HIGHEST)
    return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))


# -- the job's random law ----------------------------------------------------

def key_schedule(seed: int, rounds: int) -> Tuple[np.ndarray, np.ndarray]:
    """(budget keys, round keys), each (rounds, 2): key, kb, kr = split(key, 3)
    once per round, from ``PRNGKey(seed)``."""
    key = jax.random.PRNGKey(seed)
    kbs, krs = [], []
    for _ in range(rounds):
        key, kb, kr = jax.random.split(key, 3)
        kbs.append(kb)
        krs.append(kr)
    return jnp.stack(kbs), jnp.stack(krs)


@partial(jax.jit, static_argnums=(0,))
def round_budgets(budget: Tuple, kb, n_t):
    """(m,) steps per task: ``passes * n_t``, or U[lo n_min, hi n_min] under
    the systems model, zero for a dropped task."""
    passes, lo_frac, hi_frac, drop_prob = budget
    k_sys, k_drop = jax.random.split(kb)
    full = jnp.round(passes * n_t).astype(jnp.int32)
    base = full
    if lo_frac is not None:
        n_min = jnp.min(n_t)
        lo = lo_frac * n_min
        hi = hi_frac * n_min
        frac = jax.random.uniform(k_sys, n_t.shape)
        base = jnp.minimum(jnp.round(lo + frac * (hi - lo)).astype(jnp.int32),
                           full)
    steps = jnp.maximum(base, 1)
    if drop_prob > 0.0:
        dropped = jax.random.bernoulli(k_drop, drop_prob, n_t.shape)
        steps = jnp.where(dropped, 0, steps)
    return steps


def budget_tuple(budget: Dict) -> Tuple:
    lo = budget.get("systems_lo")
    hi = budget.get("systems_hi", 1.0) if lo is not None else None
    return (float(budget.get("passes", 1.0)), lo, hi,
            float(budget.get("drop_prob", 0.0)))


def max_steps(budget: Dict, n_max: int) -> int:
    return max(1, int(round(float(budget.get("passes", 1.0)) * n_max)))


# -- one round ---------------------------------------------------------------

def _task_sdca(X, y, mask, xn, alpha, w, q, steps, key, n_steps, precision):
    """SDCA on one task's subproblem: ``steps`` coordinate updates at the
    coordinates drawn uniformly from the task's real points."""
    n = X.shape[0]
    n_t = jnp.maximum(jnp.sum(mask), 1.0)
    draws = jax.random.uniform(key, (n_steps,))
    idx = jnp.minimum((draws * n_t).astype(jnp.int32), n - 1)

    def step(s, carry):
        dalpha, u = carry
        i = idx[s]
        x = X[i]
        a = alpha[i] + dalpha[i]
        g = _mm(x, w + q * u, precision)
        ay = a * y[i]
        ay_new = jnp.clip(ay + (1.0 - y[i] * g) / jnp.maximum(q * xn[i],
                                                               1e-12),
                          0.0, 1.0)
        delta = (ay_new - ay) * y[i]
        delta = jnp.where((s < steps) & (mask[i] > 0), delta, 0.0)
        return dalpha.at[i].add(delta), u + delta * x

    zeros = (jnp.zeros(n, X.dtype), jnp.zeros(X.shape[1], X.dtype))
    return jax.lax.fori_loop(0, n_steps, step, zeros)


@partial(jax.jit, static_argnums=(0, 1))
def sdca_round(n_steps: int, precision: str, X, y, mask, xn, alpha, v, K, q,
               steps, kr):
    """One W-round: every task solves its subproblem from W = K V / 2."""
    W = 0.5 * _mm(K, v, precision)
    keys = jax.random.split(kr, X.shape[0])
    dalpha, u = jax.vmap(
        lambda *a: _task_sdca(*a, n_steps, precision))(
            X, y, mask, xn, alpha, W, q, steps, keys)
    return alpha + dalpha, v + u


# -- the regularizer ---------------------------------------------------------

def _eig_fn(s, fn, precision):
    w, q = jnp.linalg.eigh(0.5 * (s + s.T))
    return _mm(q * fn(w), q.T, precision)


@partial(jax.jit, static_argnums=(0, 1, 2))
def coupling(precision: str, lam: float, sigma2: float, omega):
    """(Abar, K, q): Abar = lam (Omega^-1 + I / sigma^2), K = Abar^-1,
    q_t = sigma'_t K_tt / 2 with sigma'_t = sum_t' |K_tt'| / K_tt."""
    m = omega.shape[0]
    inv = _eig_fn(omega, lambda w: 1.0 / jnp.maximum(w, 1e-6), precision)
    abar = lam * (inv + jnp.eye(m) / sigma2)
    K = jnp.linalg.inv(abar)
    diag = jnp.diagonal(K)
    sig = jnp.sum(jnp.abs(K), axis=1) / jnp.maximum(diag, 1e-8)
    return abar, K, sig * diag / 2.0


@partial(jax.jit, static_argnums=(0,))
def omega_step(precision: str, K, v):
    W = 0.5 * _mm(K, v, precision)
    root = _eig_fn(_mm(W, W.T, precision),
                   lambda w: jnp.sqrt(jnp.maximum(w, 1e-10)), precision)
    tr = jnp.trace(root)
    m = W.shape[0]
    return jnp.where(tr > 1e-8, root / jnp.maximum(tr, 1e-8), jnp.eye(m) / m)


@partial(jax.jit, static_argnums=(0,))
def objectives(precision: str, X, y, mask, alpha, v, abar, K):
    """(primal, dual, gap) of the current iterate."""
    W = 0.5 * _mm(K, v, precision)
    dual = (jnp.sum(-alpha * y * mask)
            + 0.25 * jnp.sum(v * _mm(K, v, precision)))
    z = jax.vmap(lambda Xt, wt: _mm(Xt, wt, precision))(X, W)
    primal = (jnp.sum(jnp.maximum(0.0, 1.0 - y * z) * mask)
              + jnp.sum(W * _mm(abar, W, precision)))
    return primal, dual, primal + dual


@partial(jax.jit, static_argnums=(0,))
def heldout_error(precision: str, W, X, y, mask):
    """Mean over tasks of each task's share of misclassified test points."""
    z = jax.vmap(lambda Xt, wt: _mm(Xt, wt, precision))(X, W)
    wrong = (jnp.sign(z) != jnp.sign(y)) & (mask > 0)
    per_task = jnp.sum(wrong, axis=1) / jnp.maximum(jnp.sum(mask, axis=1), 1.0)
    return jnp.mean(per_task)


def run_block(X, y, mask, alpha0, omega, steps, kr, n_steps: int, lam: float,
              sigma2: float, precision: str) -> Dict:
    """One W-round of a cohort block, warm-started from the dual blocks
    ``alpha0`` (v = X^T alpha0), under relationship ``omega``."""
    X, y, mask = (jnp.asarray(a) for a in (X, y, mask))
    alpha0 = jnp.asarray(alpha0)
    v0 = jax.vmap(lambda a, Xt: _mm(a, Xt, precision))(alpha0 * mask, X)
    abar, K, q = coupling(precision, lam, sigma2, omega)
    alpha, v = sdca_round(n_steps, precision, X, y, mask,
                          jnp.sum(X * X, axis=-1), alpha0, v0, K, q, steps,
                          kr)
    gap = objectives(precision, X, y, mask, alpha, v, abar, K)[2]
    return {"W": np.asarray(0.5 * _mm(K, v, precision)),
            "alpha": np.asarray(alpha), "gap": float(gap)}


# -- a whole job -------------------------------------------------------------

def run_job(cfg: Dict, traffic: Dict, train, test, seed: int,
            precision: str = "highest") -> Dict:
    """The cross-silo job: ``traffic['rounds']`` W-rounds from alpha = 0.

    Returns the final W, the duality gap after each record round (every
    ``record_every``-th round and the last) and the held-out error."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    X, y, mask = (jnp.asarray(a) for a in train)
    m, n, _ = X.shape
    rounds, every = traffic["rounds"], cfg["omega_update_every"]
    rec_every = traffic["record_every"]
    lam, sigma2 = cfg["regularizer"]["lam"], cfg["regularizer"]["sigma2"]
    budget = budget_tuple(traffic["budget"])
    n_steps = max_steps(traffic["budget"], n)
    xn = jnp.sum(X * X, axis=-1)
    n_t = jnp.sum(mask, axis=-1)
    kbs, krs = key_schedule(seed, rounds)
    omega = jnp.eye(m) / m
    abar, K, q = coupling(precision, lam, sigma2, omega)
    alpha, v = jnp.zeros((m, n), jnp.float32), jnp.zeros((m, X.shape[2]),
                                                          jnp.float32)
    gaps = []
    for h in range(rounds):
        steps = jnp.minimum(round_budgets(budget, kbs[h], n_t), n_steps)
        alpha, v = sdca_round(n_steps, precision, X, y, mask, xn, alpha, v,
                              K, q, steps, krs[h])
        if every and (h + 1) % every == 0:
            omega = omega_step(precision, K, v)
            abar, K, q = coupling(precision, lam, sigma2, omega)
        if h % rec_every == 0 or h == rounds - 1:
            gaps.append(objectives(precision, X, y, mask, alpha, v, abar,
                                   K)[2])
    W = 0.5 * _mm(K, v, precision)
    err = heldout_error(precision, W, *(jnp.asarray(a) for a in test))
    return {"W": np.asarray(W, np.float64),
            "gap": np.asarray(jnp.stack(gaps), np.float64),
            "error": float(err)}
