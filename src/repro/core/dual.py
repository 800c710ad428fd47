"""Primal/dual objectives, the w(alpha) map, and the duality gap for (1)/(3).

Data layout (padded, vmap/shard_map friendly):
    X     : (m, n_max, d)   X[t, i] = x_t^i  (row vectors)
    y     : (m, n_max)
    mask  : (m, n_max)      1.0 for real points, 0.0 for padding
    alpha : (m, n_max)      dual variables (0 on padding)
    v     : (m, d)          v_t = X_t^T alpha_t = sum_i alpha_t^i x_t^i

With coupling Abar (m x m SPD) and K = Abar^{-1}:
    R*(X alpha) = (1/4) tr(V^T K V)_{task-space} = (1/4) sum_tt' K_tt' <v_t, v_t'>
    W(alpha)    = (1/2) K V          (rows w_t, shape (m, d))
    D(alpha)    = sum_ti mask * l*(-alpha) + R*(X alpha)         [minimize]
    P(W)        = sum_ti mask * l(x.w_t, y) + tr(W Abar W^T)     [minimize]
    gap(alpha)  = P(W(alpha)) + D(alpha) >= 0, == 0 at optimum.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.losses import Loss
from repro.utils.jax_compat import F32_DOT, fp_barrier

Array = jax.Array


class FederatedData(NamedTuple):
    """Padded per-task data for an m-node federated MTL problem.

    ``xnorm2`` is the per-run precomputed ``||x_t^i||^2`` table the SDCA
    inner loop needs every round -- ``run_mocha`` fills it once per run via
    ``with_xnorm2`` (the data is static, so recomputing it per round was
    pure waste); ``None`` means "not precomputed" and solvers fall back to
    computing it on the fly with the same pinned formula
    (``repro.core.subproblem.row_norms``).
    """

    X: Array      # (m, n_max, d)
    y: Array      # (m, n_max)
    mask: Array   # (m, n_max)
    xnorm2: Optional[Array] = None   # (m, n_max) or None

    @property
    def m(self) -> int:
        return self.X.shape[0]

    @property
    def n_max(self) -> int:
        return self.X.shape[1]

    @property
    def d(self) -> int:
        return self.X.shape[2]

    @property
    def n_t(self) -> Array:
        # axis=-1 so the property is also correct on batch-stacked data
        # (core/sweep.py stacks shuffles along a leading axis)
        return jnp.sum(self.mask, axis=-1)

    @property
    def n_total(self) -> Array:
        return jnp.sum(self.mask)


def with_xnorm2(data: FederatedData) -> FederatedData:
    """Fill the per-run ``xnorm2`` table (idempotent).

    Computed through ``repro.core.subproblem.row_norms`` so the hoisted
    table is bit-identical to what any solver would compute on the fly."""
    if data.xnorm2 is not None:
        return data
    from repro.core.subproblem import row_norms
    return data._replace(xnorm2=row_norms(data.X))


class DualState(NamedTuple):
    """MOCHA iterate: dual variables and the communicated v = X alpha blocks."""

    alpha: Array  # (m, n_max)
    v: Array      # (m, d)


def init_state(data: FederatedData) -> DualState:
    return DualState(
        alpha=jnp.zeros_like(data.y),
        v=jnp.zeros((data.m, data.d), data.X.dtype),
    )


def compute_v(data: FederatedData, alpha: Array) -> Array:
    """v_t = sum_i alpha_t^i x_t^i  -- the only cross-node quantity."""
    return jnp.einsum("tid,ti->td", data.X, alpha * data.mask,
                      precision=F32_DOT)


def primal_weights(K: Array, v: Array) -> Array:
    """W(alpha) = (1/2) K V, rows are per-task weights w_t (m, d)."""
    return 0.5 * jnp.matmul(K, v, precision=F32_DOT)


def _quad(A: Array, V: Array) -> Array:
    """sum_tt' A_tt' <V_t, V_t'> as one matmul, then a pinned mul+reduce.

    Not a three-operand einsum: its contraction path runs on dots whose
    rounding depends on the batch shape, so the vmapped sweep and a single
    run disagreed in the last bits of the objectives."""
    AV = fp_barrier(jnp.matmul(A, V, precision=F32_DOT))
    return jnp.sum(fp_barrier(V * AV))


def r_star(K: Array, v: Array) -> Array:
    """R*(X alpha) = (1/4) sum_tt' K_tt' <v_t, v_t'>."""
    return 0.25 * _quad(K, v)


def dual_objective(data: FederatedData, loss: Loss, K: Array,
                   alpha: Array, v: Array) -> Array:
    conj = loss.conjugate_neg(alpha, data.y) * data.mask
    return jnp.sum(conj) + r_star(K, v)


def primal_objective(data: FederatedData, loss: Loss, abar: Array,
                     W: Array) -> Array:
    # mul+reduce, not a batched matvec: under the sweep's vmap, X is batched
    # over shuffles only and W over (lambda, shuffle), and the dot XLA forms
    # for that rounds differently from a single run's
    z = jnp.sum(data.X * W[:, None, :], axis=-1)
    losses = loss.value(z, data.y) * data.mask
    reg = _quad(abar, W)
    return jnp.sum(losses) + reg


def duality_gap(data: FederatedData, loss: Loss, abar: Array, K: Array,
                alpha: Array, v: Array) -> Array:
    W = primal_weights(K, v)
    return (primal_objective(data, loss, abar, W)
            + dual_objective(data, loss, K, alpha, v))


def per_task_error(data: FederatedData, W: Array,
                   X_test: Array, y_test: Array, mask_test: Array) -> Array:
    """Binary classification error per task (for Table 1/4 style reporting)."""
    z = jnp.einsum("tid,td->ti", X_test, W, precision=F32_DOT)
    wrong = (jnp.sign(z) != jnp.sign(y_test)) & (mask_test > 0)
    cnt = jnp.maximum(jnp.sum(mask_test, axis=1), 1.0)
    return jnp.sum(wrong, axis=1) / cnt
