"""MTL regularizers R(W, Omega) and their coupling matrices (paper App. B).

Every regularizer in the paper reduces, for the W-step with Omega fixed, to the
quadratic form

    R(W) = tr(W Abar W^T) = vec(W)^T (Abar kron I_d) vec(W),

for an SPD m x m coupling matrix ``Abar`` (paper's M^{-1} = Abar kron I_d up to
the constant conventions in Remark 1).  All of MOCHA's dual algebra then lives
in m x m space:

    K   := Abar^{-1}
    R*(X alpha) = (1/4) sum_{t,t'} K_{t t'} <v_t, v_{t'}>,   v_t = X_t alpha_t
    W(alpha)    = (1/2) V K            (columns w_t)
    M_t         = (1/2) K_tt I_d       -> subproblem curvature q_t = sigma' K_tt / 2
    sigma'      = gamma max_t sum_{t'} |K_{t t'}| / K_{t t}          (Lemma 9)
    sigma'_t    = gamma sum_{t'} |K_{t t'}| / K_{t t}                (Remark 5)

Implemented formulations (paper eq. numbers):
  * ``MeanRegularized``  -- eq. (2)/(11), Omega = (I - 11^T/m)^2 fixed.
  * ``Clustered``        -- eq. (12), R = lam tr(W (eta I + Omega)^{-1} W^T),
                            Omega in {0 <= Omega <= I, tr = k}; water-filling update.
  * ``Probabilistic``    -- eq. (14), R = lam (sigma^-2 ||W||^2 + tr(W Omega^{-1} W^T)),
                            tr(Omega) = 1; Omega <- (W^T W)^(1/2) / tr(...).
  * ``Graphical``        -- eq. (15) (without the W l1 term), sparse precision Omega
                            via proximal-gradient (ISTA) with PSD projection.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.utils.jax_compat import F32_DOT

Array = jax.Array

_JITTER = 1e-8


def _mm(a: Array, b: Array) -> Array:
    return jnp.matmul(a, b, precision=F32_DOT)


def _sym(x: Array) -> Array:
    return 0.5 * (x + x.T)


def _psd_sqrt(s: Array, floor: float = 1e-10) -> Array:
    """Matrix square root of a PSD matrix via eigh."""
    w, q = jnp.linalg.eigh(_sym(s))
    w = jnp.maximum(w, floor)
    return _mm(q * jnp.sqrt(w), q.T)


def spd_inverse(a: Array, floor: float = 1e-10) -> Array:
    """Inverse of an SPD matrix with eigenvalue flooring (robust K computation)."""
    w, q = jnp.linalg.eigh(_sym(a))
    w = jnp.maximum(w, floor)
    return _mm(q / w, q.T)


class Regularizer:
    """Base class. Subclasses provide Abar(omega), penalty(W, omega), update_omega."""

    name: str = "base"

    def init_omega(self, m: int) -> Array:
        raise NotImplementedError

    def coupling(self, omega: Array) -> Array:
        """Return SPD Abar (m x m) such that R(W) = tr(W Abar W^T)."""
        raise NotImplementedError

    def penalty(self, W: Array, omega: Array) -> Array:
        """R(W, Omega) for the primal objective. W is (m, d) row-per-task."""
        abar = self.coupling(omega)
        return jnp.einsum("td,st,sd->", W, abar, W, precision=F32_DOT)

    def update_omega(self, W: Array, omega: Array) -> Array:
        """Central Omega-step given W (m, d). Default: fixed omega."""
        return omega

    # convenience ---------------------------------------------------------
    def K(self, omega: Array) -> Array:
        return spd_inverse(self.coupling(omega))


@dataclasses.dataclass(frozen=True)
class MeanRegularized(Regularizer):
    """Eq. (2)/(11): all tasks shrink toward their mean. Omega fixed."""

    lambda1: float = 1.0
    lambda2: float = 1.0
    name: str = "mean"

    def init_omega(self, m: int) -> Array:
        eye = jnp.eye(m)
        c = eye - jnp.full((m, m), 1.0 / m)
        return _mm(c, c)

    def coupling(self, omega: Array) -> Array:
        m = omega.shape[0]
        return self.lambda1 * omega + self.lambda2 * jnp.eye(m)


@dataclasses.dataclass(frozen=True)
class Clustered(Regularizer):
    """Eq. (12): R = lam tr(W (eta I + Omega)^{-1} W^T), Omega in Q(k)."""

    lam: float = 1.0
    eta: float = 0.5
    k: int = 2
    name: str = "clustered"

    def init_omega(self, m: int) -> Array:
        return jnp.eye(m) * (self.k / m)

    def coupling(self, omega: Array) -> Array:
        m = omega.shape[0]
        return self.lam * spd_inverse(self.eta * jnp.eye(m) + omega)

    def update_omega(self, W: Array, omega: Array) -> Array:
        """min_{0<=w_i<=1, sum=k} sum_i s_i/(eta + w_i) with s = eig(W W^T rows).

        Optimal Omega shares eigenvectors with W^T W (here S = W W^T in task
        space since W is (m, d)); eigenvalue water-filling: w_i = clip(
        sqrt(s_i)/nu - eta, 0, 1), nu by bisection on sum w_i(nu) = k.
        """
        s_mat = _mm(W, W.T)
        svals, q = jnp.linalg.eigh(_sym(s_mat))
        svals = jnp.maximum(svals, 0.0)
        root = jnp.sqrt(svals + _JITTER)

        def omega_of(nu):
            return jnp.clip(root / nu - self.eta, 0.0, 1.0)

        # bisection over nu > 0: sum omega_of(nu) is decreasing in nu
        lo = jnp.full((), 1e-8)
        hi = jnp.full((), 1.0)

        def grow(carry):
            lo, hi = carry
            return lo, hi * 2.0

        def grow_cond(carry):
            _, hi = carry
            return jnp.sum(omega_of(hi)) > self.k

        lo, hi = jax.lax.while_loop(grow_cond, grow, (lo, hi))

        def bisect(_, carry):
            lo, hi = carry
            mid = 0.5 * (lo + hi)
            too_big = jnp.sum(omega_of(mid)) > self.k
            return jnp.where(too_big, mid, lo), jnp.where(too_big, hi, mid)

        lo, hi = jax.lax.fori_loop(0, 64, bisect, (lo, hi))
        w = omega_of(0.5 * (lo + hi))
        # cold start (W = 0, e.g. the first refresh from the zero iterate):
        # the spectrum is degenerate and the bisection has no signal, so the
        # result would violate tr(Omega) = k. Keep the uninformative prior,
        # exactly as Probabilistic guards its trace normalization.
        m = W.shape[0]
        return jnp.where(jnp.sum(svals) > 1e-10,
                         _mm(q * w, q.T),
                         jnp.eye(m) * (self.k / m))


@dataclasses.dataclass(frozen=True)
class Probabilistic(Regularizer):
    """Eq. (14): R = lam (sigma^-2 ||W||_F^2 + tr(W Omega^{-1} W^T)), tr(Omega)=1."""

    lam: float = 1.0
    sigma2: float = 1.0
    name: str = "probabilistic"

    def init_omega(self, m: int) -> Array:
        return jnp.eye(m) / m

    def coupling(self, omega: Array) -> Array:
        m = omega.shape[0]
        return self.lam * (spd_inverse(omega, floor=1e-6) + jnp.eye(m) / self.sigma2)

    def update_omega(self, W: Array, omega: Array) -> Array:
        root = _psd_sqrt(_mm(W, W.T))
        tr = jnp.trace(root)
        m = W.shape[0]
        # guard the cold-start W = 0 case: keep the uninformative prior
        return jnp.where(tr > 1e-8, root / jnp.maximum(tr, 1e-8), jnp.eye(m) / m)


@dataclasses.dataclass(frozen=True)
class Graphical(Regularizer):
    """Eq. (15) precision-matrix prior (W l1 term omitted to stay in form (1)):

        R = lam (sigma^-2 ||W||^2 + tr(W Omega W^T) - d log|Omega|) + lam2 ||Omega||_1

    Omega-step: ISTA on f(Omega) = tr(S Omega) - d log|Omega| + lam2||Omega||_1,
    S = W^T W in task space, with eigenvalue clipping to stay SPD.
    """

    lam: float = 1.0
    sigma2: float = 1.0
    lam2: float = 0.01
    d_scale: float = 1.0  # stands in for d in the -d log|Omega| prior term
    ista_steps: int = 25
    ista_lr: float = 0.1
    name: str = "graphical"

    def init_omega(self, m: int) -> Array:
        return jnp.eye(m)

    def coupling(self, omega: Array) -> Array:
        m = omega.shape[0]
        return self.lam * (omega + jnp.eye(m) / self.sigma2)

    def penalty(self, W: Array, omega: Array) -> Array:
        base = super().penalty(W, omega)
        logdet = jnp.linalg.slogdet(omega)[1]
        return (base - self.lam * self.d_scale * logdet
                + self.lam2 * jnp.sum(jnp.abs(omega)))

    def update_omega(self, W: Array, omega: Array) -> Array:
        s_mat = self.lam * _mm(W, W.T)

        def step(om, _):
            grad = s_mat - self.lam * self.d_scale * spd_inverse(om, floor=1e-6)
            om = om - self.ista_lr * grad
            # soft threshold off-diagonal (standard graphical-lasso prox)
            off = jnp.sign(om) * jnp.maximum(jnp.abs(om) - self.ista_lr * self.lam2, 0.0)
            om = jnp.where(jnp.eye(om.shape[0], dtype=bool), om, off)
            # PSD projection with floor
            w, q = jnp.linalg.eigh(_sym(om))
            om = _mm(q * jnp.maximum(w, 1e-4), q.T)
            return om, None

        omega, _ = jax.lax.scan(step, omega, None, length=self.ista_steps)
        return omega


REGULARIZERS = {
    "mean": MeanRegularized,
    "clustered": Clustered,
    "probabilistic": Probabilistic,
    "graphical": Graphical,
}


def sigma_prime(K: Array, gamma: float = 1.0, per_task: bool = False) -> Array:
    """Lemma 9 / Remark 5 safe subproblem parameter from K = Abar^{-1}.

    sigma'_t = gamma * sum_{t'} |K_{t t'}| / K_{t t}; the scalar version takes
    the max over tasks.
    """
    diag = jnp.diagonal(K)
    row = jnp.sum(jnp.abs(K), axis=1) / jnp.maximum(diag, _JITTER)
    per = gamma * row
    return per if per_task else jnp.max(per)
