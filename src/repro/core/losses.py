"""Convex losses and their conjugate duals for MOCHA.

Each loss ``l(z, y)`` is paired with its conjugate ``l*(-a, y)`` evaluated at the
negated dual variable, following the paper's dual (eq. 3):

    D(alpha) = sum_t sum_i l*(-alpha_t^i) + R*(X alpha).

The per-coordinate SDCA update for the data-local quadratic subproblem (eq. 4)

    min_delta  l*(-(a + delta)) + delta * <x, g> + (q/2) * delta^2 ||x||^2

is available in closed form (or scalar Newton for logistic) via
``Loss.sdca_delta``.  ``g = w_t + q * u`` is the effective primal point where
``u = X_t dalpha_t`` is the locally accumulated update.

Dual feasibility conventions (binary classification, y in {-1, +1}):
  * hinge / smoothed hinge / logistic:  a*y in [0, 1]
  * squared:  a unconstrained

All functions are pure jnp and jit/vmap-safe.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from repro.utils.jax_compat import fp_barrier

Array = jax.Array

_EPS = 1e-12


@dataclasses.dataclass(frozen=True)
class Loss:
    """A convex loss with conjugate dual and SDCA coordinate update."""

    name: str
    #: l(z, y) -> scalar loss
    value: Callable[[Array, Array], Array]
    #: l*(-a, y) -> conjugate at the negated dual variable (finite region only)
    conjugate_neg: Callable[[Array, Array], Array]
    #: closed-form / Newton coordinate update, see ``sdca_delta``
    _delta: Callable[[Array, Array, Array, Array, Array], Array]
    #: smoothness constant: value L s.t. l is (1/L)-smooth... stored as mu where
    #: l is (1/mu)-smooth; 0.0 means non-smooth (hinge).
    mu: float
    #: Lipschitz constant of l in z (for Thm 2-style bounds); inf if unbounded.
    lipschitz: float

    def sdca_delta(self, a: Array, y: Array, xg: Array, qxx: Array) -> Array:
        """Optimal coordinate increment ``delta`` for the local subproblem.

        Args:
          a:    current total dual variable alpha_i + accumulated Delta alpha_i
          y:    label
          xg:   <x_i, g> with g = w_t + q * u  (effective primal point)
          qxx:  q * ||x_i||^2  (curvature of the quadratic term)
        """
        return self._delta(a, y, xg, qxx, jnp.asarray(_EPS, a.dtype))


# ---------------------------------------------------------------------------
# hinge: l(z, y) = max(0, 1 - y z);   l*(-a, y) = -a y,  a y in [0, 1]
# ---------------------------------------------------------------------------

def _hinge_value(z, y):
    return jnp.maximum(0.0, 1.0 - y * z)


def _hinge_conj_neg(a, y):
    return -a * y


def _hinge_step(a, y, yxg, qxx, eps):
    """Hinge coordinate update given the product ``yxg = y * <x, g>``.

    Unpinned, so the compiled Pallas kernel (which Mosaic cannot lower
    ``optimization_barrier`` in) shares the formula with ``_hinge_delta``."""
    abar = a * y
    step = (1.0 - yxg) / jnp.maximum(qxx, eps)
    abar_new = jnp.clip(abar + step, 0.0, 1.0)
    return (abar_new - abar) * y


def _hinge_delta(a, y, xg, qxx, eps):
    # barrier: forbid FMA-contracting y*xg into the subtraction, which would
    # break bit-parity with the Pallas hinge kernel (same expression there)
    return _hinge_step(a, y, fp_barrier(y * xg), qxx, eps)


# ---------------------------------------------------------------------------
# smoothed hinge (mu-smoothed):
#   l(z,y) = 0                      if yz >= 1
#            1 - yz - mu/2          if yz <= 1 - mu
#            (1 - yz)^2 / (2 mu)    otherwise
#   l*(-a, y) = -a y + (mu/2) (a y)^2,  a y in [0, 1]       (1/mu)-smooth
# ---------------------------------------------------------------------------
_SMOOTH_MU = 0.5


def _smooth_hinge_value(z, y, mu=_SMOOTH_MU):
    yz = y * z
    lin = 1.0 - yz - mu / 2.0
    quad = jnp.square(jnp.maximum(0.0, 1.0 - yz)) / (2.0 * mu)
    return jnp.where(yz >= 1.0, 0.0, jnp.where(yz <= 1.0 - mu, lin, quad))


def _smooth_hinge_conj_neg(a, y, mu=_SMOOTH_MU):
    ay = a * y
    return -ay + 0.5 * mu * jnp.square(ay)


def _smooth_hinge_delta(a, y, xg, qxx, eps, mu=_SMOOTH_MU):
    abar = a * y
    abar_new = jnp.clip(
        (1.0 - y * xg + qxx * abar) / jnp.maximum(mu + qxx, eps), 0.0, 1.0
    )
    return (abar_new - abar) * y


# ---------------------------------------------------------------------------
# logistic: l(z, y) = log(1 + exp(-y z))
#   l*(-a, y) = ab log(ab) + (1-ab) log(1-ab),  ab = a y in [0, 1]   (4-smooth)
# ---------------------------------------------------------------------------

def _logistic_value(z, y):
    return jnp.logaddexp(0.0, -y * z)


def _xlogx(p):
    return jnp.where(p > 0.0, p * jnp.log(jnp.maximum(p, _EPS)), 0.0)


def _logistic_conj_neg(a, y):
    ab = jnp.clip(a * y, 0.0, 1.0)
    return _xlogx(ab) + _xlogx(1.0 - ab)


def _logistic_delta(a, y, xg, qxx, eps, newton_steps: int = 8):
    """Scalar Newton on phi(ab) = ab log ab + (1-ab)log(1-ab) - ab
                                  + y*xg*ab + (qxx/2)(ab - ab0)^2 ... in ab-space.

    phi'(ab) = log(ab/(1-ab)) + y*xg + qxx*(ab - ab0)   [dividing delta = (ab-ab0)y]
    """
    lo = 1e-6
    ab0 = jnp.clip(a * y, lo, 1.0 - lo)

    def step(ab, _):
        g = jnp.log(ab) - jnp.log1p(-ab) + y * xg + qxx * (ab - ab0)
        h = 1.0 / (ab * (1.0 - ab)) + qxx
        ab_new = jnp.clip(ab - g / h, lo, 1.0 - lo)
        return ab_new, None

    ab, _ = jax.lax.scan(step, ab0, None, length=newton_steps)
    return (ab - ab0) * y


# ---------------------------------------------------------------------------
# squared: l(z, y) = 0.5 (z - y)^2;  l*(-a, y) = 0.5 a^2 - a y   (1-smooth)
# ---------------------------------------------------------------------------

def _squared_value(z, y):
    return 0.5 * jnp.square(z - y)


def _squared_conj_neg(a, y):
    return 0.5 * jnp.square(a) - a * y


def _squared_delta(a, y, xg, qxx, eps):
    return (y - a - xg) / (1.0 + qxx)


HINGE = Loss("hinge", _hinge_value, _hinge_conj_neg, _hinge_delta,
             mu=0.0, lipschitz=1.0)
SMOOTH_HINGE = Loss("smooth_hinge", _smooth_hinge_value, _smooth_hinge_conj_neg,
                    _smooth_hinge_delta, mu=_SMOOTH_MU, lipschitz=1.0)
LOGISTIC = Loss("logistic", _logistic_value, _logistic_conj_neg,
                _logistic_delta, mu=0.25, lipschitz=1.0)
SQUARED = Loss("squared", _squared_value, _squared_conj_neg, _squared_delta,
               mu=1.0, lipschitz=float("inf"))

LOSSES = {l.name: l for l in (HINGE, SMOOTH_HINGE, LOGISTIC, SQUARED)}


def get_loss(name: str) -> Loss:
    if name not in LOSSES:
        raise KeyError(f"unknown loss {name!r}; available: {sorted(LOSSES)}")
    return LOSSES[name]
