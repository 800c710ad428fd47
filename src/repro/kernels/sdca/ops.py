"""jit'd wrapper: drop-in accelerated local solver for the MOCHA round.

Generates the same uniform coordinate draws as
``repro.core.subproblem.local_sdca`` so the kernel can replace the jnp path
inside ``federated_round`` for hinge-loss problems.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.sdca.sdca import sdca_local_solve


def draw_coordinates(keys, n_t, n, max_steps):
    """keys: (m, 2) PRNG keys; n_t: (m,) sizes. Returns (m, max_steps)."""
    def one(key, nt):
        u = jax.random.uniform(key, (max_steps,))
        return jnp.minimum((u * jnp.maximum(nt, 1.0)).astype(jnp.int32),
                           n - 1)

    return jax.vmap(one)(keys, n_t)


def kernel_local_sdca(data, alpha, W, q_t, budgets, keys, max_steps,
                      interpret=None, gram=None):
    """Mirror of repro.core.subproblem.batched_local_sdca (hinge only).

    ``gram`` forces a residual mode; ``None`` keeps the shared
    ``_solver_plan`` rule."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n_t = jnp.sum(data.mask, axis=1)
    idx = draw_coordinates(keys, n_t, data.n_max, max_steps)
    return sdca_local_solve(data.X, data.y, data.mask, alpha, W, q_t,
                            budgets, idx, max_steps, interpret=interpret,
                            gram=gram, xnorm2=data.xnorm2)
