"""MOCHA local-solver Pallas TPU kernel: the per-node SDCA coordinate loop.

This is the per-node compute hot spot of Algorithm 1 (thousands of
sequential coordinate updates over the node's local data block).  The grid
iterates tasks; each instance pins its node's data block (n, d) plus the
dual/work vectors in VMEM and runs the budgeted coordinate loop chunk by
chunk (DESIGN.md §3).

Arithmetic version 2 (DESIGN.md §2): the kernel mirrors
``repro.core.subproblem`` chunk for chunk -- fused residual carry
``r = w + q*u`` with the statically chosen residual mode:

  * carry (d > _GRAM_MAX_D): per step one length-d reduction ``sum(x*r)``
    and one axpy into ``r``;
  * gram (d <= _GRAM_MAX_D): per chunk ``G_c = X_c X_c^T`` and
    ``p_c = X_c r``, then O(C) sequential work per step.

The mode/chunk choice and the chunk layout come from
``repro.core.subproblem`` (``_solver_plan``, ``chunk_idx_stream``), so the
kernel cannot pick a different plan than the jnp solvers.  The arithmetic
comes in two op-sets: ``_Pinned`` (interpret mode, the CPU) calls the
solver's own pinned primitives and is bit-identical to it
(tests/test_kernels.py, tests/test_runtime.py); ``_Mosaic`` (compiled, the
TPU) writes the same formulas unpinned, because Mosaic has no lowering for
``optimization_barrier``, and forms the chunk Gram on the MXU.  The
compiled kernel is held to a tolerance against ``LocalEngine`` on the chip
(chip_smoke.py).

Layout for the TPU compiler (Mosaic), DESIGN.md §3:

  * the drawn coordinate stream and the per-task ``q_t``/budget live in
    SMEM; every other value is a vector, and per-coordinate scalars are
    (1, 1) vectors, because Mosaic cannot store a scalar into VMEM;
  * a coordinate's row of X, its ``[y, mask, alpha, ||x||^2]`` row and its
    ``dalpha`` entry are read with dynamic one-row slices of (n, .) VMEM
    blocks; the chunk's rows are gathered into a (C, d) VMEM scratch;
  * per-task vectors are (m, 1, .) / (m, n, .) arrays whose blocks span
    their last two dimensions (the (8, 128) block rule).

VMEM working set per task: (n*d + C*d + 2*n*128 + 2*d) * 4B, double
buffered; for the paper's largest federation (Vehicle Sensor: n_t <= 1933,
d = 100 -> 128 lanes) about 4 MiB.  Hinge loss only (the paper's SVM
experiments); the generic multi-loss path stays in
repro/core/subproblem.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.losses import _EPS, HINGE, _hinge_step
from repro.core.subproblem import (_carry_g, _carry_step_r, _chunk_colsum,
                                   _chunk_gram, _chunk_rowdots, _gram_chunk_r,
                                   _gram_g, _solver_plan, chunk_idx_stream,
                                   row_norms)

#: lane order of the packed per-point row
_Y, _MASK, _ALPHA, _XNORM = range(4)


class _Pinned:
    """Interpret mode (the CPU): the jnp solver's own pinned primitives on
    1-D chunk vectors, so the kernel is bit-identical to ``LocalEngine``.
    ``r`` is (1, d), per-coordinate values are (1, 1)."""

    @staticmethod
    def gram(Xc, r):
        return _chunk_gram(Xc), _chunk_rowdots(Xc, r[0])

    @staticmethod
    def deltas(C):
        return jnp.zeros((C,), jnp.float32)

    @staticmethod
    def g(Xc, r, s, q, G, p, deltas):
        if G is None:
            return _carry_g(Xc[s], r[0]).reshape(1, 1)
        return _gram_g(p[s], q, G[s], deltas).reshape(1, 1)

    @staticmethod
    def delta(a, y, g, qxx):
        return HINGE.sdca_delta(a, y, g, qxx)

    @staticmethod
    def put(deltas, s, delta):
        return deltas.at[s].set(delta[0, 0])

    @staticmethod
    def carry_step(r, q, delta, Xc, s):
        return _carry_step_r(r, q, delta, Xc[s])

    @staticmethod
    def colsum(Xc, deltas):
        return _chunk_colsum(Xc, deltas)[None, :]

    @staticmethod
    def chunk_r(r, q, colsum):
        return _gram_chunk_r(r, q, colsum)


def _mul_sum(a, b, axis):
    # unpinned on purpose: Mosaic cannot lower the solver's pinned twins
    return jnp.sum(a * b, axis=axis, keepdims=True)  # reprolint: ok P202


class _Mosaic:
    """Compiled (the TPU): the same formulas on 2-D vectors, unpinned
    (Mosaic lowers neither ``optimization_barrier`` nor a scatter, nor
    stores a scalar to VMEM); the chunk Gram is one MXU matmul."""

    @staticmethod
    def gram(Xc, r):
        G = jax.lax.dot_general(Xc, Xc, (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
        return G, _mul_sum(Xc, r, 1)                     # (C, C), (C, 1)

    @staticmethod
    def deltas(C):
        return jnp.zeros((C, 1), jnp.float32)

    @staticmethod
    def g(Xc, r, s, q, G, p, deltas):
        if G is None:
            return _mul_sum(Xc[s:s + 1, :], r, 1)
        # G is symmetric: column s is the solver's row G[s]
        return p[s:s + 1, :] + q * _mul_sum(G[:, s:s + 1], deltas, 0)

    @staticmethod
    def delta(a, y, g, qxx):
        return _hinge_step(a, y, y * g, qxx, _EPS)

    @staticmethod
    def put(deltas, s, delta):
        row = jax.lax.broadcasted_iota(jnp.int32, deltas.shape, 0)
        return jnp.where(row == s, delta, deltas)

    @staticmethod
    def carry_step(r, q, delta, Xc, s):
        return r + (q * delta) * Xc[s:s + 1, :]

    @staticmethod
    def colsum(Xc, deltas):
        return _mul_sum(Xc, deltas, 0)

    @staticmethod
    def chunk_r(r, q, colsum):
        return r + q * colsum


def _sdca_kernel(qs_ref, bud_ref, idx_ref, x_ref, pt_ref, w_ref,
                 dalpha_ref, u_ref, xc_ref, *,
                 n_chunks: int, C: int, gram: bool, interpret: bool):
    """One task. Refs:
    qs/bud: (m,) SMEM q_t / clamped budgets (scalar prefetch);
    idx: (1, n_chunks * C) SMEM coordinate stream;
    x: (n, d); pt: (n, 4) per-point [y, mask, alpha, xnorm2]; w: (1, d);
    outputs dalpha: (n, 1), u: (1, d); scratch xc: (C, d)."""
    ops = _Pinned if interpret else _Mosaic
    t = pl.program_id(0)
    q = qs_ref[t]
    budget = bud_ref[t]

    dalpha_ref[...] = jnp.zeros(dalpha_ref.shape, jnp.float32)
    u_ref[...] = jnp.zeros(u_ref.shape, jnp.float32)

    def chunk_body(c, r):
        ic = [idx_ref[0, c * C + s] for s in range(C)]
        for s in range(C):
            xc_ref[pl.ds(s, 1), :] = x_ref[pl.ds(ic[s], 1), :]
        Xc = xc_ref[...]                                  # (C, d)
        G, p = ops.gram(Xc, r) if gram else (None, None)
        deltas = ops.deltas(C)
        for s in range(C):
            i = ic[s]
            pt = pt_ref[pl.ds(i, 1), :]                   # (1, 4)
            a = pt[:, _ALPHA:_ALPHA + 1] + dalpha_ref[pl.ds(i, 1), :]
            g = ops.g(Xc, r, s, q, G, p, deltas)
            delta = ops.delta(a, pt[:, _Y:_Y + 1], g,
                              q * pt[:, _XNORM:_XNORM + 1])
            live = (pt[:, _MASK:_MASK + 1] > 0.0) & (c * C + s < budget)
            delta = delta * live.astype(jnp.float32)      # (1, 1)
            dalpha_ref[pl.ds(i, 1), :] = dalpha_ref[pl.ds(i, 1), :] + delta
            deltas = ops.put(deltas, s, delta)
            if not gram:
                r = ops.carry_step(r, q, delta, Xc, s)
        colsum = ops.colsum(Xc, deltas)                   # (1, d)
        u_ref[...] = u_ref[...] + colsum
        if gram:
            r = ops.chunk_r(r, q, colsum)
        return r

    jax.lax.fori_loop(0, n_chunks, chunk_body, w_ref[...])


@functools.partial(jax.jit,
                   static_argnames=("max_steps", "interpret", "gram"))
def sdca_local_solve(X, y, mask, alpha, W, q_t, budgets, idx,
                     max_steps: int, interpret: bool = True,
                     gram=None, xnorm2=None):
    """Batched hinge-SDCA local solve.

    X: (m, n, d) f32; y/mask/alpha: (m, n); W: (m, d); q_t: (m,);
    budgets: (m,) int32; idx: (m, max_steps) int32 coordinate sequence.
    ``gram`` overrides the static residual-mode rule (None = shared
    ``_solver_plan`` default); ``xnorm2`` accepts the per-run hoisted row
    norms.  Returns (dalpha (m, n), u (m, d)).
    """
    m, n, d = X.shape
    xnorm = row_norms(X) if xnorm2 is None else xnorm2
    gram, C = _solver_plan(d, max_steps, gram)
    # padded steps have c*C + s >= max_steps >= clamped budget: never live
    budgets = jnp.minimum(budgets, max_steps).astype(jnp.int32)
    idx_c = chunk_idx_stream(idx, max_steps, C)
    n_chunks = idx_c.shape[1]
    pts = jnp.stack([y, mask, alpha, xnorm], axis=-1)       # (m, n, 4)

    kernel = functools.partial(_sdca_kernel, n_chunks=n_chunks, C=C,
                               gram=gram, interpret=interpret)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m,),
        in_specs=[
            pl.BlockSpec((None, 1, n_chunks * C), lambda t, *_: (t, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((None, n, d), lambda t, *_: (t, 0, 0)),
            pl.BlockSpec((None, n, 4), lambda t, *_: (t, 0, 0)),
            pl.BlockSpec((None, 1, d), lambda t, *_: (t, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, n, 1), lambda t, *_: (t, 0, 0)),
            pl.BlockSpec((None, 1, d), lambda t, *_: (t, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((C, d), jnp.float32)],
    )
    dalpha, u = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((m, n, 1), jnp.float32),
            jax.ShapeDtypeStruct((m, 1, d), jnp.float32),
        ],
        interpret=interpret,
    )(q_t.astype(jnp.float32), budgets, idx_c.reshape(m, 1, n_chunks * C), X,
      pts, W.reshape(m, 1, d))
    return dalpha[..., 0], u[:, 0]
