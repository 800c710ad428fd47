"""Data-local quadratic subproblem (eq. 4) and its SDCA local solver.

The t-th node at round h minimizes, over its own dual block Delta alpha_t:

    G_t(Delta) = sum_i l*(-(alpha_i + Delta_i))
               + <w_t(alpha), X_t^T Delta>
               + (q_t / 2) ||X_t^T Delta||^2            q_t := sigma'_t K_tt / 2
               + c(alpha)                                (constant, kept for
                                                          theta measurement)

Node heterogeneity is expressed as a per-node *step budget* ``H_t`` (number of
coordinate updates performed this round).  On SIMD hardware we run ``max_steps``
iterations everywhere and mask steps past ``H_t`` -- numerically identical to a
node stopping early, and ``H_t = 0`` is exactly the paper's dropped node
(theta_t^h = 1).  The *simulated* wall-clock model only charges unmasked steps.

Padding convention: real data points are packed to the left of the n_max axis
(mask[t, :n_t] == 1).  Random coordinate draws are made in [0, n_t).

Arithmetic version 2 (DESIGN.md section 2): the coordinate loop runs in
chunks of ``C`` drawn coordinates with a **fused residual carry**
``r = w + q * u`` and one of two statically chosen residual modes:

  * **carry** (``d > _GRAM_MAX_D``): each step computes one length-d
    reduction ``sum(x * r)`` and one pinned axpy ``r += (q*delta) * x`` --
    one O(d) reduction per step instead of the two the v1 loop needed;
  * **gram**  (``d <= _GRAM_MAX_D``): ``G_c = X_c X_c^T`` and
    ``p_c = X_c r`` are precomputed per chunk as (batched) GEMMs and the
    sequential step work drops to O(C):
    ``g = p_c[s] + fp_barrier(q * sum(G_c[s] * deltas))``; ``r`` is
    reconstituted once per chunk from the chunk's delta column sum.

Both modes share the chunk machinery: the drawn stream is padded to a chunk
multiple (padded steps land past every budget, so they are provably dead),
``u`` accumulates one column sum per chunk, and the inner C steps are
unrolled so per-step indices into the chunk-local arrays are static.  The
modes are exactly SDCA -- the Gram correction reconstructs
``x_s . (r + q * sum_{j<s} delta_j x_j)`` term-for-term -- so they differ
from each other and from the v1 loop only in floating-point association.
The mode/chunk choice is a pure function of the *static* problem shape
(``_solver_plan``), so every engine of a run agrees on it; all engines are
bit-identical under it (tests/test_runtime.py).
"""
from __future__ import annotations

import os
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.losses import Loss
from repro.utils.jax_compat import F32_DOT, fp_barrier

Array = jax.Array


def subproblem_value(loss: Loss, X_t: Array, y_t: Array, mask_t: Array,
                     alpha_t: Array, dalpha_t: Array, w_t: Array,
                     q_t: Array) -> Array:
    """G_t(Delta; v, alpha) minus the constant c(alpha)."""
    conj = loss.conjugate_neg(alpha_t + dalpha_t, y_t) * mask_t
    u = jnp.matmul(X_t.T, dalpha_t * mask_t, precision=F32_DOT)
    return (jnp.sum(conj) + jnp.dot(w_t, u, precision=F32_DOT)
            + 0.5 * q_t * jnp.dot(u, u, precision=F32_DOT))


#: point count at and above which the compact chunk accumulator is used: the
#: dense variant reads AND writes one element of the carried (n,) dalpha
#: buffer per step, which XLA materializes as an O(n) copy per step; the
#: compact variant touches the (n,) buffer once per chunk instead
_CHUNK_THRESHOLD = 128
#: chunk length (= Gram window) per residual mode, CPU-measured in
#: BENCH_sdca.  The gram mode pays C*d GEMM FLOPs per step, so its window
#: stays tight; the carry mode only uses the chunk for the dalpha
#: accumulator and the u column sums, where a wide window amortizes chunk
#: overhead at large d but loses to it at mid d.
_GRAM_CHUNK = 32
_CARRY_CHUNK_WIDE = 64     # d >= _CARRY_WIDE_D
_CARRY_CHUNK_NARROW = 16
_CARRY_WIDE_D = 512
#: static feature-count crossover for the default residual mode: the Gram
#: path trades the per-step O(d) reduction for C*d GEMM FLOPs per step,
#: which pays off when d is small relative to the sequential-step cost
#: (and on MXU-class hardware generally; measured on CPU in BENCH_sdca).
#: This is the CPU-measured default; ``REPRO_GRAM_MAX_D`` (env var) or
#: ``MochaConfig.gram_max_d`` override it for TPU re-tuning.
_GRAM_MAX_D = 128


def active_gram_max_d() -> int:
    """The residual-mode crossover in effect: ``REPRO_GRAM_MAX_D`` when set,
    else the CPU-measured module default.

    Read per call so benchmarks/tests can override it, but the value feeds
    STATIC solver plans inside jitted programs: set the env var before the
    first solve of a shape -- changing it mid-process will not retrace
    already-compiled programs.  ``BENCH_sdca.json`` rows record the active
    value so re-tuned runs are distinguishable."""
    return int(os.environ.get("REPRO_GRAM_MAX_D", _GRAM_MAX_D))


def resolve_gram(d: int, gram_max_d: Optional[int]) -> Optional[bool]:
    """Turn a per-run crossover override into the existing ``gram`` knob.

    ``None`` (no override) keeps the shared ``_solver_plan`` default;
    otherwise the returned bool is threaded through the engines exactly like
    a forced mode.  NOTE: forcing carry below the default crossover leaves
    the cross-engine bit-parity contract (see ``_carry_g``)."""
    return None if gram_max_d is None else d <= int(gram_max_d)


def _solver_plan(d: int, max_steps: int,
                 gram: Optional[bool] = None) -> Tuple[bool, int]:
    """Static (gram?, chunk) choice shared by every engine.

    A pure function of the static problem shape so the jnp solvers, the
    Pallas kernel, and the sharded runtime all agree without plumbing a
    config knob through the engine contract.  ``gram`` overrides the default
    rule (benchmarks / tests exercise both modes at every shape;
    ``MochaConfig.gram_max_d`` resolves to it via ``resolve_gram``).
    """
    if gram is None:
        gram = d <= active_gram_max_d()
    if gram:
        C = _GRAM_CHUNK
    else:
        C = _CARRY_CHUNK_WIDE if d >= _CARRY_WIDE_D else _CARRY_CHUNK_NARROW
    return gram, max(1, min(C, max_steps))


def n_chunks(max_steps: int, C: int) -> int:
    """Chunks of ``C`` that a stream of ``max_steps`` draws is laid out in
    (``chunk_idx_stream``): each task runs ``n_chunks * C`` lockstep trips
    a round, those past its budget masked."""
    return -(-max_steps // C)


class ChunkPlan(NamedTuple):
    """Chunk layout of a drawn coordinate stream (shared across variants).

    ``idx_c``:    (n_chunks, C) drawn coordinates, zero-padded past
                  ``max_steps`` (padded steps sit past every clamped budget,
                  so they are never live).
    ``firstpos``: (n_chunks, C) position of the first occurrence of each
                  coordinate within its chunk -- repeated draws accumulate
                  into one compact slot so later steps see earlier deltas.
    ``wb``:       (n_chunks, C) write-back scatter target: the coordinate at
                  first occurrences, ``n`` (out of bounds -> dropped)
                  elsewhere.
    """

    idx_c: Array
    firstpos: Array
    wb: Array


def chunk_idx_stream(idx: Array, max_steps: int, C: int) -> Array:
    """Zero-pad the drawn stream to a chunk multiple and reshape to chunks.

    THE shared layout rule: the jnp solvers (via ``_chunk_layout``) and the
    Pallas wrapper both derive their (.., n_chunks, C) view here, so the
    padded-tail-is-dead invariant (pad coordinate 0 at positions
    >= max_steps >= clamped budget) cannot drift between them.  Accepts a
    (max_steps,) stream or a batched (m, max_steps) stack."""
    chunks = n_chunks(max_steps, C)
    pad = chunks * C - max_steps
    widths = [(0, 0)] * (idx.ndim - 1) + [(0, pad)]
    return jnp.pad(idx, widths).reshape(idx.shape[:-1] + (chunks, C))


def _chunk_layout(idx: Array, n: int, max_steps: int, C: int) -> ChunkPlan:
    idx_c = chunk_idx_stream(idx, max_steps, C)
    eq = idx_c[:, :, None] == idx_c[:, None, :]
    firstpos = jnp.argmax(eq, axis=2).astype(jnp.int32)
    is_first = firstpos == jnp.arange(C, dtype=jnp.int32)[None, :]
    wb = jnp.where(is_first, idx_c, n)
    return ChunkPlan(idx_c=idx_c, firstpos=firstpos, wb=wb)


# ---------------------------------------------------------------------------
# pinned-association chunk primitives (DESIGN.md section 2): ONE jnp source
# of truth for every product-into-add of the inner loop.  The Pallas kernel
# imports these, so kernel and reference cannot drift.
# ---------------------------------------------------------------------------

def _pinned_gram(Xc: Array) -> Array:
    return fp_barrier(jnp.sum(fp_barrier(Xc[:, None, :] * Xc[None, :, :]),
                              axis=-1))


def _chunk_gram(Xc: Array) -> Array:
    """G_c = X_c X_c^T: (C, d) x (C, d) -> (C, C).

    On the CPU a pinned mul+reduce, not ``dot_general``: XLA's CPU dot
    picks its kernel by the batch shape, and a vmapped dot whose batch is 1
    (a one-task federation, a 1x1 sweep grid) rounds differently from both
    the unbatched and the wider batched dot.  The products are pinned
    before the reduce (as in ``row_norms``), so every batch shape sums the
    same rounded terms in the same order.  Elsewhere one float32 matmul
    (the MXU on a TPU), where engines agree to a tolerance, not bits
    (DESIGN.md section 2)."""
    return jax.lax.platform_dependent(
        Xc, cpu=_pinned_gram,
        default=lambda x: jnp.matmul(x, x.T, precision=F32_DOT))


def _chunk_rowdots(Xc: Array, r: Array) -> Array:
    """p_c[s] = sum(X_c[s] * r): per-row mul+reduce, the bit-stable lowering
    the per-step ``sum(x * w)`` of the v1 loop relied on; fp_barrier'd so
    the vector is computed once, not refused per consumer."""
    return fp_barrier(jnp.sum(Xc * r[None, :], axis=1))


def _chunk_colsum(Xc: Array, deltas: Array) -> Array:
    """Chunk update column sum ``sum_s deltas[s] * X_c[s]`` (length d).

    This single reduction replaces C per-step axpys: it is the chunk's
    contribution to ``u`` and (scaled by q, behind its own barrier) to
    ``r``; fp_barrier pins the reduce's association across contexts."""
    return fp_barrier(jnp.sum(fp_barrier(Xc * deltas[:, None]), axis=0))


def _carry_g(x_s: Array, r: Array) -> Array:
    """Carry mode: g = <x_s, w + q u> as ONE reduction over the residual.

    NOTE: a scalar-output length-d mul+reduce is only bit-stable across
    execution contexts for d comfortably above a SIMD register's worth of
    lanes (divergent partial-sum trees observed for d <= 32) -- which is
    why ``_solver_plan`` never selects carry mode below ``_GRAM_MAX_D``:
    forcing ``gram=False`` at small d is outside the parity contract."""
    return jnp.sum(x_s * r)


def _gram_g(p_s: Array, q_t: Array, G_s: Array, deltas: Array) -> Array:
    """Gram mode: g = p_c[s] + q * sum(G_c[s] * deltas).

    ``deltas`` holds this chunk's committed deltas (zeros at step s and
    later), so the sum reconstructs x_s . (q * sum_{j<s} delta_j x_j)
    exactly; the inner barrier pins the reduce's input (as in ``_carry_g``)
    and the outer one pins the product into the add the same way the v1
    loop pinned q * sum(x * u)."""
    return p_s + fp_barrier(q_t * jnp.sum(fp_barrier(G_s * deltas)))


def _carry_step_r(r: Array, q_t: Array, delta: Array, x_s: Array) -> Array:
    """Carry mode per-step residual update, pinned: r += (q*delta) * x."""
    return r + fp_barrier((q_t * delta) * x_s)


def _gram_chunk_r(r: Array, q_t: Array, colsum: Array) -> Array:
    """Gram mode per-chunk residual reconstitution, pinned: r += q * col."""
    return r + fp_barrier(q_t * colsum)


@jax.jit
def row_norms(X: Array) -> Array:
    """``||x_i||^2`` rows, barriered: THE xnorm2 used by every engine.

    The inner barrier rounds the squares before the reduce: inside a jit,
    XLA's CPU backend contracts ``x*x`` into the reduce's adds as FMAs,
    which an eager (op-by-op) call cannot, so without it the hoisted
    per-run table (``run_mocha``, eager) and the in-jit hoist (the vmapped
    sweep) differ by a ulp.  The outer barrier materializes the table so
    the reduce cannot be re-fused into a consumer with a context-dependent
    partial-sum tree -- the hoisted table, the in-solver fallback, and the
    Pallas wrapper's kernel input are then bit-identical by construction."""
    return fp_barrier(jnp.sum(fp_barrier(X * X), axis=-1))


def _draw_coordinates(X_t: Array, mask_t: Array, key: Array,
                      max_steps: int) -> Array:
    """The shared coordinate stream (DESIGN.md section 2): uniform draws over
    the real (left-packed) points.  The Pallas kernel reproduces this stream
    exactly; every solver variant must consume it unchanged."""
    n = X_t.shape[0]
    n_t = jnp.maximum(jnp.sum(mask_t), 1.0)
    draws = jax.random.uniform(key, (max_steps,))
    return jnp.minimum((draws * n_t).astype(jnp.int32), n - 1)


def _run_chunks(loss: Loss, X_t: Array, y_t: Array, mask_t: Array,
                alpha_t: Array, w_t: Array, q_t: Array, budget_t: Array,
                idx: Array, max_steps: int, xnorm2: Array,
                gram: bool, C: int, compact: bool,
                unroll_chunks: bool = False) -> Tuple[Array, Array]:
    """The arithmetic-v2 chunk loop, shared by both accumulator variants.

    ``compact=False`` (dense) scatters each delta straight into the carried
    (n,) dalpha buffer; ``compact=True`` accumulates into a chunk-local
    buffer indexed by first occurrence and writes back once per chunk.  The
    adds hit the same values in the same order either way, so the variants
    are bit-identical (tests/test_subproblem.py).

    ``unroll_chunks`` replaces the chunk ``fori_loop`` with a python loop
    (bit-identical; the body is pure).  XLA's HLO cost analysis counts a
    while-loop body once regardless of trip count, so cost probes
    (benchmarks/sdca_micro.py) difference two unrolled depths instead --
    the same methodology as launch/roofline.py's depth differencing.
    """
    n, d = X_t.shape
    # clamp so the zero-padded chunk tail (s >= max_steps >= budget_t) is
    # dead for ANY caller-supplied budget, in every variant and engine
    budget_t = jnp.minimum(budget_t, max_steps)
    plan = _chunk_layout(idx, n, max_steps, C)
    n_chunks = plan.idx_c.shape[0]

    def chunk_body(c, carry):
        dalpha, u, r = carry
        ic = plan.idx_c[c]
        Xc = X_t[ic]
        yc, xc2, mc, ac = y_t[ic], xnorm2[ic], mask_t[ic], alpha_t[ic]
        if gram:
            G = _chunk_gram(Xc)
            p = _chunk_rowdots(Xc, r)
        if compact:
            fpos, wb = plan.firstpos[c], plan.wb[c]
            acc = dalpha[ic]              # running totals, compacted
        else:
            acc = dalpha
        deltas = jnp.zeros((C,), X_t.dtype)
        # unrolled: s is static, so every chunk-local index below is static
        for s in range(C):
            k = fpos[s] if compact else ic[s]
            a = ac[s] + acc[k]
            g = (_gram_g(p[s], q_t, G[s], deltas) if gram
                 else _carry_g(Xc[s], r))
            delta = loss.sdca_delta(a, yc[s], g, q_t * xc2[s])
            live = ((c * C + s < budget_t)
                    & (mc[s] > 0)).astype(delta.dtype)
            delta = delta * live
            acc = acc.at[k].add(delta)
            deltas = deltas.at[s].set(delta)
            if not gram:
                r = _carry_step_r(r, q_t, delta, Xc[s])
        colsum = _chunk_colsum(Xc, deltas)
        if gram:
            r = _gram_chunk_r(r, q_t, colsum)
        dalpha = (dalpha.at[wb].set(acc, mode="drop") if compact else acc)
        return dalpha, u + colsum, r

    carry = (jnp.zeros(n, X_t.dtype), jnp.zeros(d, X_t.dtype), w_t)
    if unroll_chunks:
        for c in range(n_chunks):
            carry = chunk_body(c, carry)
    else:
        carry = jax.lax.fori_loop(0, n_chunks, chunk_body, carry)
    dalpha, u, _ = carry
    return dalpha, u


def _local_sdca_dense(loss: Loss, X_t: Array, y_t: Array, mask_t: Array,
                      alpha_t: Array, w_t: Array, q_t: Array, budget_t: Array,
                      idx: Array, max_steps: int, xnorm2: Array,
                      gram: bool, C: int,
                      unroll_chunks: bool = False) -> Tuple[Array, Array]:
    """Small-n variant: per-step scatter into the full (n,) dual buffer."""
    return _run_chunks(loss, X_t, y_t, mask_t, alpha_t, w_t, q_t, budget_t,
                       idx, max_steps, xnorm2, gram, C, compact=False,
                       unroll_chunks=unroll_chunks)


def _local_sdca_chunked(loss: Loss, X_t: Array, y_t: Array, mask_t: Array,
                        alpha_t: Array, w_t: Array, q_t: Array,
                        budget_t: Array, idx: Array, max_steps: int,
                        xnorm2: Array, gram: bool, C: int,
                        unroll_chunks: bool = False) -> Tuple[Array, Array]:
    """Large-n variant: compact first-occurrence accumulator, one (n,)
    write-back per chunk instead of one O(n) carry copy per step."""
    return _run_chunks(loss, X_t, y_t, mask_t, alpha_t, w_t, q_t, budget_t,
                       idx, max_steps, xnorm2, gram, C, compact=True,
                       unroll_chunks=unroll_chunks)


def local_sdca_idx(loss: Loss, X_t: Array, y_t: Array, mask_t: Array,
                   alpha_t: Array, w_t: Array, q_t: Array, budget_t: Array,
                   idx: Array, max_steps: int,
                   xnorm2: Optional[Array] = None,
                   gram: Optional[bool] = None,
                   unroll_chunks: bool = False) -> Tuple[Array, Array]:
    """Canonical SDCA local solve over an explicit coordinate stream.

    THE single jnp source of truth for the inner-loop arithmetic: the Pallas
    reference oracle (kernels/sdca/ref.py) and the key-driven entry points
    below all delegate here.  ``xnorm2`` accepts the per-run hoisted row
    norms (computed on the fly when absent); ``gram`` overrides the static
    residual-mode rule (see ``_solver_plan``).
    """
    if xnorm2 is None:
        xnorm2 = row_norms(X_t)
    gram, C = _solver_plan(X_t.shape[1], max_steps, gram)
    solver = (_local_sdca_chunked if X_t.shape[0] >= _CHUNK_THRESHOLD
              else _local_sdca_dense)
    return solver(loss, X_t, y_t, mask_t, alpha_t, w_t, q_t, budget_t,
                  idx, max_steps, xnorm2, gram, C,
                  unroll_chunks=unroll_chunks)


def local_sdca(loss: Loss, X_t: Array, y_t: Array, mask_t: Array,
               alpha_t: Array, w_t: Array, q_t: Array, budget_t: Array,
               key: Array, max_steps: int,
               xnorm2: Optional[Array] = None,
               gram: Optional[bool] = None) -> Tuple[Array, Array]:
    """Run up to ``max_steps`` SDCA coordinate updates, masked past budget_t.

    Returns (dalpha_t (n,), u_t (d,)) with u_t = X_t^T dalpha_t accumulated
    from the per-chunk column sums (this is the Delta v_t the node ships
    back).  Draws the shared coordinate stream from ``key`` and dispatches
    on the static point count to the compact accumulator for large n.
    """
    idx = _draw_coordinates(X_t, mask_t, key, max_steps)
    return local_sdca_idx(loss, X_t, y_t, mask_t, alpha_t, w_t, q_t,
                          budget_t, idx, max_steps, xnorm2, gram)


def batched_local_sdca(loss: Loss, X: Array, y: Array, mask: Array,
                       alpha: Array, W: Array, q_t: Array, budgets: Array,
                       keys: Array, max_steps: int,
                       xnorm2: Optional[Array] = None,
                       gram: Optional[bool] = None) -> Tuple[Array, Array]:
    """vmap of ``local_sdca`` across tasks: (m, n, d), (m, n), ... (m, 2).

    ``xnorm2`` (m, n) is the per-run hoisted row-norm table threaded through
    ``run_mocha`` (recomputed here when absent -- e.g. dry-run lowerings)."""
    if xnorm2 is None:
        xnorm2 = row_norms(X)
    fn = lambda X, y, mask, alpha, w, q, b, k, xn: local_sdca(
        loss, X, y, mask, alpha, w, q, b, k, max_steps, xn, gram)
    return jax.vmap(fn)(X, y, mask, alpha, W, q_t, budgets, keys, xnorm2)


def solve_exact(loss: Loss, X_t: Array, y_t: Array, mask_t: Array,
                alpha_t: Array, w_t: Array, q_t: Array, key: Array,
                passes: int = 64) -> Tuple[Array, Array]:
    """High-accuracy subproblem solution (for theta measurement / tests)."""
    n = X_t.shape[0]
    steps = int(passes) * n
    budget = jnp.asarray(steps, jnp.int32)
    return local_sdca(loss, X_t, y_t, mask_t, alpha_t, w_t, q_t, budget,
                      key, steps)


def measure_theta(loss: Loss, X_t: Array, y_t: Array, mask_t: Array,
                  alpha_t: Array, w_t: Array, q_t: Array,
                  dalpha_t: Array, key: Array, exact_passes: int = 64) -> Array:
    """Definition 1: theta = (G(Delta) - G(Delta*)) / (G(0) - G(Delta*))."""
    dstar, _ = solve_exact(loss, X_t, y_t, mask_t, alpha_t, w_t, q_t, key,
                           passes=exact_passes)
    g = partial(subproblem_value, loss, X_t, y_t, mask_t, alpha_t)
    g_zero = g(jnp.zeros_like(alpha_t), w_t, q_t)
    g_delta = g(dalpha_t, w_t, q_t)
    g_star = g(dstar, w_t, q_t)
    denom = g_zero - g_star
    return jnp.where(denom > 1e-12, (g_delta - g_star) / denom, 0.0)
