"""Local subproblem (eq. 4) and theta (Definition 1) semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (MeanRegularized, get_loss, init_state, primal_weights,
                        sigma_prime)
from repro.core.subproblem import (local_sdca, measure_theta, solve_exact,
                                   subproblem_value)
from repro.data.synthetic import tiny_problem


@pytest.fixture(scope="module")
def setup():
    train, _ = tiny_problem(m=4, n=24, d=6, seed=0)
    reg = MeanRegularized(0.5, 0.5)
    K = reg.K(reg.init_omega(train.m))
    sig = sigma_prime(K)
    q = sig * jnp.diagonal(K) / 2.0
    state = init_state(train)
    W = primal_weights(K, state.v)
    return train, K, q, state, W


def test_theta_zero_budget_is_one(setup):
    train, K, q, state, W = setup
    loss = get_loss("hinge")
    key = jax.random.PRNGKey(0)
    d_, _ = local_sdca(loss, train.X[0], train.y[0], train.mask[0],
                       state.alpha[0], W[0], q[0], jnp.asarray(0), key, 50)
    assert np.allclose(np.asarray(d_), 0.0)
    th = measure_theta(loss, train.X[0], train.y[0], train.mask[0],
                       state.alpha[0], W[0], q[0], d_, jax.random.PRNGKey(1))
    np.testing.assert_allclose(float(th), 1.0, atol=1e-6)


def test_theta_decreases_with_budget(setup):
    train, K, q, state, W = setup
    loss = get_loss("hinge")
    key = jax.random.PRNGKey(0)
    thetas = []
    for budget in [2, 10, 50, 400]:
        d_, _ = local_sdca(loss, train.X[0], train.y[0], train.mask[0],
                           state.alpha[0], W[0], q[0], jnp.asarray(budget),
                           key, 400)
        th = measure_theta(loss, train.X[0], train.y[0], train.mask[0],
                           state.alpha[0], W[0], q[0], d_,
                           jax.random.PRNGKey(1))
        thetas.append(float(th))
    assert all(b <= a + 1e-4 for a, b in zip(thetas, thetas[1:])), thetas
    assert thetas[-1] < 0.05
    assert all(0.0 - 1e-6 <= t <= 1.0 + 1e-6 for t in thetas)


def test_u_equals_xt_dalpha(setup):
    """The shipped Delta v_t must equal X_t^T Delta alpha_t exactly."""
    train, K, q, state, W = setup
    loss = get_loss("smooth_hinge")
    d_, u = local_sdca(loss, train.X[1], train.y[1], train.mask[1],
                       state.alpha[1], W[1], q[1], jnp.asarray(40),
                       jax.random.PRNGKey(3), 40)
    np.testing.assert_allclose(np.asarray(train.X[1].T @ (d_ * train.mask[1])),
                               np.asarray(u), atol=1e-4)


def test_subproblem_value_decreases(setup):
    train, K, q, state, W = setup
    loss = get_loss("hinge")
    g0 = subproblem_value(loss, train.X[0], train.y[0], train.mask[0],
                          state.alpha[0], jnp.zeros_like(state.alpha[0]),
                          W[0], q[0])
    d_, _ = local_sdca(loss, train.X[0], train.y[0], train.mask[0],
                       state.alpha[0], W[0], q[0], jnp.asarray(100),
                       jax.random.PRNGKey(0), 100)
    g1 = subproblem_value(loss, train.X[0], train.y[0], train.mask[0],
                          state.alpha[0], d_, W[0], q[0])
    assert float(g1) < float(g0)


def test_padding_never_touched(setup):
    """Updates on padded coordinates must be identically zero."""
    train, K, q, state, W = setup
    # build a task with heavy padding
    mask = train.mask[0].at[10:].set(0.0)
    loss = get_loss("hinge")
    d_, _ = local_sdca(loss, train.X[0], train.y[0], mask, state.alpha[0],
                       W[0], q[0], jnp.asarray(200), jax.random.PRNGKey(0),
                       200)
    assert np.allclose(np.asarray(d_)[10:], 0.0)


def test_exact_solver_reaches_stationarity(setup):
    """After solve_exact, no single coordinate step can improve much."""
    train, K, q, state, W = setup
    loss = get_loss("smooth_hinge")
    dstar, u = solve_exact(loss, train.X[2], train.y[2], train.mask[2],
                           state.alpha[2], W[2], q[2], jax.random.PRNGKey(5),
                           passes=64)
    g_star = subproblem_value(loss, train.X[2], train.y[2], train.mask[2],
                              state.alpha[2], dstar, W[2], q[2])
    # try one extra exact coordinate step everywhere; improvement ~ 0
    n = train.X[2].shape[0]
    alpha_eff = state.alpha[2] + dstar
    g_eff = W[2] + q[2] * u
    for i in range(0, n, 5):
        x = train.X[2][i]
        delta = loss.sdca_delta(alpha_eff[i], train.y[2][i],
                                jnp.dot(x, g_eff), q[2] * jnp.dot(x, x))
        d2 = dstar.at[i].add(delta * train.mask[2][i])
        g2 = subproblem_value(loss, train.X[2], train.y[2], train.mask[2],
                              state.alpha[2], d2, W[2], q[2])
        assert float(g_star) - float(g2) < 1e-3


def _toy(loss_name, n, d, seed=3, mask_frac=0.8):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(0, 1, (n, d)) / np.sqrt(d), jnp.float32)
    y = jnp.asarray(np.sign(rng.normal(0, 1, n)), jnp.float32)
    mask = jnp.asarray(rng.random(n) < mask_frac, jnp.float32)
    alpha = jnp.asarray(rng.normal(0, 0.01, n), jnp.float32) * y * mask
    w = jnp.asarray(rng.normal(0, 0.1, d), jnp.float32)
    return get_loss(loss_name), X, y, mask, alpha, w


def _both_variants(loss, X, y, mask, alpha, w, q, budget, idx, max_steps,
                   gram):
    from repro.core.subproblem import (_local_sdca_chunked,
                                       _local_sdca_dense, _solver_plan,
                                       row_norms)
    g, C = _solver_plan(X.shape[1], max_steps, gram)
    xn = row_norms(X)
    args = (loss, X, y, mask, alpha, w, q, budget, idx, max_steps, xn, g, C)
    return (jax.jit(_local_sdca_dense, static_argnums=(0, 9, 11, 12))(*args),
            jax.jit(_local_sdca_chunked,
                    static_argnums=(0, 9, 11, 12))(*args))


@pytest.mark.parametrize("gram", [False, True], ids=["carry", "gram"])
@pytest.mark.parametrize("loss_name", ["hinge", "smooth_hinge", "logistic"])
def test_chunked_solver_bit_identical_to_dense(loss_name, gram):
    """The compact position-indexed accumulator and the dense per-step
    scatter must be bit-identical under BOTH residual modes (same draws,
    same adds, same order -- DESIGN.md section 2)."""
    rng = np.random.default_rng(3)
    n, d = 300, 7
    loss, X, y, mask, alpha, w = _toy(loss_name, n, d)
    idx = jnp.asarray(rng.integers(0, n, 300), jnp.int32)
    budget = jnp.asarray(211, jnp.int32)   # not a chunk multiple
    (da_d, u_d), (da_c, u_c) = _both_variants(
        loss, X, y, mask, alpha, w, jnp.asarray(0.7), budget, idx, 300, gram)
    np.testing.assert_array_equal(np.asarray(da_d), np.asarray(da_c))
    np.testing.assert_array_equal(np.asarray(u_d), np.asarray(u_c))


def _gather_scatter_add_chunks(loss, X, y, mask, alpha, w, q, budget, idx,
                               max_steps, xnorm2, gram, C):
    """The compact chunk loop with the step it had before the
    position-indexed accumulator: one slot per coordinate, at its first
    position in the chunk, read by ``acc[fpos[s]]`` and updated by a
    scatter-add.  The reference the select-based step must equal bit for
    bit (DESIGN.md section 3a)."""
    from repro.core import subproblem as sp
    n, d = X.shape
    budget = jnp.minimum(budget, max_steps)
    plan = sp._chunk_layout(idx, n, max_steps, C)

    def chunk_body(c, carry):
        dalpha, u, r = carry
        ic, fpos, wb = plan.idx_c[c], plan.firstpos[c], plan.wb[c]
        Xc = X[ic]
        yc, xc2, mc, ac = y[ic], xnorm2[ic], mask[ic], alpha[ic]
        if gram:
            G, p = sp._chunk_gram(Xc), sp._chunk_rowdots(Xc, r)
        acc = dalpha[ic]
        deltas = jnp.zeros((C,), X.dtype)
        for s in range(C):
            k = fpos[s]
            g = (sp._gram_g(p[s], q, G[s], deltas) if gram
                 else sp._carry_g(Xc[s], r))
            delta = loss.sdca_delta(ac[s] + acc[k], yc[s], g, q * xc2[s])
            live = ((c * C + s < budget) & (mc[s] > 0)).astype(delta.dtype)
            delta = delta * live
            acc = acc.at[k].add(delta)
            deltas = deltas.at[s].set(delta)
            if not gram:
                r = sp._carry_step_r(r, q, delta, Xc[s])
        colsum = sp._chunk_colsum(Xc, deltas)
        if gram:
            r = sp._gram_chunk_r(r, q, colsum)
        return dalpha.at[wb].set(acc, mode="drop"), u + colsum, r

    carry = (jnp.zeros(n, X.dtype), jnp.zeros(d, X.dtype), w)
    dalpha, u, _ = jax.lax.fori_loop(0, plan.idx_c.shape[0], chunk_body,
                                     carry)
    return dalpha, u


# ---------------------------------------------------------------------------
# chunk-boundary coverage: the firstpos/write-back dedup logic at its edges
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gram", [False, True], ids=["carry", "gram"])
@pytest.mark.parametrize("case", [
    "n_eq_threshold",      # dispatch boundary: n == _CHUNK_THRESHOLD exactly
    "steps_lt_chunk",      # max_steps < C: single short chunk
    "steps_not_multiple",  # ragged tail chunk (padded steps must stay dead)
    "repeat_heavy",        # tiny n, large budget: every chunk full of repeats
    "repeat_heavy_compact",  # n just above the threshold, about six
                             # passes, one coordinate 4 times in each chunk
])
def test_chunk_boundaries_bit_identical(case, gram):
    from repro.core.subproblem import (_CHUNK_THRESHOLD, _chunk_layout,
                                       _solver_plan, row_norms)
    rng = np.random.default_rng(11)
    n, max_steps = {
        "n_eq_threshold": (_CHUNK_THRESHOLD, 2 * _CHUNK_THRESHOLD),
        "steps_lt_chunk": (40, 5),
        "steps_not_multiple": (50, 101),
        "repeat_heavy": (3, 400),
        "repeat_heavy_compact": (_CHUNK_THRESHOLD + 2, 6 * _CHUNK_THRESHOLD),
    }[case]
    d = 9
    loss, X, y, mask, alpha, w = _toy("hinge", n, d, seed=12, mask_frac=0.9)
    idx = jnp.asarray(rng.integers(0, n, max_steps), jnp.int32)
    budget = jnp.asarray(rng.integers(0, max_steps + 3), jnp.int32)
    g, C = _solver_plan(d, max_steps, gram)
    if case == "repeat_heavy_compact":
        # every 16 draws repeat their second coordinate three more times,
        # so each chunk (C = 16 or 32) holds one coordinate 4 times or more
        planted = np.array(idx).reshape(-1, 16)
        planted[:, [5, 9, 13]] = planted[:, [1]]
        idx = jnp.asarray(planted.reshape(-1))
        budget = jnp.asarray(max_steps - 5, jnp.int32)
        counts = [np.bincount(row).max() for row in np.asarray(
            _chunk_layout(idx, n, max_steps, C).idx_c)]
        assert min(counts) >= 3
    q = jnp.asarray(0.9)
    (da_d, u_d), (da_c, u_c) = _both_variants(
        loss, X, y, mask, alpha, w, q, budget, idx, max_steps, gram)
    np.testing.assert_array_equal(np.asarray(da_d), np.asarray(da_c))
    np.testing.assert_array_equal(np.asarray(u_d), np.asarray(u_c))
    # ... and the gather / scatter-add step the select replaced
    da_o, u_o = jax.jit(_gather_scatter_add_chunks,
                        static_argnums=(0, 9, 11, 12))(
        loss, X, y, mask, alpha, w, q, budget, idx, max_steps, row_norms(X),
        g, C)
    assert np.array_equal(np.asarray(da_o), np.asarray(da_c))
    assert np.array_equal(np.asarray(u_o), np.asarray(u_c))
    # repeated-coordinate totals must match a sequential numpy replay count:
    # every live draw contributes exactly once to its coordinate's total
    if case.startswith("repeat_heavy"):
        live = (np.arange(max_steps) < int(budget)) \
            & (np.asarray(mask)[np.asarray(idx)] > 0)
        touched = np.zeros(n, bool)
        touched[np.asarray(idx)[live]] = True
        assert np.all((np.asarray(da_d) != 0) <= touched)


@pytest.mark.parametrize("d", [100, 561], ids=["gram", "carry"])
def test_compact_step_has_no_gather_and_one_scatter(d):
    """The compact step reads and updates its accumulator by static index
    and select: in the vmapped solve the engines run, lowered on the CPU,
    the chunk's gathers do not grow with the chunk's C unrolled steps, and
    its scatters are one a step (the ``deltas`` write) plus the chunk's
    write-back.  ``max_steps`` below the mode's chunk width sets C."""
    from repro.core.subproblem import (_CHUNK_THRESHOLD, _solver_plan,
                                       accumulator, batched_local_sdca)
    m, n = 2, _CHUNK_THRESHOLD + 2
    assert accumulator(n) == "position"

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    counts = {}
    for steps in (8, 24):
        assert _solver_plan(d, steps) == (d <= 128, steps)
        text = jax.jit(batched_local_sdca, static_argnums=(0, 9)).lower(
            get_loss("hinge"), sds(m, n, d), sds(m, n), sds(m, n),
            sds(m, n), sds(m, d), sds(m), sds(m, dtype=jnp.int32),
            sds(m, 2, dtype=jnp.uint32), steps, sds(m, n)).as_text()
        counts[steps] = (text.count('"stablehlo.gather"('),
                         text.count('"stablehlo.scatter"('))
    assert counts[8][0] == counts[24][0]
    assert counts[8][1] == 8 + 1 and counts[24][1] == 24 + 1


def test_dispatch_uses_chunked_at_threshold():
    """n == _CHUNK_THRESHOLD must take the compact-accumulator path."""
    from repro.core import subproblem as sp
    calls = {}
    orig = sp._run_chunks

    def spy(*args, **kw):
        calls["compact"] = kw.get("compact", args[-1])
        return orig(*args, **kw)

    sp._run_chunks, spy_token = spy, None
    try:
        loss, X, y, mask, alpha, w = _toy("hinge", sp._CHUNK_THRESHOLD, 5)
        sp.local_sdca(loss, X, y, mask, alpha, w, jnp.asarray(0.5),
                      jnp.asarray(10), jax.random.PRNGKey(0), 16)
    finally:
        sp._run_chunks = orig
    assert calls["compact"] is True
    try:
        sp._run_chunks = spy
        loss, X, y, mask, alpha, w = _toy("hinge", sp._CHUNK_THRESHOLD - 1, 5)
        sp.local_sdca(loss, X, y, mask, alpha, w, jnp.asarray(0.5),
                      jnp.asarray(10), jax.random.PRNGKey(0), 16)
    finally:
        sp._run_chunks = orig
    assert calls["compact"] is False


def test_local_sdca_idx_matches_key_entry():
    """The explicit-stream entry point is the canonical solver: driving it
    with the drawn stream reproduces the key-driven entry bitwise."""
    from repro.core.subproblem import _draw_coordinates, local_sdca_idx
    loss, X, y, mask, alpha, w = _toy("hinge", 150, 10)
    key = jax.random.PRNGKey(9)
    idx = _draw_coordinates(X, mask, key, 120)
    a1, u1 = local_sdca(loss, X, y, mask, alpha, w, jnp.asarray(0.7),
                        jnp.asarray(77), key, 120)
    a2, u2 = local_sdca_idx(loss, X, y, mask, alpha, w, jnp.asarray(0.7),
                            jnp.asarray(77), idx, 120)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    np.testing.assert_array_equal(np.asarray(u1), np.asarray(u2))


# ---------------------------------------------------------------------------
# convergence-equivalence regression vs the frozen v1 arithmetic: the new
# loop is the SAME optimization algorithm (old-vs-new parity is statistical,
# not bitwise -- DESIGN.md section 2 "arithmetic version")
# ---------------------------------------------------------------------------

# ONE frozen v1 reference, shared with the benchmark's speedup baseline so
# the regression contract and BENCH_sdca's "speedup_vs_v1" cannot drift
# apart (the hazard this PR removed for kernels/sdca/ref.py)
from benchmarks.sdca_micro import _v1_dense_loop as _v1_local_sdca  # noqa: E402


@pytest.mark.parametrize("gram", [False, True], ids=["carry", "gram"])
@pytest.mark.parametrize("loss_name", ["hinge", "smooth_hinge", "logistic",
                                       "squared"])
def test_convergence_equivalent_to_v1_arithmetic(loss_name, gram):
    """Same draws => the v2 loop reaches the same subproblem value as the
    frozen v1 loop (within float tolerance) and near-identical iterates."""
    from repro.core.subproblem import local_sdca_idx
    rng = np.random.default_rng(5)
    n, d = 120, 13
    loss, X, y, mask, alpha, w = _toy(loss_name, n, d, seed=6)
    q = jnp.asarray(0.8)
    max_steps = 4 * n
    idx = jnp.asarray(rng.integers(0, n, max_steps), jnp.int32)
    budget = jnp.asarray(max_steps, jnp.int32)
    da_v1, u_v1 = jax.jit(_v1_local_sdca, static_argnums=(0, 9))(
        loss, X, y, mask, alpha, w, q, budget, idx, max_steps)
    da_v2, u_v2 = local_sdca_idx(loss, X, y, mask, alpha, w, q, budget, idx,
                                 max_steps, gram=gram)
    g_v1 = subproblem_value(loss, X, y, mask, alpha, da_v1, w, q)
    g_v2 = subproblem_value(loss, X, y, mask, alpha, da_v2, w, q)
    np.testing.assert_allclose(float(g_v2), float(g_v1), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(da_v2), np.asarray(da_v1),
                               rtol=1e-3, atol=2e-4)
    np.testing.assert_allclose(np.asarray(u_v2), np.asarray(u_v1),
                               rtol=1e-3, atol=2e-4)


def test_gram_crossover_env_override():
    """The residual-mode crossover has one home, ``_solver_plan``: gram at
    d <= 128, carry above -- checked at the two benchmark widths (Vehicle
    Sensor d=100, Human Activity d=561) and on each side of the boundary.
    An explicit ``gram`` still forces a mode."""
    from repro.core.subproblem import _GRAM_MAX_D, _solver_plan
    assert _GRAM_MAX_D == 128
    for d in (100, 128):
        assert _solver_plan(d, 256)[0] is True
    for d in (129, 561):
        assert _solver_plan(d, 256)[0] is False
    assert _solver_plan(561, 256, gram=True)[0] is True   # explicit wins
    assert _solver_plan(100, 256, gram=False)[0] is False
