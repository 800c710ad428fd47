"""System tests for MOCHA (Algorithm 1): convergence, stragglers, faults."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BudgetConfig, Clustered, Graphical, MeanRegularized,
                        MochaConfig, Probabilistic, duality_gap,
                        per_task_error, run_cocoa, run_mocha)
from repro.data.synthetic import tiny_problem


@pytest.fixture(scope="module")
def problem():
    return tiny_problem(m=5, n=30, d=8, seed=0)


REG = MeanRegularized(lambda1=0.5, lambda2=0.5)


@pytest.mark.parametrize("loss", ["hinge", "smooth_hinge", "logistic",
                                  "squared"])
def test_duality_gap_converges(problem, loss):
    train, _ = problem
    cfg = MochaConfig(loss=loss, rounds=80, budget=BudgetConfig(passes=2.0),
                      record_every=79)
    res = run_mocha(train, REG, cfg)
    rel_gap = res.final("gap") / max(abs(res.final("primal")), 1.0)
    assert rel_gap < 5e-3, f"{loss}: relative duality gap {rel_gap}"


def test_gap_monotone_trend(problem):
    train, _ = problem
    cfg = MochaConfig(loss="smooth_hinge", rounds=60,
                      budget=BudgetConfig(passes=1.0), record_every=5)
    res = run_mocha(train, REG, cfg)
    gaps = np.asarray(res.history["gap"])
    assert gaps[-1] < 1e-2 * gaps[0]
    # loose monotonicity: each recorded gap below 2x the previous
    # (absolute slack for float32 noise once the gap is ~1e-5)
    assert np.all(gaps[1:] <= 2.0 * gaps[:-1] + 1e-4)


def test_linear_rate_for_smooth_losses(problem):
    """Theorem 1: smooth losses give a geometric rate in rounds."""
    train, _ = problem
    cfg = MochaConfig(loss="smooth_hinge", rounds=40,
                      budget=BudgetConfig(passes=2.0), record_every=1)
    res = run_mocha(train, REG, cfg)
    dual = np.asarray(res.history["dual"])
    d_star = dual[-1]
    subopt = dual - d_star
    # use the prefix that is still clearly above float32 noise
    keep = subopt > 1e-4
    subopt = subopt[keep][:20]
    assert len(subopt) >= 5, "converged too fast to fit a rate"
    rounds = np.arange(len(subopt))
    slope = np.polyfit(rounds, np.log(subopt), 1)[0]
    assert slope < -0.1, f"no geometric decay, slope {slope}"


def test_straggler_budgets_still_converge(problem):
    """Systems heterogeneity (Fig 2): random budgets in [0.1, 1.0] n_min."""
    train, _ = problem
    cfg = MochaConfig(
        loss="hinge", rounds=180,
        budget=BudgetConfig(passes=1.0, systems_lo=0.1, systems_hi=1.0),
        record_every=179)
    res = run_mocha(train, REG, cfg)
    rel_gap = res.final("gap") / max(abs(res.final("primal")), 1.0)
    assert rel_gap < 2e-2


def test_fault_tolerance_converges_under_assumption2(problem):
    """Fig 3: p_t^h = 0.5 drops still converge (p_max < 1)."""
    train, _ = problem
    cfg = MochaConfig(loss="hinge", rounds=250,
                      budget=BudgetConfig(passes=1.0, drop_prob=0.5),
                      record_every=249)
    res = run_mocha(train, REG, cfg)
    rel_gap = res.final("gap") / max(abs(res.final("primal")), 1.0)
    assert rel_gap < 2e-2


def test_permanently_dead_node_breaks_convergence(problem):
    """Fig 3 green line: a node with p = 1 forever -> wrong solution."""
    train, _ = problem
    good = run_mocha(train, REG, MochaConfig(
        loss="hinge", rounds=80, budget=BudgetConfig(passes=2.0),
        record_every=79))
    with pytest.warns(UserWarning):
        bad = run_mocha(train, REG, MochaConfig(
            loss="hinge", rounds=80,
            budget=BudgetConfig(passes=2.0, never_send_node=0),
            record_every=79))
    # dead node's model never leaves the coupled prior: its dual block is 0
    assert np.allclose(np.asarray(bad.state.alpha[0]), 0.0)
    # and the achieved primal is worse than the true optimum
    assert bad.final("primal") > good.final("primal") + 0.1


def test_assumption2_validation_rejects_p1():
    with pytest.raises(ValueError):
        run_mocha(tiny_problem()[0], REG, MochaConfig(
            budget=BudgetConfig(drop_prob=1.0)))


def test_cocoa_is_uniform_special_case(problem):
    """Remark 2: with identical budgets MOCHA == CoCoA trajectory."""
    train, _ = problem
    cfg = MochaConfig(loss="hinge", rounds=30, budget=BudgetConfig(passes=1.5),
                      per_task_sigma=False, record_every=29)
    a = run_mocha(train, REG, cfg)
    b = run_cocoa(train, REG, cfg)
    np.testing.assert_allclose(a.final("dual"), b.final("dual"), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(a.W), np.asarray(b.W), atol=1e-5)


def test_gamma_less_than_one_converges(problem):
    train, _ = problem
    cfg = MochaConfig(loss="smooth_hinge", rounds=120, gamma=0.5,
                      budget=BudgetConfig(passes=1.0), record_every=119)
    res = run_mocha(train, REG, cfg)
    rel_gap = res.final("gap") / max(abs(res.final("primal")), 1.0)
    assert rel_gap < 1e-2


def test_omega_learning_improves_generalization():
    """Learning Omega (probabilistic MTL) should beat no-coupling local models
    on a cluster-structured federation (averaged over seeds, Table-1 style)."""
    e_mtl, e_loc = [], []
    for seed in range(4):
        train, test = tiny_problem(m=10, n=12, d=12, seed=seed, clusters=2)
        mtl_cfg = MochaConfig(loss="smooth_hinge", rounds=100,
                              omega_update_every=20,
                              budget=BudgetConfig(passes=2.0),
                              record_every=99)
        mtl = run_mocha(train, Probabilistic(lam=0.01, sigma2=10.0), mtl_cfg)
        local = run_mocha(train, MeanRegularized(lambda1=0.0, lambda2=0.01),
                          dataclasses.replace(mtl_cfg, omega_update_every=0))
        e_mtl.append(float(jnp.mean(per_task_error(
            train, jnp.asarray(mtl.W), test.X, test.y, test.mask))))
        e_loc.append(float(jnp.mean(per_task_error(
            train, jnp.asarray(local.W), test.X, test.y, test.mask))))
    assert np.mean(e_mtl) < np.mean(e_loc), (e_mtl, e_loc)


@pytest.mark.parametrize("record_every", [1, 2, 3, 5])
def test_history_columns_equal_length(problem, record_every):
    """Regression: round_max_steps used to be appended every round while all
    other keys followed record_every, yielding ragged history columns for any
    record_every > 1."""
    train, _ = problem
    res = run_mocha(train, REG, MochaConfig(
        loss="hinge", rounds=11, budget=BudgetConfig(passes=0.5),
        record_every=record_every))
    lengths = {k: len(v) for k, v in res.history.items()}
    assert len(set(lengths.values())) == 1, f"ragged history: {lengths}"
    expected = len({*range(0, 11, record_every), 10})
    assert set(lengths.values()) == {expected}


@pytest.mark.parametrize("rounds,record_every", [(1, 1), (1, 5), (3, 7),
                                                 (2, 5)])
@pytest.mark.parametrize("driver", ["scan", "loop"])
def test_history_degenerate_cadences(problem, rounds, record_every, driver):
    """Regression (PR 5 satellite): record_every > rounds and rounds == 1
    must keep the final-round row and a rectangular history on BOTH
    drivers."""
    train, _ = problem
    res = run_mocha(train, REG, MochaConfig(
        loss="hinge", rounds=rounds, record_every=record_every,
        driver=driver, budget=BudgetConfig(passes=0.5)))
    lengths = {len(v) for v in res.history.values()}
    assert len(lengths) == 1, f"ragged history: {res.history}"
    assert res.history["round"][-1] == rounds - 1   # final row present
    expected = sorted({*range(0, rounds, record_every), rounds - 1})
    assert res.history["round"] == expected


def test_record_rounds_validation():
    from repro.core.mocha import _record_rounds
    with pytest.raises(ValueError, match="rounds >= 1"):
        _record_rounds(0, 1)
    with pytest.raises(ValueError, match="record_every >= 1"):
        _record_rounds(5, 0)
    np.testing.assert_array_equal(_record_rounds(1, 10), [True])


def test_history_time_axis_monotone(problem):
    train, _ = problem
    res = run_mocha(train, REG, MochaConfig(
        loss="hinge", rounds=20, budget=BudgetConfig(passes=1.0),
        record_every=2))
    t = np.asarray(res.history["time"])
    assert np.all(np.diff(t) > 0)


@pytest.mark.parametrize("reg,warm", [
    (MeanRegularized(lambda1=0.5, lambda2=0.5), True),
    (Clustered(lam=0.5, eta=0.4, k=2), True),
    (Probabilistic(lam=1e-2, sigma2=10.0), True),
    (Graphical(lam=0.5, lam2=0.01), True),
    (Probabilistic(lam=1e-2, sigma2=10.0), False),   # cold start, W = 0
    (Clustered(lam=0.5, eta=0.4, k=2), False),
], ids=["mean", "clustered", "probabilistic", "graphical",
        "probabilistic-cold", "clustered-cold"])
def test_compiled_omega_step_matches_eager_composition(problem, reg, warm):
    """The drivers' one-program Omega step computes what its parts compute
    when dispatched one by one: W, ``update_omega``, the coupling terms and
    the refresh round's metrics under the new K."""
    from repro.core import dual as dual_mod
    from repro.core.losses import get_loss
    from repro.core.mocha import (_coupling_terms, _metrics_impl,
                                  _omega_step)
    train, _ = problem
    data = dual_mod.with_xnorm2(train)
    loss, gamma, m = get_loss("hinge"), 0.8, train.m
    if warm:   # a nonzero iterate, mid-run under the initial Omega
        state = run_mocha(train, reg, MochaConfig(
            rounds=4, gamma=gamma, budget=BudgetConfig(passes=1.0))).state
    else:
        state = dual_mod.init_state(data)
    omega0 = reg.init_omega(m)
    _, K0, _ = _coupling_terms(reg, omega0, gamma, True, m)
    W = dual_mod.primal_weights(K0, state.v)
    assert bool(jnp.any(W != 0)) == warm
    omega = reg.update_omega(W, omega0)
    abar, K, q_t = _coupling_terms(reg, omega, gamma, True, m)
    row = jnp.stack(_metrics_impl(loss, data, state, abar, K))
    got = _omega_step(reg, loss, gamma, True, data, state, K0, omega0)
    for want, have in zip((omega, abar, K, q_t, row), got):
        assert have.shape == want.shape and have.dtype == want.dtype
        np.testing.assert_allclose(np.asarray(have), np.asarray(want),
                                   rtol=1e-6)
