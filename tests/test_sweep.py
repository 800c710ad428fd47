"""Sweep harness: the vmapped (shuffle x lambda) grid must reproduce
individual scanned-driver runs, and stacking/eval helpers must be exact."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import repro.api as api
from repro.core import (BudgetConfig, Clustered, MeanRegularized, MochaConfig,
                        Probabilistic, per_task_error, stack_federations,
                        sweep_errors)
from repro.core.mocha import _run_mocha
from repro.core.sweep import _run_sweep
from repro.core.systems_model import SystemsConfig
from repro.data.synthetic import tiny_problem

LAMBDAS = (1e-3, 1e-2, 1e-1)


@pytest.fixture(scope="module")
def shuffles():
    return [tiny_problem(m=5, n=24, d=6, seed=s) for s in range(3)]


def test_stack_federations_pads_and_masks():
    a, _ = tiny_problem(m=4, n=12, d=5, seed=0)
    b, _ = tiny_problem(m=4, n=20, d=5, seed=1)
    assert a.n_max < b.n_max
    stacked = stack_federations([a, b])
    assert stacked.X.shape == (2, 4, b.n_max, 5)
    np.testing.assert_array_equal(np.asarray(stacked.X[0, :, :a.n_max]),
                                  np.asarray(a.X))
    assert float(stacked.mask[0, :, a.n_max:].sum()) == 0.0
    np.testing.assert_array_equal(np.asarray(stacked.n_t),
                                  np.stack([np.asarray(a.n_t),
                                            np.asarray(b.n_t)]))


def test_stack_federations_rejects_shape_mismatch():
    a, _ = tiny_problem(m=4, n=12, d=5)
    b, _ = tiny_problem(m=5, n=12, d=5)
    with pytest.raises(ValueError, match="cannot stack"):
        stack_federations([a, b])


def test_sweep_matches_individual_runs_bitwise(shuffles):
    """Fixed-Omega grid: every (lambda, shuffle) cell of the sweep equals the
    corresponding single scanned-driver run bit-for-bit."""
    cfg = MochaConfig(loss="hinge", rounds=15, budget=BudgetConfig(passes=1.0),
                      record_every=15, seed=0)
    regs = [MeanRegularized(lambda1=0.0, lambda2=lam) for lam in LAMBDAS]
    trains = stack_federations([tr for tr, _ in shuffles])
    res = _run_sweep(trains, regs, 0, cfg)
    assert res.W.shape == (3, 3, 5, 6)
    for li in range(len(LAMBDAS)):
        for s in range(3):
            ref = _run_mocha(shuffles[s][0], regs[li], cfg)
            np.testing.assert_array_equal(res.W[li, s], ref.W)
            np.testing.assert_allclose(res.gap[li, s], ref.final("gap"),
                                       atol=2e-6)


def test_sweep_matches_individual_runs_with_omega_updates(shuffles):
    """Omega-learning grid (the Table-1 'mtl' kind): batched eigh only
    differs from the unbatched path at float32 noise level."""
    cfg = MochaConfig(loss="hinge", rounds=16, omega_update_every=5,
                      budget=BudgetConfig(passes=1.0), record_every=16)
    regs = [Probabilistic(lam=lam, sigma2=10.0) for lam in LAMBDAS]
    trains = stack_federations([tr for tr, _ in shuffles])
    res = _run_sweep(trains, regs, 0, cfg)
    for li in range(len(LAMBDAS)):
        for s in range(3):
            ref = _run_mocha(shuffles[s][0], regs[li], cfg)
            scale = max(float(np.abs(ref.W).max()), 1.0)
            assert np.abs(res.W[li, s] - ref.W).max() / scale < 1e-3
            np.testing.assert_allclose(float(jnp.trace(
                jnp.asarray(res.omega[li, s]))), 1.0, atol=1e-4)


def test_sweep_errors_matches_per_task_error(shuffles):
    cfg = MochaConfig(loss="hinge", rounds=10, record_every=10)
    regs = [MeanRegularized(lambda1=0.0, lambda2=lam) for lam in LAMBDAS]
    trains = stack_federations([tr for tr, _ in shuffles])
    tests = stack_federations([te for _, te in shuffles])
    res = _run_sweep(trains, regs, 0, cfg)
    errs = sweep_errors(res, tests)
    assert errs.shape == (3, 3)
    for li in (0, 2):
        for s in (0, 1):
            te = shuffles[s][1]
            ref = float(jnp.mean(per_task_error(
                shuffles[s][0], jnp.asarray(res.W[li, s]), te.X, te.y,
                te.mask)))
            np.testing.assert_allclose(errs[li, s], ref, atol=1e-6)


def test_sweep_per_shuffle_seeds(shuffles):
    """Per-shuffle driver seeds feed through to distinct budget streams."""
    cfg = MochaConfig(loss="hinge", rounds=6, record_every=6,
                      budget=BudgetConfig(passes=1.0, systems_lo=0.3,
                                          drop_prob=0.2))
    regs = [MeanRegularized(lambda1=0.0, lambda2=1e-2)]
    trains = stack_federations([tr for tr, _ in shuffles])
    res = _run_sweep(trains, regs, [3, 4, 5], cfg)
    for s, seed in enumerate((3, 4, 5)):
        ref = _run_mocha(shuffles[s][0], regs[0],
                         dataclasses.replace(cfg, seed=seed))
        np.testing.assert_array_equal(res.W[0, s], ref.W)


def test_sweep_mixed_types_and_semi_sync_fall_back(shuffles):
    """Capability change (PR 5): grids the harness used to REJECT now
    complete -- mixed regularizer types through the router's sequential
    fallback, semi_sync clocks on the batched path itself (the caps fold
    into the pre-sampled budgets; see test_api.py for the parity test)."""
    trains = stack_federations([tr for tr, _ in shuffles])
    cfg = MochaConfig(loss="hinge", rounds=2, record_every=2)
    mixed = api.Experiment(
        problem=api.Problem(train=trains),
        method=api.Method(regularizers=(
            MeanRegularized(lambda1=0.0, lambda2=1e-2),
            Probabilistic(lam=1e-2)), rounds=2),
        eval=api.Eval(record_every=2)).run(0)
    assert mixed.provenance["path"] == "grid"
    assert mixed.result.W.shape == (2, 3, 5, 6)
    semi = dataclasses.replace(cfg, systems=SystemsConfig(
        policy="semi_sync", clock_cycle_s=0.1))
    res = _run_sweep(trains, [MeanRegularized(lambda1=0.0, lambda2=1e-2)], 0,
                     semi)
    assert res.W.shape == (1, 3, 5, 6)
    assert np.isfinite(res.gap).all()


def test_sweep_degenerate_single_cell(shuffles):
    """A 1x1 grid (the fit_eval path) still round-trips exactly."""
    cfg = MochaConfig(loss="hinge", rounds=8, record_every=8)
    reg = Clustered(lam=0.5, eta=0.4, k=2)
    train = shuffles[0][0]
    res = _run_sweep(stack_federations([train]), [reg], 0, cfg)
    ref = _run_mocha(train, reg, cfg)
    np.testing.assert_array_equal(res.W[0, 0], ref.W)
