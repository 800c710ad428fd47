"""Mini-batch baselines: sanity + the Fig-1 qualitative ordering."""
import numpy as np
import pytest

from repro.core import (BudgetConfig, MeanRegularized, MiniBatchConfig,
                        MochaConfig, run_mb_sdca, run_mb_sgd)
from repro.core.mocha import _run_mocha
from repro.data.synthetic import tiny_problem

REG = MeanRegularized(0.5, 0.5)


@pytest.fixture(scope="module")
def problem():
    return tiny_problem(m=5, n=30, d=8, seed=0)


def test_mb_sgd_decreases_primal(problem):
    train, _ = problem
    res = run_mb_sgd(train, REG, MiniBatchConfig(
        loss="hinge", rounds=200, batch=8, lr=0.05, record_every=10))
    p = np.asarray(res.history["primal"])
    assert p[-1] < 0.7 * p[0]


def test_mb_sdca_decreases_primal_and_gap(problem):
    train, _ = problem
    res = run_mb_sdca(train, REG, MiniBatchConfig(
        loss="hinge", rounds=300, batch=8, beta=4.0, record_every=20))
    gaps = np.asarray(res.history["gap"])
    assert gaps[-1] < 0.1 * gaps[0]
    assert gaps[-1] >= -1e-4  # weak duality held throughout


def test_mocha_beats_minibatch_in_rounds(problem):
    """Per communication round MOCHA makes far more progress (the Fig-1
    mechanism: mini-batch methods waste the communication budget)."""
    train, _ = problem
    rounds = 60
    mocha = _run_mocha(train, REG, MochaConfig(
        loss="hinge", rounds=rounds, budget=BudgetConfig(passes=1.0),
        record_every=rounds - 1))
    sgd = run_mb_sgd(train, REG, MiniBatchConfig(
        loss="hinge", rounds=rounds, batch=8, lr=0.05,
        record_every=rounds - 1))
    sdca = run_mb_sdca(train, REG, MiniBatchConfig(
        loss="hinge", rounds=rounds, batch=8, beta=4.0,
        record_every=rounds - 1))
    assert mocha.final("primal") < sgd.final("primal")
    assert mocha.final("primal") < sdca.final("primal")
