"""The unified experiment surface: capability routing, bit-parity against
the core drivers, the spec->driver config mapping, the sequential grid
fallback, held-out evaluation, and Report provenance."""
import dataclasses

import numpy as np
import pytest

import repro.api as api
from repro.cohort import (CohortConfig, FaultConfig, Population,
                          PopulationSpec)
from repro.cohort.driver import _run_cohort
from repro.core import (BudgetConfig, MeanRegularized, MochaConfig,
                        Probabilistic, ShardedEngine, per_task_error)
from repro.core.evaluate import evaluate_cohort, holdout_client_ids
from repro.core.losses import get_loss
from repro.core.mocha import _run_mocha
from repro.core.sweep import _run_sweep
from repro.core.systems_model import SystemsConfig
from repro.data.synthetic import tiny_problem

REG = MeanRegularized(lambda1=0.5, lambda2=0.5)
LAMBDAS = (1e-3, 1e-2, 1e-1)
#: clock tight enough that the deadline caps BIND on the tiny test problems
#: (caps 14-36 vs max_steps 24 at passes=1.0, n=24 -- partially binding, so
#: semi_sync results genuinely differ from sync)
SEMI = SystemsConfig(network="3g", policy="semi_sync", clock_cycle_s=1e-5,
                     rate_lo=0.5, rate_hi=1.5)
POP_SPEC = PopulationSpec("api_pop", m=300, d=12, n_min=12, n_max=32,
                          clusters=3)


@pytest.fixture(scope="module")
def problem():
    return tiny_problem(m=5, n=24, d=6, seed=0)


@pytest.fixture(scope="module")
def shuffles():
    return [tiny_problem(m=5, n=24, d=6, seed=s) for s in range(3)]


def _grid_exp(shuffles, systems=None, exec_=None, regs=None):
    regs = regs or tuple(MeanRegularized(lambda1=0.0, lambda2=lam)
                         for lam in LAMBDAS)
    return api.Experiment(
        problem=api.Problem(train=[tr for tr, _ in shuffles]),
        method=api.Method(loss="hinge", regularizers=regs, rounds=8),
        systems=systems or api.Systems(),
        exec=exec_ or api.Exec(),
        eval=api.Eval(record_every=8,
                      holdout=[te for _, te in shuffles]))


# -- capability router: the golden (problem, engine, policy) table -----------

_SYNC = api.Systems()
_SEMI = api.Systems(config=SEMI)

#: (problem kind, engine, systems) -> (path, inner driver, fallback?)
GOLDEN_ROUTES = [
    ("silo", "local", _SYNC, "single", "scan", False),
    ("silo", "local", _SEMI, "single", "scan", False),
    ("silo", "pallas", _SYNC, "single", "loop", False),
    ("silo", "sharded", _SEMI, "single", "loop", False),
    ("shuffles", "local", _SYNC, "sweep", "vmap", False),
    ("shuffles", "local", _SEMI, "sweep", "vmap", False),
    ("shuffles", "pallas", _SYNC, "grid", "loop", True),
    ("shuffles", "sharded", _SYNC, "grid", "loop", True),
    ("shuffles", "sharded", _SEMI, "grid", "loop", True),
    ("population", "local", _SYNC, "cohort", "scan", False),
    ("population", "local", _SEMI, "cohort", "scan", False),
    ("population", "sharded", _SYNC, "cohort", "loop", False),
]


@pytest.mark.parametrize("kind,engine,systems,path,driver,falls_back",
                         GOLDEN_ROUTES)
def test_router_golden_table(problem, kind, engine, systems, path, driver,
                             falls_back):
    train, _ = problem
    if kind == "population":
        prob = api.Problem(population=Population(POP_SPEC, seed=0))
    elif kind == "shuffles":
        prob = api.Problem(train=[train, train])
    else:
        prob = api.Problem(train=train)
    exp = api.Experiment(problem=prob, method=api.Method(regularizers=(REG,)),
                         systems=systems, exec=api.Exec(engine=engine))
    plan = api.route(exp)
    assert (plan.path, plan.driver) == (path, driver)
    assert plan.engine == engine
    assert (plan.reason is not None) == falls_back


def test_router_single_reg_grid_is_sweep(problem):
    """A lambda grid over ONE federation is still a (vmappable) grid."""
    train, _ = problem
    exp = api.Experiment(
        problem=api.Problem(train=train),
        method=api.Method(regularizers=tuple(
            MeanRegularized(lambda1=0.0, lambda2=lam) for lam in LAMBDAS)))
    assert api.route(exp).path == "sweep"


def test_router_rejects_contradictions(problem):
    train, _ = problem
    with pytest.raises(ValueError, match="scanned driver"):
        api.route(api.Experiment(problem=api.Problem(train=train),
                                 exec=api.Exec(engine="pallas",
                                               driver="scan")))
    with pytest.raises(ValueError, match="grids over populations"):
        api.route(api.Experiment(
            problem=api.Problem(population=Population(POP_SPEC, seed=0)),
            method=api.Method(regularizers=(REG, Probabilistic()))))
    with pytest.raises(ValueError, match="exactly one of"):
        api.Problem()
    with pytest.raises(ValueError, match="at least one regularizer"):
        api.Method(regularizers=())


def test_router_rejects_cohort_owned_overrides(problem):
    """Per-run internals the cohort block loop owns (budget_fn, omega0,
    state0, mesh/comm_dtype, trace) must be rejected on population
    problems, never silently dropped."""
    pop_problem = api.Problem(population=Population(POP_SPEC, seed=0))
    with pytest.raises(ValueError, match="Method.budget_fn"):
        api.route(api.Experiment(
            problem=pop_problem,
            method=api.Method(regularizers=(REG,),
                              budget_fn=lambda k, n, h: n)))
    with pytest.raises(ValueError, match="Exec.mesh"):
        api.route(api.Experiment(
            problem=pop_problem,
            exec=api.Exec(engine="sharded", mesh=object())))
    with pytest.raises(ValueError, match="Systems.trace"):
        from repro.core.systems_model import SystemsTrace
        api.route(api.Experiment(
            problem=pop_problem,
            systems=api.Systems(trace=SystemsTrace(4, 8))))


def test_grid_fallback_rejects_mismatched_shuffles(problem):
    """The sequential fallback validates shuffle shapes up front (the
    batched path gets this from stack_federations) instead of crashing
    mid-grid."""
    a, _ = tiny_problem(m=4, n=12, d=5, seed=0)
    b, _ = tiny_problem(m=5, n=12, d=5, seed=1)
    exp = api.Experiment(
        problem=api.Problem(train=[a, b]),
        method=api.Method(regularizers=(REG,), rounds=2),
        exec=api.Exec(driver="loop"))   # forces the sequential grid path
    with pytest.raises(ValueError, match="must share tasks/features"):
        exp.run(seed=0)


# -- bit-parity: Experiment.run vs the core drivers -------------------------
#
# Each case runs the same description twice: through Experiment.run, and
# straight through the core driver with a hand-built MochaConfig /
# CohortConfig -- the spec->driver mapping (as_mocha_config /
# as_cohort_config) must not change a bit.

@pytest.mark.parametrize("engine", ["local", "pallas", "sharded"])
def test_experiment_matches_legacy_run_mocha(problem, engine):
    train, _ = problem
    cfg = MochaConfig(loss="hinge", rounds=10,
                      budget=BudgetConfig(passes=1.0, systems_lo=0.5,
                                          drop_prob=0.3),
                      record_every=4, seed=3, engine=engine)
    core = _run_mocha(train, REG, cfg)
    rep = api.Experiment(
        problem=api.Problem(train=train),
        method=api.Method(loss="hinge", regularizers=(REG,), rounds=10,
                          budget=cfg.budget),
        exec=api.Exec(engine=engine),
        eval=api.Eval(record_every=4)).run(seed=3)
    np.testing.assert_array_equal(core.W, rep.result.W)
    np.testing.assert_array_equal(np.asarray(core.state.alpha),
                                  np.asarray(rep.result.state.alpha))
    assert core.history == rep.history
    np.testing.assert_array_equal(core.round_budgets,
                                  rep.result.round_budgets)


def test_experiment_matches_legacy_run_mocha_semi_sync(problem):
    train, _ = problem
    cfg = MochaConfig(loss="hinge", rounds=8, record_every=2, seed=5,
                      systems=SEMI)
    core = _run_mocha(train, REG, cfg)
    rep = api.Experiment(
        problem=api.Problem(train=train),
        method=api.Method(loss="hinge", regularizers=(REG,), rounds=8),
        systems=api.Systems(config=SEMI),
        eval=api.Eval(record_every=2)).run(seed=5)
    assert core.history == rep.history


def test_experiment_matches_legacy_run_sweep(shuffles):
    cfg = MochaConfig(loss="hinge", rounds=8, record_every=8, seed=0)
    regs = [MeanRegularized(lambda1=0.0, lambda2=lam) for lam in LAMBDAS]
    trains = [tr for tr, _ in shuffles]
    core = _run_sweep(trains, regs, (3, 4, 5), cfg)
    rep = _grid_exp(shuffles).run(seed=(3, 4, 5))
    assert rep.provenance["path"] == "sweep"
    np.testing.assert_array_equal(core.W, rep.result.W)
    np.testing.assert_array_equal(core.gap, rep.result.gap)
    assert core.seeds == rep.result.seeds


def test_experiment_matches_cohort_driver():
    pop = Population(POP_SPEC, seed=0)
    reg = Probabilistic(lam=1e-2, sigma2=10.0)
    cfg = CohortConfig(rounds=5, cohort=16, clusters=3, dropout=0.2,
                       omega_update_every=2, record_every=2, seed=1,
                       inner=MochaConfig(budget=BudgetConfig(passes=1.0)))
    core = _run_cohort(pop, reg, cfg)
    rep = api.Experiment(
        problem=api.Problem(population=pop),
        method=api.Method(loss="hinge", regularizers=(reg,), rounds=5,
                          omega_update_every=2,
                          budget=BudgetConfig(passes=1.0)),
        systems=api.Systems(dropout=0.2),
        exec=api.Exec(cohort=16, clusters=3),
        eval=api.Eval(record_every=2)).run(seed=1)
    assert core.history == rep.history
    np.testing.assert_array_equal(core.centroids, rep.result.centroids)
    np.testing.assert_array_equal(core.omega_k, rep.result.omega_k)
    np.testing.assert_array_equal(core.assign, rep.result.assign)


def test_legacy_distributed_shim_parity(problem):
    train, _ = problem
    cfg = MochaConfig(loss="hinge", rounds=6, record_every=3, seed=2)
    core = _run_mocha(train, REG, cfg, engine=ShardedEngine())
    rep = api.Experiment(
        problem=api.Problem(train=train),
        method=api.Method(loss="hinge", regularizers=(REG,), rounds=6),
        exec=api.Exec(engine="sharded"),
        eval=api.Eval(record_every=3)).run(seed=2)
    np.testing.assert_array_equal(core.W, rep.result.W)
    assert core.history == rep.history


# -- the spec -> core-config mapping, field by field -------------------------

#: (spec, field, non-default value, extra spec fields the value needs, the
#: config-view attributes that must carry it).  One case per spec field that
#: as_mocha_config / as_cohort_config reads.
_CKPT = {"checkpoint_dir": "ckpt"}
_SPEC_WIRING = [
    ("method", "loss", "smooth_hinge", {},
     ("mocha.loss", "cohort.inner.loss")),
    ("method", "rounds", 7, {}, ("mocha.rounds", "cohort.rounds")),
    ("method", "omega_update_every", 3, {},
     ("mocha.omega_update_every", "cohort.omega_update_every")),
    ("method", "gamma", 0.5, {}, ("mocha.gamma", "cohort.inner.gamma")),
    ("method", "per_task_sigma", False, {},
     ("mocha.per_task_sigma", "cohort.inner.per_task_sigma")),
    ("method", "budget", BudgetConfig(passes=2.5, drop_prob=0.1), {},
     ("mocha.budget", "cohort.inner.budget")),
    ("systems", "network", "3g", {}, ("mocha.network", "cohort.network")),
    ("systems", "config", SEMI, {}, ("mocha.systems", "cohort.systems")),
    ("systems", "sampler", "weighted", {}, ("cohort.sampler",)),
    ("systems", "dropout", 0.2, {}, ("cohort.dropout",)),
    ("systems", "faults", FaultConfig(solve_fail_prob=0.1), {},
     ("cohort.faults",)),
    ("exec", "engine", "pallas", {},
     ("mocha.engine", "cohort.inner.engine")),
    ("exec", "driver", "loop", {}, ("mocha.driver", "cohort.inner.driver")),
    ("exec", "cohort", 16, {}, ("cohort.cohort",)),
    ("exec", "inner_rounds", 2, {}, ("cohort.inner_rounds",)),
    ("exec", "clusters", 5, {}, ("cohort.clusters",)),
    ("exec", "eta", 0.25, {}, ("cohort.eta",)),
    ("exec", "cache_clients", 128, {}, ("cohort.cache_clients",)),
    ("exec", "n_pad", 40, {}, ("cohort.n_pad",)),
    ("exec", "overlap", 2, {}, ("cohort.overlap",)),
    ("exec", "staleness", 1, {}, ("cohort.staleness",)),
    ("exec", "max_retries", 2, {}, ("cohort.max_retries",)),
    ("exec", "degrade", True, {}, ("cohort.degrade",)),
    ("exec", "checkpoint_every", 3, _CKPT, ("cohort.checkpoint_every",)),
    ("exec", "checkpoint_dir", "ckpt", {}, ("cohort.checkpoint_dir",)),
    ("exec", "resume", True, _CKPT, ("cohort.resume",)),
    ("exec", "telemetry", True, {}, ("cohort.telemetry",)),
    ("exec", "trace_dir", "traces", {}, ("cohort.trace_dir",)),
    ("eval", "record_every", 5, {},
     ("mocha.record_every", "cohort.record_every")),
]


@pytest.mark.parametrize(
    "spec,field,value,extra,carried", _SPEC_WIRING,
    ids=[f"{sp}.{f}" for sp, f, *_ in _SPEC_WIRING])
def test_spec_field_reaches_core_config(problem, spec, field, value, extra,
                                        carried):
    """Setting one spec field to a non-default value shows up in the core
    drivers' config views -- the one wiring point between the specs and
    the drivers."""
    train, _ = problem
    base = api.Experiment(problem=api.Problem(train=train))
    sub = getattr(base, spec)
    assert getattr(sub, field) != value           # the case is informative
    exp = dataclasses.replace(base, **{spec: dataclasses.replace(
        sub, **{field: value}, **extra)})
    views = {"mocha": api.as_mocha_config(exp, seed=0),
             "cohort": api.as_cohort_config(exp, seed=0)}
    for path in carried:
        view, *attrs = path.split(".")
        got = views[view]
        for attr in attrs:
            got = getattr(got, attr)
        assert got == value, f"{path} = {got!r}, want {value!r}"


# -- the sequential grid fallback (the old ValueError walls) -----------------

def test_semi_sync_lambda_grid_routes_to_sweep_with_parity(shuffles):
    """Capability upgrade: a semi_sync lambda grid now BATCHES -- the
    pre-sampled clock-cycle caps fold into the vmapped sweep's budget
    matrix, so the router no longer falls back -- and stays cell-for-cell
    identical to the sequential fallback (W/omega bitwise, final metrics
    at the established float32 noise level)."""
    exp = _grid_exp(shuffles, systems=api.Systems(config=SEMI))
    rep = exp.run(seed=0)
    assert rep.provenance["path"] == "sweep"
    assert rep.provenance["fallback_reason"] is None
    assert rep.result.W.shape == (3, 3, 5, 6)
    assert np.isfinite(rep.result.gap).all()
    # per-client held-out eval rode along: (R, S, m) error table + grid
    assert rep.evaluation.per_client["error"].shape == (3, 3, 5)
    assert rep.evaluation.grid.shape == (3, 3)
    assert 0.0 <= rep.evaluation.summary["best_mean_error"] <= 1.0
    # cell-for-cell parity vs the sequential fallback (forced via
    # driver='loop'), where every cell builds a fresh per-cell trace
    seq = _grid_exp(shuffles, systems=api.Systems(config=SEMI),
                    exec_=api.Exec(driver="loop")).run(seed=0)
    assert seq.provenance["path"] == "grid"
    np.testing.assert_array_equal(rep.result.W, seq.result.W)
    np.testing.assert_array_equal(rep.result.omega, seq.result.omega)
    np.testing.assert_allclose(rep.result.gap, seq.result.gap, atol=2e-6)
    # the caps actually BIND: the same grid under a sync clock differs
    sync = _grid_exp(shuffles).run(seed=0)
    assert not np.array_equal(rep.result.W, sync.result.W)


def test_grid_fallback_bit_matches_vmapped_sweep(shuffles):
    """Forcing the loop driver routes the same grid through the sequential
    fallback; scan/loop parity makes the results bit-identical to the
    vmapped path, cell for cell."""
    batched = _grid_exp(shuffles).run(seed=0)
    seq = _grid_exp(shuffles, exec_=api.Exec(driver="loop")).run(seed=0)
    assert batched.provenance["path"] == "sweep"
    assert seq.provenance["path"] == "grid"
    assert "loop" in seq.provenance["fallback_reason"]
    np.testing.assert_array_equal(batched.result.W, seq.result.W)
    np.testing.assert_array_equal(batched.evaluation.grid,
                                  seq.evaluation.grid)


def test_grid_fallback_sharded_engine(shuffles):
    """A lambda grid on the sharded engine -- previously a ValueError --
    runs sequentially through the shard_map runtime."""
    regs = tuple(MeanRegularized(lambda1=0.0, lambda2=lam)
                 for lam in LAMBDAS[:2])
    seq = _grid_exp(shuffles[:2], exec_=api.Exec(engine="sharded"),
                    regs=regs).run(seed=0)
    assert seq.provenance["path"] == "grid"
    assert "sharded" in seq.provenance["fallback_reason"]
    # bit-identical to the local vmapped path (cross-engine parity holds
    # cell-wise through the fallback)
    batched = _grid_exp(shuffles[:2], regs=regs).run(seed=0)
    np.testing.assert_array_equal(batched.result.W, seq.result.W)


# -- evaluation harness ------------------------------------------------------

def test_evaluate_run_matches_per_task_error(problem):
    train, test = problem
    rep = api.Experiment(
        problem=api.Problem(train=train),
        method=api.Method(regularizers=(REG,), rounds=10),
        eval=api.Eval(record_every=10, holdout=test)).run(seed=0)
    ref = np.asarray(per_task_error(train, rep.result.W, test.X, test.y,
                                    test.mask))
    np.testing.assert_allclose(rep.evaluation.per_client["error"], ref,
                               atol=1e-7)
    np.testing.assert_allclose(rep.evaluation.summary["mean_error"],
                               ref.mean(), atol=1e-7)
    assert rep.evaluation.per_client["n_holdout"].sum() > 0


def test_evaluate_grid_matches_sweep_errors(shuffles):
    from repro.core import stack_federations, sweep_errors
    rep = _grid_exp(shuffles).run(seed=0)
    tests = stack_federations([te for _, te in shuffles])
    ref = sweep_errors(rep.result, tests)
    np.testing.assert_allclose(rep.evaluation.grid, ref, atol=1e-6)


def test_evaluate_cohort_prefers_unseen_clients():
    pop = Population(POP_SPEC, seed=0)
    participation = np.zeros(POP_SPEC.m, np.int64)
    participation[:250] = 3            # 50 never-trained clients remain
    ids = holdout_client_ids(POP_SPEC.m, 20, seed=7,
                             participation=participation)
    assert ids.size == 20
    assert (ids >= 250).all()
    # deterministic
    np.testing.assert_array_equal(
        ids, holdout_client_ids(POP_SPEC.m, 20, 7, participation))
    reg = Probabilistic(lam=1e-2, sigma2=10.0)
    rep = api.Experiment(
        problem=api.Problem(population=pop),
        method=api.Method(regularizers=(reg,), rounds=4),
        exec=api.Exec(cohort=16),
        eval=api.Eval(record_every=4, holdout_clients=25)).run(seed=3)
    ev = rep.evaluation
    assert ev.per_client["client"].shape == (25,)
    assert set(ev.per_cluster) >= {"cluster", "n_clients", "mean_error"}
    assert ev.per_cluster["n_clients"].sum() == 25
    assert 0.0 <= ev.summary["mean_error"] <= 1.0
    # reproducible end to end
    rep2 = api.Experiment(
        problem=api.Problem(population=pop),
        method=api.Method(regularizers=(reg,), rounds=4),
        exec=api.Exec(cohort=16),
        eval=api.Eval(record_every=4, holdout_clients=25)).run(seed=3)
    np.testing.assert_array_equal(ev.per_client["error"],
                                  rep2.evaluation.per_client["error"])


def test_evaluate_rejects_unknown_metric(problem):
    train, test = problem
    with pytest.raises(ValueError, match="unknown eval metrics"):
        api.Experiment(problem=api.Problem(train=train),
                       method=api.Method(regularizers=(REG,), rounds=2),
                       eval=api.Eval(holdout=test,
                                     metrics=("error", "auc"))).run(0)


# -- provenance --------------------------------------------------------------

def test_provenance_schema_and_gram_resolution(problem):
    from repro.api.report import PROVENANCE_KEYS
    train, _ = problem
    exp = api.Experiment(problem=api.Problem(train=train),
                         method=api.Method(regularizers=(REG,), rounds=2),
                         eval=api.Eval(record_every=2))
    rep = exp.run(0)
    assert set(rep.provenance) == set(PROVENANCE_KEYS)
    assert rep.provenance["gram_mode"] == "gram"      # d=6 <= crossover
    assert rep.provenance["fallback_reason"] is None
    # the config hash is stable across runs and moves when the spec moves
    assert rep.provenance["config_hash"] == exp.run(0).provenance[
        "config_hash"]
    moved = dataclasses.replace(exp, method=api.Method(
        regularizers=(REG,), rounds=3))
    assert moved.run(0).provenance["config_hash"] != rep.provenance[
        "config_hash"]
    # the mode follows the problem's d on each side of the crossover
    for d, mode in ((128, "gram"), (129, "carry")):
        wide, _ = tiny_problem(m=2, n=4, d=d, seed=0)
        prov = dataclasses.replace(
            exp, problem=api.Problem(train=wide)).run(0).provenance
        assert prov["gram_mode"] == mode, (d, prov["gram_mode"])


def test_base_provenance_schema():
    from repro.api.report import PROVENANCE_KEYS
    base = api.base_provenance()
    assert set(base) == set(PROVENANCE_KEYS)
    assert base["path"] is None and base["gram_mode"] is None
