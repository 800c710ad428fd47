"""Shared benchmark utilities: method runners, reduced Table-1 protocol,
time-to-epsilon extraction for the Fig-1/2 style comparisons."""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence

import jax.numpy as jnp
import numpy as np

import repro.api as api
from repro.core import (BudgetConfig, MeanRegularized, MiniBatchConfig,
                        Probabilistic, per_task_error, run_mb_sdca,
                        run_mb_sgd, stack_federations)
from repro.core import systems_model
from repro.data import synthetic as syn
# the sanctioned (result, elapsed) wrapper, re-exported for the suite
# modules -- elapsed is in MICROSECONDS (suite modules store it into *_us
# BENCH columns verbatim); benchmarks read the wall clock only through
# repro.utils.timing (reprolint rule D101)
from repro.utils.timing import timed  # noqa: F401

# reduced protocol vs the paper (documented in EXPERIMENTS.md):
#   3 shuffles instead of 10; lambda grid {1e-3, 1e-2, 0.1}; direct test-split
#   evaluation instead of 5-fold CV (CPU budget); same model classes.
# --full restores the paper's protocol (10 shuffles, wider lambda grid) --
# feasible because model_comparison dispatches the whole grid through the
# vmapped sweep harness (core/sweep.py) instead of sequential core-driver runs.
SHUFFLES = 3
LAMBDAS = (1e-3, 1e-2, 1e-1)
SHUFFLES_FULL = 10
LAMBDAS_FULL = (1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2, 1e-1, 3e-1, 1.0)


def dataset_specs(skewed: bool = False):
    if skewed:
        return [syn.HA_SKEW, syn.GG_SKEW, syn.VS_SKEW]
    return [syn.HUMAN_ACTIVITY, syn.GOOGLE_GLASS, syn.VEHICLE_SENSOR]


def _error(train, test, W) -> float:
    return float(jnp.mean(per_task_error(train, jnp.asarray(W), test.X,
                                          test.y, test.mask)))


def _kind_setup(kind: str, lam: float, rounds: int) -> api.Method:
    """The ``api.Method`` of one Table-1/4 model kind."""
    budget = BudgetConfig(passes=1.0)
    if kind in ("global", "local"):
        return api.Method(loss="hinge",
                          regularizers=MeanRegularized(lambda1=0.0,
                                                       lambda2=lam),
                          rounds=rounds, budget=budget)
    if kind == "mtl":
        return api.Method(loss="hinge",
                          regularizers=Probabilistic(lam=lam, sigma2=10.0),
                          rounds=rounds,
                          omega_update_every=max(5, rounds // 5),
                          budget=budget)
    raise ValueError(kind)


def _kind_split(kind: str, train, test):
    if kind == "global":
        return syn.make_global_problem(train), syn.make_global_problem(test)
    return train, test


def run_single(train, method: api.Method, *, seed: int = 0,
               record_every: int = 1, systems: api.Systems = api.Systems(),
               driver: str = "auto") -> api.Report:
    """One core-driver run of ``method`` through the experiment surface."""
    return api.Experiment(
        problem=api.Problem(train=train), method=method, systems=systems,
        exec=api.Exec(driver=driver),
        eval=api.Eval(record_every=record_every)).run(seed)


def _grid_experiment(train_s, method: api.Method,
                     test_s) -> api.Experiment:
    """(shuffle x lambda) grid + held-out eval as ONE experiment spec."""
    return api.Experiment(
        problem=api.Problem(train=train_s), method=method,
        eval=api.Eval(record_every=method.rounds, holdout=test_s))


def fit_eval(kind: str, train, test, lam: float, rounds: int) -> float:
    """kind in {global, local, mtl}; returns average test error.

    Single-cell convenience wrapper over the experiment surface; grids
    should call ``model_comparison`` (one batched dispatch per kind).
    """
    method = _kind_setup(kind, lam, rounds)
    train, test = _kind_split(kind, train, test)
    report = _grid_experiment(stack_federations([train]), method,
                              stack_federations([test])).run(0)
    return float(report.evaluation.grid[0, 0])


def fit_eval_sequential(kind: str, train, test, lam: float,
                        rounds: int) -> float:
    """The pre-sweep path: one Python-loop driver run per grid cell.

    Kept as the wall-clock baseline the sweep harness is measured against
    (BENCH_table1.json) and as an independent cross-check of sweep results.
    """
    method = _kind_setup(kind, lam, rounds)
    train, test = _kind_split(kind, train, test)
    res = run_single(train, method, driver="loop",
                     record_every=rounds).result
    return _error(train, test, res.W)


def model_comparison(spec, rounds: int = 60, shuffles: int = SHUFFLES,
                     lambdas: Sequence[float] = LAMBDAS,
                     ) -> Dict[str, Dict[str, float]]:
    """Table-1/4 protocol: best-lambda test error per model kind.

    One experiment per model kind covers the whole (shuffle x lambda) grid
    (the router batches it through the vmapped sweep); per shuffle the best
    lambda is chosen by held-out error from the Report's eval table, then
    mean/stderr aggregate over shuffles (EXPERIMENTS.md).  The returned dict
    carries the last Report's provenance under ``"_provenance"`` so suite
    rows can record the routed driver / residual mode.
    """
    feds = [syn.make_federation(spec, seed=seed) for seed in range(shuffles)]
    out: Dict[str, Dict[str, float]] = {}
    provenance: Dict = {}
    for kind in ("global", "local", "mtl"):
        splits = [_kind_split(kind, tr, te) for tr, te in feds]
        train_s = stack_federations([tr for tr, _ in splits])
        test_s = stack_federations([te for _, te in splits])
        regs = tuple(_kind_setup(kind, lam, rounds).regularizers[0]
                     for lam in lambdas)
        method = dataclasses.replace(_kind_setup(kind, lambdas[0], rounds),
                                     regularizers=regs)
        report = _grid_experiment(train_s, method, test_s).run(0)
        errs = report.evaluation.grid           # (lambda, shuffle)
        best = errs.min(axis=0)                 # best lambda per shuffle
        out[kind] = {"mean": float(best.mean()),
                     "stderr": float(best.std() / np.sqrt(len(best)))}
        provenance = report.provenance
    out["_provenance"] = provenance
    return out


def model_comparison_sequential(spec, rounds: int = 60,
                                shuffles: int = SHUFFLES,
                                lambdas: Sequence[float] = LAMBDAS,
                                ) -> Dict[str, Dict[str, float]]:
    """The pre-sweep Table-1/4 path: one core-driver run per grid cell."""
    out: Dict[str, List[float]] = {"global": [], "local": [], "mtl": []}
    for seed in range(shuffles):
        train, test = syn.make_federation(spec, seed=seed)
        for kind in out:
            best = min(fit_eval_sequential(kind, train, test, lam, rounds)
                       for lam in lambdas)
            out[kind].append(best)
    return {k: {"mean": float(np.mean(v)),
                "stderr": float(np.std(v) / np.sqrt(len(v)))}
            for k, v in out.items()}


def primal_star(train, reg, rounds: int = 400) -> float:
    """High-accuracy optimum for suboptimality curves."""
    res = run_single(train, api.Method(regularizers=reg, rounds=rounds,
                                       budget=BudgetConfig(passes=3.0)),
                     record_every=rounds)
    return res.final("primal")


def time_to_epsilon(history: Dict[str, List[float]], p_star: float,
                    eps_rel: float) -> float:
    """Simulated seconds until primal suboptimality <= eps_rel * |p*|."""
    target = p_star + eps_rel * max(abs(p_star), 1.0)
    for p, t in zip(history["primal"], history["time"]):
        if p <= target:
            return t
    return float("inf")


def retime_trace(primal: List[float], round_steps, d: int, network: str,
                 policy: str = "sync", clock_cycle_s: float = 0.0,
                 step_flops=None, systems=None) -> Dict[str, List[float]]:
    """Replay a recorded trajectory through a fresh event-driven SystemsTrace.

    ``round_steps``: (rounds, m) per-node executed steps (``RunResult.
    round_budgets``) or a per-round scalar list (treated as one synchronous
    worker, the mini-batch case). Trajectories are network-independent, so
    one recorded run can be timed under every network x policy combination.
    Note the *statistics* of the trajectory are whatever the recorded run
    used; ``semi_sync`` retiming is consistent when the recorded budgets
    already fit the deadline (the MOCHA deadline variants below).
    """
    steps = np.asarray(round_steps)
    if steps.ndim == 1:
        steps = steps[:, None]
    cfg = systems or systems_model.SystemsConfig(
        network=network, policy=policy, clock_cycle_s=clock_cycle_s)
    trace = systems_model.SystemsTrace(
        steps.shape[1], d, cfg,
        step_flops=step_flops or systems_model.SDCA_STEP_FLOPS)
    for row in steps:
        trace.advance(row)
    times = trace.times()
    # python floats: downstream comparisons stay JSON-serializable bools
    return {"primal": primal, "time": [float(t) for t in times[:len(primal)]]}


def simulate_cocoa_adaptive(train, reg, rounds: int, theta: float = 0.1,
                            recal_every: int = 5, max_passes: float = 16.0):
    """CoCoA with its actual semantics: every node reaches a FIXED theta each
    round.  Per-node step budgets are re-calibrated every ``recal_every``
    rounds by measuring theta after one local pass at the CURRENT iterate
    (Definition 1) and sizing passes via the SDCA geometric rate -- this
    captures the paper's observation that 'iterations tend to increase as
    the method runs' and that hard/large subproblems straggle the round.
    """
    import jax

    from repro.core import (get_loss, init_state, primal_objective,
                            primal_weights, sigma_prime)
    from repro.core.subproblem import batched_local_sdca, measure_theta
    loss = get_loss("hinge")
    omega = reg.init_omega(train.m)
    abar = reg.coupling(omega)
    K = reg.K(omega)
    sig = sigma_prime(K)                      # CoCoA: single scalar sigma'
    q_t = sig * jnp.diagonal(K) / 2.0
    n_t = np.asarray(train.n_t).astype(int)
    n_max = int(train.n_max)

    state = init_state(train)
    alpha, v = state.alpha, state.v
    key = jax.random.PRNGKey(0)
    budgets = n_t.copy()
    primal_hist, steps_hist = [], []

    for h in range(rounds):
        W = primal_weights(K, v)
        if h % recal_every == 0:
            rates = []
            for t in range(train.m):
                kcal = jax.random.PRNGKey(1000 + 31 * h + t)
                from repro.core.subproblem import local_sdca
                d_, _ = local_sdca(loss, train.X[t], train.y[t],
                                   train.mask[t], alpha[t], W[t], q_t[t],
                                   jnp.asarray(int(n_t[t])), kcal,
                                   int(n_t[t]))
                th = float(measure_theta(
                    loss, train.X[t], train.y[t], train.mask[t], alpha[t],
                    W[t], q_t[t], d_, jax.random.PRNGKey(7),
                    exact_passes=16))
                rates.append(max(-np.log(np.clip(th, 1e-6, 1.0)), 0.02))
            passes = np.clip(np.log(1.0 / theta) / np.asarray(rates),
                             0.5, max_passes)
            budgets = np.maximum((passes * n_t).astype(int), 1)
        key, k = jax.random.split(key)
        keys = jax.random.split(k, train.m)
        max_steps = int(budgets.max())
        dalpha, u = batched_local_sdca(
            loss, train.X, train.y, train.mask, alpha, W, q_t,
            jnp.asarray(budgets, jnp.int32), keys, max_steps)
        alpha, v = alpha + dalpha, v + u
        W = primal_weights(K, v)
        primal_hist.append(float(primal_objective(train, loss, abar, W)))
        steps_hist.append(int(budgets.max()))
    return primal_hist, steps_hist


def calibrate_cocoa_budgets(train, reg, theta_target: float = 0.1,
                            max_passes: float = 10.0):
    """CoCoA runs every node to a FIXED theta each round (paper Sec. 3.4).

    We calibrate per-node SDCA rates once: run one full local pass from the
    cold start, measure the achieved theta_t (Definition 1), and size the
    per-node budget as passes_t = log(1/theta_target) / -log(theta_t^1pass).
    Hard/large subproblems need many more steps -> the synchronous round
    waits on them (the straggler effect MOCHA's clock cycle avoids).
    """
    import jax

    from repro.core import (get_loss, init_state, primal_weights,
                            sigma_prime)
    from repro.core.subproblem import local_sdca, measure_theta
    loss = get_loss("hinge")
    omega = reg.init_omega(train.m)
    K = reg.K(omega)
    sig = sigma_prime(K)
    q_t = sig * jnp.diagonal(K) / 2.0
    state = init_state(train)
    W = primal_weights(K, state.v)
    n_t = np.asarray(train.n_t).astype(int)
    rates = []
    for t in range(train.m):
        key = jax.random.PRNGKey(100 + t)
        budget = jnp.asarray(int(n_t[t]))
        d_, _ = local_sdca(loss, train.X[t], train.y[t], train.mask[t],
                           state.alpha[t], W[t], q_t[t], budget, key,
                           int(n_t[t]))
        th = float(measure_theta(loss, train.X[t], train.y[t], train.mask[t],
                                 state.alpha[t], W[t], q_t[t], d_,
                                 jax.random.PRNGKey(7), exact_passes=32))
        rates.append(max(-np.log(max(th, 1e-6)), 0.05))
    passes = np.clip(np.log(1.0 / theta_target) / np.asarray(rates),
                     0.25, max_passes)
    return np.ceil(passes * n_t).astype(int)


MOCHA_DEADLINES = (1.0, 2.0, 4.0, 8.0)   # clock cycle, x mean(n_t) steps
COCOA_THETAS = (0.05, 0.2, 0.5)          # fixed approximation targets


def run_method_trajectories(train, reg, rounds: int, seed: int = 0,
                            systems_lo: float | None = None) -> Dict:
    """Run every tuned variant of every method ONCE (trajectories are
    network-independent); ``best_times_for_network`` then picks each
    method's best configuration per network -- the paper's protocol ("we
    tune all compared methods for best performance").

    MOCHA: clock-cycle deadline = c * mean(n_t) steps (nodes never exceed
    what fits; systems heterogeneity shrinks individual budgets). CoCoA:
    fixed-theta semantics via per-round calibrated budgets -- the
    synchronous round waits for the slowest node. Mini-batch: one batch per
    communication round.
    """
    import jax
    n_t = np.asarray(train.n_t)
    trajs: Dict[str, list] = {"mocha": [], "cocoa": [], "mb_sgd": [],
                              "mb_sdca": []}
    trajs["_provenance"] = {}

    for c in MOCHA_DEADLINES:
        cap = int(c * n_t.mean())

        def budget_fn(key, n_t_arr, h, cap=cap):
            caps = jnp.minimum(jnp.full_like(n_t_arr, cap,
                                             dtype=jnp.int32),
                               (16 * n_t_arr).astype(jnp.int32))
            if systems_lo is not None:
                frac = jax.random.uniform(key, (train.m,),
                                          minval=systems_lo, maxval=1.0)
                caps = jnp.maximum((caps * frac).astype(jnp.int32), 1)
            return caps

        report = run_single(
            train, api.Method(regularizers=reg, rounds=rounds * 3,
                              budget=BudgetConfig(passes=16.0),
                              budget_fn=budget_fn), seed=seed)
        trajs["_provenance"] = report.provenance
        res = report.result
        # clock cycle consistent with this variant's deadline: budgets were
        # drawn to fit cap steps, so semi_sync retiming never truncates
        cycle_s = (cap * systems_model.SDCA_STEP_FLOPS(train.d)
                   / systems_model.CLOCK_FLOPS)
        trajs["mocha"].append({
            "primal": res.history["primal"],
            "steps": res.round_budgets,
            "step_flops": systems_model.SDCA_STEP_FLOPS,
            "clock_cycle_s": cycle_s})

    for theta in COCOA_THETAS:
        p, s = simulate_cocoa_adaptive(train, reg, rounds, theta=theta)
        trajs["cocoa"].append({
            "primal": p, "steps": s,
            "step_flops": systems_model.SDCA_STEP_FLOPS,
            "clock_cycle_s": None})

    mb = MiniBatchConfig(loss="hinge", rounds=rounds * 3, batch=16, lr=0.05,
                         beta=8.0, seed=seed, record_every=1)
    sgd = run_mb_sgd(train, reg, mb)
    sdca = run_mb_sdca(train, reg, mb)
    batch_steps = [mb.batch] * (rounds * 3)
    trajs["mb_sgd"].append({
        "primal": sgd.history["primal"], "steps": batch_steps,
        "step_flops": systems_model.SGD_STEP_FLOPS, "clock_cycle_s": None})
    trajs["mb_sdca"].append({
        "primal": sdca.history["primal"], "steps": batch_steps,
        "step_flops": systems_model.SDCA_STEP_FLOPS, "clock_cycle_s": None})
    return trajs


def best_times_for_network(trajs: Dict, d: int, network: str, p_star: float,
                           eps_rel: float,
                           policy: str = "sync") -> Dict[str, float]:
    """Per method: best tuned configuration's time-to-epsilon, timed through
    a fresh SystemsTrace per variant.

    ``policy='semi_sync'`` applies MOCHA's clock cycle to the variants that
    define one (``clock_cycle_s``); methods without a deadline semantics
    (CoCoA fixed-theta, mini-batch) always pay the synchronous straggler --
    that asymmetry IS the paper's Fig-1/2 comparison.
    """
    out = {}
    for name, variants in trajs.items():
        if name == "_provenance":
            continue
        best = float("inf")
        for v in variants:
            use_semi = policy == "semi_sync" and v["clock_cycle_s"] is not None
            hist = retime_trace(
                v["primal"], v["steps"], d, network,
                policy="semi_sync" if use_semi else "sync",
                clock_cycle_s=v["clock_cycle_s"] if use_semi else 0.0,
                step_flops=v["step_flops"])
            best = min(best, time_to_epsilon(hist, p_star, eps_rel))
        out[name] = best
    return out


