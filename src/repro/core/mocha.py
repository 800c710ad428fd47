"""MOCHA driver (Algorithm 1) plus the CoCoA special case.

Outer loop alternates:
  * federated W-update rounds: every node solves its data-local quadratic
    subproblem approximately (per-node step budgets = theta_t^h), ships
    Delta v_t = X_t^T Delta alpha_t, server reduces and recomputes W(alpha);
  * a central Omega update (Appendix B.3), which needs only W, never the data.

The round itself executes on a pluggable ``RoundEngine`` (vmapped jnp, the
Pallas kernel, or the shard_map runtime -- see repro.core.engine and
DESIGN.md); this single driver owns rounds, Omega refreshes, budget control,
metric recording, and the event-driven simulated federated wall-clock
(``SystemsTrace``, eq. 30).  Under the ``semi_sync`` clock-cycle policy the
trace caps each node's per-round budget to what fits the deadline -- the
paper's theta_t^h controller.

Two drivers execute the same W-round loop (DESIGN.md section 6):

  * the **loop driver** steps rounds from Python, one engine dispatch plus
    one host sync per round -- required by engines with host-side state
    (``pallas`` caches, ``sharded`` pad caches);
  * the **scanned driver** (engines with ``supports_scan``) pre-samples the
    whole (rounds, m) budget matrix -- budgets and semi_sync deadline caps
    are round-indexed, never state-dependent -- runs the W-round loop inside
    ``lax.scan`` with metrics computed in-scan, and does a single host
    transfer at the end; the SystemsTrace then retimes the executed budget
    matrix, which is equivalent by construction (DESIGN.md section 4).

Both are bit-identical on a fixed seed
(tests/test_runtime.py::test_scan_loop_driver_parity).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import dual as dual_mod
from repro.core import systems_model
from repro.core.dual import DualState, FederatedData
from repro.core.engine import RoundEngine, get_engine
from repro.core.losses import get_loss
from repro.core.regularizers import Regularizer, sigma_prime
from repro.core.systems_model import SystemsConfig, SystemsTrace
from repro.core.theta import (BudgetConfig, presample_budgets, round_budgets,
                              round_key_schedule, validate_assumption2)

Array = jax.Array

#: every engine emits exactly these history keys (tested for parity); every
#: column follows the ``record_every`` cadence, so histories are rectangular
HISTORY_KEYS = ("round", "dual", "primal", "gap", "time", "round_max_steps")

DRIVERS = ("auto", "scan", "loop")


@dataclasses.dataclass(frozen=True)
class MochaConfig:
    loss: str = "hinge"
    rounds: int = 100                  # total federated W rounds
    omega_update_every: int = 0        # 0 = fixed Omega; k = update every k rounds
    gamma: float = 1.0                 # aggregation parameter (Remark 3: 1 is best)
    per_task_sigma: bool = True        # Remark 5 per-task sigma'_t
    budget: BudgetConfig = dataclasses.field(default_factory=BudgetConfig)
    engine: str = "local"              # round executor: local | pallas | sharded
    network: str = "lte"
    systems: Optional[SystemsConfig] = None  # full systems model; overrides network
    seed: int = 0
    record_every: int = 1
    driver: str = "auto"               # auto | scan | loop (DESIGN.md section 6)
    #: per-run override of the SDCA residual-mode crossover (DESIGN.md
    #: section 3a): d <= gram_max_d selects gram mode.  None defers to the
    #: process default (``REPRO_GRAM_MAX_D`` env var, else the CPU-measured
    #: constant in core/subproblem.py).  Forcing carry below the default
    #: crossover leaves the cross-engine bit-parity contract.
    gram_max_d: Optional[int] = None


@dataclasses.dataclass
class RunResult:
    W: np.ndarray            # (m, d) final per-task models
    omega: np.ndarray        # (m, m)
    state: DualState
    history: Dict[str, List[float]]
    trace: Optional[SystemsTrace] = None      # full per-node event log
    round_budgets: Optional[np.ndarray] = None  # (rounds, m) executed steps

    def final(self, key: str) -> float:
        return self.history[key][-1]


def _metrics_impl(loss, data, state, abar, K):
    dual_val = dual_mod.dual_objective(data, loss, K, state.alpha, state.v)
    W = dual_mod.primal_weights(K, state.v)
    primal_val = dual_mod.primal_objective(data, loss, abar, W)
    return dual_val, primal_val, primal_val + dual_val


_metrics = partial(jax.jit, static_argnums=(0,))(_metrics_impl)


def _record_rounds(rounds: int, record_every: int) -> np.ndarray:
    """(rounds,) bool mask of history-record rounds.

    Every ``record_every``-th round plus ALWAYS the final round, so the
    history is never missing its last row -- including the ``rounds == 1``
    and ``record_every > rounds`` degenerate cadences (regression-tested in
    tests/test_mocha.py::test_history_degenerate_cadences).  Invalid
    cadences fail loudly here instead of as numpy slice errors (or, for
    ``rounds < 1``, a silent empty history) deep in a driver.
    """
    if rounds < 1:
        raise ValueError(f"need rounds >= 1, got {rounds}")
    if record_every < 1:
        raise ValueError(f"need record_every >= 1, got {record_every}")
    rec = np.zeros(rounds, bool)
    rec[::record_every] = True
    rec[-1] = True
    return rec


def _coupling_terms(reg: Regularizer, omega: Array, gamma: float,
                    per_task_sigma: bool, m: int):
    abar = reg.coupling(omega)
    K = jnp.linalg.inv(abar)
    sig = sigma_prime(K, gamma, per_task=per_task_sigma)
    q_t = sig * jnp.diagonal(K) / 2.0 * jnp.ones((m,))
    return abar, K, q_t


#: the set-up's coupling terms as one compiled program (every run, and every
#: cohort block, pays it); ``core/sweep.py`` traces ``_coupling_terms`` itself
_coupling = partial(jax.jit, static_argnums=(0, 2, 3, 4))(_coupling_terms)


@partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _omega_step(reg, loss, gamma, per_task_sigma, data, state, K, omega):
    """One Omega refresh as one compiled program, shared by both drivers.

    W(alpha) under the old K, the central ``update_omega``, the new coupling
    terms, and the (dual, primal, gap) row of the refresh round under the
    POST-update K.  Dispatched eagerly, its ~55 ops (``eigh``, ``inv``, ...)
    would each be a launch of their own, with the device idle in between;
    compiled, the step is one.  Returns ``(omega, abar, K, q_t, row)``.
    """
    W = dual_mod.primal_weights(K, state.v)
    omega = reg.update_omega(W, omega)
    abar, K, q_t = _coupling_terms(reg, omega, gamma, per_task_sigma,
                                   omega.shape[0])
    row = jnp.stack(_metrics_impl(loss, data, state, abar, K))
    return omega, abar, K, q_t, row


def run_mocha(data: FederatedData, reg: Regularizer, cfg: MochaConfig,
              omega0: Optional[Array] = None,
              budget_fn: Optional[Callable[[Array, Array, int], Array]] = None,
              engine: Optional[RoundEngine] = None,
              trace: Optional[SystemsTrace] = None,
              state0: Optional[DualState] = None,
              ) -> RunResult:
    """Deprecated shim: construct a ``repro.api.Experiment`` instead.

    Kept for back-compat (bit-parity-tested against ``Experiment.run`` in
    tests/test_api.py); the override kwargs map onto the spec fields --
    ``omega0``/``budget_fn`` -> ``Method``, ``trace`` -> ``Systems``,
    ``engine``/``state0`` -> ``Exec``.
    """
    from repro.api.compat import experiment_from_mocha, warn_legacy
    warn_legacy("run_mocha()",
                "Problem(train=...), Method(...), Exec(engine=...)")
    exp = experiment_from_mocha(data, reg, cfg, omega0=omega0,
                                budget_fn=budget_fn, engine=engine,
                                trace=trace, state0=state0)
    return exp.run(cfg.seed).result


def _run_mocha(data: FederatedData, reg: Regularizer, cfg: MochaConfig,
               omega0: Optional[Array] = None,
               budget_fn: Optional[Callable[[Array, Array, int],
                                            Array]] = None,
               engine: Optional[RoundEngine] = None,
               trace: Optional[SystemsTrace] = None,
               state0: Optional[DualState] = None,
               telemetry: Optional["obs.Telemetry"] = None,
               ) -> RunResult:
    """Run Algorithm 1 on the configured round engine (the core driver).

    This is the internal single-run implementation every execution path of
    ``repro.api`` bottoms out in; user code enters through
    ``repro.api.Experiment`` (or the deprecated ``run_mocha`` shim above).

    ``budget_fn(key, n_t, round) -> (m,) int budgets`` overrides the
    BudgetConfig sampler (used by benchmark harnesses).  ``engine`` overrides
    ``cfg.engine`` (accepts a name, class, or configured instance);
    ``trace`` supplies a pre-built SystemsTrace (otherwise one is derived
    from ``cfg.systems`` / ``cfg.network``).  ``state0`` warm-starts the dual
    iterate (cross-device cohort blocks resume cached client state); the
    caller must keep ``v = X alpha`` consistent -- ``dual.compute_v``
    reconstructs it.

    ``cfg.driver`` selects the execution strategy: ``auto`` uses the
    device-resident scanned driver whenever the engine supports it
    (``RoundEngine.supports_scan``) and falls back to the Python round loop
    otherwise; ``scan`` / ``loop`` force one path.  The two drivers are
    bit-identical on a fixed seed.

    ``telemetry`` is an optional ``repro.obs.Telemetry`` (cohort blocks pass
    their solve-worker view; the single path passes the run's main view):
    the whole run gets a ``mocha.run`` span holding ``mocha.setup`` (tagged
    with the SDCA loop's plan: ``residual_mode``, ``chunk``, ``max_steps``,
    ``n_chunks``) and one ``mocha.omega_step`` per Omega step (one launch
    of ``_omega_step``; ``compile`` is true on the run's first); the scanned
    driver additionally records its presample / per-segment dispatch (first
    dispatch = trace + compile) / host-pull / replay phases.  Once the run's
    outputs are on the host, both drivers count its coordinate steps:
    ``sdca.steps_required`` (the executed budgets' sum) and
    ``sdca.steps_lockstep`` (the trips the vmapped loop ran, masked ones
    included).  Telemetry only READS state -- results are bit-identical
    with it on, off, or absent.
    """
    loss = get_loss(cfg.loss)
    validate_assumption2(cfg.budget)
    if cfg.driver not in DRIVERS:
        raise ValueError(f"driver {cfg.driver!r} not in {DRIVERS}")
    eng = get_engine(engine if engine is not None else cfg.engine)
    if cfg.driver == "scan" and not eng.supports_scan:
        raise ValueError(
            f"engine {eng.name!r} does not support the scanned driver; "
            "use driver='auto' or 'loop'")
    tel = telemetry if telemetry is not None else obs.NULL_TELEMETRY
    scanned = cfg.driver != "loop" and eng.supports_scan
    run = _run_scanned if scanned else _run_loop
    from repro.core.subproblem import _solver_plan, n_chunks, resolve_gram
    max_steps = cfg.budget.max_steps(data.n_max)
    gram = resolve_gram(data.d, cfg.gram_max_d)
    # the SDCA loop's static plan as every engine derives it
    gram_mode, chunk = _solver_plan(data.d, max_steps, gram)
    chunks = n_chunks(max_steps, chunk)
    with tel.span("mocha.run", rounds=cfg.rounds, engine=eng.name,
                  driver="scan" if scanned else "loop"):
        with tel.span("mocha.setup",
                      residual_mode="gram" if gram_mode else "carry",
                      chunk=chunk, max_steps=max_steps, n_chunks=chunks):
            # hoist the static per-run SDCA precompute (row-norm table)
            # ONCE: the data never changes across rounds, and every
            # engine/driver below reads the same table, which also keeps it
            # bit-identical across engines
            data = dual_mod.with_xnorm2(data)
            m = data.m
            omega = reg.init_omega(m) if omega0 is None else omega0
            abar, K, q_t = _coupling(reg, omega, cfg.gamma,
                                     cfg.per_task_sigma, m)
            state = eng.setup(data, loss, max_steps, gram=gram)
            if state0 is not None:
                state = state0
            if trace is None:
                sys_cfg = cfg.systems or SystemsConfig(network=cfg.network)
                trace = SystemsTrace(m, data.d, sys_cfg)
        if tel.enabled:
            # pure READ of the simulated clock; re-binding to the same
            # shared trace (the cohort case) is idempotent
            tel.set_sim_clock(lambda: trace.elapsed_s)
        res = run(data, reg, cfg, loss, eng, trace, state, omega, abar, K,
                  q_t, max_steps, budget_fn, gram, tel)
        if tel.enabled:
            # host values only: both drivers return the executed budget
            # matrix already pulled (after mocha.host_pull when scanned)
            tel.counter("sdca.steps_required").inc(
                int(res.round_budgets.sum()))
            tel.counter("sdca.steps_lockstep").inc(
                cfg.rounds * m * chunks * chunk)
        return res


def _run_loop(data, reg, cfg, loss, eng, trace, state, omega, abar, K, q_t,
              max_steps, budget_fn, gram=None,
              tel=obs.NULL_TELEMETRY) -> RunResult:
    """Python round loop: one engine dispatch + one host sync per round."""
    key = jax.random.PRNGKey(cfg.seed)
    record = _record_rounds(cfg.rounds, cfg.record_every)
    history: Dict[str, List[float]] = {k: [] for k in HISTORY_KEYS}
    budgets_log: List[np.ndarray] = []

    for h in range(cfg.rounds):
        key, k_budget, k_round = jax.random.split(key, 3)
        if budget_fn is not None:
            budgets = budget_fn(k_budget, data.n_t, h)
        else:
            budgets = round_budgets(cfg.budget, k_budget, data.n_t)
        budgets = jnp.minimum(budgets, max_steps)
        cap = trace.begin_round()
        if cap is not None:   # semi_sync: fit the work to the clock cycle
            # clamp to max_steps BEFORE the int32 cast: a generous deadline
            # gives int64 caps past 2^31, and budgets never exceed max_steps
            # anyway, so the clamp is semantics-free
            cap = np.minimum(cap, max_steps)
            budgets = jnp.minimum(budgets, jnp.asarray(cap, budgets.dtype))
        state = eng.round(state, K, q_t, budgets, cfg.gamma, k_round)
        steps_np = np.asarray(budgets)
        trace.commit(steps_np)
        budgets_log.append(steps_np.astype(np.int64))

        row = None
        if cfg.omega_update_every and (h + 1) % cfg.omega_update_every == 0:
            with tel.span("mocha.omega_step", round=h + 1,
                          compile=h + 1 == cfg.omega_update_every):
                omega, abar, K, q_t, row = _omega_step(
                    reg, loss, cfg.gamma, cfg.per_task_sigma, data, state, K,
                    omega)
            # NOTE: Omega changed => the dual problem changed. v = X alpha is
            # Omega-independent; W(alpha) and the objectives pick up the new K.

        if record[h]:
            if row is None:
                row = _metrics(loss, data, state, abar, K)
            dual_val, primal_val, gap = np.asarray(row)
            history["round"].append(h)
            history["dual"].append(float(dual_val))
            history["primal"].append(float(primal_val))
            history["gap"].append(float(gap))
            history["time"].append(trace.elapsed_s)
            history["round_max_steps"].append(int(steps_np.max()))

    W = dual_mod.primal_weights(K, state.v)
    return RunResult(W=np.asarray(W), omega=np.asarray(omega), state=state,
                     history=history, trace=trace,
                     round_budgets=np.stack(budgets_log))


@partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _scan_rounds(round_fn, loss, max_steps, gram, data, state, K, abar, q_t,
                 gamma, keys, budgets, recs):
    """One device-resident segment of W-rounds (constant Omega/K).

    Scans the engine's pure round function (``RoundEngine.scan_round_fn``, a
    stable module-level callable so jit caching works) over pre-sampled
    (per-round key, budgets, record flag) rows; metrics are computed in-scan
    only on record rounds (``lax.cond`` skips the objective evaluation
    otherwise), so the stacked (rounds, 3) metric rows are the only
    per-round output.
    """

    def body(st, xs):
        k_round, b, rec = xs
        st = round_fn(loss, max_steps, gram, data, st, K, q_t, b, gamma,
                      k_round)
        row = jax.lax.cond(
            rec,
            lambda s: jnp.stack(_metrics_impl(loss, data, s, abar, K)),
            lambda s: jnp.zeros((3,), K.dtype),
            st)
        return st, row

    return jax.lax.scan(body, state, (keys, budgets, recs))


def _run_scanned(data, reg, cfg, loss, eng, trace, state, omega, abar, K, q_t,
                 max_steps, budget_fn, gram=None,
                 tel=obs.NULL_TELEMETRY) -> RunResult:
    """Device-resident driver: the W-round loop runs inside ``lax.scan``.

    Budgets (and semi_sync deadline caps) are round-indexed, so the whole
    (rounds, m) schedule is pre-sampled up front; Omega refreshes partition
    the run into segments (K/Abar constant within a segment) and each segment
    is one scan dispatch.  The executed budget matrix is transferred once at
    the end and replayed through the SystemsTrace (DESIGN.md section 6).
    """
    rounds = cfg.rounds
    with tel.span("mocha.presample", rounds=rounds):
        budget_keys, round_keys = round_key_schedule(
            jax.random.PRNGKey(cfg.seed), rounds)
        if budget_fn is not None:
            budgets_all = jnp.stack([budget_fn(budget_keys[h], data.n_t, h)
                                     for h in range(rounds)])
        else:
            budgets_all = presample_budgets(cfg.budget, budget_keys, data.n_t)
        budgets_all = jnp.minimum(budgets_all, max_steps)
        caps = trace.presample_caps(rounds)
        if caps is not None:
            # same pre-cast clamp as the loop driver (int64 caps can exceed
            # int32)
            caps = np.minimum(caps, max_steps)
            budgets_all = jnp.minimum(budgets_all,
                                      jnp.asarray(caps, budgets_all.dtype))

    record = _record_rounds(rounds, cfg.record_every)
    every = cfg.omega_update_every
    round_fn = eng.scan_round_fn()
    omega_rows: Dict[int, Array] = {}     # Omega round -> device (3,) row
    seg_slices: List[tuple] = []          # (h0, h_end, recs, device rows)

    h0 = 0
    while h0 < rounds:
        h_end = min(rounds, (h0 // every + 1) * every) if every else rounds
        recs = record[h0:h_end].copy()
        tail_update = bool(every) and h_end % every == 0
        if tail_update and recs[-1]:
            recs[-1] = False  # metrics for an Omega round use the POST-update K
        # the FIRST dispatch traces + compiles the scan program; later
        # segments replay the jit cache and only pay async enqueue -- the
        # span's `compile` tag is the compile-vs-execute split (execution
        # itself drains under mocha.host_pull)
        with tel.span("mocha.scan_dispatch", h0=h0, h_end=h_end,
                      compile=not seg_slices):
            state, rows = _scan_rounds(round_fn, loss, max_steps, gram, data,
                                       state, K, abar, q_t, cfg.gamma,
                                       round_keys[h0:h_end],
                                       budgets_all[h0:h_end],
                                       jnp.asarray(recs))
        seg_slices.append((h0, h_end, recs, rows))
        if tail_update:
            # one launch, queued behind the segment's scan; the first
            # traces + compiles (the same `compile` split as the dispatch)
            with tel.span("mocha.omega_step", round=h_end,
                          compile=h_end == every):
                omega, abar, K, q_t, row = _omega_step(
                    reg, loss, cfg.gamma, cfg.per_task_sigma, data, state, K,
                    omega)
            if record[h_end - 1]:
                omega_rows[h_end - 1] = row
        h0 = h_end

    W = dual_mod.primal_weights(K, state.v)   # queued behind the last scan
    # the one host transfer of the run's outputs: the segments' stacked
    # metric rows, the Omega rounds' rows, executed budgets, W, Omega.  It
    # blocks on the async dispatches, so this span is where device
    # EXECUTION still queued surfaces -- the other half of the
    # compile/execute split
    with tel.span("mocha.host_pull", rounds=rounds):
        seg_np, omega_np, executed, W, omega = jax.device_get(
            (seg_slices, omega_rows, budgets_all, W, omega))
        executed = executed.astype(np.int64)
    with tel.span("mocha.replay", rounds=rounds):
        trace.replay(executed)
        # only THIS run's events: a pre-used trace already holds earlier
        # rounds, and times() is cumulative over all of them (loop-parity:
        # the loop records trace.elapsed_s, which also continues the prior
        # clock)
        times = trace.times()[-rounds:]
        history: Dict[str, List[float]] = {k: [] for k in HISTORY_KEYS}
        rows_np = dict(omega_np)
        for h0s, _, recs, rows in seg_np:
            for i, rec in enumerate(recs):
                if rec:
                    rows_np[h0s + i] = rows[i]
        for h in range(rounds):
            if not record[h]:
                continue
            dual_val, primal_val, gap = (float(x) for x in rows_np[h])
            history["round"].append(h)
            history["dual"].append(dual_val)
            history["primal"].append(primal_val)
            history["gap"].append(gap)
            history["time"].append(float(times[h]))
            history["round_max_steps"].append(int(executed[h].max()))

    return RunResult(W=W, omega=omega, state=state, history=history,
                     trace=trace, round_budgets=executed)


def run_cocoa(data: FederatedData, reg: Regularizer, cfg: MochaConfig,
              omega0: Optional[Array] = None) -> RunResult:
    """CoCoA baseline = MOCHA with a *uniform, fixed* approximation quality.

    Every node runs ``passes`` full passes over its own local data each round
    regardless of systems state (no clock cycle, no drops): the synchronous
    round then waits for the slowest node (paper Sec. 3.4).
    """
    fixed = BudgetConfig(passes=cfg.budget.passes)  # strip heterogeneity knobs
    systems = cfg.systems
    if systems is not None and systems.policy != "sync":
        # CoCoA has no clock cycle: keep the hardware model, drop the deadline
        systems = dataclasses.replace(systems, policy="sync",
                                      clock_cycle_s=0.0)
    cocoa_cfg = dataclasses.replace(cfg, budget=fixed, per_task_sigma=False,
                                    systems=systems)
    return _run_mocha(data, reg, cocoa_cfg, omega0=omega0)
