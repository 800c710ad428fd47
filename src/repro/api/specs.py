"""Declarative experiment specs: ONE surface for every MOCHA scenario.

``Experiment`` describes a run as five orthogonal sub-specs:

  * ``Problem``  -- WHAT is solved: one cross-silo federation, a stack of
                    shuffles (grid axis), or a streaming client population;
  * ``Method``   -- the statistical method: loss, regularizer (or a grid of
                    them), round/budget/omega schedules, warm starts;
  * ``Systems``  -- the simulated systems environment: network, clock policy,
                    participation sampling, fault injection;
  * ``Exec``     -- HOW it executes: engine, driver, mesh/wire dtype, cohort
                    and cache sizes;
  * ``Eval``     -- what is measured: metric set, cadence, the per-client
                    held-out split / held-out-client count.

``Experiment.run(seed)`` routes through the capability router
(repro.api.router) to the scanned/vmapped/loop/cohort execution paths and
returns a unified ``Report``.  Each knob has its one home here:
``as_mocha_config`` / ``as_cohort_config`` build the core drivers' internal
views (``MochaConfig`` / ``CohortConfig``) from the sub-specs, the one
mapping between this surface and the drivers.  See DESIGN.md section 8.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Any, Callable, Optional, Sequence, Tuple, Union

import numpy as np

from repro.cohort.resilience import FaultConfig
from repro.core.dual import DualState, FederatedData
from repro.core.mocha import DRIVERS, MochaConfig
from repro.core.regularizers import MeanRegularized, Regularizer
from repro.core.systems_model import SystemsConfig, SystemsTrace
from repro.core.theta import BudgetConfig

#: the problem shapes the router distinguishes (DESIGN.md section 8)
PROBLEM_KINDS = ("silo", "shuffles", "population")


@dataclasses.dataclass(frozen=True)
class Problem:
    """WHAT is being solved -- exactly one of the two fields is set.

    ``train``: a single ``FederatedData`` federation (cross-silo), a stacked
    ``(S, m, n, d)`` federation, or a sequence of per-shuffle federations
    (the grid axis of a sweep).  ``population``: a streaming
    ``repro.cohort.Population`` (cross-device; cohorts are sampled per
    round, the population never materializes).
    """

    train: Optional[Union[FederatedData, Sequence[FederatedData]]] = None
    population: Optional[Any] = None      # repro.cohort.Population

    def __post_init__(self):
        if (self.train is None) == (self.population is None):
            raise ValueError(
                "Problem needs exactly one of train= or population=")
        if self.train is not None and not isinstance(self.train,
                                                     FederatedData):
            object.__setattr__(self, "train", tuple(self.train))
        if self.train is not None and isinstance(self.train, FederatedData):
            if self.train.X.ndim not in (3, 4):
                raise ValueError(
                    "Problem.train expects (m, n, d) or stacked (S, m, n, d) "
                    f"data; got X of shape {self.train.X.shape}")

    @property
    def kind(self) -> str:
        if self.population is not None:
            return "population"
        if not isinstance(self.train, FederatedData) or self.train.X.ndim == 4:
            return "shuffles"
        return "silo"

    @property
    def shuffle_count(self) -> int:
        if self.kind == "population":
            raise ValueError("populations have no shuffle axis")
        if not isinstance(self.train, FederatedData):
            return len(self.train)
        return 1 if self.train.X.ndim == 3 else self.train.X.shape[0]

    @property
    def d(self) -> int:
        """Feature dimension (drives the gram/carry residual-mode choice)."""
        if self.population is not None:
            return int(self.population.spec.d)
        first = (self.train if isinstance(self.train, FederatedData)
                 else self.train[0])
        return int(first.X.shape[-1])

    def stacked(self) -> FederatedData:
        """The (S, m, n, d) stacked view of the shuffle axis."""
        from repro.core.sweep import stack_federations
        if not isinstance(self.train, FederatedData):
            return stack_federations(self.train)
        if self.train.X.ndim == 3:
            return stack_federations([self.train])
        return self.train

    def shuffle_list(self) -> Tuple[FederatedData, ...]:
        """Per-shuffle (m, n, d) federations (the sequential-fallback view).

        A sequence input is returned as given (unpadded); an already-stacked
        input is sliced (shuffles keep the common padding, which is inert
        under the masks exactly as in the vmapped path).
        """
        if not isinstance(self.train, FederatedData):
            return self.train
        if self.train.X.ndim == 3:
            return (self.train,)
        t = self.train
        return tuple(
            FederatedData(X=t.X[s], y=t.y[s], mask=t.mask[s],
                          xnorm2=None if t.xnorm2 is None else t.xnorm2[s])
            for s in range(t.X.shape[0]))


@dataclasses.dataclass(frozen=True)
class Method:
    """The statistical method: what MOCHA optimizes and on what schedule.

    ``regularizers`` is a grid: one entry runs a single problem, several run
    a hyperparameter sweep (batched when the router finds a vmapped path,
    sequential otherwise).  ``budget_fn(key, n_t, round) -> (m,) budgets``
    overrides the ``BudgetConfig`` sampler; ``omega0`` fixes the initial
    relationship matrix (otherwise ``Regularizer.init_omega``).
    """

    loss: str = "hinge"
    regularizers: Union[Regularizer, Tuple[Regularizer, ...]] = (
        MeanRegularized(),)
    rounds: int = 100                  # W rounds (outer blocks for cohorts)
    omega_update_every: int = 0        # 0 = fixed Omega
    gamma: float = 1.0
    per_task_sigma: bool = True
    budget: BudgetConfig = dataclasses.field(default_factory=BudgetConfig)
    budget_fn: Optional[Callable] = None
    omega0: Optional[Any] = None       # initial (m, m) relationship

    def __post_init__(self):
        regs = self.regularizers
        if isinstance(regs, Regularizer):
            regs = (regs,)
        regs = tuple(regs)
        if not regs:
            raise ValueError("Method needs at least one regularizer")
        object.__setattr__(self, "regularizers", regs)


@dataclasses.dataclass(frozen=True)
class Systems:
    """The simulated systems environment (networks, clocks, participation).

    ``config`` is the full event-driven model (overrides ``network``);
    ``trace`` supplies a pre-built ``SystemsTrace`` whose clock continues
    across runs (single-problem runs only).  ``sampler`` / ``dropout``
    describe cross-device participation: cohort selection law and the
    selected-but-failed probability (population problems only).
    ``faults`` injects the deterministic chaos schedule
    (``repro.cohort.resilience.FaultPlan``) into the cohort block loop --
    one more simulated systems effect, pre-sampled like everything else
    (population problems only; pair with ``Exec.max_retries`` /
    ``Exec.degrade``).
    """

    network: str = "lte"
    config: Optional[SystemsConfig] = None
    trace: Optional[SystemsTrace] = None
    sampler: str = "uniform"           # uniform | weighted (availability)
    dropout: float = 0.0               # per-(selected client, round) failure
    faults: Optional[FaultConfig] = None  # deterministic fault injection

    @property
    def policy(self) -> str:
        return self.config.policy if self.config is not None else "sync"


@dataclasses.dataclass(frozen=True)
class Exec:
    """HOW the experiment executes -- substrate knobs, no statistics.

    ``engine`` accepts a name, ``RoundEngine`` class, or configured
    instance; ``mesh`` / ``comm_dtype`` configure the sharded runtime when
    ``engine='sharded'``.  The cohort block is sized by
    ``cohort`` / ``inner_rounds`` / ``clusters`` / ``eta`` /
    ``cache_clients`` / ``n_pad`` and pipelined by ``overlap`` /
    ``staleness`` (population problems only).
    """

    engine: Any = "local"              # local | pallas | sharded | instance
    driver: str = "auto"               # auto | scan | loop
    mesh: Any = None                   # sharded: explicit device mesh
    comm_dtype: Any = None             # sharded: wire dtype for Delta v
    state0: Optional[DualState] = None  # warm-start dual iterate
    cohort: int = 64                   # K sampled clients per block
    inner_rounds: int = 1              # W-rounds per cohort block
    clusters: int = 3                  # k of the factored relationship
    eta: float = 0.5                   # per-client self-affinity in Omega_S
    cache_clients: int = 4096          # bounded warm-start/delta cache
    n_pad: Optional[int] = None        # None = PopulationSpec.pad_width
    #: cohort pipeline depth: how many blocks may be packed ahead of the
    #: one currently solving (1 = the strictly sequential block loop)
    overlap: int = 1
    #: max solved-but-unmerged blocks when a block launches (0 = every
    #: prior block folds in first -- bit-identical to sequential)
    staleness: int = 0
    #: per-block retry budget: a failed pack/solve attempt retries up to
    #: this many times, each charging capped backoff to the simulated clock
    max_retries: int = 0
    #: exhausted block -> graceful degradation to the theory's dropped-node
    #: fold (participated=False everywhere) instead of raising BlockFailure
    degrade: bool = False
    #: blocks between atomic state snapshots (0 = no cadence; failures
    #: still force-save when ``checkpoint_dir`` is set)
    checkpoint_every: int = 0
    checkpoint_dir: Optional[str] = None  # where step_<block>.ckpt land
    #: restore the latest snapshot under ``checkpoint_dir`` and continue
    #: (bit-identical to the uninterrupted run; config-hash validated)
    resume: bool = False
    #: record runtime telemetry (repro.obs): per-worker span traces with
    #: wall AND simulated clocks, plus a counters/histograms registry
    #: flattened into ``Report.provenance["telemetry"]``.  Telemetry only
    #: READS state -- results are bit-identical on or off
    telemetry: bool = False
    #: write the Chrome trace-event JSON (chrome://tracing / Perfetto)
    #: under this directory (``trace_<config_hash>_s<seed>.json``, path in
    #: ``Report.provenance["trace_path"]``); setting it implies telemetry
    trace_dir: Optional[str] = None

    def __post_init__(self):
        if self.driver not in DRIVERS:
            raise ValueError(f"driver {self.driver!r} not in {DRIVERS}")
        if self.overlap < 1:
            raise ValueError(f"need overlap >= 1, got {self.overlap}")
        if self.staleness < 0:
            raise ValueError(f"need staleness >= 0, got {self.staleness}")
        if self.max_retries < 0:
            raise ValueError(
                f"need max_retries >= 0, got {self.max_retries}")
        if self.checkpoint_every < 0:
            raise ValueError(
                f"need checkpoint_every >= 0, got {self.checkpoint_every}")
        if ((self.checkpoint_every > 0 or self.resume)
                and self.checkpoint_dir is None):
            raise ValueError(
                "checkpoint_every/resume need Exec.checkpoint_dir")

    def resolve_engine(self):
        """Instantiate the engine (mesh/comm_dtype configure 'sharded')."""
        from repro.core.engine import ShardedEngine, get_engine
        if (self.engine == "sharded"
                and (self.mesh is not None or self.comm_dtype is not None)):
            return ShardedEngine(mesh=self.mesh, comm_dtype=self.comm_dtype)
        return get_engine(self.engine)

    @property
    def engine_name(self) -> str:
        if isinstance(self.engine, str):
            return self.engine
        return getattr(self.engine, "name", "local")


@dataclasses.dataclass(frozen=True)
class Eval:
    """What is measured, how often, and against which held-out data.

    ``record_every`` is the driver history cadence.  ``holdout`` is the
    per-client held-out split (a test ``FederatedData`` matching the
    problem's shape; stacked or a sequence for shuffle grids) -- when set,
    the Report carries a per-client table of the requested ``metrics``.
    ``holdout_clients`` is the population analogue: how many never- (or
    least-) trained clients to materialize and score per cluster.
    """

    record_every: int = 1
    holdout: Optional[Union[FederatedData, Sequence[FederatedData]]] = None
    holdout_clients: int = 0
    metrics: Tuple[str, ...] = ("error", "loss")

    def holdout_stacked(self) -> Optional[FederatedData]:
        if self.holdout is None or isinstance(self.holdout, FederatedData):
            if (self.holdout is not None and self.holdout.X.ndim == 3):
                from repro.core.sweep import stack_federations
                return stack_federations([self.holdout])
            return self.holdout
        from repro.core.sweep import stack_federations
        return stack_federations(tuple(self.holdout))


@dataclasses.dataclass(frozen=True)
class Serve:
    """Online-serving sub-spec for ``Experiment.serve()``.

    ``publish_every`` is the snapshot refresh cadence in folded blocks (1 =
    every fold publishes).  ``prewarm`` publishes the deterministic cold
    state as version 0 before training starts, so predictions are
    answerable from t=0 (cold clients resolve to their cluster centroid).
    Serving never changes training: a run with a ``ServeSession`` attached
    is bit-identical to ``Experiment.run`` -- the same guarantee shape as
    ``Exec.telemetry``.
    """

    publish_every: int = 1
    prewarm: bool = True

    def __post_init__(self):
        if self.publish_every < 1:
            raise ValueError(
                f"need publish_every >= 1 folds, got {self.publish_every}")


@dataclasses.dataclass(frozen=True)
class Experiment:
    """A fully-described experiment; ``run(seed)`` executes and evaluates it.

    The capability router (repro.api.router) inspects
    (problem axes x engine x systems policy) and picks the fastest
    applicable path -- vmapped sweep, device-resident scan, Python loop, or
    the cohort block loop -- falling back (with a logged reason, recorded in
    ``Report.provenance``) instead of raising when a batched path does not
    apply.
    """

    problem: Problem
    method: Method = Method()
    systems: Systems = Systems()
    exec: Exec = Exec()
    eval: Eval = Eval()

    def run(self, seed: Union[int, Sequence[int]] = 0) -> "Report":
        from repro.api.execute import run_experiment
        return run_experiment(self, seed)

    def serve(self, seed: int = 0,
              serve: Optional[Serve] = None) -> "ServeSession":
        """An online :class:`~repro.serve.refresh.ServeSession` over this
        experiment: cohort training streams in the background (``start()``
        / ``join()``, or inline ``run()``) while ``predict(ids, X)``
        answers from atomically-swapped snapshots.  Cohort-routed
        populations only."""
        from repro.api.execute import serve_experiment
        return serve_experiment(self, seed, serve)

    def route(self) -> "RoutePlan":
        from repro.api.router import route
        return route(self)


# ---------------------------------------------------------------------------
# Config views: the core drivers' settings, derived from the specs in ONE place
# ---------------------------------------------------------------------------


def as_mocha_config(exp: Experiment, seed: int = 0, *,
                    rounds: Optional[int] = None,
                    record_every: Optional[int] = None) -> MochaConfig:
    """``MochaConfig`` as a thin frozen view over (Method, Systems, Exec,
    Eval) -- the single wiring point between the declarative surface and the
    core driver."""
    return MochaConfig(
        loss=exp.method.loss,
        rounds=exp.method.rounds if rounds is None else rounds,
        omega_update_every=exp.method.omega_update_every,
        gamma=exp.method.gamma,
        per_task_sigma=exp.method.per_task_sigma,
        budget=exp.method.budget,
        engine=exp.exec.engine_name,
        network=exp.systems.network,
        systems=exp.systems.config,
        seed=int(seed),
        record_every=(exp.eval.record_every if record_every is None
                      else record_every),
        driver=exp.exec.driver,
    )


def as_cohort_config(exp: Experiment, seed: int = 0):
    """``CohortConfig`` as a thin frozen view over the sub-specs.

    The inner per-block solver settings are themselves a ``MochaConfig``
    view (``CohortConfig.inner``)."""
    from repro.cohort.driver import CohortConfig
    inner = dataclasses.replace(as_mocha_config(exp, seed=seed), systems=None)
    return CohortConfig(
        rounds=exp.method.rounds,
        cohort=exp.exec.cohort,
        inner_rounds=exp.exec.inner_rounds,
        sampler=exp.systems.sampler,
        dropout=exp.systems.dropout,
        clusters=exp.exec.clusters,
        eta=exp.exec.eta,
        omega_update_every=exp.method.omega_update_every,
        cache_clients=exp.exec.cache_clients,
        network=exp.systems.network,
        systems=exp.systems.config,
        seed=int(seed),
        record_every=exp.eval.record_every,
        n_pad=exp.exec.n_pad,
        overlap=exp.exec.overlap,
        staleness=exp.exec.staleness,
        max_retries=exp.exec.max_retries,
        degrade=exp.exec.degrade,
        faults=exp.systems.faults,
        checkpoint_every=exp.exec.checkpoint_every,
        checkpoint_dir=exp.exec.checkpoint_dir,
        resume=exp.exec.resume,
        telemetry=bool(exp.exec.telemetry or exp.exec.trace_dir is not None),
        trace_dir=exp.exec.trace_dir,
        inner=inner,
    )


# ---------------------------------------------------------------------------
# Config fingerprint (Report provenance)
# ---------------------------------------------------------------------------


def _canon(x) -> Any:
    """Canonical JSON-able form of a spec tree for hashing.

    Arrays contribute shape + dtype (a CONFIG hash, not a data checksum:
    hashing 10^6-client payloads per run would defeat the point); stateful
    runtime objects (traces, engines, callables) contribute stable names.
    """
    if x is None or isinstance(x, (bool, int, float, str)):
        return x
    if dataclasses.is_dataclass(x) and not isinstance(x, type):
        out = {"__class__": type(x).__name__}
        for f in dataclasses.fields(x):
            out[f.name] = _canon(getattr(x, f.name))
        return out
    if isinstance(x, tuple) and hasattr(x, "_fields"):   # NamedTuple
        return {"__class__": type(x).__name__,
                **{k: _canon(v) for k, v in zip(x._fields, x)}}
    if isinstance(x, (list, tuple)):
        return [_canon(v) for v in x]
    if isinstance(x, dict):
        return {str(k): _canon(v) for k, v in sorted(x.items())}
    if hasattr(x, "shape") and hasattr(x, "dtype"):      # ndarray / jax.Array
        return ["array", [int(s) for s in x.shape], str(x.dtype)]
    if hasattr(x, "spec") and hasattr(x, "client_block"):   # Population
        return {"__class__": "Population", "spec": _canon(x.spec),
                "seed": _canon(getattr(x, "seed", None))}
    if isinstance(x, np.dtype) or isinstance(x, type):
        return str(getattr(x, "__name__", x))
    if callable(x):
        return getattr(x, "__qualname__", type(x).__name__)
    return type(x).__name__


def config_fingerprint(exp: Experiment) -> str:
    """Stable 12-hex-digit hash of the experiment description."""
    blob = json.dumps(_canon(exp), sort_keys=True, default=repr)
    return hashlib.sha256(blob.encode()).hexdigest()[:12]
