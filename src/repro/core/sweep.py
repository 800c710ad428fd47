"""Vmapped hyperparameter-sweep harness over the scanned MOCHA driver.

Table-1/4 style evaluation is a (shuffle x lambda) grid of otherwise
identical MOCHA runs -- exactly the hyperparameter-tuning workload that
dominates federated evaluation cost.  ``_run_sweep`` executes the whole grid
as ONE batched device program: the scanned driver (core/mocha.py) is vmapped
over shuffles (data batched, regularizer fixed) and again over the
regularizer grid (data broadcast, hyperparameters batched), so an R x S grid
costs a handful of XLA dispatches instead of R * S Python-loop runs.

Constraints (asserted):
  * all regularizers must be the same dataclass type; the fields that vary
    across the grid must be floats (they become traced scalars inside the
    vmapped driver -- shape-like ints such as ``Clustered.k`` must be fixed);
  * no ``budget_fn`` (budgets must pre-sample from the round-indexed key
    schedule);
  * the LocalEngine scanned path only (the engine that supports vmap).

Systems clocks: ``sync`` grids carry no caps.  ``semi_sync`` grids DO batch:
the clock-cycle deadline caps are round-indexed and state-independent
(``SystemsTrace.presample_caps``), and because each sequential-fallback cell
builds a fresh trace from the SAME ``SystemsConfig``, every cell sees the
same (rounds, m) cap matrix -- so one pre-sampled matrix, folded into the
pre-sampled budgets exactly as the scanned driver folds it, reproduces the
fallback cell-for-cell bitwise.  The sweep measures statistics, not time:
no trace is replayed (run a single ``_run_mocha`` for wall-clock curves).

Shuffles with different ``n_max`` are right-padded to a common size by
``stack_federations``; masks/budgets make padding inert (padded points are
never drawn into the SDCA coordinate stream's live set, and metric sums mask
them out), though the coordinate-draw stream itself depends on ``n_max``, so
a padded run equals an unpadded run statistically rather than bitwise.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dual as dual_mod
from repro.core.dual import FederatedData
from repro.core.losses import get_loss
from repro.core.mocha import MochaConfig, _coupling_terms, _metrics_impl
from repro.core.regularizers import Regularizer
from repro.core.theta import (presample_budgets, round_key_schedule,
                              validate_assumption2)

Array = jax.Array


@dataclasses.dataclass
class SweepResult:
    """Grid-shaped results: axis 0 = regularizer grid, axis 1 = shuffles."""

    W: np.ndarray        # (R, S, m, d) final per-task models
    omega: np.ndarray    # (R, S, m, m)
    dual: np.ndarray     # (R, S) final dual objective
    primal: np.ndarray   # (R, S) final primal objective
    gap: np.ndarray      # (R, S) final duality gap
    regs: Tuple[Regularizer, ...]
    seeds: Tuple[int, ...]


def stack_federations(datas: Sequence[FederatedData]) -> FederatedData:
    """Stack federations (shuffles) into one batched FederatedData.

    Right-pads each shuffle's point axis to the common ``n_max`` (padding has
    mask 0 and is inert everywhere).  All shuffles must share (m, d).
    """
    if not datas:
        raise ValueError("stack_federations needs at least one federation")
    m, d = datas[0].m, datas[0].d
    for f in datas:
        if (f.m, f.d) != (m, d):
            raise ValueError(
                f"cannot stack federations of shape (m={f.m}, d={f.d}) with "
                f"(m={m}, d={d})")
    n_max = max(f.n_max for f in datas)

    def pad(a, width):
        cfgs = [(0, 0), (0, width)] + [(0, 0)] * (a.ndim - 2)
        return jnp.pad(a, cfgs)

    return FederatedData(
        X=jnp.stack([pad(f.X, n_max - f.n_max) for f in datas]),
        y=jnp.stack([pad(f.y, n_max - f.n_max) for f in datas]),
        mask=jnp.stack([pad(f.mask, n_max - f.n_max) for f in datas]),
    )


def grid_batch_reason(regs: Sequence[Regularizer]) -> Optional[str]:
    """Why a regularizer grid cannot be batched (None = it can).

    The non-raising twin of ``_grid_fields``'s validation, consumed by the
    capability router (repro.api.router): grids that fail these checks fall
    back to the sequential cell loop instead of erroring.
    """
    template = regs[0]
    for r in regs:
        if type(r) is not type(template):
            return (f"mixed regularizer types ({type(template).__name__} vs "
                    f"{type(r).__name__}) cannot become one traced template")
    for f in dataclasses.fields(template):
        vals = [getattr(r, f.name) for r in regs]
        if any(v != vals[0] for v in vals):
            if not all(isinstance(v, (float, int)) and not isinstance(v, bool)
                       for v in vals):
                return (f"grid field {f.name!r} is not numeric and cannot "
                        "become a traced scalar")
    return None


def _grid_fields(regs: Sequence[Regularizer]) -> Tuple[str, ...]:
    """Names of dataclass fields that vary across the regularizer grid."""
    template = regs[0]
    for r in regs:
        if type(r) is not type(template):
            raise TypeError(
                f"mixed regularizer types in sweep: {type(template).__name__}"
                f" vs {type(r).__name__}")
    varying = []
    for f in dataclasses.fields(template):
        vals = [getattr(r, f.name) for r in regs]
        if any(v != vals[0] for v in vals):
            if not all(isinstance(v, (float, int)) and not isinstance(v, bool)
                       for v in vals):
                raise TypeError(
                    f"sweep field {f.name!r} must be numeric to be batched")
            varying.append(f.name)
    return tuple(varying)


@partial(jax.jit, static_argnums=(0, 1, 2))
def _sweep_exec(cfg: MochaConfig, template: Regularizer,
                vfields: Tuple[str, ...], data: FederatedData,
                params: Tuple[Array, ...], keys: Array,
                caps: Optional[Array]):
    """The whole grid as one compiled program (cached on static config).

    One ``lax.scan`` covers every round; Omega refreshes run under a
    ``lax.cond`` on the (unbatched) round index, so the program compiles a
    single loop body no matter how many refreshes the schedule has.

    ``caps`` is the pre-sampled (rounds, m) semi_sync deadline-cap matrix
    (already clamped to ``max_steps`` on host, exactly as ``_run_scanned``
    clamps before its min), broadcast to every grid cell, or None under
    ``sync``.  None is an empty pytree, so the sync program traces without
    the extra ``minimum`` and stays bitwise untouched.
    """
    from repro.core.engine import _local_round

    loss = get_loss(cfg.loss)
    m, n_max = data.X.shape[1], data.X.shape[2]
    max_steps = cfg.budget.max_steps(n_max)
    rounds, every = cfg.rounds, cfg.omega_update_every

    def driver(d, pvals, key, caps):
        d = dual_mod.with_xnorm2(d)   # per-cell hoist of the static SDCA
        reg = dataclasses.replace(template, **dict(zip(vfields, pvals)))
        omega = reg.init_omega(m)
        abar, K, q_t = _coupling_terms(reg, omega, cfg.gamma,
                                       cfg.per_task_sigma, m)
        state = dual_mod.init_state(d)
        budget_keys, round_keys = round_key_schedule(key, rounds)
        budgets = presample_budgets(cfg.budget, budget_keys, d.n_t)
        budgets = jnp.minimum(budgets, max_steps)
        if caps is not None:
            budgets = jnp.minimum(budgets, caps.astype(budgets.dtype))

        def refresh(carry):
            state, omega, abar, K, q_t = carry
            W = dual_mod.primal_weights(K, state.v)
            omega = reg.update_omega(W, omega)
            abar, K, q_t = _coupling_terms(reg, omega, cfg.gamma,
                                           cfg.per_task_sigma, m)
            return state, omega, abar, K, q_t

        def body(carry, xs):
            state, omega, abar, K, q_t = carry
            h, k_round, b = xs
            state = _local_round(loss, max_steps, None, d, state, K, q_t, b,
                                 cfg.gamma, k_round)
            carry = (state, omega, abar, K, q_t)
            if every:   # pred is round-indexed (unbatched), so cond stays lazy
                carry = jax.lax.cond((h + 1) % every == 0, refresh,
                                     lambda c: c, carry)
            return carry, None

        carry = (state, omega, abar, K, q_t)
        carry, _ = jax.lax.scan(
            body, carry, (jnp.arange(rounds), round_keys, budgets))
        state, omega, abar, K, q_t = carry
        W = dual_mod.primal_weights(K, state.v)
        dual_val, primal_val, gap = _metrics_impl(loss, d, state, abar, K)
        return W, omega, dual_val, primal_val, gap

    over_shuffles = jax.vmap(driver, in_axes=(0, None, 0, None))
    over_grid = jax.vmap(over_shuffles, in_axes=(None, 0, None, None))
    return over_grid(data, params, keys, caps)


def _shard_grid(data: FederatedData, params: Tuple[Array, ...], keys: Array,
                n_regs: int, n_shuffles: int):
    """Shard independent grid cells across available devices.

    Grid cells never communicate, so partitioning either batch axis is a pure
    wall-clock win (results are bit-identical to the single-device program).
    The shuffle axis is preferred when it divides the device count evenly,
    then the regularizer axis; otherwise everything stays on one device.
    Multiple CPU devices come from ``--xla_force_host_platform_device_count``
    (set by benchmarks/run.py); real multi-device backends shard the same
    way.
    """
    devices = jax.devices()
    ndev = len(devices)
    if ndev <= 1:
        return data, params, keys
    # largest usable device subset: the sharded axis must divide evenly
    k_shuffle = max((k for k in range(2, ndev + 1) if n_shuffles % k == 0),
                    default=1)
    k_reg = max((k for k in range(2, ndev + 1) if n_regs % k == 0), default=1)
    k = max(k_shuffle, k_reg)
    if k <= 1:
        return data, params, keys
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.asarray(devices[:k]), ("cells",))
    split = NamedSharding(mesh, PartitionSpec("cells"))
    replicate = NamedSharding(mesh, PartitionSpec())
    if k_shuffle >= k_reg:
        data = jax.device_put(data, split)
        keys = jax.device_put(keys, split)
        params = jax.device_put(params, replicate)
    else:
        data = jax.device_put(data, replicate)
        keys = jax.device_put(keys, replicate)
        params = jax.device_put(params, split)
    return data, params, keys


def _run_sweep(data: Union[FederatedData, Sequence[FederatedData]],
               regs: Sequence[Regularizer],
               seeds: Union[int, Sequence[int]],
               cfg: MochaConfig) -> SweepResult:
    """Run the (regularizer-grid x shuffle) sweep as batched dispatches.

    ``data``: a stacked FederatedData (leading shuffle axis) or a sequence of
    federations (stacked via ``stack_federations``).  ``regs``: the grid of
    same-type regularizers (e.g. one per lambda).  ``seeds``: driver seed per
    shuffle (a scalar broadcasts).  ``cfg``: shared MochaConfig; the scanned
    LocalEngine driver semantics apply (see module docstring for limits).
    """
    if not isinstance(data, FederatedData):
        data = stack_federations(data)
    if data.X.ndim != 4:
        raise ValueError("the sweep expects stacked (S, m, n, d) data; got "
                         f"X of shape {data.X.shape}")
    from repro.core.engine import get_engine
    if get_engine(cfg.engine).name != "local":
        raise ValueError(
            f"the sweep batches the LocalEngine scanned driver only; "
            f"cfg.engine={cfg.engine!r} is not supported")
    validate_assumption2(cfg.budget)
    if not regs:
        raise ValueError("the sweep needs at least one regularizer")

    n_shuffles = data.X.shape[0]
    if isinstance(seeds, (int, np.integer)):
        seeds = (int(seeds),) * n_shuffles
    seeds = tuple(int(s) for s in seeds)
    if len(seeds) != n_shuffles:
        raise ValueError(f"{len(seeds)} seeds for {n_shuffles} shuffles")

    vfields = _grid_fields(regs)
    template = regs[0]
    if vfields:
        params = tuple(jnp.asarray([float(getattr(r, f)) for r in regs])
                       for f in vfields)
    else:
        # degenerate grid (identical regs): batch a dummy so R is preserved
        params = (jnp.zeros(len(regs)),)
    keys = jnp.stack([jax.random.PRNGKey(s) for s in seeds])

    # semi_sync: one (rounds, m) cap matrix covers every cell -- the caps a
    # fresh per-cell trace would derive are a pure function of the shared
    # SystemsConfig.  Clamp to max_steps on host BEFORE the device min, in
    # the same order/dtype as _run_scanned, so cells match it bitwise.
    caps = None
    if cfg.systems is not None:
        from repro.core.systems_model import presample_policy_caps
        m, n_max = data.X.shape[1], data.X.shape[2]
        caps = presample_policy_caps(m, data.X.shape[3], cfg.systems,
                                     cfg.rounds)
        if caps is not None:
            caps = jnp.asarray(
                np.minimum(caps, cfg.budget.max_steps(n_max)), jnp.int32)

    data, params, keys = _shard_grid(data, params, keys, len(regs),
                                     n_shuffles)
    W, omega, dual_val, primal_val, gap = _sweep_exec(
        cfg, template, vfields, data, params, keys, caps)
    return SweepResult(
        W=np.asarray(W), omega=np.asarray(omega),
        dual=np.asarray(dual_val), primal=np.asarray(primal_val),
        gap=np.asarray(gap), regs=tuple(regs), seeds=seeds)


@jax.jit
def _grid_errors(W: Array, X: Array, y: Array, mask: Array) -> Array:
    def one(W_sm, X_s, y_s, m_s):
        test = FederatedData(X=X_s, y=y_s, mask=m_s)
        return jnp.mean(dual_mod.per_task_error(test, W_sm, X_s, y_s, m_s))

    over_shuffles = jax.vmap(one, in_axes=(0, 0, 0, 0))
    over_grid = jax.vmap(over_shuffles, in_axes=(0, None, None, None))
    return over_grid(W, X, y, mask)


def sweep_errors(result: Union[SweepResult, np.ndarray],
                 test: FederatedData) -> np.ndarray:
    """(R, S) mean per-task test error for every grid cell.

    ``test`` is the stacked (S, m, n, d) test split matching the sweep's
    shuffle axis; ``result`` is a SweepResult or a raw (R, S, m, d) W array.
    """
    W = result.W if isinstance(result, SweepResult) else result
    return np.asarray(_grid_errors(jnp.asarray(W), test.X, test.y, test.mask))
