"""RoundEngine: pluggable executors for MOCHA's federated W-round.

Algorithm 1's outer loop (Omega refreshes, budget/theta control, the simulated
systems clock, metric recording) is engine-independent; what varies is HOW one
round of data-local subproblem solves runs.  Each engine maps the same
mathematical round onto a different execution substrate:

  * ``LocalEngine``   -- vmapped pure-jnp SDCA (``batched_local_sdca``), the
                         reference path; every loss, every backend.
  * ``PallasEngine``  -- the fused Pallas TPU kernel
                         (``repro.kernels.sdca``), hinge loss only; compiled
                         on TPU, interpret-mode elsewhere.  Shares the
                         reference path's coordinate-draw stream so results
                         are bit-identical given the same keys/budgets.
  * ``ShardedEngine`` -- the shard_map runtime (``repro.federated.runtime``):
                         tasks sharded over the mesh ``data`` axis, Delta v
                         exchanged with one all_gather (the paper's only
                         communication).

Contract: ``setup(data, loss, max_steps, gram=None)`` returns the initial
real-size ``DualState`` (``gram`` forces a residual mode for tests that
compare the two modes at one shape; the driver never sets it, leaving the
mode to ``subproblem._solver_plan``.  Every engine threads it to its solver
so a forced mode stays engine-consistent);
``round(state, K, q_t, budgets, gamma, key)`` returns the
updated real-size state.  Engines may keep padded / device-resident internals,
but the driver only ever sees (m, n_max) / (m, d) arrays, so metrics and the
Omega update are engine-agnostic.  ``key`` is split into per-task keys with
``jax.random.split(key, m)`` by EVERY engine -- that shared convention is what
makes cross-engine runs reproducible (tests/test_runtime.py asserts parity).

See DESIGN.md for the layering diagram and how to add a backend.
"""
from __future__ import annotations

import abc
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import dual as dual_mod
from repro.core.dual import DualState, FederatedData
from repro.core.losses import Loss
from repro.core.subproblem import batched_local_sdca

Array = jax.Array


class RoundEngine(abc.ABC):
    """Executes one federated W-update round for the MOCHA driver."""

    name: str = "abstract"
    #: capability flag: True iff the driver may run this engine's rounds
    #: inside its device-resident ``lax.scan`` path (requires a pure,
    #: trace-compatible ``round`` exposed via ``scan_round_fn``).  Engines
    #: with host-side state (the sharded pad caches) or external kernels
    #: keep the loop path.
    supports_scan: bool = False

    def scan_round_fn(self):
        """Pure round function for the scanned driver, called as
        ``fn(loss, max_steps, gram, data, state, K, q_t, budgets, gamma,
        key)`` (``gram`` = a forced residual mode, a static argument like
        ``loss``/``max_steps``; the scanned driver passes None).

        Must be a stable module-level callable (it is a static jit argument)
        whose results are bit-identical to ``round``.  Only meaningful when
        ``supports_scan`` is True.
        """
        raise NotImplementedError(
            f"engine {self.name!r} does not support the scanned driver")

    @abc.abstractmethod
    def setup(self, data: FederatedData, loss: Loss, max_steps: int,
              gram: Optional[bool] = None) -> DualState:
        """Bind the engine to a problem; return the initial dual state."""

    @abc.abstractmethod
    def round(self, state: DualState, K: Array, q_t: Array, budgets: Array,
              gamma: float, key: Array) -> DualState:
        """One round: every node solves its local subproblem, server reduces."""


@partial(jax.jit, static_argnums=(0, 1, 2))
def _local_round(loss: Loss, max_steps: int, gram: Optional[bool],
                 data: FederatedData, state: DualState, K: Array, q_t: Array,
                 budgets: Array, gamma: float, key: Array) -> DualState:
    W = dual_mod.primal_weights(K, state.v)
    keys = jax.random.split(key, data.m)
    dalpha, u = batched_local_sdca(
        loss, data.X, data.y, data.mask, state.alpha, W, q_t,
        budgets, keys, max_steps, xnorm2=data.xnorm2, gram=gram)
    return DualState(alpha=state.alpha + gamma * dalpha,
                     v=state.v + gamma * u)


class LocalEngine(RoundEngine):
    """Single-process vmapped SDCA: the reference execution path."""

    name = "local"
    supports_scan = True

    def setup(self, data: FederatedData, loss: Loss, max_steps: int,
              gram: Optional[bool] = None) -> DualState:
        self.data, self.loss, self.max_steps = data, loss, max_steps
        self.gram = gram
        return dual_mod.init_state(data)

    def round(self, state, K, q_t, budgets, gamma, key):
        return _local_round(self.loss, self.max_steps, self.gram, self.data,
                            state, K, q_t, budgets, gamma, key)

    def scan_round_fn(self):
        return _local_round


@partial(jax.jit, static_argnums=(0, 1, 2))
def _pallas_round(max_steps: int, interpret: bool, gram: Optional[bool],
                  data: FederatedData, state: DualState, K: Array,
                  q_t: Array, budgets: Array, gamma: float,
                  key: Array) -> DualState:
    from repro.kernels.sdca.ops import kernel_local_sdca
    W = dual_mod.primal_weights(K, state.v)
    keys = jax.random.split(key, data.m)
    dalpha, u = kernel_local_sdca(data, state.alpha, W, q_t, budgets, keys,
                                  max_steps, interpret=interpret, gram=gram)
    return DualState(alpha=state.alpha + gamma * dalpha,
                     v=state.v + gamma * u)


class PallasEngine(RoundEngine):
    """Fused Pallas SDCA kernel (hinge loss).

    ``interpret=None`` resolves per backend: compiled on TPU, interpret mode
    on CPU/GPU (where the TPU lowering is unavailable but semantics are
    preserved for testing).
    """

    name = "pallas"

    def __init__(self, interpret: Optional[bool] = None):
        self.interpret = interpret

    def setup(self, data: FederatedData, loss: Loss, max_steps: int,
              gram: Optional[bool] = None) -> DualState:
        if loss.name != "hinge":
            raise ValueError(
                f"PallasEngine implements the hinge kernel only, got "
                f"{loss.name!r}; use engine='local' for other losses.")
        self.data, self.max_steps, self.gram = data, max_steps, gram
        self._interpret = (jax.default_backend() != "tpu"
                           if self.interpret is None else self.interpret)
        return dual_mod.init_state(data)

    def round(self, state, K, q_t, budgets, gamma, key):
        return _pallas_round(self.max_steps, self._interpret, self.gram,
                             self.data, state, K, q_t, budgets, gamma, key)


class ShardedEngine(RoundEngine):
    """shard_map runtime: tasks sharded over the mesh ``data`` axis.

    Data/alpha/budgets/keys live task-sharded; v is replicated and the
    per-round Delta v exchange is one all_gather.  The task axis is padded to
    a multiple of the shard count with empty tasks (mask = 0, budget = 0)
    which provably receive zero updates; the driver only sees real-size
    state.  ``comm_dtype`` optionally quantizes the wire tensor (e.g. bf16).
    """

    name = "sharded"

    def __init__(self, mesh=None, comm_dtype=None):
        self._mesh_arg = mesh
        self.comm_dtype = comm_dtype

    def setup(self, data: FederatedData, loss: Loss, max_steps: int,
              gram: Optional[bool] = None) -> DualState:
        from repro.federated import sharding
        from repro.federated.runtime import make_federated_mesh
        self.mesh = self._mesh_arg or make_federated_mesh()
        self.loss, self.max_steps, self.gram = loss, max_steps, gram
        self.data_p, _ = sharding.pad_tasks(data, self.mesh.devices.size)
        self.m_real, self.m_pad = data.m, self.data_p.m
        self._K_src = self._q_src = None
        return dual_mod.init_state(data)

    def _padded_coupling(self, K: Array, q_t: Array):
        # K/q_t only change on an Omega refresh; cache the O(m^2) pad by
        # identity instead of re-padding every round
        from repro.federated import sharding
        if self._K_src is not K:
            self._K_src = K
            self._K_p = sharding.pad_task_matrix(K, self.m_pad)
        if self._q_src is not q_t:
            self._q_src = q_t
            self._q_p = sharding.pad_vector(q_t, self.m_pad, fill=1.0)
        return self._K_p, self._q_p

    def _pad_keys(self, key: Array) -> Array:
        # split for the REAL tasks (cross-engine key parity), pad with nulls:
        # padded tasks have budget 0 and mask 0, so their draws never matter
        keys = jax.random.split(key, self.m_real)
        if self.m_pad == self.m_real:
            return keys
        extra = jnp.zeros((self.m_pad - self.m_real,) + keys.shape[1:],
                          keys.dtype)
        return jnp.concatenate([keys, extra], axis=0)

    def round(self, state, K, q_t, budgets, gamma, key):
        from repro.federated import sharding
        from repro.federated.runtime import distributed_round
        m_pad = self.m_pad
        alpha = sharding.pad_vector(state.alpha, m_pad)
        v = sharding.pad_vector(state.v, m_pad)
        K_p, q_p = self._padded_coupling(K, q_t)
        b_p = sharding.pad_vector(budgets.astype(jnp.int32), m_pad)
        alpha, v = distributed_round(
            self.mesh, self.loss, self.max_steps, self.data_p, alpha, v,
            K_p, q_p, b_p, gamma, self._pad_keys(key),
            comm_dtype=self.comm_dtype, gram=self.gram)
        return DualState(alpha=alpha[:self.m_real], v=v[:self.m_real])


ENGINES = {"local": LocalEngine, "pallas": PallasEngine,
           "sharded": ShardedEngine}


def get_engine(spec=None) -> RoundEngine:
    """Resolve an engine spec: None | name | class | instance."""
    if spec is None:
        return LocalEngine()
    if isinstance(spec, RoundEngine):
        return spec
    if isinstance(spec, str):
        if spec not in ENGINES:
            raise KeyError(
                f"unknown engine {spec!r}; available: {sorted(ENGINES)}")
        return ENGINES[spec]()
    if isinstance(spec, type) and issubclass(spec, RoundEngine):
        return spec()
    raise TypeError(f"cannot resolve engine from {spec!r}")
