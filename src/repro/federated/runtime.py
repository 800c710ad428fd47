"""Distributed MOCHA federated round via shard_map.

Communication pattern (the paper's Section 3.3 mapped to TPU collectives):

  * alpha, X, y, mask, budgets:  sharded over the ``data`` mesh axis (tasks)
  * v = X alpha (m, d):          replicated; the per-round update Delta v is
                                 produced shard-locally and exchanged with ONE
                                 ``jax.lax.all_gather`` over ``data`` -- this
                                 is the paper's "only v_t must be communicated"
  * K rows:                      each shard holds the rows of K = Abar^{-1}
                                 for its own tasks (w_t = 1/2 K_t: V needs all
                                 of v but only local rows of K)

The shard-local solve is the same ``batched_local_sdca`` used by the
single-process driver, so distributed and local runs are bit-identical given
the same budgets and keys (tested in tests/test_runtime.py).
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.dual import FederatedData
from repro.core.losses import Loss
from repro.core.subproblem import batched_local_sdca
from repro.utils.jax_compat import F32_DOT

Array = jax.Array


def make_federated_mesh(n_shards: int | None = None) -> Mesh:
    """1-D mesh over the ``data`` axis for the MTL runtime."""
    devices = jax.devices()
    n = n_shards or len(devices)
    return jax.make_mesh((n,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))


@partial(jax.jit, static_argnums=(0, 1, 2),
         static_argnames=("comm_dtype", "gram"))
def distributed_round(mesh: Mesh, loss: Loss, max_steps: int,
                      data: FederatedData, alpha: Array, v: Array,
                      K: Array, q_t: Array, budgets: Array, gamma: float,
                      keys: Array, comm_dtype=None,
                      gram=None) -> Tuple[Array, Array]:
    """One federated W-round, tasks sharded over mesh axis ``data``.

    Args:
      data/alpha/q_t/budgets/keys: task-major arrays, m divisible by |data|.
      v: replicated (m, d) communicated state.
      K: (m, m); rows are distributed, columns stay full.
      comm_dtype: optional wire dtype for the Delta v exchange (beyond-paper:
        bf16 halves the round's only communicated tensor; the replicated v
        accumulator stays f32 so quantization error does not compound --
        validated in tests/test_runtime.py).
      gram: forced residual mode; None (what the driver passes) keeps the
        shared ``_solver_plan`` rule.
    Returns (alpha', v') with the same shardings.  Jitted on the static
    (mesh, loss, max_steps, comm_dtype, gram), so every round after the
    first reuses one compiled program.
    """
    task_sharded = P("data")
    replicated = P()
    # the per-run hoisted row-norm table is task-major state like X; compute
    # it here only for direct callers (dry-run lowerings) that skip _run_mocha
    from repro.core.subproblem import row_norms
    xnorm2 = data.xnorm2 if data.xnorm2 is not None else row_norms(data.X)

    def shard_fn(X_sh, y_sh, mask_sh, xn_sh, alpha_sh, v_full, K_rows, q_sh,
                 budgets_sh, keys_sh):
        # local W rows for this shard's tasks: w_t = 1/2 sum_s K_ts v_s
        W_sh = 0.5 * jnp.matmul(K_rows, v_full, precision=F32_DOT)
        dalpha, u = batched_local_sdca(
            loss, X_sh, y_sh, mask_sh, alpha_sh, W_sh, q_sh, budgets_sh,
            keys_sh, max_steps, xnorm2=xn_sh, gram=gram)
        # THE federated communication: exchange Delta v blocks
        wire = u if comm_dtype is None else u.astype(comm_dtype)
        du_full = jax.lax.all_gather(wire, "data", tiled=True)
        du_full = du_full.astype(v_full.dtype)
        return alpha_sh + gamma * dalpha, v_full + gamma * du_full

    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(task_sharded, task_sharded, task_sharded, task_sharded,
                  task_sharded, replicated, task_sharded, task_sharded,
                  task_sharded, task_sharded),
        out_specs=(task_sharded, replicated),
        # the solver builds zero-initialized carries internally; their varying
        # manual axes are established by the first masked update
        check_vma=False,
    )
    return fn(data.X, data.y, data.mask, xnorm2, alpha, v, K, q_t, budgets,
              keys)


def lower_federated_round(mesh: Mesh, loss: Loss, max_steps: int,
                          m: int, n_max: int, d: int):
    """Lower (no execution) the distributed round for dry-run inspection.

    Every argument carries the ``NamedSharding`` the run gives it on
    ``mesh`` (task-major state split over ``data``, v replicated), so the
    mesh may hold described devices (``jax.experimental.topologies``) and
    ``.compile()`` then compiles for that chip without one attached."""
    f32 = jnp.float32
    task = NamedSharding(mesh, P("data"))
    repl = NamedSharding(mesh, P())

    def sds(shape, dtype=f32, sharding=task):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    data = FederatedData(X=sds((m, n_max, d)), y=sds((m, n_max)),
                         mask=sds((m, n_max)), xnorm2=sds((m, n_max)))
    args = (data, sds((m, n_max)), sds((m, d), sharding=repl),
            sds((m, m)), sds((m,)), sds((m,), jnp.int32),
            sds((m, 2), jnp.uint32))

    def step(data, alpha, v, K, q_t, budgets, keys):
        return distributed_round(mesh, loss, max_steps, data, alpha, v, K,
                                 q_t, budgets, 1.0, keys)

    return jax.jit(step).lower(*args)
