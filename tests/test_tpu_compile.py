"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler is installed alongside jax; ``jax.experimental.topologies``
describes a ``v5e:2x2`` host and ``.compile()`` then raises whatever the
chip's compiler would (unaligned blocks, unlowerable primitives, VMEM or HBM
overflow) -- failures interpret mode cannot show.  Shapes are the paper's
federations at full width (``repro.data.synthetic``): Human Activity
(d=561, carry mode) and Vehicle Sensor (d=100, gram mode).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every pytest-xdist worker imports
this module.  All compiles stay in this one file for the same reason.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.core.dual import DualState, FederatedData
from repro.core.engine import _local_round
from repro.core.losses import get_loss
from repro.core.subproblem import _solver_plan
from repro.core.theta import BudgetConfig
from repro.data.synthetic import HUMAN_ACTIVITY, VEHICLE_SENSOR
from repro.kernels.sdca.sdca import sdca_local_solve

F32, I32 = jnp.float32, jnp.int32
#: (m, n_max, d) of make_federation(spec)'s train split, seed 0
SHAPES = {
    HUMAN_ACTIVITY.name: (HUMAN_ACTIVITY.m, 228, HUMAN_ACTIVITY.d),
    VEHICLE_SENSOR.name: (VEHICLE_SENSOR.m, 1426, VEHICLE_SENSOR.d),
}
#: one v5e chip's HBM
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _fits(compiled) -> None:
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used} bytes on a {HBM_BYTES}-byte chip"


@pytest.mark.parametrize("fed", sorted(SHAPES))
def test_local_round_compiles_for_v5e(fed, one_chip, no_persistent_cache):
    """The default engine's round (the scan driver's body)."""
    m, n, d = SHAPES[fed]
    steps = BudgetConfig().max_steps(n)

    def sds(shape, dtype=F32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    data = FederatedData(X=sds((m, n, d)), y=sds((m, n)), mask=sds((m, n)),
                         xnorm2=sds((m, n)))
    state = DualState(alpha=sds((m, n)), v=sds((m, d)))
    compiled = _local_round.lower(
        get_loss("hinge"), steps, None, data, state, sds((m, m)), sds((m,)),
        sds((m,), I32), sds(()), sds((2,), jnp.uint32)).compile()
    _fits(compiled)


@pytest.mark.parametrize("fed", sorted(SHAPES))
def test_sdca_kernel_compiles_for_v5e(fed, one_chip, no_persistent_cache):
    """``engine='pallas'``'s kernel, compiled (not interpreted): the lowered
    program must hold the Mosaic custom call."""
    m, n, d = SHAPES[fed]
    steps = BudgetConfig().max_steps(n)

    def sds(shape, dtype=F32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = sdca_local_solve.lower(
        sds((m, n, d)), sds((m, n)), sds((m, n)), sds((m, n)), sds((m, d)),
        sds((m,)), sds((m,), I32), sds((m, steps), I32), max_steps=steps,
        interpret=False, xnorm2=sds((m, n))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)
    # the residual mode the compile covered (carry at d=561, gram at d=100)
    assert _solver_plan(d, steps)[0] == (d <= 128)


@pytest.mark.parametrize("fed", sorted(SHAPES))
def test_sharded_round_compiles_for_v5e_2x2(fed, topo, no_persistent_cache):
    """``engine='sharded'``: tasks split over a 4-chip ``data`` mesh (m
    padded to a multiple of 4), Delta v exchanged by one all-gather."""
    from repro.federated.runtime import lower_federated_round
    m, n, d = SHAPES[fed]
    m_pad = -(-m // 4) * 4
    mesh = Mesh(np.asarray(topo.devices).reshape(-1), ("data",))
    compiled = lower_federated_round(mesh, get_loss("hinge"),
                                     BudgetConfig().max_steps(n),
                                     m_pad, n, d).compile()
    text = compiled.as_text()
    assert "all-gather" in text
    _fits(compiled)
