"""Round-engine parity: local / pallas / sharded backends of the ONE driver
produce bit-identical results -- as do the scanned and loop drivers -- plus
the shard_map runtime's own invariants."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (HISTORY_KEYS, BudgetConfig, MeanRegularized,
                        MochaConfig, PallasEngine, Probabilistic,
                        ShardedEngine, get_engine, get_loss, sigma_prime)
from repro.core.mocha import _run_mocha
from repro.core.systems_model import SystemsConfig
from repro.data.synthetic import tiny_problem
from repro.federated.runtime import distributed_round, make_federated_mesh
from repro.federated.sharding import pad_task_matrix, pad_tasks, pad_vector

REG = MeanRegularized(0.5, 0.5)

ENGINES = ("local", "pallas", "sharded")


@pytest.fixture(scope="module")
def engine_runs():
    """One heterogeneous run (stragglers + drops) per engine, same seed."""
    train, _ = tiny_problem(m=5, n=24, d=6, seed=2)
    cfg = MochaConfig(
        loss="hinge", rounds=12,
        budget=BudgetConfig(passes=1.0, systems_lo=0.5, drop_prob=0.3),
        record_every=4, seed=3)
    return {e: _run_mocha(train, REG, cfg, engine=e) for e in ENGINES}


def _assert_runs_bit_identical(a, b):
    np.testing.assert_array_equal(np.asarray(a.state.alpha),
                                  np.asarray(b.state.alpha))
    np.testing.assert_array_equal(np.asarray(a.state.v),
                                  np.asarray(b.state.v))
    np.testing.assert_array_equal(a.W, b.W)
    assert a.history == b.history
    np.testing.assert_array_equal(a.round_budgets, b.round_budgets)


@pytest.mark.parametrize("other", ["pallas", "sharded"])
def test_engine_parity_bit_identical(engine_runs, other):
    """Same seed/budgets => bit-identical (alpha, v), W, and history."""
    _assert_runs_bit_identical(engine_runs["local"], engine_runs[other])


# engine-parity scenario matrix (DESIGN.md section 2): every engine must be
# bit-identical under gamma < 1, Omega refreshes, the semi_sync clock-cycle
# deadline path, and under BOTH residual modes of the v2 arithmetic --
# d = 6 exercises the default gram mode, d = 72 the carry mode
_ENGINE_CASES = {
    "gamma_half": dict(
        problem=dict(m=4, n=20, d=6, seed=4),
        cfg=MochaConfig(loss="hinge", rounds=10, gamma=0.5,
                        budget=BudgetConfig(passes=1.0), record_every=4,
                        seed=1)),
    "omega_refresh": dict(
        problem=dict(m=4, n=20, d=6, seed=0),
        cfg=MochaConfig(loss="hinge", rounds=12, omega_update_every=4,
                        record_every=4, seed=0)),
    "semi_sync": dict(
        problem=dict(m=4, n=20, d=6, seed=5),
        cfg=MochaConfig(loss="hinge", rounds=8, record_every=2, seed=5,
                        systems=SystemsConfig(
                            network="3g", policy="semi_sync",
                            clock_cycle_s=0.001, rate_lo=0.5, rate_hi=1.5,
                            straggler_prob=0.3, comm_jitter=0.2))),
    "carry_mode": dict(   # d > _GRAM_MAX_D: the large-d residual-carry path
        problem=dict(m=3, n=18, d=160, seed=2),
        cfg=MochaConfig(loss="hinge", rounds=8,
                        budget=BudgetConfig(passes=1.0, systems_lo=0.5,
                                            drop_prob=0.3),
                        record_every=3, seed=7)),
}


@pytest.mark.parametrize("other", ["pallas", "sharded"])
@pytest.mark.parametrize("case", sorted(_ENGINE_CASES))
def test_engine_parity_scenarios(case, other):
    from repro.core.subproblem import _GRAM_MAX_D
    spec = _ENGINE_CASES[case]
    if case == "carry_mode":
        assert spec["problem"]["d"] > _GRAM_MAX_D
    train, _ = tiny_problem(**spec["problem"])
    ref = _run_mocha(train, REG, spec["cfg"], engine="local")
    got = _run_mocha(train, REG, spec["cfg"], engine=other)
    _assert_runs_bit_identical(ref, got)


def test_engine_history_schema_parity(engine_runs):
    """One schema across every engine (the old distributed driver dropped
    round_max_steps); EVERY column follows the record cadence, so histories
    are rectangular (the old driver appended round_max_steps per round)."""
    # rounds=12, record_every=4 -> records at rounds 0, 4, 8 and the last (11)
    for e in ENGINES:
        h = engine_runs[e].history
        assert set(h) == set(HISTORY_KEYS)
        lengths = {k: len(v) for k, v in h.items()}
        assert set(lengths.values()) == {4}, lengths
        assert h["round"] == [0, 4, 8, 11]


# scan/loop driver parity scenarios: heterogeneous budgets + drops, gamma<1,
# Omega refreshes, and the semi_sync clock-cycle deadline path
_PARITY_CASES = {
    "hetero": (MochaConfig(
        loss="hinge", rounds=12,
        budget=BudgetConfig(passes=1.0, systems_lo=0.5, drop_prob=0.3),
        record_every=4, seed=3), MeanRegularized(0.5, 0.5)),
    "gamma_half": (MochaConfig(
        loss="smooth_hinge", rounds=15, gamma=0.5,
        budget=BudgetConfig(passes=1.0), record_every=3, seed=1),
        MeanRegularized(0.5, 0.5)),
    "omega_refresh": (MochaConfig(
        loss="hinge", rounds=20, omega_update_every=6, record_every=4,
        seed=0), Probabilistic(lam=0.1, sigma2=10.0)),
    "semi_sync": (MochaConfig(
        loss="hinge", rounds=10, record_every=2, seed=5,
        systems=SystemsConfig(network="3g", policy="semi_sync",
                              clock_cycle_s=0.001, rate_lo=0.5, rate_hi=1.5,
                              straggler_prob=0.3, comm_jitter=0.2)),
        MeanRegularized(0.5, 0.5)),
}


@pytest.mark.parametrize("case", sorted(_PARITY_CASES))
def test_scan_loop_driver_parity(case):
    """The device-resident scanned driver is bit-identical to the Python
    round loop on a fixed seed: state, history, and executed budgets."""
    train, _ = tiny_problem(m=5, n=24, d=6, seed=2)
    cfg, reg = _PARITY_CASES[case]
    loop = _run_mocha(train, reg, dataclasses.replace(cfg, driver="loop"))
    scan = _run_mocha(train, reg, dataclasses.replace(cfg, driver="scan"))
    np.testing.assert_array_equal(np.asarray(loop.state.alpha),
                                  np.asarray(scan.state.alpha))
    np.testing.assert_array_equal(np.asarray(loop.state.v),
                                  np.asarray(scan.state.v))
    np.testing.assert_array_equal(loop.W, scan.W)
    np.testing.assert_array_equal(loop.round_budgets, scan.round_budgets)
    assert loop.history == scan.history


def test_scan_loop_parity_on_reused_trace():
    """A pre-used SystemsTrace continues its clock: both drivers must record
    the continuation times, not re-index from the trace's first event."""
    from repro.core.systems_model import SystemsTrace
    train, _ = tiny_problem(m=4, n=16, d=5, seed=7)
    cfg = MochaConfig(loss="hinge", rounds=4, record_every=2, seed=2)
    histories = {}
    for driver in ("loop", "scan"):
        trace = SystemsTrace(train.m, train.d, SystemsConfig(network="lte"))
        trace.advance(np.full(train.m, 7))     # prior simulation activity
        res = _run_mocha(train, MeanRegularized(0.5, 0.5),
                         dataclasses.replace(cfg, driver=driver), trace=trace)
        assert res.history["time"][0] > trace.events[0].duration_s
        histories[driver] = res.history
    assert histories["loop"] == histories["scan"]


def test_scan_driver_is_default_for_local():
    """driver='auto' takes the scanned path on LocalEngine and matches it."""
    train, _ = tiny_problem(m=4, n=16, d=5, seed=7)
    cfg = MochaConfig(loss="hinge", rounds=8, record_every=3, seed=2)
    auto = _run_mocha(train, MeanRegularized(0.5, 0.5), cfg)
    scan = _run_mocha(train, MeanRegularized(0.5, 0.5),
                      dataclasses.replace(cfg, driver="scan"))
    assert auto.history == scan.history
    np.testing.assert_array_equal(auto.W, scan.W)


def test_scan_driver_rejected_without_capability():
    train, _ = tiny_problem(m=4, n=16, d=5, seed=7)
    cfg = MochaConfig(loss="hinge", rounds=2, engine="sharded", driver="scan")
    with pytest.raises(ValueError, match="scanned driver"):
        _run_mocha(train, MeanRegularized(0.5, 0.5), cfg)
    assert not get_engine("sharded").supports_scan
    assert not get_engine("pallas").supports_scan
    assert get_engine("local").supports_scan


def test_engine_parity_dropped_node_through_pallas():
    """budget = 0 (the paper's dropped node) must be a no-op through the
    Pallas kernel exactly as through the reference solver."""
    train, _ = tiny_problem(m=4, n=16, d=5, seed=7)
    cfg = MochaConfig(loss="hinge", rounds=6, record_every=5, seed=1)

    def budget_fn(key, n_t, h):
        return jnp.full((4,), 10, jnp.int32).at[2].set(0)

    res = {e: _run_mocha(train, REG, cfg, engine=e, budget_fn=budget_fn)
           for e in ENGINES}
    for e in ("pallas", "sharded"):
        np.testing.assert_array_equal(np.asarray(res["local"].state.v),
                                      np.asarray(res[e].state.v))
    # node 2 never ran a step: its dual block must be exactly zero
    assert float(jnp.abs(res["pallas"].state.alpha[2]).max()) == 0.0
    assert float(jnp.abs(res["pallas"].state.v[2]).max()) == 0.0


def test_pallas_engine_rejects_non_hinge():
    train, _ = tiny_problem(m=3, n=12, d=4, seed=0)
    cfg = MochaConfig(loss="logistic", rounds=2, engine="pallas")
    with pytest.raises(ValueError, match="hinge"):
        _run_mocha(train, REG, cfg)


def test_get_engine_resolution():
    assert get_engine().name == "local"
    assert get_engine("sharded").name == "sharded"
    eng = PallasEngine(interpret=True)
    assert get_engine(eng) is eng
    with pytest.raises(KeyError):
        get_engine("warp")


def test_pad_tasks_roundtrip():
    train, _ = tiny_problem(m=5, n=20, d=6)
    padded, m_real = pad_tasks(train, 4)
    assert m_real == 5
    assert padded.m == 8
    assert float(padded.mask[5:].sum()) == 0.0
    np.testing.assert_array_equal(np.asarray(padded.X[:5]),
                                  np.asarray(train.X))


def test_pad_task_matrix_identity_block():
    K = jnp.asarray(np.random.default_rng(0).normal(0, 1, (3, 3)),
                    jnp.float32)
    Kp = pad_task_matrix(K, 5)
    np.testing.assert_array_equal(np.asarray(Kp[:3, :3]), np.asarray(K))
    np.testing.assert_array_equal(np.asarray(Kp[3:, 3:]), np.eye(2))
    assert float(jnp.abs(Kp[:3, 3:]).sum()) == 0.0


def test_distributed_round_matches_local():
    """Same budgets + same per-task keys => bit-identical update."""
    train, _ = tiny_problem(m=4, n=16, d=5, seed=1)
    loss = get_loss("hinge")
    K = REG.K(REG.init_omega(train.m))
    sig = sigma_prime(K)
    q_t = sig * jnp.diagonal(K) / 2.0
    budgets = jnp.asarray([16, 8, 16, 4], jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(3), train.m)
    alpha0 = jnp.zeros_like(train.y)
    v0 = jnp.zeros((train.m, train.d))

    # local reference
    from repro.core.dual import primal_weights
    from repro.core.subproblem import batched_local_sdca
    W = primal_weights(K, v0)
    dalpha, u = batched_local_sdca(loss, train.X, train.y, train.mask,
                                   alpha0, W, q_t, budgets, keys, 16)
    alpha_ref, v_ref = alpha0 + dalpha, v0 + u

    mesh = make_federated_mesh()  # 1 device -> 1 shard, still exercises path
    alpha_d, v_d = distributed_round(mesh, loss, 16, train, alpha0, v0, K,
                                     q_t, budgets, 1.0, keys)
    np.testing.assert_allclose(np.asarray(alpha_d), np.asarray(alpha_ref),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(v_d), np.asarray(v_ref), atol=1e-5)


def test_distributed_driver_converges():
    train, _ = tiny_problem(m=5, n=24, d=6, seed=2)
    cfg = MochaConfig(loss="hinge", rounds=60, budget=BudgetConfig(passes=2.0),
                      record_every=59)
    res = _run_mocha(train, REG, cfg, engine=ShardedEngine())
    rel_gap = res.final("gap") / max(abs(res.final("primal")), 1.0)
    assert rel_gap < 5e-3


def test_distributed_matches_serial_driver():
    train, _ = tiny_problem(m=6, n=20, d=6, seed=4)
    cfg = MochaConfig(loss="smooth_hinge", rounds=40,
                      budget=BudgetConfig(passes=1.0), record_every=39)
    serial = _run_mocha(train, REG, cfg)
    dist = _run_mocha(train, REG, cfg, engine=ShardedEngine())
    # identical problem, same convergence target; allow solver-path noise
    np.testing.assert_allclose(dist.final("primal"), serial.final("primal"),
                               rtol=1e-2)


def test_forced_gram_threads_to_engines():
    """A mode forced through ``setup(..., gram=True)`` reaches every
    engine's solver: forced gram above the crossover (d=160) stays
    bit-identical across engines (the gram GEMM primitives are the
    context-stable ones) and differs from the default plan, which runs
    carry there (same optimum, different association)."""
    from repro.core import dual as dual_mod
    from repro.core.mocha import _coupling_terms
    from repro.core.subproblem import _GRAM_MAX_D
    train, _ = tiny_problem(m=3, n=18, d=160, seed=2)
    assert train.d > _GRAM_MAX_D
    data, loss = dual_mod.with_xnorm2(train), get_loss("hinge")
    max_steps = BudgetConfig().max_steps(train.n_max)
    _, K, q_t = _coupling_terms(REG, REG.init_omega(train.m), 1.0, True,
                                train.m)
    budgets = jnp.asarray([max_steps, max_steps // 2, 5], jnp.int32)

    def rounds(engine, gram):
        eng = get_engine(engine)
        state = eng.setup(data, loss, max_steps, gram=gram)
        for r in range(3):
            state = eng.round(state, K, q_t, budgets, 1.0,
                              jax.random.PRNGKey(r))
        return state

    forced = {e: rounds(e, True) for e in ENGINES}
    for other in ("pallas", "sharded"):
        for a, b in zip(forced["local"], forced[other]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    default = rounds("local", None)
    assert not np.array_equal(np.asarray(default.v),
                              np.asarray(forced["local"].v))


def test_lowered_round_contains_all_gather():
    """The round's HLO must contain exactly the paper's communication: an
    all-gather of the Delta v blocks (and nothing heavier)."""
    from repro.federated.runtime import lower_federated_round
    mesh = make_federated_mesh()
    loss = get_loss("hinge")
    lowered = lower_federated_round(mesh, loss, 8, m=4, n_max=8, d=4)
    txt = lowered.as_text()
    assert "all-gather" in txt or "all_gather" in txt
