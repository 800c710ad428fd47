"""Event-driven SystemsTrace: per-node clock semantics, round policies, and
back-compat with the scalar wall-clock model."""
import numpy as np
import pytest

from repro.core import (BudgetConfig, MeanRegularized, MochaConfig,
                        SystemsConfig, SystemsTrace)
from repro.core import systems_model
from repro.core.mocha import _run_mocha
from repro.data.synthetic import tiny_problem

REG = MeanRegularized(0.5, 0.5)


def test_default_trace_matches_scalar_model():
    """Homogeneous default config reproduces round_time_sync exactly."""
    m, d = 4, 10
    net = systems_model.NETWORKS["lte"]
    trace = SystemsTrace(m, d, SystemsConfig(network="lte"))
    steps = np.asarray([100, 40, 0, 250])
    dur = trace.advance(steps)
    assert dur == systems_model.round_time_sync(steps, d, net)
    assert trace.elapsed_s == dur


def test_dropped_node_pays_message_slot_only():
    trace = SystemsTrace(3, 8, SystemsConfig(network="3g"))
    trace.advance(np.asarray([0, 0, 0]))
    ev = trace.events[0]
    assert np.all(ev.compute_s == 0.0)
    assert np.all(ev.dropped)
    assert ev.duration_s == pytest.approx(
        systems_model.comm_time(systems_model.NETWORKS["3g"], 8.0 * 8))


def test_heterogeneous_rates_are_deterministic_by_seed():
    cfg = SystemsConfig(rate_lo=0.2, rate_hi=1.0, seed=11)
    a, b = SystemsTrace(6, 5, cfg), SystemsTrace(6, 5, cfg)
    np.testing.assert_array_equal(a.rates, b.rates)
    assert a.rates.min() >= 0.2 * systems_model.CLOCK_FLOPS
    assert len(np.unique(a.rates)) > 1


def test_straggler_tail_slows_round():
    base = SystemsTrace(8, 10, SystemsConfig(seed=0))
    tail = SystemsTrace(8, 10, SystemsConfig(
        straggler_prob=1.0, straggler_mult=10.0, seed=0))
    steps = np.full(8, 500)
    assert tail.advance(steps) > base.advance(steps)


def test_semi_sync_caps_and_deadline_duration():
    cfg = SystemsConfig(policy="semi_sync", clock_cycle_s=0.01,
                        rate_lo=0.5, rate_hi=1.0, seed=3)
    trace = SystemsTrace(5, 10, cfg)
    cap = trace.begin_round()
    assert cap is not None and cap.shape == (5,)
    # feasible steps: exactly what fits the deadline at that node's rate
    expected = np.floor(0.01 * trace._round_rates
                        / systems_model.SDCA_STEP_FLOPS(10))
    np.testing.assert_array_equal(cap, expected.astype(np.int64))
    dur = trace.commit(np.minimum(cap, 100))
    comm = trace.events[0].comm_s
    assert dur == pytest.approx(0.01 + float(np.max(comm)))


def test_semi_sync_requires_deadline():
    with pytest.raises(ValueError, match="clock_cycle_s"):
        SystemsTrace(3, 4, SystemsConfig(policy="semi_sync"))


def test_begin_round_twice_is_an_error():
    trace = SystemsTrace(2, 4, SystemsConfig())
    trace.begin_round()
    with pytest.raises(RuntimeError):
        trace.begin_round()


def test_times_and_utilization_consistency():
    trace = SystemsTrace(3, 6, SystemsConfig(rate_lo=0.5, rate_hi=1.0,
                                             comm_jitter=0.2, seed=5))
    for steps in ([10, 200, 30], [0, 50, 50], [400, 1, 1]):
        trace.advance(np.asarray(steps))
    t = trace.times()
    assert len(t) == 3 and t[-1] == pytest.approx(trace.elapsed_s)
    assert np.all(np.diff(t) > 0)
    util = trace.utilization()
    assert np.all(util >= 0) and np.all(util <= 1.0)
    assert trace.summary()["rounds"] == 3


def test_driver_semi_sync_caps_budgets():
    """A tight clock cycle must shrink executed budgets vs the sync run."""
    train, _ = tiny_problem(m=5, n=30, d=8, seed=0)
    d = train.d
    # deadline that fits ~8.5 steps at the homogeneous rate (the .5 keeps
    # floor() away from a float-rounding boundary)
    cycle = 8.5 * systems_model.SDCA_STEP_FLOPS(d) / systems_model.CLOCK_FLOPS
    base = MochaConfig(loss="hinge", rounds=10,
                       budget=BudgetConfig(passes=1.0), record_every=9)
    sync = _run_mocha(train, REG, base)
    import dataclasses
    semi = _run_mocha(train, REG, dataclasses.replace(
        base, systems=SystemsConfig(policy="semi_sync", clock_cycle_s=cycle)))
    assert semi.round_budgets.max() == 8
    assert semi.round_budgets.max() < sync.round_budgets.max()
    # every round costs exactly deadline + comm, so less than the sync
    # straggler round at these budgets
    ev = semi.trace.events[0]
    assert ev.cap_steps is not None
    assert semi.final("time") == pytest.approx(
        10 * (cycle + float(np.max(ev.comm_s))))


# -- resilience hooks: charge + clock_state (repro.cohort.resilience) -------

_SEMI = SystemsConfig(policy="semi_sync", clock_cycle_s=0.01,
                      rate_lo=0.5, rate_hi=1.0, comm_jitter=0.2, seed=9)


def test_charge_consumes_no_rng_draws_under_presampled_caps():
    """Out-of-round charges must leave the round-indexed cap stream
    untouched: caps presampled BEFORE any charge must be exactly the caps
    the later rounds draw, however much overhead is charged in between."""
    trace = SystemsTrace(4, 6, _SEMI)
    caps = trace.presample_caps(3)
    assert caps is not None and caps.shape == (3, 4)
    elapsed = 0.0
    for r in range(3):
        elapsed += trace.charge(0.25 * (r + 1))   # backoff before the round
        live = trace.begin_round()
        np.testing.assert_array_equal(live, caps[r])
        elapsed += trace.commit(live)
        elapsed += trace.charge(0.125)            # fold delay after
    assert trace.elapsed_s == pytest.approx(elapsed)
    # charges are pure clock advances: no round event, no busy time
    assert len(trace.events) == 3
    assert trace.summary()["rounds"] == 3


def test_charge_guards():
    trace = SystemsTrace(2, 4, _SEMI)
    trace.begin_round()
    with pytest.raises(RuntimeError, match="mid-round"):
        trace.charge(1.0)
    trace.commit(np.zeros(2))
    with pytest.raises(ValueError, match=">= 0"):
        trace.charge(-0.1)
    assert trace.charge(0.0) == 0.0


def test_clock_state_round_trip_semi_sync():
    """restore_clock of a snapshot makes a fresh same-config trace redraw
    the continuation bit-identically -- caps, durations, clock and busy
    time -- with charges interleaved on both sides of the snapshot."""
    a = SystemsTrace(5, 8, _SEMI)
    for _ in range(2):
        a.commit(np.full(5, 50))
        a.charge(0.5)
    snap = a.clock_state()
    assert set(snap) == {"rng", "elapsed_s", "node_busy_s"}
    assert snap["rng"].shape == (6,) and snap["rng"].dtype == np.uint64

    b = SystemsTrace(5, 8, _SEMI)      # same config -> same static rates
    b.restore_clock(snap)
    assert b.elapsed_s == a.elapsed_s
    np.testing.assert_array_equal(b.node_busy_s, a.node_busy_s)
    for r in range(3):
        cap_a, cap_b = a.begin_round(), b.begin_round()
        np.testing.assert_array_equal(cap_a, cap_b)
        steps = np.minimum(cap_a, 20 + r)
        assert a.commit(steps) == b.commit(steps)
        a.charge(0.125)
        b.charge(0.125)
    assert b.elapsed_s == a.elapsed_s
    np.testing.assert_array_equal(b.node_busy_s, a.node_busy_s)
    # the event log is NOT part of the snapshot: the resumed trace's
    # events hold only the continuation rounds
    assert len(a.events) == 5 and len(b.events) == 3


def test_clock_state_mid_round_guard():
    trace = SystemsTrace(3, 4, _SEMI)
    snap = trace.clock_state()
    trace.begin_round()
    with pytest.raises(RuntimeError, match="mid-round"):
        trace.clock_state()
    with pytest.raises(RuntimeError, match="mid-round"):
        trace.restore_clock(snap)


def test_driver_records_trace_and_budgets():
    train, _ = tiny_problem(m=4, n=16, d=5, seed=1)
    res = _run_mocha(train, REG, MochaConfig(
        loss="hinge", rounds=7, budget=BudgetConfig(passes=0.5),
        record_every=3))
    assert res.trace is not None and len(res.trace.events) == 7
    assert res.round_budgets.shape == (7, 4)
    assert res.final("time") == pytest.approx(res.trace.elapsed_s)
