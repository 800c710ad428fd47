"""Fault-tolerant cohort runtime (repro.cohort.resilience): deterministic
fault injection, retry with graceful degradation, Assumption-2 guarding,
and bit-identical checkpoint/resume on both block loops."""
import dataclasses
import threading

import numpy as np
import pytest

from repro.cohort import (BlockFailure, CohortConfig, CohortPacker,
                          FaultConfig, FaultPlan, InjectedFault, Population,
                          PopulationSpec, run_mocha_cohort)
from repro.cohort.resilience import (ASSUMPTION2_MAX_P, backoff_delay,
                                     run_fingerprint)
from repro.core import BudgetConfig, MochaConfig, Probabilistic
from repro.train import checkpoint as ckpt

SPEC = PopulationSpec("t_res", m=400, d=12, n_min=12, n_max=32, clusters=3)
REG = Probabilistic(lam=1e-2, sigma2=10.0)


def _cfg(**kw):
    base = dict(rounds=8, cohort=16, clusters=3, dropout=0.2,
                omega_update_every=2, record_every=1, seed=1,
                inner=MochaConfig(budget=BudgetConfig(passes=1.0)))
    base.update(kw)
    return CohortConfig(**base)


def _expected_counts(plan):
    """Derive (retries, degraded) straight from the plan -- the wrapper's
    per-block ladder: pack attempts until success, then solve attempts
    until success; a seam failing every attempt degrades the block and
    skips the later seam entirely."""
    retries = degraded = 0
    for b in range(plan.rounds):
        pf, sf = plan.pack_fail[b], plan.solve_fail[b]
        if pf.all():
            retries += plan.attempts
            degraded += 1
            continue
        retries += int(np.argmax(~pf))
        if sf.all():
            retries += plan.attempts
            degraded += 1
            continue
        retries += int(np.argmax(~sf))
    return retries, degraded


# -- the plan ---------------------------------------------------------------

def test_fault_plan_presample_deterministic():
    fc = FaultConfig(pack_fail_prob=0.3, solve_fail_prob=0.3,
                     fold_delay_prob=0.5, fold_delay_s=2.5)
    a = FaultPlan.presample(fc, seed=7, rounds=20, max_retries=2)
    b = FaultPlan.presample(fc, seed=7, rounds=20, max_retries=2)
    np.testing.assert_array_equal(a.pack_fail, b.pack_fail)
    np.testing.assert_array_equal(a.solve_fail, b.solve_fail)
    np.testing.assert_array_equal(a.fold_delay_s, b.fold_delay_s)
    assert a.pack_fail.shape == (20, 3)
    # the run seed and the plan's own seed both move the schedule
    c = FaultPlan.presample(fc, seed=8, rounds=20, max_retries=2)
    d = FaultPlan.presample(dataclasses.replace(fc, seed=1), 7, 20, 2)
    assert not np.array_equal(a.solve_fail, c.solve_fail)
    assert not np.array_equal(a.solve_fail, d.solve_fail)
    # injected delays are the configured constant or zero
    assert set(np.unique(a.fold_delay_s)) <= {0.0, 2.5}


def test_fault_plan_hard_blocks_and_backoff_cap():
    fc = FaultConfig(solve_fail_blocks=(2, 5), pack_fail_blocks=(3,),
                     backoff_s=1.5, backoff_cap_s=10.0)
    plan = FaultPlan.presample(fc, seed=0, rounds=6, max_retries=3)
    assert plan.solve_fail[2].all() and plan.solve_fail[5].all()
    assert plan.pack_fail[3].all()
    np.testing.assert_array_equal(plan.degraded_blocks(),
                                  [False, False, True, True, False, True])
    # capped exponential: 1.5, 3, 6, then clamped at the cap
    assert [plan.backoff(a) for a in range(5)] == [1.5, 3.0, 6.0, 10.0, 10.0]
    assert backoff_delay(0) == 1.0 and backoff_delay(50, cap_s=60.0) == 60.0


def test_fault_config_validation():
    with pytest.raises(ValueError, match="solve_fail_prob"):
        FaultPlan.presample(FaultConfig(solve_fail_prob=1.5), 0, 4, 0)
    with pytest.raises(ValueError, match="backoff_s"):
        FaultPlan.presample(FaultConfig(backoff_s=-1.0), 0, 4, 0)
    with pytest.raises(ValueError, match="max_retries"):
        FaultPlan.presample(FaultConfig(), 0, 4, -1)


def test_assumption2_guard_aborts_before_running():
    """A plan that degrades (almost) every block pushes the effective
    per-client failure probability past the line -- the run must abort up
    front with the Assumption-2 diagnostic, not burn blocks."""
    plan = FaultPlan.presample(FaultConfig(solve_fail_prob=1.0), 0, 8, 0)
    with pytest.raises(ValueError, match="Assumption 2"):
        plan.validate_assumption2(0.0)
    # composed with dropout: each factor alone is under the line
    half = FaultPlan.presample(
        FaultConfig(solve_fail_blocks=tuple(range(0, 8))), 0, 8, 0)
    with pytest.raises(ValueError, match="Assumption 2"):
        half.validate_assumption2(ASSUMPTION2_MAX_P - 0.01)
    plan_ok = FaultPlan.presample(FaultConfig(solve_fail_prob=0.3), 0, 8, 2)
    plan_ok.validate_assumption2(0.2)        # comfortably below: no raise
    # end-to-end: the guard fires from the driver before any block runs
    pop = Population(SPEC, seed=0)
    with pytest.raises(ValueError, match="Assumption 2"):
        run_mocha_cohort(pop, REG, _cfg(
            degrade=True, faults=FaultConfig(solve_fail_prob=1.0)))


# -- zero-fault identity ----------------------------------------------------

def test_zero_fault_path_bit_identical(tmp_path):
    """Armed-but-silent resilience (zero-probability plan, retry budget,
    degradation, checkpointing) must not perturb a single bit of the run --
    the wrappers reduce to the bare pack/solve calls."""
    pop = Population(SPEC, seed=0)
    plain = run_mocha_cohort(pop, REG, _cfg())
    armed = run_mocha_cohort(pop, REG, _cfg(
        max_retries=2, degrade=True, faults=FaultConfig()))
    assert plain.history == armed.history
    np.testing.assert_array_equal(plain.centroids, armed.centroids)
    np.testing.assert_array_equal(plain.omega_k, armed.omega_k)
    np.testing.assert_array_equal(plain.assign, armed.assign)
    np.testing.assert_array_equal(plain.participation, armed.participation)
    assert (armed.fault_stats.retries,
            armed.fault_stats.degraded_blocks) == (0, 0)
    ck = run_mocha_cohort(pop, REG, _cfg(
        checkpoint_every=2, checkpoint_dir=str(tmp_path / "ck")))
    assert plain.history == ck.history
    np.testing.assert_array_equal(plain.centroids, ck.centroids)
    # and the pipelined loop keeps its staleness-0 parity with all of it on
    piped = run_mocha_cohort(pop, REG, _cfg(
        overlap=3, max_retries=2, degrade=True, faults=FaultConfig(),
        checkpoint_every=2, checkpoint_dir=str(tmp_path / "ck2")))
    assert plain.history == piped.history
    np.testing.assert_array_equal(plain.centroids, piped.centroids)


# -- retry and degradation --------------------------------------------------

def test_retries_complete_with_plan_derived_counts():
    """Transient faults retry to completion: the run's fault accounting
    matches counts derived independently from the plan, retries cost only
    SIMULATED time (backoff), and the model trajectory is untouched."""
    pop = Population(SPEC, seed=0)
    faults = FaultConfig(solve_fail_prob=0.3, pack_fail_prob=0.2, seed=0)
    cfg = _cfg(max_retries=2, degrade=True, faults=faults)
    plan = FaultPlan.presample(faults, cfg.seed, cfg.rounds, cfg.max_retries)
    want_retries, want_degraded = _expected_counts(plan)
    assert want_retries > 0 and want_degraded == 0   # transient-only plan
    res = run_mocha_cohort(pop, REG, cfg)
    assert res.fault_stats.retries == want_retries
    assert res.fault_stats.degraded_blocks == 0
    ref = run_mocha_cohort(pop, REG, _cfg())
    # backoff charges push the simulated clock past the clean run...
    assert res.final("time") > ref.final("time")
    # ...and change NOTHING else: same solves, same folds, same coverage
    for key in ref.history:
        if key != "time":
            assert res.history[key] == ref.history[key], key
    np.testing.assert_array_equal(res.centroids, ref.centroids)
    np.testing.assert_array_equal(res.participation, ref.participation)


def test_degraded_block_folds_as_dropped_nodes():
    """A block that exhausts its retries degrades to the theory's
    dropped-node semantics: zero participation (no state motion, no
    seen/participation increment) and carried-forward metrics."""
    pop = Population(SPEC, seed=0)
    dead = 2
    res = run_mocha_cohort(pop, REG, _cfg(
        max_retries=1, degrade=True,
        faults=FaultConfig(solve_fail_blocks=(dead,))))
    assert res.fault_stats.degraded_blocks == 1
    assert res.fault_stats.retries == 2          # both attempts at block 2
    h = res.history
    # metrics carry forward (nothing was solved at the dead block)...
    for key in ("dual", "primal", "gap"):
        assert h[key][dead] == h[key][dead - 1], key
    # ...while the clock still moved (zero-step rounds + backoff)
    assert h["time"][dead] > h["time"][dead - 1]
    # no client gained coverage or participation from the dead block
    assert h["unique_clients"][dead] == h["unique_clients"][dead - 1]
    sched = res.schedule.participation_counts(SPEC.m)
    lost = int((~res.schedule.dropped[dead]).sum())
    assert res.participation.sum() == sched.sum() - lost
    # later blocks still solve and record their own (real) metrics
    assert h["round_max_steps"][dead] == 0
    assert h["round_max_steps"][dead + 1] > 0


def test_block_failure_without_degradation_names_the_remedy():
    pop = Population(SPEC, seed=0)
    with pytest.raises(BlockFailure, match="degrade") as ei:
        run_mocha_cohort(pop, REG, _cfg(
            faults=FaultConfig(solve_fail_blocks=(1,))))
    assert (ei.value.block, ei.value.stage) == (1, "solve")


def _threads(monkeypatch, k):
    from repro.cohort import packing
    monkeypatch.setattr(packing, "_usable_cores", lambda: k)


def test_pack_retry_through_the_draw_pool_bit_identical(monkeypatch):
    """A pack that fails in one draw task and is retried gives the serial
    pack's federation: the retry overwrites every slot, and the failed
    call returned only after its other draws finished."""
    pop = Population(SPEC, seed=0)
    ids = np.arange(16) * 23
    _threads(monkeypatch, 1)
    serial = CohortPacker(pop, 16)
    want, want_sizes = serial.pack(ids)
    _threads(monkeypatch, 4)
    packer = CohortPacker(pop, 16)
    try:
        assert packer.threads == 4
        packer.pack(ids[::-1])                  # stale bytes in every slot
        real = pop.client_block

        def flaky(t):
            if t == int(ids[5]):
                raise InjectedFault("pack", 0, 0)
            return real(t)

        monkeypatch.setattr(pop, "client_block", flaky)
        with pytest.raises(InjectedFault):
            packer.pack(ids)
        monkeypatch.setattr(pop, "client_block", real)
        got, sizes = packer.pack(ids)
    finally:
        packer.close()
    np.testing.assert_array_equal(sizes, want_sizes)
    for name in ("X", "y", "mask", "xnorm2"):
        assert (np.asarray(getattr(got, name)).tobytes()
                == np.asarray(getattr(want, name)).tobytes()), name


def test_injected_pack_faults_retry_through_the_pool(monkeypatch):
    """Injected pack faults retried by the block loop give the same
    federation through the draw pool as a clean serial-pack run."""
    pop = Population(SPEC, seed=0)
    _threads(monkeypatch, 1)
    ref = run_mocha_cohort(pop, REG, _cfg())
    _threads(monkeypatch, 4)
    faults = FaultConfig(pack_fail_prob=0.4, seed=2)
    res = run_mocha_cohort(pop, REG, _cfg(max_retries=3, faults=faults))
    assert res.fault_stats.retries > 0
    for key in ref.history:
        if key != "time":
            assert res.history[key] == ref.history[key], key
    np.testing.assert_array_equal(res.centroids, ref.centroids)
    np.testing.assert_array_equal(res.assign, ref.assign)


@pytest.mark.parametrize("threads", [1, 4])
def test_oversized_client_raises_the_same_error(monkeypatch, threads):
    """A client with n_t > n_pad raises pack's ValueError, whether the
    draws ran serially or on the pool."""
    _threads(monkeypatch, threads)
    pop = Population(SPEC, seed=0)
    sizes = pop.client_sizes(np.arange(16))
    n_pad = int(np.sort(sizes)[-2]) - 1       # two clients do not fit
    big = int(np.flatnonzero(sizes > n_pad)[0])
    packer = CohortPacker(pop, 16, n_pad)
    try:
        with pytest.raises(ValueError, match=(
                f"client {big} has n_t={sizes[big]} > n_pad={n_pad}; "
                r"raise PopulationSpec.n_pad \(cohort shapes are static")):
            packer.pack(np.arange(16))
    finally:
        packer.close()


def test_packers_release_their_draw_threads(monkeypatch):
    """Opening, using and closing many packers (and whole runs, which
    close theirs) leaves the process's thread count where it started."""
    _threads(monkeypatch, 4)
    pop = Population(SPEC, seed=0)
    run_mocha_cohort(pop, REG, _cfg(rounds=1))     # warm JAX's own threads
    before = threading.active_count()
    for _ in range(12):
        packer = CohortPacker(pop, 16)
        packer.pack(np.arange(16))
        packer.close()
        packer.close()                              # idempotent
    for overlap in (1, 2):
        run_mocha_cohort(pop, REG, _cfg(rounds=2, overlap=overlap))
    assert threading.active_count() == before


# -- checkpoint / resume ----------------------------------------------------

@pytest.mark.parametrize("overlap,staleness", [(1, 0), (4, 0), (3, 2)])
def test_checkpoint_resume_bit_identical(tmp_path, overlap, staleness):
    """Kill a run at block 6 with a planted hard fault, resume from its
    checkpoints WITHOUT the fault config: the completed run must be
    bit-identical to the uninterrupted reference at every (overlap,
    staleness) -- history, factored state, coverage, everything."""
    pop = Population(SPEC, seed=0)
    kw = dict(rounds=10, overlap=overlap, staleness=staleness)
    ref = run_mocha_cohort(pop, REG, _cfg(**kw))
    ckdir = str(tmp_path / "ck")
    with pytest.raises(BlockFailure) as ei:
        run_mocha_cohort(pop, REG, _cfg(
            **kw, checkpoint_every=2, checkpoint_dir=ckdir,
            faults=FaultConfig(solve_fail_blocks=(6,))))
    assert (ei.value.block, ei.value.stage) == (6, "solve")
    res = run_mocha_cohort(pop, REG, _cfg(
        **kw, checkpoint_every=2, checkpoint_dir=ckdir, resume=True))
    assert res.resumed_from is not None and 0 <= res.resumed_from < 6
    assert res.history == ref.history
    np.testing.assert_array_equal(res.centroids, ref.centroids)
    np.testing.assert_array_equal(res.omega_k, ref.omega_k)
    np.testing.assert_array_equal(res.assign, ref.assign)
    np.testing.assert_array_equal(res.participation, ref.participation)
    np.testing.assert_array_equal(res.relationship.counts,
                                  ref.relationship.counts)
    assert res.schedule.ids.tolist() == ref.schedule.ids.tolist()


def test_resume_rejects_mismatched_config(tmp_path):
    """The fingerprint covers WHAT is computed (population, regularizer,
    config) and normalizes out the resilience knobs -- resuming a different
    computation must fail loudly, resuming with different fault/cadence
    settings must not."""
    pop = Population(SPEC, seed=0)
    ckdir = str(tmp_path / "ck")
    run_mocha_cohort(pop, REG, _cfg(
        rounds=4, checkpoint_every=2, checkpoint_dir=ckdir))
    with pytest.raises(ValueError, match="config hash"):
        run_mocha_cohort(pop, REG, _cfg(
            rounds=4, dropout=0.3, checkpoint_every=2, checkpoint_dir=ckdir,
            resume=True))
    base = _cfg(rounds=4)
    assert run_fingerprint(pop, REG, base) == run_fingerprint(
        pop, REG, dataclasses.replace(
            base, max_retries=3, degrade=True, checkpoint_every=7,
            checkpoint_dir="/elsewhere", resume=True,
            faults=FaultConfig(solve_fail_prob=0.5)))
    assert run_fingerprint(pop, REG, base) != run_fingerprint(
        pop, REG, dataclasses.replace(base, rounds=5))


# -- pipelined failure hardening --------------------------------------------

def test_pipelined_solve_failure_folds_predecessors_and_checkpoints(tmp_path):
    """A solve failure surfacing mid-pipeline must fold every completed
    predecessor (the drain is strictly ordered, so they were consumed
    first), force-checkpoint that frontier, cancel queued work, and
    propagate -- never hang and never fold past the drain schedule."""
    pop = Population(SPEC, seed=0)
    ckdir = str(tmp_path / "ck")
    with pytest.raises(BlockFailure) as ei:
        run_mocha_cohort(pop, REG, _cfg(
            rounds=10, overlap=3, staleness=2, checkpoint_dir=ckdir,
            faults=FaultConfig(solve_fail_blocks=(5,))))
    assert (ei.value.block, ei.value.stage) == (5, "solve")
    # the force-saved frontier IS the fold schedule's value: every block
    # before the failed one folded, nothing after it did
    assert ckpt.latest_step(ckdir) == 4


def test_pipelined_pack_failure_respects_drain_schedule(tmp_path):
    """A pack failure surfaces at launch time, when the drain has folded
    only through b - 1 - staleness: the exception path must checkpoint
    EXACTLY that frontier -- folding the already-solved successors would
    shift later launch-time state reads and break resume bit-identity."""
    pop = Population(SPEC, seed=0)
    ckdir = str(tmp_path / "ck")
    fail, staleness = 4, 2
    with pytest.raises(BlockFailure) as ei:
        run_mocha_cohort(pop, REG, _cfg(
            rounds=10, overlap=3, staleness=staleness, checkpoint_dir=ckdir,
            faults=FaultConfig(pack_fail_blocks=(fail,))))
    assert (ei.value.block, ei.value.stage) == (fail, "pack")
    assert ckpt.latest_step(ckdir) == fail - 1 - staleness
    # and that checkpoint resumes to the reference bit-identically
    ref = run_mocha_cohort(pop, REG, _cfg(rounds=10, overlap=3,
                                          staleness=staleness))
    res = run_mocha_cohort(pop, REG, _cfg(
        rounds=10, overlap=3, staleness=staleness, checkpoint_dir=ckdir,
        resume=True))
    assert res.resumed_from == fail - 1 - staleness
    assert res.history == ref.history
    np.testing.assert_array_equal(res.centroids, ref.centroids)
