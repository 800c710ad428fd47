"""Cross-device cohort subsystem: streaming population, pre-sampled
selection, bounded-memory factored state, and degradation to plain MOCHA
under full participation."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.cohort import (ClusterOmega, CohortConfig, CohortPacker,
                          CohortSampler, Population, PopulationSpec,
                          StalenessBoundedMerger, pack_cohort,
                          run_mocha_cohort)
from repro.core import BudgetConfig, MochaConfig, Probabilistic, run_mocha
from repro.core.systems_model import (SystemsConfig, SystemsTrace,
                                      population_rates)
from repro.data.synthetic import HUMAN_ACTIVITY

SPEC = PopulationSpec("t_pop", m=400, d=12, n_min=12, n_max=32, clusters=3)
REG = Probabilistic(lam=1e-2, sigma2=10.0)


# -- population -------------------------------------------------------------

def test_population_streaming_deterministic():
    """Client t is bit-reproducible across Population instances and access
    orders, with O(k*d) resident state."""
    a, b = Population(SPEC, seed=0), Population(SPEC, seed=0)
    blk_a = a.client_block(123)
    b.client_block(7)                      # different access order
    blk_b = b.client_block(123)
    np.testing.assert_array_equal(blk_a.X, blk_b.X)
    np.testing.assert_array_equal(blk_a.y, blk_b.y)
    assert (blk_a.n, blk_a.cluster) == (blk_b.n, blk_b.cluster)
    # metadata derivable without materializing, and consistent with the block
    assert a.client_meta(123) == (blk_a.cluster, blk_a.n)
    assert SPEC.n_min <= blk_a.n <= SPEC.n_max
    # resident state is the centers only -- nothing scales with m
    assert a.resident_bytes == a.centers.nbytes
    big = Population(dataclasses.replace(SPEC, m=10**6), seed=0)
    assert big.resident_bytes == a.resident_bytes


def test_population_seed_changes_data():
    a, b = Population(SPEC, seed=0), Population(SPEC, seed=1)
    assert not np.array_equal(a.client_block(5).X, b.client_block(5).X)


def test_population_spec_extends_federation():
    """PopulationSpec carries every calibrated FederationSpec knob."""
    spec = PopulationSpec.from_federation(HUMAN_ACTIVITY, m=50_000)
    assert spec.m == 50_000
    assert (spec.d, spec.n_min, spec.n_max) == (
        HUMAN_ACTIVITY.d, HUMAN_ACTIVITY.n_min, HUMAN_ACTIVITY.n_max)
    assert spec.pad_width == spec.n_max
    padded = dataclasses.replace(spec, n_pad=512)
    assert padded.pad_width == 512


# -- sampler ----------------------------------------------------------------

def test_sampler_uniform_schedule():
    s = CohortSampler(m=100, cohort=16, dropout=0.25)
    sched = s.presample(seed=3, rounds=20)
    assert sched.ids.shape == (20, 16) and sched.dropped.shape == (20, 16)
    for h in range(20):                      # without replacement
        assert len(set(sched.ids[h].tolist())) == 16
    # reproducible; a different seed moves it
    np.testing.assert_array_equal(sched.ids, s.presample(3, 20).ids)
    assert not np.array_equal(sched.ids, s.presample(4, 20).ids)
    assert 0.05 < sched.dropped.mean() < 0.6


def test_sampler_weighted_biases_selection():
    m = 200
    w = np.ones(m)
    w[:20] = 50.0                            # 20 hot clients
    s = CohortSampler(m=m, cohort=10, kind="weighted", weights=w)
    sched = s.presample(seed=0, rounds=60)
    hot_frac = (sched.ids < 20).mean()
    assert hot_frac > 0.5                    # 10% of clients, >50% of slots
    for h in range(60):
        assert len(set(sched.ids[h].tolist())) == 10


def test_sampler_validation():
    with pytest.raises(ValueError, match="Assumption 2"):
        CohortSampler(m=10, cohort=4, dropout=1.0).validate()
    with pytest.raises(ValueError, match="cohort size"):
        CohortSampler(m=10, cohort=11).validate()
    with pytest.raises(ValueError, match="weights"):
        CohortSampler(m=10, cohort=4, kind="weighted").validate()


# -- packing ----------------------------------------------------------------

def test_pack_cohort_layout():
    pop = Population(SPEC, seed=0)
    ids = np.asarray([5, 0, 399, 7])
    data = pack_cohort(pop, ids)
    assert data.X.shape == (4, SPEC.pad_width, SPEC.d)
    assert data.xnorm2 is not None           # per-run table threaded
    # left-packed mask, real sizes
    sizes = pop.client_sizes(ids)
    np.testing.assert_array_equal(np.asarray(data.n_t), sizes)
    for slot, n in enumerate(sizes):
        assert float(data.mask[slot, :n].min()) == 1.0
        assert float(data.mask[slot, n:].max() if n < SPEC.pad_width
                     else 0.0) == 0.0
    # slot order follows ids: same client -> same rows
    again = pack_cohort(pop, [399])
    np.testing.assert_array_equal(np.asarray(again.X[0]),
                                  np.asarray(data.X[2]))
    # pad_tasks-compatible: the SHARDED engine pads the cohort, never the
    # population
    from repro.federated.sharding import pad_tasks
    padded, m_real = pad_tasks(data, 8)
    assert (m_real, padded.m) == (4, 8)
    assert padded.xnorm2 is not None


def test_cohort_packer_reuses_buffers_without_corruption():
    """CohortPacker hoists the per-block host work: layout resolved once,
    staging buffers reused -- a later pack must not corrupt an earlier
    pack's device arrays, sizes come from the metadata stream (no device
    pull), and the packed bytes match the one-shot pack_cohort."""
    pop = Population(SPEC, seed=0)
    packer = CohortPacker(pop, 4)
    ids_a, ids_b = np.asarray([5, 0, 399, 7]), np.asarray([1, 2, 3, 4])
    data_a, sizes_a = packer.pack(ids_a)
    ref_a = pack_cohort(pop, ids_a)
    np.testing.assert_array_equal(np.asarray(data_a.X), np.asarray(ref_a.X))
    np.testing.assert_array_equal(np.asarray(data_a.y), np.asarray(ref_a.y))
    np.testing.assert_array_equal(sizes_a, pop.client_sizes(ids_a))
    a_before = np.asarray(data_a.X).copy()
    data_b, sizes_b = packer.pack(ids_b)             # reuses the buffers
    np.testing.assert_array_equal(np.asarray(data_a.X), a_before)
    np.testing.assert_array_equal(np.asarray(data_b.X),
                                  np.asarray(pack_cohort(pop, ids_b).X))
    np.testing.assert_array_equal(sizes_b, pop.client_sizes(ids_b))
    with pytest.raises(ValueError, match="static per run"):
        packer.pack(np.asarray([1, 2]))


#: HAR's width and padded point axis, with sizes that differ client to client
HAR_SPEC = PopulationSpec("t_har", m=5_000, d=561, n_min=210, n_max=306,
                          clusters=3, n_pad=306)


def _serial_pack(pop, ids, n_pad):
    """The pack law written out: one ``client_block`` per slot, zero tails."""
    K, d = len(ids), pop.spec.d
    X = np.zeros((K, n_pad, d), np.float32)
    y = np.zeros((K, n_pad), np.float32)
    mask = np.zeros((K, n_pad), np.float32)
    sizes = np.zeros(K, np.int64)
    for slot, t in enumerate(ids):
        blk = pop.client_block(int(t))
        X[slot, :blk.n], y[slot, :blk.n], mask[slot, :blk.n] = blk.X, blk.y, 1
        sizes[slot] = blk.n
    return X, y, mask, sizes


def _assert_same_bytes(got, want):
    got = np.asarray(got)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("threads", [1, 2, 8])
def test_cohort_packer_bit_identical_at_every_pool_size(monkeypatch,
                                                        threads):
    """The threaded draw packs the same bytes as a serial ``client_block``
    loop, at d=561 and n_pad=306: X, y, mask, xnorm2 and sizes, over two
    packs on one packer whose second cohort has shorter clients in some
    slots (so a tail left over from the first pack would show)."""
    from repro.cohort import packing
    from repro.core.dual import FederatedData, with_xnorm2
    monkeypatch.setattr(packing, "_usable_cores", lambda: threads)
    pop = Population(HAR_SPEC, seed=0)
    K, n_pad = 12, HAR_SPEC.pad_width
    ids_a, ids_b = np.arange(K) * 37, np.arange(K) * 41 + 3
    packer = CohortPacker(pop, K)
    try:
        assert packer.threads == threads
        seen = []
        for ids in (ids_a, ids_b):
            data, sizes = packer.pack(ids)
            X, y, mask, want_sizes = _serial_pack(pop, ids, n_pad)
            _assert_same_bytes(data.X, X)
            _assert_same_bytes(data.y, y)
            _assert_same_bytes(data.mask, mask)
            _assert_same_bytes(sizes, want_sizes)
            want_x2 = with_xnorm2(FederatedData(
                X=jnp.array(X), y=jnp.array(y), mask=jnp.array(mask))).xnorm2
            _assert_same_bytes(data.xnorm2, np.asarray(want_x2))
            seen.append(want_sizes)
        # sizes differ, and some slot holds a shorter client the 2nd time
        assert len(set(seen[0].tolist())) > 1 and (seen[1] < seen[0]).any()
        one_shot = pack_cohort(pop, ids_b)
        for name in ("X", "y", "mask", "xnorm2"):
            _assert_same_bytes(getattr(one_shot, name),
                               np.asarray(getattr(data, name)))
    finally:
        packer.close()


# -- driver -----------------------------------------------------------------

def _small_cfg(**kw):
    base = dict(rounds=6, cohort=16, clusters=3, dropout=0.2,
                omega_update_every=2, record_every=2, seed=1,
                inner=MochaConfig(budget=BudgetConfig(passes=1.0)))
    base.update(kw)
    return CohortConfig(**base)


def test_cohort_run_bit_reproducible():
    pop = Population(SPEC, seed=0)
    a = run_mocha_cohort(pop, REG, _small_cfg())
    b = run_mocha_cohort(pop, REG, _small_cfg())
    assert a.history == b.history
    np.testing.assert_array_equal(a.centroids, b.centroids)
    np.testing.assert_array_equal(a.omega_k, b.omega_k)
    np.testing.assert_array_equal(a.assign, b.assign)
    np.testing.assert_array_equal(a.schedule.ids, b.schedule.ids)


def test_cohort_sharded_engine_matches_local():
    """engine='sharded' shards the 16-task cohort over the mesh and stays
    bit-identical to the local engine (cross-engine parity holds through
    the cohort layer)."""
    pop = Population(SPEC, seed=0)
    loc = run_mocha_cohort(pop, REG, _small_cfg())
    sh = run_mocha_cohort(pop, REG, _small_cfg(
        inner=MochaConfig(budget=BudgetConfig(passes=1.0),
                          engine="sharded")))
    assert loc.history == sh.history
    np.testing.assert_array_equal(loc.centroids, sh.centroids)


def test_cohort_bounded_memory_structural():
    """No O(m^2) -- the factored state fits an explicit linear-in-m budget
    and the cohort tensors are population-size independent."""
    m, cache = 2000, 64
    pop = Population(dataclasses.replace(SPEC, m=m), seed=0)
    cfg = _small_cfg(cache_clients=cache)
    res = run_mocha_cohort(pop, REG, cfg)
    state = res.relationship
    k, d, n_pad = cfg.clusters, SPEC.d, SPEC.pad_width
    assert state.omega_k.shape == (k, k)
    assert state.centroids.shape == (k, d)
    assert state.assign.shape == (m,)
    assert state.cached_clients <= cache
    # explicit budget: O(m) assignments + O(k^2 + k d) + bounded cache.
    # An O(m^2) float32 matrix alone would be 16 MB at m = 2000.
    budget = (4 * m + 8 * m                      # assign + any O(m) vector
              + 8 * k * k + 8 * k * d + 8 * k
              + cache * 4 * (n_pad + d) + 4096)
    assert state.memory_bytes() <= budget
    assert res.rate_mult.shape == (m,)


def test_cohort_dropout_fault_tolerance():
    """The paper's H_t -> 0 story at population scale: selected-but-failed
    clients contribute nothing, the run still makes progress."""
    pop = Population(SPEC, seed=0)
    cfg = _small_cfg(rounds=12, dropout=0.5, record_every=1,
                     omega_update_every=0)
    res = run_mocha_cohort(pop, REG, cfg)
    # drops visibly reduce coverage vs the no-failure run
    full = run_mocha_cohort(pop, REG, dataclasses.replace(cfg, dropout=0.0))
    assert res.final("unique_clients") < full.final("unique_clients")
    # and the cohort objective still improves despite 50% failures
    assert res.history["primal"][-1] < res.history["primal"][0]


def test_all_dropped_block_folds_zero_participation(monkeypatch):
    """The theory's H_t -> 0 boundary block: ``CohortSchedule.
    with_all_dropped`` composed with ``theta.drop_masked_budgets`` must
    fold a whole-cohort failure as zero participation -- no centroid/Omega
    motion, no ``seen``/``participation`` increment -- on BOTH block
    loops."""
    from repro.cohort.driver import _BlockLoop
    pop = Population(SPEC, seed=0)
    dead = 2
    cfg = _small_cfg(dropout=0.0, record_every=1)

    # sequential loop, stepped manually so state motion brackets the fold
    loop = _BlockLoop(pop, REG, cfg)
    loop.schedule = loop.schedule.with_all_dropped(dead)
    for b in range(cfg.rounds):
        ids, dropped, alpha0, omega0 = loop.launch_args(b)
        packed = loop.pack_block(b)
        s = loop.solve_block(b, packed, ids, dropped, alpha0, omega0)
        if b == dead:
            # drop_masked_budgets zeroed every slot's budget -> no steps
            assert not s.participated.any()
            cen = loop.state.centroids.copy()
            omk = loop.state.omega_k.copy()
            seen = loop.seen.copy()
        loop.fold(b, ids, packed.sizes, s)
        if b == dead:
            np.testing.assert_array_equal(loop.state.centroids, cen)
            np.testing.assert_array_equal(loop.state.omega_k, omk)
            np.testing.assert_array_equal(loop.seen, seen)
    seq = loop.result()
    # executed participation equals the schedule with the dead block out
    np.testing.assert_array_equal(
        seq.participation, seq.schedule.participation_counts(SPEC.m))
    assert seq.history["unique_clients"][dead] == \
        seq.history["unique_clients"][dead - 1]

    # pipelined loop under the same schedule: bit-identical fold semantics
    from repro.cohort.sampler import CohortSampler
    orig = CohortSampler.presample
    monkeypatch.setattr(
        CohortSampler, "presample",
        lambda self, seed, rounds: orig(self, seed,
                                        rounds).with_all_dropped(dead))
    pipe = run_mocha_cohort(pop, REG, dataclasses.replace(cfg, overlap=3))
    assert pipe.schedule.dropped[dead].all()
    assert seq.history == pipe.history
    np.testing.assert_array_equal(seq.centroids, pipe.centroids)
    np.testing.assert_array_equal(seq.participation, pipe.participation)


def test_cohort_learns_cluster_structure():
    """With separated latent clusters and k = truth, the learned
    assignments recover the ground truth for participated clients.

    Recovery is a property of most streams, not all: the online assignment
    (``ClusterOmega.update``) can settle with one true cluster split across
    two learned ones.  Driver seeds 0-7 recover every cluster at > 0.69
    except seed 2 (0.59 on cluster 0) under jax's threefry stream since
    jax 0.5 (``jax_threefry_partitionable``); this test pins seed 0."""
    spec = dataclasses.replace(SPEC, m=300, d=16, n_min=24, n_max=48,
                               cluster_spread=0.15, feature_shift=0.2,
                               label_noise=0.02)
    pop = Population(spec, seed=1)
    cfg = CohortConfig(rounds=40, cohort=32, clusters=3,
                       omega_update_every=10, record_every=40, seed=0,
                       inner=MochaConfig(budget=BudgetConfig(passes=2.0)))
    res = run_mocha_cohort(pop, REG, cfg)
    ids = np.arange(spec.m)
    true = pop.true_assignments(ids)
    part = res.participation > 0
    learned = res.assign
    for c in range(3):
        sel = (true == c) & part
        assert sel.sum() > 10
        _, counts = np.unique(learned[sel], return_counts=True)
        assert counts.max() / sel.sum() > 0.6, f"cluster {c} not recovered"


def test_cohort_small_cohorts_warm_every_cluster():
    """Regression: with K < k, clusters missing from the first block's
    coverage must still become warm later -- a client whose current cluster
    is cold keeps it (and warms it) instead of being pulled to the warm
    subset forever."""
    pop = Population(dataclasses.replace(SPEC, m=200), seed=3)
    cfg = CohortConfig(rounds=25, cohort=4, clusters=8, dropout=0.0,
                       record_every=25, seed=5,
                       inner=MochaConfig(budget=BudgetConfig(passes=1.0)))
    res = run_mocha_cohort(pop, REG, cfg)
    assert (res.relationship.counts > 0).all(), res.relationship.counts
    # participation ground truth matches the schedule bound here (no drops)
    np.testing.assert_array_equal(
        res.participation, res.schedule.participation_counts(200))


def test_cohort_participation_reflects_budget_drops():
    """res.participation counts EXECUTED blocks: in-round budget drops
    (BudgetConfig.drop_prob) land below the schedule, so the schedule-level
    bound must exceed it."""
    pop = Population(SPEC, seed=0)
    cfg = _small_cfg(rounds=10, dropout=0.0, record_every=10,
                     inner=MochaConfig(
                         budget=BudgetConfig(passes=1.0, drop_prob=0.5)))
    res = run_mocha_cohort(pop, REG, cfg)
    sched = res.schedule.participation_counts(SPEC.m)
    assert res.participation.sum() < sched.sum()
    assert (res.participation <= sched).all()


def test_cohort_pipeline_staleness0_bit_identical():
    """The overlapped pipeline's parity contract: at staleness 0 every
    block still launches from a fully-merged state, so any overlap depth is
    bit-identical to the sequential block loop -- state, history,
    participation, everything."""
    pop = Population(SPEC, seed=0)
    seq = run_mocha_cohort(pop, REG, _small_cfg(rounds=8, record_every=1))
    for depth in (2, 4):
        pipe = run_mocha_cohort(pop, REG, _small_cfg(
            rounds=8, record_every=1, overlap=depth))
        assert seq.history == pipe.history
        np.testing.assert_array_equal(seq.centroids, pipe.centroids)
        np.testing.assert_array_equal(seq.omega_k, pipe.omega_k)
        np.testing.assert_array_equal(seq.assign, pipe.assign)
        np.testing.assert_array_equal(seq.participation, pipe.participation)


def test_cohort_pipeline_stale_merge_deterministic_and_bounded():
    """staleness >= 1 lets a block launch from a state missing up to S
    prior folds.  The inexactness is real (results move off the sequential
    reference) but bounded and DETERMINISTIC: merge points are a pure
    function of block counts, never thread timing, and staleness delays
    merges without changing which clients run or how much budget they
    execute."""
    pop = Population(SPEC, seed=0)
    cfg = _small_cfg(rounds=12, record_every=1, overlap=4, staleness=2)
    a = run_mocha_cohort(pop, REG, cfg)
    b = run_mocha_cohort(pop, REG, cfg)
    assert a.history == b.history                 # run-to-run bitwise
    np.testing.assert_array_equal(a.centroids, b.centroids)
    np.testing.assert_array_equal(a.assign, b.assign)
    seq = run_mocha_cohort(pop, REG, dataclasses.replace(
        cfg, overlap=1, staleness=0))
    # the stale launches genuinely read older state...
    assert not np.array_equal(a.centroids, seq.centroids)
    # ...but the schedule and executed budgets are untouched
    np.testing.assert_array_equal(a.schedule.ids, seq.schedule.ids)
    np.testing.assert_array_equal(a.participation, seq.participation)
    # and the run still descends: bounded inexactness, not divergence
    assert a.history["primal"][-1] < a.history["primal"][0]


def test_cohort_participation_always_populated():
    """Regression for the Optional annotation: _run_cohort always returns
    a populated (m,) participation vector on every execution path."""
    pop = Population(SPEC, seed=0)
    for kw in ({}, {"overlap": 3}, {"overlap": 3, "staleness": 1}):
        res = run_mocha_cohort(pop, REG, _small_cfg(rounds=3, **kw))
        assert res.participation is not None
        assert res.participation.shape == (SPEC.m,)
        assert res.participation.sum() > 0


def test_staleness_merger_orders_folds_and_bounds_launches():
    """StalenessBoundedMerger: folds must arrive in schedule order, and a
    block is admissible to launch iff at most S earlier blocks are still
    unmerged."""
    k, d, n_pad, cohort = 2, 4, 8, 3
    state = ClusterOmega(m=10, k=k, d=d, reg=REG)
    mg = StalenessBoundedMerger(state, REG, staleness=1)
    assert mg.admissible(0) and mg.admissible(1) and not mg.admissible(2)
    ids = np.arange(cohort)
    W = np.zeros((cohort, d), np.float32)
    alpha = np.zeros((cohort, n_pad), np.float32)
    sizes = np.full(cohort, n_pad, np.int64)
    part = np.ones(cohort, bool)
    with pytest.raises(RuntimeError, match="out-of-order"):
        mg.fold(1, ids, W, alpha, sizes, part)
    mg.fold(0, ids, W, alpha, sizes, part)
    assert mg.merged_through == 0 and mg.admissible(2)
    with pytest.raises(ValueError, match="staleness"):
        StalenessBoundedMerger(state, REG, staleness=-1)


def test_cohort_full_participation_matches_run_mocha():
    """K = m, uniform, no dropout, fixed Omega: the cohort driver IS plain
    MOCHA over the (permuted) population -- final objectives agree to
    convergence tolerance against run_mocha on the materialized federation
    with the equivalent expanded Omega."""
    m, eta, rounds = 32, 0.5, 150
    spec = PopulationSpec("parity", m=m, d=10, n_min=16, n_max=32, clusters=2)
    pop = Population(spec, seed=0)
    cfg = CohortConfig(rounds=rounds, cohort=m, clusters=1, eta=eta,
                       dropout=0.0, sampler="uniform", omega_update_every=0,
                       record_every=rounds, seed=4,
                       inner=MochaConfig(budget=BudgetConfig(passes=2.0)))
    res_c = run_mocha_cohort(pop, REG, cfg)

    data = pack_cohort(pop, np.arange(m))
    om0 = float(np.asarray(REG.init_omega(1))[0, 0])
    omega_full = jnp.asarray(om0 * np.ones((m, m)) + eta * np.eye(m),
                             jnp.float32)
    res_f = run_mocha(data, REG,
                      MochaConfig(loss="hinge", rounds=rounds,
                                  budget=BudgetConfig(passes=2.0),
                                  record_every=rounds, seed=4),
                      omega0=omega_full)
    pc, pf = res_c.final("primal"), res_f.final("primal")
    assert abs(pc - pf) / abs(pf) < 2e-2
    # both runs actually descended: hinge P(0) = n_total at the cold start
    assert pc < 0.8 * float(jnp.sum(data.mask))
    # every client participated every block
    assert res_c.final("unique_clients") == m


def test_cohort_history_schema():
    pop = Population(SPEC, seed=0)
    res = run_mocha_cohort(pop, REG, _small_cfg())
    from repro.cohort import COHORT_HISTORY_KEYS
    assert set(res.history) == set(COHORT_HISTORY_KEYS)
    lengths = {k: len(v) for k, v in res.history.items()}
    assert len(set(lengths.values())) == 1
    # simulated clock advances monotonically across blocks
    times = res.history["time"]
    assert all(b > a for a, b in zip(times, times[1:]))
    # serving weights defined for never-sampled clients (centroid fallback)
    W = res.client_weights([0, 1, 2])
    assert W.shape == (3, SPEC.d)


# -- systems-model extensions the subsystem rides on ------------------------

def test_population_rates_deterministic_o_m():
    cfg = SystemsConfig(rate_lo=0.5, rate_hi=2.0, seed=7)
    r1 = population_rates(1000, cfg)
    r2 = population_rates(1000, cfg)
    np.testing.assert_array_equal(r1, r2)
    assert r1.shape == (1000,)
    assert (r1 >= 0.5).all() and (r1 <= 2.0).all()


def test_trace_rate_scale_injection():
    """Injected per-slot multipliers rescale compute time; mid-round calls
    and bad shapes are rejected."""
    cfg = SystemsConfig(network="lte")
    t = SystemsTrace(4, 8, cfg)
    base = t.advance(np.full(4, 100))
    t.set_rate_scale(np.full(4, 2.0))        # 2x faster hardware
    fast = t.advance(np.full(4, 100))
    assert fast < base
    with pytest.raises(ValueError, match="rate_scale"):
        t.set_rate_scale(np.ones(3))
    t.begin_round()
    with pytest.raises(RuntimeError, match="mid-round"):
        t.set_rate_scale(np.ones(4))
    t.commit(np.full(4, 10))


@pytest.mark.slow
def test_cohort_population_scale_100k():
    """Acceptance: 10^5 clients, K = 64, clustered Omega -- bounded memory,
    bit-reproducible across two invocations."""
    m = 100_000
    spec = PopulationSpec("pop100k", m=m, d=32, n_min=16, n_max=64,
                          clusters=5)
    pop = Population(spec, seed=0)
    cfg = CohortConfig(rounds=10, cohort=64, clusters=5, sampler="weighted",
                       dropout=0.1, omega_update_every=5,
                       systems=SystemsConfig(rate_lo=0.5, rate_hi=2.0),
                       record_every=5, seed=0, cache_clients=1024,
                       inner=MochaConfig(budget=BudgetConfig(passes=1.0)))
    a = run_mocha_cohort(pop, REG, cfg)
    b = run_mocha_cohort(pop, REG, cfg)
    assert a.history == b.history
    np.testing.assert_array_equal(a.centroids, b.centroids)
    np.testing.assert_array_equal(a.omega_k, b.omega_k)
    state = a.relationship
    # linear-in-m budget (an m x m float32 would be 40 GB)
    budget = (12 * m + 8 * 25 + 8 * 5 * 32 + 64
              + 1024 * 4 * (spec.pad_width + 32) + 4096)
    assert state.memory_bytes() <= budget
    assert state.cached_clients <= 1024


def test_cluster_omega_snapshot_roundtrip_under_lru_eviction():
    """snapshot/restore must round-trip the LRU cache bitwise even at
    capacity with evictions in flight: the restored state and the original
    stay bit-identical under the SAME further updates -- including which
    clients get evicted next (eviction ORDER is state too)."""
    m, k, d, cap, n_pad = 60, 3, 5, 8, 7
    reg = Probabilistic(lam=1e-2, sigma2=10.0)

    def make_updates(seed, n):
        rng = np.random.default_rng(seed)
        ups = []
        for _ in range(n):
            ids = np.sort(rng.choice(m, size=6, replace=False)).astype(
                np.int64)
            W = rng.normal(size=(6, d)).astype(np.float32)
            alpha = rng.normal(size=(6, n_pad)).astype(np.float32)
            sizes = rng.integers(2, n_pad + 1, size=6)
            part = rng.random(6) < 0.8
            part[0] = True  # never an all-dropped update
            ups.append((ids, W, alpha, sizes, part))
        return ups

    a = ClusterOmega(m, k, d, reg, cache_clients=cap)
    for u in make_updates(1, 10):
        a.update(*u)
    assert a.cached_clients == cap  # at capacity: evictions already ran
    snap = a.snapshot(n_pad)

    b = ClusterOmega(m, k, d, reg, cache_clients=cap)
    b.restore_state(snap)
    for key, val in snap.items():
        np.testing.assert_array_equal(val, b.snapshot(n_pad)[key],
                                      err_msg=key)

    # identical future: same updates => same evictions, bit-identical state
    for u in make_updates(2, 6):
        a.update(*u)
        b.update(*u)
    sa, sb = a.snapshot(n_pad), b.snapshot(n_pad)
    for key in sa:
        np.testing.assert_array_equal(sa[key], sb[key], err_msg=key)
    ids = np.arange(m)
    np.testing.assert_array_equal(a.client_weights(ids),
                                  b.client_weights(ids))
