"""Fig 1: simulated time to reach suboptimality targets for MOCHA vs CoCoA vs
Mb-SGD vs Mb-SDCA under 3G / LTE / WiFi communication-cost regimes.

Statistical heterogeneity comes from the unbalanced n_t of the federation;
MOCHA's per-node budgets absorb it (clock-cycle capped), CoCoA must wait for
the slowest node every round, and mini-batch methods pay a communication
round per tiny step.

All timing flows through the event-driven ``SystemsTrace``: each recorded
trajectory is replayed per network under BOTH round policies -- ``sync``
(server waits for the slowest node) and ``semi_sync`` (MOCHA's clock-cycle
deadline caps the round; methods without deadline semantics still pay the
straggler).  An additional end-to-end ``semi_sync`` MOCHA run exercises the
driver-level controller path (budgets capped by ``trace.begin_round()``).
"""
from __future__ import annotations

import repro.api as api
from benchmarks import common
from repro.core import (BudgetConfig, MeanRegularized, SystemsConfig,
                        systems_model)
from repro.data import synthetic as syn

EPS = 1e-2


def semi_sync_end_to_end(train, reg, rounds: int, network: str,
                         p_star: float) -> float:
    """MOCHA through the driver with a live semi_sync trace: the clock cycle
    caps per-node budgets each round via ``trace.begin_round()``."""
    n_mean = float(sum(train.n_t) / train.m)
    # the most generous deadline variant (c = 8): reliably reaches eps
    # within the round budget on every network
    cycle_s = (common.MOCHA_DEADLINES[-1] * n_mean
               * systems_model.SDCA_STEP_FLOPS(train.d)
               / systems_model.CLOCK_FLOPS)
    res = common.run_single(
        train, api.Method(regularizers=reg, rounds=rounds * 3,
                          budget=BudgetConfig(passes=16.0)),
        systems=api.Systems(config=SystemsConfig(
            network=network, policy="semi_sync", clock_cycle_s=cycle_s)))
    return common.time_to_epsilon(res.history, p_star, EPS)


def run(quick: bool = True):
    import dataclasses
    # most skewed n_t of the three (Table 2) + per-node conditioning
    # heterogeneity (the real federations' statistical stragglers)
    spec = dataclasses.replace(syn.VEHICLE_SENSOR, difficulty_spread=1.0)
    train, _ = syn.make_federation(spec, seed=0)
    reg = MeanRegularized(lambda1=0.1, lambda2=0.1)
    p_star = common.primal_star(train, reg, rounds=150 if quick else 400)
    rounds = 40 if quick else 120
    trajs, us = common.timed(common.run_method_trajectories, train, reg,
                             rounds)
    rows = []
    for network in ("3g", "lte", "wifi"):
        e2e = semi_sync_end_to_end(train, reg, rounds, network, p_star)
        for policy in ("sync", "semi_sync"):
            times = common.best_times_for_network(trajs, train.d, network,
                                                  p_star, EPS, policy=policy)
            row = {"bench": "fig1", "network": network, "policy": policy,
                   "eps_rel": EPS, "us_per_call": us,
                   "t_mocha_semi_sync_e2e": e2e,
                   "provenance": trajs.get("_provenance", {})}
            row.update({f"t_{m}": t for m, t in times.items()})
            row["mocha_fastest"] = times["mocha"] <= min(
                times["cocoa"], times["mb_sgd"], times["mb_sdca"])
            rows.append(row)
    return rows
