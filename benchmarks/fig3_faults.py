"""Fig 3: fault tolerance -- nodes drop each round with probability p.
MOCHA converges for p < 1 (Assumption 2); a permanently dead node (p == 1)
converges to the wrong solution (the paper's green dotted line)."""
from __future__ import annotations

import repro.api as api
from benchmarks import common
from repro.core import BudgetConfig, MeanRegularized
from repro.data import synthetic as syn
import warnings


def run(quick: bool = True):
    train, _ = syn.make_federation(syn.HUMAN_ACTIVITY, seed=0)
    reg = MeanRegularized(lambda1=0.1, lambda2=0.1)
    rounds = 120 if quick else 400
    ref = common.run_single(train, api.Method(
        regularizers=reg, rounds=rounds, budget=BudgetConfig(passes=1.0)),
        record_every=rounds)
    p_ref = ref.final("primal")
    rows = []
    for p in (0.0, 0.25, 0.5, 0.75, 0.9):
        res, us = common.timed(common.run_single, train, api.Method(
            regularizers=reg, rounds=rounds,
            budget=BudgetConfig(passes=1.0, drop_prob=p)),
            record_every=rounds)
        sim = res.trace.summary()
        rows.append({
            "bench": "fig3", "drop_prob": p, "us_per_call": us,
            "provenance": res.provenance,
            "primal_gap_vs_ref": res.final("primal") - p_ref,
            "rel_gap": res.final("gap") / max(abs(res.final("primal")), 1.0),
            "converged": (res.final("gap")
                          / max(abs(res.final("primal")), 1.0)) < 0.05,
            "mean_dropped_per_round": sim["mean_dropped"],
            "sim_elapsed_s": sim["elapsed_s"],
        })
    # p == 1 on one node: must NOT converge to the reference solution
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        dead = common.run_single(train, api.Method(
            regularizers=reg, rounds=rounds,
            budget=BudgetConfig(passes=1.0, never_send_node=0)),
            record_every=rounds)
    rows.append({
        "bench": "fig3", "drop_prob": 1.0,
        "provenance": dead.provenance,
        "primal_gap_vs_ref": dead.final("primal") - p_ref,
        "wrong_solution": dead.final("primal") > p_ref + 1e-3,
    })
    return rows
