"""Theorem 1/2 empirical check: geometric dual convergence for smooth losses,
slower sublinear-style decay for the (non-smooth) hinge."""
from __future__ import annotations

import numpy as np

import repro.api as api
from benchmarks import common
from repro.core import BudgetConfig, MeanRegularized
from repro.data.synthetic import tiny_problem


def _rate(loss: str, rounds: int):
    train, _ = tiny_problem(m=6, n=40, d=10, seed=0)
    res = common.run_single(train, api.Method(
        loss=loss, regularizers=MeanRegularized(0.5, 0.5), rounds=rounds,
        budget=BudgetConfig(passes=1.0)))
    dual = np.asarray(res.history["dual"])
    sub = dual - dual[-1]
    keep = sub > 1e-4
    sub = sub[keep][:30]
    if len(sub) < 5:
        return float("-inf"), res.provenance
    slope = float(np.polyfit(np.arange(len(sub)), np.log(sub), 1)[0])
    return slope, res.provenance


def run(quick: bool = True):
    rounds = 60 if quick else 150
    rows = []
    for loss in ("smooth_hinge", "logistic", "hinge"):
        (slope, prov), us = common.timed(_rate, loss, rounds)
        rows.append({"bench": "convergence", "loss": loss,
                     "log_decay_slope": slope, "us_per_call": us,
                     "geometric": slope < -0.05, "provenance": prov})
    return rows
