"""Back-to-back cross-silo training jobs through ``repro.api.Experiment``.

Set-up makes the federation from the seed and builds one ``Experiment``;
every job of the window is ``Experiment.run(job_seed)`` on it: ``rounds``
W-rounds from alpha = 0, each job with its own budgets and coordinate
draws.  ``train_rounds_per_s`` counts W-rounds (``jobs.py``).  A job's
answer is its final W, the duality gap at the record rounds and the
held-out error, which the plain reference (``reference.run_job``) checks.

Traffic file keys: ``rounds``, ``record_every``, ``budget`` (``passes``,
``systems_lo``, ``systems_hi``, ``drop_prob``), ``check_jobs``,
``trace_seconds``.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmarks.chip import compare, federation, jobs, reference, trace_reduce
from benchmarks.chip import work
from benchmarks.chip.common import Cell, Outcome


def experiment(cfg: Dict, traffic: Dict, train, test):
    """The configuration's method on the program's own engine and driver."""
    import jax.numpy as jnp

    from repro.api import Eval, Exec, Experiment, Method, Problem
    from repro.core import BudgetConfig, FederatedData, Probabilistic
    reg = cfg["regularizer"]
    as_fed = lambda s: FederatedData(*(jnp.asarray(a) for a in s))
    return Experiment(
        problem=Problem(train=as_fed(train)),
        method=Method(loss=cfg["loss"],
                      regularizers=Probabilistic(lam=reg["lam"],
                                                 sigma2=reg["sigma2"]),
                      rounds=traffic["rounds"],
                      omega_update_every=cfg["omega_update_every"],
                      budget=BudgetConfig(**traffic["budget"])),
        exec=Exec(engine=cfg["engine"], driver=cfg["driver"]),
        eval=Eval(record_every=traffic["record_every"],
                  holdout=as_fed(test)))


def make(cell: Cell, seed: int) -> jobs.Jobs:
    cfg, tr = cell.config, cell.traffic
    train, test = federation.make_federation(cfg, seed)
    held = {"exp": experiment(cfg, tr, train, test)}

    def job(job_seed: int) -> Dict:
        report = held["exp"].run(seed=job_seed)
        prov = report.provenance
        if (prov["engine"], prov["driver"]) != (cfg["engine"],
                                                cfg["driver"]):
            raise RuntimeError(f"routed to {prov['engine']}/"
                               f"{prov['driver']}")
        res = report.result
        return {"W": np.asarray(res.W),
                "gap": np.asarray(res.history["gap"]),
                "error": float(report.evaluation.summary["mean_error"]),
                "executed": np.asarray(res.round_budgets)}

    def layer(trace: trace_reduce.Trace, done: List[Dict]) -> Dict:
        import jax
        executed = np.concatenate([j["executed"] for j in done])
        flops, bytes_ = work.sdca_round_work(executed, cfg["m"], cfg["d"])
        peak = work.peaks(jax.devices()[0].device_kind)
        return {"rounds": int(executed.shape[0]),
                "scan_rounds_s": trace_reduce.module_time(trace,
                                                          "_scan_rounds"),
                "min_s": work.min_seconds(flops, bytes_, peak)}

    return jobs.Jobs(
        job=job,
        reference=lambda s, precision: reference.run_job(
            cfg, tr, train, test, s, precision),
        readings=compare.job_readings, layer=layer, units=tr["rounds"],
        metric="train_rounds_per_s", release=held.clear)


def run(cell: Cell) -> Outcome:
    return jobs.run(cell, make(cell, cell.seed), jobs.job_seeds(cell.seed))
