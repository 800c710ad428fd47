"""repro.api: the ONE declarative experiment surface.

Compose an ``Experiment`` from orthogonal sub-specs and run it::

    from repro.api import Experiment, Problem, Method, Systems, Exec, Eval

    report = Experiment(
        problem=Problem(train=train),
        method=Method(loss="hinge", regularizers=(reg,), rounds=80),
        systems=Systems(network="lte"),
        exec=Exec(engine="local"),
        eval=Eval(record_every=10, holdout=test),
    ).run(seed=0)

The capability router picks the fastest applicable execution path (vmapped
sweep / device-resident scan / Python loop / cohort blocks) and falls back
sequentially -- with the reason recorded in ``report.provenance`` -- where
a batched path does not apply.  ``report`` carries history, trace, held-out
eval tables, and provenance (engine, driver, SDCA residual mode, config
hash).  DESIGN.md section 8 documents the routing rules and the Report
schema.
"""
from repro.api.execute import (base_provenance, run_experiment,
                               serve_experiment)
from repro.api.report import PROVENANCE_KEYS, Report
from repro.api.router import PATHS, RoutePlan, batch_incompatibility, route
from repro.api.specs import (PROBLEM_KINDS, Eval, Exec, Experiment, Method,
                             Problem, Serve, Systems, as_cohort_config,
                             as_mocha_config, config_fingerprint)
from repro.core.evaluate import METRICS, EvalReport

__all__ = [
    "Experiment",
    "Problem",
    "Method",
    "Systems",
    "Exec",
    "Eval",
    "Serve",
    "Report",
    "EvalReport",
    "RoutePlan",
    "route",
    "run_experiment",
    "serve_experiment",
    "batch_incompatibility",
    "as_mocha_config",
    "as_cohort_config",
    "config_fingerprint",
    "base_provenance",
    "PATHS",
    "PROBLEM_KINDS",
    "PROVENANCE_KEYS",
    "METRICS",
]
