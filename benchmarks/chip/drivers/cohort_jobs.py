"""Back-to-back cross-device training jobs: cohort blocks over a population.

Set-up builds a seeded ``repro.cohort.Population`` (the program makes each
sampled client's data in its pack stage, as the simulator does) and one
``Experiment`` over it; every job of the window is ``Experiment.run
(job_seed)``: ``blocks`` cohort blocks from the cold state, each job with
its own cohort schedule and block seeds.  ``cohort_blocks_per_s`` counts
blocks (``jobs.py``).  A job's answer is the factored state it ends with
(centroids, assignments, the cached per-client offsets) and each block's
duality gap, which the plain reference (``reference_cohort.run_job``)
checks: it covers pack (the clients' data), each block's solve, and the
fold.

With ``--trace 1`` the program's own telemetry is on as well, and the
per-layer readers get the summed ``pack`` and ``solve`` span seconds of its
Chrome traces.

Traffic file keys: ``blocks``, ``check_jobs``, ``trace_seconds``.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import tempfile
from typing import Dict, List

import numpy as np

from benchmarks.chip import compare, jobs, reference_cohort, trace_reduce
from benchmarks.chip.common import Cell, Outcome, stream_seeds

_POP_STREAM = 0x706F73   # "pos": the population seed of a run


def experiment(cfg: Dict, traffic: Dict, pop, trace_dir=None):
    from repro.api import Eval, Exec, Experiment, Method, Problem, Systems
    from repro.core import BudgetConfig, Probabilistic
    reg = cfg["regularizer"]
    return Experiment(
        problem=Problem(population=pop),
        method=Method(loss=cfg["loss"],
                      regularizers=Probabilistic(lam=reg["lam"],
                                                 sigma2=reg["sigma2"]),
                      rounds=traffic["blocks"],
                      omega_update_every=cfg["omega_update_every"],
                      budget=BudgetConfig(**cfg["budget"])),
        systems=Systems(dropout=cfg["dropout"]),
        exec=Exec(engine=cfg["engine"], driver=cfg["driver"],
                  cohort=cfg["cohort"], inner_rounds=1,
                  clusters=cfg["clusters"], eta=cfg["eta"],
                  cache_clients=cfg["cache_clients"], n_pad=cfg["n_pad"],
                  trace_dir=trace_dir),
        eval=Eval(record_every=1))


def population(cfg: Dict, pop_seed: int):
    from repro.cohort import Population, PopulationSpec
    spec = PopulationSpec(
        name=cfg["name"], m=cfg["m"], d=cfg["d"], n_min=cfg["n_min"],
        n_max=cfg["n_max"], clusters=cfg["clusters"],
        cluster_spread=cfg["cluster_spread"],
        feature_shift=cfg["feature_shift"], label_noise=cfg["label_noise"],
        n_pad=cfg["n_pad"])
    return Population(spec, seed=pop_seed)


def answer(result) -> Dict:
    """The factored state of a finished run, as host arrays."""
    rel = result.relationship
    ids, deltas = rel.cache_entries()
    order = np.argsort(ids)
    return {"centroids": np.asarray(rel.centroids),
            "assign": np.asarray(rel.assign), "cache_ids": ids[order],
            "cache_delta": deltas[order],
            "gap": np.asarray(result.history["gap"])}


def _span_seconds(trace_dir: str, names) -> Dict[str, float]:
    out = {n: 0.0 for n in names}
    for path in glob.glob(os.path.join(trace_dir, "*.json")):
        with open(path) as f:
            doc = json.load(f)
        for ev in doc["traceEvents"]:
            if (ev.get("ph") == "X" and ev.get("cat") == "wall"
                    and ev["name"] in out):
                out[ev["name"]] += ev["dur"] * 1e-6
    return out


def make(cell: Cell, seed: int) -> jobs.Jobs:
    cfg, tr = cell.config, cell.traffic
    pop_seed = int(stream_seeds(seed, _POP_STREAM, 1)[0])
    trace_dir = (tempfile.mkdtemp(prefix="chip-bench-spans-")
                 if cell.trace else None)
    held = {"exp": experiment(cfg, tr, population(cfg, pop_seed),
                              trace_dir)}

    def job(job_seed: int) -> Dict:
        report = held["exp"].run(seed=job_seed)
        prov = report.provenance
        if prov["path"] != "cohort" or prov["engine"] != cfg["engine"]:
            raise RuntimeError(f"routed to {prov['path']}/{prov['engine']}")
        if prov["retries"] or prov["degraded_blocks"]:
            raise RuntimeError(f"faults on a clean run: {prov}")
        return answer(report.result)

    def layer(trace: trace_reduce.Trace, done: List[Dict]) -> Dict:
        spans = _span_seconds(trace_dir, ("pack", "solve"))
        return {"blocks": tr["blocks"] * len(done),
                "pack_s": spans["pack"], "solve_s": spans["solve"]}

    def begin() -> None:
        """Drop the set-up job's span files: it compiled."""
        if trace_dir is None:
            return
        for path in glob.glob(os.path.join(trace_dir, "*.json")):
            os.remove(path)

    def release() -> None:
        held.clear()
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    return jobs.Jobs(
        job=job,
        reference=lambda s, precision: reference_cohort.run_job(
            cfg, pop_seed, s, tr["blocks"], precision),
        readings=compare.cohort_readings, layer=layer, units=tr["blocks"],
        metric="cohort_blocks_per_s", begin=begin, release=release)


def run(cell: Cell) -> Outcome:
    return jobs.run(cell, make(cell, cell.seed), jobs.job_seeds(cell.seed))
