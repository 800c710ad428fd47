"""CI telemetry smoke: one overlapped, faulty cohort run with tracing on.

Exercises the full observability surface in one shot (the gate CI runs
after the tier-1 suite):

  * an overlapped cohort run (``overlap=2``, ``staleness=1``) with
    deterministic fault injection -- transient pack/solve faults so the
    retry path fires, one hard solve-fail block so graceful degradation
    fires -- and periodic checkpointing;
  * ``Exec.telemetry``/``Exec.trace_dir`` produce a Chrome trace-event
    JSON artifact plus a flat metrics summary in ``Report.provenance``;
  * the artifact must pass ``repro.obs.validate_chrome_trace`` and COVER
    the run: every pack/solve/fold occurrence has a span, every injected
    retry an instant event, every degraded block a degrade span, every
    checkpoint a checkpoint span.

Exit 0 on success (artifact left at ``--out`` for upload), 1 with the
failed checks listed otherwise.  Deterministic end to end: same seed,
same trace structure (wall-clock durations differ, event counts do not).

Usage::

    python -m tools.telemetry_smoke [--out results/telemetry_smoke]
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List

ROUNDS = 8


def _run(out_dir: str):
    from repro import obs
    from repro.api import Exec, Experiment, Method, Problem, Systems
    from repro.cohort.population import Population, PopulationSpec
    from repro.cohort.resilience import FaultConfig
    from repro.core.regularizers import Probabilistic

    spec = PopulationSpec("tel_smoke", m=240, d=10, n_min=8, n_max=20,
                          clusters=3)
    exp = Experiment(
        problem=Problem(population=Population(spec, seed=0)),
        method=Method(regularizers=[Probabilistic(lam=1e-2, sigma2=10.0)],
                      rounds=ROUNDS),
        systems=Systems(faults=FaultConfig(pack_fail_prob=0.3,
                                           solve_fail_prob=0.3,
                                           solve_fail_blocks=(4,),
                                           seed=7)),
        exec=Exec(cohort=12, clusters=3, overlap=2, staleness=1,
                  max_retries=2, degrade=True,
                  checkpoint_every=3, checkpoint_dir=f"{out_dir}/ckpt",
                  telemetry=True, trace_dir=out_dir),
    )
    report = exp.run(seed=0)
    return obs, report


def _wall_counts(doc: dict) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for ev in doc.get("traceEvents", []):
        if ev.get("ph") in ("X", "i") and ev.get("cat") == "wall":
            counts[ev["name"]] = counts.get(ev["name"], 0) + 1
    return counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="results/telemetry_smoke",
                    help="artifact directory (trace JSON + checkpoints)")
    ns = ap.parse_args(argv)

    obs, report = _run(ns.out)
    prov = report.provenance
    failures: List[str] = []

    trace_path = prov["trace_path"]
    if not trace_path:
        print("FAIL: no trace artifact written")
        return 1
    with open(trace_path) as fh:
        doc = json.load(fh)
    for err in obs.validate_chrome_trace(doc):
        failures.append(f"schema: {err}")

    counts = _wall_counts(doc)
    summary = prov["telemetry"] or {}
    stats = report.result.fault_stats

    def check(cond: bool, what: str) -> None:
        if not cond:
            failures.append(what)

    # coverage: every block-stage occurrence has a span / event
    check(counts.get("pack", 0) == ROUNDS,
          f"pack spans: want {ROUNDS}, got {counts.get('pack', 0)}")
    check(counts.get("solve", 0) == ROUNDS,
          f"solve spans: want {ROUNDS}, got {counts.get('solve', 0)}")
    check(counts.get("fold", 0) == ROUNDS,
          f"fold spans: want {ROUNDS}, got {counts.get('fold', 0)}")
    check(counts.get("degrade", 0) == stats.degraded_blocks,
          f"degrade spans: want {stats.degraded_blocks}, "
          f"got {counts.get('degrade', 0)}")
    check(counts.get("retry", 0) == stats.retries,
          f"retry events: want {stats.retries}, "
          f"got {counts.get('retry', 0)}")
    check(counts.get("checkpoint", 0) == summary.get("checkpoint_saves"),
          "checkpoint spans != checkpoint_saves counter")
    # the injected faults must actually have fired, or the smoke is a no-op
    check(stats.degraded_blocks >= 1, "no degraded block despite hard fault")
    check(stats.retries >= 1, "no retry fired")
    check(summary.get("checkpoint_saves", 0) >= 1, "no checkpoint saved")
    # metrics/trace agreement
    check(summary.get("blocks_folded") == ROUNDS,
          f"blocks_folded counter: want {ROUNDS}, "
          f"got {summary.get('blocks_folded')}")
    check(summary.get("blocks_degraded") == stats.degraded_blocks,
          "blocks_degraded != degraded block count")
    # the simulated-clock track must be populated alongside the wall track
    sim = sum(1 for ev in doc["traceEvents"] if ev.get("cat") == "sim")
    check(sim >= ROUNDS, f"simulated-clock track too sparse ({sim} events)")

    print(f"trace artifact: {trace_path}")
    print(f"wall event counts: {dict(sorted(counts.items()))}")
    print(f"fault stats: retries={stats.retries} "
          f"degraded={stats.degraded_blocks}")
    if failures:
        for f in failures:
            print(f"FAIL: {f}")
        return 1
    print("telemetry smoke: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
