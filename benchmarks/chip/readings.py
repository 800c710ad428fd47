"""Readings that set a training cell's correctness limits, on the chip.

    python3 benchmarks/chip/readings.py --workload ha.full --seeds 12 \\
        --faults 3 --first-seed 1000

For each of ``--seeds`` seeds, in one process: the cell's federation from
the seed; the first ``check_jobs`` jobs of the seed's job stream (as many
as a run checks) through the program's timed path, the same
``Experiment`` and programs the window runs; the plain reference's run of
each at the configuration's precision; and the control -- the reference at
the next precision down (``reference.py``, ``precision="high"``) put in
the program's place.  The seed's jobs are combined as a run combines its
checked jobs (``compare.worst``).  On the first ``--faults`` seeds, each
planted fault of ``faults.py`` too, on the seed's last job.  Prints one
JSON line per job and per seed, then a summary: the lower reading of each
number (the largest over the seeds' sound readings), the control's
smallest, and each fault's smallest.  The limits in ``limits/<cell>.json``
are set between them.  A driver whose traffic is not a run of jobs (the
serving driver) supplies its own ``readings`` of the same kinds.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

import importlib  # noqa: E402

from benchmarks.chip import compare, faults, jobs  # noqa: E402
from benchmarks.chip import run as bench_run  # noqa: E402


def _emit(kind: str, seed: int, numbers) -> None:
    print(json.dumps({"kind": kind, "seed": seed, **numbers}), flush=True)


def readings(cell, seeds, n_faults: int):
    driver = importlib.import_module(
        f"benchmarks.chip.drivers.{cell.traffic['driver']}")
    if hasattr(driver, "readings"):
        out = driver.readings(cell, seeds, n_faults)
        for kind, rows in out.items():
            for seed, r in zip(seeds, rows):
                _emit(kind, seed, r)
        return out
    out = {"sound": [], "control": [],
           **{name: [] for name in faults.FAULTS}}
    for i, seed in enumerate(seeds):
        cell_jobs = driver.make(cell, seed)
        stream = jobs.job_seeds(seed)
        per_job = {"sound": [], "control": []}
        for _ in range(cell.traffic["check_jobs"]):
            job_seed = next(stream)
            prog = cell_jobs.job(job_seed)
            ref = cell_jobs.reference(job_seed, "highest")
            ctl = cell_jobs.reference(job_seed, "high")
            for kind, got in (("sound", prog), ("control", ctl)):
                r = cell_jobs.readings(got, ref)
                per_job[kind].append(r)
                _emit(kind + ".job", seed, dict(r, job=job_seed))
        for kind, rows in per_job.items():
            r = compare.worst(rows)
            out[kind].append(r)
            _emit(kind, seed, r)
        if i < n_faults:
            for name, plant in faults.FAULTS.items():
                with plant():
                    bad = cell_jobs.job(job_seed)
                r = cell_jobs.readings(bad, ref)
                out[name].append(r)
                _emit(name, seed, r)
        cell_jobs.release()
    return out


def summary(out):
    keys = list(out["sound"][0])
    doc = {"lower": {k: max(r[k] for r in out["sound"]) for k in keys}}
    for kind, rows in out.items():
        if kind != "sound" and rows:
            doc[kind] = {k: min(r[k] for r in rows) for k in keys}
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="run on JAX's default device, chip or not")
    args = ap.parse_args(argv)
    bench = bench_run.load_benchmark()
    cell = bench_run.make_cell(bench, args.workload, args.first_seed, 0.0,
                               False)
    if not args.allow_cpu:
        bench_run.check_chips(cell.chips)
    bench_run.use_compile_cache()
    seeds = [args.first_seed + i for i in range(args.seeds)]
    doc = summary(readings(cell, seeds, args.faults))
    print(json.dumps({"summary": doc}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
