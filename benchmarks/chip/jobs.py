"""The window of a cell whose traffic is back-to-back training jobs.

A driver of such traffic describes its jobs with four functions (``Jobs``);
this module runs them the same way for every such cell:

* set-up runs one job, which compiles (or loads from the cache) every
  program the window runs, on the same object the window then uses;
* the window runs jobs back to back, each from its own seed drawn from
  ``--seed``, until ``--seconds`` have passed; the job running at that
  moment completes.  A job ends when its answer is on the host.  The rate
  is the work units (rounds, blocks) of all jobs / (end of the last job -
  start of the window);
* once the window has closed, ``check_jobs`` of its jobs, drawn from the
  seed, are run again by the plain reference and compared number by number
  (``compare.py``).

With ``--trace 1`` the window lasts ``trace_seconds`` under the profiler,
and the driver's ``layer`` turns the trace into what the per-layer metric
readers read.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
from typing import Callable, Dict, List

import numpy as np

from benchmarks.chip import compare, federation, trace_reduce
from benchmarks.chip.clock import now
from benchmarks.chip.common import (Cell, CompileCounter, Outcome, log,
                                    memory_peak_bytes, stream_seeds)

JOB_STREAM = 0x6A6F62    # "job"
CHECK_STREAM = 0x63686B  # "chk"


@dataclasses.dataclass
class Jobs:
    """What a driver supplies.

    ``job(seed)`` runs one job through the program and returns its answer
    (host arrays); ``reference(seed, precision)`` runs the same job on the
    plain reference; ``readings(answer, ref)`` compares them; ``layer(trace,
    jobs)`` reduces a traced window for the per-layer readers.  ``units``
    is the work one job completes; ``metric`` names the rate."""

    job: Callable[[int], Dict]
    reference: Callable[[int, str], Dict]
    readings: Callable[[Dict, Dict], Dict[str, float]]
    layer: Callable[[trace_reduce.Trace, List[Dict]], Dict]
    units: int
    metric: str
    #: called as the window opens (after set-up's job) and once it closed
    begin: Callable[[], None] = lambda: None
    release: Callable[[], None] = lambda: None


def job_seeds(seed: int):
    return iter(int(s) for s in stream_seeds(seed, JOB_STREAM, 100_000))


def run(cell: Cell, jobs: Jobs, seeds) -> Outcome:
    import jax
    tr = cell.traffic
    jobs.job(next(seeds))                        # compiles or loads

    counter = CompileCounter()
    seconds = tr["trace_seconds"] if cell.trace else cell.seconds
    done: List[Dict] = []
    profile = (trace_reduce.Profile() if cell.trace
               else contextlib.nullcontext())
    annotate = jax.profiler.TraceAnnotation
    with profile as prof:
        if cell.trace:
            # the profiler's start-up stalls the first job it sees (about
            # 1.4 s on a v5e): one job outside the window takes it
            with annotate("bench.trace_warm"):
                jobs.job(next(seeds))
        jobs.begin()
        counter.active = True
        t0 = now()
        with annotate("bench.window"):
            while True:
                seed = next(seeds)
                with annotate("bench.job"):
                    out = jobs.job(seed)
                t = now()
                with annotate("bench.between_jobs"):
                    done.append(dict(out, seed=seed, end=t))
                if t - t0 >= seconds:
                    break
    counter.active = False
    log(f"compiles in window: {counter.counts}")
    took = np.diff([t0] + [j["end"] for j in done])
    log(f"job seconds: min {took.min()!r} median {np.median(took)!r} "
        f"max {took.max()!r} over {len(done)} jobs")
    metrics = {jobs.metric: jobs.units * len(done) / (done[-1]["end"] - t0)}
    outcome = Outcome(metrics=metrics, window_start=t0, attempted=len(done),
                      failed=0, memory_peak_bytes=memory_peak_bytes(),
                      correct=False, checks={})
    if cell.trace:
        trace = prof.trace
        outcome.busy_s = trace_reduce.busy_s(trace)
        outcome.window_s = trace.window[1] - trace.window[0]
        outcome.layer = dict(jobs.layer(trace, done),
                             busy_s=outcome.busy_s,
                             window_s=outcome.window_s)
        outcome.breakdown = {"device_ops": trace_reduce.top_ops(trace),
                             "idle_gaps": trace_reduce.longest_gaps(trace)}

    jobs.release()
    gc.collect()
    rng = np.random.default_rng(np.random.SeedSequence(
        [CHECK_STREAM, federation.seed_entropy(cell.seed)]))
    picked = rng.choice(len(done), min(tr["check_jobs"], len(done)),
                        replace=False)
    readings = [jobs.readings(done[j], jobs.reference(done[j]["seed"],
                                                      "highest"))
                for j in sorted(picked)]
    outcome.correct, outcome.checks = compare.judge(
        compare.worst(readings), compare.load_limits(cell.name))
    return outcome
