"""Open-loop predictions while cohort training publishes snapshots.

Set-up builds a seeded ``repro.cohort.Population``, runs a short job of the
same shapes up to the first Omega step (which compiles, or loads, the block
programs), opens
``Experiment.serve(train_seed, Serve(publish_every))`` and answers a few
requests from its cold snapshot (which compiles the lookup).  The window
then starts training in the background and sends requests on the
``arrivals.py`` schedule from this thread: each waits for its scheduled
time, or goes at once when the server is behind.  A request's latency is
its completion time minus its scheduled time, so a stall delays every
request queued behind it; a request that raised counts as missing
(infinite latency).  The window closes when the last request scheduled in
``--seconds`` is answered; training then runs to its end.

``predict_p50_ms`` / ``predict_p99_ms`` are over all requests of the
window.  Once it has closed, ``check_requests`` requests drawn from the
seed are checked: the plain reference replays the training run
(``reference_cohort.run_job``) up to the newest snapshot version any of
them used, and recomputes each one's margins under the version it used.

Traffic file keys: ``rate_per_s``, ``batch``, ``zipf_s``, ``feature_pool``,
``publish_every``, ``train_blocks`` (enough to outlast the window),
``warm_requests``, ``check_requests``, ``trace_seconds``.
"""
from __future__ import annotations

import contextlib
import gc
import time
from typing import Dict, List

import numpy as np

from benchmarks.chip import (arrivals, compare, federation, reference_cohort,
                             stats, trace_reduce)
from benchmarks.chip.clock import now
from benchmarks.chip.common import (Cell, CompileCounter, Outcome, log,
                                    memory_peak_bytes, stream_seeds)
from benchmarks.chip.drivers import cohort_jobs

_TRAIN_STREAM = 0x74726E  # "trn"
_CHECK_STREAM = 0x63686B  # "chk"


class Server:
    """The population, its train seed, and the compiled programs."""

    def __init__(self, cfg: Dict, traffic: Dict, seed: int):
        self.cfg, self.traffic = cfg, traffic
        self.pop_seed = int(stream_seeds(seed, cohort_jobs._POP_STREAM,
                                         1)[0])
        self.train_seed = int(stream_seeds(seed, _TRAIN_STREAM, 1)[0])
        self.pop = cohort_jobs.population(cfg, self.pop_seed)
        # as many blocks as reach the first Omega step, so that its
        # programs are compiled too
        cohort_jobs.experiment(
            cfg, {"blocks": max(cfg["omega_update_every"], 1)},
            self.pop).run(seed=self.train_seed)

    def session(self):
        from repro.api import Serve
        exp = cohort_jobs.experiment(
            self.cfg, {"blocks": self.traffic["train_blocks"]}, self.pop)
        return exp.serve(seed=self.train_seed, serve=Serve(
            publish_every=self.traffic["publish_every"]))


def window(server: Server, sess, arr: arrivals.Arrivals) -> Dict:
    """Send the arrivals to ``sess`` while it trains; per-request records."""
    import jax
    annotate = jax.profiler.TraceAnnotation
    n = len(arr)
    late = np.zeros(n)
    latency = np.full(n, np.inf)
    version = np.full(n, -1, np.int64)
    margins: List = [None] * n
    sess.start()
    t0 = now()
    with annotate("bench.window"):
        for i in range(n):
            due = t0 + arr.offsets[i]
            wait = due - now()
            if wait > 0:
                with annotate("bench.generate"):
                    time.sleep(wait)
            late[i] = now() - due
            ids, X = arr.request(i)
            try:
                with annotate("bench.request"):
                    margins[i] = sess.predict(ids, X)
                version[i] = sess.predictor.snapshot_version
                latency[i] = now() - due
            except Exception as e:  # noqa: BLE001 -- counted as missing
                log(f"request {i} failed: {e!r}")
    t_end = now()
    sess.join()
    return {"t0": t0, "t_end": t_end, "late": late, "latency": latency,
            "version": version, "margins": margins}


def _reference_margins(server: Server, arr: arrivals.Arrivals, rec: Dict,
                       picked, precision: str) -> np.ndarray:
    """The reference's margins of the requests ``picked``, each under the
    snapshot version it used: training replayed up to the newest one."""
    newest = int(rec["version"][picked].max())
    versions = [reference_cohort.State(server.cfg).snapshot()]
    if newest > 0:
        reference_cohort.run_job(server.cfg, server.pop_seed,
                                 server.train_seed,
                                 server.traffic["train_blocks"], precision,
                                 versions=versions, stop_after=newest)
    return np.concatenate([
        reference_cohort.margins(versions[rec["version"][i]],
                                 *arr.request(int(i)), precision)
        for i in picked])


def pick(rec: Dict, seed: int, count: int) -> np.ndarray:
    """``count`` answered requests, drawn from the seed."""
    answered = np.flatnonzero(np.isfinite(rec["latency"]))
    rng = np.random.default_rng(np.random.SeedSequence(
        [_CHECK_STREAM, federation.seed_entropy(seed)]))
    return rng.choice(answered, min(count, answered.size), replace=False)


def check(server: Server, arr: arrivals.Arrivals, rec: Dict, seed: int,
          count: int) -> Dict[str, float]:
    """The served margins of the picked requests against the reference's."""
    picked = pick(rec, seed, count)
    if picked.size == 0:
        return {"margin_rel": float("inf")}
    got = np.concatenate([np.asarray(rec["margins"][i], np.float64)
                          for i in picked])
    return compare.margin_readings(
        got, _reference_margins(server, arr, rec, picked, "highest"))


def readings(cell: Cell, seeds, n_faults: int) -> Dict[str, List]:
    """Sound and control readings on each seed, for ``readings.py``: a
    window of ``trace_seconds`` at the cell's rate, its picked requests
    checked against the reference, and the reference at the next precision
    down put in the program's place on the same requests and versions."""
    cfg, tr = cell.config, cell.traffic
    out = {"sound": [], "control": []}
    for seed in seeds:
        server = Server(cfg, tr, seed)
        arr = arrivals.Arrivals(tr, cfg["m"], cfg["d"], seed,
                                tr["rate_per_s"], tr["trace_seconds"])
        rec = window(server, server.session(), arr)
        picked = pick(rec, seed, tr["check_requests"])
        want = _reference_margins(server, arr, rec, picked, "highest")
        got = np.concatenate([np.asarray(rec["margins"][i], np.float64)
                              for i in picked])
        ctl = _reference_margins(server, arr, rec, picked, "high")
        out["sound"].append(compare.margin_readings(got, want))
        out["control"].append(compare.margin_readings(ctl, want))
    return out


def run(cell: Cell) -> Outcome:
    import jax
    cfg, tr = cell.config, cell.traffic
    server = Server(cfg, tr, cell.seed)
    seconds = tr["trace_seconds"] if cell.trace else cell.seconds
    arr = arrivals.Arrivals(tr, cfg["m"], cfg["d"], cell.seed,
                            tr["rate_per_s"], seconds)
    sess = server.session()
    for i in range(tr["warm_requests"]):
        jax.block_until_ready(sess.predict(*arr.request(i)))

    counter = CompileCounter()
    profile = (trace_reduce.Profile() if cell.trace
               else contextlib.nullcontext())
    with profile as prof:
        if cell.trace:
            # the profiler's start-up stalls the first device work it sees
            # (about 1.4 s on a v5e): requests outside the window take it
            with jax.profiler.TraceAnnotation("bench.trace_warm"):
                for i in range(tr["warm_requests"]):
                    jax.block_until_ready(sess.predict(*arr.request(i)))
        counter.active = True
        rec = window(server, sess, arr)
    counter.active = False
    log(f"compiles in window: {counter.counts}")
    latency_ms = 1e3 * rec["latency"]
    n = len(arr)
    outcome = Outcome(
        metrics={"predict_p50_ms": stats.percentile(latency_ms, 50),
                 "predict_p99_ms": stats.percentile(latency_ms, 99)},
        window_start=rec["t0"], attempted=n,
        failed=int(np.sum(~np.isfinite(rec["latency"]))),
        memory_peak_bytes=memory_peak_bytes(), correct=False, checks={})
    log(f"requests {n}, window {rec['t_end'] - rec['t0']:.3f} s, "
        f"newest version {int(rec['version'].max())}")
    if cell.trace:
        trace = prof.trace
        outcome.busy_s = trace_reduce.busy_s(trace)
        outcome.window_s = trace.window[1] - trace.window[0]
        outcome.layer = {
            "requests": n, "busy_s": outcome.busy_s,
            "window_s": outcome.window_s,
            "margins_s": trace_reduce.module_time(trace, "_margins"),
            "late_ms_p99": stats.percentile(1e3 * rec["late"], 99)}
        outcome.breakdown = {"device_ops": trace_reduce.top_ops(trace),
                             "idle_gaps": trace_reduce.longest_gaps(trace)}
    del sess
    gc.collect()
    readings = check(server, arr, rec, cell.seed, tr["check_requests"])
    outcome.correct, outcome.checks = compare.judge(
        readings, compare.load_limits(cell.name))
    return outcome
