"""Runtime telemetry layer (repro.obs): tracer/metrics/export units, the
off-path inertness and on-vs-off bit-identity guarantees, span coverage of
the faulty overlapped cohort pipeline, and the summarize CLI."""
import dataclasses
import glob
import json

import numpy as np
import pytest

from repro import obs
from repro.obs import tracer as tracer_mod
from repro.cohort import (CohortConfig, FaultConfig, Population,
                          PopulationSpec)
from repro.cohort.driver import _run_cohort
from repro.core import BudgetConfig, MochaConfig, Probabilistic
from repro.obs import summarize as summarize_mod
from repro.utils import timing

SPEC = PopulationSpec("t_obs", m=240, d=10, n_min=8, n_max=20, clusters=3)
REG = Probabilistic(lam=1e-2, sigma2=10.0)


def _cfg(**kw):
    base = dict(rounds=6, cohort=12, clusters=3, dropout=0.2,
                omega_update_every=2, record_every=1, seed=1,
                inner=MochaConfig(budget=BudgetConfig(passes=1.0)))
    base.update(kw)
    return CohortConfig(**base)


# -- tracer -----------------------------------------------------------------

def test_null_telemetry_is_inert():
    tel = obs.NULL_TELEMETRY
    assert not tel.enabled
    with tel.span("anything", block=3) as sp:
        sp.set(more=1)
    tel.event("retry", block=0)
    tel.counter("c").inc(5)
    tel.gauge("g").set(2.0)
    tel.histogram("h").observe(1.0)
    assert tel.tracer.spans() == {}
    assert tel.tracer.count("anything") == 0
    assert tel.metrics.summary() == {}
    # disabled views are shared, not copied
    assert tel.for_worker("pack") is tel
    assert obs.telemetry(False) is tel


def test_tracer_records_spans_per_worker():
    tel = obs.telemetry()
    assert tel.enabled
    with tel.span("fold", block=0) as sp:
        sp.set(degraded=False)
    with tel.for_worker("pack").span("pack", block=0):
        pass
    tel.for_worker("solve").event("retry", seam="solve", block=0, attempt=0)
    spans = tel.tracer.spans()
    assert set(spans) == {"main", "pack", "solve"}
    fold, = spans["main"]
    assert fold.name == "fold"
    assert fold.args == {"block": 0, "degraded": False}
    assert fold.dur_s is not None and fold.dur_s >= 0.0
    retry, = spans["solve"]
    assert retry.dur_s is None            # events are instants
    assert tel.tracer.count("pack") == 1
    assert tel.tracer.count("nope") == 0


def test_tracer_samples_sim_clock_alongside_wall():
    tel = obs.telemetry()
    sim = {"now": 5.0}
    tel.set_sim_clock(lambda: sim["now"])
    with tel.span("solve", block=1):
        sim["now"] = 7.5
    tel.event("retry", block=1)
    sp, ev = tel.tracer.spans()["main"]
    assert sp.sim_ts_s == 5.0 and sp.sim_dur_s == pytest.approx(2.5)
    assert ev.sim_ts_s == 7.5 and ev.sim_dur_s is None


# -- metrics ----------------------------------------------------------------

def test_metrics_registry_summary():
    tel = obs.telemetry()
    tel.counter("blocks_folded").inc()
    tel.counter("blocks_folded").inc(2)
    tel.gauge("frontier").set(4.0)
    tel.gauge("frontier").set(6.0)
    for v in (1.0, 2.0, 3.0, 4.0):
        tel.histogram("depth").observe(v)
    s = obs.metrics_summary(tel)
    assert s["blocks_folded"] == 3
    assert s["frontier.last"] == 6.0
    assert s["depth.count"] == 4 and s["depth.total"] == 10.0
    assert s["depth.p50"] == 2.0 and s["depth.p99"] == 4.0
    # same name -> same instrument (get-or-create semantics)
    assert tel.counter("blocks_folded") is tel.counter("blocks_folded")


def test_percentile_nearest_rank():
    from repro.obs.metrics import percentile
    vals = [10.0, 20.0, 30.0, 40.0]
    assert percentile(vals, 0.0) == 10.0
    assert percentile(vals, 50.0) == 20.0
    assert percentile(vals, 99.0) == 40.0
    with pytest.raises(ValueError):
        percentile([], 50.0)


# -- chrome export ----------------------------------------------------------

def _sample_tel():
    tel = obs.telemetry()
    clock = {"now": 0.0}
    tel.set_sim_clock(lambda: clock["now"])
    with tel.for_worker("pack").span("pack", block=0):
        clock["now"] = 1.0
    with tel.for_worker("solve").span("solve", block=0):
        clock["now"] = 3.0
    tel.for_worker("solve").event("retry", block=0, attempt=0)
    with tel.span("fold", block=0):
        pass
    tel.counter("blocks_folded").inc()
    return tel


def test_chrome_trace_layout_and_schema():
    doc = obs.to_chrome_trace(_sample_tel())
    assert obs.validate_chrome_trace(doc) == []
    names = {ev["args"]["name"] for ev in doc["traceEvents"]
             if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert names == {"main", "pack", "solve", "simulated-clock"}
    wall = [ev for ev in doc["traceEvents"] if ev.get("cat") == "wall"]
    sim = [ev for ev in doc["traceEvents"] if ev.get("cat") == "sim"]
    assert {ev["name"] for ev in wall} == {"pack", "solve", "retry", "fold"}
    # every span mirrors onto the single simulated-clock track
    assert len(sim) == len(wall)
    assert {ev["tid"] for ev in sim} == {100}
    # sim timestamps are the simulated clock, not wall offsets
    sim_solve, = (ev for ev in sim if ev["name"] == "solve")
    assert sim_solve["ts"] == pytest.approx(1.0 * 1e6)
    assert sim_solve["dur"] == pytest.approx(2.0 * 1e6)
    assert doc["otherData"]["metrics"]["blocks_folded"] == 1


def test_validate_chrome_trace_rejects_malformed():
    assert obs.validate_chrome_trace([]) != []
    assert obs.validate_chrome_trace({}) == ["traceEvents missing or not "
                                             "a list"]
    errs = obs.validate_chrome_trace({"traceEvents": [
        {"ph": "Z", "name": "x", "pid": 1, "tid": 1},
        {"ph": "X", "name": "x", "pid": 1, "tid": 1, "ts": 0.0, "dur": -1.0},
        {"ph": "X", "name": 3, "pid": 1, "tid": "t", "ts": "now"},
        {"ph": "M", "name": "process_name", "pid": 1, "tid": 1},
    ]})
    assert len(errs) == 7
    assert any("negative dur" in e for e in errs)


def test_wall_extent_uses_interval_union(tmp_path):
    # nested + overlapping spans must not double-count busy time
    def x(name, tid, ts, dur):
        return {"ph": "X", "name": name, "cat": "wall", "pid": 1, "tid": tid,
                "ts": ts, "dur": dur}
    doc = {"traceEvents": [
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 1,
         "args": {"name": "main"}},
        x("fold", 1, 0.0, 10.0), x("checkpoint", 1, 2.0, 4.0),  # nested
        x("fold", 1, 20.0, 10.0),
    ]}
    ext = obs.wall_extent(doc, worker="main")
    assert ext["span_s"] == pytest.approx(30.0 / 1e6)
    assert ext["busy_s"] == pytest.approx(20.0 / 1e6)
    assert obs.wall_extent(doc, worker="pack") == {"span_s": 0.0,
                                                   "busy_s": 0.0}


def test_write_trace_roundtrip(tmp_path):
    path = obs.write_trace(str(tmp_path / "sub" / "t.json"), _sample_tel())
    with open(path) as fh:
        doc = json.load(fh)
    assert obs.validate_chrome_trace(doc) == []
    assert not (tmp_path / "sub" / "t.json.tmp").exists()


# -- the sanctioned wall clock (satellite: timing unit pin) -----------------

def test_timed_returns_microseconds(monkeypatch):
    reads = iter([2.0, 2.5])
    monkeypatch.setattr(timing.time, "perf_counter", lambda: next(reads))
    out, elapsed = timing.timed(lambda a: a + 1, 41)
    assert out == 42
    assert elapsed == pytest.approx(0.5e6)   # microseconds, NOT seconds


# -- cohort integration -----------------------------------------------------

def test_cohort_bit_identity_telemetry_on_vs_off():
    """Exec.telemetry=True must not perturb one bit of the run: tracing
    only READS state -- no RNG draw, no simulated-clock charge."""
    pop = Population(SPEC, seed=0)
    kw = dict(overlap=2, staleness=1, max_retries=1, degrade=True,
              faults=FaultConfig(solve_fail_prob=0.3, seed=3))
    plain = _run_cohort(pop, REG, _cfg(**kw))
    traced = _run_cohort(pop, REG, _cfg(telemetry=True, **kw))
    assert plain.history == traced.history
    np.testing.assert_array_equal(plain.centroids, traced.centroids)
    np.testing.assert_array_equal(plain.omega_k, traced.omega_k)
    np.testing.assert_array_equal(plain.assign, traced.assign)
    np.testing.assert_array_equal(plain.participation, traced.participation)


def test_cohort_span_coverage_under_faults():
    """Every pack/solve/fold/retry/degrade/checkpoint occurrence of a
    faulty overlapped run appears in the trace, and the counters agree
    with the run's own fault accounting."""
    pop = Population(SPEC, seed=0)
    tel = obs.telemetry()
    cfg = _cfg(overlap=2, staleness=1, max_retries=1, degrade=True,
               faults=FaultConfig(solve_fail_prob=0.25,
                                  solve_fail_blocks=(3,), seed=5))
    res = _run_cohort(pop, REG, cfg, telemetry=tel)
    stats = res.fault_stats
    assert stats.degraded_blocks >= 1 and stats.retries >= 1
    tr = tel.tracer
    assert tr.count("pack") == cfg.rounds
    assert tr.count("solve") == cfg.rounds     # pack never exhausts here
    assert tr.count("fold") == cfg.rounds
    assert tr.count("degrade") == stats.degraded_blocks
    assert tr.count("retry") == stats.retries
    s = obs.metrics_summary(tel)
    assert s["blocks_folded"] == cfg.rounds
    assert s["blocks_degraded"] == stats.degraded_blocks
    assert s["retries"] == stats.retries
    assert s["blocks_solved"] == cfg.rounds - stats.degraded_blocks
    # the pack queue depth is observed once per block
    assert s["pack_queue_depth.count"] == cfg.rounds
    assert s["launch_staleness.p99"] <= cfg.staleness
    # worker attribution: pack spans on the pack track, solves on solve
    spans = tr.spans()
    assert {sp.name for sp in spans["pack"]} <= {"pack", "retry"}
    assert "solve" in {sp.name for sp in spans["solve"]}
    assert "fold" in {sp.name for sp in spans["main"]}


@pytest.mark.parametrize("threads", [1, 4])
def test_pack_spans_carry_the_draw_pool_size(monkeypatch, threads):
    """Each `pack` span says how many workers drew its block and the
    `pack_threads` gauge holds it; off, the run is bit-identical and
    records nothing."""
    from repro.cohort import packing
    monkeypatch.setattr(packing, "_usable_cores", lambda: threads)
    pop = Population(SPEC, seed=0)
    tel = obs.telemetry()
    traced = _run_cohort(pop, REG, _cfg(), telemetry=tel)
    packs = [sp for sp in tel.tracer.spans()["pack"] if sp.name == "pack"]
    assert len(packs) == 6
    assert all(sp.args["threads"] == threads for sp in packs)
    assert obs.metrics_summary(tel)["pack_threads.last"] == threads
    appended = []
    monkeypatch.setattr(tracer_mod.Tracer, "_append",
                        lambda self, sp: appended.append(sp.name))
    plain = _run_cohort(pop, REG, _cfg())
    assert appended == []
    assert plain.history == traced.history
    np.testing.assert_array_equal(plain.centroids, traced.centroids)
    np.testing.assert_array_equal(plain.assign, traced.assign)


def test_degraded_metrics_carried_emits_event_and_counter():
    """Satellite regression: a degraded block's carried-forward metrics are
    VISIBLE -- one `degraded_metrics_carried` event tagged with the stale
    values, counted by `blocks_degraded`, so silent staleness cannot
    recur."""
    pop = Population(SPEC, seed=0)
    dead = 2
    tel = obs.telemetry()
    res = _run_cohort(pop, REG, _cfg(
        max_retries=1, degrade=True,
        faults=FaultConfig(solve_fail_blocks=(dead,))), telemetry=tel)
    assert res.fault_stats.degraded_blocks == 1
    summary = obs.metrics_summary(tel)
    assert summary["blocks_degraded"] == 1
    assert "degraded_metrics_carried" not in summary
    events = [sp for sp in tel.tracer.spans()["main"]
              if sp.name == "degraded_metrics_carried"]
    assert len(events) == 1
    args = events[0].args
    assert args["block"] == dead
    h = res.history
    # the event carries exactly the stale (previous block's) metrics
    assert args["dual"] == h["dual"][dead - 1] == h["dual"][dead]
    assert args["primal"] == h["primal"][dead - 1]
    assert args["gap"] == h["gap"][dead - 1]


def test_checkpoint_spans_record_bytes():
    pop = Population(SPEC, seed=0)
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        tel = obs.telemetry()
        _run_cohort(pop, REG, _cfg(checkpoint_every=2, checkpoint_dir=td),
                    telemetry=tel)
        saves = [sp for sp in tel.tracer.spans()["main"]
                 if sp.name == "checkpoint"]
        assert len(saves) == 3                 # blocks 2, 4, 6
        assert all(sp.args["bytes"] > 0 for sp in saves)
        s = obs.metrics_summary(tel)
        assert s["checkpoint_saves"] == 3
        assert s["checkpoint_bytes"] == sum(sp.args["bytes"] for sp in saves)
        assert s["checkpoint_save_s.count"] == 3


# -- api surface ------------------------------------------------------------

def test_experiment_trace_artifact_and_provenance(tmp_path):
    from repro.api import Exec, Experiment, Method, Problem
    exp = Experiment(
        problem=Problem(population=Population(SPEC, seed=0)),
        method=Method(regularizers=[REG], rounds=4),
        exec=Exec(cohort=12, clusters=3, overlap=2, staleness=1,
                  trace_dir=str(tmp_path)),   # trace_dir implies telemetry
    )
    rep = exp.run(seed=0)
    prov = rep.provenance
    assert prov["telemetry"]["blocks_folded"] == 4
    assert prov["trace_path"] == str(
        tmp_path / f"trace_{prov['config_hash']}_s0.json")
    with open(prov["trace_path"]) as fh:
        doc = json.load(fh)
    assert obs.validate_chrome_trace(doc) == []
    wall = [ev["name"] for ev in doc["traceEvents"]
            if ev.get("cat") == "wall"]
    assert wall.count("fold") == 4 and "route" in wall
    # rerun -> deterministic artifact name, so reruns overwrite in place
    rep2 = exp.run(seed=0)
    assert rep2.provenance["trace_path"] == prov["trace_path"]


def test_telemetry_off_by_default_in_provenance():
    from repro.api import Exec, Experiment, Method, Problem
    from repro.data.synthetic import tiny_problem
    train, _ = tiny_problem(m=4, n=16, d=5, seed=0)
    exp = Experiment(problem=Problem(train=train),
                     method=Method(regularizers=[REG], rounds=3))
    rep = exp.run(seed=0)
    assert rep.provenance["telemetry"] is None
    assert rep.provenance["trace_path"] is None


def test_run_fingerprint_normalizes_telemetry_knobs():
    from repro.cohort.resilience import run_fingerprint
    pop = Population(SPEC, seed=0)
    base = run_fingerprint(pop, REG, _cfg())
    assert run_fingerprint(pop, REG, _cfg(
        telemetry=True, trace_dir="/tmp/x")) == base
    assert run_fingerprint(pop, REG, _cfg(rounds=7)) != base


# -- summarize CLI ----------------------------------------------------------

def test_summarize_cli_renders_trace(tmp_path, capsys):
    path = obs.write_trace(str(tmp_path / "t.json"), _sample_tel())
    assert summarize_mod.main([path, "--strict"]) == 0
    out = capsys.readouterr().out
    for phase in ("pack", "solve", "fold"):
        assert phase in out
    assert "bubble fraction" in out
    assert "blocks_folded = 1" in out


def test_summarize_cli_strict_rejects_malformed(tmp_path, capsys):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [{"ph": "Z"}]}))
    assert summarize_mod.main([str(bad), "--strict"]) == 1
    assert summarize_mod.main([str(bad)]) == 0   # non-strict: warn only


# -- the profiler bridge and the jax.trace / jax.compile events -------------

def _host_events(trace_dir, names):
    """{name -> [(start_ns, end_ns, stats)]} of the host-line events whose
    name is in ``names``, from the xplane ``jax.profiler`` wrote."""
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.end_ns, dict(ev.stats)))
    return out


def test_spans_land_on_the_profiler_host_line(tmp_path):
    """A recording span and a span nested in it appear in the xplane's host
    line under their names, nested, with their scalar args as metadata;
    an instant event is a zero-length annotation inside them."""
    import jax
    tel = obs.telemetry()
    with jax.profiler.trace(str(tmp_path)):
        with tel.span("obs.outer", block=3):
            with tel.span("obs.inner", tag="x"):
                tel.event("obs.mark", n=2)
    got = _host_events(str(tmp_path), {"obs.outer", "obs.inner", "obs.mark"})
    (o0, o1, outer), = got["obs.outer"]
    (i0, i1, inner), = got["obs.inner"]
    (m0, m1, mark), = got["obs.mark"]
    assert o0 <= i0 <= m0 <= m1 <= i1 <= o1
    assert outer == {"block": 3} and inner == {"tag": "x"}
    assert mark == {"n": 2}
    # the Chrome-JSON sink still records the same spans
    assert [sp.name for sp in tel.tracer.spans()["main"]] == [
        "obs.mark", "obs.inner", "obs.outer"]


def _fresh_experiment(telemetry, d):
    """A single-path experiment at a feature width no other test uses, so
    its programs trace anew."""
    from repro.api import Eval, Exec, Experiment, Method, Problem
    from repro.data.synthetic import tiny_problem
    train, test = tiny_problem(m=3, n=12, d=d, seed=0)
    return Experiment(problem=Problem(train=train),
                      method=Method(regularizers=[REG], rounds=4,
                                    omega_update_every=2),
                      exec=Exec(driver="scan", telemetry=telemetry),
                      eval=Eval(record_every=2, holdout=test))


def test_telemetry_off_builds_no_annotation_and_records_nothing(
        monkeypatch):
    """Off: no TraceAnnotation is constructed and the jax.monitoring
    listener appends nothing, although the run traces fresh programs; on,
    the same counters move (so they do watch the right calls)."""
    import jax
    obs.telemetry()                       # the listener is installed
    built, appended = [], []
    real = jax.profiler.TraceAnnotation
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda *a, **k: built.append(a) or real(*a, **k))
    real_append = tracer_mod.Tracer._append
    monkeypatch.setattr(tracer_mod.Tracer, "_append",
                        lambda self, sp: appended.append(sp.name)
                        or real_append(self, sp))
    _fresh_experiment(False, d=13).run(seed=0)
    assert built == [] and appended == []
    _fresh_experiment(True, d=14).run(seed=0)
    assert built and "jax.trace" in appended


def test_jax_trace_event_names_the_open_span():
    """A fresh jitted function called inside a span records exactly one
    jax.trace event, with span=<that span>; a second call records none,
    and a trace with no recording span open records nothing."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    tel = obs.telemetry()
    # lax primitives only: a jnp operator would trace its own inner jit
    f = jax.jit(lambda x: lax.add(lax.mul(x, x), x))
    g = jax.jit(lambda x: lax.sub(x, x))
    x = jnp.arange(4.0)
    g(x)                                  # no span open: dropped
    with tel.span("obs.first"):
        with tel.span("obs.call"):
            f(x).block_until_ready()
    with tel.span("obs.second"):
        f(x).block_until_ready()
    traces = [sp for sp in tel.tracer.spans()["main"]
              if sp.name == "jax.trace"]
    assert len(traces) == 1
    assert traces[0].args["span"] == "obs.call"
    assert tel.tracer.count("jax.trace") == 1
    compiles = [sp.args["span"] for sp in tel.tracer.spans()["main"]
                if sp.name == "jax.compile"]
    if not jax.config.jax_compilation_cache_dir:   # no cache hit possible
        assert compiles == ["obs.call"]


def test_single_path_records_every_phase(tmp_path):
    """Experiment.run on the scanned single path: one setup / presample /
    host_pull / replay / eval span per run, a scan_dispatch per segment and
    an omega_step per Omega step."""
    from repro.api import Eval, Exec, Experiment, Method, Problem
    from repro.data.synthetic import tiny_problem
    train, test = tiny_problem(m=4, n=16, d=5, seed=0)
    exp = Experiment(problem=Problem(train=train),
                     method=Method(regularizers=[REG], rounds=10,
                                   omega_update_every=3),
                     exec=Exec(driver="scan", trace_dir=str(tmp_path)),
                     eval=Eval(record_every=2, holdout=test))
    with open(exp.run(seed=0).provenance["trace_path"]) as fh:
        wall = [ev for ev in json.load(fh)["traceEvents"]
                if ev.get("cat") == "wall"]
    names = [ev["name"] for ev in wall]
    for name in ("experiment", "mocha.run", "mocha.setup", "mocha.presample",
                 "mocha.host_pull", "mocha.replay", "eval"):
        assert names.count(name) == 1, name
    assert names.count("mocha.scan_dispatch") == 4   # rounds 0-3-6-9-10
    assert names.count("mocha.omega_step") == 3      # after rounds 3, 6, 9
    run, = (ev for ev in wall if ev["name"] == "mocha.run")
    setup, = (ev for ev in wall if ev["name"] == "mocha.setup")
    assert run["ts"] <= setup["ts"]
    assert setup["ts"] + setup["dur"] <= run["ts"] + run["dur"]


@pytest.mark.parametrize("driver", ["scan", "loop"])
def test_omega_step_compiles_once_per_shape(tmp_path, driver):
    """Each Omega step is one compiled program: only a run's first
    ``mocha.omega_step`` carries ``compile=True``, and a second run of the
    same Experiment traces nothing under any of its steps."""
    from repro.api import Exec, Experiment, Method, Problem
    from repro.data.synthetic import tiny_problem
    train, _ = tiny_problem(m=4, n=16, d=5, seed=2)
    exp = Experiment(problem=Problem(train=train),
                     method=Method(regularizers=[REG], rounds=9,
                                   omega_update_every=3),
                     exec=Exec(driver=driver, trace_dir=str(tmp_path)))

    def wall_events(seed):
        with open(exp.run(seed=seed).provenance["trace_path"]) as fh:
            return [ev for ev in json.load(fh)["traceEvents"]
                    if ev.get("cat") == "wall"]

    first, second = wall_events(0), wall_events(1)
    for wall in (first, second):
        steps = [ev["args"] for ev in wall if ev["name"] == "mocha.omega_step"]
        assert [a["round"] for a in steps] == [3, 6, 9]
        assert [a["compile"] for a in steps] == [True, False, False]
    assert not [ev for ev in second if ev["name"] == "jax.trace"
                and ev["args"]["span"] == "mocha.omega_step"]


@pytest.mark.parametrize("driver", ["scan", "loop"])
def test_single_path_bit_identity_telemetry_on_vs_off(driver):
    """The single path's results with telemetry on equal those with it
    off, to the bit, on both drivers."""
    from repro.api import Eval, Exec, Experiment, Method, Problem
    from repro.data.synthetic import tiny_problem
    train, test = tiny_problem(m=4, n=16, d=5, seed=1)

    def run(telemetry):
        return Experiment(
            problem=Problem(train=train),
            method=Method(regularizers=[REG], rounds=8,
                          omega_update_every=3),
            exec=Exec(driver=driver, telemetry=telemetry),
            eval=Eval(record_every=1, holdout=test)).run(seed=7)

    plain, traced = run(False), run(True)
    assert plain.provenance["telemetry"] is None
    assert traced.provenance["telemetry"] is not None
    res_p, res_t = plain.result, traced.result
    np.testing.assert_array_equal(res_p.W, res_t.W)
    np.testing.assert_array_equal(res_p.omega, res_t.omega)
    np.testing.assert_array_equal(np.asarray(res_p.state.alpha),
                                  np.asarray(res_t.state.alpha))
    np.testing.assert_array_equal(res_p.round_budgets, res_t.round_budgets)
    assert res_p.history == res_t.history
    assert plain.evaluation.summary == traced.evaluation.summary


@pytest.mark.parametrize("driver", ["scan", "loop"])
@pytest.mark.parametrize("mode,d", [("gram", 12), ("carry", 136)])
def test_setup_args_and_sdca_counters_follow_the_solver_plan(tmp_path, mode,
                                                             d, driver):
    """``mocha.setup`` carries the SDCA loop's plan as ``_solver_plan``
    gives it; ``sdca.steps_required`` is the executed budgets' sum and
    ``sdca.steps_lockstep`` rounds x m x n_chunks x C, on either driver; W
    is the same to the bit with telemetry off.  36 training points per task
    are no multiple of either chunk, so the lockstep count includes a
    padded chunk."""
    from repro.api import Exec, Experiment, Method, Problem
    from repro.core.subproblem import _solver_plan, chunk_idx_stream
    from repro.data.synthetic import tiny_problem
    m, rounds, budget = 3, 5, BudgetConfig(passes=1.0)
    train, _ = tiny_problem(m=m, n=48, d=d, seed=3)
    steps = budget.max_steps(train.n_max)

    def run(**exec_kw):
        return Experiment(
            problem=Problem(train=train),
            method=Method(regularizers=[REG], rounds=rounds,
                          omega_update_every=2, budget=budget),
            exec=Exec(driver=driver, **exec_kw)).run(seed=4)

    traced, plain = run(trace_dir=str(tmp_path)), run()
    gram, C = _solver_plan(d, steps, None)
    n_chunks = chunk_idx_stream(np.zeros(steps, np.int32), steps, C).shape[0]
    assert steps == 36 and n_chunks * C > steps
    assert ("gram" if gram else "carry") == mode
    assert traced.provenance["gram_mode"] == mode
    with open(traced.provenance["trace_path"]) as fh:
        setup, = (ev for ev in json.load(fh)["traceEvents"]
                  if ev.get("cat") == "wall" and ev["name"] == "mocha.setup")
    assert setup["args"] == {"residual_mode": mode, "chunk": C,
                             "max_steps": steps, "n_chunks": n_chunks}
    counters = traced.provenance["telemetry"]
    assert counters["sdca.steps_required"] == int(
        traced.result.round_budgets.sum())
    assert counters["sdca.steps_lockstep"] == rounds * m * n_chunks * C
    np.testing.assert_array_equal(plain.result.W, traced.result.W)
    np.testing.assert_array_equal(plain.result.round_budgets,
                                  traced.result.round_budgets)
