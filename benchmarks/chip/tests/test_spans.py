"""The program's spans in a profiler trace: ``span_time`` and
``idle_by_span`` against brute force on the trace recorded on a v5e, and the
``jax.trace`` events of a traced CPU run by the span that made them."""
import gzip
import shutil

import numpy as np

from benchmarks.chip import spans
from benchmarks.chip import trace_reduce as tr


def _sample_trace(tmp_path):
    raw = tmp_path / "sample.xplane.pb"
    with gzip.open(tr.SAMPLE, "rb") as src, open(raw, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(raw), tr.load(str(raw))


def _made_up_trace():
    ops = [(0.1, 0.15, "a"), (0.35, 0.4, "b"), (0.7, 0.8, "c")]
    host = [(0.05, 0.95, "bench.window"), (0.05, 0.5, "bench.job"),
            (0.2, 0.3, "PjitFunction(_metrics)")]
    return tr.Trace(ops=[ops], modules=[[]], host=host, window=(0.05, 0.95))


def _grid(trace, names, step):
    """Grid midpoints of the window, whether device 0 is idle at each, and
    the innermost (shortest) named host span open there."""
    lo, hi = trace.window
    t = np.arange(lo, hi, step) + 0.5 * step
    idle = np.ones(t.shape, bool)
    for s, e, _ in trace.ops[0]:
        idle &= ~((t >= s) & (t < e))
    named = [sp for sp in trace.host if sp[2] in names]
    labels = []
    for x in t:
        open_ = [sp for sp in named if sp[0] <= x <= sp[1]]
        best = min(open_, key=lambda sp: sp[1] - sp[0], default=None)
        labels.append(best[2] if best else "none")
    return t, idle, np.array(labels)


def test_span_time_on_the_v5e_sample(tmp_path):
    """Its four ``bench.job`` and ``bench.sleep`` spans, against a grid."""
    _, trace = _sample_trace(tmp_path)
    lo, hi = trace.window
    step = 2e-7
    for name in ("bench.job", "bench.sleep", "bench.window"):
        runs = [(s, e) for s, e, n in trace.host if n == name]
        t = np.arange(lo, hi, step) + 0.5 * step
        hit = np.zeros(t.shape, bool)
        for s, e in runs:
            hit |= (t >= s) & (t < e)
        assert abs(spans.span_time(trace, name) - hit.sum() * step) \
            < 2 * step * len(runs)
    assert spans.span_time(trace, "absent") == 0.0
    assert spans.span_time(trace, "bench.job", within="bench.sleep") == 0.0
    assert spans.span_time(trace, "bench.job", within="bench.window") \
        == spans.span_time(trace, "bench.job")
    assert spans.has_span(trace, "bench.job")
    assert not spans.has_span(trace, "experiment")


def test_idle_by_span_on_the_v5e_sample(tmp_path):
    """Every idle second of the window goes to exactly one named span, the
    innermost open, as a grid finds it; the sleeps hold the idle time."""
    _, trace = _sample_trace(tmp_path)
    names = ("bench.window", "bench.job", "bench.sleep")
    got = spans.idle_by_span(trace, names)
    _, idle, labels = _grid(trace, names, 2e-7)
    for name in set(labels[idle]) | set(got):
        brute = float(np.sum(idle & (labels == name)) * 2e-7)
        assert abs(got.get(name, 0.0) - brute) < 2e-6, name
    total_idle = sum(e - s for s, e in tr.idle_gaps(trace))
    assert abs(sum(got.values()) - total_idle) < 1e-12
    assert got["bench.sleep"] > 0.5 * total_idle


def test_idle_by_span_passes_over_unnamed_spans():
    """A Python frame or JAX event inside a named span is passed over: the
    idle time under it goes to the named span around it."""
    trace = _made_up_trace()
    named = spans.idle_by_span(trace, ("bench.window", "bench.job"))
    framed = spans.idle_by_span(trace, ("bench.window", "bench.job",
                                        "PjitFunction(_metrics)"))
    assert set(named) == {"bench.window", "bench.job"}
    assert abs(named["bench.job"] - 0.35) < 1e-12
    assert abs(framed["PjitFunction(_metrics)"] - 0.1) < 1e-12
    assert abs(sum(named.values()) - sum(framed.values())) < 1e-12
    bare = tr.Trace(ops=trace.ops, modules=[[]], host=[],
                    window=trace.window)
    assert set(spans.idle_by_span(bare)) == {"none"}


def test_marks_by_span_counts_inside_the_window():
    marks = [(0.1, "jax.trace", {"span": "mocha.presample"}),
             (0.2, "jax.trace", {"span": "mocha.presample"}),
             (0.3, "jax.compile", {"span": "eval"}),
             (0.99, "jax.trace", {"span": "mocha.presample"})]
    assert spans.marks_by_span(marks, (0.05, 0.95)) == {
        "jax.trace": {"mocha.presample": 2}, "jax.compile": {"eval": 1}}
    assert spans.marks_by_span([], (0.0, 1.0)) == {}


def test_program_spans_and_traces_in_a_cpu_trace(tmp_path):
    """Under the profiler, a recording span of the program is a host span
    of the window's thread, and the fresh jit called in it a ``jax.trace``
    event naming it; the v5e sample, from a program without them, has
    none."""
    import jax
    from jax import lax

    from repro import obs
    tel = obs.telemetry()
    f = jax.jit(lambda x: lax.mul(lax.add(x, x), x))
    x = jax.numpy.arange(5.0)
    with jax.profiler.trace(str(tmp_path / "run")):
        with jax.profiler.TraceAnnotation("bench.window"):
            with tel.span("mocha.presample"):
                f(x).block_until_ready()
    path = tr.find_xplane(str(tmp_path / "run"))
    trace = tr.load(path)
    assert spans.span_time(trace, "mocha.presample") > 0.0
    got = spans.marks_by_span(spans.load_marks(path), trace.window)
    assert got["jax.trace"] == {"mocha.presample": 1}
    sample, _ = _sample_trace(tmp_path)
    assert spans.load_marks(sample) == []
