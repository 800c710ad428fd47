"""The trace reduction against brute force on a made-up window."""
import numpy as np

from benchmarks.chip import trace_reduce as tr


def _trace(seed=0, n=200):
    rng = np.random.default_rng(seed)
    starts = np.sort(rng.uniform(0.0, 1.0, n))
    ops = [(s, s + d, f"op{k % 7}")
           for k, (s, d) in enumerate(zip(starts, rng.uniform(0, 0.01, n)))]
    modules = [(0.1, 0.4, "jit__scan_rounds(1)"), (0.5, 0.9, "jit_other")]
    host = [(0.05, 0.95, "bench.window"), (0.05, 0.5, "bench.job"),
            (0.2, 0.3, "PjitFunction(_metrics)")]
    return tr.Trace(ops=[ops], modules=[modules], host=host,
                    window=(0.05, 0.95))


def _grid_busy(intervals, lo, hi, step=1e-6):
    t = np.arange(lo, hi, step) + 0.5 * step
    hit = np.zeros(t.shape, bool)
    for s, e in intervals:
        hit |= (t >= s) & (t < e)
    return hit.sum() * step, t, hit


def test_busy_and_gaps_match_brute_force():
    trace = _trace()
    lo, hi = trace.window
    brute, _, _ = _grid_busy([(s, e) for s, e, _ in trace.ops[0]], lo, hi)
    busy = tr.busy_s(trace)
    assert abs(busy - brute) < 1e-6 * len(trace.ops[0])
    idle = sum(e - s for s, e in tr.idle_gaps(trace))
    assert abs(busy + idle - (hi - lo)) < 1e-12


def test_module_time_is_ops_inside_the_program():
    trace = _trace()
    ops = [(max(s, 0.1), min(e, 0.4)) for s, e, _ in trace.ops[0]
           if e > 0.1 and s < 0.4]
    brute, _, _ = _grid_busy(ops, 0.1, 0.4)
    got = tr.module_time(trace, "_scan_rounds")
    assert abs(got - brute) < 1e-6 * len(ops)
    assert tr.module_time(trace, "absent") == 0.0


def test_op_time_sums_and_gap_labels():
    trace = _trace()
    lo, hi = trace.window
    total = sum(min(e, hi) - max(s, lo) for s, e, _ in trace.ops[0]
                if e > lo and s < hi)
    assert abs(sum(tr.op_time(trace).values()) - total) < 1e-12
    assert [n for n, _ in tr.top_ops(trace, 3)] == [
        n for n, _ in sorted(tr.op_time(trace).items(),
                             key=lambda kv: -kv[1])[:3]]
    assert tr.label(trace, 0.25) == "PjitFunction(_metrics)"
    assert tr.label(trace, 0.45) == "bench.job"
    assert tr.label(trace, 0.7) == "bench.window"
    gaps = tr.longest_gaps(trace, 10)
    assert len(gaps) <= 10 and gaps == sorted(gaps, key=lambda g: -g[1])


def test_v5e_sample_reduces_to_its_recorded_numbers():
    """The trace recorded on a one-chip v5e (``testdata/``): one chip plane
    (its other ``/device:`` planes run nothing), busy time by the union
    equal to brute force, and the numbers recorded with it."""
    assert tr.self_check() == 0
