"""The program's own spans in a profiler trace.

With telemetry on, every ``repro.obs`` span is also a
``jax.profiler.TraceAnnotation``, and every JAX trace or compile made under a
span is an instant ``jax.trace`` / ``jax.compile`` event whose ``span`` arg
names it.  Both land on the ``/host:CPU`` line of the thread that opened them,
on the device's clock, beside the benchmark's ``bench.*`` spans.  On a
``trace_reduce.Trace`` (and, for the instant events, its ``.xplane.pb``):

* ``span_time``    -- wall seconds of the union of one span's intervals,
                      clipped to the window (and to another span's);
* ``idle_by_span`` -- the device-idle seconds of the window, by the
                      innermost of the named spans open at each moment;
* ``load_marks`` / ``marks_by_span`` -- the ``jax.trace`` and
                      ``jax.compile`` events of the window, by span.

Print the idle seconds and the events by span of a saved trace::

    python3 benchmarks/chip/spans.py <directory holding one .xplane.pb>

Checked against brute force on the v5e sample in ``tests/test_spans.py``.
"""
from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmarks.chip import trace_reduce as tr  # noqa: E402

Span = Tuple[float, float, str]
Mark = Tuple[float, str, Dict[str, Any]]

#: the instant events ``repro.obs`` records for JAX's traces and compiles
MARKS = ("jax.trace", "jax.compile")

#: the host spans an idle second is attributed to: the benchmark's own and
#: the program's
SPANS = ("bench.window", "bench.job", "bench.between_jobs", "experiment",
         "eval", "pack", "solve", "fold", "checkpoint", "mocha.run",
         "mocha.setup", "mocha.presample", "mocha.scan_dispatch",
         "mocha.omega_step", "mocha.host_pull", "mocha.replay")


def has_span(trace: tr.Trace, name: str) -> bool:
    """Whether the driving thread opened a host span ``name`` at all."""
    return any(n == name for _, _, n in trace.host)


def span_time(trace: tr.Trace, name: str, within: Optional[str] = None
              ) -> float:
    """Wall seconds of the union of the host spans named ``name``, clipped
    to the window and, given ``within``, to the spans of that name."""
    lo, hi = trace.window
    runs = tr.union([(s, e) for s, e, n in trace.host if n == name], lo, hi)
    if within is None:
        return sum(e - s for s, e in runs)
    return tr._overlap(runs, tr.union([(s, e) for s, e, n in trace.host
                                       if n == within], lo, hi))


def _innermost_segments(spans: Sequence[Span], lo: float, hi: float
                        ) -> List[Span]:
    """[lo, hi] cut at every span boundary, each piece labelled by the
    innermost (shortest) of ``spans`` open in it, else "none"."""
    cuts = sorted({lo, hi} | {t for s, e, _ in spans for t in (s, e)
                              if lo < t < hi})
    by_start = sorted(spans)
    active: List[Span] = []
    out, i = [], 0
    for a, b in zip(cuts, cuts[1:]):
        mid = 0.5 * (a + b)
        while i < len(by_start) and by_start[i][0] <= mid:
            active.append(by_start[i])
            i += 1
        active = [sp for sp in active if sp[1] >= mid]
        best = min(active, key=lambda sp: sp[1] - sp[0], default=None)
        out.append((a, b, best[2] if best else "none"))
    return out


def idle_by_span(trace: tr.Trace, names: Iterable[str] = SPANS,
                 device: int = 0) -> Dict[str, float]:
    """Device-idle seconds of the window by the innermost host span open at
    each moment, among the spans named in ``names`` (Python frames and
    JAX's own events are not named, so they are passed over); "none" where
    no named span is open."""
    wanted = set(names)
    lo, hi = trace.window
    segs = _innermost_segments(
        [sp for sp in trace.host if sp[2] in wanted], lo, hi)
    out: Dict[str, float] = {}
    gaps, i, j = tr.idle_gaps(trace, device), 0, 0
    while i < len(gaps) and j < len(segs):
        s, e = max(gaps[i][0], segs[j][0]), min(gaps[i][1], segs[j][1])
        if e > s:
            out[segs[j][2]] = out.get(segs[j][2], 0.0) + (e - s)
        if gaps[i][1] < segs[j][1]:
            i += 1
        else:
            j += 1
    return out


def load_marks(xplane_path: str, window_span: str = "bench.window"
               ) -> List[Mark]:
    """The ``MARKS`` events of the host thread that holds ``window_span``
    (the one ``trace_reduce.load`` reads): (time, name, args), sorted."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    lines = [line for plane in data.planes if plane.name == "/host:CPU"
             for line in plane.lines
             if any(e.name == window_span for e in line.events)]
    if len(lines) != 1:
        raise RuntimeError(f"{len(lines)} host threads hold a "
                           f"{window_span!r} span; expected one")
    return sorted(((e.start_ns * 1e-9, e.name, dict(e.stats))
                   for e in lines[0].events if e.name in MARKS),
                  key=lambda m: m[0])


def marks_by_span(marks: Sequence[Mark], window: tr.Interval
                  ) -> Dict[str, Dict[str, int]]:
    """Counts of the window's events by name, then by the ``span`` arg."""
    lo, hi = window
    out: Dict[str, Dict[str, int]] = {}
    for t, name, args in marks:
        if lo <= t <= hi:
            per = out.setdefault(name, {})
            span = str(args.get("span", "none"))
            per[span] = per.get(span, 0) + 1
    return out


def main(argv: Sequence[str]) -> int:
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    path = tr.find_xplane(argv[0])
    trace = tr.load(path)
    print(json.dumps({"idle_s_by_span": idle_by_span(trace),
                      "events_by_span": marks_by_span(load_marks(path),
                                                      trace.window)},
                     indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
