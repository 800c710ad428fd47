"""From a JAX profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with JAX
alone: the operations each device ran (the ``XLA Ops`` line of each
``/device:`` plane), the programs it ran (``XLA Modules``), and the host
spans of the thread that drove the window (the benchmark's own
``TraceAnnotation`` spans and JAX's dispatch events around them).  The rest
is interval arithmetic on those events:

* ``busy_s``    -- the measure of the union of the op intervals inside the
                   window, averaged over the devices;
* ``idle_gaps`` -- the stretches of the window no op covers, each labelled
                   by the innermost host span that was open at its middle;
* ``op_time`` / ``module_time`` -- summed device time per op name, or of
                   the programs whose name holds a given string.

Self-check (no chip needed), against a trace recorded on a v5e and checked
in beside this file (``testdata/``)::

    python3 benchmarks/chip/trace_reduce.py --self-check

and the interval arithmetic against brute force, in
``tests/test_trace_reduce.py``.
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import gzip
import json
import os
import re
import shutil
import sys
import tempfile
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]           # seconds, same clock as the host

_CHIP_PLANE = re.compile(r"^/device:(?:TPU|GPU):\d+$")


@dataclasses.dataclass
class Trace:
    """Events of one traced window, times in seconds."""

    #: per device: (start, end, op name)
    ops: List[List[Tuple[float, float, str]]]
    #: per device: (start, end, program name)
    modules: List[List[Tuple[float, float, str]]]
    #: host spans of the driving thread: (start, end, name)
    host: List[Tuple[float, float, str]]
    window: Interval


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def load(xplane_path: str, window_span: str = "bench.window") -> Trace:
    """Read a profiler trace; the window is the host span ``window_span``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    ops, modules, host_lines = [], [], []
    for plane in data.planes:
        # one plane per chip; a v5e trace also holds planes such as
        # "/device:CUSTOM:Megascale Trace" that run no op of the program
        if _CHIP_PLANE.match(plane.name):
            dev_ops, dev_mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    # a TPU names an op by its whole HLO instruction;
                    # keep the part before " = " ("%while.107")
                    for e in line.events:
                        dev_ops.append((e.start_ns * 1e-9, e.end_ns * 1e-9,
                                        e.name.partition(" = ")[0]))
                elif line.name == "XLA Modules":
                    for e in line.events:
                        dev_mods.append((e.start_ns * 1e-9, e.end_ns * 1e-9,
                                         e.name))
            ops.append(sorted(dev_ops))
            modules.append(sorted(dev_mods))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                events = [(e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                          for e in line.events]
                if any(name == window_span for _, _, name in events):
                    host_lines.append(events)
    if len(host_lines) != 1:
        raise RuntimeError(f"{len(host_lines)} host threads hold a "
                           f"{window_span!r} span; expected one")
    host = sorted(host_lines[0])
    spans = [(s, e) for s, e, name in host if name == window_span]
    window = (min(s for s, _ in spans), max(e for _, e in spans))
    return Trace(ops=ops, modules=modules, host=host, window=window)


def union(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Disjoint sorted union of the intervals, clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(trace: Trace) -> float:
    """Seconds in which some op ran, averaged over the devices."""
    lo, hi = trace.window
    per_dev = [sum(e - s for s, e in union([(s, e) for s, e, *_ in dev],
                                           lo, hi))
               for dev in trace.ops]
    return sum(per_dev) / max(len(per_dev), 1)


def idle_gaps(trace: Trace, device: int = 0) -> List[Interval]:
    """The window's stretches in which the device ran no op."""
    lo, hi = trace.window
    if device >= len(trace.ops):
        return []
    busy = union([(s, e) for s, e, *_ in trace.ops[device]], lo, hi)
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = e
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def label(trace: Trace, t: float) -> str:
    """Name of the innermost host span open at time t."""
    best = None
    for s, e, name in trace.host:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "none"


def longest_gaps(trace: Trace, k: int = 10) -> List[List]:
    """The k longest idle gaps of device 0 as [host label, seconds]."""
    gaps = sorted(idle_gaps(trace), key=lambda g: g[0] - g[1])[:k]
    return [[label(trace, 0.5 * (s + e)), e - s] for s, e in gaps]


def op_time(trace: Trace, device: int = 0) -> Dict[str, float]:
    """Device seconds per op name inside the window."""
    lo, hi = trace.window
    out: Dict[str, float] = {}
    for s, e, name in (trace.ops[device] if trace.ops else []):
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out[name] = out.get(name, 0.0) + (e - s)
    return out


def top_ops(trace: Trace, k: int = 10) -> List[List]:
    ranked = sorted(op_time(trace).items(), key=lambda kv: -kv[1])
    return [[name, sec] for name, sec in ranked[:k]]


def _overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Measure of the intersection of two disjoint sorted interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(e - s, 0.0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def module_time(trace: Trace, needle: str, device: int = 0) -> float:
    """Device seconds in which an op ran inside a run of a program whose
    name holds ``needle``."""
    lo, hi = trace.window
    if device >= len(trace.ops):
        return 0.0
    runs = union([(s, e) for s, e, name in trace.modules[device]
                  if needle in name], lo, hi)
    busy = union([(s, e) for s, e, *_ in trace.ops[device]], lo, hi)
    return _overlap(busy, runs)


class Profile:
    """``with Profile() as p:`` traces the block; ``p.trace`` afterwards.

    The raw trace goes to a temporary directory under ``TMPDIR`` and is
    deleted once read."""

    def __init__(self):
        self.trace: Optional[Trace] = None
        self._dir = None

    def __enter__(self):
        import jax
        self._dir = tempfile.mkdtemp(prefix="chip-bench-trace-")
        jax.profiler.start_trace(self._dir)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        try:
            if exc[0] is None:
                self.trace = load(find_xplane(self._dir))
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return False


# -- self-check ---------------------------------------------------------------

_HERE = os.path.dirname(os.path.abspath(__file__))
SAMPLE = os.path.join(_HERE, "testdata", "v5e_sample.xplane.pb.gz")
SAMPLE_EXPECT = os.path.join(_HERE, "testdata", "v5e_sample.expect.json")


def _brute_busy(intervals: Sequence[Interval], lo: float, hi: float,
                step: float) -> float:
    """Busy time by sampling the window on a fine grid (an independent
    check of ``union``; exact to about one step per interval end)."""
    import numpy as np
    t = np.arange(lo, hi, step) + 0.5 * step
    hit = np.zeros(t.shape, bool)
    for s, e in intervals:
        hit |= (t >= s) & (t < e)
    return float(hit.sum() * step)


def self_check(path: str = SAMPLE, expect_path: str = SAMPLE_EXPECT) -> int:
    if not os.path.exists(path):
        print(f"no v5e sample trace at {path} (record one on the chip); "
              "the interval arithmetic is checked by "
              "benchmarks/chip/tests/test_trace_reduce.py")
        return 2
    with tempfile.TemporaryDirectory() as tmp:
        raw = os.path.join(tmp, "sample.xplane.pb")
        with gzip.open(path, "rb") as src, open(raw, "wb") as dst:
            shutil.copyfileobj(src, dst)
        trace = load(raw)
    with open(expect_path) as f:
        expect = json.load(f)
    lo, hi = trace.window
    busy = busy_s(trace)
    brute = _brute_busy([(s, e) for s, e, *_ in trace.ops[0]], lo, hi,
                        step=1e-7)
    gaps = idle_gaps(trace)
    idle = sum(e - s for s, e in gaps)
    checks = {
        "devices": (len(trace.ops), expect["devices"]),
        "ops": (len(trace.ops[0]), expect["ops"]),
        "busy_vs_brute": (abs(busy - brute) <= 1e-7 * (1 + len(trace.ops[0])),
                          True),
        "busy_plus_idle": (abs(busy + idle - (hi - lo)) <= 1e-9, True),
        "busy_s": (round(busy, 9), round(expect["busy_s"], 9)),
        "window_s": (round(hi - lo, 9), round(expect["window_s"], 9)),
        "module_s": (round(module_time(trace, expect["module"]), 9),
                     round(expect["module_s"], 9)),
        "longest_gap_label": (longest_gaps(trace, 1)[0][0],
                              expect["longest_gap_label"]),
    }
    bad = {k: v for k, v in checks.items() if v[0] != v[1]}
    for k, (got, want) in checks.items():
        print(f"{k}: got {got} want {want}")
    print("self-check", "FAILED" if bad else "ok")
    return 1 if bad else 0


def record_sample(path: str = SAMPLE, expect_path: str = SAMPLE_EXPECT
                  ) -> int:
    """On the chip: trace four annotated calls of one jitted program with
    host pauses between them, and keep the trace and its reduction as the
    self-check's sample."""
    import time

    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda a: jnp.tanh(a @ a).sum())
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.window"):
            for i in range(4):
                with jax.profiler.TraceAnnotation("bench.job"):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.sleep"):
                    time.sleep(0.002 * (i + 1))
        jax.profiler.stop_trace()
        raw = find_xplane(tmp)
        trace = load(raw)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(raw, "rb") as src, gzip.open(path, "wb") as dst:
            shutil.copyfileobj(src, dst)
    expect = {"devices": len(trace.ops), "ops": len(trace.ops[0]),
              "busy_s": busy_s(trace),
              "window_s": trace.window[1] - trace.window[0],
              "module": "jit", "module_s": module_time(trace, "jit"),
              "longest_gap_label": longest_gaps(trace, 1)[0][0]}
    with open(expect_path, "w") as f_out:
        json.dump(expect, f_out, indent=1)
    print(json.dumps(expect))
    return 0


def main(argv: Sequence[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--self-check", action="store_true",
                    help="reduce the checked-in v5e sample trace")
    ap.add_argument("--record-sample", action="store_true",
                    help="on a chip: record the self-check's sample trace")
    args = ap.parse_args(argv)
    if args.record_sample:
        return record_sample()
    if args.self_check:
        return self_check()
    ap.print_help()
    return 2


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
