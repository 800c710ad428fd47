"""Span tracing with lock-free per-worker buffers and two clock columns.

A ``Span`` records one named interval (or instant event) on one pipeline
worker, with BOTH clock domains side by side:

  * real wall time, read exclusively through ``repro.utils.timing.tick``
    (the sanctioned wall-clock module -- reprolint D101/D106 keep it that
    way), because telemetry measures the *implementation*;
  * the simulated ``SystemsTrace`` clock, sampled through an injected
    ``sim_clock`` callable, because the interesting question is always
    "where did the wall time go RELATIVE to the simulated federated time".

The tracer is deterministic-safe by construction: it only ever READS state
-- ``sim_clock`` must be a pure read (``trace.elapsed_s``), never a draw or
a charge -- so tracing on/off cannot perturb results (pinned by
tests/test_obs.py bit-identity tests).

Lock-free buffers: spans are bucketed per worker name, and the cohort
pipeline's ownership contract (repro.cohort.driver._BlockLoop: one pack
worker, one solve worker, the main thread) guarantees each bucket is only
ever appended to by the single thread playing that role.  ``dict.setdefault``
and ``list.append`` are single-bytecode atomic under the GIL, so no lock is
needed on the hot path; ``spans()`` copies, so readers never observe a
buffer mid-mutation.

One system, two sinks: a recording tracer also opens a
``jax.profiler.TraceAnnotation`` for the exact extent of every span (scalar
args as TraceMe metadata, instant events as zero-length annotations), so
under ``jax.profiler`` each span lands on the host line of the thread that
opened it, on the same clock as the device's ops.  Outside a profiler
session an annotation costs one native call.

Retraces and compiles where they happen: the first recording tracer
registers one ``jax.monitoring`` listener for the process.  A JAX trace
(``.../jaxpr_trace_duration``) becomes an instant ``jax.trace`` event, a
backend compile (``.../backend_compile_duration``) a ``jax.compile`` event,
each on the worker of the innermost recording span open on the CALLING
thread, with ``span=<that span's name>``.  With no recording span open on
the thread the listener records nothing, so every bucket keeps its single
appending thread.

``NullTracer`` is the off-path: every operation is a constant-time no-op on
shared singletons, so an instrumented call site costs one attribute lookup
and one no-op call when telemetry is disabled.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro.utils.timing import tick

#: the known worker roles, in display order: the cohort pipeline's three
#: stages plus the serve tier's reader role; unknown worker names are
#: legal (export assigns them tracks after these)
WORKERS = ("main", "pack", "solve", "serve")

#: jax.monitoring duration events (by suffix) -> the instant event recorded
JAX_EVENTS = (("jaxpr_trace_duration", "jax.trace"),
              ("backend_compile_duration", "jax.compile"))

#: per thread: the recording spans open on it as (tracer, span), innermost
#: last -- where the jax.monitoring listener files an event
_OPEN = threading.local()
_LISTENER_LOCK = threading.Lock()
_listener_installed = False


@dataclasses.dataclass
class Span:
    """One traced interval (``dur_s`` set) or instant event (``dur_s`` None).

    ``ts_s``/``dur_s`` are wall seconds from ``utils.timing.tick`` (a
    monotonic origin, differences only); ``sim_ts_s``/``sim_dur_s`` are the
    simulated clock's seconds at entry / elapsed across the span (None when
    no ``sim_clock`` was bound).  ``args`` is a small JSON-able tag dict
    (block index, attempt, staleness, ...).
    """

    name: str
    worker: str
    ts_s: float = 0.0
    dur_s: Optional[float] = None
    sim_ts_s: Optional[float] = None
    sim_dur_s: Optional[float] = None
    args: Dict[str, Any] = dataclasses.field(default_factory=dict)


def _open_spans() -> List[Tuple["Tracer", Span]]:
    stack = getattr(_OPEN, "spans", None)
    if stack is None:
        stack = _OPEN.spans = []
    return stack


def _scalar_args(args: Dict[str, Any]) -> Dict[str, Any]:
    """The args a TraceMe can carry as metadata (``block=3``)."""
    return {k: v for k, v in args.items()
            if isinstance(v, (int, float, str))}


def _on_jax_event(event: str, duration: float, **_: Any) -> None:
    """jax.monitoring listener: file a trace/compile as an instant event on
    the innermost recording span open on this thread (else drop it)."""
    stack = getattr(_OPEN, "spans", None)
    if not stack:
        return
    for suffix, name in JAX_EVENTS:
        if event.endswith(suffix):
            tracer, span = stack[-1]
            tracer.event(name, worker=span.worker, span=span.name)
            return


def _install_listener() -> None:
    """Register the jax.monitoring listener once per process."""
    global _listener_installed
    with _LISTENER_LOCK:
        if _listener_installed:
            return
        import jax
        jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
        _listener_installed = True


class _SpanCtx:
    """Context manager for one in-flight span; ``set(**tags)`` adds args."""

    __slots__ = ("_tracer", "_span", "_note")

    def __init__(self, tracer: "Tracer", span: Span):
        self._tracer = tracer
        self._span = span
        self._note = None

    def set(self, **tags: Any) -> "_SpanCtx":
        self._span.args.update(tags)
        return self

    def __enter__(self) -> "_SpanCtx":
        sp = self._span
        sim = self._tracer._sim_clock
        if sim is not None:
            sp.sim_ts_s = float(sim())
        self._note = self._tracer._annotate(sp.name, **_scalar_args(sp.args))
        self._note.__enter__()
        _open_spans().append((self._tracer, sp))
        sp.ts_s = tick()
        return self

    def __exit__(self, *exc: Any) -> bool:
        sp = self._span
        sp.dur_s = tick() - sp.ts_s
        _open_spans().pop()
        self._note.__exit__(*exc)
        sim = self._tracer._sim_clock
        if sim is not None and sp.sim_ts_s is not None:
            sp.sim_dur_s = float(sim()) - sp.sim_ts_s
        self._tracer._append(sp)
        return False


class Tracer:
    """Recording tracer: per-worker append-only span buffers."""

    enabled = True

    def __init__(self, sim_clock: Optional[Callable[[], float]] = None):
        import jax
        self._sim_clock = sim_clock
        self._annotate = jax.profiler.TraceAnnotation
        self.origin_s = tick()
        self._buffers: Dict[str, List[Span]] = {}
        _install_listener()

    def set_sim_clock(self, fn: Callable[[], float]) -> None:
        """Bind the simulated-clock read (e.g. ``lambda: trace.elapsed_s``).

        Must be a pure READ of the simulated clock -- never a draw, never a
        charge; binding may happen after construction because the
        ``SystemsTrace`` usually exists only once the run is set up.
        """
        self._sim_clock = fn

    def span(self, name: str, worker: str = "main", **args: Any) -> _SpanCtx:
        return _SpanCtx(self, Span(name=name, worker=worker, args=dict(args)))

    def event(self, name: str, worker: str = "main", **args: Any) -> None:
        """Record an instant event (a zero-duration span)."""
        sim = self._sim_clock
        with self._annotate(name, **_scalar_args(args)):
            ts = tick()
        self._append(Span(
            name=name, worker=worker, ts_s=ts,
            sim_ts_s=float(sim()) if sim is not None else None,
            args=dict(args)))

    def _append(self, span: Span) -> None:
        # setdefault + append are GIL-atomic; each worker-name bucket has
        # exactly one appending thread (the pipeline ownership contract)
        self._buffers.setdefault(span.worker, []).append(span)

    def spans(self) -> Dict[str, List[Span]]:
        """{worker -> spans in record order}; copied, safe to iterate."""
        return {w: list(buf) for w, buf in self._buffers.items()}

    def count(self, name: str) -> int:
        """How many spans/events named ``name`` were recorded (all workers)."""
        return sum(1 for buf in self._buffers.values()
                   for sp in buf if sp.name == name)


class _NullSpanCtx:
    """Shared no-op span context (the zero-cost off path)."""

    __slots__ = ()

    def set(self, **tags: Any) -> "_NullSpanCtx":
        return self

    def __enter__(self) -> "_NullSpanCtx":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False


_NULL_SPAN = _NullSpanCtx()


class NullTracer:
    """Inert tracer: every method is a no-op returning shared singletons."""

    enabled = False

    def set_sim_clock(self, fn: Callable[[], float]) -> None:
        pass

    def span(self, name: str, worker: str = "main",
             **args: Any) -> _NullSpanCtx:
        return _NULL_SPAN

    def event(self, name: str, worker: str = "main", **args: Any) -> None:
        pass

    def spans(self) -> Dict[str, List[Span]]:
        return {}

    def count(self, name: str) -> int:
        return 0
