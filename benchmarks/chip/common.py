"""What every traffic driver shares: the cell, the outcome, and the probes
of the device (compilations, memory) a run reports."""
from __future__ import annotations

import dataclasses
import os
import sys
from typing import Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@dataclasses.dataclass
class Cell:
    """One run of one cell, as the command line and the files describe it."""

    name: str
    config: Dict
    traffic: Dict
    chips: int
    seed: int
    seconds: float
    trace: bool
    #: the clock reading at the top of the entry script (process start)
    t_start: float = 0.0


@dataclasses.dataclass
class Outcome:
    """What a driver measured and checked in one run."""

    metrics: Dict[str, float]            # end-to-end, without setup_s
    window_start: float                  # clock reading; set-up ends here
    attempted: int
    failed: int
    memory_peak_bytes: int
    correct: bool
    checks: Dict[str, Dict[str, float]]
    #: for --trace 1: what the per-layer metric readers read
    layer: Dict = dataclasses.field(default_factory=dict)
    busy_s: Optional[float] = None
    window_s: Optional[float] = None
    breakdown: Optional[Dict[str, List]] = None


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def stream_seeds(seed: int, stream: int, count: int) -> np.ndarray:
    """``count`` seeds in [0, 2^31) drawn from (stream, seed)."""
    from benchmarks.chip.federation import seed_entropy
    rng = np.random.default_rng(
        np.random.SeedSequence([stream, seed_entropy(seed)]))
    return rng.integers(0, 2**31, count)


class CompileCounter:
    """Counts JAX traces and backend compilations while ``active``."""

    _EVENTS = ("jaxpr_trace_duration", "backend_compile_duration")

    def __init__(self):
        import jax
        self.active = False
        self.counts = {e: 0 for e in self._EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if not self.active:
            return
        for e in self._EVENTS:
            if event.endswith(e):
                self.counts[e] += 1

    @property
    def total(self) -> int:
        return sum(self.counts.values())


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest local device (0 where unknown)."""
    import jax
    peaks = []
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)
