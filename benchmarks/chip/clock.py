"""The benchmark's one wall-clock read.

Every time the chip benchmark reports (set-up, windows, request latency,
generator lateness) comes from ``now()``.  It is the benchmark's own clock,
kept apart from the program's ``repro.utils.timing`` so that no change to
the program can move the yardstick.
"""
from __future__ import annotations

import time


def now() -> float:
    """Seconds on the host's monotonic high-resolution clock."""
    # the benchmark measures wall time by design; nothing here feeds results
    return time.perf_counter()  # reprolint: ok D101
