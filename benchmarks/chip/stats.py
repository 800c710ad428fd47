"""Percentiles: the benchmark's own arithmetic, numpy's linear
interpolation (the rule ``benchmarks/serve_bench.py`` uses)."""
from __future__ import annotations

from typing import Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation."""
    return float(np.percentile(np.asarray(values, np.float64), q))

