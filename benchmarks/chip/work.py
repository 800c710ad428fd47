"""The work a MOCHA round requires, counted from shapes and budgets.

The counts are of what the algorithm needs, not of what an implementation
executes: a lockstep loop that runs masked trips, recomputes a product or
pads a shape does more, and that excess is what a roofline share shows.
They come from the executed budget matrix (steps per task per round), so
they hold whatever implements the round.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Tuple

import numpy as np

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")
F32 = 4


def peaks(device_kind: str) -> Dict:
    """Published peaks of one chip; an unknown device is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def sdca_round_work(executed: np.ndarray, m: int, d: int) -> Tuple[float,
                                                                   float]:
    """(FLOPs, bytes) of the W-rounds whose budgets are ``executed``
    ((rounds, m) coordinate steps).

    * each executed coordinate step reads its data row (d float32) and does
      about 4d FLOPs: the inner product <x_i, w_t + q u> (2d) and the
      update u += delta x_i (2d);
    * each round forms W = K V / 2 once: 2 m^2 d FLOPs, reading K (m^2) and
      V (m d) and writing W (m d).
    """
    steps = float(np.sum(executed))
    rounds = int(executed.shape[0])
    flops = 4.0 * d * steps + rounds * 2.0 * m * m * d
    bytes_ = F32 * d * steps + rounds * F32 * (m * m + 2.0 * m * d)
    return flops, bytes_


def min_seconds(flops: float, bytes_: float, peak: Dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["flops_per_s"], bytes_ / peak["bytes_per_s"])
