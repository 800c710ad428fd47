"""The comparison that decides ``correct``, and the limits it is held to.

Each cell has a limits file, ``limits/<cell>.json``: for every number
compared, its limit and the two readings it was set between (the largest
that sound runs of the program gave, and the smallest that the
lower-precision control or a planted fault gave).  A run is correct when
every number is at or under its limit.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Tuple

import numpy as np

_LIMITS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "limits")


def load_limits(cell: str) -> Dict[str, Dict]:
    with open(os.path.join(_LIMITS, f"{cell}.json")) as f:
        return json.load(f)


def job_readings(prog: Dict, ref: Dict) -> Dict[str, float]:
    """Numbers of one cross-silo job against the reference's run of it.

    * ``w_rel``: largest deviation of the final W, over the largest |W| of
      the reference;
    * ``w_med``: the median over tasks of ||W_t - W_ref,t|| / ||W_ref,t||,
      W_t the task's row of W; a run takes its median over the checked
      jobs (``worst``);
    * ``gap_rel``: largest relative deviation of the duality gap over the
      record rounds;
    * ``err_diff``: deviation of the mean held-out error.
    """
    inf = float("inf")
    W, Wr = np.asarray(prog["W"], np.float64), np.asarray(ref["W"],
                                                          np.float64)
    g, gr = np.asarray(prog["gap"], np.float64), np.asarray(ref["gap"],
                                                           np.float64)
    if W.shape != Wr.shape or g.shape != gr.shape:
        return {"w_rel": inf, "w_med": inf, "gap_rel": inf, "err_diff": inf}
    task = (np.linalg.norm(W - Wr, axis=1)
            / np.maximum(np.linalg.norm(Wr, axis=1), 1e-30))
    out = {"w_rel": float(np.abs(W - Wr).max()
                          / max(np.abs(Wr).max(), 1e-30)),
           "w_med": float(np.median(task)),
           "gap_rel": float(np.max(np.abs(g - gr)
                                   / np.maximum(np.abs(gr), 1e-30)))}
    if not (np.isfinite(W).all() and np.isfinite(g).all()):
        out = {k: inf for k in out}
    out["err_diff"] = abs(float(prog["error"]) - float(ref["error"]))
    return out


#: Numbers that a run takes as the median over its checked jobs.  Rounding
#: tips a job now and then (about one in fifty on a v5e) into a deviation
#: of W several times the usual, in every task alike; the largest over a
#: run's jobs then reads near the lower-precision control, whose every job
#: deviates more.  The median of the jobs keeps them apart.
MEDIAN_OVER_JOBS = ("w_med",)


def worst(readings: List[Dict[str, float]]) -> Dict[str, float]:
    """Each number over a run's checked answers: the largest, or the
    median for ``MEDIAN_OVER_JOBS``."""
    return {k: float(np.median([r[k] for r in readings]))
            if k in MEDIAN_OVER_JOBS else max(r[k] for r in readings)
            for k in readings[0]}


def judge(readings: Dict[str, float], limits: Dict[str, Dict]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """(correct, {name: {"value", "limit"}}) over the limited numbers."""
    checks = {}
    for name, lim in limits.items():
        value = readings.get(name, float("inf"))
        checks[name] = {"value": value, "limit": lim["limit"]}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


def cohort_readings(prog: Dict, ref: Dict) -> Dict[str, float]:
    """Numbers of one cohort job against the reference's run of it.

    * ``centroid_rel``: largest deviation of the final centroids, over the
      largest |centroid| of the reference;
    * ``delta_rel``: the same for every cached client's offset from its
      centroid (each participating client's solved weights); clients
      cached by one side only make it infinite;
    * ``assign_diff``: clients whose cluster differs;
    * ``gap_rel``: largest relative deviation of a block's duality gap.
    """
    inf = float("inf")
    C, Cr = (np.asarray(x, np.float64) for x in (prog["centroids"],
                                                 ref["centroids"]))
    g, gr = (np.asarray(x, np.float64) for x in (prog["gap"], ref["gap"]))
    same_ids = (prog["cache_ids"].shape == ref["cache_ids"].shape
                and bool(np.all(prog["cache_ids"] == ref["cache_ids"])))
    out = {"centroid_rel": float(np.abs(C - Cr).max()
                                 / max(np.abs(Cr).max(), 1e-30)),
           "delta_rel": inf, "assign_diff": float(
               np.sum(np.asarray(prog["assign"]) != np.asarray(
                   ref["assign"]))),
           "gap_rel": inf}
    if same_ids and prog["cache_delta"].size:
        D = np.asarray(prog["cache_delta"], np.float64)
        Dr = np.asarray(ref["cache_delta"], np.float64)
        out["delta_rel"] = float(np.abs(D - Dr).max()
                                 / max(np.abs(Dr).max(), 1e-30))
    if g.shape == gr.shape:
        out["gap_rel"] = float(np.max(np.abs(g - gr)
                                      / np.maximum(np.abs(gr), 1e-30)))
    return {k: (v if np.isfinite(v) else inf) for k, v in out.items()}


def margin_readings(got: np.ndarray, want: np.ndarray) -> Dict[str, float]:
    """``margin_rel``: largest deviation of a served margin, over the
    larger of 1 and the largest |margin| of the reference."""
    if got.shape != want.shape or not np.isfinite(got).all():
        return {"margin_rel": float("inf")}
    return {"margin_rel": float(np.abs(got - want).max()
                                / max(1.0, np.abs(want).max()))}
