"""The unified experiment result: history + trace + eval tables + provenance.

Every execution path -- single run, vmapped sweep, sequential grid, cohort
block loop -- returns the SAME container, so benchmark suites and callers
never switch on which path produced a result.  The path-specific payload
(``RunResult`` / ``SweepResult`` / ``CohortRunResult``) stays reachable as
``result``, while the cross-path views -- ``history``, ``trace``,
``evaluation``, ``provenance`` -- are uniform.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

from repro.core.evaluate import EvalReport

#: keys every provenance block carries (pinned by tests/test_api_surface.py)
#: -- retries/degraded_blocks are the fault accounting (None outside the
#: cohort path, which is the only one that retries/degrades);
#: telemetry/trace_path are the observability block (flat metrics summary
#: and Chrome-trace artifact path, None unless Exec.telemetry/trace_dir)
PROVENANCE_KEYS = ("path", "driver", "engine", "fallback_reason",
                   "gram_mode", "config_hash", "backend", "retries",
                   "degraded_blocks", "telemetry", "trace_path")


@dataclasses.dataclass
class Report:
    """What an ``Experiment.run`` hands back.

    ``provenance`` records how the run actually executed: the router's
    chosen ``path`` and inner ``driver``, the resolved ``engine``, the
    ``fallback_reason`` (None when a batched path served), the SDCA
    ``gram_mode`` the problem's d selects, the spec ``config_hash``, and the
    jax ``backend``.
    """

    result: Any                            # RunResult | SweepResult | CohortRunResult
    provenance: Dict[str, Any]
    evaluation: Optional[EvalReport] = None

    @property
    def history(self) -> Optional[Dict]:
        return getattr(self.result, "history", None)

    @property
    def trace(self):
        return getattr(self.result, "trace", None)

    def final(self, key: str) -> float:
        """Last recorded value of a history column (single/cohort runs)."""
        return self.result.final(key)
