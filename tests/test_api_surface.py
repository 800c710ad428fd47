"""Public-API snapshot: changes to ``repro.api.__all__`` or to the spec /
config field lists must show up as explicit diffs of THIS file.

The golden data below is the published surface.  If a test here fails, you
changed the API: either revert, or update the snapshot in the same PR and
call the change out in CHANGES.md.
"""
import dataclasses

import repro.api as api
from repro.cohort.driver import CohortConfig
from repro.core.mocha import MochaConfig

EXPECTED_ALL = {
    "Experiment", "Problem", "Method", "Systems", "Exec", "Eval", "Serve",
    "Report", "EvalReport", "RoutePlan", "route", "run_experiment",
    "serve_experiment", "batch_incompatibility", "as_mocha_config",
    "as_cohort_config", "config_fingerprint", "base_provenance", "PATHS",
    "PROBLEM_KINDS", "PROVENANCE_KEYS", "METRICS",
}

EXPECTED_FIELDS = {
    "Problem": ("train", "population"),
    "Method": ("loss", "regularizers", "rounds", "omega_update_every",
               "gamma", "per_task_sigma", "budget", "budget_fn", "omega0"),
    "Systems": ("network", "config", "trace", "sampler", "dropout", "faults"),
    "Exec": ("engine", "driver", "mesh", "comm_dtype",
             "state0", "cohort", "inner_rounds", "clusters", "eta",
             "cache_clients", "n_pad", "overlap", "staleness",
             "max_retries", "degrade", "checkpoint_every", "checkpoint_dir",
             "resume", "telemetry", "trace_dir"),
    "Eval": ("record_every", "holdout", "holdout_clients", "metrics"),
    "Serve": ("publish_every", "prewarm"),
    "Experiment": ("problem", "method", "systems", "exec", "eval"),
    "RoutePlan": ("path", "driver", "engine", "reason"),
    "Report": ("result", "provenance", "evaluation"),
}

#: the core drivers' config views are pinned too (built from the specs by
#: as_mocha_config / as_cohort_config; CohortConfig.inner nests the
#: per-block MochaConfig)
EXPECTED_CONFIG_FIELDS = {
    MochaConfig: ("loss", "rounds", "omega_update_every", "gamma",
                  "per_task_sigma", "budget", "engine", "network", "systems",
                  "seed", "record_every", "driver"),
    CohortConfig: ("rounds", "cohort", "inner_rounds", "sampler", "dropout",
                   "clusters", "eta", "omega_update_every", "cache_clients",
                   "network", "systems", "seed", "record_every", "n_pad",
                   "overlap", "staleness", "max_retries", "degrade",
                   "faults", "checkpoint_every", "checkpoint_dir", "resume",
                   "telemetry", "trace_dir", "inner"),
}


def test_api_all_snapshot():
    assert set(api.__all__) == EXPECTED_ALL
    for name in api.__all__:
        assert hasattr(api, name), f"__all__ exports missing name {name!r}"


def test_spec_field_snapshot():
    for name, fields in EXPECTED_FIELDS.items():
        cls = getattr(api, name)
        got = tuple(f.name for f in dataclasses.fields(cls))
        assert got == fields, f"{name} fields drifted: {got}"


def test_config_view_field_snapshot():
    for cls, fields in EXPECTED_CONFIG_FIELDS.items():
        got = tuple(f.name for f in dataclasses.fields(cls))
        assert got == fields, f"{cls.__name__} fields drifted: {got}"


def test_route_paths_and_provenance_keys_snapshot():
    assert api.PATHS == ("single", "sweep", "grid", "cohort")
    assert api.PROBLEM_KINDS == ("silo", "shuffles", "population")
    assert api.PROVENANCE_KEYS == ("path", "driver", "engine",
                                   "fallback_reason", "gram_mode",
                                   "config_hash", "backend", "retries",
                                   "degraded_blocks", "telemetry",
                                   "trace_path")
    assert api.METRICS == ("error", "loss")
