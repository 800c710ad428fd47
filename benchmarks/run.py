"""Benchmark orchestrator: one module per paper table/figure + the roofline
report.  Prints ``name,us_per_call,derived`` CSV rows (plus per-benchmark
detail columns) and writes a machine-readable ``BENCH_<name>.json`` next to
the CSV stream for each suite, so the perf trajectory (e.g. the Table-1
sweep-vs-sequential wall-clock) is tracked across PRs.

Usage:  PYTHONPATH=src python -m benchmarks.run [--full] [--only fig1 ...]
        [--json-dir DIR]
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

# On an explicit CPU run (JAX_PLATFORMS=cpu), expose every core as an XLA
# host device BEFORE jax initializes: the sweep harness (core/sweep.py)
# shards independent grid cells across devices.  Any other platform keeps
# the devices JAX finds.
if (os.environ.get("JAX_PLATFORMS") == "cpu"
        and "--xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", "")):
    os.environ["XLA_FLAGS"] = (
        f"{os.environ.get('XLA_FLAGS', '')} "
        f"--xla_force_host_platform_device_count={os.cpu_count()}").strip()


def _fmt(v):
    if isinstance(v, float):
        return f"{v:.6g}"
    return str(v)


def _json_safe(obj):
    """Strict-JSON sanitizer: inf/nan floats become strings (json.dump would
    emit bare ``Infinity`` tokens that strict parsers reject)."""
    if isinstance(obj, float):
        import math
        return obj if math.isfinite(obj) else repr(obj)
    if isinstance(obj, dict):
        return {k: _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    return obj


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale protocol (slower)")
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--json-dir", default=".",
                    help="directory for the BENCH_<name>.json files")
    args = ap.parse_args()
    quick = not args.full
    json_dir = pathlib.Path(args.json_dir)
    json_dir.mkdir(parents=True, exist_ok=True)

    # persistent XLA compilation cache: repeat benchmark invocations skip the
    # sweep programs' compile entirely (the cache survives the process)
    import jax

    from repro.utils.jax_compat import use_compile_cache
    use_compile_cache(str(pathlib.Path(__file__).resolve().parent.parent))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

    from benchmarks import (cohort_scale, convergence, faults_scale,
                            fig1_stragglers, fig2_systems, fig3_faults,
                            roofline_report, sdca_micro, serve_bench,
                            table1_mtl, table4_skew)
    suites = {
        "table1": table1_mtl, "table4": table4_skew,
        "fig1": fig1_stragglers, "fig2": fig2_systems, "fig3": fig3_faults,
        "convergence": convergence,
        # sdca before roofline: it emits the results/roofline artifacts the
        # report consumes (real HLO FLOP/byte rows)
        "sdca": sdca_micro, "roofline": roofline_report,
        "cohort": cohort_scale, "faults": faults_scale,
        "serve": serve_bench,
    }
    if args.only:
        suites = {k: v for k, v in suites.items() if k in args.only}

    from repro.utils.timing import tick

    all_rows = []
    failed = []
    print("name,us_per_call,derived")
    for name, mod in suites.items():
        t0 = tick()
        try:
            rows = mod.run(quick=quick)
        except Exception as e:  # noqa: BLE001
            print(f"{name},0,ERROR:{type(e).__name__}:{e}")
            failed.append(name)
            continue
        wall_s = tick() - t0
        # every BENCH row carries the shared provenance schema: rows that ran
        # through the experiment router recorded their own block (routed
        # driver, config hash); everything else gets the ambient one (the
        # backend), replacing per-suite ad-hoc plumbing of individual fields
        from repro.api import base_provenance
        ambient = base_provenance()
        for row in rows:
            row.setdefault("provenance", dict(ambient))
        out_path = json_dir / f"BENCH_{name}.json"
        with out_path.open("w") as fh:
            json.dump(_json_safe({"bench": name, "quick": quick,
                                  "wall_s": wall_s, "rows": rows}),
                      fh, indent=2, default=str)
        for row in rows:
            us = row.get("us_per_call", 0.0)
            derived = {k: v for k, v in row.items()
                       if k not in ("bench", "us_per_call")}
            print(f"{row.get('bench', name)},{_fmt(us)},"
                  f"\"{json.dumps(derived, default=str)}\"")
        all_rows.extend(rows)

    # hard claims the paper makes -- fail loudly if the reproduction breaks
    claims = [r for r in all_rows if "mtl_beats_local" in r]
    bad = [r for r in claims if not (r["mtl_beats_local"]
                                     and r["mtl_beats_global"])]
    if claims and len(bad) > len(claims) // 2:
        print(f"CLAIM-CHECK: MTL failed to win on {len(bad)}/{len(claims)} "
              "datasets", file=sys.stderr)
    if failed:
        print(f"FAILED suites: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
