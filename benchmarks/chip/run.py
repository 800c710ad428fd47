"""Chip benchmark: run one cell of ``BENCHMARK.json`` once.

    python3 benchmarks/chip/run.py --workload ha.full --seed 7 \\
        --seconds 10 --trace 0

Everything is found by name:

* the cell (``workloads`` in ``BENCHMARK.json``) names a configuration and a
  traffic mix;
* the configuration is ``configs/<name>.json`` (its ``file`` entry): the
  published sizes, ``reduced``/``assumed`` and the method;
* the traffic mix is ``workloads/<traffic>.json``; its ``driver`` key names
  the module under ``drivers/`` that runs it;
* a per-layer metric ``<metric>`` is read by ``metrics/<metric>.py`` from what
  the driver found in the traced window;
* the correctness limits of a cell are ``limits/<cell>.json``.

Adding a configuration, a traffic mix or a metric adds files and an entry
in ``BENCHMARK.json``; no harness file changes.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` the
``breakdown``, and last the numbers compared for ``correct`` with their
limits (``checks``), which are also the last lines of standard error.  With
``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics.  The run fails, and prints no result,
when JAX's first device is not a TPU or there are fewer chips than the cell
asks for.  JAX's persistent compile cache is ``.jax_cache`` at the root of
the checkout.
"""
from __future__ import annotations

import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(os.path.dirname(_HERE))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from benchmarks.chip.clock import now  # noqa: E402

T_START = now()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
from typing import Dict, List, Optional, Sequence  # noqa: E402

from benchmarks.chip.common import HERE, ROOT, Cell, Outcome, log  # noqa: E402


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> Dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def make_cell(bench: Dict, name: str, seed: int, seconds: float,
              trace: bool, root: str = ROOT) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(HERE, "workloads",
                                      w["traffic"] + ".json"))
    return Cell(name=name, config=config, traffic=traffic, chips=w["chips"],
                seed=seed, seconds=seconds, trace=trace, t_start=T_START)


def check_chips(chips: int) -> Dict:
    """The device block of the result; raises NoChip off a TPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devices[0].platform!r}, "
                     "not a TPU")
    if len(devices) < chips:
        raise NoChip(f"{len(devices)} chips, the cell needs {chips}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def use_compile_cache(root: str = ROOT) -> None:
    """JAX's persistent compile cache at a fixed place in the checkout,
    caching every program however fast it compiled."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def _metric_reader(name: str):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.metrics.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def per_layer(bench: Dict, cell: str, e2e: Sequence[str],
              layer: Dict) -> Dict[str, Dict]:
    out = {}
    for m in bench["per_layer"]:
        if m["moves"] not in e2e or not _reports(m, cell):
            continue
        value = _metric_reader(m["name"])(layer)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def _finite(x: float) -> float:
    return x if math.isfinite(x) else 1e300


def result(bench: Dict, cell: Cell, out: Outcome, device: Dict) -> Dict:
    e2e = [m for m in bench["end_to_end"] if _reports(m, cell.name)]
    values = dict(out.metrics, setup_s=out.window_start - cell.t_start)
    if cell.trace:
        metrics = per_layer(bench, cell.name, [m["name"] for m in e2e],
                            out.layer)
        device = dict(device, busy_s=out.busy_s, window_s=out.window_s)
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in e2e}
    doc = {"correct": bool(out.correct), "attempted": out.attempted,
           "failed": out.failed, "metrics": metrics,
           "device": dict(device, memory_peak_bytes=out.memory_peak_bytes)}
    if cell.trace and out.breakdown is not None:
        doc["breakdown"] = out.breakdown
    doc["checks"] = {k: {"value": _finite(v["value"]), "limit": v["limit"]}
                     for k, v in out.checks.items()}
    return doc


def run_cell(cell: Cell, require_chip: bool = True,
             bench: Optional[Dict] = None) -> Dict:
    """Run one cell; the result object.  ``require_chip=False`` is for the
    tests beside this file, which drive a run on the CPU at a small size."""
    bench = bench if bench is not None else load_benchmark()
    import jax
    if require_chip:
        device = check_chips(cell.chips)
    else:
        dev = jax.devices()[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices())}
    use_compile_cache()
    driver = importlib.import_module(
        f"benchmarks.chip.drivers.{cell.traffic['driver']}")
    out = driver.run(cell)
    doc = result(bench, cell, out, device)
    for name, c in doc["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    return doc


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_benchmark()
    cell = make_cell(bench, args.workload, args.seed, args.seconds,
                     bool(args.trace))
    try:
        doc = run_cell(cell, bench=bench)
    except NoChip as e:
        log(f"no chip: {e}")
        return 3
    print(json.dumps(doc), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
