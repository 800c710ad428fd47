"""Rate sweep of a serving cell, to find the knee once, on the chip.

    python3 benchmarks/chip/sweep.py --workload xdev.serve \\
        --rates 100,200,400,800 --seconds 8 --seed 1

One process sets the server up once; each rate gets a fresh serving
session (training from the cold state again) and an open-loop window of
``--seconds``.  One JSON line per rate: requests, p50/p99 latency, the
generator's lateness over the first and the last tenth of the window (a
backlog that grows shows as the second far above the first).  The knee is
the highest rate whose backlog does not grow and whose p99 stays under the
limit ``PERF.md`` states; the cell's traffic file then fixes its rate at
0.8 of it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

import numpy as np  # noqa: E402

from benchmarks.chip import arrivals, stats  # noqa: E402
from benchmarks.chip import run as bench_run  # noqa: E402
from benchmarks.chip.drivers import serve_open_loop  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench = bench_run.load_benchmark()
    cell = bench_run.make_cell(bench, args.workload, args.seed, args.seconds,
                               False)
    bench_run.check_chips(cell.chips)
    bench_run.use_compile_cache()
    cfg, tr = cell.config, cell.traffic
    server = serve_open_loop.Server(cfg, tr, args.seed)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        arr = arrivals.Arrivals(tr, cfg["m"], cfg["d"], args.seed + k, rate,
                                args.seconds)
        sess = server.session()
        for i in range(tr["warm_requests"]):
            sess.predict(*arr.request(i))
        rec = serve_open_loop.window(server, sess, arr)
        lat, late = 1e3 * rec["latency"], 1e3 * rec["late"]
        tenth = max(len(arr) // 10, 1)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(arr),
            "window_s": rec["t_end"] - rec["t0"],
            "p50_ms": stats.percentile(lat, 50),
            "p99_ms": stats.percentile(lat, 99),
            "late_first_ms": float(np.mean(late[:tenth])),
            "late_last_ms": float(np.mean(late[-tenth:])),
            "failed": int(np.sum(~np.isfinite(lat))),
            "versions": int(rec["version"].max())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
